"""Per-strategy fragment-ANI throughput + packing-waste breakdown.

Early ladder rungs put the exact-ANI refinement at ~half the
end-to-end wall with one XLA searchsorted dispatch per genome pair.
This stage prices every
membership strategy (ops/fragment_ani._resolve_fragment_strategy) on
the SAME synthetic pair list and decomposes the Pallas path's cost:

  * pallas: wall-clock through _directed_ani_batch_pallas — includes
    host planning, plane uploads and the bincount fold, so it is the
    rate a production run would see; the launch/job/span counters
    quantify pow2 padding waste;
  * xla: the per-bucket vmapped-searchsorted path, same wall-clock
    protocol;
  * c merge: the compiled-C host path (skipped without the toolchain);
  * kernel amortized: the bare _window_hits launch on resident
    planes via bench_amortized's slope method — per-launch dispatch
    cost and on-chip element rate with host work excluded, so
    (wall - kernel) isolates the host-side term.

Self-budgeting like bench_pairlist_variants: variants run in priority
order under a budget (default 300 s; GALAH_BENCH_STAGE_CAP caps it
harder) and a partial run still prints FRAGMENT_JSON with what it
measured and what it skipped.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_amortized import _measure_amortized  # noqa: E402

_T0 = time.monotonic()

# Launch-related counters copied into each pallas row (deltas across
# the timed call), mirroring the pairlist stage's waste counters.
_COUNTERS = ("fragment-pallas-launches", "fragment-pallas-jobs",
             "fragment-pallas-job-slots", "fragment-pallas-ref-blocks",
             "fragment-pallas-ref-blocks-needed",
             "fragment-pallas-ref-rows", "fragment-pallas-uploads")


def _mutate(codes, rate, seed):
    r = np.random.default_rng(seed)
    out = codes.copy()
    mut = r.random(out.shape[0]) < rate
    out[mut] = r.integers(0, 4, size=int(mut.sum())).astype(np.uint8)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="CPU smoke mode: tiny shapes, interpret=True")
    ap.add_argument("--budget", type=float, default=None,
                    help="seconds for the whole stage (default 300, "
                         "capped by GALAH_BENCH_STAGE_CAP)")
    args = ap.parse_args()

    budget = args.budget if args.budget is not None else 300.0
    cap = os.environ.get("GALAH_BENCH_STAGE_CAP")
    if cap:
        budget = min(budget, float(cap))

    import jax

    interpret = args.interpret
    if interpret:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from galah_tpu.io.fasta import Genome, GenomeStats
    from galah_tpu.ops import fragment_ani as fa
    from galah_tpu.ops import pallas_fragment as pf
    from galah_tpu.utils import timing

    if not interpret:
        assert jax.default_backend() == "tpu", jax.default_backend()

    # Interpret mode is a wiring smoke, not a measurement: small
    # genomes, heavy FracMinHash subsampling, few pairs.
    size = 80_000 if interpret else 3_000_000
    sub_c = 4 if interpret else 125
    n_var = 4 if interpret else 8
    n_pairs = 24 if interpret else 512
    rng = np.random.default_rng(3)
    results = {}
    skipped = []

    def left():
        return budget - (time.monotonic() - _T0)

    def admit(cost_s, label):
        if left() >= cost_s:
            return True
        skipped.append(label)
        print(f"SKIP {label}: needs ~{cost_s:.0f}s, "
              f"{left():.0f}s left", flush=True)
        return False

    base = rng.integers(0, 4, size=size).astype(np.uint8)
    offs = np.array([0, size], dtype=np.int64)
    profiles = []
    for i in range(n_var):
        codes = base if i == 0 else _mutate(base, 0.01 * i, 50 + i)
        g = Genome(path=f"bench{i}.fna", codes=codes,
                   contig_offsets=offs.copy(),
                   stats=GenomeStats(1, 0, size))
        profiles.append(fa.build_profile(g, 15, 3000,
                                         subsample_c=sub_c))
    directed = [(profiles[i], profiles[j])
                for i in range(n_var) for j in range(n_var) if i != j]
    pairs = [directed[i % len(directed)] for i in range(n_pairs)]
    # warm the per-profile caches outside any timed region
    for p in profiles:
        p.sorted_query()
        p.padded_windows()

    def wall(fn, label, cost_s, extra=None):
        if not admit(cost_s, label):
            return
        try:
            fn()                       # warmup: compiles + caches
            before = timing.GLOBAL.counters()
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            after = timing.GLOBAL.counters()
            rate = len(pairs) / dt if dt > 0 else 0.0
            row = {"rate_per_s": round(rate, 1),
                   "wall_ms": round(dt * 1e3, 3),
                   "us_per_pair": round(dt * 1e6 / len(pairs), 3),
                   "n_pairs": len(pairs)}
            for c in _COUNTERS:
                d = after.get(c, 0) - before.get(c, 0)
                if d:
                    row[c] = d
            launches = row.get("fragment-pallas-launches")
            if launches:
                row["pairs_per_launch"] = round(
                    len(pairs) / launches, 2)
                slots = row.get("fragment-pallas-job-slots", 0)
                jobs = row.get("fragment-pallas-jobs", 0)
                if slots:
                    row["job_occupancy"] = round(jobs / slots, 4)
                scanned = row.get("fragment-pallas-ref-blocks", 0)
                needed = row.get("fragment-pallas-ref-blocks-needed", 0)
                if scanned:
                    row["span_occupancy"] = round(needed / scanned, 4)
            if extra:
                row.update(extra)
            print(f"{label}: {rate:,.0f} pairs/s wall "
                  f"({row['us_per_pair']} us/pair)", flush=True)
            results[label] = row
        except Exception as e:  # noqa: BLE001 - record, keep going
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
            results[label] = {"error": f"{type(e).__name__}: {e}"}

    # --- pallas: one launch per directed pair on resident planes ---
    wall(lambda: fa._directed_ani_batch_pallas(pairs, 0.80, 0.5),
         "pallas", 60)

    # --- xla vmapped searchsorted, same protocol ---
    wall(lambda: fa._directed_ani_batch_xla(pairs, 0.80, 0.5),
         "xla vmapped", 60 if interpret else 90)

    # --- compiled-C merge (host path) ---
    if fa._c_merge_available():
        wall(lambda: fa._directed_ani_batch_cmerge(
            pairs, 0.80, 0.5, threads=1), "c merge", 30)
    else:
        skipped.append("c merge (no toolchain)")

    # --- bare kernel, amortized slope: dispatch cost + on-chip rate
    # on pre-packed planes (host packing excluded) ---
    label = "kernel amortized"
    if admit(60 if interpret else 45, label):
        try:
            jobs, span = (8, 2)
            qb = pf.A_SUB * pf.QLA
            rb = pf.RSB * pf.B_LANE
            q = np.sort(rng.integers(
                0, 1 << 63, size=jobs * qb, dtype=np.uint64))
            r = np.sort(rng.integers(
                0, 1 << 63, size=jobs * span * rb, dtype=np.uint64))
            q_hi, q_lo = pf._query_planes(q)
            r_hi, r_lo = pf._ref_planes(r)
            # job j scans the rows of blocks j*span .. j*span+span-1
            first_row = jnp.asarray(
                np.arange(jobs, dtype=np.int32) * span * pf.RSB)
            last_row = first_row + (span * pf.RSB - 1)

            def make_fn(reps):
                @jax.jit
                def run():
                    def body(_, acc):
                        a, b, c, d = jax.lax.optimization_barrier(
                            (q_hi, q_lo, r_hi, r_lo))
                        h = pf._window_hits(
                            first_row, last_row, a, b, c, d, span=span,
                            interpret=interpret)
                        return acc + jnp.sum(h, dtype=jnp.int32)
                    return jax.lax.fori_loop(
                        0, reps, body, jnp.int32(0), unroll=False)
                return lambda: int(np.asarray(run()))

            lo_hi = (1, 3) if interpret else (1, 6)
            per, disp, sus, ok = _measure_amortized(make_fn, *lo_hi)
            elems = jobs * qb
            results[label] = {
                "per_iter_ms": round(per * 1e3, 4),
                "dispatch_ms": round(disp * 1e3, 4),
                "elems_per_iter": elems,
                "elem_rate_per_s": round(elems / per, 1) if per else 0,
                "jobs": jobs, "span": span,
                "suspect": sus, "drift_ok": ok,
            }
            print(f"{label}: {per*1e3:.3f} ms/launch, "
                  f"dispatch {disp*1e3:.3f} ms", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
            results[label] = {"error": f"{type(e).__name__}: {e}"}

    # --- breakdown: host vs device split at the auto pack ---
    auto = results.get("pallas", {})
    kern = results.get("kernel amortized", {})
    breakdown = {}
    if auto.get("us_per_pair") is not None:
        breakdown["pallas_wall_us_per_pair"] = auto["us_per_pair"]
    if auto.get("fragment-pallas-launches") and kern.get("dispatch_ms"):
        breakdown["launch_overhead_us_per_pair"] = round(
            auto["fragment-pallas-launches"] * kern["dispatch_ms"]
            * 1e3 / len(pairs), 3)
    if auto.get("job_occupancy") is not None:
        breakdown["job_occupancy"] = auto["job_occupancy"]
    if auto.get("span_occupancy") is not None:
        breakdown["span_occupancy"] = auto["span_occupancy"]
    xla = results.get("xla vmapped", {})
    if xla.get("us_per_pair") and auto.get("us_per_pair"):
        breakdown["speedup_vs_xla"] = round(
            xla["us_per_pair"] / auto["us_per_pair"], 2)
    if breakdown:
        results["breakdown"] = breakdown
    if skipped:
        results["skipped"] = skipped

    print("FRAGMENT_JSON " + json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
