"""Per-strategy screened-pair throughput + per-term cost breakdown.

The round-5 campaign measured the one-pair pairlist grid at 62.8k
pairs/s amortized (7.8% of the derived VPU ceiling) with NO analysis
of where the other 92% goes. This stage times every survivor-
evaluation strategy (ops/sparse_device.py) and decomposes the blocked
kernel's per-pair cost into named terms so a hardware negative is a
documented decision:

  * blocked P sweep (P = 1 is the retired round-5 grid): amortized
    on-chip pairs/s per bench_amortized's slope method;
  * xla: the vmapped u64-searchsorted fallback path;
  * gather-dense: wall-clock through ops/sparse_device's dense-tile
    strategy on a duplication-heavy (family-clique) and a low-dup
    pair list — includes host planning, so it is the rate a
    production run would see;
  * lo_only: the blocked kernel with the hi-plane compare halves
    dropped (WRONG integers, bench-only) — the same DMA traffic with
    ~1/3 of the compare work, pricing the u64-emulation tax.

Per-term model (per-pair microseconds, B pairs per dispatch):
    u(P) = c_pair + c_grid / P
  c_grid        = (u(1) - u(8)) * 8/7   -- per-program fixed cost
  u64_tax       = u_full(8) - u_lo(8)   -- extra compares for 64-bit
  dma_floor     = bytes_per_pair / HBM_BW (analytic, v5e ~8.1e11 B/s)
  u32_residual  = u(8) - c_grid/8 - u64_tax - dma_floor

Self-budgeting: variants run in priority order and each is admitted
only if its estimated cost fits the remaining budget (default 300 s;
GALAH_BENCH_STAGE_CAP caps it harder) — a partial run still prints
PAIRLIST_JSON with what it measured and what it skipped.
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

from bench_amortized import (  # noqa: E402
    PAIR_CEILING,
    _measure_amortized,
    _row,
)

HBM_BW = 8.1e11  # bytes/s, v5e spec sheet
_T0 = time.monotonic()


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

    from galah_tpu.ops.pairwise import _pair_stats
    from galah_tpu.ops.pallas_pairlist import pair_stats_pairs_pallas

    if not interpret:
        assert jax.default_backend() == "tpu", jax.default_backend()

    # Interpret mode is a wiring smoke, not a measurement: shrink both
    # the sketch width (compile cost scales with K_pad/8 static lane
    # loops) and the batch so the whole variant matrix fits the budget.
    K = 256 if interpret else 1000
    B = 64 if interpret else 8192
    rng = np.random.default_rng(1)
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

    n_pool = 256 if interpret else 1024
    pool = rng.integers(0, 1 << 63, size=(n_pool, K), dtype=np.uint64)
    pool.sort(axis=1)
    pa = jax.device_put(
        jnp.asarray(pool[rng.integers(0, n_pool, size=B)]))
    pb = jax.device_put(
        jnp.asarray(pool[rng.integers(0, n_pool, size=B)]))

    def make_blocked(block_pairs, lo_only=False):
        def make_fn(reps):
            @jax.jit
            def run():
                def body(_, acc):
                    aa, bb = jax.lax.optimization_barrier((pa, pb))
                    cm, tt = pair_stats_pairs_pallas(
                        aa, bb, K, interpret=interpret,
                        block_pairs=block_pairs, _lo_only=lo_only)
                    return acc + jnp.sum(cm, dtype=jnp.int32) \
                        + jnp.sum(tt, dtype=jnp.int32)
                return jax.lax.fori_loop(
                    0, reps, body, jnp.int32(0), unroll=False)
            return lambda: int(np.asarray(run()))
        return make_fn

    def make_xla(reps):
        @jax.jit
        def run():
            def body(_, acc):
                aa, bb = jax.lax.optimization_barrier((pa, pb))
                cm, tt = jax.vmap(
                    lambda a, b: _pair_stats(a, b, K))(aa, bb)
                return acc + jnp.sum(cm, dtype=jnp.int32) \
                    + jnp.sum(tt, dtype=jnp.int32)
            return jax.lax.fori_loop(
                0, reps, body, jnp.int32(0), unroll=False)
        return lambda: int(np.asarray(run()))

    lo_hi = (1, 3) if interpret else (1, 6)
    # Priority order: the tentpole A/B first (P=8 vs the retired P=1
    # grid gives the grid-overhead term), then the u64-tax probe, then
    # the fallback and the sweep tails, then the gather-dense regimes.
    # Cost estimates are per-variant admission guards; interpret mode
    # uses the shrunk shapes so its estimates shrink with them.
    c_blk, c_xla = (45, 20) if interpret else (60, 90)
    jobs = [(f"blocked P={p}", c_blk, make_blocked(p))
            for p in ((8, 1) if interpret else (8, 1, 4, 16))]
    jobs.insert(2, ("blocked P=8 lo_only", c_blk, make_blocked(8, True)))
    jobs.insert(3, ("xla vmapped", c_xla, make_xla))
    for label, cost, mk in jobs:
        if not admit(cost, label):
            continue
        try:
            per, disp, sus, ok = _measure_amortized(mk, *lo_hi)
            _row(label, B, per, disp, sus, ok, PAIR_CEILING, results)
        except Exception as e:  # noqa: BLE001 - record, keep going
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
            results[label] = {"error": f"{type(e).__name__}: {e}"}

    # --- gather-dense strategy, wall-clock (host plan + tiles) ---
    from galah_tpu.ops.sparse_device import (
        _gather_dense_pair_stats,
        _plan_gather_segments,
    )

    n_rows = 128 if interpret else 1024
    jmat = jax.device_put(jnp.asarray(pool[:n_rows]))

    def gather_pairs(regime):
        if regime == "high-dup":   # family cliques: m-member all-pairs
            m, nfam = 32, (2 if interpret else 24)
            pi = np.concatenate([
                np.repeat(np.arange(m, dtype=np.int32) + f * m, m)
                for f in range(nfam)])
            pj = np.concatenate([
                np.tile(np.arange(m, dtype=np.int32) + f * m, m)
                for f in range(nfam)])
            keep = pi < pj
            return pi[keep], pj[keep]
        n_p = 256 if interpret else 8192   # low-dup: scattered pairs
        pi = rng.integers(0, n_rows - 1, size=n_p).astype(np.int32)
        pj = np.minimum(pi + 1 + rng.integers(0, 64, size=n_p),
                        n_rows - 1).astype(np.int32)
        return pi, pj

    c_gather = 30 if interpret else 90
    for regime in ("high-dup", "low-dup"):
        label = f"gather-dense {regime}"
        if not admit(c_gather, label):
            continue
        try:
            pi, pj = gather_pairs(regime)
            order = np.lexsort((pj, pi))
            _, cells = _plan_gather_segments(pi[order], pj[order])
            got = _gather_dense_pair_stats(
                jmat, pi, pj, K, interpret, explicit=True)
            t0 = time.perf_counter()
            got = _gather_dense_pair_stats(
                jmat, pi, pj, K, interpret, explicit=True)
            dt = time.perf_counter() - t0
            rate = pi.shape[0] / dt if dt > 0 else 0.0
            fill = pi.shape[0] / max(cells, 1)
            print(f"{label}: {rate:,.0f} pairs/s wall "
                  f"(fill {fill:.3f}, {pi.shape[0]} pairs, "
                  f"{cells} cells)", flush=True)
            results[label] = {
                "rate_per_s": round(rate, 1),
                "fill": round(fill, 4),
                "n_pairs": int(pi.shape[0]),
                "tile_cells": int(cells),
                "pct_of_ceiling": round(100.0 * rate / PAIR_CEILING, 2),
            }
        except Exception as e:  # noqa: BLE001
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
            results[label] = {"error": f"{type(e).__name__}: {e}"}

    # --- per-term breakdown from the measured rows ---
    def u(label):
        r = results.get(label, {})
        per = r.get("per_iter_ms")
        return per * 1e3 / B if per else None   # us/pair

    u8, u1, ulo = u("blocked P=8"), u("blocked P=1"), \
        u("blocked P=8 lo_only")
    k_pad = 1024
    bytes_per_pair = 2 * (k_pad * 8) + 2 * (8 * 128 * 4)
    breakdown = {"model": "u(P) = c_pair + c_grid/P; us per pair",
                 "bytes_per_pair": bytes_per_pair,
                 "dma_floor_us": round(bytes_per_pair / HBM_BW * 1e6,
                                       4)}
    if u8 is not None and u1 is not None:
        breakdown["grid_overhead_us"] = round((u1 - u8) * 8.0 / 7.0, 3)
    if u8 is not None and ulo is not None:
        breakdown["u64_tax_us"] = round(u8 - ulo, 3)
    if all(k in breakdown for k in ("grid_overhead_us", "u64_tax_us")):
        breakdown["u32_residual_us"] = round(
            u8 - breakdown["grid_overhead_us"] / 8.0
            - breakdown["u64_tax_us"] - breakdown["dma_floor_us"], 3)
    r8 = results.get("blocked P=8", {})
    if r8.get("dispatch_ms") is not None:
        breakdown["dispatch_ms"] = r8["dispatch_ms"]
    results["breakdown"] = breakdown
    if skipped:
        results["skipped"] = skipped

    print("PAIRLIST_JSON " + json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
