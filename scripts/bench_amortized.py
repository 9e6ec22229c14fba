"""Amortized ON-CHIP kernel throughput: the MFU measurement campaign.

Per-dispatch timings conflate kernel speed with dispatch and transfer
overhead. This script separates them: inputs are uploaded ONCE and
stay device-resident, the kernel runs `reps` times inside ONE jitted
`lax.fori_loop` dispatch (with `lax.optimization_barrier` on the
inputs each iteration so XLA cannot hoist the loop-invariant call),
and the per-iteration time comes from the slope between two rep
counts — subtracting the single dispatch+RTT constant exactly.

Reports, per kernel family (dense pair-stats tile, pairlist, murmur3
sketch core Mosaic AND XLA-emulated): amortized work/s, the implied
dispatch overhead, and achieved % of a self-derived VPU roofline
(~800k pairs/s/chip for the O(K_pad^2) pair kernels at K=1000, ~9 G
k-mer/s for the murmur core).

Hoist guard: if total time fails to grow ~linearly in reps the
optimization barrier did not hold and the row is marked SUSPECT
instead of being reported as a (bogus) super-roofline number.

Reference contract being measured against: the compiled dense pair
loop the reference runs on host (reference: src/finch.rs:53-73).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Self-derived VPU ceilings: ~6e12 u32
# ops/s per v5e chip; ~7.3M u32 ops per pair at K_pad=1024 for the
# O(K_pad^2) compare kernels; ~650 u32 ops per k-mer for murmur3.
PAIR_CEILING = 800_000.0      # pairs/s/chip, K=1000
SKETCH_CEILING = 9.0e9        # k-mers/s/chip


def _measure_amortized(make_fn, reps_lo, reps_hi, repeats=2):
    """Per-iteration seconds from the slope between two rep counts.

    make_fn(reps) -> zero-arg callable returning a scalar (host
    materialization forces completion). Returns (per_iter_s, dispatch_s,
    suspect, drift_ok)."""
    f_lo, f_hi = make_fn(reps_lo), make_fn(reps_hi)
    ref_lo, ref_hi = f_lo(), f_hi()   # compile + warm both rep counts

    def best_of(f, expect):
        best, drift = float("inf"), True
        for _ in range(repeats):
            t0 = time.perf_counter()
            got = f()
            best = min(best, time.perf_counter() - t0)
            drift &= (got == expect)
        return best, drift

    t_lo, ok_lo = best_of(f_lo, ref_lo)
    t_hi, ok_hi = best_of(f_hi, ref_hi)
    per_iter = (t_hi - t_lo) / (reps_hi - reps_lo)
    dispatch = t_lo - reps_lo * per_iter
    # linearity guard: reps_hi/reps_lo >= 4 must show real growth
    suspect = t_hi < 1.5 * t_lo or per_iter <= 0
    return per_iter, max(dispatch, 0.0), suspect, ok_lo and ok_hi


def _row(label, work_per_iter, per_iter, dispatch, suspect, drift_ok,
         ceiling, out):
    rate = work_per_iter / per_iter if per_iter > 0 else 0.0
    pct = 100.0 * rate / ceiling if ceiling else None
    flag = " SUSPECT-HOIST" if suspect else ""
    flag += "" if drift_ok else " DRIFT"
    print(f"{label}: {rate:,.0f} /s amortized "
          f"({per_iter*1e3:.2f} ms/iter, dispatch {dispatch*1e3:.0f} ms"
          + (f", {pct:.1f}% of ceiling" if pct is not None else "")
          + f"){flag}", flush=True)
    out[label] = {
        "rate_per_s": round(rate, 1),
        "per_iter_ms": round(per_iter * 1e3, 3),
        "dispatch_ms": round(dispatch * 1e3, 1),
        "pct_of_ceiling": round(pct, 2) if pct is not None else None,
        "suspect": bool(suspect),
        "drift_ok": bool(drift_ok),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="CPU smoke mode: tiny shapes, interpret=True")
    ap.add_argument("--fast", action="store_true",
                    help="bench.py stage mode: skip the range_skip "
                         "variant (fewer compiles)")
    args = ap.parse_args()

    import jax

    interpret = args.interpret
    if interpret:
        # the CPU smoke must not touch the chip
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from galah_tpu.ops.pallas_pairlist import pair_stats_pairs_pallas
    from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

    if not interpret:
        assert jax.default_backend() == "tpu", jax.default_backend()

    K = 1000
    rng = np.random.default_rng(1)
    results = {}

    def dev(x):
        return jax.device_put(jnp.asarray(x))

    # --- dense pair-stats tile kernel (and range_skip variant) ---
    n = 64 if interpret else 512
    m = rng.integers(0, 1 << 63, size=(2 * n, K), dtype=np.uint64)
    m.sort(axis=1)
    r_d, c_d = dev(m[:n]), dev(m[n:])

    def make_tile(range_skip):
        def make_fn(reps):
            @jax.jit
            def run():
                def body(_, acc):
                    rr, cc = jax.lax.optimization_barrier((r_d, c_d))
                    cm, tt = tile_stats_pallas(
                        rr, cc, K, interpret=interpret,
                        range_skip=range_skip)
                    return acc + jnp.sum(cm, dtype=jnp.int32) \
                        + jnp.sum(tt, dtype=jnp.int32)
                return jax.lax.fori_loop(
                    0, reps, body, jnp.int32(0), unroll=False)
            return lambda: int(np.asarray(run()))
        return make_fn

    lo, hi = (1, 3) if interpret else (1, 6)
    for skip in ((False,) if args.fast else (False, True)):
        label = f"dense-tile {n}x{n}" + ("+skip" if skip else "")
        per, disp, sus, ok = _measure_amortized(make_tile(skip), lo, hi)
        _row(label, n * n, per, disp, sus, ok, PAIR_CEILING, results)

    # --- pairlist kernel (the sparse production path's exact pass) ---
    b = 256 if interpret else 8192
    pool = rng.integers(0, 1 << 63, size=(1024, K), dtype=np.uint64)
    pool.sort(axis=1)
    pa = dev(pool[rng.integers(0, 1024, size=b)])
    pb = dev(pool[rng.integers(0, 1024, size=b)])

    def make_pairlist(range_skip, block_pairs=None):
        def make_fn(reps):
            @jax.jit
            def run():
                def body(_, acc):
                    aa, bb = jax.lax.optimization_barrier((pa, pb))
                    cm, tt = pair_stats_pairs_pallas(
                        aa, bb, K, interpret=interpret,
                        range_skip=range_skip,
                        block_pairs=block_pairs)
                    return acc + jnp.sum(cm, dtype=jnp.int32) \
                        + jnp.sum(tt, dtype=jnp.int32)
                return jax.lax.fori_loop(
                    0, reps, body, jnp.int32(0), unroll=False)
            return lambda: int(np.asarray(run()))
        return make_fn

    from galah_tpu.ops.pallas_pairlist import pairlist_block_pairs

    P = pairlist_block_pairs()
    # blocked production default, plus the retired one-pair grid as
    # the A/B baseline (the round-5 62.8k pairs/s configuration)
    variants = [(False, P, f"pairlist B={b} P={P}"),
                (False, 1, f"pairlist B={b} P=1")]
    if not args.fast:
        variants.append((True, 1, f"pairlist B={b} P=1+skip"))
    for skip, bp, label in variants:
        per, disp, sus, ok = _measure_amortized(
            make_pairlist(skip, block_pairs=bp),
            *((1, 3) if interpret else (1, 6)))
        _row(label, b, per, disp, sus, ok, PAIR_CEILING, results)

    # --- murmur3 sketch core: Mosaic kernel vs XLA u64 emulation ---
    from galah_tpu.ops.hashing import _murmur3_k21_1d
    from galah_tpu.ops.pallas_sketch import murmur3_k21_pallas

    nk = (1 << 16) if interpret else (1 << 21)
    kw = [dev(rng.integers(0, 1 << 64, size=nk, dtype=np.uint64))
          for _ in range(3)]

    def make_mosaic(reps):
        @jax.jit
        def run():
            def body(_, acc):
                k1, k2, t = jax.lax.optimization_barrier(tuple(kw))
                h = murmur3_k21_pallas(k1, k2, t, seed=0,
                                       interpret=interpret)
                return acc + jnp.sum(
                    h.astype(jnp.uint32).astype(jnp.int32),
                    dtype=jnp.int32)
            return jax.lax.fori_loop(
                0, reps, body, jnp.int32(0), unroll=False)
        return lambda: int(np.asarray(run()))

    def make_xla(reps):
        @jax.jit
        def run():
            def body(_, acc):
                k1, k2, t = jax.lax.optimization_barrier(tuple(kw))
                cb = [(k1 >> jnp.uint64(8 * bb)) & jnp.uint64(0xFF)
                      for bb in range(8)]
                cb += [(k2 >> jnp.uint64(8 * bb)) & jnp.uint64(0xFF)
                       for bb in range(8)]
                cb += [(t >> jnp.uint64(8 * bb)) & jnp.uint64(0xFF)
                       for bb in range(5)]
                h = _murmur3_k21_1d(cb, 0)
                return acc + jnp.sum(
                    h.astype(jnp.uint32).astype(jnp.int32),
                    dtype=jnp.int32)
            return jax.lax.fori_loop(
                0, reps, body, jnp.int32(0), unroll=False)
        return lambda: int(np.asarray(run()))

    lo, hi = (1, 3) if interpret else (2, 16)
    per, disp, sus, ok = _measure_amortized(make_mosaic, lo, hi)
    _row(f"murmur-mosaic n={nk}", nk, per, disp, sus, ok,
         SKETCH_CEILING, results)
    per, disp, sus, ok = _measure_amortized(make_xla, lo, hi)
    _row(f"murmur-xla n={nk}", nk, per, disp, sus, ok,
         SKETCH_CEILING, results)

    mos = results.get(f"murmur-mosaic n={nk}", {})
    xla = results.get(f"murmur-xla n={nk}", {})
    if mos.get("rate_per_s") and xla.get("rate_per_s"):
        ratio = mos["rate_per_s"] / xla["rate_per_s"]
        print(f"murmur verdict: Mosaic/XLA = {ratio:.2f}x on-chip "
              f"(default flips to Mosaic if >= 1.1)", flush=True)
        results["murmur_mosaic_over_xla"] = round(ratio, 3)

    print("AMORTIZED_JSON " + json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
