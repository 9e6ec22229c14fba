"""Measure the Mosaic pair-stats kernel variants on the live TPU.

Timings force host materialization. Decides whether
range_skip should become the default inside tile_stats_pallas.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _measure(fn, repeats=3):
    """Warm/compile, then best-of-repeats with a drift check: the
    summed stats must not change between timed calls (forces host
    materialization too)."""
    ref = int(np.asarray(fn()[0]).sum())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        got = int(np.asarray(fn()[0]).sum())
        best = min(best, time.perf_counter() - t0)
        assert got == ref
    return best


def main():
    import jax
    import jax.numpy as jnp

    from galah_tpu.ops.pairwise import tile_stats
    from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

    assert jax.default_backend() == "tpu", jax.default_backend()
    rng = np.random.default_rng(1)
    K = 1000

    def mats(n):
        m = rng.integers(0, 1 << 63, size=(2 * n, K), dtype=np.uint64)
        m.sort(axis=1)
        return jnp.asarray(m[:n]), jnp.asarray(m[n:])

    def run(label, fn, n_pairs):
        # One bad variant (e.g. a worker crash on an oversized XLA
        # gather — seen 2026-07-31 on xla 512x512) must not lose the
        # rest of the capture; later variants fail fast if the client
        # died with it, and the raw log records both.
        try:
            best = _measure(fn)
            print(f"{label}: {best*1e3:.1f} ms = "
                  f"{n_pairs/best:,.0f} pairs/s", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{label}: FAILED {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Pairlist kernel first (the sparse production pipeline's exact
    # pass — the most decision-relevant number) vs the vmapped XLA
    # searchsorted on the same gathered pair batch.
    from galah_tpu.ops.pairwise import _pair_stats
    from galah_tpu.ops.pallas_pairlist import pair_stats_pairs_pallas

    m = rng.integers(0, 1 << 63, size=(1024, K), dtype=np.uint64)
    m.sort(axis=1)
    b = 8192
    pa = jnp.asarray(m[rng.integers(0, 1024, size=b)])
    pb = jnp.asarray(m[rng.integers(0, 1024, size=b)])

    @jax.jit
    def xla_pairs(a, bb):
        return jax.vmap(lambda x, y: _pair_stats(x, y, K))(a, bb)

    run(f"pairlist-mosaic B={b}",
        lambda: pair_stats_pairs_pallas(pa, pb, K), b)
    run(f"pairlist-mosaic+skip B={b}",
        lambda: pair_stats_pairs_pallas(pa, pb, K, range_skip=True), b)
    run(f"pairlist-xla B={b}", lambda: xla_pairs(pa, pb), b)

    for n in (128, 512):
        r, c = mats(n)
        run(f"pallas {n}x{n}", lambda: tile_stats_pallas(r, c, K),
            n * n)
        run(f"pallas+skip {n}x{n}",
            lambda: tile_stats_pallas(r, c, K, range_skip=True), n * n)
        if n <= 128:  # xla 512x512 crashed the TPU worker (see above)
            run(f"xla {n}x{n}", lambda: tile_stats(r, c, K, 21), n * n)


if __name__ == "__main__":
    main()
