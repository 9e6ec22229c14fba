"""Measure the sketching-stage variants on the live TPU.

Answers, with captured numbers:
  1. packed vs unpacked chunk upload (is the 2.7x byte cut visible?);
  2. hash-only vs hash+bottom-k fold (is the u64 sort the bottleneck?);
  3. per-genome vs grouped batch sketching on real MAGs (dispatch
     round-trip amortization).

Timings force host materialization.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _timeit(fn, repeats=3):
    fn()  # compile/warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    import jax
    import jax.numpy as jnp

    from galah_tpu.ops import hashing

    assert jax.default_backend() == "tpu", jax.default_backend()

    C = 1 << 21  # 2 Mi bases — one mid-size MAG chunk
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 4, size=C).astype(np.uint8)
    offs = jnp.asarray(np.full(1, 2**31 - 1, dtype=np.int32))
    packed, ambits = hashing.pack_codes_host(codes)

    for algo in ("murmur3", "tpufast"):
        # materialize only 4 hashes: a full-array download would be a
        # constant ~16 MiB device->host cost swamping the upload delta
        t_unpacked = _timeit(lambda: np.asarray(
            hashing.canonical_kmer_hashes_chunk(
                jnp.asarray(codes), offs, jnp.int32(0), k=21,
                algo=algo)[:4]))
        t_packed = _timeit(lambda: np.asarray(
            hashing.canonical_kmer_hashes_chunk_packed(
                jnp.asarray(packed), jnp.asarray(ambits), offs,
                jnp.int32(0), k=21, algo=algo)[:4]))
        print(f"{algo}: unpacked {C / t_unpacked / 1e6:.1f} Mpos/s, "
              f"packed {C / t_packed / 1e6:.1f} Mpos/s "
              f"(upload {C} vs {C // 4 + C // 8} B)", flush=True)

    # hash+fold vs hash-only (device-resident input isolates compute)
    dev_packed = jax.device_put(jnp.asarray(packed))
    dev_ambits = jax.device_put(jnp.asarray(ambits))

    def hash_only():
        h = hashing.canonical_kmer_hashes_chunk_packed(
            dev_packed, dev_ambits, offs, jnp.int32(0), k=21)
        return np.asarray(h[:4])

    def hash_fold():
        h = hashing.canonical_kmer_hashes_chunk_packed(
            dev_packed, dev_ambits, offs, jnp.int32(0), k=21)
        running = jnp.full((1000,), hashing.HASH_SENTINEL)
        return np.asarray(hashing.bottom_k_update(running, h, 1000)[:4])

    t_h = _timeit(hash_only)
    t_hf = _timeit(hash_fold)
    print(f"hash-only {C / t_h / 1e6:.1f} Mpos/s, hash+bottom-k fold "
          f"{C / t_hf / 1e6:.1f} Mpos/s (sort overhead "
          f"{(t_hf - t_h) / t_hf * 100:.0f}%)", flush=True)

    # Mosaic murmur state machine (ops/pallas_sketch.py) vs the XLA
    # u64-emulated hash core, device-resident key words: answers
    # whether the 16-bit-limb kernel beats XLA's generic emulation
    # on-chip.
    from galah_tpu.ops.hashing import _murmur3_k21_1d
    from galah_tpu.ops.pallas_sketch import murmur3_k21_pallas

    n = C
    kw = [jax.device_put(jnp.asarray(
        rng.integers(0, 1 << 64, size=n, dtype=np.uint64)))
        for _ in range(3)]

    @jax.jit
    def xla_hash(k1, k2, t):
        # the same state machine on the same pre-assembled words, via
        # XLA's u64 emulation (byte re-extraction feeds the shared
        # assembly in _murmur3_k21_1d; shift/and cost is negligible
        # next to the 11 u64 multiplies being measured)
        cb = [(k1 >> jnp.uint64(8 * b)) & jnp.uint64(0xFF)
              for b in range(8)]
        cb += [(k2 >> jnp.uint64(8 * b)) & jnp.uint64(0xFF)
               for b in range(8)]
        cb += [(t >> jnp.uint64(8 * b)) & jnp.uint64(0xFF)
               for b in range(5)]
        return _murmur3_k21_1d(cb, 0)

    t_xla = _timeit(lambda: np.asarray(xla_hash(*kw)[:4]))
    t_mosaic = _timeit(lambda: np.asarray(
        murmur3_k21_pallas(*kw, seed=0)[:4]))
    print(f"murmur core: XLA {n / t_xla / 1e6:.1f} Mkmer/s, Mosaic "
          f"{n / t_mosaic / 1e6:.1f} Mkmer/s "
          f"({t_xla / t_mosaic:.2f}x)", flush=True)

    # per-genome vs batch on real MAGs (shared bench corpus)
    from bench import bench_genomes
    from galah_tpu.ops.minhash import (
        sketch_genome_device,
        sketch_genomes_device_batch,
    )

    genomes, total_bp = bench_genomes()
    t_single = _timeit(
        lambda: [sketch_genome_device(g) for g in genomes], repeats=2)
    t_batch = _timeit(
        lambda: sketch_genomes_device_batch(genomes), repeats=2)
    print(f"6 real MAGs ({total_bp / 1e6:.1f} Mbp): per-genome "
          f"{total_bp / t_single / 1e6:.1f} Mbp/s, batch "
          f"{total_bp / t_batch / 1e6:.1f} Mbp/s", flush=True)


if __name__ == "__main__":
    main()
