"""Mosaic murmur3 sketch kernel: bit-parity with the XLA hash core,
run in interpreter mode on the CPU test mesh.

Whole module is slow-tier: the kernel is QUARANTINED (measured on a
chip at 0.06x XLA; see ops/pallas_sketch.py) and reachable only via the
GALAH_TPU_PALLAS_HASH opt-in, so its parity no longer gates the
default per-commit loop."""

import numpy as np
import pytest

import jax.numpy as jnp

pytestmark = pytest.mark.slow

from galah_tpu.ops.hashing import _murmur3_k21_1d
from galah_tpu.ops.murmur3_np import murmur3_x64_128_h1 as mm3_np
from galah_tpu.ops.pallas_sketch import (
    assemble_k21_words,
    murmur3_k21_pallas,
)


def _random_byte_vectors(rng, n):
    """21 per-byte u64 vectors, the _hash_core cb[] shape."""
    raw = rng.integers(0, 256, size=(n, 21), dtype=np.uint64)
    return raw, [jnp.asarray(raw[:, j]) for j in range(21)]


@pytest.mark.parametrize("n,seed", [(1000, 0), (4097, 7)])
def test_kernel_matches_xla_hash_core(n, seed):
    rng = np.random.default_rng(31 + n)
    _, cb = _random_byte_vectors(rng, n)
    want = np.asarray(_murmur3_k21_1d(cb, seed))
    k1, k2, t = assemble_k21_words(cb)
    got = np.asarray(murmur3_k21_pallas(k1, k2, t, seed=seed,
                                        interpret=True))
    np.testing.assert_array_equal(got, want)


def test_kernel_matches_host_reference_on_ascii_kmers():
    """Against the numpy reference implementation on real ACGT k-mer
    bytes (the exact finch contract, reference: src/finch.rs:33-47)."""
    rng = np.random.default_rng(5)
    n = 512
    kmers = rng.choice(np.frombuffer(b"ACGT", dtype=np.uint8), size=(n, 21))
    want = mm3_np(kmers, seed=0)
    cb = [jnp.asarray(kmers[:, j].astype(np.uint64)) for j in range(21)]
    k1, k2, t = assemble_k21_words(cb)
    got = np.asarray(murmur3_k21_pallas(k1, k2, t, seed=0,
                                        interpret=True))
    np.testing.assert_array_equal(got, np.asarray(want, dtype=np.uint64))


def test_kernel_padding_boundaries():
    """Sizes straddling the block quantum pad and trim correctly."""
    rng = np.random.default_rng(9)
    for n in (1, 127, 128, 65536, 65537):
        _, cb = _random_byte_vectors(rng, n)
        want = np.asarray(_murmur3_k21_1d(cb, 0))
        k1, k2, t = assemble_k21_words(cb)
        got = np.asarray(murmur3_k21_pallas(k1, k2, t, interpret=True))
        assert got.shape == (n,)
        np.testing.assert_array_equal(got, want)


def test_env_optin_end_to_end_sketch_identical(monkeypatch):
    """GALAH_TPU_PALLAS_HASH=1 routes the chunk hashers through the
    Mosaic kernel (interpret mode off-TPU) with bit-identical sketches.
    The env is read at first trace, so the cache is cleared around it."""
    import jax

    from galah_tpu.io.fasta import read_genome
    from galah_tpu.ops.minhash import sketch_genome_device

    g = read_genome("/root/reference/tests/data/set1/500kb.fna")
    base = sketch_genome_device(g, sketch_size=1000, k=21, seed=0)

    monkeypatch.setenv("GALAH_TPU_PALLAS_HASH", "1")
    jax.clear_caches()
    try:
        via_kernel = sketch_genome_device(g, sketch_size=1000, k=21,
                                          seed=0)
    finally:
        monkeypatch.delenv("GALAH_TPU_PALLAS_HASH")
        jax.clear_caches()
    np.testing.assert_array_equal(via_kernel.hashes, base.hashes)


def test_tail_word_high_bytes_ignored():
    """The contract uses only the low 5 bytes of the tail word; bytes
    5-7 must not affect the hash (callers may feed full-random
    words)."""
    rng = np.random.default_rng(13)
    n = 256
    k1 = jnp.asarray(rng.integers(0, 1 << 64, size=n, dtype=np.uint64))
    k2 = jnp.asarray(rng.integers(0, 1 << 64, size=n, dtype=np.uint64))
    t_full = rng.integers(0, 1 << 64, size=n, dtype=np.uint64)
    t_masked = t_full & np.uint64(0xFFFFFFFFFF)
    a = np.asarray(murmur3_k21_pallas(k1, k2, jnp.asarray(t_full),
                                      interpret=True))
    b = np.asarray(murmur3_k21_pallas(k1, k2, jnp.asarray(t_masked),
                                      interpret=True))
    np.testing.assert_array_equal(a, b)
