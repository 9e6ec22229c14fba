"""JAX kernels: canonical k-mer extraction + MurmurHash3 x64_128 (h1).

Device-side twin of ops/murmur3_np.py / ops/minhash_np.py, verified
bit-exact against them in tests/test_minhash.py. All shapes are static; a
genome is processed as fixed-size chunks so XLA compiles once per chunk
size. uint64 arithmetic wraps (XLA emulates 64-bit integers with u32
pairs on TPU). The explicit u32-pair Mosaic implementation of the
murmur state machine exists in ops/pallas_sketch.py (16-bit-limb
constant multiplies, bit-identical): opt in with GALAH_TPU_PALLAS_HASH=1
(read at first trace; k=21 murmur3 only), benched against this XLA
path by scripts/bench_sketch_variants.py on hardware.

Hash semantics mirror the reference's finch backend contract
(reference: src/finch.rs:33-47): canonical (lexicographic min of forward /
reverse-complement) k-mer ASCII bytes, murmur3 x64_128 seed 0, low u64.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

# Ensure 64-bit integer support; all dtypes in this package are explicit so
# enabling x64 does not change any float widths we use.
jax.config.update("jax_enable_x64", True)

from galah_tpu.ops.constants import SENTINEL

_C1 = jnp.uint64(0x87C37B91114253D5)
_C2 = jnp.uint64(0x4CF5AD432745937F)

# Mosaic murmur3 state-machine default on TPU backends when
# GALAH_TPU_PALLAS_HASH is unset: an early on-chip campaign
# (scripts/bench_amortized.py) measured Mosaic at 0.06x the XLA
# emulation at n=2Mi hashes (650 M/s vs 10.9 G/s amortized), so the
# default stays False. Re-run the campaign before revisiting.
_PALLAS_HASH_TPU_DEFAULT = False


def _use_pallas_hash() -> bool:
    """GALAH_TPU_PALLAS_HASH: '1' forces the Mosaic hash kernel, '0'
    forces the XLA emulation; unset defers to the data-driven TPU
    default above (never on for CPU backends — interpret mode is for
    tests that pin it explicitly)."""
    env = os.environ.get("GALAH_TPU_PALLAS_HASH")
    if env == "1":
        return True
    if env == "0":
        return False
    return _PALLAS_HASH_TPU_DEFAULT and jax.default_backend() == "tpu"

HASH_SENTINEL = jnp.uint64(SENTINEL)  # "no k-mer here"

# Chunking policy shared by every consumer of iter_chunk_hashes /
# iter_genome_groups (MinHash, HLL, fragment profiles): 8 Mi positions
# per single-genome chunk — one dispatch covers most MAGs — and at most
# 32 Mi total positions per batched group dispatch (u64 hash rows + sort
# workspace stay well under HBM).
DEFAULT_CHUNK = 1 << 23
BATCH_BUDGET = 1 << 25

_ASCII = jnp.array([65, 67, 71, 84], dtype=jnp.uint8)  # ACGT


def device_transfer_bound() -> bool:
    """True when host->device transfer + dispatch round trips dominate
    small ops — i.e. on a real TPU backend. Gates the
    packed-upload and batched-grouping policies: on the CPU backend both
    are pure overhead (data is already in host memory, and the big
    batched arrays lose cache locality — measured 3x slower profile
    builds). Override with GALAH_PACKED_TRANSFER=0/1 for testing."""
    env = os.environ.get("GALAH_PACKED_TRANSFER")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "off", "")
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backend probing never raises
        return False


def _rotl64(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint64(r)) | (x >> jnp.uint64(64 - r))


def _fmix64(x: jax.Array) -> jax.Array:
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(0xFF51AFD7ED558CCD)
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(0xC4CEB9FE1A85EC53)
    x = x ^ (x >> jnp.uint64(33))
    return x


def murmur3_x64_128_h1(keys: jax.Array, seed: int = 0) -> jax.Array:
    """h1 of murmur3 x64_128 over uint8 rows, shape (n, L) -> (n,) uint64.

    L is a static (trace-time) constant; the byte loops unroll at trace
    time into pure vector ops over the n axis.
    """
    n, length = keys.shape
    h1 = jnp.full((n,), jnp.uint64(seed))
    h2 = jnp.full((n,), jnp.uint64(seed))

    nblocks = length // 16
    for blk in range(nblocks):
        base = blk * 16
        k1 = jnp.zeros((n,), jnp.uint64)
        k2 = jnp.zeros((n,), jnp.uint64)
        for b in range(8):
            k1 = k1 | (keys[:, base + b].astype(jnp.uint64)
                       << jnp.uint64(8 * b))
            k2 = k2 | (keys[:, base + 8 + b].astype(jnp.uint64)
                       << jnp.uint64(8 * b))
        k1 = _rotl64(k1 * _C1, 31) * _C2
        h1 = h1 ^ k1
        h1 = _rotl64(h1, 27) + h2
        h1 = h1 * jnp.uint64(5) + jnp.uint64(0x52DCE729)
        k2 = _rotl64(k2 * _C2, 33) * _C1
        h2 = h2 ^ k2
        h2 = _rotl64(h2, 31) + h1
        h2 = h2 * jnp.uint64(5) + jnp.uint64(0x38495AB5)

    rem = length & 15
    base = nblocks * 16
    if rem > 8:
        k2 = jnp.zeros((n,), jnp.uint64)
        for b in range(8, rem):
            k2 = k2 | (keys[:, base + b].astype(jnp.uint64)
                       << jnp.uint64(8 * (b - 8)))
        k2 = _rotl64(k2 * _C2, 33) * _C1
        h2 = h2 ^ k2
    if rem > 0:
        k1 = jnp.zeros((n,), jnp.uint64)
        for b in range(min(rem, 8)):
            k1 = k1 | (keys[:, base + b].astype(jnp.uint64)
                       << jnp.uint64(8 * b))
        k1 = _rotl64(k1 * _C1, 31) * _C2
        h1 = h1 ^ k1

    h1 = h1 ^ jnp.uint64(length)
    h2 = h2 ^ jnp.uint64(length)
    h1 = h1 + h2
    h2 = h2 + h1
    h1 = _fmix64(h1)
    h2 = _fmix64(h2)
    h1 = h1 + h2
    return h1


def _ascii64(c: jax.Array) -> jax.Array:
    """2-bit code (uint8 vector) -> ACGT ASCII byte as uint64.

    A select chain instead of a table gather: gathers are the scarce
    resource on the VPU, selects are plain vector ops.
    """
    b = jnp.where(
        c == jnp.uint8(0), jnp.uint8(65),
        jnp.where(c == jnp.uint8(1), jnp.uint8(67),
                  jnp.where(c == jnp.uint8(2), jnp.uint8(71),
                            jnp.uint8(84))))
    return b.astype(jnp.uint64)


def _murmur3_k21_1d(cb, seed: int) -> jax.Array:
    """murmur3 x64_128 h1 over 21-byte keys given as a list of 21 uint64
    byte vectors — the 1-D twin of murmur3_x64_128_h1's (n, 21) path
    (one 16-byte block + a 5-byte k1 tail), bit-identical."""
    length = len(cb)
    assert length == 21
    n = cb[0].shape[0]
    h1 = jnp.full((n,), jnp.uint64(seed))
    h2 = jnp.full((n,), jnp.uint64(seed))

    k1 = cb[0]
    for b in range(1, 8):
        k1 = k1 | (cb[b] << jnp.uint64(8 * b))
    k2 = cb[8]
    for b in range(1, 8):
        k2 = k2 | (cb[8 + b] << jnp.uint64(8 * b))
    k1 = _rotl64(k1 * _C1, 31) * _C2
    h1 = h1 ^ k1
    h1 = _rotl64(h1, 27) + h2
    h1 = h1 * jnp.uint64(5) + jnp.uint64(0x52DCE729)
    k2 = _rotl64(k2 * _C2, 33) * _C1
    h2 = h2 ^ k2
    h2 = _rotl64(h2, 31) + h1
    h2 = h2 * jnp.uint64(5) + jnp.uint64(0x38495AB5)

    k1 = cb[16]
    for b in range(1, 5):
        k1 = k1 | (cb[16 + b] << jnp.uint64(8 * b))
    k1 = _rotl64(k1 * _C1, 31) * _C2
    h1 = h1 ^ k1

    h1 = h1 ^ jnp.uint64(length)
    h2 = h2 ^ jnp.uint64(length)
    h1 = h1 + h2
    h2 = h2 + h1
    h1 = _fmix64(h1)
    h2 = _fmix64(h2)
    h1 = h1 + h2
    return h1


def _tpufast_mix(x: jax.Array, seed: int) -> jax.Array:
    """Multiply-free 64-bit mixer for TPU (shift-add sparse-constant
    rounds).

    The TPU VPU has no fast integer multiplier (a u64 multiply costs
    ~50x a shift/xor under XLA's emulation), which makes MurmurHash3 —
    12 u64 multiplies per k-mer — the sketching bottleneck. MinHash only
    needs a UNIFORM ranking hash, not murmur parity, so this mixer
    replaces every dense multiply with a sparse-constant multiply
    (x * (1 + 2^a + 2^b) = x + (x<<a) + (x<<b): two shifts + two adds)
    interleaved with xorshifts. Avalanche quality is validated
    empirically in tests/test_tpufast_hash.py (bit balance, sketch-level
    Jaccard accuracy vs the murmur path).
    """
    x = x ^ jnp.uint64((seed * 0x9E3779B97F4A7C15 + 0x1B873593) % (1 << 64))
    for sh_a, sh_b, sh_x in ((21, 37, 29), (13, 47, 31), (17, 41, 33)):
        # x *= (1 + 2^a + 2^b); x ^= x >> c  — wrap-around adds mix the
        # low bits upward, the xorshift folds high entropy back down.
        x = x + (x << jnp.uint64(sh_a)) + (x << jnp.uint64(sh_b))
        x = x ^ (x >> jnp.uint64(sh_x))
    x = x + (x << jnp.uint64(26))
    x = x ^ (x >> jnp.uint64(32))
    return x


def _canonical_core(
    cs: jax.Array,          # uint8 (C,) sanitized codes, 0-3 everywhere
    valid1: jax.Array,      # bool (C,) False at ambiguous/pad positions
    offsets: jax.Array,     # int32 (B,) contig start offsets (padded with
                            # a value > any position; see iter_chunk_hashes)
    pos: jax.Array,         # int32 scalar: global position of cs[0]
    k: int,
):
    """Window packing + boundary masking + canonical orientation: the
    hash-independent front half of `_hash_core`, shared with the fused
    Pallas sketch preamble (`canonical_kmer_words`).

    Returns (fwd, rev, valid, use_fwd) over the C-k+1 window positions:
    the forward and reverse-complement 2-bit packed windows (uint64),
    the window validity (no ambiguous base, no contig crossing), and the
    canonical-orientation select.
    """
    n = cs.shape[0]
    n_win = n - k + 1

    # Sliding-window packs via log-doubling: pack(i, 2m) =
    # pack(i, m) << 2m | pack(i+m, m), so k-wide window packs (and the
    # window validity ANDs) cost O(log k) combines over 1-D arrays
    # instead of O(k) shift-or chains.
    w = {1: cs.astype(jnp.uint64)}                  # fwd pack, MSB-first
    r = {1: (jnp.uint8(3) - cs).astype(jnp.uint64)}  # revcomp pack
    v = {1: valid1}
    m = 1
    while 2 * m <= k:
        lm = n - 2 * m + 1
        w[2 * m] = (w[m][:lm] << jnp.uint64(2 * m)) | w[m][m:m + lm]
        r[2 * m] = r[m][:lm] | (r[m][m:m + lm] << jnp.uint64(2 * m))
        v[2 * m] = v[m][:lm] & v[m][m:m + lm]
        m *= 2

    # Combine the binary decomposition of k (most-significant first).
    parts = [p for p in sorted(w, reverse=True) if k & p]
    fwd = w[parts[0]][:n_win]
    rev = r[parts[0]][:n_win]
    valid = v[parts[0]][:n_win]
    off = parts[0]
    for p in parts[1:]:
        fwd = (fwd << jnp.uint64(2 * p)) | w[p][off:off + n_win]
        rev = rev | (r[p][off:off + n_win] << jnp.uint64(2 * off))
        valid = valid & v[p][off:off + n_win]
        off += p

    gpos = pos + jnp.arange(n, dtype=jnp.int32)
    boundary = jnp.searchsorted(offsets, gpos, side="right")
    valid = valid & (boundary[:n_win] == boundary[k - 1:k - 1 + n_win])

    # Lexicographic-min canonical compare: code order A<C<G<T matches
    # ASCII order, so integer compare == string compare at fixed length
    # (k <= 32 bases in 64 bits).
    use_fwd = fwd <= rev
    return fwd, rev, valid, use_fwd


def _canonical_bytes(cs, use_fwd, k: int, n_win: int):
    """Canonical ASCII byte vectors for the murmur contract: byte j is
    fwd ? ascii(cs[j]) : ascii(3-cs[k-1-j]). The select chains run ONCE
    over the full chunk; the per-byte views are slices of those two
    arrays."""
    af = _ascii64(cs)
    ar = _ascii64(jnp.uint8(3) - cs)
    return [
        jnp.where(use_fwd, af[j:j + n_win],
                  ar[k - 1 - j:k - 1 - j + n_win])
        for j in range(k)
    ]


def _hash_core(
    cs: jax.Array,          # uint8 (C,) sanitized codes, 0-3 everywhere
    valid1: jax.Array,      # bool (C,) False at ambiguous/pad positions
    offsets: jax.Array,     # int32 (B,) contig start offsets (padded with
                            # a value > any position; see iter_chunk_hashes)
    pos: jax.Array,         # int32 scalar: global position of cs[0]
    k: int,
    seed: int,
    algo: str,
) -> jax.Array:
    """Hash every canonical k-mer starting in this chunk -> (C-k+1,) uint64.

    Positions whose window contains an ambiguous base or crosses a contig
    boundary produce HASH_SENTINEL. The caller overlaps consecutive chunks
    by k-1 positions so every k-mer is seen exactly once. The contig id
    per position is derived ON DEVICE from the (tiny) offsets array —
    uploading a per-position boundary array would quadruple the
    host->device traffic of the 1-byte codes.

    Everything is formulated over 1-D shifted slices of `cs` (k static
    slices, fused elementwise chains) — the earlier (n_win, k) 2-D
    formulation materialized hundreds of MB of uint64 intermediates per
    chunk and was HBM-bound.

    `algo` selects the hash: "murmur3" reproduces the reference's finch
    contract bit-for-bit (canonical ASCII k-mer, murmur3 x64_128 h1,
    reference: src/finch.rs:33-47; the golden 0.9808188 depends on it);
    "tpufast" hashes the canonical 2-bit packed k-mer with a
    multiply-free mixer — statistically equivalent MinHash estimates at
    ~20x the device throughput (the VPU has no fast integer multiply).
    """
    n = cs.shape[0]
    n_win = n - k + 1
    fwd, rev, valid, use_fwd = _canonical_core(cs, valid1, offsets, pos, k)

    if algo == "tpufast":
        # the canonical 2-bit packed key is already in hand — no ASCII
        # expansion, no murmur: just the multiply-free mixer
        hashes = _tpufast_mix(jnp.where(use_fwd, fwd, rev), seed)
    elif algo == "murmur3":
        cb = _canonical_bytes(cs, use_fwd, k, n_win)
        if k == 21:
            # Opt-in Mosaic hash state machine (read at FIRST TRACE of
            # the enclosing jit — set before first use, or
            # jax.clear_caches()); interpret mode keeps the opt-in
            # exercisable on CPU backends.
            if _use_pallas_hash():
                from galah_tpu.ops.pallas_sketch import (
                    assemble_k21_words,
                    murmur3_k21_pallas,
                )

                kw1, kw2, kwt = assemble_k21_words(cb)
                hashes = murmur3_k21_pallas(
                    kw1, kw2, kwt, seed=seed,
                    interpret=jax.default_backend() != "tpu")
            else:
                hashes = _murmur3_k21_1d(cb, seed)
        else:
            ascii_kmers = jnp.stack(cb, axis=1).astype(jnp.uint8)
            hashes = murmur3_x64_128_h1(ascii_kmers, seed=seed)
    else:
        raise ValueError(f"unknown hash algorithm {algo!r}")
    return jnp.where(valid, hashes, HASH_SENTINEL)


def canonical_kmer_words(cs, valid1, offsets, pos, k: int, algo: str):
    """Canonical k-mer KEY WORDS + window validity — the front half of
    `_hash_core` (window packing, boundary masking, canonical selection)
    without the hash, for the fused Pallas sketch kernel
    (ops/pallas_sketch.fused_sketch_candidates) which hashes in-kernel.

    Returns (words, valid): `words` is a tuple of uint64 (C-k+1,)
    arrays — the assembled murmur3 key words (k1, k2, tail) for
    algo="murmur3" (k must be 21: the fused kernel bakes the 21-byte
    state machine), or the single canonical 2-bit packed k-mer for
    algo="tpufast". Bit-identical inputs to what `_hash_core` feeds its
    hash stage, so fused sketches match the XLA/C paths exactly.
    """
    n_win = cs.shape[0] - k + 1
    fwd, rev, valid, use_fwd = _canonical_core(cs, valid1, offsets, pos, k)
    if algo == "tpufast":
        return (jnp.where(use_fwd, fwd, rev),), valid
    if algo == "murmur3":
        if k != 21:
            raise ValueError(
                f"fused murmur3 sketching requires k=21, got k={k}")
        from galah_tpu.ops.pallas_sketch import assemble_k21_words

        cb = _canonical_bytes(cs, use_fwd, k, n_win)
        return assemble_k21_words(cb), valid
    raise ValueError(f"unknown hash algorithm {algo!r}")


def canonical_kmer_words_batch(packed, ambits, offsets, k, algo):
    """Batched-row twin of `canonical_kmer_words` over packed genome
    groups (same row layout as canonical_kmer_hashes_batch): (G, C/4)
    packed + (G, C/8) mask + (G, B) offsets -> (words, valid) with each
    word (G, C-k+1) uint64 and valid (G, C-k+1) bool.

    Unjitted building block: the fused sketch path embeds it in the
    same jit as the Pallas launch so XLA fuses the unpack/select chains
    into the kernel's operand production.
    """
    def row(p, a, o):
        cs, v1 = _unpack_codes(p, a)
        return canonical_kmer_words(cs, v1, o, jnp.int32(0), k, algo)

    return jax.vmap(row)(packed, ambits, offsets)


@functools.partial(jax.jit, static_argnames=("k", "seed", "algo"))
def canonical_kmer_hashes_chunk(
    codes: jax.Array,       # uint8 (C,), 0-3 valid, 255 ambiguous/pad
    offsets: jax.Array,
    pos: jax.Array,
    k: int = 21,
    seed: int = 0,
    algo: str = "murmur3",
) -> jax.Array:
    """Hash canonical k-mers from unpacked 1-byte-per-base codes.

    See _hash_core for semantics. Production chunk iteration uses the
    packed twin below (2.7x less host->device transfer); this entry point
    stays for callers holding codes already on device.
    """
    cs = jnp.where(codes == jnp.uint8(255), jnp.uint8(0), codes)
    return _hash_core(cs, codes != jnp.uint8(255), offsets, pos,
                      k, seed, algo)


def _unpack_codes(packed, ambits):
    """Planar 2-bit codes + ambiguity bitmask (pack_codes_host) ->
    (codes uint8 (C,), valid bool): a concatenation of contiguous
    planes. An interleaving unpack (stack on a minor axis of 4 or 8,
    then flatten) costs the TPU compiler over a minute per Mbp-scale
    shape."""
    p = packed
    cs = jnp.concatenate(
        [(p >> jnp.uint8(s)) & jnp.uint8(3) for s in (6, 4, 2, 0)])
    a = ambits
    amb = jnp.concatenate(
        [(a >> jnp.uint8(s)) & jnp.uint8(1) for s in range(7, -1, -1)])
    return cs, amb == jnp.uint8(0)


def _packed_core(packed, ambits, offsets, pos, k, seed, algo):
    """Unpack 2-bit codes + ambiguity bitmask on device, then hash."""
    cs, valid1 = _unpack_codes(packed, ambits)
    return _hash_core(cs, valid1, offsets, pos, k, seed, algo)


@functools.partial(jax.jit, static_argnames=("k", "seed", "algo"))
def canonical_kmer_hashes_chunk_packed(
    packed: jax.Array,      # uint8 (C/4,): 4 bases/byte, planar
    ambits: jax.Array,      # uint8 (C/8,): ambiguity bitmask, planar
    offsets: jax.Array,
    pos: jax.Array,
    k: int = 21,
    seed: int = 0,
    algo: str = "murmur3",
) -> jax.Array:
    """Packed-transfer twin of canonical_kmer_hashes_chunk, bit-identical.

    The host packs 4 bases/byte plus a 1-bit/base ambiguity mask (0.375
    bytes/base vs 1), and the device unpacks with shift/mask chains —
    host->device bytes are a scarce resource on a TPU host, and the
    unpack is a handful of fused vector ops.
    """
    return _packed_core(packed, ambits, offsets, pos, k, seed, algo)


def canonical_kmer_hashes_batch(packed, ambits, offsets, k, seed, algo):
    """Batched rows: (G, C/4) packed + (G, C/8) mask + (G, B) offsets ->
    (G, C-k+1) uint64 hashes. Each row is an independent genome starting
    at position 0 (offsets are that genome's interior contig starts).

    Unjitted building block (callers embed it in their own jit): one
    dispatch hashes a whole group of genomes — the per-dispatch round
    trip otherwise dominates small-genome sketching.
    """
    return jax.vmap(
        lambda p, a, o: _packed_core(p, a, o, jnp.int32(0), k, seed, algo)
    )(packed, ambits, offsets)


@functools.partial(jax.jit, static_argnames=("k", "seed", "algo"))
def canonical_kmer_hashes_batch_jit(packed, ambits, offsets, k=21,
                                    seed=0, algo="murmur3"):
    """Jitted standalone wrapper of canonical_kmer_hashes_batch for
    callers that want the raw positional hash rows (fragment profiles)."""
    return canonical_kmer_hashes_batch(packed, ambits, offsets, k, seed,
                                       algo)


def iter_genome_groups(genomes, budget, max_len, quantum=1 << 16,
                       pow2=False):
    """Host-side grouping for batched sketching: bucket genomes by
    quantum-padded length (+ pow2 interior-offset width, bounding compile
    variants), pack each group, and yield
    (indices, packed (G, L/4), ambits (G, L/8), offsets (G, B)).

    Genomes longer than `max_len` are NOT yielded — callers handle them
    via their chunked single-genome path (their indices are returned in
    the `skipped` list, populated before the first yield). With `pow2`
    the padded length is a power of two: a handful of compile variants
    for a whole real length distribution instead of one per quantum.
    """
    import numpy as np

    groups: dict = {}
    skipped = []
    for i, g in enumerate(genomes):
        n = g.codes.shape[0]
        if n > max_len:
            skipped.append(i)
            continue
        lb = max(quantum, -(-n // quantum) * quantum)
        if pow2:
            lb = 1 << (lb - 1).bit_length()
        n_off = max(len(g.contig_offsets) - 2, 0)
        b = 1
        while b < max(n_off, 1):
            b <<= 1
        groups.setdefault((lb, b), []).append(i)

    def gen():
        for (lb, b), idxs in sorted(groups.items()):
            per = max(1, budget // lb)
            for start in range(0, len(idxs), per):
                chunk_idxs = idxs[start:start + per]
                G = len(chunk_idxs)
                packed = np.empty((G, lb // 4), dtype=np.uint8)
                ambits = np.empty((G, lb // 8), dtype=np.uint8)
                offs = np.full((G, b), np.int32(2**31 - 1),
                               dtype=np.int32)
                row_codes = np.full(lb, 255, dtype=np.uint8)
                for row, gi in enumerate(chunk_idxs):
                    g = genomes[gi]
                    row_codes[:] = 255
                    row_codes[: g.codes.shape[0]] = g.codes
                    packed[row], ambits[row] = pack_codes_host(row_codes)
                    interior = np.asarray(g.contig_offsets[1:-1],
                                          dtype=np.int64)
                    offs[row, : interior.shape[0]] = (
                        interior.astype(np.int32))
                yield chunk_idxs, packed, ambits, offs

    return skipped, gen()


def pack_codes_host(c: "np.ndarray"):
    """Host-side packing: uint8 codes (len % 8 == 0, 255 = ambiguous/pad)
    -> (packed 4 bases/byte, ambiguity bitmask), both uint8, planar.

    With n = len(c): byte j of `packed` holds bases j, j + n/4, j + n/2
    and j + 3n/4 in bits 7-6, 5-4, 3-2 and 1-0; bit 7-e of `ambits` byte
    j flags position j + e*n/8. The device unpack (_unpack_codes) is
    then a concatenation of contiguous planes."""
    import numpy as np

    amb = c == 255
    sane = np.where(amb, np.uint8(0), c)
    s4 = sane.reshape(4, -1)
    packed = ((s4[0] << 6) | (s4[1] << 4)
              | (s4[2] << 2) | s4[3]).astype(np.uint8)
    return packed, np.packbits(amb.reshape(8, -1).T, axis=1).reshape(-1)


def iter_chunk_hashes(codes, contig_offsets, k: int, chunk: int,
                      seed: int = 0, algo: str = "murmur3"):
    """Yield (hashes, n_new) device arrays over fixed-size overlapping chunks.

    Single implementation of the chunk/pad/overlap discipline shared by the
    MinHash sketcher and the fragment-ANI profiler: chunks overlap by k-1 so
    every k-mer window is hashed exactly once; `n_new` is how many leading
    entries of `hashes` are first-time positions (the rest are overlap).
    """
    import numpy as np

    if chunk <= k - 1:
        raise ValueError(f"chunk ({chunk}) must exceed k-1 ({k - 1})")
    n = codes.shape[0]

    # Bucket the chunk size to the genome: padding a 2 Mbp genome into an
    # 8 Mi chunk would upload 4x the bytes for nothing. Buckets are 64 Ki
    # multiples so XLA compiles a handful of variants.
    quantum = 1 << 16
    chunk = max(quantum, min(chunk, -(-n // quantum) * quantum))
    # Host packing (4 bases/byte + bitmask) needs chunk % 8 == 0; only a
    # caller-supplied chunk between the quantum and the bucketed size can
    # be ragged — round it down (still > k-1 since chunk >= 64 Ki).
    chunk &= ~7

    # Contig offsets, padded to a power-of-two length (bounding compile
    # variants) with a sentinel beyond any real position so the padded
    # entries never split a window.
    offs = np.asarray(contig_offsets[1:-1], dtype=np.int64)
    b = 1
    while b < max(offs.shape[0], 1):
        b <<= 1
    offs_pad = np.full(b, np.int64(2**31 - 1), dtype=np.int64)
    offs_pad[: offs.shape[0]] = offs
    joffs = jnp.asarray(offs_pad.astype(np.int32))

    packed_transfer = device_transfer_bound()
    step = chunk - (k - 1)
    pos = 0
    total = max(n - k + 1, 0)
    while pos < total or pos == 0:
        end = min(pos + chunk, n)
        c = np.full(chunk, 255, dtype=np.uint8)
        c[: end - pos] = codes[pos:end]
        if packed_transfer:
            # Pack on host: 4 bases/byte + 1-bit ambiguity mask (chunk
            # is a 64 Ki multiple, so always divisible by 8). Cuts
            # host->device bytes 2.7x. On CPU the unpack is pure
            # overhead, so the
            # unpacked twin runs instead (bit-identical).
            packed, ambits = pack_codes_host(c)
            hashes = canonical_kmer_hashes_chunk_packed(
                jnp.asarray(packed), jnp.asarray(ambits), joffs,
                jnp.int32(pos), k=k, seed=seed, algo=algo)
        else:
            hashes = canonical_kmer_hashes_chunk(
                jnp.asarray(c), joffs, jnp.int32(pos), k=k, seed=seed,
                algo=algo)
        n_new = min(total - pos, chunk - k + 1) if total else 0
        yield hashes, pos, n_new
        pos += step
        if end >= n:
            break


@functools.partial(jax.jit, static_argnames=("sketch_size",))
def bottom_k_update(
    running: jax.Array,  # uint64 (sketch_size,) sorted asc, SENTINEL-padded
    hashes: jax.Array,   # uint64 (m,) chunk hashes, SENTINEL where invalid
    sketch_size: int = 1000,
) -> jax.Array:
    """Fold a chunk of hashes into a running bottom-k distinct sketch."""
    allh = jnp.concatenate([running, hashes])
    allh = jnp.sort(allh)
    # Mark duplicates (keep first occurrence), then re-sort and truncate.
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), allh[1:] == allh[:-1]])
    allh = jnp.where(dup, HASH_SENTINEL, allh)
    allh = jnp.sort(allh)
    return allh[:sketch_size]
