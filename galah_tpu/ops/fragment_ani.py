"""Fragment-containment exact ANI — the TPU-native ANI refinement kernel.

This is the framework's re-design of the reference's two exact-ANI
backends, built on one primitive that maps well to the hardware instead of
their irregular algorithms:

  * fastANI (reference: src/fastani.rs:31-150) decomposes the query into
    3 kb fragments, maps each against the reference with Mashmap, and
    averages per-fragment identity over mapped fragments, gating on the
    mapped-fragment fraction.
  * skani (reference: src/skani.rs:125-177) chains FracMinHash seed
    matches into syntenic runs and reports identity over aligned regions
    plus an aligned fraction.

Both separate "how much of the genome aligns" (aligned fraction) from
"identity within aligned regions" (ANI). The TPU-native equivalent here:

  1. the query is cut into fixed-length windows (fragments); every
     canonical k-mer hash in a window is tested for membership in the
     reference's full distinct k-mer set (one big `searchsorted` — a
     regular, batchable gather instead of chaining/mapping);
  2. a window with matched-kmer fraction c_w above a floor counts as
     aligned; its identity estimate is c_w^(1/k) (the standard k-mer
     survival model: a fraction ANI^k of k-mers survives substitutions);
  3. ANI = mean identity over aligned windows; aligned fraction =
     aligned windows / total windows. Both directions are computed and
     combined by the caller's gate semantics.

Static shapes via bucketing: reference sets pad to the next power of two,
window counts to multiples of 64, so XLA compiles a handful of kernel
variants for any genome collection.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galah_tpu.io.fasta import Genome
from galah_tpu.ops import hashing
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.utils import timing

MARKER_C = 1000  # FracMinHash compression for screening markers
                 # (reference: src/skani.rs:158 "let m = 1000")

# Numeric-determinism contract checked by `galah-tpu lint` (GL9xx):
# directed/bidirectional ANI must be bit-identical across the single,
# batch, and distributed paths. Weighted fragment coverage accumulates
# in float64 THROUGH COMPRESSED SEGMENTS (_seq_sum over c_w[mask],
# _segment_compressed_sums over concatenated survivors) — summing a
# zero-filled np.where instead drifts a ulp (the PR 5 regression).
DETERMINISM_CONTRACT = {
    "family": "fragment",
    "dtype": "float64",
    "functions": ["directed_ani", "directed_ani_batch",
                  "bidirectional_ani", "bidirectional_ani_batch",
                  "bidirectional_ani_values",
                  "_directed_from_counts",
                  "_directed_from_counts_arrays",
                  "_seq_sum", "_segment_compressed_sums"],
}


@dataclasses.dataclass
class GenomeProfile:
    """Device-facing k-mer views of one genome for exact ANI."""

    path: str
    k: int
    fraglen: int
    flat_hashes: np.ndarray   # uint64 (n-k+1,), positional, SENTINEL-masked
    ref_set: np.ndarray       # uint64 sorted distinct hashes
    markers: np.ndarray       # uint64 sorted, hashes < 2^64 / MARKER_C
    #: FracMinHash compression: only k-mers with hash < 2^64/c
    #: participate in window counting and the reference set (the
    #: reference's skani uses c=125 the same way, src/skani.rs:159-161);
    #: c=1 keeps every k-mer (dense, exact)
    subsample_c: int = 1

    # lazily cached device-resident padded views (upload once per genome)
    _dev_windows: Optional[jax.Array] = None
    _dev_ref_set: Optional[jax.Array] = None
    # ... and their padded host twins (computed once, reused by both the
    # single-device upload and the batch-sharding assembly path)
    _np_windows_padded: Optional[np.ndarray] = None
    _np_ref_padded: Optional[np.ndarray] = None
    # unpadded windows, cached for padded_windows() and the numpy
    # sorted_query twin
    _np_windows: Optional[np.ndarray] = None
    # (sorted hashes, their window ids, per-window totals) — cached for
    # the C merge and Pallas strategies; totals are pair-independent
    _np_sorted_query: "Optional[tuple]" = None
    # kept (hash, position) pairs from the C profile walk: lets
    # windows() assemble compacted rows in O(n_valid) instead of two
    # streaming passes over the 8-byte-per-bp flat array
    _kept_hashes: Optional[np.ndarray] = None
    _kept_pos: Optional[np.ndarray] = None

    @property
    def n_windows(self) -> int:
        return -(-self.flat_hashes.shape[0] // self.fraglen)

    def sorted_query(self) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
        """(qh, qw, totals): the profile's surviving window hashes
        sorted ascending, their window row ids, and each window's
        valid-hash count; equal hashes in ascending window order.
        Built once and cached — the exact-ANI strategies that read it
        (the C merge, csrc/pairstats.c::galah_window_match_counts_merge,
        and the Pallas kernel) consume it per pair.

        The C walk over flat_hashes (csrc/pairstats.c::
        galah_sorted_query) builds it without the windows() matrix;
        sorted_query_numpy over windows() is its bit-identical twin
        where the toolchain is absent. flat_hashes holds exactly the surviving k-mers of
        windows() at every subsample_c: the FracMinHash mask is
        positional, and the profile walk's kept (pos, hash) pairs are
        the flat array's non-SENTINEL entries."""
        if self._np_sorted_query is None:
            try:
                from galah_tpu.ops import _cpairstats
            except ImportError:
                self._np_sorted_query = sorted_query_numpy(self.windows())
            else:
                self._np_sorted_query = _cpairstats.sorted_query(
                    self.flat_hashes, self.n_windows, self.fraglen,
                    self.k)
                timing.counter("fragment-sorted-query-native", 1)
        return self._np_sorted_query

    def query_positions(self) -> int:
        """The profile's query length: surviving k-mer positions that
        lie inside their fragment, which is the sum of sorted_query()'s
        totals and the count of windows()' non-SENTINEL entries,
        counted from the profile walk's kept positions where it left
        them, else from flat_hashes, without building either."""
        flat, length = self.flat_hashes, self.fraglen
        inner = max(length - (self.k - 1), 0)
        if self._kept_pos is not None:
            return int(np.count_nonzero(self._kept_pos % length < inner))
        full = flat.shape[0] // length
        sentinel = np.uint64(SENTINEL)
        rows = flat[:full * length].reshape(full, length)[:, :inner]
        return (int(np.count_nonzero(rows != sentinel))
                + int(np.count_nonzero(
                    flat[full * length:][:inner] != sentinel)))

    def padded_windows(self) -> np.ndarray:
        if self._np_windows_padded is None:
            self._np_windows_padded = pad_windows(self.windows())
        return self._np_windows_padded

    def padded_ref_set(self) -> np.ndarray:
        if self._np_ref_padded is None:
            self._np_ref_padded = pad_ref_set(self.ref_set)
        return self._np_ref_padded

    def device_windows(self) -> jax.Array:
        if self._dev_windows is None:
            self._dev_windows = jnp.asarray(self.padded_windows())
        return self._dev_windows

    def device_ref_set(self) -> jax.Array:
        if self._dev_ref_set is None:
            self._dev_ref_set = jnp.asarray(self.padded_ref_set())
        return self._dev_ref_set

    def windows(self) -> np.ndarray:
        """(W, slots) positional hash windows; k-mers crossing a window
        boundary are masked so each fragment is self-contained, matching
        fastANI's disjoint 3 kb fragments.

        With subsample_c > 1 the surviving (non-SENTINEL) hashes are
        compacted to the front of each row and the row width shrinks to
        the longest window's count (padded to a multiple of 64) — the
        per-window (matched, total) integers are unchanged (counting is
        SENTINEL-aware and order-independent), but the membership-test
        work really does drop ~c-fold.

        Cached after the first call (the greedy loop re-queries the
        same profile across many batches).
        """
        if self._np_windows is not None:
            return self._np_windows
        L = self.fraglen
        flat = self.flat_hashes
        w = self.n_windows
        if self.subsample_c > 1:
            # Compacted layout from the profile walk's kept (pos, hash)
            # pairs when available — O(n_valid) assembly; else two
            # streaming C passes over flat — both bit-identical to the
            # numpy stable-argsort twin below (tests/test_cpairstats.py),
            # which costs ~150 ms per 3 Mbp genome and was the
            # realistic-rung exact-ANI wall. Host-side on any backend.
            try:
                from galah_tpu.ops import _cpairstats

                if self._kept_pos is not None:
                    self._np_windows = _cpairstats.windows_from_pairs(
                        self._kept_pos, self._kept_hashes, w, L,
                        self.k)
                    # consumed exactly once; the result is cached
                    self._kept_pos = None
                    self._kept_hashes = None
                else:
                    self._np_windows = _cpairstats.compact_windows(
                        flat, w, L, self.k)
                return self._np_windows
            except ImportError:
                pass
        pad = np.full(w * L, np.uint64(SENTINEL), dtype=np.uint64)
        pad[: flat.shape[0]] = flat
        wins = pad.reshape(w, L).copy()
        wins[:, L - (self.k - 1):] = np.uint64(SENTINEL)
        if self.subsample_c > 1:
            # stable argsort of the sentinel mask moves surviving
            # hashes to the front of each row, preserving their order
            order = np.argsort(wins == np.uint64(SENTINEL), axis=1,
                               kind="stable")
            wins = np.take_along_axis(wins, order, axis=1)
            counts = (wins != np.uint64(SENTINEL)).sum(axis=1)
            slots = max(int(counts.max()) if counts.size else 1, 1)
            slots = -(-slots // 64) * 64
            wins = wins[:, :slots].copy()
        self._np_windows = wins
        return wins


def sorted_query_numpy(
        wins: np.ndarray) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    """GenomeProfile.sorted_query's (qh, qw, totals) from its windows()
    matrix — the numpy twin of csrc/pairstats.c::galah_sorted_query.
    The stable argsort keeps np.nonzero's row-major order among equal
    hashes, so they come out in ascending window order, as the C sort
    emits them."""
    mask = wins != np.uint64(SENTINEL)
    totals = mask.sum(axis=1, dtype=np.int32)
    rows, _cols = np.nonzero(mask)
    qh = wins[mask]
    order = np.argsort(qh, kind="stable")
    return qh[order], rows[order].astype(np.int32), totals


# Half the generic hashing.BATCH_BUDGET: profile batches download the
# FULL positional hash rows (8 bytes/position) to host, unlike the
# sketch paths that reduce on device first, so the per-dispatch host
# array is kept to ~128 MB.
PROFILE_BATCH_BUDGET = hashing.BATCH_BUDGET // 2


def positional_hashes(genome: Genome, k: int,
                      chunk: int = hashing.DEFAULT_CHUNK,
                      algo: str = "murmur3") -> np.ndarray:
    """All canonical k-mer hashes of a genome in genome order (device).

    On a single-process CPU backend the compiled-C walker
    (csrc/sketch.c::galah_positional_hashes) runs instead —
    bit-identical, and an order of magnitude faster than the XLA-CPU
    chunk pipeline on one core. An explicit non-default chunk pins the
    JAX path (parity tests drive it that way)."""
    n = genome.codes.shape[0]
    if n < k:
        return np.zeros(0, dtype=np.uint64)
    if k <= 32 and chunk == hashing.DEFAULT_CHUNK:
        try:
            from galah_tpu.ops import _csketch

            return _csketch.positional_hashes(
                genome.codes, genome.contig_offsets, k=k, algo=algo)
        except ImportError:
            pass  # no C toolchain: fall through to the JAX path
    out = np.empty(n - k + 1, dtype=np.uint64)
    for h, pos, n_new in hashing.iter_chunk_hashes(
            genome.codes, genome.contig_offsets, k=k, chunk=chunk,
            algo=algo):
        timing.dispatch()
        timing.dispatch(sync=True)
        out[pos: pos + n_new] = np.asarray(h)[:n_new]
    return out


def positional_hashes_batch(genomes, k: int,
                            budget: int = PROFILE_BATCH_BUDGET,
                            algo: str = "murmur3") -> list:
    """Batch twin of positional_hashes: grouped one-dispatch hashing of
    many genomes (same grouping as ops/minhash batch sketching), each
    entry bit-identical to positional_hashes(genome, k)."""
    out = [None] * len(genomes)
    skipped, group_iter = hashing.iter_genome_groups(
        genomes, budget=budget, max_len=hashing.DEFAULT_CHUNK)
    for i in skipped:
        out[i] = positional_hashes(genomes[i], k, algo=algo)
    for chunk_idxs, packed, ambits, offs in group_iter:
        import jax.numpy as jnp

        from galah_tpu.obs import metrics as obs_metrics

        obs_metrics.counter(
            "hash.batched_genomes",
            help="Genomes hashed in grouped one-dispatch batches "
                 "(vs the per-genome chunk pipeline)",
            unit="genomes").inc(len(chunk_idxs))
        timing.dispatch()
        timing.dispatch(sync=True)
        h = np.asarray(hashing.canonical_kmer_hashes_batch_jit(
            jnp.asarray(packed), jnp.asarray(ambits), jnp.asarray(offs),
            k=k, algo=algo))
        for row, gi in enumerate(chunk_idxs):
            n = genomes[gi].codes.shape[0]
            if n < k:
                out[gi] = np.zeros(0, dtype=np.uint64)
            else:
                out[gi] = h[row, : n - k + 1].copy()
    return out


def _check_subsample(subsample_c: int) -> None:
    if not 1 <= subsample_c <= MARKER_C:
        raise ValueError(
            f"subsample_c must be in [1, {MARKER_C}], got {subsample_c}")


def _finish_profile(path: str, flat: np.ndarray, valid: np.ndarray,
                    k: int, fraglen: int, subsample_c: int,
                    pos: Optional[np.ndarray] = None) -> GenomeProfile:
    """Distinct set + marker slice + construction — the one tail
    shared by the C single-pass and generic profile builds. `pos`
    (the kept hashes' positions, when the C profile walk produced
    them) enables the O(n_valid) window assembly."""
    # np.unique stays: numpy's u64 sort is radix-backed and measured
    # 4x FASTER than the inlined C quicksort on 3M-hash inputs
    # (74 vs 287 ms, 2026-07-31) — a C dedup here is a pessimization.
    ref_set = np.unique(valid)
    markers = ref_set[ref_set < np.uint64((1 << 64) // MARKER_C)]
    return GenomeProfile(
        path=path, k=k, fraglen=fraglen,
        flat_hashes=flat, ref_set=ref_set, markers=markers,
        subsample_c=subsample_c,
        _kept_hashes=valid if pos is not None else None,
        _kept_pos=pos)


def _profile_from_flat(path: str, flat: np.ndarray, k: int, fraglen: int,
                       subsample_c: int) -> GenomeProfile:
    """Host post-pass shared by single and batched profile builds:
    FracMinHash subsample mask, distinct set, marker slice."""
    _check_subsample(subsample_c)
    if subsample_c > 1:
        cut = np.uint64((1 << 64) // subsample_c)
        flat = np.where(flat < cut, flat, np.uint64(SENTINEL))
    valid = flat[flat != np.uint64(SENTINEL)]
    return _finish_profile(path, flat, valid, k, fraglen, subsample_c)


def _c_profile_available(k: int) -> bool:
    """Gate for the C single-pass profile build — genome-independent
    by construction (k width, toolchain), so callers may decide once
    per batch. It holds on every backend: a profile is host data (every
    positional hash comes back to the host), so the device pass saves
    no transfer, and on a v5e chip its batched hashing of real-size
    genomes compiled one variant per length bucket and then exceeded
    HBM."""
    if k > 32:
        return False
    try:
        from galah_tpu.ops import _csketch  # noqa: F401
    except ImportError:
        return False
    return True


def _profile_via_c(genome: Genome, k: int, fraglen: int,
                   subsample_c: int,
                   algo: str = "murmur3") -> GenomeProfile:
    """Single-pass C profile build: hash walk + FracMinHash mask +
    valid compaction in one sweep (csrc/sketch.c::
    galah_positional_hashes_masked), leaving only a small np.unique on
    the kept hashes. Bit-identical to the _profile_from_flat post-pass
    (parity: tests/test_csketch.py). Callers must check
    _c_profile_available first."""
    from galah_tpu.ops import _csketch

    if subsample_c == 1:
        # dense profile: windows() uses the flat layout directly, so
        # the kept-positions array would be 8 B/bp of dead weight
        flat, valid = _csketch.positional_hashes_masked(
            genome.codes, genome.contig_offsets, k=k, cut=0, algo=algo)
        return _finish_profile(genome.path, flat, valid, k, fraglen,
                               subsample_c)
    cut = (1 << 64) // subsample_c
    flat, valid, pos = _csketch.positional_hashes_profile(
        genome.codes, genome.contig_offsets, k=k, cut=cut, algo=algo)
    return _finish_profile(genome.path, flat, valid, k, fraglen,
                           subsample_c, pos=pos)


def build_profile(genome: Genome, k: int, fraglen: int,
                  subsample_c: int = 1,
                  hash_algorithm: str = "murmur3") -> GenomeProfile:
    """Profile a genome for fragment ANI.

    With subsample_c > 1 only k-mers whose hash falls below 2^64/c are
    kept (positionally SENTINEL-masked, so window structure survives):
    a FracMinHash subsample, exactly the compression the reference's
    skani applies with c=125 (reference: src/skani.rs:159-161). Both
    the query windows AND the reference set shrink by ~c, cutting the
    membership-test work ~c^2/c = c-fold per direction with an
    unbiased per-window matched-fraction estimate. Markers are computed
    from the full distinct set's sub-2^64/MARKER_C slice, which is a
    subset of any c <= MARKER_C selection, so screening semantics are
    unchanged.
    """
    _check_subsample(subsample_c)  # fail before any device hashing
    if _c_profile_available(k):
        return _profile_via_c(genome, k, fraglen, subsample_c,
                              algo=hash_algorithm)
    return _profile_from_flat(
        genome.path, positional_hashes(genome, k, algo=hash_algorithm),
        k, fraglen, subsample_c)


def build_profiles_batch(genomes, k: int, fraglen: int,
                         subsample_c: int = 1,
                         hash_algorithm: str = "murmur3") -> list:
    """Batch twin of build_profile: one hash dispatch per genome group
    instead of per genome (reference analog: skani's fastx_to_sketches
    over all files, src/skani.rs:46)."""
    _check_subsample(subsample_c)  # fail before any device hashing
    if genomes and _c_profile_available(k):
        # With the C walker, per-genome single-pass builds beat device
        # batch grouping.
        return [_profile_via_c(g, k, fraglen, subsample_c,
                               algo=hash_algorithm)
                for g in genomes]
    flats = positional_hashes_batch(genomes, k, algo=hash_algorithm)
    return [
        _profile_from_flat(g.path, flat, k, fraglen, subsample_c)
        for g, flat in zip(genomes, flats)
    ]


def _bucket_pow2(n: int, floor: int = 1 << 12) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def pad_ref_set(ref_set: np.ndarray) -> np.ndarray:
    h = _bucket_pow2(max(ref_set.shape[0], 1))
    out = np.full(h, np.uint64(SENTINEL), dtype=np.uint64)
    out[: ref_set.shape[0]] = ref_set
    return out


def pad_windows(wins: np.ndarray, quantum: int = 64) -> np.ndarray:
    w = -(-wins.shape[0] // quantum) * quantum
    out = np.full((w, wins.shape[1]), np.uint64(SENTINEL), dtype=np.uint64)
    out[: wins.shape[0]] = wins
    return out


def _window_match_counts_impl(
    windows: jax.Array,   # uint64 (W, L), SENTINEL-masked
    ref_set: jax.Array,   # uint64 (H,) sorted, SENTINEL-padded
) -> Tuple[jax.Array, jax.Array]:
    """Per-window (matched k-mers, valid k-mers) against the ref set."""
    w, length = windows.shape
    q = windows.reshape(-1)
    valid = q != hashing.HASH_SENTINEL
    pos = jnp.searchsorted(ref_set, q)
    hit = jnp.take(ref_set, jnp.minimum(pos, ref_set.shape[0] - 1)) == q
    hit = hit & valid
    matched = jnp.sum(hit.reshape(w, length).astype(jnp.int32), axis=1)
    total = jnp.sum(valid.reshape(w, length).astype(jnp.int32), axis=1)
    return matched, total


_window_match_counts = jax.jit(_window_match_counts_impl)

# Batched twin: (B, W, L) windows x (B, H) ref sets -> (B, W) counts.
# One dispatch covers every directed query in a same-shape bucket.
_window_match_counts_batched = jax.jit(jax.vmap(_window_match_counts_impl))

# Memory cap for one batched dispatch: B * W * L uint64 elements.
_BATCH_ELEM_CAP = 32 << 20  # 256 MiB of window data per dispatch


@dataclasses.dataclass
class DirectedANI:
    ani: float               # mean identity over aligned windows (fraction)
    aligned_fraction: float  # aligned windows / valid windows
    frags_matching: int
    frags_total: int


# Fraction of a window's k-mer slots that must be valid for it to
# count as a fragment — shared by every ANI entry point so the
# per-pair and batched-array paths cannot drift apart.
DEFAULT_MIN_WINDOW_VALID_FRAC = 0.5


def directed_ani(
    query: GenomeProfile,
    ref: GenomeProfile,
    identity_floor: float = 0.80,
    min_window_valid_frac: float = DEFAULT_MIN_WINDOW_VALID_FRAC,
) -> DirectedANI:
    """One-way fragment ANI of `query` against `ref` (device dispatch).

    A window counts as a fragment iff at least `min_window_valid_frac` of
    its k-mer slots are valid (unambiguous, within one contig); it counts
    as ALIGNED iff its matched fraction implies identity >=
    `identity_floor` (c_w >= identity_floor^k).
    """
    matched, total = _window_match_counts(
        query.device_windows(), ref.device_ref_set())
    return _directed_from_counts(
        np.asarray(matched), np.asarray(total), query,
        identity_floor, min_window_valid_frac)


def _seq_sum(a: np.ndarray) -> float:
    """f64 sum in np.add.reduceat's order over a COMPRESSED array.

    reduceat's pairwise blocking is a function of the summed run's
    length, so the only way two code paths produce bit-identical sums
    is to hand reduceat the same element run: masked windows must be
    compressed OUT (a[mask]), never zero-filled in place — interleaved
    +0.0 terms shift the pairwise block boundaries and can move the
    total a ulp. The batched twin (_directed_from_counts_arrays)
    reduces each pair's compressed segment with reduceat at the
    compressed starts (_segment_compressed_sums), which is
    bit-identical to this call on the segment alone (reduceat's
    blocking does not depend on the segment's offset)."""
    if a.shape[0] == 0:
        return 0.0
    return float(np.add.reduceat(a, np.zeros(1, dtype=np.intp))[0])


def _segment_compressed_sums(
    values: np.ndarray,   # (W_total,) f64
    mask: np.ndarray,     # (W_total,) bool — which entries count
    starts: np.ndarray,   # (n_segs,) segment starts into values
) -> "Tuple[np.ndarray, np.ndarray]":
    """Per-segment (sum of values[mask], count of mask) — each sum
    bit-identical to _seq_sum over that segment's compressed slice.

    Compresses FIRST, then reduceat at the nonempty segments'
    compressed starts (empty segments occupy zero width in the
    compressed array, so the next nonempty start is exactly this
    segment's end — reduceat's [start_i, start_{i+1}) windows line up
    without materializing per-segment ends, and its empty-segment wart
    — a zero-width window yields a[start], not 0 — never arises)."""
    n = starts.shape[0]
    sums = np.zeros(n, dtype=np.float64)
    idx = np.flatnonzero(mask)
    counts = (np.searchsorted(idx, np.append(starts[1:],
                                             values.shape[0]))
              - np.searchsorted(idx, starts))
    if idx.size == 0:
        return sums, counts
    comp = values[idx]
    cstarts = np.searchsorted(idx, starts)
    nonempty = np.flatnonzero(counts > 0)
    sums[nonempty] = np.add.reduceat(
        comp, cstarts[nonempty].astype(np.intp))
    return sums, counts


def _directed_from_counts(
    matched: np.ndarray,
    total: np.ndarray,
    query: GenomeProfile,
    identity_floor: float,
    min_window_valid_frac: float,
) -> DirectedANI:
    """Host post-processing from per-window (matched, valid) counts."""
    k = query.k
    matched = matched.astype(np.float64)
    total = total.astype(np.float64)

    # expected k-mer slots per window shrink by the FracMinHash factor
    min_valid = (min_window_valid_frac * (query.fraglen - k + 1)
                 / query.subsample_c)
    frag_ok = total >= max(min_valid, 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        c_w = np.where(frag_ok, matched / np.maximum(total, 1.0), 0.0)
    c_floor = identity_floor ** k
    aligned = frag_ok & (c_w >= c_floor)

    frags_total = int(frag_ok.sum())
    frags_matching = int(aligned.sum())
    if frags_matching == 0:
        return DirectedANI(0.0, 0.0, 0, frags_total)

    # Background correction: unaligned windows measure the random k-mer
    # collision rate against this reference set (repeats, chance hits);
    # subtracting it from aligned windows' matched fraction removes the
    # upward bias before inverting the k-mer survival model.
    below = frag_ok & ~aligned
    r_est = (_seq_sum(c_w[below]) / int(below.sum())
             if below.any() else 0.0)
    c_adj = np.clip((c_w[aligned] - r_est) / max(1.0 - r_est, 1e-9),
                    1e-12, 1.0)
    identity = c_adj ** (1.0 / k)
    ani = _seq_sum(identity) / frags_matching
    af = frags_matching / max(frags_total, 1)
    return DirectedANI(ani, af, frags_matching, frags_total)


def _directed_from_counts_arrays(
    matched_cat: np.ndarray,   # (W_total,) int32, segments per pair
    total_cat: np.ndarray,     # (W_total,) int32, aligned to matched
    starts: np.ndarray,        # (n_pairs,) int64 segment starts
    k: int,
    fraglen: int,
    subsample_c: int,
    identity_floor: float,
    min_window_valid_frac: float,
):
    """Vectorized batch twin of _directed_from_counts over concatenated
    per-pair window segments — bit-identical floats: every f64
    reduction compresses masked windows out and reduceats the same
    element run the per-pair path's _seq_sum consumes (see
    _segment_compressed_sums; zero-filling masked slots instead would
    shift reduceat's pairwise block boundaries and drift a ulp).

    Returns (ani, af, frags_matching, frags_total) arrays, one entry
    per pair."""
    matched = matched_cat.astype(np.float64)
    total = total_cat.astype(np.float64)
    starts = np.ascontiguousarray(starts, dtype=np.intp)

    min_valid = (min_window_valid_frac * (fraglen - k + 1)
                 / subsample_c)
    frag_ok = total >= max(min_valid, 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        c_w = np.where(frag_ok, matched / np.maximum(total, 1.0), 0.0)
    c_floor = identity_floor ** k
    aligned = frag_ok & (c_w >= c_floor)

    frags_total = np.add.reduceat(
        frag_ok.astype(np.int64), starts)
    frags_matching = np.add.reduceat(
        aligned.astype(np.int64), starts)

    below = frag_ok & ~aligned
    sum_below, cnt_below = _segment_compressed_sums(c_w, below, starts)
    r_est = np.where(cnt_below > 0,
                     sum_below / np.maximum(cnt_below, 1), 0.0)

    seg_lens = np.diff(np.append(starts, matched.shape[0]))
    r_w = np.repeat(r_est, seg_lens)
    c_adj = np.clip((c_w - r_w) / np.maximum(1.0 - r_w, 1e-9),
                    1e-12, 1.0)
    # the power is elementwise (position-independent), so raising the
    # full array then compressing matches the per-pair compressed pow
    sum_id, _ = _segment_compressed_sums(c_adj ** (1.0 / k), aligned,
                                         starts)

    has = frags_matching > 0
    ani = np.where(has, sum_id / np.maximum(frags_matching, 1), 0.0)
    af = np.where(
        has,
        frags_matching / np.maximum(frags_total, 1).astype(np.float64),
        0.0)
    return ani, af, frags_matching, frags_total


# Window elements per batched-merge chunk: bounds the concatenated
# matched/total scratch (~26 B/window across the f64 temporaries) to
# ~200 MB while keeping chunks big enough to amortize the C call.
_MERGE_BATCH_WINDOW_CAP = 8 << 20

# Hard cap on the batched path's per-genome concatenation (qh/qw/ref
# elements across unique profiles, ~28 B/element): ~1.8 GB. Above it
# the per-pair loop runs instead — by then pairs-per-genome is low
# (the cap is only reachable with many LARGE genomes, where the
# screen keeps the pair list sparse and per-pair overhead is noise).
_MERGE_BATCH_CONCAT_CAP = 64 << 20


def _batch_path_worthwhile(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
) -> bool:
    """Whether the batched C path pays for its concatenation: enough
    pairs to amortize the setup (>= 64) and a bounded concat volume.
    The estimate mirrors _directed_ani_arrays_c's actual layout — a
    genome contributes its query-role arrays AND its ref-role set
    when it appears in both roles (the bidirectional case always has
    both). Expected survivor counts (flat length / subsample) stand
    in for len(sorted_query()) so no profile arrays are materialized
    early."""
    if len(queries) < 64:
        return False
    seen_q: "set[int]" = set()
    seen_r: "set[int]" = set()
    est = 0
    for q, r in queries:
        if id(q) not in seen_q:
            seen_q.add(id(q))
            est += q.flat_hashes.shape[0] // max(q.subsample_c, 1)
        if id(r) not in seen_r:
            seen_r.add(id(r))
            est += r.ref_set.shape[0]
    return est <= _MERGE_BATCH_CONCAT_CAP


def _directed_ani_batch_c(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
    identity_floor: float,
    min_window_valid_frac: float,
    threads: int,
) -> "list[DirectedANI]":
    """Boxed twin of _directed_ani_arrays_c (same arrays, DirectedANI
    objects out) — the directed_ani_batch fast path."""
    ani, af, fm, ft = _directed_ani_arrays_c(
        queries, identity_floor, min_window_valid_frac, threads)
    return [DirectedANI(float(ani[i]), float(af[i]),
                        int(fm[i]), int(ft[i]))
            for i in range(len(queries))]


def _directed_ani_arrays_c(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
    identity_floor: float,
    min_window_valid_frac: float,
    threads: int,
):
    """Batched CPU exact-ANI: per-pair merges in ONE threaded C call
    per chunk (csrc/pairstats.c::galah_window_match_counts_merge_batch)
    plus vectorized post-math, returning per-pair (ani, af,
    frags_matching, frags_total) ARRAYS — the per-pair Python loop
    costs ~100x the O(nq + H) merge itself at small-genome sizes,
    which is the entire wall of the dense-similarity mega-family
    regime (reference analog: one skani
    call per screened pair, src/skani.rs:85-104)."""
    from galah_tpu.ops._cpairstats import window_match_counts_merge_batch

    q0 = queries[0][0]
    k, fraglen, subsample_c = q0.k, q0.fraglen, q0.subsample_c

    # Unique profiles by object identity; sorted_query/ref_set are
    # cached per profile, so concatenation cost is one copy.
    q_idx: "dict[int, int]" = {}
    r_idx: "dict[int, int]" = {}
    q_profiles: "list[GenomeProfile]" = []
    r_profiles: "list[GenomeProfile]" = []
    pair_q = np.empty(len(queries), dtype=np.int32)
    pair_r = np.empty(len(queries), dtype=np.int32)
    for n, (q, r) in enumerate(queries):
        qi = q_idx.setdefault(id(q), len(q_profiles))
        if qi == len(q_profiles):
            q_profiles.append(q)
        ri = r_idx.setdefault(id(r), len(r_profiles))
        if ri == len(r_profiles):
            r_profiles.append(r)
        pair_q[n] = qi
        pair_r[n] = ri

    _warm_sorted_queries(queries, threads)
    qh_parts, qw_parts, tot_parts = [], [], []
    for q in q_profiles:
        qh, qw, totals = q.sorted_query()
        qh_parts.append(qh)
        qw_parts.append(qw)
        tot_parts.append(totals)
    q_off = np.zeros(len(q_profiles) + 1, dtype=np.int64)
    np.cumsum([p.shape[0] for p in qh_parts], out=q_off[1:])
    tot_off = np.zeros(len(q_profiles) + 1, dtype=np.int64)
    np.cumsum([t.shape[0] for t in tot_parts], out=tot_off[1:])
    qh_cat = (np.concatenate(qh_parts) if qh_parts
              else np.zeros(0, dtype=np.uint64))
    qw_cat = (np.concatenate(qw_parts) if qw_parts
              else np.zeros(0, dtype=np.int32))
    tot_cat = (np.concatenate(tot_parts) if tot_parts
               else np.zeros(0, dtype=np.int32))
    n_win = np.asarray([t.shape[0] for t in tot_parts], dtype=np.int64)

    r_off = np.zeros(len(r_profiles) + 1, dtype=np.int64)
    np.cumsum([p.ref_set.shape[0] for p in r_profiles], out=r_off[1:])
    ref_cat = (np.concatenate([p.ref_set for p in r_profiles])
               if r_profiles else np.zeros(0, dtype=np.uint64))

    out_ani = np.zeros(len(queries), dtype=np.float64)
    out_af = np.zeros(len(queries), dtype=np.float64)
    out_fm = np.zeros(len(queries), dtype=np.int64)
    out_ft = np.zeros(len(queries), dtype=np.int64)
    pair_wins = n_win[pair_q]
    # zero-window queries never enter the C kernel (reduceat cannot
    # represent empty segments); their result is the all-zero row the
    # outputs are initialized to
    live = np.nonzero(pair_wins != 0)[0]

    pos = 0
    while pos < live.shape[0]:
        # chunk by total window volume
        end = pos
        vol = 0
        while end < live.shape[0] and (vol == 0
                                       or vol + pair_wins[live[end]]
                                       <= _MERGE_BATCH_WINDOW_CAP):
            vol += int(pair_wins[live[end]])
            end += 1
        chunk = live[pos:end]
        pos = end

        cw = pair_wins[chunk]
        m_off = np.zeros(chunk.shape[0], dtype=np.int64)
        np.cumsum(cw[:-1], out=m_off[1:])
        total_windows = int(cw.sum())
        matched_cat = window_match_counts_merge_batch(
            qh_cat, qw_cat, q_off, ref_cat, r_off,
            pair_q[chunk], pair_r[chunk], m_off, total_windows,
            threads=max(1, threads))
        # gather each pair's per-window valid counts
        within = np.arange(total_windows, dtype=np.int64) \
            - np.repeat(m_off, cw)
        tidx = np.repeat(tot_off[pair_q[chunk]], cw) + within
        total_cat = tot_cat[tidx]

        ani, af, fm, ft = _directed_from_counts_arrays(
            matched_cat, total_cat, m_off, k, fraglen, subsample_c,
            identity_floor, min_window_valid_frac)
        out_ani[chunk] = ani
        out_af[chunk] = af
        out_fm[chunk] = fm
        out_ft[chunk] = ft
    return out_ani, out_af, out_fm, out_ft


# Fragment-ANI membership strategies (GALAH_TPU_FRAGMENT_STRATEGY to
# pin; unset/"auto" resolves per backend):
#   pallas — ops/pallas_fragment.py's blocked multi-pair Mosaic kernel
#            (interpret-mode on non-TPU backends, so parity tests can
#            pin it on CPU)
#   xla    — the vmapped searchsorted dispatch path
#   c      — csrc/pairstats.c's merge membership counter (host)
FRAGMENT_STRATEGIES = ("pallas", "xla", "c")


def _c_merge_available() -> bool:
    try:
        from galah_tpu.ops import _cpairstats
    except Exception:  # pragma: no cover - import error == no C
        return False
    return hasattr(_cpairstats, "window_match_counts_merge")


def _warm_sorted_queries(queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
                         threads: int) -> None:
    """Build each distinct query profile's sorted_query cache, on up to
    `threads` pool workers: the C build (~0.1 s for a 3 Mbp genome on
    one x86 core; ~0.8-1.0 s for the numpy twin's stable argsort)
    releases the GIL, and a catalogue's exact-ANI pass needs one per
    genome before its first pair. Span: `fragment-ani.sort-queries`."""
    todo = [q for q in {id(q): q for q, _ in queries}.values()
            if q._np_sorted_query is None]
    with timing.stage("fragment-ani.sort-queries"):
        if threads > 1 and len(todo) > 1:
            from galah_tpu.io.prefetch import _shared_pool

            list(_shared_pool(threads).map(GenomeProfile.sorted_query,
                                           todo))
        for q in todo:
            q.sorted_query()


def _resolve_fragment_strategy(
    backend: "Optional[str]" = None,
    n_devices: "Optional[int]" = None,
    c_ok: "Optional[bool]" = None,
) -> "Tuple[str, bool]":
    """(strategy, explicit) for the exact-ANI membership stage.

    An explicit GALAH_TPU_FRAGMENT_STRATEGY pin always wins (and its
    failures propagate — parity runs must never silently compare a
    fallback to itself). AUTO mirrors the historical defaults: the
    single-core C merge on a single-device CPU runtime (it beat the
    XLA-CPU searchsorted by avoiding padding entirely), the Mosaic
    kernel on a real TPU backend, the vmapped XLA path everywhere
    else (notably multi-device CPU meshes, whose sharded batch path
    the C merge cannot use). The injectable parameters exist for
    selection tests; production callers pass nothing.
    """
    env = (os.environ.get("GALAH_TPU_FRAGMENT_STRATEGY") or "").lower()
    if env in FRAGMENT_STRATEGIES:
        return env, True
    backend = jax.default_backend() if backend is None else backend
    n_devices = jax.device_count() if n_devices is None else n_devices
    if c_ok is None:
        c_ok = _c_merge_available()
    if backend == "cpu" and n_devices == 1 and c_ok:
        return "c", False
    from galah_tpu.ops.hll import use_pallas_default

    if backend == "tpu" and use_pallas_default():
        return "pallas", False
    return "xla", False


def directed_ani_batch(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
    identity_floor: float = 0.80,
    min_window_valid_frac: float = DEFAULT_MIN_WINDOW_VALID_FRAC,
    threads: int = 1,
) -> "list[DirectedANI]":
    """Directed fragment ANI for many (query, ref) pairs, coalescing
    device dispatches.

    The membership stage runs under the resolved fragment strategy
    (see _resolve_fragment_strategy): the C merge path consumes cached
    sorted queries with no padding; the XLA and Pallas paths group
    queries by their padded (W, L, H) shape bucket so a handful of
    kernel variants cover any genome collection. Results are
    bit-identical across all three (the per-window integers are exact
    and the f64 reduction is shared). This is the framework's answer
    to the reference's one-subprocess-per-pair fastANI calls
    (reference: src/fastani.rs:88-105) — and the reason the engine's
    backend interface is batched (see backends/base.py).
    """
    if not queries:
        return []
    strategy, _explicit = _resolve_fragment_strategy()
    timing.counter(f"fragment-strategy-{strategy}", 1)
    timing.counter("fragment-ani-directions", len(queries))
    if strategy == "c":
        return _directed_ani_batch_cmerge(
            queries, identity_floor, min_window_valid_frac, threads)
    if strategy == "pallas":
        return _directed_ani_batch_pallas(
            queries, identity_floor, min_window_valid_frac, threads)
    return _directed_ani_batch_xla(
        queries, identity_floor, min_window_valid_frac)


def _directed_ani_batch_cmerge(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
    identity_floor: float,
    min_window_valid_frac: float,
    threads: int,
) -> "list[DirectedANI]":
    """The compiled-C merge membership strategy (csrc/pairstats.c::
    galah_window_match_counts_merge — O(nq + H) per pair on the
    profile's cached sorted query, vs the matrix walker's
    O(slots * log H) binary searches); a host path, no device work.
    ImportError propagates: AUTO only resolves here when the extension
    probe passed, so reaching it without the toolchain means an
    explicit pin — which must fail loudly."""
    from galah_tpu.ops._cpairstats import window_match_counts_merge

    # Large pair lists (the dense-similarity regime can carry
    # N^2/2 screened pairs) take the fully batched path: ONE
    # threaded C call per chunk for the merges and vectorized
    # host post-math — bit-identical DirectedANI floats to the
    # per-pair loop below (see _directed_from_counts_arrays).
    if _batch_path_worthwhile(queries):
        uniform = len({(q.k, q.fraglen, q.subsample_c)
                       for q, _ in queries}) == 1
        if uniform:
            return _directed_ani_batch_c(
                queries, identity_floor, min_window_valid_frac,
                threads)

    def one(pair):
        q, r = pair
        qh, qw, totals = q.sorted_query()
        matched = window_match_counts_merge(
            qh, qw, q.n_windows, r.ref_set, validate=False)
        return _directed_from_counts(
            matched, totals, q, identity_floor,
            min_window_valid_frac)

    if threads > 1 and len(queries) > 1:
        # pairs are independent and the merge releases the GIL
        # (ctypes) — honor the threads knob across pairs. Warm
        # each unique query's sorted_query cache first so the
        # first wave of threads doesn't build it redundantly
        # (one candidate vs many refs is the common shape).
        from galah_tpu.io.prefetch import _shared_pool

        _warm_sorted_queries(queries, threads)
        # The shared pool is sized to the LARGEST worker count
        # ever requested in-process; keep at most `threads`
        # futures outstanding so a smaller knob here still
        # bounds concurrency to what the user asked for, and
        # refill on EACH completion (not in waves — pair costs
        # are heterogeneous, one big query vs many small refs
        # is the common shape).
        from concurrent.futures import FIRST_COMPLETED, wait

        pool = _shared_pool(threads)
        out: "list[Optional[DirectedANI]]" = [None] * len(queries)
        it = iter(enumerate(queries))
        pending = {}

        def submit_next() -> bool:
            try:
                i, pair = next(it)
            except StopIteration:
                return False
            pending[pool.submit(one, pair)] = i
            return True

        for _ in range(threads):
            if not submit_next():
                break
        while pending:
            done, _ = wait(pending, return_when=FIRST_COMPLETED)
            for f in done:
                out[pending.pop(f)] = f.result()
                submit_next()
        return out  # type: ignore[return-value]
    return [one(pair) for pair in queries]


def _directed_ani_batch_xla(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
    identity_floor: float,
    min_window_valid_frac: float,
) -> "list[DirectedANI]":
    """The vmapped-searchsorted strategy: queries grouped by padded
    shape bucket, each bucket dispatched in chunks of at most
    _BATCH_ELEM_CAP window elements (multi-device runtimes shard the
    batch dim over the host-local mesh). Bit-identical to per-pair
    `directed_ani` — the vmap computes the same per-row searchsorted;
    only the dispatch granularity changes."""
    out: "list[Optional[DirectedANI]]" = [None] * len(queries)
    groups: "dict[tuple, list[int]]" = {}
    for n, (q, r) in enumerate(queries):
        # padded host shapes only — no device upload during grouping
        key = (q.padded_windows().shape, r.padded_ref_set().shape[0])
        groups.setdefault(key, []).append(n)

    n_dev = len(jax.local_devices())  # host-local (see _shard_batch)
    for (wshape, _h), idxs in groups.items():
        per_query_elems = wshape[0] * wshape[1]
        b_max = max(1, _BATCH_ELEM_CAP // max(per_query_elems, 1))
        for start in range(0, len(idxs), b_max):
            chunk = idxs[start:start + b_max]
            timing.dispatch()
            timing.dispatch(sync=True)
            if len(chunk) == 1:
                n = chunk[0]
                q, r = queries[n]
                matched, total = _window_match_counts(
                    q.device_windows(), r.device_ref_set())
                mt = [(matched, total)]
            else:
                if n_dev > 1:
                    # Shard the batch over the mesh: the vmapped
                    # membership test is embarrassingly parallel per
                    # directed query, so a batch-dim sharding turns one
                    # dispatch into n_dev-way data parallelism. Staged
                    # through host numpy so padding never materializes
                    # a super-cap array on one device.
                    wins, refs = _shard_batch(
                        [queries[n] for n in chunk], n_dev)
                else:
                    wins = jnp.stack(
                        [queries[n][0].device_windows() for n in chunk])
                    refs = jnp.stack(
                        [queries[n][1].device_ref_set() for n in chunk])
                m_b, t_b = _window_match_counts_batched(wins, refs)
                mt = [(m_b[i], t_b[i]) for i in range(len(chunk))]
            for n, (m, t) in zip(chunk, mt):
                out[n] = _directed_from_counts(
                    np.asarray(m), np.asarray(t), queries[n][0],
                    identity_floor, min_window_valid_frac)
    return out  # type: ignore[return-value]


def _directed_ani_batch_pallas(
    queries: "list[Tuple[GenomeProfile, GenomeProfile]]",
    identity_floor: float,
    min_window_valid_frac: float,
    threads: int = 1,
) -> "list[DirectedANI]":
    """The Mosaic strategy (ops/pallas_fragment.py): one launch per
    directed pair on device-resident hash planes, a few in flight.
    Per-ELEMENT membership flags come back host-side; one bincount per
    pair folds them into the same per-window matched counts the other
    strategies produce while the next launches run, and the shared
    _directed_from_counts_arrays reduction keeps the DirectedANI
    floats bit-identical."""
    from galah_tpu.ops import pallas_fragment

    # interpret-mode on non-TPU backends: parity tests pin the
    # strategy on CPU; a real TPU lowers through Mosaic
    interpret = jax.default_backend() != "tpu"
    _warm_sorted_queries(queries, threads)
    out: "list[Optional[DirectedANI]]" = [None] * len(queries)
    groups: "dict[tuple, list[int]]" = {}
    for n, (q, _r) in enumerate(queries):
        groups.setdefault((q.k, q.fraglen, q.subsample_c), []).append(n)

    for (k, fraglen, subsample_c), idxs in groups.items():
        with timing.stage("fragment-ani.plan"):
            # pairs sharing a reference run back to back, so its planes
            # stay resident while its queries stream past; the query
            # order reverses from one reference to the next, so the
            # queries the cache still holds come first
            by_ref: "dict[int, list[int]]" = {}
            for n in idxs:
                by_ref.setdefault(id(queries[n][1]), []).append(n)
            idxs = []
            for g, group in enumerate(by_ref.values()):
                group.sort(key=lambda n: id(queries[n][0]),
                           reverse=g % 2 == 1)
                idxs += group
            items = [(queries[n][0].sorted_query()[0],
                      queries[n][1].ref_set) for n in idxs]
        matched_by_item = {}
        # the pipeline's self time is its own bookkeeping between the
        # launch, fetch and fold spans
        with timing.stage("fragment-ani.pipeline"):
            for j, flags in pallas_fragment.iter_element_hits(
                    items, interpret=interpret):
                _qh, qw, totals = queries[idxs[j]][0].sorted_query()
                if not flags.shape[0]:
                    # an empty query matches in no window
                    matched_by_item[j] = np.zeros(totals.shape[0],
                                                  np.int32)
                    continue
                with timing.stage("fragment-ani.fold", {"direction": j}):
                    matched_by_item[j] = _fold_flags(flags, qw,
                                                     totals.shape[0])

        with timing.stage("fragment-ani.collect"):
            matched_parts, total_parts, starts, live = [], [], [], []
            seg = 0
            for j, n in enumerate(idxs):
                q, _r = queries[n]
                _qh, qw, totals = q.sorted_query()
                w = totals.shape[0]
                if w == 0:
                    # reduceat cannot represent empty segments; the
                    # zero-window result is all-zero by definition
                    out[n] = DirectedANI(0.0, 0.0, 0, 0)
                    continue
                matched_parts.append(matched_by_item[j])
                total_parts.append(totals)
                starts.append(seg)
                seg += w
                live.append(n)
            if not live:
                continue
            with timing.stage("fragment-ani.reduce"):
                ani, af, fm, ft = _directed_from_counts_arrays(
                    np.concatenate(matched_parts),
                    np.concatenate(total_parts),
                    np.asarray(starts, dtype=np.int64), k, fraglen,
                    subsample_c, identity_floor, min_window_valid_frac)
            for i, n in enumerate(live):
                out[n] = DirectedANI(float(ani[i]), float(af[i]),
                                     int(fm[i]), int(ft[i]))
    return out  # type: ignore[return-value]


def _fold_flags(flags: np.ndarray, qw: np.ndarray,
                n_windows: int) -> np.ndarray:
    """Per-window matched counts from a pair's per-element membership
    flags: the compiled-C fold (~10x numpy's bincount at MAG length)
    where the toolchain built it."""
    try:
        from galah_tpu.ops._cpairstats import fold_window_flags
    except ImportError:
        return np.bincount(qw[flags != 0],
                           minlength=n_windows).astype(np.int32)
    return fold_window_flags(flags, qw, n_windows)


def _shard_batch(pairs: "list[Tuple[GenomeProfile, GenomeProfile]]",
                 n_dev: int):
    """Batch-dim-sharded (wins, refs) device arrays for (query, ref)
    pairs, padded to a mesh multiple (padding repeats the first pair;
    callers index only the real rows).

    The padded batch is assembled in host numpy and device_put straight
    into its sharded layout, so each device only ever holds its own
    shard — never the whole super-capacity batch.

    The mesh is HOST-LOCAL (jax.local_devices()): on a multi-host
    runtime each process batches its own (possibly host-divergent)
    pair work — a global sharding would demand identical values on
    every process, which the host-sharded exact-ANI split
    (backends/fragment_backend._exact_ani_multihost) deliberately
    violates. Single-process behavior is identical.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    b = len(pairs)
    b_pad = -(-b // n_dev) * n_dev
    padded = pairs + [pairs[0]] * (b_pad - b)
    wins_np = np.stack([q.padded_windows() for q, _ in padded])
    refs_np = np.stack([r.padded_ref_set() for _, r in padded])
    mesh = Mesh(np.array(jax.local_devices()), ("i",))
    wins = jax.device_put(wins_np, NamedSharding(mesh, P("i", None, None)))
    refs = jax.device_put(refs_np, NamedSharding(mesh, P("i", None)))
    return wins, refs


def _check_same_subsample(a: GenomeProfile, b: GenomeProfile) -> None:
    """Profiles built at different FracMinHash cuts are incomparable —
    a query filtered at one cut can never match a reference filtered at
    another, silently collapsing ANI to nothing."""
    if a.subsample_c != b.subsample_c:
        raise ValueError(
            f"GenomeProfiles built with different subsample_c "
            f"({a.subsample_c} vs {b.subsample_c}) cannot be compared")


def bidirectional_ani_batch(
    pairs: "list[Tuple[GenomeProfile, GenomeProfile]]",
    min_aligned_frac: float,
    identity_floor: float = 0.80,
    threads: int = 1,
) -> "list[Tuple[Optional[float], DirectedANI, DirectedANI]]":
    """Batched twin of `bidirectional_ani`: both directions of every pair
    go through one `directed_ani_batch` call; the gate/max semantics per
    pair are identical to the scalar path."""
    for a, b in pairs:
        _check_same_subsample(a, b)
    directed = directed_ani_batch(
        [(a, b) for a, b in pairs] + [(b, a) for a, b in pairs],
        identity_floor=identity_floor, threads=threads)
    n = len(pairs)
    with timing.stage("fragment-ani.combine"):
        return [(_combine_bidirectional(directed[i], directed[n + i],
                                        min_aligned_frac),
                 directed[i], directed[n + i]) for i in range(n)]


def bidirectional_ani_values(
    pairs: "list[Tuple[GenomeProfile, GenomeProfile]]",
    min_aligned_frac: float,
    identity_floor: float = 0.80,
    threads: int = 1,
) -> "list[Optional[float]]":
    """ANI values only — `[ani for ani, _, _ in
    bidirectional_ani_batch(...)]` with the DirectedANI boxing removed
    on the batched-C path (at mega-pair volumes the 2x-per-pair object
    construction and per-pair gate loop dominate the exact math;
    identical Nones/floats either way — the gate arithmetic is the
    same f64 ops _combine_bidirectional runs on ints)."""
    n = len(pairs)
    # Gate exactly as the fallback's inner directed_ani_batch would on
    # the doubled directed list — but WITHOUT materializing that list
    # (2n tuples is hundreds of MB at mega-pair volumes) unless the
    # arrays path is actually taken: in the bidirectional list every
    # genome appears in both roles, so the concat estimate is each
    # unique genome's query-role plus ref-role contribution.
    seen: "set[int]" = set()
    est = 0
    for a, b in pairs:
        for p in (a, b):
            if id(p) not in seen:
                seen.add(id(p))
                est += (p.flat_hashes.shape[0]
                        // max(p.subsample_c, 1))
                est += p.ref_set.shape[0]
    # the boxing-free shortcut only exists for the C merge strategy;
    # pallas/xla resolve to the fallback below, whose inner
    # directed_ani_batch re-resolves and routes accordingly (AUTO only
    # returns "c" when the extension probe passed; an explicit c pin
    # without the toolchain fails loudly inside the arrays path)
    strategy, _explicit = _resolve_fragment_strategy()
    use_arrays = (
        strategy == "c"
        and 2 * n >= 64 and est <= _MERGE_BATCH_CONCAT_CAP
        and len({(p.k, p.fraglen, p.subsample_c)
                 for pair in pairs for p in pair}) == 1)
    if not use_arrays:
        return [ani for ani, _, _ in bidirectional_ani_batch(
            pairs, min_aligned_frac, identity_floor=identity_floor,
            threads=threads)]

    directed = [(a, b) for a, b in pairs] + [(b, a) for a, b in pairs]
    timing.counter("fragment-ani-directions", len(directed))
    ani, _af, fm, ft = _directed_ani_arrays_c(
        directed, identity_floor, DEFAULT_MIN_WINDOW_VALID_FRAC,
        threads)
    ab, ba = slice(0, n), slice(n, 2 * n)
    gate = (
        ((ft[ab] > 0)
         & (fm[ab] / np.maximum(ft[ab], 1) >= min_aligned_frac))
        | ((ft[ba] > 0)
           & (fm[ba] / np.maximum(ft[ba], 1) >= min_aligned_frac)))
    has = (fm[ab] > 0) | (fm[ba] > 0)
    keep = gate & has
    val = np.maximum(ani[ab], ani[ba])
    af_ab = fm[ab] / np.maximum(ft[ab], 1)
    af_ba = fm[ba] / np.maximum(ft[ba], 1)
    hazard = keep & _repeat_hazard_mask(af_ab, af_ba, min_aligned_frac)
    if hazard.any():
        i = int(np.flatnonzero(hazard)[0])
        _warn_repeat_merge_hazard(
            int(hazard.sum()), float(max(af_ab[i], af_ba[i])),
            float(min(af_ab[i], af_ba[i])), min_aligned_frac)
    return [float(v) if k_ else None
            for v, k_ in zip(val.tolist(), keep.tolist())]


# Repeat-merge hazard signature (tests/test_repeat_regime.py): the
# gate passes on an aligned fraction that is both MARGINAL (below
# margin x threshold) and ASYMMETRIC (the other direction far lower).
# Genome-wide relatedness aligns a similar fraction in both directions;
# shared repeats/mobile elements align a sliver of each genome and the
# slivers differ with genome size — exactly this shape.
_HAZARD_AF_MARGIN = 2.0
_HAZARD_ASYMMETRY = 3.0


def _repeat_hazard_mask(af_ab, af_ba, min_aligned_frac: float):
    """Vectorized hazard test on aligned-fraction pairs that already
    passed the gate: marginal pass + strong directional asymmetry."""
    hi = np.maximum(af_ab, af_ba)
    lo = np.minimum(af_ab, af_ba)
    return ((hi < _HAZARD_AF_MARGIN * min_aligned_frac)
            & (hi >= _HAZARD_ASYMMETRY * lo))


def _warn_repeat_merge_hazard(count: int, af_hi: float, af_lo: float,
                              min_aligned_frac: float) -> None:
    warnings.warn(
        f"{count} pair(s) passed the aligned-fraction gate marginally "
        f"and asymmetrically (e.g. {af_hi:.3f} vs {af_lo:.3f} against "
        f"threshold {min_aligned_frac:.3f}) — the signature of shared "
        "repeats/mobile elements rather than genome-wide identity; "
        "the reported ANI is the max over directions and may merge "
        "unrelated genomes. Consider raising --min-aligned-fraction "
        "(see the manpage's 'Repeat-driven merges' note).",
        RuntimeWarning, stacklevel=3)


def _combine_bidirectional(
    ab: DirectedANI, ba: DirectedANI, min_aligned_frac: float
) -> Optional[float]:
    """The reference's fastANI-wrapper gate (reference:
    src/fastani.rs:56-65): pass iff EITHER direction's matched-fragment
    fraction >= min_aligned_frac; result is the max ANI."""
    af_ab = ab.frags_matching / max(ab.frags_total, 1)
    af_ba = ba.frags_matching / max(ba.frags_total, 1)
    gate = ((ab.frags_total > 0 and af_ab >= min_aligned_frac)
            or (ba.frags_total > 0 and af_ba >= min_aligned_frac))
    if not gate or (ab.frags_matching == 0 and ba.frags_matching == 0):
        return None
    if bool(_repeat_hazard_mask(af_ab, af_ba, min_aligned_frac)):
        _warn_repeat_merge_hazard(1, max(af_ab, af_ba),
                                  min(af_ab, af_ba), min_aligned_frac)
    return max(ab.ani, ba.ani)


def bidirectional_ani(
    a: GenomeProfile,
    b: GenomeProfile,
    min_aligned_frac: float,
    identity_floor: float = 0.80,
) -> Tuple[Optional[float], DirectedANI, DirectedANI]:
    """Bidirectional max-ANI with the reference's fragment-fraction gate.

    Mirrors the reference's fastANI wrapper (reference:
    src/fastani.rs:31-73): both directions are computed; the pair passes
    iff EITHER direction's matched-fragment fraction >= min_aligned_frac;
    the returned ANI is the max of the two directions. Returns None (gate
    failed / nothing aligned) plus both directed results for callers that
    need them.
    """
    _check_same_subsample(a, b)
    ab = directed_ani(a, b, identity_floor=identity_floor)
    ba = directed_ani(b, a, identity_floor=identity_floor)
    return _combine_bidirectional(ab, ba, min_aligned_frac), ab, ba
