"""ctypes binding for the native pair-stats kernel (csrc/pairstats.c).

Exposes

    threshold_pairs_c(mat, sketch_size, kmer, min_ani, threads)
        -> {(i, j): ani}

the compiled-C twin of ops/pairwise.threshold_pairs for host CPUs —
same f64 rational keep-check, same Mash ANI values (reference analog:
the compiled pair loop of src/finch.rs:53-73). Build/load failures
raise ImportError (cached by utils/cbuild); set
GALAH_TPU_NO_CPAIRSTATS=1 to force callers' fallbacks.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from galah_tpu.ops.constants import SENTINEL
from galah_tpu.utils import cbuild

_lib = cbuild.build_and_load(
    "pairstats.c", "_libpairstats",
    out_dir=os.path.dirname(os.path.abspath(__file__)),
    extra_flags=("-lpthread", "-lm"),
    disable_env="GALAH_TPU_NO_CPAIRSTATS")
_fn = _lib.galah_pair_stats_threshold
_fn.restype = ctypes.c_int64
_fn.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
    ctypes.c_double, ctypes.c_int,
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
]


_fn_wm = _lib.galah_window_match_counts
_fn_wm.restype = None
_fn_wm.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int,
    ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
]


def window_match_counts(
        wins: np.ndarray, ref_set: np.ndarray,
        threads: int = 1) -> "tuple[np.ndarray, np.ndarray]":
    """Per-window (matched, valid) counts of SENTINEL-masked hash
    windows against a sorted distinct reference set — C twin of
    ops/fragment_ani._window_match_counts_impl, row-parallel over
    `threads`."""
    wins = np.ascontiguousarray(wins, dtype=np.uint64)
    ref_set = np.ascontiguousarray(ref_set, dtype=np.uint64)
    if wins.ndim != 2:
        raise ValueError(
            f"wins must be a (W, L) window matrix, got shape "
            f"{wins.shape}")
    w = wins.shape[0]
    matched = np.empty(w, dtype=np.int32)
    total = np.empty(w, dtype=np.int32)
    _fn_wm(wins.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
           w, wins.shape[1],
           ref_set.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
           ref_set.shape[0], max(int(threads), 1),
           matched.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
           total.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return matched, total


_fn_pl = _lib.galah_pair_stats_for_pairs
_fn_pl.restype = None
_fn_pl.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
    ctypes.c_double, ctypes.c_int, ctypes.POINTER(ctypes.c_double),
]

# Crossover/env policy and the conservative screen live with the
# collision counter; re-exported for existing importers.
from galah_tpu.ops.collision import (  # noqa: E402
    SPARSE_SCREEN_MIN_N,
    candidate_pairs_minhash as _candidate_pairs_sparse,
    sparse_screen_min_n,
)


def threshold_pairs_c(mat: np.ndarray, sketch_size: int, kmer: int,
                      min_ani: float, threads: int = 0,
                      initial_cap: int = 0) -> dict:
    """All-pairs merged-bottom-k Mash ANI at or above min_ani.

    `mat` is the (N, width) uint64 SENTINEL-padded sorted sketch matrix
    (ops/minhash.sketch_matrix layout). The keep decision is the same
    f64 rational check as the device path (common >= j_thr * total with
    j_thr from pairwise.ani_to_jaccard), so both backends agree on
    borderline pairs. Retries with a grown buffer on overflow, so the
    result is always complete (`initial_cap` exists for tests).
    """
    from galah_tpu.ops.pairwise import ani_to_jaccard

    mat = np.ascontiguousarray(mat, dtype=np.uint64)
    n, width = mat.shape
    lens = (mat != np.uint64(SENTINEL)).sum(axis=1).astype(np.int64)
    if threads <= 0:
        threads = os.cpu_count() or 1
    j_thr = ani_to_jaccard(min_ani, kmer)

    if (n >= sparse_screen_min_n()
            and not os.environ.get("GALAH_TPU_DENSE_PAIRS")):
        pi, pj = _candidate_pairs_sparse(mat, lens, j_thr, sketch_size)
        from galah_tpu.utils import timing

        timing.counter("screen-candidates", int(pi.shape[0]))
        timing.counter("screen-possible-pairs", n * (n - 1) // 2)
        out_ani = np.full(pi.shape[0], float("-inf"), dtype=np.float64)
        if pi.shape[0]:
            _fn_pl(
                mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                pi.shape[0], width,
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                np.ascontiguousarray(pi).ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int64)),
                np.ascontiguousarray(pj).ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int64)),
                sketch_size, kmer, float(j_thr), int(threads),
                out_ani.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        kept_n = int((out_ani != float("-inf")).sum())
        timing.counter("screen-kept-pairs", kept_n)
        from galah_tpu.obs import metrics as obs_metrics

        obs_metrics.gauge(
            "screen.survival_rate",
            help="Fraction of screened candidate pairs the threshold "
                 "kept (last screening pass)", unit="fraction").set(
            float(kept_n) / pi.shape[0] if pi.shape[0] else 0.0)
        return {(int(a), int(b)): float(v)
                for a, b, v in zip(pi, pj, out_ani)
                if v != float("-inf")}

    cap = initial_cap if initial_cap > 0 else max(4 * n + 1024, 1 << 16)
    while True:
        out_i = np.empty(cap, dtype=np.int64)
        out_j = np.empty(cap, dtype=np.int64)
        out_ani = np.empty(cap, dtype=np.float64)
        total = _fn(
            mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            n, width,
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            sketch_size, kmer, float(j_thr), int(threads),
            out_i.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out_j.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out_ani.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            cap)
        if total <= cap:
            break
        cap = int(total) + 1024
    m = int(min(total, cap))
    return {(int(out_i[x]), int(out_j[x])): float(out_ani[x])
            for x in range(m)}


_fn_wsc = _lib.galah_window_survivor_counts
_fn_wsc.restype = None
_fn_wsc.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int64, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
]

_fn_fcw = _lib.galah_fill_compact_windows
_fn_fcw.restype = None
_fn_fcw.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
    ctypes.POINTER(ctypes.c_uint64),
]


def compact_windows(flat: np.ndarray, n_windows: int, fraglen: int,
                    k: int) -> np.ndarray:
    """Compacted (W, slots) positional-hash windows from a flat
    SENTINEL-masked array — C twin of the subsample_c > 1 branch of
    fragment_ani.GenomeProfile.windows() (two streaming passes instead
    of a full stable argsort). Bit-identical layout: survivors to the
    front in order, boundary-crossing k-mers dropped, slots = the
    longest row's count rounded up to a multiple of 64 (min 64)."""
    flat = np.ascontiguousarray(flat, dtype=np.uint64)
    if flat.shape[0] > n_windows * fraglen:
        # the numpy twin fails loudly on inconsistent sizing; the C
        # walk would write past counts/wins instead
        raise ValueError(
            f"flat length {flat.shape[0]} exceeds n_windows*fraglen "
            f"{n_windows}*{fraglen}")
    counts = np.empty(max(n_windows, 1), dtype=np.int64)
    _fn_wsc(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        flat.shape[0], n_windows, fraglen, int(k),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    slots = max(int(counts[:n_windows].max()) if n_windows else 1, 1)
    # the numpy twin slices its (W, L) array to `slots` columns, so
    # the effective width can never exceed L
    slots = min(-(-slots // 64) * 64, fraglen)
    wins = np.full((n_windows, slots), np.uint64(SENTINEL),
                   dtype=np.uint64)
    _fn_fcw(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        flat.shape[0], n_windows, fraglen, int(k), slots,
        wins.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return wins


_fn_sqt = _lib.galah_sorted_query_totals
_fn_sqt.restype = ctypes.c_int64
_fn_sqt.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int64, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_uint64),
]

_fn_sq = _lib.galah_sorted_query
_fn_sq.restype = ctypes.c_int
_fn_sq.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int64, ctypes.c_int, ctypes.c_uint64, ctypes.c_int64,
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int32),
]


def sorted_query(flat: np.ndarray, n_windows: int, fraglen: int,
                 k: int) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    """(qh, qw, totals) of a flat SENTINEL-masked positional-hash array
    — C twin of fragment_ani.sorted_query_numpy over the profile's
    windows() (mask, nonzero, stable argsort, gathers), bit-identical:
    the surviving window hashes sorted ascending, equal hashes in
    ascending window order, their window ids, and each window's
    survivor count. One walk for the counts and two for a radix sort,
    with no (W, L) matrix."""
    flat = np.ascontiguousarray(flat, dtype=np.uint64)
    totals = np.empty(n_windows, dtype=np.int32)
    hash_or = ctypes.c_uint64(0)
    flat_p = flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
    nq = _fn_sqt(flat_p, flat.shape[0], n_windows, fraglen, int(k),
                 totals.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                 ctypes.byref(hash_or))
    qh = np.empty(nq, dtype=np.uint64)
    qw = np.empty(nq, dtype=np.int32)
    rc = _fn_sq(flat_p, flat.shape[0], n_windows, fraglen, int(k),
                hash_or.value, nq,
                qh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                qw.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc == -1:
        raise MemoryError(f"sorted query scratch for {nq} hashes")
    if rc != 0:
        raise RuntimeError(f"sorted query walk disagrees with its "
                           f"survivor count {nq}")
    return qh, qw, totals


_fn_wcp = _lib.galah_window_counts_pairs
_fn_wcp.restype = None
_fn_wcp.argtypes = [
    ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int64, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
]

_fn_fwp = _lib.galah_fill_windows_pairs
_fn_fwp.restype = None
_fn_fwp.argtypes = [
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
    ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
    ctypes.POINTER(ctypes.c_uint64),
]


def windows_from_pairs(pos: np.ndarray, hashes: np.ndarray,
                       n_windows: int, fraglen: int,
                       k: int) -> np.ndarray:
    """Compacted (W, slots) windows from the profile walk's kept
    (pos, hash) pairs — bit-identical layout to compact_windows, in
    O(n_valid) instead of two streaming passes over the 8-byte-per-bp
    flat array."""
    pos = np.ascontiguousarray(pos, dtype=np.int64)
    hashes = np.ascontiguousarray(hashes, dtype=np.uint64)
    if pos.shape != hashes.shape:
        raise ValueError("pos/hashes shape mismatch")
    if pos.shape[0] and (pos.min() < 0
                         or pos.max() >= n_windows * fraglen):
        raise ValueError("position out of range")
    counts = np.zeros(max(n_windows, 1), dtype=np.int64)
    _fn_wcp(
        pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        pos.shape[0], n_windows, fraglen, int(k),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    slots = max(int(counts[:n_windows].max()) if n_windows else 1, 1)
    slots = min(-(-slots // 64) * 64, fraglen)
    wins = np.full((max(n_windows, 1), slots), np.uint64(SENTINEL),
                   dtype=np.uint64)
    cursors = np.zeros(max(n_windows, 1), dtype=np.int64)
    _fn_fwp(
        pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        hashes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        pos.shape[0], n_windows, fraglen, int(k), slots,
        cursors.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        wins.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return wins[:n_windows]


_fn_wmm = _lib.galah_window_match_counts_merge
_fn_wmm.restype = None
_fn_wmm.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int32),
    ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
    ctypes.POINTER(ctypes.c_int32),
]


_fn_fold = _lib.galah_fold_window_flags
_fn_fold.restype = None
_fn_fold.argtypes = [
    ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(ctypes.c_int32),
    ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
]


def fold_window_flags(flags: np.ndarray, qw: np.ndarray,
                      n_windows: int) -> np.ndarray:
    """Per-window matched counts from 0/1 membership flags aligned to
    a profile's sorted query (qw its window ids, all < n_windows, as
    GenomeProfile.sorted_query guarantees) — np.bincount(qw[flags !=
    0], minlength=n_windows) as int32, without holding the GIL."""
    flags = np.ascontiguousarray(flags, dtype=np.int8)
    qw = np.ascontiguousarray(qw, dtype=np.int32)
    if flags.shape != qw.shape:
        raise ValueError("flags/qw shape mismatch")
    matched = np.zeros(n_windows, dtype=np.int32)
    _fn_fold(flags.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
             qw.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
             flags.shape[0],
             matched.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return matched


_fn_avx = _lib.galah_merge_uses_avx512
_fn_avx.restype = ctypes.c_int
_fn_avx.argtypes = []


def merge_uses_avx512() -> bool:
    """True iff the merge counter would dispatch to the AVX-512 kernel
    right now (build + CPU support, GALAH_TPU_NO_AVX512 unset).
    Re-resolved per call, so env toggles within a process are seen."""
    return bool(_fn_avx())


def window_match_counts_merge(
        qh: np.ndarray, qw: np.ndarray, n_windows: int,
        ref_set: np.ndarray, validate: bool = True) -> np.ndarray:
    """Per-window matched counts via one linear merge of the profile's
    pre-sorted surviving hashes against the sorted distinct ref set —
    bit-identical to window_match_counts' matched output. qh must be
    sorted ascending with qw its window ids. Pass validate=False only
    when the arrays come from a source that already guarantees the
    bounds (GenomeProfile.sorted_query) — the check is two O(nq) scans,
    which would otherwise repeat per pair on the hot path."""
    qh = np.ascontiguousarray(qh, dtype=np.uint64)
    qw = np.ascontiguousarray(qw, dtype=np.int32)
    ref_set = np.ascontiguousarray(ref_set, dtype=np.uint64)
    if qh.shape != qw.shape:
        raise ValueError("qh/qw shape mismatch")
    if validate and qw.shape[0] and (qw.min() < 0
                                     or qw.max() >= n_windows):
        raise ValueError("window id out of range")
    matched = np.zeros(n_windows, dtype=np.int32)
    _fn_wmm(
        qh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        qw.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        qh.shape[0],
        ref_set.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ref_set.shape[0],
        matched.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return matched

_fn_wmb = _lib.galah_window_match_counts_merge_batch
_fn_wmb.restype = None
_fn_wmb.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
    ctypes.c_int64, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
]


def window_match_counts_merge_batch(
        qh_cat: np.ndarray, qw_cat: np.ndarray, q_off: np.ndarray,
        ref_cat: np.ndarray, r_off: np.ndarray, pair_q: np.ndarray,
        pair_r: np.ndarray, m_off: np.ndarray, total_windows: int,
        threads: int = 1) -> np.ndarray:
    """Concatenated per-window matched counts for a PAIR LIST — the
    batched twin of window_match_counts_merge (bit-identical counts per
    pair), with the per-pair loop and threading in C. Layouts (all
    contiguous, caller-guaranteed in-bounds — GenomeProfile data):

      qh_cat/qw_cat: per-genome sorted_query() arrays concatenated,
        genome g at [q_off[g], q_off[g+1]);
      ref_cat: per-genome sorted distinct ref sets concatenated,
        genome g at [r_off[g], r_off[g+1]);
      pair_q/pair_r: genome indices per pair (int32);
      m_off: per-pair output offset (int64 prefix over each pair's
        query window count), with `total_windows` the grand total.

    Returns the zero-initialized (total_windows,) int32 matched array
    filled per pair at [m_off[p], m_off[p] + n_windows(pair_q[p]))."""
    qh_cat = np.ascontiguousarray(qh_cat, dtype=np.uint64)
    qw_cat = np.ascontiguousarray(qw_cat, dtype=np.int32)
    q_off = np.ascontiguousarray(q_off, dtype=np.int64)
    ref_cat = np.ascontiguousarray(ref_cat, dtype=np.uint64)
    r_off = np.ascontiguousarray(r_off, dtype=np.int64)
    pair_q = np.ascontiguousarray(pair_q, dtype=np.int32)
    pair_r = np.ascontiguousarray(pair_r, dtype=np.int32)
    m_off = np.ascontiguousarray(m_off, dtype=np.int64)
    if qh_cat.shape != qw_cat.shape:
        raise ValueError("qh_cat/qw_cat shape mismatch")
    if pair_q.shape != pair_r.shape or pair_q.shape != m_off.shape:
        raise ValueError("pair array shape mismatch")
    matched = np.zeros(int(total_windows), dtype=np.int32)
    if pair_q.shape[0] == 0:
        return matched
    _fn_wmb(
        qh_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        qw_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        q_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ref_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        r_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        pair_q.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        pair_r.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        m_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        pair_q.shape[0], int(threads),
        matched.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return matched
