"""Sparse screened pairwise evaluation on the device path.

The CPU backend eliminates the O(N^2) pair wall with the host
inverted-index collision screen (ops/collision.py). This module ports
that two-phase shape to the device backends (TPU and meshes, per the
docs/DISTRIBUTED.md roadmap): the host produces the sparse candidate
list by exact collision counting, and the device evaluates ONLY the
survivors — batched (common, total) pair stats over gathered (i, j)
sketch rows instead of dense (row x col) tiles. This is the screening
idea of the reference's skani preclusterer (reference:
src/skani.rs:54-70) applied to the MinHash pass on device.

Exactness: the collision screen is conservative for merged-bottom-k
Mash (ops/collision.candidate_pairs_minhash proves the bound), and the
gathered-pair device pass computes the identical integer
(common, total) as the dense tiles, so results are bit-identical to
the dense path — pinned by tests/test_sparse_device.py.

Cost model: collision counting is O(NK log NK + colliding pairs) on
host; the device pass is O(S * K log K) for S surviving candidates.
Above ops/collision.SPARSE_SCREEN_MIN_N genomes this replaces the
O(N^2 * K log K / tile-throughput) dense wall whenever similarity is
sparse (real dereplication inputs are: most genome pairs share no
sketch hashes at all).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from galah_tpu.obs.profile import profiled
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.pairwise import (
    _pair_stats,
    ani_to_jaccard,
    stats_to_ani_f64,
)
from galah_tpu.utils import timing

# Candidate pairs evaluated per device dispatch. Large enough to
# amortize dispatch latency (the gathered rows are B x K u64 reads
# from HBM), small enough that the gather scratch stays tens of MB.
# On TPU the default is 4x larger (HBM is plentiful);
# GALAH_TPU_PAIR_BATCH overrides either way.
PAIR_BATCH = 8192

# ---- survivor-evaluation strategy selection (AUTO) -------------------
#
# Three ways to evaluate the screen's survivors, picked per call from
# survivor count and duplication factor (how many pairs each distinct
# sketch row participates in), with the decision and per-strategy waste
# recorded as timing counters in the stage report:
#
#   blocked — the P-pairs-per-program Mosaic pairlist kernel
#             (ops/pallas_pairlist.py), the default device strategy;
#   gather  — permute survivor rows into (GATHER_ROWS x GATHER_COLS)
#             dense tiles and evaluate through the 27.3%-of-ceiling
#             dense kernel (ops/pallas_pairwise.py), ignoring the
#             unused cells; wins only when the survivors are so
#             duplication-heavy (near-clique families) that tile fill
#             beats the blocked kernel's rate;
#   cpu     — a single host-side numpy evaluation for survivor counts
#             too small to be worth even one device dispatch.
#
# The rate constants are estimates: the dense tile measured 218,077
# pairs/s on a v5e chip in an early campaign; the blocked kernel's is
# the design target. Recalibrate both from chip runs, or pin a strategy
# with GALAH_TPU_PAIRLIST_STRATEGY=blocked|gather|xla|cpu.
DENSE_RATE_EST = 218_077.0
BLOCKED_RATE_EST = 200_000.0
GATHER_MIN_DUP = 4.0     # don't even plan tiles below this duplication
PAIRLIST_CPU_MAX = 256   # survivor count where one host eval wins
GATHER_ROWS = 64         # unique a-rows per gather-dense tile
GATHER_COLS = 128        # unique b-rows per gather-dense tile

# Numeric-determinism contract checked by `galah-tpu lint` (GL9xx):
# all survivor-evaluation strategies must agree bit-for-bit on the
# integer (matches, lengths) stats, so the AUTO strategy pick can
# never change clustering output.
DETERMINISM_CONTRACT = {
    "family": "pairlist",
    "dtype": "int32",
    "functions": ["pair_stats_for_pairs", "threshold_pairs_sparse",
                  "_batch_pair_stats", "_gather_dense_pair_stats",
                  "_cpu_pair_stats"],
}


def _default_pair_batch() -> int:
    env = os.environ.get("GALAH_TPU_PAIR_BATCH")
    if env:
        return max(1, int(env))
    return 4 * PAIR_BATCH if jax.default_backend() == "tpu" \
        else PAIR_BATCH


def pair_block_quantum() -> int:
    """Pairs per device evaluation block — callers sizing speculative
    batches (cluster/engine.py) round up to a multiple of this so the
    blocked pairlist kernel's programs run full."""
    from galah_tpu.ops.hll import use_pallas_default

    if not use_pallas_default():
        return 1
    from galah_tpu.ops.pallas_pairlist import pairlist_block_pairs

    return pairlist_block_pairs()


@profiled("sparse.batch_pair_stats")
@functools.partial(
    jax.jit,
    static_argnames=("sketch_size", "use_pallas", "interpret"))
def _batch_pair_stats(jmat: jax.Array, pi: jax.Array, pj: jax.Array,
                      sketch_size: int,
                      use_pallas: bool = False,
                      interpret: bool = False,
                      ) -> Tuple[jax.Array, jax.Array]:
    """(common, total) int32 for each gathered (pi[b], pj[b]) row pair.

    With use_pallas (the default on a TPU backend) the gathered pairs
    run the Mosaic pairlist kernel (ops/pallas_pairlist.py) instead of
    the vmapped u64 searchsorted — bit-identical integers either way.
    """
    rows = jnp.take(jmat, pi, axis=0)
    cols = jnp.take(jmat, pj, axis=0)
    if use_pallas:
        from galah_tpu.ops.pallas_pairlist import pair_stats_pairs_pallas

        return pair_stats_pairs_pallas(rows, cols, sketch_size,
                                       interpret=interpret)
    return jax.vmap(
        lambda a, b: _pair_stats(a, b, sketch_size))(rows, cols)


@functools.lru_cache(maxsize=8)
def _make_sharded_batch_stats(mesh: Mesh, sketch_size: int,
                              use_pallas: bool = False,
                              interpret: bool = False):
    """SPMD twin: the candidate batch is sharded over the mesh axis,
    the sketch matrix is replicated; each device evaluates its slice
    of the pair list. The per-pair outputs are all-gathered back to a
    replicated (B,) layout so a multi-host run (where P("i") shards
    are not host-addressable) reads the identical arrays on every
    host."""

    def spmd(jmat, pi, pj):
        c, t = _batch_pair_stats(jmat, pi, pj, sketch_size,
                                 use_pallas=use_pallas,
                                 interpret=interpret)
        return (jax.lax.all_gather(c, "i", tiled=True),
                jax.lax.all_gather(t, "i", tiled=True))

    # check_vma off: the outputs ARE replicated post-gather, but the
    # vma type system cannot express that for P() out_specs.
    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(None, None), P("i"), P("i")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def _plan_gather_segments(spi: np.ndarray, spj: np.ndarray,
                          rows_cap: int = GATHER_ROWS,
                          cols_cap: int = GATHER_COLS):
    """Host plan for the gather-dense strategy: split a (pi, pj)-sorted
    pair list into dense-tile jobs of at most `rows_cap` unique a-rows
    x `cols_cap` unique b-rows. Every job is padded to the fixed caps
    (repeating row 0 — its cells are computed and never read) so all
    segments share ONE compiled tile shape.

    Returns (segments, cells): segments is a list of
    (ua, ub, ra, rb, idx) — gather indices (rows_cap,)/(cols_cap,),
    per-pair tile coordinates, and the pair positions in the sorted
    list; cells is the total padded tile area (the strategy's waste
    denominator). O(S log S) numpy throughout — no per-pair Python."""
    n = spi.shape[0]
    # dense rank of each pair's a-row (pairs are a-sorted, so ranks are
    # a prefix-sum over boundaries) -> blocks of rows_cap distinct a's
    a_rank = np.zeros(n, dtype=np.int64)
    if n > 1:
        a_rank[1:] = np.cumsum(spi[1:] != spi[:-1])
    block = a_rank // rows_cap
    starts = np.flatnonzero(np.r_[True, block[1:] != block[:-1]])
    bounds = np.r_[starts, n]
    segments = []
    for bi in range(len(starts)):
        s, e = int(bounds[bi]), int(bounds[bi + 1])
        ua_vals = np.unique(spi[s:e])
        ub_all = np.unique(spj[s:e])
        ra_all = (a_rank[s:e] - a_rank[s]).astype(np.int32)
        pos_b = np.searchsorted(ub_all, spj[s:e]).astype(np.int64)
        piece = pos_b // cols_cap
        for t in range(int(piece.max()) + 1 if e > s else 0):
            mask = piece == t
            idx = np.flatnonzero(mask) + s
            ua = np.zeros(rows_cap, dtype=np.int32)
            ua[:ua_vals.size] = ua_vals
            ub_piece = ub_all[t * cols_cap:(t + 1) * cols_cap]
            ub = np.zeros(cols_cap, dtype=np.int32)
            ub[:ub_piece.size] = ub_piece
            segments.append((ua, ub, ra_all[mask],
                             (pos_b[mask] - t * cols_cap).astype(np.int32),
                             idx))
    cells = len(segments) * rows_cap * cols_cap
    return segments, cells


@profiled("sparse.gather_tile_stats")
@functools.partial(jax.jit,
                   static_argnames=("sketch_size", "interpret"))
def _gather_tile_stats(jmat: jax.Array, ua: jax.Array, ub: jax.Array,
                       sketch_size: int, interpret: bool = False):
    """One gather-dense tile: permute the survivor rows and run the
    dense Mosaic kernel over the full cross product."""
    from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

    rows = jnp.take(jmat, ua, axis=0)
    cols = jnp.take(jmat, ub, axis=0)
    return tile_stats_pallas(rows, cols, sketch_size,
                             interpret=interpret)


def _gather_dense_pair_stats(
    jmat: jax.Array,
    pi32: np.ndarray,
    pj32: np.ndarray,
    sketch_size: int,
    interpret: bool,
) -> Tuple[np.ndarray, np.ndarray]:
    """Evaluate the pair list through dense tiles (the gather-dense
    strategy). Every tile rides JAX's async dispatch queue; a kernel
    failure propagates."""
    n_pairs = pi32.shape[0]
    order = np.lexsort((pj32, pi32))
    spi, spj = pi32[order], pj32[order]
    segments, cells = _plan_gather_segments(spi, spj)
    timing.counter("pairlist-gather-cells", int(cells))
    timing.counter("pairlist-gather-used", int(n_pairs))

    common = np.empty(n_pairs, dtype=np.int32)
    total = np.empty(n_pairs, dtype=np.int32)
    futs = []
    for ua, ub, ra, rb, idx in segments:
        timing.dispatch()
        futs.append((ra, rb, idx, _gather_tile_stats(
            jmat, jnp.asarray(ua), jnp.asarray(ub), sketch_size,
            interpret=interpret)))
    for ra, rb, idx, (c, t) in futs:
        timing.dispatch(sync=True)
        common[order[idx]] = np.asarray(c)[ra, rb]
        total[order[idx]] = np.asarray(t)[ra, rb]
    return common, total


def _cpu_pair_stats(sketch_mat: np.ndarray, pi32: np.ndarray,
                    pj32: np.ndarray, sketch_size: int,
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Tiny survivor lists: the merged bottom-k walk in numpy on host —
    no device dispatch, no batching, no padding, and no CPU backend
    (a TPU-pinned process has none). Same integers as _pair_stats:
    total = min(sketch_size, |a u b|) and common = the shared hashes
    among the `total` smallest of the union."""
    mat = np.ascontiguousarray(sketch_mat, dtype=np.uint64)
    common = np.zeros(pi32.shape[0], dtype=np.int32)
    total = np.zeros(pi32.shape[0], dtype=np.int32)
    sentinel = np.uint64(SENTINEL)
    for n, (i, j) in enumerate(zip(pi32.tolist(), pj32.tolist())):
        a = mat[i][mat[i] != sentinel]
        b = mat[j][mat[j] != sentinel]
        shared = np.intersect1d(a, b, assume_unique=True)
        tot = min(sketch_size, a.size + b.size - shared.size)
        total[n] = tot
        if tot:
            cutoff = np.union1d(a, b)[tot - 1]
            common[n] = int(np.count_nonzero(shared <= cutoff))
    return common, total


def _resolve_pairlist_strategy(
    pi32: np.ndarray,
    pj32: np.ndarray,
    use_pallas: bool,
    explicit: bool,
    mesh: Optional[Mesh],
    batch: Optional[int],
) -> str:
    """AUTO strategy pick from survivor count and duplication factor.

    GALAH_TPU_PAIRLIST_STRATEGY pins it. AUTO only deviates from the
    historical batched path when nothing else is pinned: an explicit
    use_pallas, a mesh, or a caller batch size all mean the caller
    chose a shape — keep it (and parity/fault tests rely on that)."""
    env = os.environ.get("GALAH_TPU_PAIRLIST_STRATEGY", "").lower()
    if env in ("blocked", "gather", "xla", "cpu"):
        return env
    if not use_pallas:
        return "xla"
    if explicit or batch is not None or (
            mesh is not None and mesh.devices.size > 1):
        return "blocked"
    n_pairs = int(pi32.shape[0])
    if n_pairs <= PAIRLIST_CPU_MAX:
        return "cpu"
    uniq = np.union1d(pi32, pj32).size
    dup = n_pairs / max(uniq, 1)
    if dup < GATHER_MIN_DUP:
        return "blocked"
    order = np.lexsort((pj32, pi32))
    _, cells = _plan_gather_segments(pi32[order], pj32[order])
    fill = n_pairs / max(cells, 1)
    if fill * DENSE_RATE_EST > BLOCKED_RATE_EST:
        return "gather"
    return "blocked"


def pair_stats_for_pairs(
    sketch_mat: np.ndarray,
    pi: np.ndarray,
    pj: np.ndarray,
    sketch_size: int,
    mesh: Optional[Mesh] = None,
    batch: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact merged-bottom-k (common, total) for an explicit pair list.

    One device dispatch per `batch` candidates (fixed shape, so the
    trace compiles once); the final partial batch is padded with pair
    (0, 0) and trimmed on host. With a multi-device `mesh` the batch is
    sharded over the mesh axis. use_pallas selects the Mosaic pairlist
    kernel (default: on for TPU backends); kernel failures propagate.

    On the default path an AUTO strategy pick (see the module's
    strategy block) may reroute the evaluation through the gather-dense
    tiles or a single host evaluation; the decision and each
    strategy's waste land in the timing counters
    (pairlist-strategy-*, pairlist-gather-cells/used,
    pairlist-pad-slots, pairlist-blocked-pad-pairs). All strategies
    produce bit-identical integers (tests/test_pallas_pairlist.py).
    """
    n_pairs = int(pi.shape[0])
    common = np.empty(n_pairs, dtype=np.int32)
    total = np.empty(n_pairs, dtype=np.int32)
    if n_pairs == 0:
        return common, total

    explicit = use_pallas is not None
    if use_pallas is None:
        from galah_tpu.ops.hll import use_pallas_default

        use_pallas = use_pallas_default()

    pi32 = np.ascontiguousarray(pi, dtype=np.int32)
    pj32 = np.ascontiguousarray(pj, dtype=np.int32)
    strategy = _resolve_pairlist_strategy(pi32, pj32, bool(use_pallas),
                                          explicit, mesh, batch)
    timing.counter(f"pairlist-strategy-{strategy}", 1)
    if strategy == "cpu":
        return _cpu_pair_stats(sketch_mat, pi32, pj32, sketch_size)
    if strategy == "xla":
        use_pallas = False

    jmat = jnp.asarray(np.ascontiguousarray(sketch_mat, dtype=np.uint64))
    if strategy == "gather":
        return _gather_dense_pair_stats(jmat, pi32, pj32, sketch_size,
                                        interpret)

    n_dev = mesh.devices.size if mesh is not None else 1
    if batch is None:
        batch = _default_pair_batch()
    b = -(-batch // n_dev) * n_dev

    if mesh is not None and n_dev > 1:
        fn = _make_sharded_batch_stats(mesh, sketch_size, bool(use_pallas),
                                       interpret=interpret)
    else:
        fn = functools.partial(_batch_pair_stats, sketch_size=sketch_size,
                               use_pallas=bool(use_pallas),
                               interpret=interpret)

    starts = list(range(0, n_pairs, b))
    # Waste on the record: zero-padded slots in the final partial batch
    # plus, on the blocked kernel path, the sentinel pairs each
    # dispatch adds to fill its last P-pair program.
    timing.counter("pairlist-pad-slots", len(starts) * b - n_pairs)
    if use_pallas:
        from galah_tpu.ops.pallas_pairlist import pairlist_block_pairs

        timing.counter("pairlist-blocked-pad-pairs",
                       len(starts) * (-b % pairlist_block_pairs()))

    def dispatch(s):
        e = min(s + b, n_pairs)
        bi = np.zeros(b, dtype=np.int32)
        bj = np.zeros(b, dtype=np.int32)
        bi[: e - s] = pi32[s:e]
        bj[: e - s] = pj32[s:e]
        timing.dispatch()
        return s, fn(jmat, jnp.asarray(bi), jnp.asarray(bj))

    def store(s, ct):
        e = min(s + b, n_pairs)
        timing.dispatch(sync=True)
        common[s:e] = np.asarray(ct[0])[: e - s]
        total[s:e] = np.asarray(ct[1])[: e - s]

    # Batches PIPELINE with a bounded in-flight window: dispatches run
    # ahead of the ordered host syncs so each sync's round trip
    # overlaps the next batches' compute, while the window caps live
    # device buffers — a mega-run can carry 100k+ batches, so unbounded
    # queueing would hold O(n_batches * batch) device memory.
    from collections import deque

    window = 16
    inflight: deque = deque()
    for s in starts:
        inflight.append(dispatch(s))
        if len(inflight) >= window:
            store(*inflight.popleft())
    while inflight:
        store(*inflight.popleft())
    return common, total


def threshold_pairs_sparse(
    sketch_mat: np.ndarray,
    k: int,
    min_ani: float,
    sketch_size: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    batch: Optional[int] = None,
) -> dict:
    """Sparse {(i, j): ani} for i<j pairs with ani >= min_ani — the
    screened device pipeline: host collision screen, batched gathered
    pair stats on device, exact f64 integer-Jaccard check on host.

    Bit-identical to ops/pairwise.threshold_pairs' dense tiled path
    (same integers, same f64 keep-check and ANI), selected by it above
    ops/collision.SPARSE_SCREEN_MIN_N genomes on device backends.
    """
    from galah_tpu.ops.collision import candidate_pairs_minhash

    mat = np.ascontiguousarray(sketch_mat, dtype=np.uint64)
    n = mat.shape[0]
    if sketch_size is None:
        sketch_size = mat.shape[1]
    lens = (mat != np.uint64(SENTINEL)).sum(axis=1).astype(np.int64)
    j_thr = ani_to_jaccard(min_ani, k)
    pi, pj = candidate_pairs_minhash(mat, lens, j_thr, sketch_size)
    # Survivor economics on the record: candidates = pairs the exact
    # device pass must evaluate, out of n*(n-1)/2 possible.
    timing.counter("screen-candidates", int(pi.shape[0]))
    timing.counter("screen-possible-pairs", n * (n - 1) // 2)
    del n  # candidates are already in-bounds i < j < n
    if pi.shape[0] == 0:
        return {}
    common, total = pair_stats_for_pairs(
        mat, pi, pj, sketch_size, mesh=mesh, batch=batch)
    common = common.astype(np.int64)
    total = total.astype(np.int64)
    keep = common.astype(np.float64) >= j_thr * total
    timing.counter("screen-kept-pairs", int(keep.sum()))
    from galah_tpu.obs import metrics as obs_metrics

    obs_metrics.gauge(
        "screen.survival_rate",
        help="Fraction of screened candidate pairs the threshold "
             "kept (last screening pass)", unit="fraction").set(
        float(keep.sum()) / pi.shape[0] if pi.shape[0] else 0.0)
    ani = stats_to_ani_f64(common[keep], total[keep], k)
    return {(int(a), int(b)): float(v)
            for a, b, v in zip(pi[keep], pj[keep], ani)}
