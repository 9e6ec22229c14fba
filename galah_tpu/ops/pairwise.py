"""Tiled all-pairs MinHash ANI on device — the framework's hot op.

Replaces the reference's dense O(N^2) host pair loop
(reference: src/finch.rs:53-73) with a tiled device computation:

  * a pair's Mash Jaccard is computed WITHOUT sorting the union: both
    sketches are already sorted, so two `searchsorted` passes + cumulative
    sums yield (a) which elements are common and (b) each element's rank in
    the distinct union — enough to count commons inside the merged
    bottom-k. O(K log K) per pair, O(K) memory, MXU/VPU friendly.
  * pairs are evaluated in (row_tile x col_tile) blocks via nested vmap.
  * across devices, rows are sharded over a 1-D mesh with `shard_map`;
    every device holds the (replicated) sketch matrix and computes its row
    block against all columns, `lax.map`-ing over column tiles to bound
    memory. ANI tiles stay on device; thresholding happens there too, so
    only the sparse survivors ever reach the host.

Semantics (merged bottom-k Jaccard, Mash distance, ANI = 1 - d) are
bit-compatible with ops/minhash_np.py and the reference's finch backend
(golden 0.9808188, reference: src/finch.rs:96).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.hashing import HASH_SENTINEL
from galah_tpu.utils import timing

jax.config.update("jax_enable_x64", True)

# GL10xx pipeline-discipline contract (analysis/pipeline_check.py): the
# streamed pair pass must never be eagerly materialized and must report
# how busy it kept the device between block arrivals.
PIPELINE_STAGE = {
    "streaming": ["iter_threshold_pairs_streamed"],
    "occupancy_gauge": "workload.pipeline_occupancy",
}


def _pair_stats(a: jax.Array, b: jax.Array,
                sketch_size: int) -> Tuple[jax.Array, jax.Array]:
    """(common, total) of the merged bottom-`sketch_size` distinct union.

    `a`, `b`: (K,) uint64 sorted ascending, SENTINEL-padded.
    """
    valid_a = a != HASH_SENTINEL
    valid_b = b != HASH_SENTINEL
    na = jnp.sum(valid_a.astype(jnp.int32))
    nb = jnp.sum(valid_b.astype(jnp.int32))

    pos_b = jnp.searchsorted(b, a)  # count of b-elements < a[i]
    match = (pos_b < b.shape[0]) & valid_a
    match = match & (jnp.take(b, jnp.minimum(pos_b, b.shape[0] - 1)) == a)

    n_common = jnp.sum(match.astype(jnp.int32))
    n_union = na + nb - n_common
    total = jnp.minimum(jnp.int32(sketch_size), n_union)

    # Rank of a[i] in the distinct union = (#a < a[i]) + (#b < a[i])
    # - (#common < a[i]); a is distinct so #a < a[i] is just i.
    cmatch_excl = jnp.cumsum(match.astype(jnp.int32)) - match.astype(jnp.int32)
    urank = jnp.arange(a.shape[0], dtype=jnp.int32) + pos_b.astype(jnp.int32) \
        - cmatch_excl
    common = jnp.sum((match & (urank < total)).astype(jnp.int32))
    return common, total


def _stats_to_ani(common: jax.Array, total: jax.Array, k: int) -> jax.Array:
    """Mash ANI (f32) from merged-bottom-k (common, total)."""
    j = common.astype(jnp.float32) / jnp.maximum(
        total.astype(jnp.float32), 1.0)
    d = -jnp.log(2.0 * j / (1.0 + j)) / jnp.float32(k)
    ani = 1.0 - d
    return jnp.where(common > 0, ani, jnp.float32(0.0))


@functools.partial(jax.jit, static_argnames=("sketch_size", "k"))
def tile_ani(rows: jax.Array, cols: jax.Array,
             sketch_size: int, k: int) -> jax.Array:
    """ANI for every (row, col) sketch pair: (Br,K),(Bc,K) -> (Br,Bc) f32."""
    def one_row(a):
        c, t = jax.vmap(lambda b: _pair_stats(a, b, sketch_size))(cols)
        return _stats_to_ani(c, t, k)

    return jax.vmap(one_row)(rows)


@functools.partial(jax.jit, static_argnames=("sketch_size", "k"))
def tile_stats(rows: jax.Array, cols: jax.Array,
               sketch_size: int, k: int) -> Tuple[jax.Array, jax.Array]:
    """(common, total) int32 tiles — used for exact-parity tests."""
    def one_row(a):
        return jax.vmap(lambda b: _pair_stats(a, b, sketch_size))(cols)

    return jax.vmap(one_row)(rows)


@jax.jit
def tile_intersect_counts(rows: jax.Array, cols: jax.Array) -> jax.Array:
    """|row ∩ col| for sorted SENTINEL-padded hash rows -> (Br, Bc) int32.

    Used for marker-containment screening (the skani-equivalent
    preclusterer's candidate filter, reference: src/skani.rs:54-70).
    """
    def one_pair(a, b):
        valid = a != HASH_SENTINEL
        pos = jnp.searchsorted(b, a)
        hit = jnp.take(b, jnp.minimum(pos, b.shape[0] - 1)) == a
        return jnp.sum((hit & valid).astype(jnp.int32))

    return jax.vmap(lambda a: jax.vmap(lambda b: one_pair(a, b))(cols))(rows)


def _block_ani(block_rows: jax.Array, all_cols: jax.Array,
               sketch_size: int, k: int, col_tile: int) -> jax.Array:
    """(Br, N) ANI of a row block vs all columns, lax.map over col tiles."""
    n = all_cols.shape[0]
    n_tiles = n // col_tile  # caller pads N to a multiple of col_tile

    def one_tile(t):
        cols = jax.lax.dynamic_slice_in_dim(
            all_cols, t * col_tile, col_tile, axis=0)
        return tile_ani(block_rows, cols, sketch_size, k)

    tiles = jax.lax.map(one_tile, jnp.arange(n_tiles))  # (T, Br, col_tile)
    return jnp.transpose(tiles, (1, 0, 2)).reshape(block_rows.shape[0], n)


def all_pairs_ani(
    sketch_mat: np.ndarray,
    k: int,
    sketch_size: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    col_tile: int = 128,
) -> np.ndarray:
    """Full (N, N) ANI matrix, rows sharded over the mesh's devices.

    The reference walks i<j pairs on host threads; here the whole matrix is
    one sharded device computation (upper-triangle extraction happens in
    `threshold_pairs`). For very large N prefer `threshold_pairs`, which
    never materializes the full matrix on host — N is capped here so an
    API caller cannot accidentally allocate an O(N^2) host matrix.
    """
    n_genomes = sketch_mat.shape[0]
    if n_genomes > 16384:
        raise ValueError(
            f"all_pairs_ani materializes a dense ({n_genomes}, "
            f"{n_genomes}) matrix; use threshold_pairs for large N")
    if sketch_size is None:
        sketch_size = sketch_mat.shape[1]
    if mesh is None:
        devs = jax.devices()
        mesh = Mesh(np.array(devs), ("i",))
    n_dev = mesh.devices.size

    n = sketch_mat.shape[0]
    # Padded size must be divisible by the row sharding (n_dev) AND the
    # column tiling, so round up to a multiple of lcm(n_dev, col_tile).
    import math

    quantum = math.lcm(n_dev, col_tile)
    pad_n = -(-n // quantum) * quantum
    mat = np.full((pad_n, sketch_mat.shape[1]),
                  np.uint64(SENTINEL), dtype=np.uint64)
    mat[:n] = sketch_mat
    jmat = jnp.asarray(mat)

    fn = jax.shard_map(
        functools.partial(_block_ani, sketch_size=sketch_size, k=k,
                          col_tile=col_tile),
        mesh=mesh,
        in_specs=(P("i", None), P(None, None)),
        out_specs=P("i", None),
    )
    ani = jax.jit(fn)(jmat, jmat)
    return np.asarray(ani[:n, :n])


def ani_to_jaccard(min_ani: float, k: int) -> float:
    """Invert Mash ANI to the equivalent Jaccard threshold (f64, exact)."""
    import math

    q = math.exp(-float(k) * (1.0 - float(min_ani)))
    return q / (2.0 - q)


def stats_to_ani_f64(common: np.ndarray, total: np.ndarray,
                     k: int) -> np.ndarray:
    """Host-side f64 Mash ANI from integer (common, total) — bit-compatible
    with ops/minhash_np.mash_ani and the reference's finch path."""
    j = common.astype(np.float64) / np.maximum(total.astype(np.float64), 1.0)
    with np.errstate(divide="ignore"):
        d = -np.log(2.0 * j / (1.0 + j)) / float(k)
    return np.where(common > 0, 1.0 - d, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("sketch_size", "k", "row_tile", "col_tile", "cap",
                     "n", "use_pallas"))
def _rowblock_candidates(
    jmat: jax.Array,     # (n_pad, K) uint64 padded sketch matrix
    r0: jax.Array,       # scalar i32: first global row of this block
    j_thr_lo: jax.Array, # f64: conservative (slightly lowered) threshold
    sketch_size: int,
    k: int,
    row_tile: int,
    col_tile: int,
    cap: int,
    n: int,
    use_pallas: bool,
):
    """One device dispatch: a (row_tile, n_pad) stats stripe, thresholded
    and compacted to at most `cap` candidate pairs on device.

    Returns (flat_idx (cap,), common (cap,), total (cap,), count) where
    flat_idx indexes the (row_tile, n_pad) stripe (-1 padding). count is
    the TRUE number of passing entries — count > cap signals overflow
    and the caller must re-run this block another way.
    """
    n_pad = jmat.shape[0]
    rows = jax.lax.dynamic_slice_in_dim(jmat, r0, row_tile, axis=0)
    n_ct = n_pad // col_tile

    if use_pallas:
        from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

        def stats_fn(rows, cols):
            return tile_stats_pallas(rows, cols, sketch_size)
    else:
        def stats_fn(rows, cols):
            return tile_stats(rows, cols, sketch_size, k)

    # Tiles entirely below the diagonal contribute nothing; lax.map is a
    # sequential scan, so lax.cond really skips their compute at runtime
    # while keeping one compiled shape for every row block.
    t_first = r0 // col_tile

    def one_tile(t):
        def compute(_):
            cols = jax.lax.dynamic_slice_in_dim(
                jmat, t * col_tile, col_tile, axis=0)
            c, tt = stats_fn(rows, cols)
            return c.astype(jnp.int32), tt.astype(jnp.int32)

        def skip(_):
            z = jnp.zeros((row_tile, col_tile), jnp.int32)
            return z, z

        return jax.lax.cond(t >= t_first, compute, skip, None)

    common, total = jax.lax.map(one_tile, jnp.arange(n_ct))
    # (T, rt, ct) -> (rt, n_pad)
    common = jnp.transpose(common, (1, 0, 2)).reshape(row_tile, n_pad)
    total = jnp.transpose(total, (1, 0, 2)).reshape(row_tile, n_pad)

    gi = r0 + jnp.arange(row_tile)[:, None]
    gj = jnp.arange(n_pad)[None, :]
    mask = (common.astype(jnp.float64)
            >= j_thr_lo * total.astype(jnp.float64))
    mask &= (common > 0) & (gi < gj) & (gj < n)
    count = jnp.sum(mask.astype(jnp.int32))
    (flat_idx,) = jnp.nonzero(mask.ravel(), size=cap, fill_value=-1)
    safe = jnp.maximum(flat_idx, 0)
    return (flat_idx, jnp.take(common.ravel(), safe),
            jnp.take(total.ravel(), safe), count)


@functools.partial(
    jax.jit,
    static_argnames=("row_tile", "col_tile", "cap", "use_pallas"))
def _rowblock_screen(
    jmat: jax.Array,     # (n_pad, M) uint64 padded marker matrix
    counts: jax.Array,   # (n_pad,) int32 marker counts per genome
    r0: jax.Array,       # scalar i32: first global row of this block
    c_floor_lo: jax.Array,  # f64: conservative (lowered) containment floor
    n: jax.Array,        # scalar i32: true genome count
    row_tile: int,
    col_tile: int,
    cap: int,
    use_pallas: bool = False,
):
    """One device dispatch: a (row_tile, n_pad) marker-intersection
    stripe, containment-thresholded and compacted on device.

    Returns (flat_idx (cap,), inter (cap,), count) — flat_idx indexes the
    (row_tile, n_pad) stripe, inter is the raw intersection count so the
    host can apply the EXACT f64 containment check.
    """
    n_pad = jmat.shape[0]
    rows = jax.lax.dynamic_slice_in_dim(jmat, r0, row_tile, axis=0)
    n_ct = n_pad // col_tile
    t_first = r0 // col_tile

    def one_tile(t):
        def compute(_):
            cols = jax.lax.dynamic_slice_in_dim(
                jmat, t * col_tile, col_tile, axis=0)
            if use_pallas:
                from galah_tpu.ops.pallas_pairwise import (
                    tile_intersect_pallas,
                )

                return tile_intersect_pallas(rows, cols)
            return tile_intersect_counts(rows, cols).astype(jnp.int32)

        def skip(_):
            return jnp.zeros((row_tile, col_tile), jnp.int32)

        return jax.lax.cond(t >= t_first, compute, skip, None)

    inter = jax.lax.map(one_tile, jnp.arange(n_ct))
    inter = jnp.transpose(inter, (1, 0, 2)).reshape(row_tile, n_pad)

    rcnt = jax.lax.dynamic_slice_in_dim(counts, r0, row_tile, axis=0)
    denom = jnp.minimum(rcnt[:, None], counts[None, :])
    gi = r0 + jnp.arange(row_tile)[:, None]
    gj = jnp.arange(n_pad)[None, :]
    mask = (inter.astype(jnp.float64)
            >= c_floor_lo * denom.astype(jnp.float64))
    mask &= (inter > 0) & (gi < gj) & (gj < n)
    count = jnp.sum(mask.astype(jnp.int32))
    (flat_idx,) = jnp.nonzero(mask.ravel(), size=cap, fill_value=-1)
    return (flat_idx, jnp.take(inter.ravel(), jnp.maximum(flat_idx, 0)),
            count)


def screen_pairs(
    marker_mat: np.ndarray,   # (N, M) uint64 sorted SENTINEL-padded markers
    counts: np.ndarray,       # (N,) marker counts per genome
    c_floor: float,
    row_tile: Optional[int] = None,
    col_tile: Optional[int] = None,
    cap_per_row: int = 256,
    mesh: "Optional[Mesh]" = None,
    use_pallas: Optional[bool] = None,
) -> list[tuple[int, int]]:
    """i<j pairs whose marker containment >= c_floor, blocked on device.

    Containment = |markers_i ∩ markers_j| / min(|markers_i|, |markers_j|)
    — the skani-equivalent candidate screen (reference: src/skani.rs:54-70,
    screen_refs(0.80, ..)). ONE device dispatch per row block: the block's
    intersection stripe is computed tile-by-tile on device (lax.map),
    thresholded conservatively there, and only compacted candidates come
    back; the host applies the exact f64 containment check. On a
    multi-device runtime the column-sharded SPMD twin
    (parallel/mesh.sharded_screen_pairs) is selected automatically.
    """
    # No knobs pinned and above the sparse crossover: the inverted-index
    # collision counts ARE the containment numerators (marker sets are
    # distinct), so the host check below is exact with no second pass —
    # O(NM log NM + colliding pairs) instead of O(N^2) tiles, on ANY
    # backend (the screen is pure host work; the device never needs to
    # see the dense marker matrix at all). Tile/pallas knobs and an
    # explicit mesh pin the dense tiled implementations for parity
    # tests. The denom > 0 guard matches the tiled paths
    # (see _screen_pairs_single).
    from galah_tpu.ops.collision import sparse_screen_min_n

    if (mesh is None and use_pallas is None and row_tile is None
            and col_tile is None
            and (marker_mat.shape[0] >= sparse_screen_min_n()
                 or not _dense_marker_screen_default(marker_mat.shape[1]))
            and not os.environ.get("GALAH_TPU_DENSE_PAIRS")):
        from galah_tpu.ops.collision import collision_pair_counts

        counts64 = np.asarray(counts, dtype=np.int64)
        pi, pj, inter = collision_pair_counts(
            np.ascontiguousarray(marker_mat, dtype=np.uint64), counts64)
        denom = np.minimum(counts64[pi], counts64[pj]).astype(np.float64)
        keep = (denom > 0) & (inter.astype(np.float64)
                              >= c_floor * denom)
        n = marker_mat.shape[0]
        timing.counter("screen-candidates", int(pi.shape[0]))
        timing.counter("screen-possible-pairs", n * (n - 1) // 2)
        timing.counter("screen-kept-pairs", int(keep.sum()))
        from galah_tpu.obs import metrics as obs_metrics

        obs_metrics.gauge(
            "screen.survival_rate",
            help="Fraction of screened candidate pairs the threshold "
                 "kept (last screening pass)", unit="fraction").set(
            float(keep.sum()) / pi.shape[0] if pi.shape[0] else 0.0)
        return list(zip(pi[keep].tolist(), pj[keep].tolist()))

    if mesh is None and jax.device_count() > 1:
        from galah_tpu.parallel.mesh import auto_mesh

        mesh = auto_mesh()
    if mesh is not None and mesh.devices.size > 1:
        from galah_tpu.parallel.mesh import sharded_screen_pairs

        return sharded_screen_pairs(
            marker_mat, counts, c_floor, mesh=mesh,
            row_tile=row_tile if row_tile is not None else 64,
            col_tile=col_tile if col_tile is not None else 256,
            cap_per_row=cap_per_row, use_pallas=use_pallas)

    # Mosaic intersect kernel on TPU by default, for rows it compiles
    # for; a kernel failure propagates, whichever way it was chosen.
    if use_pallas is None:
        use_pallas = _marker_pallas_default(marker_mat.shape[1])
    # per-path tile defaults, honoring explicit caller values
    return _screen_pairs_single(
        marker_mat, counts, c_floor,
        row_tile if row_tile is not None else (128 if use_pallas else 64),
        col_tile if col_tile is not None else 256,
        cap_per_row, bool(use_pallas))


def _dense_marker_screen_default(width: int) -> bool:
    """Below the sparse crossover, whether the dense tiled screen is
    the default: everywhere but a TPU whose rows are too wide for the
    Mosaic kernel. There the XLA twin compares every marker pair of
    every tile — a 256-genome catalogue of MAGs (8,064-wide rows) took
    158 s of a v5e chip — while the host collision screen is exact and
    takes seconds."""
    from galah_tpu.ops.hll import use_pallas_default

    return not use_pallas_default() or _marker_pallas_default(width)


def _marker_pallas_default(width: int) -> bool:
    """The marker screen's default kernel choice: Mosaic on a TPU
    backend up to pallas_pairwise.MAX_DEFAULT_WIDTH markers per row."""
    from galah_tpu.ops.hll import use_pallas_default
    from galah_tpu.ops.pallas_pairwise import MAX_DEFAULT_WIDTH

    return use_pallas_default() and width <= MAX_DEFAULT_WIDTH


def _screen_pairs_single(
    marker_mat: np.ndarray,
    counts: np.ndarray,
    c_floor: float,
    row_tile: int,
    col_tile: int,
    cap_per_row: int,
    use_pallas: bool,
) -> list[tuple[int, int]]:
    import math

    n = marker_mat.shape[0]
    quantum = math.lcm(row_tile, col_tile)
    n_pad = -(-n // quantum) * quantum
    mat = np.full((n_pad, marker_mat.shape[1]),
                  np.uint64(SENTINEL), dtype=np.uint64)
    mat[:n] = marker_mat
    cnt = np.zeros(n_pad, dtype=np.int32)
    cnt[:n] = counts
    jmat = jnp.asarray(mat)
    jcnt = jnp.asarray(cnt)

    c_floor_lo = jnp.float64(c_floor * (1.0 - 1e-12) - 1e-300)
    counts64 = np.asarray(counts, dtype=np.int64)

    from galah_tpu.ops.compact import iter_blocks

    out: list[tuple[int, int]] = []
    for r0, (flat_idx, inter, count) in iter_blocks(
            n, row_tile, cap_per_row,
            lambda r0, cap: _rowblock_screen(
                jmat, jcnt, jnp.int32(r0), c_floor_lo, jnp.int32(n),
                row_tile=row_tile, col_tile=col_tile, cap=cap,
                use_pallas=use_pallas)):
        count = int(count)
        flat_idx = np.asarray(flat_idx)[:count]
        inter = np.asarray(inter)[:count].astype(np.int64)
        gi = r0 + flat_idx // n_pad
        gj = flat_idx % n_pad
        # exact host-side containment check. denom > 0 is belt and
        # braces: the device stripe mask already requires inter > 0,
        # and inter <= denom, so a denom == 0 pair cannot reach here —
        # the guard just keeps this check self-contained.
        denom = np.minimum(counts64[gi], counts64[gj]).astype(np.float64)
        keep = (denom > 0) & (inter.astype(np.float64) >= c_floor * denom)
        out.extend(zip(gi[keep].tolist(), gj[keep].tolist()))
    return out


def threshold_pairs(
    sketch_mat: np.ndarray,
    k: int,
    min_ani: float,
    sketch_size: Optional[int] = None,
    row_tile: Optional[int] = None,
    col_tile: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    cap_per_row: int = 64,
    mesh: "Optional[Mesh]" = None,
) -> dict[tuple[int, int], float]:
    """Sparse {(i, j): ani} for i<j pairs with ani >= min_ani.

    row_tile/col_tile default per path (XLA: 64x128; Mosaic: 128x512);
    explicit values are honored on every path.

    One device dispatch per ROW BLOCK (not per tile): the block's stats
    stripe is computed tile-by-tile on device (`lax.map`), thresholded
    conservatively there, and only the compacted sparse candidates come
    back — the host then applies the exact f64 integer-Jaccard check
    (common/total >= j_thr), sidestepping f32 log rounding, and reports
    the f64 ANI. Direct replacement for the reference's thresholded
    pair-cache insert (reference: src/finch.rs:69-71). If a block's
    candidates overflow the on-device capacity (cap_per_row * row_tile),
    that block transparently re-runs with a larger one. With use_pallas,
    stats tiles run the Mosaic kernel (ops/pallas_pairwise.py) instead
    of the XLA searchsorted path — bit-identical integers either way.

    On a multi-device runtime the column-sharded SPMD implementation
    (parallel/mesh.sharded_threshold_pairs) is selected automatically;
    pass `mesh` to choose one explicitly.

    Above ops/collision.SPARSE_SCREEN_MIN_N genomes (no tile/pallas
    knobs pinned) EVERY backend takes the screened sparse path instead
    of dense tiles: host collision screen, then batched gathered pair
    evaluation on device (ops/sparse_device.py) — bit-identical
    results, O(NK log NK + survivors) instead of O(N^2).
    """
    # Single-device CPU backend with no knobs pinned: the compiled-C
    # merged-bottom-k walk (csrc/pairstats.c) measures ~13x the XLA-CPU
    # tiled pass on one core and computes the identical f64 mash ANI —
    # use it outright. Knob-pinning callers (tiles, pallas, mesh) and
    # TPU backends keep the device path.
    if (mesh is None and use_pallas is None and row_tile is None
            and col_tile is None):
        if jax.default_backend() == "cpu" and jax.device_count() == 1:
            try:
                from galah_tpu.ops._cpairstats import threshold_pairs_c

                eff = (sketch_size if sketch_size is not None
                       else sketch_mat.shape[1])
                return threshold_pairs_c(
                    np.asarray(sketch_mat), eff, k, float(min_ani))
            except ImportError:
                pass  # no C toolchain: fall through to the XLA path

    # Device backends above the sparse crossover: screened evaluation
    # (host collision screen + batched gathered pair stats on device)
    # replaces the dense O(N^2) tiles — same two-phase shape as the CPU
    # C path above, same bit-identical results. Tile/pallas knobs pin
    # the dense implementations (parity tests rely on that); an
    # explicit mesh is honored by sharding the candidate batches.
    from galah_tpu.ops.collision import sparse_screen_min_n

    if (use_pallas is None and row_tile is None and col_tile is None
            and sketch_mat.shape[0] >= sparse_screen_min_n()
            and not os.environ.get("GALAH_TPU_DENSE_PAIRS")):
        from galah_tpu.ops.sparse_device import threshold_pairs_sparse

        m = mesh
        if m is None and jax.device_count() > 1:
            from galah_tpu.parallel.mesh import make_mesh

            m = make_mesh()
        return threshold_pairs_sparse(
            sketch_mat, k=k, min_ani=min_ani, sketch_size=sketch_size,
            mesh=m if (m is not None and m.devices.size > 1) else None)

    # Auto-shard only when the caller left the knobs unset: explicit
    # use_pallas (True OR False) pins the single-device implementation,
    # as does an explicit mesh.
    if mesh is None and use_pallas is None and jax.device_count() > 1:
        from galah_tpu.parallel.mesh import auto_mesh

        mesh = auto_mesh()
    if mesh is not None and mesh.devices.size > 1:
        from galah_tpu.parallel.mesh import sharded_threshold_pairs

        return sharded_threshold_pairs(
            sketch_mat, k=k, min_ani=min_ani, mesh=mesh,
            sketch_size=sketch_size,
            row_tile=row_tile, col_tile=col_tile,
            cap_per_row=cap_per_row, use_pallas=use_pallas)

    # The Mosaic kernel by default on TPU; its failures propagate.
    if use_pallas is None:
        from galah_tpu.ops.hll import use_pallas_default

        use_pallas = use_pallas_default()
    # Per-path tile defaults, honoring any explicit caller values: the
    # Mosaic kernel's program covers 8 query rows x all columns of its
    # call, so wider column tiles amortize dispatch overhead (VMEM
    # residency for the reference planes caps the width).
    rt = row_tile if row_tile is not None else (128 if use_pallas else 64)
    ct = col_tile if col_tile is not None else (512 if use_pallas else 128)

    if sketch_size is None:
        sketch_size = sketch_mat.shape[1]
    return _threshold_pairs_single(sketch_mat, k, min_ani, sketch_size,
                                   rt, ct, bool(use_pallas), cap_per_row)


def _threshold_pairs_single(
    sketch_mat: np.ndarray,
    k: int,
    min_ani: float,
    sketch_size: int,
    row_tile: int,
    col_tile: int,
    use_pallas: bool,
    cap_per_row: int,
) -> dict[tuple[int, int], float]:
    n = sketch_mat.shape[0]
    import math

    quantum = math.lcm(row_tile, col_tile)
    n_pad = -(-n // quantum) * quantum
    mat = np.full((n_pad, sketch_mat.shape[1]),
                  np.uint64(SENTINEL), dtype=np.uint64)
    mat[:n] = sketch_mat
    jmat = jnp.asarray(mat)

    j_thr = ani_to_jaccard(min_ani, k)
    # Conservative device-side prefilter: exact f64 check happens on host
    # over the sparse survivors, so borderline pairs are never lost to
    # accumulated device rounding.
    j_thr_lo = jnp.float64(j_thr * (1.0 - 1e-12) - 1e-300)

    from galah_tpu.ops.compact import iter_blocks

    def run_block(r0, cap):
        timing.dispatch()
        return _rowblock_candidates(
            jmat, jnp.int32(r0), j_thr_lo,
            sketch_size=sketch_size, k=k, row_tile=row_tile,
            col_tile=col_tile, cap=cap, n=n,
            use_pallas=use_pallas)

    out: dict[tuple[int, int], float] = {}
    for r0, (flat_idx, common, total, count) in iter_blocks(
            n, row_tile, cap_per_row, run_block):
        timing.dispatch(sync=True)
        count = int(count)
        flat_idx = np.asarray(flat_idx)[:count]
        common = np.asarray(common)[:count].astype(np.int64)
        total = np.asarray(total)[:count].astype(np.int64)

        # exact host-side threshold + ANI
        keep = common.astype(np.float64) >= j_thr * total
        flat_idx, common, total = flat_idx[keep], common[keep], total[keep]
        ani = stats_to_ani_f64(common, total, k)
        gi = r0 + flat_idx // n_pad
        gj = flat_idx % n_pad
        for a, b, v in zip(gi.tolist(), gj.tolist(), ani.tolist()):
            out[(int(a), int(b))] = float(v)
    return out


@functools.partial(
    jax.jit, static_argnames=("sketch_size", "k", "row_tile"))
def _stripe_stats(rows_mat: jax.Array, cols_mat: jax.Array,
                  sketch_size: int, k: int,
                  row_tile: int) -> Tuple[jax.Array, jax.Array]:
    """(common, total) int32 of EVERY done row against one incoming
    column block — the per-block device dispatch of the streamed pair
    pass, lax.map over row tiles to bound the vmap intermediates."""
    n_rt = rows_mat.shape[0] // row_tile

    def one_tile(t):
        rows = jax.lax.dynamic_slice_in_dim(
            rows_mat, t * row_tile, row_tile, axis=0)
        c, tt = tile_stats(rows, cols_mat, sketch_size, k)
        return c.astype(jnp.int32), tt.astype(jnp.int32)

    c, t = jax.lax.map(one_tile, jnp.arange(n_rt))
    b = cols_mat.shape[0]
    return c.reshape(n_rt * row_tile, b), t.reshape(n_rt * row_tile, b)


def iter_threshold_pairs_streamed(
    blocks_iter,
    n: int,
    k: int,
    min_ani: float,
    sketch_size: int,
    mesh: "Optional[Mesh]" = None,
    block: int = 256,
    row_tile: int = 64,
):
    """Streamed pair pass as a GENERATOR: consume (r0, rows) sketch
    blocks (ops/sketch_stream.iter_sketch_row_blocks) and, per block,
    yield `(r1, increment)` where `increment` maps surviving (i, j)
    pairs with j < r1 that were first resolvable on this stripe. The
    union of all increments is IDENTICAL to
    `threshold_pairs(full_matrix, ...)` by construction: every i<j
    pair is covered exactly once (rows [0, r1) x cols [r0, r1),
    filtered to i < j), and the exact f64 integer-Jaccard check runs
    on host over the integer stats.

    Yielding per block is what lets a downstream consumer (the
    overlapped cluster engine) act on the prefix [0, r1) — whose pair
    neighborhood is COMPLETE at that point — while later genomes are
    still being ingested and sketched.

    Done-row counts are padded to powers of two (>= the tiling
    quantum) to bound the jit variants at O(log n); sentinel padding
    rows/cols are killed by the `common > 0` guard (a sentinel row
    intersects nothing). With a multi-device `mesh`, each stripe is
    computed with rows sharded over the mesh
    (parallel/mesh.sharded_stripe_stats) — bit-identical integers
    either way.

    Emits the stage="pairs" `workload.pipeline_occupancy` gauge on
    exhaustion: the fraction of this stage's wall spent working (vs
    blocked waiting on the upstream sketch stream).
    """
    j_thr = ani_to_jaccard(min_ani, k)
    n_dev = mesh.devices.size if mesh is not None else 1
    if n_dev > 1 and (n_dev & (n_dev - 1)):
        # non-pow2 mesh would break the pow2 row padding below; the
        # single-device stripe is always correct, just unsharded
        mesh, n_dev = None, 1
    quantum = row_tile * n_dev

    from galah_tpu.obs import flow as obs_flow
    from galah_tpu.obs import metrics as obs_metrics

    done = np.full((n, sketch_size), np.uint64(SENTINEL),
                   dtype=np.uint64)
    r1 = 0
    stripes = 0
    t_start = time.monotonic()
    wait_s = 0.0
    blocks = iter(blocks_iter)
    while True:
        # blocked on the upstream sketch stream (obs/flow records it
        # as the pairs stage's upstream-empty wait)
        with obs_flow.blocked("pairs", "upstream-empty") as bw:
            try:
                r0, rows = next(blocks)
            except StopIteration:
                break
        wait_s += bw.seconds
        obs_flow.absorb("sketch", "pairs")
        t_block = time.monotonic()
        bsz = rows.shape[0]
        assert r0 == r1, f"streamed blocks out of order: {r0} != {r1}"
        done[r0:r0 + bsz] = rows
        r1 = r0 + bsz

        # pow2 (>= quantum) done-row padding and fixed column width:
        # O(log n) distinct dispatch shapes across the whole stream.
        r_pad = quantum
        while r_pad < r1:
            r_pad <<= 1
        cols = np.full((block, sketch_size), np.uint64(SENTINEL),
                       dtype=np.uint64)
        cols[:bsz] = rows
        with obs_flow.blocked("pairs", "device-dispatch") as bdev:
            timing.dispatch()
            if mesh is not None:
                from galah_tpu.parallel.mesh import sharded_stripe_stats

                common, total = sharded_stripe_stats(
                    done[:r1], cols, sketch_size=sketch_size, k=k,
                    mesh=mesh, row_tile=row_tile, r_pad=r_pad)
            else:
                jrows = jnp.asarray(
                    np.vstack([done[:r1],
                               np.full((r_pad - r1, sketch_size),
                                       np.uint64(SENTINEL), np.uint64)]))
                common, total = _stripe_stats(
                    jrows, jnp.asarray(cols), sketch_size=sketch_size,
                    k=k, row_tile=row_tile)
            timing.dispatch(sync=True)
        stripes += 1

        common = np.asarray(common).astype(np.int64)
        total = np.asarray(total).astype(np.int64)
        gi = np.arange(common.shape[0])[:, None]
        gj = r0 + np.arange(block)[None, :]
        # exact host-side threshold + ANI; common > 0 kills sentinel
        # padding rows/cols (and the degenerate empty-sketch pairs,
        # matching the dense paths' device prefilter)
        keep = ((gi < gj) & (gj < r1) & (common > 0)
                & (common.astype(np.float64) >= j_thr * total))
        ki, kj = np.nonzero(keep)
        ani = stats_to_ani_f64(common[keep], total[keep], k)
        inc: dict[tuple[int, int], float] = {}
        for a, b, v in zip(ki.tolist(), (r0 + kj).tolist(),
                           ani.tolist()):
            inc[(int(a), int(b))] = float(v)
        # host post-processing time = stripe wall minus the device
        # bracket (the upstream wait is already excluded)
        obs_flow.record_service(
            "pairs", max(time.monotonic() - t_block - bdev.seconds,
                         0.0))
        efid = obs_flow.begin("edge_stripe")
        obs_flow.emit("pairs", efid)
        yield r1, inc
        # live gauge refresh (heartbeat samples the time-series)
        wall_now = time.monotonic() - t_start
        if wall_now > 0:
            obs_metrics.pipeline_occupancy(1.0 - wait_s / wall_now,
                                           stage="pairs")
    if r1 != n:
        raise ValueError(
            f"streamed pair pass saw {r1} rows, expected {n}")
    timing.counter("pairs-streamed-stripes", stripes)
    wall = time.monotonic() - t_start
    if wall > 0 and stripes:
        obs_metrics.pipeline_occupancy(1.0 - wait_s / wall,
                                       stage="pairs")


def threshold_pairs_streamed(
    blocks_iter,
    n: int,
    k: int,
    min_ani: float,
    sketch_size: int,
    mesh: "Optional[Mesh]" = None,
    block: int = 256,
    row_tile: int = 64,
) -> dict[tuple[int, int], float]:
    """`threshold_pairs` over an ARRIVING sketch stream — drains
    `iter_threshold_pairs_streamed` into one dict. The result is
    IDENTICAL to `threshold_pairs(full_matrix, ...)`; see the
    generator's docstring for the exactness argument."""
    out: dict[tuple[int, int], float] = {}
    for _r1, inc in iter_threshold_pairs_streamed(
            blocks_iter, n, k=k, min_ani=min_ani,
            sketch_size=sketch_size, mesh=mesh, block=block,
            row_tile=row_tile):
        out.update(inc)
    return out
