"""Device-mesh helpers: the distributed execution layer.

`sharded_threshold_pairs` is the production sparse precluster pass: for
each row block, ONE SPMD dispatch computes the block's (common, total)
stripe with columns sharded over the mesh, thresholds conservatively and
compacts on device, and returns per-device candidate lists; the host
applies the exact f64 check. `sharded_pair_count` is the reduction-only
variant used by benchmarks and the multi-chip dry run.

The reference's only parallel runtime is a rayon thread pool over shared
memory (reference: src/cluster_argument_parsing.rs:409-412 and the
par_iter sites catalogued in SURVEY.md §2.3). The TPU-native equivalent is
a JAX device mesh: the sketch matrix is sharded by genome row, each device
computes its row block of the pair matrix against (replicated or
all-gathered) columns, and XLA collectives reduce the results over ICI.
Multi-host scale-out uses the same code path — `jax.distributed.initialize`
plus a bigger mesh — since shard_map is SPMD over whatever mesh it's given.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from galah_tpu.obs.profile import profiled
from jax.sharding import Mesh, PartitionSpec as P

logger = logging.getLogger(__name__)

#: The sharded pair passes must stay bit-identical to the host and
#: single-device paths whatever the mesh geometry: integer tile stats,
#: conservative f64 on-device prefilter, exact f64 host check.
DETERMINISM_CONTRACT = {
    "family": "mesh",
    "dtype": "float64",
    "functions": [
        "tile2d_stats",
        "sharded_threshold_pairs",
        "_sharded_threshold_pairs_impl",
        "sharded_stripe_stats_2d",
    ],
}


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = "i") -> Mesh:
    """1-D mesh over the first n (default: all) local devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


# ---------------------------------------------------------------------------
# 2D tiled meshes (GALAH_TPU_MESH_SHAPE, docs/DISTRIBUTED.md)
# ---------------------------------------------------------------------------


def _squarest_factorization(n: int) -> Tuple[int, int]:
    r = int(math.isqrt(n))
    while n % r:
        r -= 1
    return r, n // r


def _demote_mesh_shape(raw: str, n: int, reason: str) -> None:
    from galah_tpu.obs import events, metrics as obs_metrics

    events.record("mesh-demoted", shape=raw, n_devices=n, reason=reason)
    obs_metrics.counter(
        "mesh.demoted_1d",
        help="2D mesh requests demoted to the 1-D fallback "
             "(non-factorable device count or a shape that does not "
             "cover it)").inc()
    logger.warning("mesh shape %r demoted to 1-D over %d devices: %s",
                   raw, n, reason)


def resolve_mesh_shape(
        n_devices: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """(rows, cols) of the 2D tiled mesh, or None for the 1-D fallback.

    GALAH_TPU_MESH_SHAPE: 'auto' picks the squarest factorization of
    the device count (1-D when the count is 1 or prime — with a
    mesh-demoted event for the prime case), '1d' pins the single-axis
    mesh, 'RxC' pins that exact shape (a shape that does not cover the
    device count demotes to 1-D with an event rather than crashing a
    run over a config typo).
    """
    from galah_tpu.config import env_value

    n = len(jax.devices()) if n_devices is None else n_devices
    raw = (env_value("GALAH_TPU_MESH_SHAPE") or "auto").strip().lower()
    if raw in ("1d", "1"):
        return None
    if raw == "auto":
        if n < 2:
            return None
        r, c = _squarest_factorization(n)
        if r == 1:
            _demote_mesh_shape(
                raw, n, "device count has no non-trivial factorization")
            return None
        return r, c
    try:
        r_s, _, c_s = raw.partition("x")
        r, c = int(r_s), int(c_s)
    except ValueError:
        _demote_mesh_shape(
            raw, n, "unparseable shape (want 'auto', '1d' or 'RxC')")
        return None
    if r < 1 or c < 1 or r * c != n:
        _demote_mesh_shape(
            raw, n, f"{r}x{c} does not cover {n} devices")
        return None
    return r, c


def make_mesh_2d(shape: Tuple[int, int],
                 n_devices: Optional[int] = None) -> Mesh:
    """2D ("row", "col") mesh over the first r*c local devices."""
    r, c = shape
    devs = jax.devices()[:r * c]
    return Mesh(np.array(devs).reshape(r, c), ("row", "col"))


def auto_mesh(n_devices: Optional[int] = None) -> Mesh:
    """The mesh the distance passes should run on: the 2D tiled shape
    GALAH_TPU_MESH_SHAPE resolves to, else the 1-D fallback."""
    shape = resolve_mesh_shape(n_devices)
    if shape is not None:
        return make_mesh_2d(shape, n_devices)
    return make_mesh(n_devices)


def mesh_is_2d(mesh) -> bool:
    return mesh is not None and "row" in mesh.axis_names


def _dcn_crossings(mesh) -> int:
    """Interconnect hops each sketch row makes in one all-pairs pass.

    1-D: every row is replicated to every other device (n_dev - 1
    crossings). 2D tiled: a row is replicated only along its mesh row
    (as tile rows) and its mesh column (as tile columns) —
    (r - 1) + (c - 1) crossings, the communication-avoiding win.
    """
    if mesh_is_2d(mesh):
        r, c = mesh.devices.shape
        return (r - 1) + (c - 1)
    return mesh.devices.size - 1


def _emit_dcn_gauge(mesh, row_bytes: int) -> None:
    from galah_tpu.obs import metrics as obs_metrics

    obs_metrics.gauge(
        "mesh.dcn_bytes_per_row",
        help="Modeled interconnect bytes each sketch row crosses in "
             "one all-pairs pass: row bytes x mesh crossings "
             "(n_dev - 1 on the 1-D mesh, (r-1)+(c-1) on the 2D "
             "tiled mesh)",
        unit="bytes").set(float(_dcn_crossings(mesh) * row_bytes))


@profiled("mesh.tile2d_stats")
@functools.partial(jax.jit, static_argnames=("sketch_size", "k"))
def tile2d_stats(rows: jax.Array, cols: jax.Array,
                 sketch_size: int, k: int):
    """(common, total) int32 stats of one (row tile x col tile) lattice
    tile — the per-device unit of the 2D tiled passes. A thin jitted
    wrapper over ops/pairwise.tile_stats so the profiler and the shape
    lattice cover the 2D kernel as its own entry point; the integers
    are bit-identical to every other stats path."""
    from galah_tpu.ops.pairwise import tile_stats

    c, t = tile_stats(rows, cols, sketch_size, k)
    return c.astype(jnp.int32), t.astype(jnp.int32)


def sharded_pair_count(
    sketch_mat: np.ndarray,
    k: int,
    min_ani: float,
    mesh: Mesh,
    col_tile: int = 64,
    row_tile: Optional[int] = None,
    use_pallas: Optional[bool] = None,
) -> int:
    """Count i<j sketch pairs with ANI >= min_ani, fully on-mesh.

    One SPMD program: rows sharded over the mesh axis, per-device
    (row tile x col tile) loop over its row shard against all columns,
    upper-triangle mask via global row/col ids, and a `psum` over ICI
    producing the replicated global count. Tiling both axes bounds the
    (row_tile, col_tile, sketch) intermediates regardless of shard size,
    so a single dispatch covers any N. This is the collective-reduction
    pattern the bigger pipelines reuse (and what dryrun_multichip
    exercises on a virtual mesh).
    """
    from galah_tpu.ops.constants import SENTINEL
    from galah_tpu.ops.hll import use_pallas_default
    from galah_tpu.ops.pairwise import ani_to_jaccard, tile_stats

    if use_pallas is None:
        use_pallas = use_pallas_default()
    if use_pallas:
        col_tile = max(col_tile, 128)

    n = sketch_mat.shape[0]
    n_dev = mesh.devices.size
    import math

    if row_tile is None:
        row_tile = min(64, col_tile) if not use_pallas else 128
    quantum = math.lcm(n_dev * row_tile, col_tile)
    pad_n = -(-n // quantum) * quantum
    mat = np.full((pad_n, sketch_mat.shape[1]), np.uint64(SENTINEL),
                  dtype=np.uint64)
    mat[:n] = sketch_mat
    j_thr = jnp.float32(ani_to_jaccard(min_ani, k))
    sketch_size = sketch_mat.shape[1]

    if use_pallas:
        from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

        def stats_fn(rows, cols):
            return tile_stats_pallas(rows, cols, sketch_size)
    else:
        def stats_fn(rows, cols):
            return tile_stats(rows, cols, sketch_size, k)

    def spmd(rows_block, all_cols):
        block = rows_block.shape[0]
        row0 = jax.lax.axis_index("i") * block
        n_rt = block // row_tile
        n_ct = all_cols.shape[0] // col_tile

        def one_tile(t):
            tr = t // n_ct
            tc = t % n_ct
            rows = jax.lax.dynamic_slice_in_dim(
                rows_block, tr * row_tile, row_tile, axis=0)
            cols = jax.lax.dynamic_slice_in_dim(
                all_cols, tc * col_tile, col_tile, axis=0)
            common, total = stats_fn(rows, cols)
            passing = (common.astype(jnp.float32)
                       >= j_thr * total.astype(jnp.float32))
            passing = passing & (common > 0)
            gi = row0 + tr * row_tile + jnp.arange(row_tile)[:, None]
            gj = tc * col_tile + jnp.arange(col_tile)[None, :]
            mask = (gi < gj) & (gj < n) & (gi < n)
            return jnp.sum((passing & mask).astype(jnp.int32))

        local = jnp.sum(jax.lax.map(one_tile, jnp.arange(n_rt * n_ct)))
        return jax.lax.psum(local, "i")

    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P("i", None), P(None, None)),
        out_specs=P(),
    )
    return int(jax.jit(fn)(jnp.asarray(mat), jnp.asarray(mat)))


def sharded_stripe_stats(
    rows_mat: np.ndarray,
    cols_mat: np.ndarray,
    sketch_size: int,
    k: int,
    mesh: Mesh,
    row_tile: int = 64,
    r_pad: Optional[int] = None,
):
    """(common, total) int32 of every done row against one incoming
    column block, rows sharded over the mesh — the SPMD twin of
    ops/pairwise._stripe_stats for the streamed pair pass. Each device
    lax.maps over the row tiles of its contiguous row shard against the
    (replicated) column block; the integers are bit-identical to the
    single-device stripe. `r_pad` must be a multiple of
    mesh_size * row_tile (the caller's pow2 padding guarantees it for
    pow2 meshes). A 2D ("row", "col") mesh dispatches to the tiled
    twin (rows sharded over mesh rows, the column block over mesh
    columns)."""
    from galah_tpu.ops.constants import SENTINEL
    from galah_tpu.ops.pairwise import tile_stats

    if mesh_is_2d(mesh):
        return sharded_stripe_stats_2d(
            rows_mat, cols_mat, sketch_size, k, mesh,
            row_tile=row_tile, r_pad=r_pad)
    n_dev = mesh.devices.size
    if r_pad is None:
        q = n_dev * row_tile
        r_pad = -(-rows_mat.shape[0] // q) * q
    if r_pad % (n_dev * row_tile):
        raise ValueError(
            f"r_pad {r_pad} not a multiple of mesh size {n_dev} x "
            f"row_tile {row_tile}")
    mat = np.full((r_pad, rows_mat.shape[1]), np.uint64(SENTINEL),
                  dtype=np.uint64)
    mat[:rows_mat.shape[0]] = rows_mat

    def spmd(rows_shard, cols):
        n_rt = rows_shard.shape[0] // row_tile

        def one_tile(t):
            rows = jax.lax.dynamic_slice_in_dim(
                rows_shard, t * row_tile, row_tile, axis=0)
            c, tt = tile_stats(rows, cols, sketch_size, k)
            return c.astype(jnp.int32), tt.astype(jnp.int32)

        c, t = jax.lax.map(one_tile, jnp.arange(n_rt))
        b = cols.shape[0]
        return (c.reshape(n_rt * row_tile, b),
                t.reshape(n_rt * row_tile, b))

    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P("i", None), P(None, None)),
        out_specs=(P("i", None), P("i", None)),
    )
    _emit_dcn_gauge(mesh, cols_mat.shape[1] * cols_mat.dtype.itemsize)
    return jax.jit(fn)(jnp.asarray(mat), jnp.asarray(cols_mat))


def sharded_stripe_stats_2d(
    rows_mat: np.ndarray,
    cols_mat: np.ndarray,
    sketch_size: int,
    k: int,
    mesh: Mesh,
    row_tile: int = 64,
    r_pad: Optional[int] = None,
):
    """2D tiled twin of sharded_stripe_stats: done rows sharded over
    mesh rows, the incoming column block sharded over mesh columns, so
    each device computes its (row shard x column chunk) tile and a row
    is replicated along exactly one mesh axis instead of to every
    device. The assembled (r_pad, block) integer stripes are
    bit-identical to the 1-D and single-device paths (tile_stats is
    elementwise per pair)."""
    from galah_tpu.ops.constants import SENTINEL

    r, c = mesh.devices.shape
    block = cols_mat.shape[0]
    if block % c:
        raise ValueError(
            f"column block {block} not divisible by mesh cols {c}")
    q = r * row_tile
    if r_pad is None:
        r_pad = -(-rows_mat.shape[0] // q) * q
    if r_pad % q:
        raise ValueError(
            f"r_pad {r_pad} not a multiple of mesh rows {r} x "
            f"row_tile {row_tile}")
    mat = np.full((r_pad, rows_mat.shape[1]), np.uint64(SENTINEL),
                  dtype=np.uint64)
    mat[:rows_mat.shape[0]] = rows_mat

    def spmd(rows_shard, cols_shard):
        n_rt = rows_shard.shape[0] // row_tile

        def one_tile(t):
            rows = jax.lax.dynamic_slice_in_dim(
                rows_shard, t * row_tile, row_tile, axis=0)
            return tile2d_stats(rows, cols_shard, sketch_size, k)

        cm, tt = jax.lax.map(one_tile, jnp.arange(n_rt))
        b = cols_shard.shape[0]
        return (cm.reshape(n_rt * row_tile, b),
                tt.reshape(n_rt * row_tile, b))

    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P("row", None), P("col", None)),
        out_specs=(P("row", "col"), P("row", "col")),
    )
    _emit_dcn_gauge(mesh, cols_mat.shape[1] * cols_mat.dtype.itemsize)
    return jax.jit(fn)(jnp.asarray(mat), jnp.asarray(cols_mat))


def _sharded_blocked_extract(
    mesh: Mesh,
    arrays,              # tuple of replicated device arrays
    n: int,
    n_pad: int,
    row_tile: int,
    col_tile: int,
    cap_per_row: int,
    slice_rows,          # (arrays, r0) -> per-block row context
    compute_tile,        # (arrays, rows_ctx, gt) -> tuple of stripes
    stripe_dtypes,       # dtypes of compute_tile's outputs (for skips)
    stripe_mask,         # (stripes, ) -> bool pass mask (thresholding)
):
    """Core of the column-sharded sparse extractions.

    One SPMD dispatch per row block: every device computes the block's
    stripes against its contiguous column range tile by tile (lax.cond
    skips tiles entirely below the diagonal), applies `stripe_mask`
    plus the upper-triangle/bounds mask, and compacts passing entries
    to a fixed capacity on device. The compacted per-device outputs are
    all-gathered inside the SPMD program and returned REPLICATED, so a
    multi-host run (where per-device shards are not host-addressable)
    reads the same arrays as a single host — every host sees every
    device's candidates and produces the identical pair set. Yields
    (gi, gj, payloads) numpy arrays per (row block, device); overflow
    retry policy comes from ops/compact.iter_blocks.
    """
    from galah_tpu.ops.compact import iter_blocks

    n_dev = mesh.devices.size
    cols_per_dev = n_pad // n_dev
    tiles_per_dev = cols_per_dev // col_tile
    n_payload = len(stripe_dtypes)

    def spmd(*args):
        *arrs, r0, cap = args
        dev = jax.lax.axis_index("i")
        col0 = dev * cols_per_dev
        rows_ctx = slice_rows(arrs, r0)
        t_first = r0 // col_tile

        def one_tile(t):
            gt = col0 // col_tile + t

            def compute(_):
                return tuple(compute_tile(arrs, rows_ctx, gt))

            def skip(_):
                # pcast marks the constant zeros as device-varying so
                # the cond branches type-check under shard_map's vma
                # typing.
                return tuple(
                    jax.lax.pcast(jnp.zeros((row_tile, col_tile), dt),
                                  "i", to="varying")
                    for dt in stripe_dtypes)

            return jax.lax.cond(gt >= t_first, compute, skip, None)

        stripes = jax.lax.map(one_tile, jnp.arange(tiles_per_dev))
        stripes = tuple(
            jnp.transpose(s, (1, 0, 2)).reshape(row_tile, cols_per_dev)
            for s in stripes)

        gi = r0 + jnp.arange(row_tile)[:, None]
        gj = col0 + jnp.arange(cols_per_dev)[None, :]
        mask = stripe_mask(stripes) & (gi < gj) & (gj < n)
        count = jnp.sum(mask.astype(jnp.int32))
        (flat_idx,) = jnp.nonzero(mask.ravel(), size=cap, fill_value=-1)
        safe = jnp.maximum(flat_idx, 0)
        payloads = tuple(jnp.take(s.ravel(), safe) for s in stripes)
        # Replicate the (tiny) compacted results to every device so a
        # multi-host run can read them from any host: (n_dev, cap) per
        # payload, (n_dev,) counts.
        gather = functools.partial(jax.lax.all_gather, axis_name="i")
        return (gather(flat_idx), *map(gather, payloads), gather(count))

    @functools.partial(jax.jit, static_argnames=("cap",))
    def run_block(*args, cap):
        in_specs = tuple(P(*([None] * a.ndim)) for a in arrays) + (P(),)
        # check_vma off: the outputs ARE replicated (each is an
        # all_gather result, identical on every device), but the vma
        # type system cannot express post-gather invariance for P()
        # out_specs (pcast has no varying->invariant direction).
        fn = jax.shard_map(
            functools.partial(lambda *a, cap: spmd(*a, cap), cap=cap),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=tuple(P() for _ in range(n_payload + 2)),
            check_vma=False,
        )
        return fn(*args)

    for r0, result in iter_blocks(
            n, row_tile, cap_per_row,
            lambda r0, cap: run_block(*arrays, jnp.int32(r0), cap=cap)):
        flat_idx = np.asarray(result[0])
        payloads = [np.asarray(p) for p in result[1:-1]]
        counts = np.asarray(result[-1])
        for dev in range(n_dev):
            cnt = int(counts[dev])
            fi = flat_idx[dev, :cnt]
            gi = r0 + fi // cols_per_dev
            gj = dev * cols_per_dev + fi % cols_per_dev
            yield gi, gj, tuple(p[dev, :cnt] for p in payloads)


def _sharded_blocked_extract_2d(
    mesh: Mesh,
    arrays,              # tuple of full (padded) device arrays
    n: int,
    n_pad: int,
    row_tile: int,
    col_tile: int,
    cap_per_row: int,
    slice_rows,          # (row_shards, local_r0) -> per-block row ctx
    compute_tile,        # (col_shards, rows_ctx, local_t) -> stripes
    stripe_dtypes,       # dtypes of compute_tile's outputs (for skips)
    stripe_mask,         # (stripes, ) -> bool pass mask (thresholding)
):
    """2D tiled twin of _sharded_blocked_extract.

    Each device owns one (row shard x column shard) tile of the pair
    lattice: every array is passed twice, once sharded over mesh rows
    (the row context) and once over mesh columns (the tile columns),
    so a sketch row is replicated along exactly one mesh row and one
    mesh column — (r-1)+(c-1) interconnect crossings instead of the
    1-D path's n_dev-1. One SPMD dispatch covers local row-block `lb`
    on EVERY device at once (mesh row i works global rows
    i*rows_per_dev + lb ..); the per-tile lax.cond skips tiles
    entirely below the diagonal, which prunes the redundant
    lower-triangle half of the lattice. The same closures as the 1-D
    core apply (slices address the LOCAL shard at LOCAL offsets), so
    the integers — and therefore the extracted pair set — are
    bit-identical. Yields (gi, gj, payloads) per (row block, device).
    """
    from galah_tpu.ops.compact import iter_blocks

    r, c = mesh.devices.shape
    rows_per_dev = n_pad // r
    cols_per_dev = n_pad // c
    tiles_per_dev = cols_per_dev // col_tile
    n_arr = len(arrays)
    n_payload = len(stripe_dtypes)

    def spmd(*args):
        *arrs, lb, cap = args
        row_arrs, col_arrs = arrs[:n_arr], arrs[n_arr:]
        mi = jax.lax.axis_index("row")
        mj = jax.lax.axis_index("col")
        r0 = mi * rows_per_dev + lb
        col0 = mj * cols_per_dev
        rows_ctx = slice_rows(row_arrs, lb)
        t_first = r0 // col_tile

        def one_tile(t):
            gt = col0 // col_tile + t

            def compute(_):
                return tuple(compute_tile(col_arrs, rows_ctx, t))

            def skip(_):
                return tuple(
                    jax.lax.pcast(jax.lax.pcast(
                        jnp.zeros((row_tile, col_tile), dt),
                        "row", to="varying"), "col", to="varying")
                    for dt in stripe_dtypes)

            return jax.lax.cond(gt >= t_first, compute, skip, None)

        stripes = jax.lax.map(one_tile, jnp.arange(tiles_per_dev))
        stripes = tuple(
            jnp.transpose(s, (1, 0, 2)).reshape(row_tile, cols_per_dev)
            for s in stripes)

        gi = r0 + jnp.arange(row_tile)[:, None]
        gj = col0 + jnp.arange(cols_per_dev)[None, :]
        mask = stripe_mask(stripes) & (gi < gj) & (gj < n)
        count = jnp.sum(mask.astype(jnp.int32))
        (flat_idx,) = jnp.nonzero(mask.ravel(), size=cap, fill_value=-1)
        safe = jnp.maximum(flat_idx, 0)
        payloads = tuple(jnp.take(s.ravel(), safe) for s in stripes)

        # Replicate the (tiny) compacted results to every device —
        # (r, c, cap) per payload, (r, c) counts — same multi-host
        # rationale as the 1-D core.
        def gather(x):
            x = jax.lax.all_gather(x, axis_name="col")
            return jax.lax.all_gather(x, axis_name="row")

        return (gather(flat_idx), *map(gather, payloads), gather(count))

    @functools.partial(jax.jit, static_argnames=("cap",))
    def run_block(*args, cap):
        in_specs = (
            tuple(P(*(["row"] + [None] * (a.ndim - 1)))
                  for a in arrays)
            + tuple(P(*(["col"] + [None] * (a.ndim - 1)))
                    for a in arrays)
            + (P(),))
        # check_vma off for the same reason as the 1-D core: the
        # all_gather outputs ARE replicated but the vma type system
        # cannot express post-gather invariance for P() out_specs.
        fn = jax.shard_map(
            functools.partial(lambda *a, cap: spmd(*a, cap), cap=cap),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=tuple(P() for _ in range(n_payload + 2)),
            check_vma=False,
        )
        return fn(*args)

    # Local row blocks with lb >= n are empty on every mesh row (mesh
    # row 0 starts at lb; higher rows start even later), so the block
    # loop is bounded by min(rows_per_dev, n).
    for lb, result in iter_blocks(
            min(rows_per_dev, n), row_tile, cap_per_row,
            lambda lb, cap: run_block(*arrays, *arrays, jnp.int32(lb),
                                      cap=cap)):
        flat_idx = np.asarray(result[0])
        payloads = [np.asarray(p) for p in result[1:-1]]
        counts = np.asarray(result[-1])
        for mi in range(r):
            for mj in range(c):
                cnt = int(counts[mi, mj])
                fi = flat_idx[mi, mj, :cnt]
                gi = mi * rows_per_dev + lb + fi // cols_per_dev
                gj = mj * cols_per_dev + fi % cols_per_dev
                yield gi, gj, tuple(p[mi, mj, :cnt] for p in payloads)


def sharded_threshold_pairs(
    sketch_mat: np.ndarray,
    k: int,
    min_ani: float,
    mesh: Mesh,
    sketch_size: Optional[int] = None,
    row_tile: Optional[int] = None,
    col_tile: Optional[int] = None,
    cap_per_row: int = 64,
    use_pallas: Optional[bool] = None,
) -> dict:
    """Sparse {(i, j): ani} for i<j pairs with ani >= min_ani, columns
    sharded over the mesh.

    The multi-device twin of ops/pairwise.threshold_pairs: the blocked
    extraction core computes (common, total) stats stripes per device,
    prefilters with a conservative f64 threshold on device, and the
    host applies the exact f64 integer-Jaccard check over the sparse
    survivors. One dispatch per row block regardless of mesh size.
    With use_pallas (the default on a TPU backend) each device's stats
    tiles run the Mosaic kernel instead of the XLA searchsorted path —
    bit-identical integers either way.
    """
    from galah_tpu.ops.hll import use_pallas_default

    if use_pallas is None:
        use_pallas = use_pallas_default()
    if use_pallas:
        try:
            return _sharded_threshold_pairs_impl(
                sketch_mat, k, min_ani, mesh, sketch_size,
                row_tile if row_tile is not None else 128,
                col_tile if col_tile is not None else 128,
                cap_per_row, True)
        except Exception:
            # A Mosaic lowering failure must not take down the
            # multi-device production path either (the single-device
            # twin has the same fallback).
            import logging

            logging.getLogger(__name__).warning(
                "Pallas pair-stats kernel unavailable on the sharded "
                "path; falling back to XLA", exc_info=True)
    return _sharded_threshold_pairs_impl(
        sketch_mat, k, min_ani, mesh, sketch_size,
        row_tile if row_tile is not None else 64,
        col_tile if col_tile is not None else 128,
        cap_per_row, False)


def _sharded_threshold_pairs_impl(
    sketch_mat: np.ndarray,
    k: int,
    min_ani: float,
    mesh: Mesh,
    sketch_size: Optional[int],
    row_tile: int,
    col_tile: int,
    cap_per_row: int,
    use_pallas: bool,
) -> dict:
    import math

    from galah_tpu.ops.constants import SENTINEL
    from galah_tpu.ops.pairwise import (
        ani_to_jaccard,
        stats_to_ani_f64,
        tile_stats,
    )

    n = sketch_mat.shape[0]
    if sketch_size is None:
        sketch_size = sketch_mat.shape[1]
    two_d = mesh_is_2d(mesh)
    if two_d:
        r, c = mesh.devices.shape
        quantum = math.lcm(r * row_tile, c * col_tile)
    else:
        quantum = math.lcm(mesh.devices.size * col_tile, row_tile)
    n_pad = -(-n // quantum) * quantum
    mat = np.full((n_pad, sketch_mat.shape[1]), np.uint64(SENTINEL),
                  dtype=np.uint64)
    mat[:n] = sketch_mat
    jmat = jnp.asarray(mat)
    _emit_dcn_gauge(mesh, sketch_mat.shape[1]
                    * sketch_mat.dtype.itemsize)

    j_thr = ani_to_jaccard(min_ani, k)
    j_thr_lo = j_thr * (1.0 - 1e-12) - 1e-300

    def slice_rows(arrs, r0):
        return jax.lax.dynamic_slice_in_dim(arrs[0], r0, row_tile, axis=0)

    if use_pallas:
        from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

        def stats_fn(rows, cols):
            return tile_stats_pallas(rows, cols, sketch_size)
    elif two_d:
        def stats_fn(rows, cols):
            return tile2d_stats(rows, cols, sketch_size, k)
    else:
        def stats_fn(rows, cols):
            return tile_stats(rows, cols, sketch_size, k)

    def compute_tile(arrs, rows, gt):
        cols = jax.lax.dynamic_slice_in_dim(
            arrs[0], gt * col_tile, col_tile, axis=0)
        c, t = stats_fn(rows, cols)
        return c.astype(jnp.int32), t.astype(jnp.int32)

    def stripe_mask(stripes):
        common, total = stripes
        mask = (common.astype(jnp.float64)
                >= jnp.float64(j_thr_lo) * total.astype(jnp.float64))
        return mask & (common > 0)

    extract = (_sharded_blocked_extract_2d if two_d
               else _sharded_blocked_extract)
    out: dict = {}
    for gi, gj, (common, total) in extract(
            mesh, (jmat,), n, n_pad, row_tile, col_tile, cap_per_row,
            slice_rows, compute_tile, (jnp.int32, jnp.int32),
            stripe_mask):
        common = common.astype(np.int64)
        total = total.astype(np.int64)
        keep = common.astype(np.float64) >= j_thr * total
        gi, gj = gi[keep], gj[keep]
        ani = stats_to_ani_f64(common[keep], total[keep], k)
        for a, b, v in zip(gi.tolist(), gj.tolist(), ani.tolist()):
            out[(int(a), int(b))] = float(v)
    return out


def sharded_screen_pairs(
    marker_mat: np.ndarray,
    counts: np.ndarray,
    c_floor: float,
    mesh: Mesh,
    row_tile: int = 64,
    col_tile: int = 256,
    cap_per_row: int = 256,
    use_pallas: Optional[bool] = None,
) -> list:
    """i<j pairs with marker containment >= c_floor, columns sharded over
    the mesh — the multi-device twin of ops/pairwise.screen_pairs (the
    same blocked extraction core, with the marker-intersection count as
    the tile computation and min-count containment as the threshold)."""
    import math

    from galah_tpu.ops.constants import SENTINEL
    from galah_tpu.ops.pairwise import (_marker_pallas_default,
                                        tile_intersect_counts)

    if use_pallas is None:
        use_pallas = _marker_pallas_default(marker_mat.shape[1])

    n = marker_mat.shape[0]
    two_d = mesh_is_2d(mesh)
    if two_d:
        r, c = mesh.devices.shape
        quantum = math.lcm(r * row_tile, c * col_tile)
    else:
        quantum = math.lcm(mesh.devices.size * col_tile, row_tile)
    n_pad = -(-n // quantum) * quantum
    mat = np.full((n_pad, marker_mat.shape[1]), np.uint64(SENTINEL),
                  dtype=np.uint64)
    mat[:n] = marker_mat
    cnt = np.zeros(n_pad, dtype=np.int32)
    cnt[:n] = counts
    jmat = jnp.asarray(mat)
    jcnt = jnp.asarray(cnt)
    _emit_dcn_gauge(mesh, marker_mat.shape[1]
                    * marker_mat.dtype.itemsize)

    c_floor_lo = c_floor * (1.0 - 1e-12) - 1e-300

    def slice_rows(arrs, r0):
        return (jax.lax.dynamic_slice_in_dim(arrs[0], r0, row_tile,
                                             axis=0),
                jax.lax.dynamic_slice_in_dim(arrs[1], r0, row_tile,
                                             axis=0))

    def compute_tile(arrs, rows_ctx, gt):
        rows, rcnt = rows_ctx
        cols = jax.lax.dynamic_slice_in_dim(
            arrs[0], gt * col_tile, col_tile, axis=0)
        ccnt = jax.lax.dynamic_slice_in_dim(
            arrs[1], gt * col_tile, col_tile, axis=0)
        if use_pallas:
            from galah_tpu.ops.pallas_pairwise import tile_intersect_pallas

            inter = tile_intersect_pallas(rows, cols)
        else:
            inter = tile_intersect_counts(rows, cols).astype(jnp.int32)
        denom = jnp.minimum(rcnt[:, None], ccnt[None, :]).astype(jnp.int32)
        denom = jnp.broadcast_to(denom, inter.shape)
        return inter, denom

    def stripe_mask(stripes):
        inter, denom = stripes
        mask = (inter.astype(jnp.float64)
                >= jnp.float64(c_floor_lo) * denom.astype(jnp.float64))
        return mask & (inter > 0)

    extract = (_sharded_blocked_extract_2d if two_d
               else _sharded_blocked_extract)
    out: list = []
    for gi, gj, (inter, denom) in extract(
            mesh, (jmat, jcnt), n, n_pad, row_tile, col_tile,
            cap_per_row, slice_rows, compute_tile,
            (jnp.int32, jnp.int32), stripe_mask):
        inter = inter.astype(np.float64)
        denom = denom.astype(np.float64)
        # denom > 0 is belt and braces: the stripe mask already
        # requires inter > 0 and inter <= denom, so a denom == 0 pair
        # cannot reach here — the guard keeps this check self-contained
        keep = (denom > 0) & (inter >= c_floor * denom)
        out.extend(zip(gi[keep].tolist(), gj[keep].tolist()))
    out.sort()
    return out


def sharded_hll_threshold_pairs(
    regs_mat: np.ndarray,
    k: int,
    min_ani: float,
    mesh: Mesh,
    row_tile: int = 64,
    col_tile: int = 128,
    cap_per_row: int = 64,
) -> dict:
    """Sparse {(i, j): ani} over HLL register sketches, columns sharded
    over the mesh — the multi-device twin of ops/hll.hll_threshold_pairs
    (the same blocked extraction core, with the HLL union estimator as
    the tile computation)."""
    import math

    from galah_tpu.ops import hll as hll_ops

    n, m = regs_mat.shape
    two_d = mesh_is_2d(mesh)
    if two_d:
        r, c = mesh.devices.shape
        quantum = math.lcm(r * row_tile, c * col_tile)
    else:
        quantum = math.lcm(mesh.devices.size * col_tile, row_tile)
    n_pad = -(-n // quantum) * quantum
    mat = np.zeros((n_pad, m), dtype=np.uint8)
    mat[:n] = regs_mat
    jmat = jnp.asarray(mat)
    cards = hll_ops.hll_cardinality(jmat)
    pow2 = jnp.exp2(-jmat.astype(jnp.float32))
    _emit_dcn_gauge(mesh, m * regs_mat.dtype.itemsize)

    def slice_rows(arrs, r0):
        return (jax.lax.dynamic_slice_in_dim(arrs[0], r0, row_tile,
                                             axis=0),
                jax.lax.dynamic_slice_in_dim(arrs[1], r0, row_tile,
                                             axis=0))

    def compute_tile(arrs, rows_ctx, gt):
        rows, rcards = rows_ctx
        cols = jax.lax.dynamic_slice_in_dim(
            arrs[0], gt * col_tile, col_tile, axis=0)
        ccards = jax.lax.dynamic_slice_in_dim(
            arrs[1], gt * col_tile, col_tile, axis=0)
        powsum, zeros = hll_ops._xla_union_stats(rows, cols)
        return (hll_ops._ani_from_union_stats(
            powsum, zeros, rcards, ccards, k, m),)

    def stripe_mask(stripes):
        return stripes[0] >= jnp.float32(min_ani)

    extract = (_sharded_blocked_extract_2d if two_d
               else _sharded_blocked_extract)
    out: dict = {}
    for gi, gj, (vals,) in extract(
            mesh, (pow2, cards), n, n_pad, row_tile, col_tile,
            cap_per_row, slice_rows, compute_tile, (jnp.float32,),
            stripe_mask):
        for a, b, v in zip(gi.tolist(), gj.tolist(), vals.tolist()):
            out[(int(a), int(b))] = float(v)
    return out
