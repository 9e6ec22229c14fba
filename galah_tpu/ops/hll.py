"""Device HyperLogLog: sketching, cardinality, tiled pairwise union/ANI.

The framework's dashing analog. The reference shells out to the dashing
C++ binary, which HLL-sketches every genome and emits a full N x N
Mash-like distance matrix (reference: src/dashing.rs:33-100). Here the
whole pipeline is on-device JAX:

  * sketching: each canonical k-mer hash h (the same murmur3 pipeline the
    MinHash backend uses) updates register h >> (64-p) with
    rho = clz(h << p) + 1 via a scatter-max — chunked like the MinHash
    sketcher, so any genome length compiles to the same kernels;
  * cardinality: the classic HLL estimator (alpha_m * m^2 / sum 2^-reg)
    with the small-range linear-counting correction;
  * pairwise: |A u B| from the register-wise max of two sketches, Jaccard
    by inclusion-exclusion, then Mash distance d = -ln(2j/(1+j))/k and
    ANI = 1 - d, computed for (row_tile x col_tile) blocks per device
    dispatch.

Unlike dashing's matrix-on-stdout, tiles are thresholded on device and
only surviving sparse pairs reach the host. Exact dashing value parity is
not a goal (different hash; dashing itself is an estimator whose values
differ from finch/skani); cluster-level parity is covered by tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galah_tpu.io.fasta import Genome
from galah_tpu.ops import hashing
from galah_tpu.utils import timing

DEFAULT_P = 12  # 4096 registers: ~1.6% cardinality std error, 4 KiB/genome


def _alpha(m: int) -> float:
    if m >= 128:
        return 0.7213 / (1.0 + 1.079 / m)
    if m == 64:
        return 0.709
    if m == 32:
        return 0.697
    return 0.673


@functools.partial(jax.jit, static_argnames=("p",))
def _hll_update(regs: jax.Array, hashes: jax.Array, p: int) -> jax.Array:
    """Fold a chunk of uint64 hashes into (2^p,) uint8 registers."""
    idx = (hashes >> jnp.uint64(64 - p)).astype(jnp.int32)
    rest = hashes << jnp.uint64(p)
    rho = jnp.minimum(jax.lax.clz(rest) + jnp.uint64(1),
                      jnp.uint64(64 - p + 1)).astype(jnp.uint8)
    # Invalid positions carry HASH_SENTINEL (all ones): rho == 1 there,
    # but their register index is m-1; mask them to rho 0 (a no-op for
    # max) instead.
    rho = jnp.where(hashes == hashing.HASH_SENTINEL, jnp.uint8(0), rho)
    return regs.at[idx].max(rho)


def c_hll_available(k: int, p: int) -> bool:
    """Gate for the compiled-C register walk. It holds on every backend:
    registers are host data (2^p bytes per genome, one pass over it), and
    on a v5e chip the batched device pass of MAG-length genomes laid its
    (8, L) hash rows out 16x padded and asked for twice the chip's HBM."""
    if k > 32 or not 1 <= p <= 24:
        return False
    try:
        from galah_tpu.ops import _csketch  # noqa: F401
    except ImportError:
        return False
    return True


def hll_sketch_genome(
    genome: Genome,
    p: int = DEFAULT_P,
    k: int = 21,
    seed: int = 0,
    chunk: int = hashing.DEFAULT_CHUNK,
    algo: str = "murmur3",
) -> np.ndarray:
    """(2^p,) uint8 HLL registers over the genome's canonical k-mers.

    Where c_hll_available() holds, the compiled-C walker runs
    (csrc/sketch.c::galah_hll_registers, bit-identical); an explicit
    non-default chunk pins the JAX path."""
    if c_hll_available(k, p) and chunk == hashing.DEFAULT_CHUNK:
        from galah_tpu.ops import _csketch

        return _csketch.hll_registers(
            genome.codes, genome.contig_offsets, k=k, p=p,
            seed=seed, algo=algo)
    regs = jnp.zeros((1 << p,), dtype=jnp.uint8)
    for hashes, _pos, _n_new in hashing.iter_chunk_hashes(
            genome.codes, genome.contig_offsets, k=k, chunk=chunk,
            seed=seed, algo=algo):
        regs = _hll_update(regs, hashes, p)
        timing.dispatch()
    timing.dispatch(sync=True)
    return np.asarray(regs)


@functools.partial(jax.jit, static_argnames=("p", "k", "seed", "algo"))
def _batch_hll_kernel(packed, ambits, offsets, p, k, seed, algo):
    """(G, C/4) packed genome rows -> (G, 2^p) uint8 HLL registers in one
    dispatch (vmapped hash + per-row register fold)."""
    h = hashing.canonical_kmer_hashes_batch(
        packed, ambits, offsets, k, seed, algo)
    return jax.vmap(
        lambda hrow: _hll_update(jnp.zeros((1 << p,), jnp.uint8),
                                 hrow, p))(h)


def hll_sketch_genomes_batch(
    genomes,
    p: int = DEFAULT_P,
    k: int = 21,
    seed: int = 0,
    algo: str = "murmur3",
    budget: int = hashing.BATCH_BUDGET,
) -> list:
    """Batch twin of hll_sketch_genome: grouped one-dispatch sketching
    of many genomes (see ops/minhash.sketch_genomes_device_batch for the
    rationale), bit-identical registers per genome."""
    out = [None] * len(genomes)
    skipped, group_iter = hashing.iter_genome_groups(
        genomes, budget=budget, max_len=hashing.DEFAULT_CHUNK)
    for i in skipped:
        out[i] = hll_sketch_genome(genomes[i], p=p, k=k, seed=seed,
                                   algo=algo)
    for chunk_idxs, packed, ambits, offs in group_iter:
        timing.dispatch()
        timing.dispatch(sync=True)
        regs = np.asarray(_batch_hll_kernel(
            jnp.asarray(packed), jnp.asarray(ambits), jnp.asarray(offs),
            p=p, k=k, seed=seed, algo=algo))
        for row, gi in enumerate(chunk_idxs):
            out[gi] = regs[row]
    return out


def _estimate(regs_f32_powsum: jax.Array, zeros: jax.Array,
              m: int) -> jax.Array:
    """HLL estimate from sum(2^-reg) and zero-register count (f32)."""
    raw = jnp.float32(_alpha(m) * m * m) / regs_f32_powsum
    small = raw <= jnp.float32(2.5 * m)
    lc = jnp.float32(m) * jnp.log(
        jnp.float32(m) / jnp.maximum(zeros, jnp.float32(1.0)))
    return jnp.where(small & (zeros > 0), lc, raw)


@jax.jit
def hll_cardinality(regs: jax.Array) -> jax.Array:
    """Cardinality estimate(s): (..., m) uint8 registers -> (...) f32."""
    m = regs.shape[-1]
    pow2 = jnp.exp2(-regs.astype(jnp.float32))
    powsum = jnp.sum(pow2, axis=-1)
    zeros = jnp.sum((regs == 0).astype(jnp.float32), axis=-1)
    return _estimate(powsum, zeros, m)


@functools.partial(jax.jit, static_argnames=("k", "m"))
def _ani_from_union_stats(
    powsum: jax.Array,     # f32 (Br, Bc) sum of 2^-union_reg
    zeros: jax.Array,      # f32 (Br, Bc) count of zero union registers
    row_cards: jax.Array,  # f32 (Br,) precomputed cardinalities
    col_cards: jax.Array,  # f32 (Bc,)
    k: int,
    m: int,
) -> jax.Array:
    u = _estimate(powsum, zeros, m)                  # (Br, Bc)
    inter = row_cards[:, None] + col_cards[None, :] - u
    j = jnp.clip(inter / jnp.maximum(u, jnp.float32(1.0)), 0.0, 1.0)
    ani = 1.0 + jnp.log(2.0 * j / (1.0 + j)) / jnp.float32(k)
    return jnp.where(j > 0, ani, jnp.float32(0.0))


@jax.jit
def _xla_union_stats(rows_pow2: jax.Array,
                     cols_pow2: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """XLA fallback for pallas_hll.hll_union_stats_tile (same contract)."""
    mn = jnp.minimum(rows_pow2[:, None, :], cols_pow2[None, :, :])
    return mn.sum(-1), (mn == 1.0).astype(jnp.float32).sum(-1)


def use_pallas_default() -> bool:
    """Pallas kernels are the default path on a real TPU backend."""
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backend probing never raises
        return False


def tile_hll_ani(
    rows: jax.Array,       # uint8 (Br, m) registers
    cols: jax.Array,       # uint8 (Bc, m)
    row_cards: jax.Array,  # f32 (Br,) precomputed cardinalities
    col_cards: jax.Array,  # f32 (Bc,)
    k: int,
) -> jax.Array:
    """Mash-style ANI for every (row, col) pair -> (Br, Bc) f32.

    Union registers are the elementwise max (the HLL merge) — computed as
    the elementwise MIN of 2^-reg (monotonicity), so the union pass is
    pure min+add with exp2 hoisted out; Jaccard by inclusion-exclusion,
    clamped to [0, 1]; ANI = 1 + ln(2j/(1+j))/k, 0 where the estimated
    intersection is empty.
    """
    rows_pow2 = jnp.exp2(-rows.astype(jnp.float32))
    cols_pow2 = jnp.exp2(-cols.astype(jnp.float32))
    powsum, zeros = _xla_union_stats(rows_pow2, cols_pow2)
    return _ani_from_union_stats(powsum, zeros, row_cards, col_cards,
                                 k, rows.shape[-1])


@functools.partial(
    jax.jit,
    static_argnames=("k", "row_tile", "col_tile", "use_pallas", "cap"))
def _hll_rowblock(pow2, cards, r0, min_ani, n, *, k, row_tile, col_tile,
                  use_pallas, cap):
    """One dispatch: a row block's full ANI stripe, thresholded and
    compacted on device (same blocked-dispatch pattern as
    ops/pairwise.threshold_pairs). Module-level so the jit cache is
    shared across calls (keyed on shapes + the static tiling knobs, not
    on a per-call closure identity)."""
    m = pow2.shape[1]
    n_pad = pow2.shape[0]
    n_ct = n_pad // col_tile

    if use_pallas:
        from galah_tpu.ops.pallas_hll import hll_union_stats_tile

        def union_stats(rows, cols):
            return hll_union_stats_tile(rows, cols, chunk=min(1024, m))
    else:
        union_stats = _xla_union_stats

    rows = jax.lax.dynamic_slice_in_dim(pow2, r0, row_tile, axis=0)
    rcards = jax.lax.dynamic_slice_in_dim(cards, r0, row_tile, axis=0)
    t_first = r0 // col_tile

    def one_tile(t):
        def compute(_):
            cols = jax.lax.dynamic_slice_in_dim(
                pow2, t * col_tile, col_tile, axis=0)
            ccards = jax.lax.dynamic_slice_in_dim(
                cards, t * col_tile, col_tile, axis=0)
            powsum, zeros = union_stats(rows, cols)
            return _ani_from_union_stats(
                powsum, zeros, rcards, ccards, k, m)

        def skip(_):
            return jnp.zeros((row_tile, col_tile), jnp.float32)

        return jax.lax.cond(t >= t_first, compute, skip, None)

    ani = jax.lax.map(one_tile, jnp.arange(n_ct))
    ani = jnp.transpose(ani, (1, 0, 2)).reshape(row_tile, n_pad)
    gi = r0 + jnp.arange(row_tile)[:, None]
    gj = jnp.arange(n_pad)[None, :]
    mask = (ani >= min_ani) & (gi < gj) & (gj < n)
    count = jnp.sum(mask.astype(jnp.int32))
    (flat_idx,) = jnp.nonzero(mask.ravel(), size=cap, fill_value=-1)
    vals = jnp.take(ani.ravel(), jnp.maximum(flat_idx, 0))
    return flat_idx, vals, count


def hll_threshold_pairs(
    regs_mat: np.ndarray,
    k: int,
    min_ani: float,
    row_tile: int = 64,
    col_tile: int = 256,
    use_pallas: bool | None = None,
    cap_per_row: int = 64,
    mesh=None,
) -> dict[Tuple[int, int], float]:
    """Sparse {(i, j): ani} over i<j HLL pairs with ani >= min_ani.

    Host-orchestrated upper-triangle tiling; each tile is one device
    dispatch (union stats + estimate + threshold) and only surviving
    entries come back. The device-side analog of parsing dashing's full
    TSV matrix (reference: src/dashing.rs:76-100). The 2^-reg transform
    is applied ONCE to the whole matrix; each tile is then a pure
    min+add reduction — the Pallas kernel (ops/pallas_hll.py) on TPU,
    an XLA broadcast-min elsewhere.
    """
    # Auto-dispatch to the sharded SPMD implementation only when the
    # caller left BOTH knobs unset: an explicit use_pallas (or an
    # explicit mesh) pins the single-device implementation so kernel
    # parity tests and single-chip callers get what they asked for.
    if mesh is None and use_pallas is None and jax.device_count() > 1:
        from galah_tpu.parallel.mesh import auto_mesh

        mesh = auto_mesh()
    if mesh is not None and mesh.devices.size > 1:
        # Multi-device runtime: the column-sharded SPMD extraction
        # (parallel/mesh.py) covers the mesh with one dispatch per row
        # block.
        from galah_tpu.parallel.mesh import sharded_hll_threshold_pairs

        return sharded_hll_threshold_pairs(
            regs_mat, k=k, min_ani=min_ani, mesh=mesh,
            row_tile=row_tile, col_tile=col_tile,
            cap_per_row=cap_per_row)

    # The Mosaic kernel by default on TPU; its failures propagate. It
    # is compiled and validated at the 128x128 output tile geometry
    # (square tiles keep the out block at the native (8,128)-register
    # multiple).
    if use_pallas is None:
        use_pallas = use_pallas_default()
    return _hll_threshold_single(
        regs_mat, k, min_ani, 128 if use_pallas else row_tile,
        128 if use_pallas else col_tile, bool(use_pallas), cap_per_row)


def _hll_threshold_single(
    regs_mat: np.ndarray,
    k: int,
    min_ani: float,
    row_tile: int,
    col_tile: int,
    use_pallas: bool,
    cap_per_row: int,
) -> dict[Tuple[int, int], float]:
    import math

    n, m = regs_mat.shape
    quantum = math.lcm(row_tile, col_tile)
    n_pad = -(-n // quantum) * quantum
    mat = np.zeros((n_pad, m), dtype=np.uint8)
    mat[:n] = regs_mat
    jmat = jnp.asarray(mat)
    cards = hll_cardinality(jmat)
    pow2 = jnp.exp2(-jmat.astype(jnp.float32))

    from galah_tpu.ops.compact import iter_blocks

    def run_block(r0, cap):
        timing.dispatch()
        return _hll_rowblock(
            pow2, cards, jnp.int32(r0), jnp.float32(min_ani),
            jnp.int32(n), k=k, row_tile=row_tile, col_tile=col_tile,
            use_pallas=use_pallas, cap=cap)

    out: dict[Tuple[int, int], float] = {}
    for r0, (flat_idx, vals, count) in iter_blocks(
            n, row_tile, cap_per_row, run_block):
        timing.dispatch(sync=True)
        count = int(count)
        flat_idx = np.asarray(flat_idx)[:count]
        vals = np.asarray(vals)[:count]
        gi = r0 + flat_idx // n_pad
        gj = flat_idx % n_pad
        for a, b, v in zip(gi.tolist(), gj.tolist(), vals.tolist()):
            out[(int(a), int(b))] = float(v)
    return out
