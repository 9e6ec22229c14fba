"""Pallas TPU kernel: pairwise merged-bottom-k MinHash statistics.

The finch-equivalent precluster pass needs, for every sketch pair, the
(common, total) stats of the merged bottom-k distinct union
(ops/pairwise._pair_stats). The XLA path does a per-pair u64
searchsorted — gather-heavy and 64-bit-emulated, both scarce on TPU.
This kernel recomputes the same integers from dense block compares on
u32 hi/lo planes, the VPU-friendly trade: O(K^2) vectorized compares
per pair instead of O(K log K) gathers.

Layouts (chosen so every BlockSpec is legal under Mosaic's (8, 128)
tiling rule — blocks either tile-align or span the full axis, and all
dynamic indexing happens on sublane (second-minor) dims, never lanes):

  * query sketches `a`: (Br*8, K/8) — query i's k-mer k = l*8 + s sits
    at row i*8+s, lane l: one query is a dynamically sliceable (8, K/8)
    sublane group, and a CHUNK of 8 consecutive sorted values is one
    static lane column (8, 1);
  * reference sketches `b`: (Bc*(K/128), 128) — reference j's chunk s
    (128 consecutive sorted values) is the dynamically sliceable row
    j*(K/128)+s;
  * outputs: (Br, Bc) int32 in (8, 128)-aligned VMEM blocks.

One grid program computes an (8, Bc) output stripe: fori loops walk the
8 query rows and all references; per pair, a static loop over a-chunks
and a fori loop over b-chunks accumulate, via broadcast (8, 1) x
(1, 128) compares, both #(b < a_i) and #(b == a_i) per query element;
union ranks come from log-step prefix sums exactly as in the XLA path.
Per-pair scalars land in the output lane vector via one-hot
accumulation (dynamic lane stores don't exist on TPU). Bit-identical
integers to ops/pairwise.tile_stats.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galah_tpu.obs.profile import profiled

A_SUB = 8     # a-chunk height: consecutive sketch values per lane column
B_LANE = 128  # b-chunk width: consecutive sketch values per sublane row
ROWS_PER_PROGRAM = 8

#: Widest row the kernel is chosen for by default. Its compare loops are
#: unrolled over (K/8) x (K/128) blocks, so compile time grows with K^2:
#: K=1024 compiles for v5e in ~17 s, while the ~8k-wide marker rows of
#: real MAGs had not compiled after 8 minutes. Wider rows take the XLA
#: twin (7 s, 7.4 GB of temporaries at 8064 on v5e).
MAX_DEFAULT_WIDTH = 1024

# Static kernel contract checked by `galah-tpu lint` (GL1xx): bindings
# are representative *maximum* values of the call-site locals the
# BlockSpec shapes reference — k_pad=1024 (la = k_pad/A_SUB,
# sb = k_pad/B_LANE) and bc at its 4 MiB reference-side chunk limit.
PALLAS_CONTRACT = {
    "tile_stats_pallas": {
        "bindings": {"rp": 8, "la": 128, "sb": 8, "bc": 512},
        "in_dtypes": ["uint32", "uint32", "uint32", "uint32"],
        "kernel_fns": ["_make_kernel", "_pairmin", "_pairmax",
                       "_col_reduce", "_ssum_i32"],
    },
}


def _inclusive_cumsum_axis0(x: jax.Array) -> jax.Array:
    """Hillis-Steele prefix sum along sublanes via static shifts."""
    n = x.shape[0]
    sh = 1
    while sh < n:
        shifted = jnp.concatenate(
            [jnp.zeros((sh, x.shape[1]), x.dtype), x[:-sh, :]], axis=0)
        x = x + shifted
        sh *= 2
    return x


def _inclusive_cumsum_axis1(x: jax.Array) -> jax.Array:
    n = x.shape[1]
    sh = 1
    while sh < n:
        shifted = jnp.concatenate(
            [jnp.zeros((x.shape[0], sh), x.dtype), x[:, :-sh]], axis=1)
        x = x + shifted
        sh *= 2
    return x



def _ssum_i32(x) -> jax.Array:
    """Scalar int32 sum that survives Mosaic lowering under x64: the
    scalar-reduce proxy in the Mosaic lowering re-sums WITHOUT a dtype
    (promoting to int64, unsupported on TPU), so keep every reduction's
    output non-scalar — one axis at a time, keepdims, explicit dtype —
    and only then extract the scalar."""
    s = jnp.sum(x.astype(jnp.int32), axis=1, keepdims=True,
                dtype=jnp.int32)
    s = jnp.sum(s, axis=0, keepdims=True, dtype=jnp.int32)
    return s[0, 0]

def _pairmin(h1, l1, h2, l2):
    take2 = (h2 < h1) | ((h2 == h1) & (l2 < l1))
    return jnp.where(take2, h2, h1), jnp.where(take2, l2, l1)


def _pairmax(h1, l1, h2, l2):
    take2 = (h2 > h1) | ((h2 == h1) & (l2 > l1))
    return jnp.where(take2, h2, h1), jnp.where(take2, l2, l1)


def _col_reduce(h, low, op):
    """Per-column u64 min/max over sublanes of u32 (rows, la) planes
    via a slicing tournament -> (1, la) planes."""
    rows = h.shape[0]
    while rows > 1:
        half = rows // 2
        h, low = op(h[:half], low[:half], h[half:rows], low[half:rows])
        rows = half
    return h, low


def _make_kernel(la: int, sb: int, bc: int, sketch_size: int,
                 intersect: bool, range_skip: bool):
    """Kernel for K = 8*la = 128*sb padded sketch width.

    One program: rp=8 queries (a 64-sublane block) against all bc
    references. The compare loop batches ALL 8 queries into each
    (64, 128) vector op, so per-pair cost is one-eighth of a
    query-at-a-time formulation; the rank epilogue then runs per query
    on (8, la) slices. With `intersect` the kernel skips the less-than
    accumulation and rank math entirely and reports the raw
    |query ∩ reference| per pair (the marker-screening primitive,
    ops/pairwise.tile_intersect_counts).
    """
    rp = ROWS_PER_PROGRAM
    nrows = rp * A_SUB  # 64

    def kernel(a_hi_ref, a_lo_ref, b_hi_ref, b_lo_ref,
               common_ref, total_ref, lt_scr, eq_scr):
        umax = jnp.uint32(0xFFFFFFFF)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
        subl = jax.lax.broadcasted_iota(jnp.int32, (rp, bc), 0)
        ah = a_hi_ref[:]          # (64, la); query q row group q*8..q*8+7
        al = a_lo_ref[:]
        valid_a = ~((ah == umax) & (al == umax))
        # per-query valid counts, computed once per program
        na_q = [
            _ssum_i32(valid_a[q * A_SUB:(q + 1) * A_SUB, :])
            for q in range(rp)
        ]
        if range_skip:
            # per-column u64 min/max over all 64 query values, once per
            # program: the skip tests below compare b-chunk endpoint
            # scalars against these
            amin_h, amin_l = _col_reduce(ah, al, _pairmin)   # (1, la)
            amax_h, amax_l = _col_reduce(ah, al, _pairmax)

        def j_body(j, carry):
            crows, trows = carry      # (rp, bc) int32 accumulators

            # reference j's valid count (shared by all queries); b rows
            # are sorted, so chunk endpoints are free scalar extracts
            nb = jnp.int32(0)
            b_first = []
            b_last = []
            for s in range(sb):
                bh = b_hi_ref[pl.ds(j * sb + s, 1), :]
                bl = b_lo_ref[pl.ds(j * sb + s, 1), :]
                nb = nb + _ssum_i32(~((bh == umax) & (bl == umax)))
                if range_skip:
                    b_first.append((bh[0, 0], bl[0, 0]))
                    b_last.append((bh[0, B_LANE - 1], bl[0, B_LANE - 1]))

            # compare loop: for each a-chunk column l, all 8 queries'
            # chunk-l elements (64, 1) against every b chunk (1, 128);
            # u64 compares from lexicographic (hi, lo) u32 halves.
            # Sentinel b entries (UMAX, UMAX) are never < a valid value
            # and only equal other sentinels, which valid_a masks out.
            for l in range(la):
                a_h = ah[:, l:l + 1]  # (64, 1) — static lane slice
                a_l = al[:, l:l + 1]
                if range_skip:
                    # chunks wholly below the column minimum form a
                    # PREFIX (b sorted): they contribute 128 to every
                    # lt count and nothing to eq; chunks wholly above
                    # the maximum form a suffix and contribute nothing.
                    # A wholly-below chunk can't hold sentinels (its
                    # max would be UMAX), so its valid count is exactly
                    # B_LANE. Only [s_lo, s_hi) compares elementwise.
                    mn_h = amin_h[0, l]
                    mn_l = amin_l[0, l]
                    mx_h = amax_h[0, l]
                    mx_l = amax_l[0, l]
                    s_lo = jnp.int32(0)
                    s_hi = jnp.int32(sb)
                    for s in range(sb):
                        fh, fl = b_first[s]
                        lh, ll = b_last[s]
                        below = (lh < mn_h) | ((lh == mn_h) & (ll < mn_l))
                        above = (fh > mx_h) | ((fh == mx_h) & (fl > mx_l))
                        s_lo = s_lo + below.astype(jnp.int32)
                        s_hi = s_hi - above.astype(jnp.int32)

                    def body(s, carry, a_h=a_h, a_l=a_l):
                        lt_c, eq_c = carry
                        bh = b_hi_ref[pl.ds(j * sb + s, 1), :]
                        bl = b_lo_ref[pl.ds(j * sb + s, 1), :]
                        eq = (bh == a_h) & (bl == a_l)
                        eq_c = eq_c + eq.astype(jnp.int32)
                        if not intersect:
                            lt = (bh < a_h) | ((bh == a_h) & (bl < a_l))
                            lt_c = lt_c + lt.astype(jnp.int32)
                        return lt_c, eq_c

                    zero = jnp.zeros((nrows, B_LANE), jnp.int32)
                    ltacc, eqacc = jax.lax.fori_loop(
                        s_lo, jnp.maximum(s_hi, s_lo), body, (zero, zero))
                    if not intersect:
                        lt_scr[:, l:l + 1] = (
                            jnp.sum(ltacc, axis=1, keepdims=True,
                                    dtype=jnp.int32)
                            + s_lo * jnp.int32(B_LANE))
                    eq_scr[:, l:l + 1] = jnp.sum(
                        eqacc, axis=1, keepdims=True, dtype=jnp.int32)
                    continue
                ltacc = jnp.zeros((nrows, B_LANE), jnp.int32)
                eqacc = jnp.zeros((nrows, B_LANE), jnp.int32)
                for s in range(sb):
                    bh = b_hi_ref[pl.ds(j * sb + s, 1), :]   # (1, 128)
                    bl = b_lo_ref[pl.ds(j * sb + s, 1), :]
                    eq = (bh == a_h) & (bl == a_l)           # (64, 128)
                    eqacc = eqacc + eq.astype(jnp.int32)
                    if not intersect:
                        lt = (bh < a_h) | ((bh == a_h) & (bl < a_l))
                        ltacc = ltacc + lt.astype(jnp.int32)
                if not intersect:
                    lt_scr[:, l:l + 1] = jnp.sum(
                        ltacc, axis=1, keepdims=True, dtype=jnp.int32)
                eq_scr[:, l:l + 1] = jnp.sum(
                    eqacc, axis=1, keepdims=True, dtype=jnp.int32)

            eqv_all = eq_scr[:]
            hot = (lane == j).astype(jnp.int32)              # (1, bc)
            if not intersect:
                ltv_all = lt_scr[:]

            # per-query epilogue on its (8, la) slice
            for q in range(rp):
                sl = slice(q * A_SUB, (q + 1) * A_SUB)
                eqv = eqv_all[sl, :]
                va = valid_a[sl, :]
                match = ((eqv > 0) & va).astype(jnp.int32)
                n_common_all = _ssum_i32(match)
                if intersect:
                    qmask = (subl == q).astype(jnp.int32)
                    crows = crows + qmask * (hot * n_common_all)
                    trows = trows + qmask * (hot * na_q[q])
                    continue
                ltv = ltv_all[sl, :]
                n_union = na_q[q] + nb - n_common_all
                total = jnp.minimum(jnp.int32(sketch_size), n_union)

                # union rank of matched a_i (i = l*8 + s): i + #(b<a_i)
                # - #(matches before i), via log-step shift cumsums
                colsum = jnp.sum(match, axis=0, keepdims=True,
                                 dtype=jnp.int32)             # (1, la)
                col_excl = _inclusive_cumsum_axis1(colsum) - colsum
                row_excl = _inclusive_cumsum_axis0(match) - match
                cexcl = col_excl + row_excl

                s_idx = jax.lax.broadcasted_iota(
                    jnp.int32, (A_SUB, la), 0)
                l_idx = jax.lax.broadcasted_iota(
                    jnp.int32, (A_SUB, la), 1)
                i_idx = l_idx * A_SUB + s_idx
                urank = i_idx + ltv - cexcl
                common = _ssum_i32(
                    match * (urank < total).astype(jnp.int32))

                qmask = (subl == q).astype(jnp.int32)         # (rp, bc)
                crows = crows + qmask * (hot * common)
                trows = trows + qmask * (hot * total)
            return crows, trows

        crows, trows = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(bc), j_body,
            (jnp.zeros((rp, bc), jnp.int32),
             jnp.zeros((rp, bc), jnp.int32)))
        common_ref[:] = crows
        total_ref[:] = trows

    return kernel


def _zi(i):
    """Index-map zero with the grid index's own dtype: a literal 0 in an
    index map canonicalizes to int64 under x64, which Mosaic rejects."""
    return i * 0


def _split_planes(mat: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return ((mat >> jnp.uint64(32)).astype(jnp.uint32),
            mat.astype(jnp.uint32))


@profiled("pairwise.tile_stats_pallas")
@functools.partial(jax.jit,
                   static_argnames=("sketch_size", "interpret",
                                    "intersect", "range_skip"))
def tile_stats_pallas(
    rows: jax.Array,   # uint64 (Br, K) sorted asc, SENTINEL-padded
    cols: jax.Array,   # uint64 (Bc, K)
    sketch_size: int,
    interpret: bool = False,
    intersect: bool = False,
    range_skip: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(common, total) int32 (Br, Bc) tiles — the Pallas twin of
    ops/pairwise.tile_stats (bit-identical integers). With `intersect`,
    `common` is the raw |row ∩ col| count (the twin of
    ops/pairwise.tile_intersect_counts) and `total` the row's valid
    count.

    range_skip is QUARANTINED: an early on-chip campaign measured the
    skip variant 3.7x SLOWER on the dense tile (218.1k -> 59.4k
    pairs/s at 512x512) — the data-dependent window bounds defeat Mosaic's static scheduling.
    No default path sets it; its parity tests run only in the
    slow/hardware tier. Kept as the reference windowed-compare
    formulation."""
    br_in, k_in = rows.shape
    bc_in = cols.shape[0]
    sent = ~jnp.uint64(0)

    # The reference side resides fully in VMEM (bc * k_pad * 8 bytes of
    # u32 planes); chunk the columns when it would overflow.
    k_pad_probe = -(-k_in // B_LANE) * B_LANE
    bc_limit = max(B_LANE, (4 << 20) // (k_pad_probe * 8))
    bc_limit = (bc_limit // B_LANE) * B_LANE
    if bc_in > bc_limit:
        parts = [
            tile_stats_pallas(rows, cols[c0:c0 + bc_limit], sketch_size,
                              interpret=interpret, intersect=intersect,
                              range_skip=range_skip)
            for c0 in range(0, bc_in, bc_limit)
        ]
        return (jnp.concatenate([p[0] for p in parts], axis=1),
                jnp.concatenate([p[1] for p in parts], axis=1))

    k_pad = -(-k_in // B_LANE) * B_LANE
    if k_pad != k_in:
        fill = jnp.full((1, k_pad - k_in), sent, jnp.uint64)
        rows = jnp.concatenate(
            [rows, jnp.tile(fill, (br_in, 1))], axis=1)
        cols = jnp.concatenate(
            [cols, jnp.tile(fill, (bc_in, 1))], axis=1)

    # Pad rows to the program height, cols to the output lane quantum.
    br = -(-br_in // ROWS_PER_PROGRAM) * ROWS_PER_PROGRAM
    bc = -(-bc_in // B_LANE) * B_LANE
    if br != br_in:
        rows = jnp.concatenate(
            [rows, jnp.full((br - br_in, k_pad), sent, jnp.uint64)],
            axis=0)
    if bc != bc_in:
        cols = jnp.concatenate(
            [cols, jnp.full((bc - bc_in, k_pad), sent, jnp.uint64)],
            axis=0)

    la = k_pad // A_SUB
    sb = k_pad // B_LANE

    # a: (Br, K) -> (Br*8, la); query i's value k = l*8 + s at
    # (row i*8 + s, lane l)
    a_hi, a_lo = _split_planes(rows)
    a_hi2 = a_hi.reshape(br, la, A_SUB).transpose(0, 2, 1).reshape(
        br * A_SUB, la)
    a_lo2 = a_lo.reshape(br, la, A_SUB).transpose(0, 2, 1).reshape(
        br * A_SUB, la)
    # b: (Bc, K) -> (Bc*sb, 128); ref j's chunk s (k = s*128 + l) at
    # row j*sb + s
    b_hi, b_lo = _split_planes(cols)
    b_hi2 = b_hi.reshape(bc * sb, B_LANE)
    b_lo2 = b_lo.reshape(bc * sb, B_LANE)

    kernel = _make_kernel(la, sb, bc, sketch_size, bool(intersect),
                          bool(range_skip))
    rp = ROWS_PER_PROGRAM
    common, total = pl.pallas_call(
        kernel,
        grid=(br // rp,),
        in_specs=[
            pl.BlockSpec((rp * A_SUB, la), lambda i: (i, _zi(i)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rp * A_SUB, la), lambda i: (i, _zi(i)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bc * sb, B_LANE),
                         lambda i: (_zi(i), _zi(i)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bc * sb, B_LANE),
                         lambda i: (_zi(i), _zi(i)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rp, bc), lambda i: (i, _zi(i)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rp, bc), lambda i: (i, _zi(i)),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((br, bc), jnp.int32),
            jax.ShapeDtypeStruct((br, bc), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rp * A_SUB, la), jnp.int32),
            pltpu.VMEM((rp * A_SUB, la), jnp.int32),
        ],
        interpret=interpret,
    )(a_hi2, a_lo2, b_hi2, b_lo2)
    return common[:br_in, :bc_in], total[:br_in, :bc_in]


def tile_intersect_pallas(
    rows: jax.Array,   # uint64 (Br, M) sorted asc, SENTINEL-padded
    cols: jax.Array,   # uint64 (Bc, M)
    interpret: bool = False,
) -> jax.Array:
    """|row ∩ col| int32 (Br, Bc) — the Mosaic twin of
    ops/pairwise.tile_intersect_counts for marker-containment
    screening (reference: src/skani.rs:54-70)."""
    common, _total = tile_stats_pallas(
        rows, cols, rows.shape[1], interpret=interpret, intersect=True)
    return common
