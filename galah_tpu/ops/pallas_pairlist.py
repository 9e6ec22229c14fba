"""Pallas TPU kernel: merged-bottom-k stats for an explicit PAIR LIST.

The screened sparse pipeline (ops/sparse_device.py) evaluates only the
collision screen's survivors — gathered (a_p, b_p) sketch row pairs,
one result per pair, not a (rows x cols) tile. The XLA formulation is
a vmapped u64 searchsorted (gather-heavy and 64-bit-emulated — the
same costs that motivated ops/pallas_pairwise.py, which measured ~26x
over the XLA path on chip). This kernel recomputes the identical
integers from dense block compares on u32 hi/lo planes.

Design note (hardware-driven): the first cut of this kernel walked 64
pairs per grid program with `pl.ds(q, 1)` row loads; Mosaic rejects
that on real v5e hardware ("dynamic load with unaligned indices" —
dynamic sublane offsets must be 8-aligned). Both kernels here
therefore have NO dynamic indexing at all: the BlockSpec index maps
select each program's rows — block windowing is a DMA copy, which
takes arbitrary row offsets — and everything inside a program is a
STATIC slice.

Round-5 hardware data showed the one-pair-per-program grid paying its
full per-program fixed cost (grid bookkeeping + tiny DMA windows) per
pair: 62.8k pairs/s amortized, 7.8% of the derived VPU ceiling,
vs 27.3% for the dense tile whose programs pool 8 queries. The
BLOCKED kernel closes that gap by processing `block_pairs` (P,
default 8) pairs per program: the per-program fixed cost is amortized
P ways and the DMA windows are P× larger, so the pipeline's
double-buffered window loads (Pallas DMAs block g+1's a/b planes into
the alternate VMEM buffer while block g computes) run at useful
sizes. Layouts (P = 1 is exactly the round-5 one-pair kernel):

  * a side: (B*8, la) planes, la = K_pad/8 — pair p's value k = l*8+s
    at row p*8 + s, lane l (the dense kernel's query layout); block
    (P*8, la) at block-row g, pair p of the block at STATIC rows
    [p*8, p*8+8);
  * b side: (B*sb, 128) planes, sb = K_pad/128 — pair p's sorted row
    chunk s on row p*sb + s; block (P*sb, 128) at block-row g, chunk
    s of pair p at the block's STATIC row p*sb + s (K_pad is padded
    so P*sb satisfies the sublane-divisibility rule — a multiple of
    1024 = 8*128 only for P=1; P=8 needs just a multiple of 128);
  * out: (B*8, 128) int32, block (P*8, 128) at block-row g; pair p's
    (common, total) is broadcast across rows [p*8, p*8+8) and read
    back at (row p*8, lane 0). The pair axis is padded to a multiple
    of P with all-sentinel rows (their stats are (0, 0)) and trimmed
    on the host.

Per pair, static loops over a lanes x b chunks accumulate
#(b < a_i) and #(b == a_i) from (8, 1) x (1, 128) broadcast compares —
(8, 128) is one native vreg, so the VPU stays full. The union-rank
epilogue is the dense kernel's, on (8, la) planes. Bit-identical
integers to ops/pairwise._pair_stats (tests/test_pallas_pairlist.py);
the blocked kernel compiles for v5e (tests/test_tpu_compile.py) and
runs on the chip in chip_smoke.py.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galah_tpu.obs.profile import profiled

from galah_tpu.ops.pallas_pairwise import (
    _inclusive_cumsum_axis0,
    _inclusive_cumsum_axis1,
    _split_planes,
    _ssum_i32,
    _zi,
)

A_SUB = 8
B_LANE = 128

# Pairs per grid program for the blocked kernel. 8 mirrors the dense
# tile's 8-query pooling (the 27.3%-of-ceiling configuration); the
# per-program fixed cost that dominated the one-pair grid is amortized
# across the block.
PAIRLIST_BLOCK_DEFAULT = 8

# Static kernel contract checked by `galah-tpu lint` (GL1xx):
# representative bindings at the default block (bp=8) and k_pad=1024,
# so la = k_pad/A_SUB and sb = k_pad/B_LANE.
PALLAS_CONTRACT = {
    "_pair_stats_pairs_jit": {
        "bindings": {"bp": 8, "la": 128, "sb": 8},
        "in_dtypes": ["uint32", "uint32", "uint32", "uint32"],
        "kernel_fns": ["_make_blocked_kernel", "_make_kernel",
                       "_pair_body"],
    },
}

# Numeric-determinism contract checked by `galah-tpu lint` (GL9xx):
# pair statistics are exact integer match counts — every pairlist
# strategy (blocked / gather / xla / cpu) must produce bit-identical
# (matches, lengths) for the same pairs, independent of strategy.
DETERMINISM_CONTRACT = {
    "family": "pairlist",
    "dtype": "int32",
    "functions": ["pair_stats_pairs_pallas", "_pair_stats_pairs_jit"],
}


def pairlist_block_pairs() -> int:
    """P for the blocked pairlist kernel (GALAH_TPU_PAIRLIST_BLOCK to
    tune; 1 selects the round-5 one-pair reference grid)."""
    return max(1, int(os.environ.get("GALAH_TPU_PAIRLIST_BLOCK",
                                     PAIRLIST_BLOCK_DEFAULT)))


def _pair_body(ah, al, bh_chunks, bl_chunks, la: int, sb: int,
               sketch_size: int, lo_only: bool = False):
    """One pair's merged-bottom-k stats from already-loaded planes.

    `ah`/`al` are the pair's (8, la) a-side hi/lo planes; `bh_chunks`/
    `bl_chunks` its sb (1, 128) b-side row chunks. Returns (common,
    total) int32 scalars — the integers of ops/pairwise._pair_stats.

    `lo_only` is a BENCH-ONLY knob (scripts/bench_pairlist_variants.py)
    that drops the hi-plane halves of the lt/eq compares to price the
    u64-emulation tax; its integers are WRONG for real sketches and no
    production path sets it."""
    umax = jnp.uint32(0xFFFFFFFF)
    valid_a = ~((ah == umax) & (al == umax))
    na = _ssum_i32(valid_a)

    nb = jnp.int32(0)
    for s in range(sb):
        nb = nb + _ssum_i32(
            ~((bh_chunks[s] == umax) & (bl_chunks[s] == umax)))

    lt_cols = []
    eq_cols = []
    for l in range(la):
        a_h = ah[:, l:l + 1]   # (8, 1)
        a_l = al[:, l:l + 1]
        ltacc = jnp.zeros((A_SUB, B_LANE), jnp.int32)
        eqacc = jnp.zeros((A_SUB, B_LANE), jnp.int32)
        for s in range(sb):
            bh = bh_chunks[s]
            bl = bl_chunks[s]
            if lo_only:
                eq = bl == a_l
                lt = bl < a_l
            else:
                eq = (bh == a_h) & (bl == a_l)
                lt = (bh < a_h) | ((bh == a_h) & (bl < a_l))
            eqacc = eqacc + eq.astype(jnp.int32)
            ltacc = ltacc + lt.astype(jnp.int32)
        lt_cols.append(jnp.sum(ltacc, axis=1, keepdims=True,
                               dtype=jnp.int32))
        eq_cols.append(jnp.sum(eqacc, axis=1, keepdims=True,
                               dtype=jnp.int32))
    ltv = jnp.concatenate(lt_cols, axis=1)   # (8, la)
    eqv = jnp.concatenate(eq_cols, axis=1)

    match = ((eqv > 0) & valid_a).astype(jnp.int32)
    n_common_all = _ssum_i32(match)
    n_union = na + nb - n_common_all
    total = jnp.minimum(jnp.int32(sketch_size), n_union)

    colsum = jnp.sum(match, axis=0, keepdims=True, dtype=jnp.int32)
    col_excl = _inclusive_cumsum_axis1(colsum) - colsum
    row_excl = _inclusive_cumsum_axis0(match) - match
    cexcl = col_excl + row_excl
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (A_SUB, la), 0)
    l_idx = jax.lax.broadcasted_iota(jnp.int32, (A_SUB, la), 1)
    urank = l_idx * A_SUB + s_idx + ltv - cexcl
    common = _ssum_i32(match * (urank < total).astype(jnp.int32))
    return common, total


def _make_blocked_kernel(la: int, sb: int, sketch_size: int,
                         block_pairs: int, lo_only: bool = False):
    """Kernel for K_pad = 8*la = 128*sb; one program = `block_pairs`
    pairs, each at a STATIC row offset inside the (P*8, la) /
    (P*sb, 128) windows — no dynamic indexing, per the module's Mosaic
    design note. Pallas's pipeline double-buffers the windows across
    grid steps, so block g+1's hash-row DMAs overlap block g's
    compute."""

    def kernel(a_hi_ref, a_lo_ref, b_hi_ref, b_lo_ref,
               common_ref, total_ref):
        for p in range(block_pairs):
            r0 = p * A_SUB
            ah = a_hi_ref[r0:r0 + A_SUB, :]   # (8, la)
            al = a_lo_ref[r0:r0 + A_SUB, :]
            bh_chunks = [b_hi_ref[p * sb + s:p * sb + s + 1, :]
                         for s in range(sb)]
            bl_chunks = [b_lo_ref[p * sb + s:p * sb + s + 1, :]
                         for s in range(sb)]
            common, total = _pair_body(ah, al, bh_chunks, bl_chunks,
                                       la, sb, sketch_size,
                                       lo_only=lo_only)
            common_ref[r0:r0 + A_SUB, :] = jnp.broadcast_to(
                common, (A_SUB, B_LANE))
            total_ref[r0:r0 + A_SUB, :] = jnp.broadcast_to(
                total, (A_SUB, B_LANE))

    return kernel


def _make_kernel(la: int, sb: int, sketch_size: int,
                 range_skip: bool = False):
    """Kernel for K_pad = 8*la = 128*sb; one program = one pair.

    NON-PRODUCTION REFERENCE (hardware-retired). This is the round-5
    one-pair grid; production traffic now routes through
    `_make_blocked_kernel` (P=1 there reproduces this kernel's exact
    non-skip op sequence). It is kept solely as the home of the
    `range_skip` variant, which an early on-chip campaign measured
    3.2x SLOWER than the plain compare loop (62.8k -> 19.5k pairs/s
    at B=8192) — the data-dependent `pl.when` breaks Mosaic's pipelining on v5e. No
    default code path selects it; parity coverage lives behind the
    slow/hardware test gate.

    With `range_skip`, each lane column's 8 consecutive sorted a
    values carry tight scalar [min, max] bounds (ONE query per
    program, unlike the dense kernel's 8-query-pooled bounds), so b
    chunks wholly below contribute a scalar 128 to every lt count and
    chunks wholly above contribute nothing — only the 1-2 straddling
    chunks run vector compares, guarded by pl.when so the skipped
    work is actually skipped, not predicated."""

    def kernel(a_hi_ref, a_lo_ref, b_hi_ref, b_lo_ref,
               common_ref, total_ref, *scratch):
        umax = jnp.uint32(0xFFFFFFFF)

        ah = a_hi_ref[:, :]   # (8, la)
        al = a_lo_ref[:, :]
        valid_a = ~((ah == umax) & (al == umax))
        na = _ssum_i32(valid_a)

        # The pair's b row, materialized once as (1, 128) lane chunks:
        # chunk s is static row s of the (sb, 128) block.
        bh_chunks = [b_hi_ref[s:s + 1, :] for s in range(sb)]
        bl_chunks = [b_lo_ref[s:s + 1, :] for s in range(sb)]
        nb = jnp.int32(0)
        for s in range(sb):
            nb = nb + _ssum_i32(
                ~((bh_chunks[s] == umax) & (bl_chunks[s] == umax)))

        lt_cols = []
        eq_cols = []
        if range_skip:
            lt_scr, eq_scr = scratch
            b_first = [(bh_chunks[s][0, 0], bl_chunks[s][0, 0])
                       for s in range(sb)]
            b_last = [(bh_chunks[s][0, B_LANE - 1],
                       bl_chunks[s][0, B_LANE - 1]) for s in range(sb)]
        for l in range(la):
            a_h = ah[:, l:l + 1]   # (8, 1)
            a_l = al[:, l:l + 1]
            if range_skip:
                # Column l holds sorted values a[8l..8l+7]; a wholly-
                # below chunk can hold no sentinel (its max < a_min <=
                # UMAX) so it adds exactly B_LANE to every row's lt and
                # nothing to eq; a wholly-above chunk adds nothing to
                # either. (An all-padding column has a_min = UMAX, so
                # every valid chunk counts below — harmless: its rows
                # are masked out of `match` by valid_a.)
                amn_h, amn_l = ah[0, l], al[0, l]
                amx_h, amx_l = ah[A_SUB - 1, l], al[A_SUB - 1, l]
                lt_scr[:] = jnp.zeros((A_SUB, B_LANE), jnp.int32)
                eq_scr[:] = jnp.zeros((A_SUB, B_LANE), jnp.int32)
                n_below = jnp.int32(0)
                for s in range(sb):
                    fh, fl = b_first[s]
                    lh, ll = b_last[s]
                    below = (lh < amn_h) | ((lh == amn_h) & (ll < amn_l))
                    above = (fh > amx_h) | ((fh == amx_h) & (fl > amx_l))
                    n_below = n_below + below.astype(jnp.int32)

                    @pl.when(~(below | above))
                    def _(s=s, a_h=a_h, a_l=a_l):
                        bh = bh_chunks[s]
                        bl = bl_chunks[s]
                        eq = (bh == a_h) & (bl == a_l)
                        eq_scr[:] = eq_scr[:] + eq.astype(jnp.int32)
                        lt = (bh < a_h) | ((bh == a_h) & (bl < a_l))
                        lt_scr[:] = lt_scr[:] + lt.astype(jnp.int32)

                lt_cols.append(
                    jnp.sum(lt_scr[:], axis=1, keepdims=True,
                            dtype=jnp.int32)
                    + n_below * jnp.int32(B_LANE))
                eq_cols.append(jnp.sum(eq_scr[:], axis=1, keepdims=True,
                                       dtype=jnp.int32))
                continue
            ltacc = jnp.zeros((A_SUB, B_LANE), jnp.int32)
            eqacc = jnp.zeros((A_SUB, B_LANE), jnp.int32)
            for s in range(sb):
                bh = bh_chunks[s]
                bl = bl_chunks[s]
                eq = (bh == a_h) & (bl == a_l)
                eqacc = eqacc + eq.astype(jnp.int32)
                lt = (bh < a_h) | ((bh == a_h) & (bl < a_l))
                ltacc = ltacc + lt.astype(jnp.int32)
            lt_cols.append(jnp.sum(ltacc, axis=1, keepdims=True,
                                   dtype=jnp.int32))
            eq_cols.append(jnp.sum(eqacc, axis=1, keepdims=True,
                                   dtype=jnp.int32))
        ltv = jnp.concatenate(lt_cols, axis=1)   # (8, la)
        eqv = jnp.concatenate(eq_cols, axis=1)

        match = ((eqv > 0) & valid_a).astype(jnp.int32)
        n_common_all = _ssum_i32(match)
        n_union = na + nb - n_common_all
        total = jnp.minimum(jnp.int32(sketch_size), n_union)

        colsum = jnp.sum(match, axis=0, keepdims=True,
                         dtype=jnp.int32)
        col_excl = _inclusive_cumsum_axis1(colsum) - colsum
        row_excl = _inclusive_cumsum_axis0(match) - match
        cexcl = col_excl + row_excl
        s_idx = jax.lax.broadcasted_iota(jnp.int32, (A_SUB, la), 0)
        l_idx = jax.lax.broadcasted_iota(jnp.int32, (A_SUB, la), 1)
        urank = l_idx * A_SUB + s_idx + ltv - cexcl
        common = _ssum_i32(match * (urank < total).astype(jnp.int32))

        common_ref[:] = jnp.broadcast_to(common, (A_SUB, B_LANE))
        total_ref[:] = jnp.broadcast_to(total, (A_SUB, B_LANE))

    return kernel


def pair_stats_pairs_pallas(
    rows_a: jax.Array,   # uint64 (B, K) sorted asc, SENTINEL-padded
    rows_b: jax.Array,   # uint64 (B, K)
    sketch_size: int,
    interpret: bool = False,
    range_skip: bool = False,
    block_pairs: int = None,
    _lo_only: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(common, total) int32 (B,) for each (rows_a[p], rows_b[p]) pair
    — the Mosaic twin of the vmapped ops/pairwise._pair_stats used by
    the screened sparse pipeline. Bit-identical integers for any
    block_pairs / range_skip setting (see _pair_body / _make_kernel).

    `block_pairs=None` takes GALAH_TPU_PAIRLIST_BLOCK (default 8):
    the blocked grid amortizes the per-program fixed cost that held
    the one-pair grid to 7.8% of the VPU ceiling. `block_pairs=1`
    without range_skip still goes through the blocked builder — same
    op sequence as the retired one-pair kernel.

    range_skip stays False by default: an early on-chip campaign
    measured the skip variant 3.2x SLOWER (62.8k -> 19.5k pairs/s at
    B=8192) — the data-dependent `pl.when` breaks Mosaic's pipelining on v5e and
    costs more than the skipped compares save. It is a quarantined
    reference variant, only reachable by passing the flag, and forces
    the one-pair grid (the only kernel that implements it).

    `_lo_only` is bench-only (u64-emulation tax pricing) — WRONG
    integers for real sketches; see _pair_body."""
    if range_skip:
        block_pairs = 1
    elif block_pairs is None:
        block_pairs = pairlist_block_pairs()
    block_pairs = int(block_pairs)
    # Pad to the kernel's (pair, K) quanta OUT here, before the jit
    # boundary, so the cache keys on canonical padded shapes: every
    # ragged tail (b % P != 0) and sub-quantum width would otherwise
    # compile its own executable — one avoidable Mosaic compile per
    # ragged batch in production. The jit body's own padding is a
    # no-op on pre-padded inputs.
    b_in, k_in = rows_a.shape
    if b_in:
        sent = ~jnp.uint64(0)
        k_quantum = B_LANE * (A_SUB // math.gcd(block_pairs, A_SUB))
        k_pad = -(-k_in // k_quantum) * k_quantum
        if k_pad != k_in:
            fill = jnp.full((b_in, k_pad - k_in), sent, jnp.uint64)
            rows_a = jnp.concatenate([rows_a, fill], axis=1)
            rows_b = jnp.concatenate([rows_b, fill], axis=1)
        b_pad = -(-b_in // block_pairs) * block_pairs
        if b_pad != b_in:
            fill = jnp.full((b_pad - b_in, k_pad), sent, jnp.uint64)
            rows_a = jnp.concatenate([rows_a, fill], axis=0)
            rows_b = jnp.concatenate([rows_b, fill], axis=0)
    common, total = _pair_stats_pairs_jit(
        rows_a, rows_b, sketch_size=sketch_size,
        interpret=bool(interpret), range_skip=bool(range_skip),
        block_pairs=block_pairs, lo_only=bool(_lo_only))
    return common[:b_in], total[:b_in]


@profiled("pairlist.pair_stats_pairs")
@functools.partial(jax.jit,
                   static_argnames=("sketch_size", "interpret",
                                    "range_skip", "block_pairs",
                                    "lo_only"))
def _pair_stats_pairs_jit(
    rows_a: jax.Array,
    rows_b: jax.Array,
    sketch_size: int,
    interpret: bool,
    range_skip: bool,
    block_pairs: int,
    lo_only: bool,
) -> Tuple[jax.Array, jax.Array]:
    b_in, k_in = rows_a.shape
    if b_in == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z
    sent = ~jnp.uint64(0)
    bp = block_pairs

    # K_pad must make the b-side (P*sb, 128) block satisfy Mosaic's
    # sublane-divisibility rule ((P*sb) % 8 == 0): a multiple of
    # 8*128/gcd(P, 8) — the full 1024 only for the P=1 grid, 128 at
    # the default P=8.
    k_quantum = B_LANE * (A_SUB // math.gcd(bp, A_SUB))
    k_pad = -(-k_in // k_quantum) * k_quantum
    if k_pad != k_in:
        fill = jnp.full((b_in, k_pad - k_in), sent, jnp.uint64)
        rows_a = jnp.concatenate([rows_a, fill], axis=1)
        rows_b = jnp.concatenate([rows_b, fill], axis=1)

    # Pair axis pads to a whole number of P-pair blocks; the sentinel
    # pairs cost one wasted program slot each (counted by the caller's
    # pairlist-blocked-pad counter) and compute to (0, 0).
    b_pad = -(-b_in // bp) * bp
    if b_pad != b_in:
        fill = jnp.full((b_pad - b_in, k_pad), sent, jnp.uint64)
        rows_a = jnp.concatenate([rows_a, fill], axis=0)
        rows_b = jnp.concatenate([rows_b, fill], axis=0)

    la = k_pad // A_SUB
    sb = k_pad // B_LANE

    a_hi, a_lo = _split_planes(rows_a)
    a_hi2 = a_hi.reshape(b_pad, la, A_SUB).transpose(0, 2, 1).reshape(
        b_pad * A_SUB, la)
    a_lo2 = a_lo.reshape(b_pad, la, A_SUB).transpose(0, 2, 1).reshape(
        b_pad * A_SUB, la)
    b_hi, b_lo = _split_planes(rows_b)
    b_hi2 = b_hi.reshape(b_pad * sb, B_LANE)
    b_lo2 = b_lo.reshape(b_pad * sb, B_LANE)

    if range_skip:
        kernel = _make_kernel(la, sb, sketch_size, range_skip=True)
    else:
        kernel = _make_blocked_kernel(la, sb, sketch_size, bp,
                                      lo_only=lo_only)
    common, total = pl.pallas_call(
        kernel,
        grid=(b_pad // bp,),
        in_specs=[
            pl.BlockSpec((bp * A_SUB, la), lambda g: (g, _zi(g)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp * A_SUB, la), lambda g: (g, _zi(g)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp * sb, B_LANE), lambda g: (g, _zi(g)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp * sb, B_LANE), lambda g: (g, _zi(g)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bp * A_SUB, B_LANE), lambda g: (g, _zi(g)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp * A_SUB, B_LANE), lambda g: (g, _zi(g)),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad * A_SUB, B_LANE), jnp.int32),
            jax.ShapeDtypeStruct((b_pad * A_SUB, B_LANE), jnp.int32),
        ],
        scratch_shapes=(
            [pltpu.VMEM((A_SUB, B_LANE), jnp.int32),
             pltpu.VMEM((A_SUB, B_LANE), jnp.int32)]
            if range_skip else []),
        interpret=interpret,
    )(a_hi2, a_lo2, b_hi2, b_lo2)
    return (common.reshape(b_pad, A_SUB, B_LANE)[:b_in, 0, 0],
            total.reshape(b_pad, A_SUB, B_LANE)[:b_in, 0, 0])
