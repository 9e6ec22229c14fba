"""Pallas TPU kernel: MurmurHash3 x64_128 (h1) over u32 plane pairs.

The reference's finch contract hashes every canonical k-mer with
murmur3 x64_128 (reference: src/finch.rs:33-47) — 11 u64
multiply-by-constant operations per k-mer. The TPU VPU has no 64-bit
integer unit; XLA emulates every u64 op over u32 pairs generically,
and the multiplies dominate device sketching. This kernel is the
promised explicit u32-pair implementation (ops/hashing.py's module
docstring): the murmur state machine runs on (hi, lo) uint32 planes
with each constant multiply decomposed into 16-bit limb products
(every 16x16 product fits u32 exactly; per-column limb accumulators
stay below 2^19, so one carry-propagation pass at the end suffices) —
the minimal-width schoolbook XLA's generic emulation cannot assume.

Scope: the k=21 MinHash production path. Input is the three assembled
key words (k1: bytes 0-7, k2: bytes 8-15, k1 tail: bytes 16-20) that
ops/hashing's XLA preamble already builds with cheap shift/or chains;
the kernel fuses the whole hash state machine — one block-elementwise
pass, no u64 intermediates in HBM. Bit-identical to
ops/hashing._murmur3_k21_1d (tests/test_pallas_sketch.py, interpret
mode on CPU).

QUARANTINED, kept for the record. An early on-chip campaign measured
this kernel at 0.06x the XLA u64 emulation on the murmur core: XLA's generic emulation fuses the constant multiplies
better than the 16-bit-limb schoolbook once the state machine is one
elementwise pass. No default path selects it — activation requires
BOTH hash_algo="murmur3" AND GALAH_TPU_PALLAS_HASH=1 (ops/hashing.py)
— and its parity tests run only in the slow/hardware tier
(tests/test_pallas_sketch.py). It stays in-tree as the reference
16-bit-limb u64-multiply decomposition should a future Mosaic release
change the economics.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galah_tpu.obs.profile import profiled

LANES = 128
BLOCK_SUB = 512  # sublanes per grid program (block = BLOCK_SUB x 128)

# Static kernel contract checked by `galah-tpu lint` (GL1xx). The
# hash-only entry's blocks are all (BLOCK_SUB, LANES) u32 planes; the
# fused entry's bindings pin a representative launch (murmur arity,
# 8 jobs x span 2) so the evaluator can size its blocks and VMEM.
PALLAS_CONTRACT = {
    "murmur3_k21_pallas": {
        "bindings": {},
        "in_dtypes": ["uint32", "uint32", "uint32",
                      "uint32", "uint32", "uint32"],
        "kernel_fns": ["_make_kernel", "_murmur3_planes", "_mulc64",
                       "_add64", "_addc64", "_xorc64", "_rotl64",
                       "_shr64_xor", "_fmix64"],
    },
    "_fused_sketch_call": {
        "bindings": {"n_planes": 7, "jobs": 8, "span": 2},
        "in_dtypes": ["uint32", "uint32", "uint32", "uint32",
                      "uint32", "uint32", "uint32"],
        "kernel_fns": ["_make_fused_kernel", "_murmur3_planes",
                       "_tpufast_planes", "_mulc64", "_add64", "_addc64",
                       "_xorc64", "_rotl64", "_shl64", "_shr64_xor",
                       "_fmix64"],
    },
}

_C1 = 0x87C37B91114253D5
_C2 = 0x4CF5AD432745937F
_F1 = 0xFF51AFD7ED558CCD
_F2 = 0xC4CEB9FE1A85EC53


def _limbs16(c: int):
    return [(c >> (16 * j)) & 0xFFFF for j in range(4)]


def _mulc64(hi: jax.Array, lo: jax.Array, c: int):
    """(hi, lo) u32 planes * 64-bit constant c, mod 2^64.

    Schoolbook over 16-bit limbs: products x_i * c_j with i + j <= 3,
    lo16 into column i+j, hi16 into column i+j+1; each column
    accumulates at most 8 terms < 2^16 (< 2^19 total), then one carry
    sweep rebuilds the planes. Zero limbs of c skip their products at
    trace time.
    """
    x = [lo & 0xFFFF, lo >> jnp.uint32(16), hi & 0xFFFF, hi >> jnp.uint32(16)]
    cl = _limbs16(c)
    acc = [None, None, None, None]

    def _addto(k, v):
        acc[k] = v if acc[k] is None else acc[k] + v

    for i in range(4):
        for j in range(4 - i):
            if cl[j] == 0:
                continue
            p = x[i] * jnp.uint32(cl[j])
            k = i + j
            _addto(k, p & 0xFFFF)
            if k + 1 < 4:
                _addto(k + 1, p >> jnp.uint32(16))
    zero = jnp.zeros_like(lo)
    acc = [a if a is not None else zero for a in acc]

    l0 = acc[0] & 0xFFFF
    carry = acc[0] >> jnp.uint32(16)
    a1 = acc[1] + carry
    l1 = a1 & 0xFFFF
    carry = a1 >> jnp.uint32(16)
    a2 = acc[2] + carry
    l2 = a2 & 0xFFFF
    carry = a2 >> jnp.uint32(16)
    l3 = (acc[3] + carry) & 0xFFFF
    return (l2 | (l3 << jnp.uint32(16))), (l0 | (l1 << jnp.uint32(16)))


def _add64(hi, lo, bhi, blo):
    lo2 = lo + blo
    carry = (lo2 < blo).astype(jnp.uint32)
    return hi + bhi + carry, lo2


def _addc64(hi, lo, c: int):
    return _add64(hi, lo, jnp.uint32((c >> 32) & 0xFFFFFFFF),
                  jnp.uint32(c & 0xFFFFFFFF))


def _xorc64(hi, lo, c: int):
    return (hi ^ jnp.uint32((c >> 32) & 0xFFFFFFFF),
            lo ^ jnp.uint32(c & 0xFFFFFFFF))


def _rotl64(hi, lo, r: int):
    if r == 32:
        return lo, hi
    if r < 32:
        return ((hi << jnp.uint32(r)) | (lo >> jnp.uint32(32 - r)),
                (lo << jnp.uint32(r)) | (hi >> jnp.uint32(32 - r)))
    s = r - 32
    return ((lo << jnp.uint32(s)) | (hi >> jnp.uint32(32 - s)),
            (hi << jnp.uint32(s)) | (lo >> jnp.uint32(32 - s)))


def _shr64_xor(hi, lo, r: int):
    """(hi, lo) ^= (hi, lo) >> r, for the fmix xorshifts (r = 33)."""
    if r < 32:
        nhi = hi >> jnp.uint32(r)
        nlo = (lo >> jnp.uint32(r)) | (hi << jnp.uint32(32 - r))
    else:
        nhi = jnp.zeros_like(hi)
        nlo = hi >> jnp.uint32(r - 32)
    return hi ^ nhi, lo ^ nlo


def _fmix64(hi, lo):
    hi, lo = _shr64_xor(hi, lo, 33)
    hi, lo = _mulc64(hi, lo, _F1)
    hi, lo = _shr64_xor(hi, lo, 33)
    hi, lo = _mulc64(hi, lo, _F2)
    return _shr64_xor(hi, lo, 33)


def _murmur3_planes(k1h, k1l, k2h, k2l, th, tl, seed: int):
    """The full murmur3 x64_128 h1 state machine over u32 plane VALUES
    (one 16-byte block + 5-byte k1 tail, length 21) — shared by the
    hash-only kernel and the fused sketch kernel."""
    seed_hi = (seed >> 32) & 0xFFFFFFFF
    seed_lo = seed & 0xFFFFFFFF
    h1h = jnp.full_like(k1h, jnp.uint32(seed_hi))
    h1l = jnp.full_like(k1l, jnp.uint32(seed_lo))
    h2h, h2l = h1h, h1l

    # body block: k1 = rotl(k1*C1, 31)*C2 folded into h1, then k2
    a, b = _mulc64(k1h, k1l, _C1)
    a, b = _rotl64(a, b, 31)
    a, b = _mulc64(a, b, _C2)
    h1h, h1l = h1h ^ a, h1l ^ b
    h1h, h1l = _rotl64(h1h, h1l, 27)
    h1h, h1l = _add64(h1h, h1l, h2h, h2l)
    h1h, h1l = _mulc64(h1h, h1l, 5)
    h1h, h1l = _addc64(h1h, h1l, 0x52DCE729)

    a, b = _mulc64(k2h, k2l, _C2)
    a, b = _rotl64(a, b, 33)
    a, b = _mulc64(a, b, _C1)
    h2h, h2l = h2h ^ a, h2l ^ b
    h2h, h2l = _rotl64(h2h, h2l, 31)
    h2h, h2l = _add64(h2h, h2l, h1h, h1l)
    h2h, h2l = _mulc64(h2h, h2l, 5)
    h2h, h2l = _addc64(h2h, h2l, 0x38495AB5)

    # 5-byte tail folds into h1 only; the contract uses only the
    # low 5 bytes of the tail word, so mask byte 4's plane here
    # rather than trusting every caller to pre-zero bytes 5-7
    a, b = _mulc64(th & 0xFF, tl, _C1)
    a, b = _rotl64(a, b, 31)
    a, b = _mulc64(a, b, _C2)
    h1h, h1l = h1h ^ a, h1l ^ b

    # finalization, length = 21
    h1h, h1l = _xorc64(h1h, h1l, 21)
    h2h, h2l = _xorc64(h2h, h2l, 21)
    h1h, h1l = _add64(h1h, h1l, h2h, h2l)
    h2h, h2l = _add64(h2h, h2l, h1h, h1l)
    h1h, h1l = _fmix64(h1h, h1l)
    h2h, h2l = _fmix64(h2h, h2l)
    h1h, h1l = _add64(h1h, h1l, h2h, h2l)
    return h1h, h1l


def _make_kernel(seed: int):
    def kernel(k1h, k1l, k2h, k2l, th, tl, outh, outl):
        h1h, h1l = _murmur3_planes(k1h[:], k1l[:], k2h[:], k2l[:],
                                   th[:], tl[:], seed)
        outh[:] = h1h
        outl[:] = h1l

    return kernel


def _zi(i):
    return i * 0


@profiled("sketch.murmur3_k21_pallas")
@functools.partial(jax.jit, static_argnames=("seed", "interpret"))
def murmur3_k21_pallas(
    k1: jax.Array,    # uint64 (n,): bytes 0-7 of the canonical k-mer
    k2: jax.Array,    # uint64 (n,): bytes 8-15
    k1t: jax.Array,   # uint64 (n,): bytes 16-20 (low 5 bytes used)
    seed: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """h1 of murmur3 x64_128 over 21-byte keys given as assembled
    little-endian words — bit-identical to ops/hashing._murmur3_k21_1d.
    """
    n = k1.shape[0]
    quantum = BLOCK_SUB * LANES
    n_pad = max(quantum, -(-n // quantum) * quantum)

    def planes(x):
        xp = jnp.zeros((n_pad,), jnp.uint64).at[:n].set(x)
        return ((xp >> jnp.uint64(32)).astype(jnp.uint32)
                .reshape(n_pad // LANES, LANES),
                xp.astype(jnp.uint32).reshape(n_pad // LANES, LANES))

    k1h, k1l = planes(k1)
    k2h, k2l = planes(k2)
    th, tl = planes(k1t)

    rows = n_pad // LANES
    grid = rows // BLOCK_SUB
    spec = pl.BlockSpec((BLOCK_SUB, LANES), lambda i: (i, _zi(i)),
                        memory_space=pltpu.VMEM)
    outh, outl = pl.pallas_call(
        _make_kernel(seed),
        grid=(grid,),
        in_specs=[spec] * 6,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.uint32)],
        interpret=interpret,
    )(k1h, k1l, k2h, k2l, th, tl)
    out = (outh.reshape(-1).astype(jnp.uint64) << jnp.uint64(32)) \
        | outl.reshape(-1).astype(jnp.uint64)
    return out[:n]


# --------------------------------------------------------------------
# Fused hash + running bottom-k candidate reduction (NOT quarantined —
# this is the production fused sketch path behind
# GALAH_TPU_SKETCH_STRATEGY=fused; the quarantine note above covers
# only the hash-only murmur3_k21_pallas entry).
#
# Mosaic has no sort and no scatter, so an exact in-kernel bottom-k is
# off the table. Instead each job (genome) maintains a candidate file
# of per-POSITION-CLASS distinct minima: class = (sublane mod CAND_SUB,
# lane) of the incoming (BLOCK_SUB, LANES) hash block — C = CAND_SUB *
# LANES classes — and R_REG sorted registers per class, updated by a
# dedup check plus a compare-exchange bubble insert on u32 (hi, lo)
# planes. Registers only ever decrease, which yields a completeness
# CERTIFICATE the XLA post-pass checks: with T = the sketch_size-th
# smallest distinct candidate, any class whose final largest register
# m_R < T may have dropped a distinct value below T ("suspect"); if no
# class is suspect the candidate file provably contains the exact
# distinct bottom-k and the fused sketch is bit-identical to the
# chunked XLA / C paths. Suspect jobs (P ~ 1e-4 at the default
# sketch_size=1000: per-class Poisson load lambda ~ 0.5 vs R_REG = 8)
# are re-sketched on the exact chunked path, so the hard determinism
# gate holds unconditionally. Hashes never round-trip to XLA top-k:
# per launch only R_REG * CAND_SUB * LANES candidates per job leave
# the kernel, ~1/1000th of the hash stream.
# --------------------------------------------------------------------

CAND_SUB = 16   # candidate-class sublanes (classes = CAND_SUB x LANES)
R_REG = 8       # distinct-minima registers per class

_U32_SENT = 0xFFFFFFFF  # both planes -> ops/constants.SENTINEL (u64 max)


def _shl64(hi, lo, s: int):
    """(hi, lo) << s, mod 2^64 — the tpufast sparse-multiply shifts."""
    if s == 0:
        return hi, lo
    if s < 32:
        return ((hi << jnp.uint32(s)) | (lo >> jnp.uint32(32 - s)),
                lo << jnp.uint32(s))
    return lo << jnp.uint32(s - 32), jnp.zeros_like(lo)


def _tpufast_planes(kh, kl, seed: int):
    """ops/hashing._tpufast_mix on u32 (hi, lo) planes, bit-identical:
    seed xor, three shift-add sparse-constant rounds with xorshifts,
    and the final fold — adds/shifts/xors only, no multiplies."""
    c = (seed * 0x9E3779B97F4A7C15 + 0x1B873593) % (1 << 64)
    xh, xl = _xorc64(kh, kl, c)
    for sh_a, sh_b, sh_x in ((21, 37, 29), (13, 47, 31), (17, 41, 33)):
        ah, al = _shl64(xh, xl, sh_a)
        bh, bl = _shl64(xh, xl, sh_b)
        xh, xl = _add64(xh, xl, ah, al)
        xh, xl = _add64(xh, xl, bh, bl)
        xh, xl = _shr64_xor(xh, xl, sh_x)
    ah, al = _shl64(xh, xl, 26)
    xh, xl = _add64(xh, xl, ah, al)
    return _shr64_xor(xh, xl, 32)


def _make_fused_kernel(algo: str, seed: int):
    """Fused kernel: hash one (BLOCK_SUB, LANES) block of canonical key
    planes, then fold it into the job's per-class distinct-minima
    registers (the revisited output block, @pl.when-initialized on the
    job's first span step)."""
    n_words = 3 if algo == "murmur3" else 1

    def kernel(*refs):
        word_refs = refs[:2 * n_words]
        mask_ref = refs[2 * n_words]
        outh_ref = refs[2 * n_words + 1]
        outl_ref = refs[2 * n_words + 2]
        s = pl.program_id(1)

        @pl.when(s == 0)
        def _init():
            outh_ref[:] = jnp.full_like(outh_ref[:], jnp.uint32(_U32_SENT))
            outl_ref[:] = jnp.full_like(outl_ref[:], jnp.uint32(_U32_SENT))

        if algo == "murmur3":
            h_hi, h_lo = _murmur3_planes(
                word_refs[0][:], word_refs[1][:], word_refs[2][:],
                word_refs[3][:], word_refs[4][:], word_refs[5][:], seed)
        else:
            h_hi, h_lo = _tpufast_planes(word_refs[0][:], word_refs[1][:],
                                         seed)
        sent = jnp.uint32(_U32_SENT)
        invalid = mask_ref[:] == jnp.uint32(0)
        h_hi = jnp.where(invalid, sent, h_hi)
        h_lo = jnp.where(invalid, sent, h_lo)

        for f in range(BLOCK_SUB // CAND_SUB):
            vh = h_hi[f * CAND_SUB:(f + 1) * CAND_SUB, :]
            vl = h_lo[f * CAND_SUB:(f + 1) * CAND_SUB, :]
            regs = [(outh_ref[i * CAND_SUB:(i + 1) * CAND_SUB, :],
                     outl_ref[i * CAND_SUB:(i + 1) * CAND_SUB, :])
                    for i in range(R_REG)]
            # distinct-minima: a value already held by a register is a
            # duplicate — demote it to the sentinel (which also catches
            # invalid positions: SENT == SENT in the all-SENT init).
            dup = (vh == regs[0][0]) & (vl == regs[0][1])
            for mh, ml in regs[1:]:
                dup = dup | ((vh == mh) & (vl == ml))
            vh = jnp.where(dup, sent, vh)
            vl = jnp.where(dup, sent, vl)
            # sorted bubble insert (u64 lexicographic on the planes):
            # each step keeps the min in register i and carries the max
            # forward; the value displaced from the last register drops
            # out of the file — that loss is what the certificate
            # bounds. Each register is read before its single write, so
            # the pre-read `regs` values stay current through the fold.
            for i in range(R_REG):
                mh, ml = regs[i]
                lt = (vh < mh) | ((vh == mh) & (vl < ml))
                outh_ref[i * CAND_SUB:(i + 1) * CAND_SUB, :] = \
                    jnp.where(lt, vh, mh)
                outl_ref[i * CAND_SUB:(i + 1) * CAND_SUB, :] = \
                    jnp.where(lt, vl, ml)
                vh = jnp.where(lt, mh, vh)
                vl = jnp.where(lt, ml, vl)

    return kernel


def _fused_sketch_call(planes, span: int, algo: str, seed: int,
                       interpret: bool):
    """The fused pallas_call: grid (jobs, span), each job revisiting its
    (R_REG * CAND_SUB, LANES) candidate planes across its span of
    (BLOCK_SUB, LANES) key blocks. `planes` is 2 u32 planes per key
    word plus the validity plane, each (jobs * span * BLOCK_SUB, LANES).
    """
    n_planes = len(planes)
    jobs = planes[0].shape[0] // (span * BLOCK_SUB)
    in_spec = pl.BlockSpec((BLOCK_SUB, LANES),
                           lambda j, s, sp=span: (j * sp + s, _zi(j)),
                           memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((R_REG * CAND_SUB, LANES),
                            lambda j, s: (j, _zi(j)),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _make_fused_kernel(algo, seed),
        grid=(jobs, span),
        in_specs=[in_spec] * n_planes,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((jobs * R_REG * CAND_SUB, LANES),
                                 jnp.uint32),
            jax.ShapeDtypeStruct((jobs * R_REG * CAND_SUB, LANES),
                                 jnp.uint32),
        ],
        interpret=interpret,
    )(*planes)


def fused_sketch_candidates(
    words,            # tuple of uint64 (jobs, W) key-word rows
    valid,            # bool (jobs, W) window validity
    algo: str = "murmur3",
    seed: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """One fused launch: hash every key word and reduce to per-class
    distinct-minima candidates -> (jobs, R_REG, CAND_SUB * LANES)
    uint64, register-major (candidates[:, R_REG - 1] are the per-class
    largest registers the completeness certificate checks).

    W must be a span * BLOCK_SUB * LANES multiple; pad with valid=False
    (padding hashes to the sentinel and never enters the file).
    Unjitted building block — callers embed it in their own jit
    (ops/sketch_stream's group kernel) so the XLA preamble fuses into
    operand production.
    """
    jobs, w = valid.shape
    span = w // (BLOCK_SUB * LANES)
    if span * BLOCK_SUB * LANES != w:
        raise ValueError(
            f"fused sketch width {w} is not a multiple of the "
            f"{BLOCK_SUB * LANES}-position block")

    def planes(x):
        xr = x.reshape(jobs * span * BLOCK_SUB, LANES)
        return ((xr >> jnp.uint64(32)).astype(jnp.uint32),
                xr.astype(jnp.uint32))

    ins = []
    for word in words:
        hi, lo = planes(word)
        ins.extend((hi, lo))
    ins.append(valid.astype(jnp.uint32).reshape(
        jobs * span * BLOCK_SUB, LANES))
    outh, outl = _fused_sketch_call(tuple(ins), span, algo, seed,
                                    interpret)
    cand = (outh.astype(jnp.uint64) << jnp.uint64(32)) \
        | outl.astype(jnp.uint64)
    return cand.reshape(jobs, R_REG, CAND_SUB * LANES)


def assemble_k21_words(cb) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Little-endian key words (k1, k2, tail) from 21 per-byte u64
    vectors — the same shift/or assembly _murmur3_k21_1d runs inline;
    shared so the kernel consumes identical inputs."""
    k1 = cb[0]
    for b in range(1, 8):
        k1 = k1 | (cb[b] << jnp.uint64(8 * b))
    k2 = cb[8]
    for b in range(1, 8):
        k2 = k2 | (cb[8 + b] << jnp.uint64(8 * b))
    t = cb[16]
    for b in range(1, 5):
        t = t | (cb[16 + b] << jnp.uint64(8 * b))
    return k1, k2, t
