"""Pallas TPU kernel: fragment-ANI window matching on device-resident planes.

The exact-ANI refinement counts, per (query, reference) genome pair,
how many of each query window's surviving k-mer hashes are members of
the reference's sorted distinct-hash set. At MAG length a genome holds
millions of such hashes, so what a pair costs is dominated by moving
them: a launch that packs and uploads both sides per pair spends far
longer on the host and the link than in the kernel. Here each
profile's hashes are uploaded ONCE, as 32-bit hi/lo planes kept in a
bounded device cache, and a pair's launch carries only its per-job
row plan (two int32 scalars per 1024 query hashes).

Membership: both sides are sorted, so query job j's values lie in
[first_j, last_j] and only the reference rows (of 128 sorted hashes)
holding values in that range can hit. The host plans them with one
`searchsorted` per pair (O(jobs log H)) as a first and last row per
job; the kernel reads the plan by scalar prefetch:

  * JOB = QB = 8*128 = 1024 consecutive sorted query hashes, row-major
    in an (8, 128) int32 plane pair in VMEM, so the int8 flags come
    back in the query's own element order;
  * the grid is (jobs, SPAN): step s of job j brings reference block
    first_j // 8 + s — an (8, 128) plane pair — into SMEM, SPAN being
    the pow2 bucket of the widest job; steps past a job's own blocks
    repeat its last block (no new DMA) and skip the compare;
  * each needed reference hash is a scalar read from SMEM and compared
    against the whole query block in two vector ops, OR-accumulated;
    no lane broadcast, rotate or reduction is needed.

u64 hashes are split into hi/lo 32-bit planes ON THE HOST, so no 64-bit
dtype reaches the kernel boundary (GL106); only equality is asked of
them, so they travel as int32 bit patterns. Sentinel query tail slots
(both planes all ones) are masked in-kernel; sentinel reference
padding can only equal them.

Launches are dispatched asynchronously, a few in flight, so the host
folds one pair's flags (one bincount per pair, in fragment_ani) while
the device runs the next. Counts are exact integers, so DirectedANI
floats stay bit-identical to the XLA and C paths.
"""

from __future__ import annotations

import collections
import weakref
from typing import Iterator, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galah_tpu.obs.profile import profiled
from galah_tpu.ops.pallas_pairwise import _zi
from galah_tpu.utils import timing

A_SUB = 8
B_LANE = 128

# Query elements per job row: an (A_SUB, QLA) int32 plane pair.
QLA = 128
# Reference sublane rows per block: an (RSB, B_LANE) plane pair.
RSB = 8
_RSB_LOG2 = 3
QB = A_SUB * QLA
RB = RSB * B_LANE

# Job-count bucket floor: a profile's query planes are padded to a pow2
# job count so the compile cache sees a small lattice of grid sizes.
_JOB_FLOOR = 8

# Device bytes the plane cache may hold. A 3.5 Mbp genome's query and
# reference planes take ~64 MB, so this keeps ~90 genomes resident and
# leaves most of a 16 GB chip to the stages that run before and after.
PLANE_CACHE_BYTES = 6 << 30

# Launches in flight before the oldest one's flags are fetched.
PIPELINE_DEPTH = 4

# Independent accumulators in the kernel's compare chain, so successive
# compares do not wait on one another.
_N_ACC = 4

# Static kernel contract checked by `galah-tpu lint` (GL1xx):
# representative bindings at the production geometry (QLA=128, RSB=8)
# and a 2-block scan window.
PALLAS_CONTRACT = {
    "_window_hits_jit": {
        "bindings": {"qla": 128, "rsb": 8, "span": 2},
        "in_dtypes": ["int32", "int32", "int32", "int32", "int32",
                      "int32"],
        "kernel_fns": ["_make_fragment_kernel"],
    },
}

# Numeric-determinism contract checked by `galah-tpu lint` (GL9xx):
# per-element membership hits are exact integer counts; the kernel and
# the XLA path must agree bit-for-bit.
DETERMINISM_CONTRACT = {
    "family": "fragment",
    "dtype": "int32",
    "functions": ["iter_element_hits", "_window_hits_jit"],
}


def _make_fragment_kernel(qla: int, rsb: int):
    """Kernel body: one (job, span-step) program OR-accumulating per-
    element membership flags of an (A_SUB, qla) query block against the
    rows of one (rsb, B_LANE) reference block that the job needs.

    The reference block sits in SMEM, so each of its hashes is a scalar
    compared against the whole query block in two vector ops — no lane
    broadcast, rotate or reduce."""

    def kernel(first_ref, last_ref, qh_ref, ql_ref, rh_ref, rl_ref,
               hits_ref):
        j = pl.program_id(0)
        s = pl.program_id(1)
        first = first_ref[j]
        last = last_ref[j]
        # shifts, not //: floor division promotes to int64 under x64
        block = (first >> _RSB_LOG2) + s

        @pl.when(s == 0)
        def _init():
            hits_ref[...] = jnp.zeros_like(hits_ref)

        @pl.when(s <= (last >> _RSB_LOG2) - (first >> _RSB_LOG2))
        def _scan():
            qh = qh_ref[...]
            ql = ql_ref[...]
            sent = jnp.int32(-1)
            valid = jnp.logical_not((qh == sent) & (ql == sent))
            zero = jnp.zeros((A_SUB, qla), dtype=jnp.int32)
            one = jnp.ones((A_SUB, qla), dtype=jnp.int32)

            def row(r, accs):
                accs = list(accs)
                for lane in range(B_LANE):
                    hit = (qh == rh_ref[r, lane]) & (ql == rl_ref[r, lane])
                    k = lane % _N_ACC
                    accs[k] = jnp.where(hit, one, accs[k])
                return tuple(accs)

            row0 = jnp.maximum(first - block * rsb, 0)
            row1 = jnp.minimum(last - block * rsb, rsb - 1) + 1
            accs = jax.lax.fori_loop(row0, row1, row, (zero,) * _N_ACC)
            acc = accs[0]
            for other in accs[1:]:
                acc = acc | other
            hits_ref[...] = hits_ref[...] | jnp.where(valid, acc, zero)

    return kernel


def _window_hits_jit(
    first: jax.Array,  # int32 (jobs,) first reference row per job
    last: jax.Array,   # int32 (jobs,) last reference row (-1: none)
    q_hi: jax.Array,   # int32 (jobs*A_SUB, qla) row-major query plane
    q_lo: jax.Array,
    r_hi: jax.Array,   # int32 (blocks*rsb, B_LANE) reference planes
    r_lo: jax.Array,
    span: int,
    interpret: bool,
) -> jax.Array:
    """One pair: per-element membership flags, int8 (jobs*A_SUB, qla)
    in the query planes' (element) order. Job j scans reference rows
    first[j] .. last[j], in span steps of one block each."""
    n_rows, qla = q_hi.shape
    n_jobs = n_rows // A_SUB

    def q_map(j, s, first_ref, last_ref):
        return (j, _zi(j))

    def r_map(j, s, first_ref, last_ref):
        f = first_ref[j] >> _RSB_LOG2
        last_step = jnp.maximum((last_ref[j] >> _RSB_LOG2) - f, 0)
        return (f + jnp.minimum(s, last_step), _zi(j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_jobs, span),
        in_specs=[
            pl.BlockSpec((A_SUB, qla), q_map),
            pl.BlockSpec((A_SUB, qla), q_map),
            pl.BlockSpec((RSB, B_LANE), r_map, memory_space=pltpu.SMEM),
            pl.BlockSpec((RSB, B_LANE), r_map, memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((A_SUB, qla), q_map),
    )
    hits = pl.pallas_call(
        _make_fragment_kernel(qla, RSB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, qla), jnp.int32),
        interpret=interpret,
    )(first, last, q_hi, q_lo, r_hi, r_lo)
    return hits.astype(jnp.int8)


_window_hits = profiled("fragment.window_hits")(
    jax.jit(_window_hits_jit, static_argnames=("span", "interpret")))


def _bucket_jobs(n: int) -> int:
    b = _JOB_FLOOR
    while b < n:
        b <<= 1
    return b


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _planes(vals: np.ndarray, n_slots: int,
            width: int) -> Tuple[jax.Array, jax.Array]:
    """Sorted u64 hashes as sentinel-padded (n_slots / width, width)
    int32 hi/lo planes on the device (bit patterns: only equality is
    ever asked of them; the sentinel is all ones in both)."""
    words = np.ascontiguousarray(vals, dtype="<u8").view("<i4").reshape(
        -1, 2)
    hi = np.full(n_slots, -1, dtype=np.int32)
    lo = np.full(n_slots, -1, dtype=np.int32)
    hi[:vals.shape[0]] = words[:, 1]
    lo[:vals.shape[0]] = words[:, 0]
    return (jnp.asarray(hi.reshape(-1, width)),
            jnp.asarray(lo.reshape(-1, width)))


def _query_planes(qh: np.ndarray) -> Tuple[jax.Array, jax.Array]:
    """Sorted query hashes as (jobs*A_SUB, QLA) planes, the job count
    padded to its pow2 bucket."""
    return _planes(qh, _bucket_jobs(-(-qh.shape[0] // QB)) * QB, QLA)


def _ref_planes(ref: np.ndarray) -> Tuple[jax.Array, jax.Array]:
    """A sorted distinct reference set as (blocks*RSB, B_LANE) planes,
    the block count padded to its pow2 bucket."""
    return _planes(ref, _pow2(max(-(-ref.shape[0] // RB), 1)) * RB,
                   B_LANE)


class _PlaneCache:
    """Device planes of recently used host arrays, least recently used
    evicted past a byte budget. Entries are keyed by the host array's
    identity and hold it weakly: once a profile drops the array, its
    planes go at the next lookup."""

    def __init__(self, budget: int) -> None:
        self.budget = budget
        self._entries: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        self._bytes = 0

    def _drop(self, key: int) -> None:
        _ref, _planes, nbytes = self._entries.pop(key)
        self._bytes -= nbytes

    def get(self, arr: np.ndarray, build) -> Tuple[jax.Array, jax.Array]:
        for key in [k for k, e in self._entries.items() if e[0]() is None]:
            self._drop(key)
        key = id(arr)
        entry = self._entries.get(key)
        if entry is not None and entry[0]() is arr:
            self._entries.move_to_end(key)
            return entry[1]
        if entry is not None:
            self._drop(key)
        planes = build(arr)
        nbytes = sum(int(p.nbytes) for p in planes)
        timing.counter("fragment-pallas-uploads", 1)
        self._entries[key] = (weakref.ref(arr), planes, nbytes)
        self._bytes += nbytes
        while self._bytes > self.budget and len(self._entries) > 1:
            self._drop(next(iter(self._entries)))
        return planes

    def clear(self) -> None:
        self._entries.clear()
        self._bytes = 0


_QUERY_CACHE = _PlaneCache(PLANE_CACHE_BYTES // 2)
_REF_CACHE = _PlaneCache(PLANE_CACHE_BYTES // 2)


def clear_plane_cache() -> None:
    """Drop every cached device plane (tests; the start of each
    cluster run)."""
    _QUERY_CACHE.clear()
    _REF_CACHE.clear()


def _plan_pair(qh: np.ndarray, ref: np.ndarray, n_rblocks: int
               ) -> Tuple[int, np.ndarray, np.ndarray]:
    """(n_jobs, first_row, last_row) for one pair: the reference rows of
    B_LANE sorted hashes that each query job's [first, last] value range
    can hit. Computed on the UNPADDED reference (padding is
    all-sentinel, above every valid hash); a job with no reference
    values in range still names one row, so it scans one block."""
    n_q = qh.shape[0]
    n_jobs = -(-n_q // QB)
    if n_jobs == 0:
        z = np.zeros(0, dtype=np.int64)
        return 0, z, z
    n_rrows = max(n_rblocks, 1) * RSB
    firsts = qh[::QB]
    lasts = qh[np.minimum(np.arange(1, n_jobs + 1) * QB, n_q) - 1]
    lo_e = np.searchsorted(ref, firsts, side="left")
    hi_e = np.searchsorted(ref, lasts, side="right")
    first = np.minimum(lo_e // B_LANE, n_rrows - 1)
    last = np.clip((hi_e - 1) // B_LANE, first, n_rrows - 1)
    return n_jobs, first, last


def _dispatch(qh: np.ndarray, ref: np.ndarray,
              interpret: bool) -> jax.Array:
    """Plan one pair and launch its kernel (asynchronously)."""
    q_hi, q_lo = _QUERY_CACHE.get(qh, _query_planes)
    r_hi, r_lo = _REF_CACHE.get(ref, _ref_planes)
    n_slots = q_hi.shape[0] // A_SUB
    n_jobs, first, last = _plan_pair(qh, ref, r_hi.shape[0] // RSB)
    first_p = np.zeros(n_slots, dtype=np.int32)
    last_p = np.full(n_slots, -1, dtype=np.int32)
    first_p[:n_jobs] = first
    last_p[:n_jobs] = last
    blocks = last // RSB - first // RSB + 1
    span_max = _pow2(int(blocks.max()))

    timing.counter("fragment-pallas-launches", 1)
    timing.counter("fragment-pallas-jobs", n_jobs)
    timing.counter("fragment-pallas-job-slots", n_slots)
    timing.counter("fragment-pallas-ref-blocks", n_slots * span_max)
    timing.counter("fragment-pallas-ref-blocks-needed", int(blocks.sum()))
    timing.counter("fragment-pallas-ref-rows", int((last - first + 1).sum()))
    timing.dispatch()
    return _window_hits(first_p, last_p, q_hi, q_lo, r_hi, r_lo,
                        span=span_max, interpret=interpret)


def iter_element_hits(
    items: Sequence[Tuple[np.ndarray, np.ndarray]],
    interpret: bool = False,
) -> Iterator[Tuple[int, np.ndarray]]:
    """(index, flags) per item, in item order, with up to
    PIPELINE_DEPTH launches in flight.

    `items[i]` is `(qh, ref)`: the pair's sorted surviving query hashes
    (uint64, no sentinels — GenomeProfile.sorted_query()'s first array)
    and the reference's sorted distinct set (GenomeProfile.ref_set). The
    same array objects across calls reuse their device planes. `flags`
    is int8 0/1 aligned to `qh` — `flags[e] == 1` iff `qh[e]` is a
    member of `ref`.

    Each live item's launch (plane lookup or upload, plan, launch) and
    its blocking flag fetch are the spans `fragment-ani.launch` and
    `fragment-ani.fetch`, both with the item's index as `direction`.
    """
    pending: "collections.deque" = collections.deque()

    def fetch():
        i, n_q, dev = pending.popleft()
        if not n_q:
            return i, dev
        with timing.stage("fragment-ani.fetch", {"direction": i}):
            flags = np.asarray(dev).reshape(-1)[:n_q]
        timing.dispatch(sync=True)
        return i, flags

    for i, (qh, ref) in enumerate(items):
        if qh.shape[0] == 0:
            # empty queries hit nothing by definition
            pending.append((i, 0, np.zeros(0, dtype=np.int8)))
        else:
            with timing.stage("fragment-ani.launch", {"direction": i}):
                dev = _dispatch(qh, ref, interpret)
            pending.append((i, qh.shape[0], dev))
        if len(pending) > PIPELINE_DEPTH:
            yield fetch()
    while pending:
        yield fetch()
