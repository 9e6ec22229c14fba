"""Streaming ingest->sketch pipeline: storage-bound, not dispatch-bound.

BASELINE's 100k rung left sketching as the dominant wall term (~7-8
Mbp/s, far below disk bandwidth): the serial shape read-everything ->
sketch-everything leaves the disk idle while the device hashes and the
device idle while the host parses. This module makes the sketch stage a
three-stage stream instead:

  stage 1  ingest    — FASTA parse on the shared prefetch pool
                       (io/prefetch.py; the C parser in csrc/ingest.c
                       already streams gzip), bounded look-ahead;
  stage 2  staging   — host-side packing of genome groups into the
                       device layout (2-bit codes + ambiguity masks +
                       offsets), double-buffered on the same pool so
                       the NEXT batch packs while the previous batch's
                       launch runs;
  stage 3  sketch    — one device dispatch per packed group under the
                       resolved strategy (below).

Memory stays O(depth + workers) genomes: stage 1 holds at most `depth`
parsed genomes ahead, stage 2 at most 2 staged batches, and nothing
else accumulates (sketches are ~8 KB each).

Strategy (GALAH_TPU_SKETCH_STRATEGY pin; unset resolves per backend):

  fused — ops/pallas_sketch.fused_sketch_candidates: ONE Pallas launch
          hashes a whole packed group and reduces it in-kernel to
          per-class distinct-minima candidates, so per-chunk hashes
          never round-trip through an XLA top-k. The XLA post-pass
          checks the completeness certificate; the rare "suspect" job
          re-runs on the exact chunked path — fused sketches are
          therefore BIT-IDENTICAL to the other strategies, always.
  xla   — ops/minhash's chunked/batched XLA kernels (hash -> sort ->
          distinct bottom-k), the historical device path.
  c     — csrc/sketch.c's host bottom-k sketcher, the historical
          single-device-CPU path.

A kernel failure propagates under every strategy, pinned or AUTO.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galah_tpu.config import Defaults
from galah_tpu.obs.profile import profiled
from galah_tpu.ops import hashing
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.minhash import (
    DEFAULT_CHUNK,
    sketch_genome_device,
    sketch_genomes_device_batch,
)
from galah_tpu.ops.minhash_np import MinHashSketch
from galah_tpu.ops.pallas_sketch import (
    BLOCK_SUB,
    CAND_SUB,
    LANES,
    R_REG,
    fused_sketch_candidates,
)
from galah_tpu.obs import flow as obs_flow
from galah_tpu.utils import timing

#: Max total positions per fused launch. Each position ships
#: 2 * n_words + 1 uint32 planes to the kernel (28 B/position for
#: murmur3), so this bounds the staged-buffer and device operand
#: footprint at ~120 MB while still amortizing the launch over many
#: genomes.
FUSED_BUDGET = 1 << 22

#: Positions per (BLOCK_SUB, LANES) kernel block.
_BLOCK = BLOCK_SUB * LANES

#: Candidates per job the fused kernel emits.
_CAND = R_REG * CAND_SUB * LANES

#: Job-count floor for pow2 padding (compile-variant bounding, the
#: pallas_fragment recipe).
_JOB_FLOOR = 8

SKETCH_STRATEGIES = ("fused", "xla", "c")

# Determinism contract, machine-checked by `galah-tpu lint` (GL9xx):
# all three strategies produce bit-identical uint64 sketches — fused
# via the completeness certificate + exact re-sketch of suspect jobs,
# never via float accumulation order.
DETERMINISM_CONTRACT = {
    "family": "sketch",
    "dtype": "uint64",
    "functions": ["resolve_sketch_strategy", "sketch_genomes_fused",
                  "iter_path_sketches"],
}

# Pipeline contract, machine-checked by `galah-tpu lint` (GL10xx):
# these stages are generators that must stay streamed (GL1001/GL1002),
# and this module feeds the occupancy gauge that proves the overlap
# (GL1004; the ROADMAP's "no stage starves" target).
PIPELINE_STAGE = {
    "streaming": ["iter_path_sketches", "iter_sketch_row_blocks"],
    "occupancy_gauge": "workload.pipeline_occupancy",
}


def _c_sketcher_available() -> bool:
    try:
        from galah_tpu.ops import _csketch  # noqa: F401
    except Exception:  # pragma: no cover - import error == no C
        return False
    return True


def resolve_sketch_strategy(
    backend: Optional[str] = None,
    n_devices: Optional[int] = None,
    c_ok: Optional[bool] = None,
) -> Tuple[str, bool]:
    """(strategy, explicit) for the sketch stage.

    An explicit GALAH_TPU_SKETCH_STRATEGY pin always wins (and its
    failures propagate). AUTO keeps the historical winners: the C
    bottom-k sketcher on a single-device CPU runtime, the fused Pallas
    kernel on a real TPU backend, the chunked/batched XLA path
    everywhere else. The injectable parameters exist for selection
    tests; production callers pass nothing.
    """
    env = (os.environ.get("GALAH_TPU_SKETCH_STRATEGY") or "").lower()
    if env in SKETCH_STRATEGIES:
        return env, True
    backend = jax.default_backend() if backend is None else backend
    n_devices = jax.device_count() if n_devices is None else n_devices
    if c_ok is None:
        c_ok = _c_sketcher_available()
    if backend == "cpu" and n_devices == 1 and c_ok:
        return "c", False
    from galah_tpu.ops.hll import use_pallas_default

    if backend == "tpu" and use_pallas_default():
        return "fused", False
    return "xla", False


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _fused_group_sketch_jit(packed, ambits, offsets, k: int, seed: int,
                            algo: str, sketch_size: int, span: int,
                            interpret: bool):
    """One fused dispatch over a packed genome group: XLA preamble
    (unpack + canonical key words), the Pallas hash+reduce launch, and
    the tiny candidate post-pass (sort + dedup + certificate) — all in
    one jit. Returns (sketches (G, sketch_size) uint64 ascending with
    sentinel padding, suspect (G,) bool).

    The certificate: T = the sketch_size-th smallest distinct
    candidate; a job is suspect iff any class's final largest register
    is < T (that class filled up below T and may have dropped a
    distinct value the true bottom-k needs). Non-suspect jobs are
    PROVABLY exact; suspect jobs re-run on the chunked path.
    """
    words, valid = hashing.canonical_kmer_words_batch(
        packed, ambits, offsets, k, algo)
    g, n_win = valid.shape
    pad = span * _BLOCK - n_win
    words = tuple(jnp.pad(w, ((0, 0), (0, pad))) for w in words)
    valid = jnp.pad(valid, ((0, 0), (0, pad)))
    cand = fused_sketch_candidates(words, valid, algo=algo, seed=seed,
                                   interpret=interpret)
    flat = jnp.sort(cand.reshape(g, _CAND), axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((g, 1), bool), flat[:, 1:] == flat[:, :-1]], axis=1)
    distinct = jnp.sort(
        jnp.where(dup, jnp.uint64(SENTINEL), flat), axis=-1)
    sketch = distinct[:, :sketch_size]
    t = distinct[:, sketch_size - 1]
    suspect = jnp.any(cand[:, R_REG - 1, :] < t[:, None], axis=-1)
    return sketch, suspect


_fused_group_sketch = profiled("sketch.fused")(jax.jit(
    _fused_group_sketch_jit,
    static_argnames=("k", "seed", "algo", "sketch_size", "span",
                     "interpret")))


def _pack_fused(genomes):
    """Stage-2 host transform: bucket + 2-bit pack the genomes into
    padded fused launch groups (the pallas_fragment recipe: pow2 job
    count, pow2 length and block span; padding jobs are all-ambiguous
    rows whose
    positions hash to the sentinel and never enter the candidate file).
    The job count is padded up to _JOB_FLOOR only while the launch stays
    within FUSED_BUDGET positions: eight padded jobs of a 2 Mbp genome
    already need more than a v5e chip's 16 GB of HBM. Pure — safe on
    pool threads."""
    skipped, group_iter = hashing.iter_genome_groups(
        genomes, budget=FUSED_BUDGET, max_len=DEFAULT_CHUNK, pow2=True)
    groups = []
    for chunk_idxs, packed, ambits, offs in group_iter:
        g = len(chunk_idxs)
        lb = packed.shape[1] * 4
        span = _pow2(lb // _BLOCK)
        floor = max(1, min(_JOB_FLOOR, FUSED_BUDGET // lb))
        g_pad = _pow2(max(g, floor))
        if g_pad > g:
            packed = np.vstack(
                [packed, np.zeros((g_pad - g, packed.shape[1]),
                                  np.uint8)])
            ambits = np.vstack(
                [ambits, np.full((g_pad - g, ambits.shape[1]), 0xFF,
                                 np.uint8)])
            offs = np.vstack(
                [offs, np.full((g_pad - g, offs.shape[1]),
                               np.int32(2**31 - 1), np.int32)])
        groups.append((chunk_idxs, packed, ambits, offs, span))
    return skipped, groups


def _sketch_packed_fused(genomes, skipped, groups, sketch_size, k,
                         seed, algo, interpret) -> List[MinHashSketch]:
    """Stage-3 launches over prepacked groups + the exact-path sweep
    for skipped (over-length) and suspect jobs."""
    out: List[MinHashSketch] = [None] * len(genomes)  # type: ignore
    for i in skipped:
        out[i] = sketch_genome_device(
            genomes[i], sketch_size=sketch_size, k=k, seed=seed,
            algo=algo)
    launches = jobs = slots = blocks = blocks_needed = suspects = 0
    for chunk_idxs, packed, ambits, offs, span in groups:
        g = len(chunk_idxs)
        g_pad = packed.shape[0]
        timing.dispatch()
        sketch, suspect = _fused_group_sketch(
            jnp.asarray(packed), jnp.asarray(ambits), jnp.asarray(offs),
            k=k, seed=seed, algo=algo, sketch_size=sketch_size,
            span=span, interpret=interpret)
        timing.dispatch(sync=True)
        mat = np.asarray(sketch)
        susp = np.asarray(suspect)
        launches += 1
        jobs += g
        slots += g_pad
        blocks += g_pad * span
        blocks_needed += sum(
            -(-(max(genomes[gi].codes.shape[0] - k + 1, 1)) // _BLOCK)
            for gi in chunk_idxs)
        for row, gi in enumerate(chunk_idxs):
            if susp[row]:
                # the certificate flagged a possible candidate drop:
                # re-sketch exactly (deterministic detection, so the
                # strategy stays bit-identical end to end)
                suspects += 1
                out[gi] = sketch_genome_device(
                    genomes[gi], sketch_size=sketch_size, k=k,
                    seed=seed, algo=algo)
            else:
                hs = mat[row]
                hs = hs[hs != np.uint64(SENTINEL)]
                out[gi] = MinHashSketch(
                    hashes=hs, sketch_size=sketch_size, kmer=k)
    if launches:
        from galah_tpu.obs import metrics as obs_metrics

        timing.counter("sketch-fused-launches", launches)
        timing.counter("sketch-fused-jobs", jobs)
        timing.counter("sketch-fused-job-slots", slots)
        timing.counter("sketch-fused-blocks", blocks)
        timing.counter("sketch-fused-blocks-needed", blocks_needed)
        if suspects:
            timing.counter("sketch-fused-suspect", suspects)
        obs_metrics.gauge(
            "sketch.fused_job_occupancy",
            help="real jobs / padded job slots of the fused sketch "
                 "launches (pow2 job padding waste)",
            unit="fraction").set(jobs / slots)
        obs_metrics.gauge(
            "sketch.fused_span_occupancy",
            help="needed kernel blocks / launched blocks of the fused "
                 "sketch launches (length-bucket + pow2 span waste)",
            unit="fraction").set(blocks_needed / blocks)
    return out


def sketch_genomes_fused(
    genomes: Sequence,
    sketch_size: int = Defaults.MINHASH_SKETCH_SIZE,
    k: int = Defaults.MINHASH_KMER,
    seed: int = Defaults.MINHASH_SEED,
    algo: str = Defaults.HASH_ALGO,
    interpret: Optional[bool] = None,
) -> List[MinHashSketch]:
    """Fused-kernel twin of ops/minhash.sketch_genomes_device_batch,
    bit-identical per genome (hard gate; the suspect certificate makes
    it unconditional). Genomes longer than DEFAULT_CHUNK, and
    sketch_size beyond the candidate capacity, take the exact chunked
    path.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if sketch_size > _CAND // 4:
        # candidate capacity cannot certify completeness cheaply —
        # not a production shape (default sketch_size=1000 vs 16384
        # candidates); take the exact path outright.
        return sketch_genomes_device_batch(
            genomes, sketch_size=sketch_size, k=k, seed=seed, algo=algo)
    skipped, groups = _pack_fused(genomes)
    return _sketch_packed_fused(genomes, skipped, groups, sketch_size,
                                k, seed, algo, interpret)


def ingest_depth(threads: int) -> int:
    """Stage-1 look-ahead depth: GALAH_TPU_INGEST_DEPTH pin, else
    max(2, threads) — deep enough to keep `threads` parser workers
    busy, shallow enough to bound resident parsed genomes."""
    env = os.environ.get("GALAH_TPU_INGEST_DEPTH")
    if env:
        return max(1, int(env))
    return max(2, threads)


def _ingest_read(path: str):
    """Stage-1 loader: the FASTA read, with the fault injector
    consulted at an `io.ingest` site first so slow-disk/backpressure
    behavior is testable (GALAH_FI kind=slow-io)."""
    from galah_tpu.io.fasta import read_genome
    from galah_tpu.resilience import faults

    injector = faults.get_injector()
    if injector is not None:
        injector.filesystem(f"io.ingest[{path}]")
    return read_genome(path)


def _iter_staged(items: Iterator, stage_fn, depth: int = 2):
    """Ordered double-buffered staging on the shared prefetch pool:
    submit stage_fn(item) keeping at most `depth` staged results in
    flight, yield (item, result) in submission order. With depth=2 the
    next batch packs while the caller consumes (launches) the previous
    one."""
    from galah_tpu.io import prefetch

    pool = prefetch._shared_pool(depth)
    pending: deque = deque()
    it = iter(items)
    token = timing.stage_token()
    ftoken = obs_flow.token()

    def staged(item):
        # stage-token adoption: telemetry from the pool thread lands
        # on the submitting thread's stage (and flow context), not an
        # empty stack
        with timing.adopt(token), obs_flow.adopt(ftoken):
            return stage_fn(item)

    def submit_next() -> bool:
        try:
            item = next(it)
        except StopIteration:
            return False
        pending.append((item, pool.submit(staged, item)))
        return True

    try:
        for _ in range(depth):
            if not submit_next():
                break
        while pending:
            item, fut = pending.popleft()
            result = fut.result()
            submit_next()
            yield item, result
    finally:
        prefetch._settle(fut for _, fut in pending)


def _iter_fused_sketches(miss_iter, sketch_size, k, seed, algo):
    """(path, sketch) stream under the fused strategy: stage-2 packing
    double-buffered against stage-3 launches. The pack step is a pure
    host transform (iter_genome_groups' bucketing + 2-bit packing);
    the launch step runs the fused group dispatches on the consumer
    thread."""
    from galah_tpu.io import prefetch

    interpret = jax.default_backend() != "tpu"

    def pack(buf):
        return _pack_fused([g for _, g in buf])

    batches = prefetch.iter_batches(
        miss_iter, lambda g: g.codes.shape[0], FUSED_BUDGET)
    for buf, (skipped, groups) in _iter_staged(batches, pack, depth=2):
        gs = [g for _, g in buf]
        sketches = _sketch_packed_fused(gs, skipped, groups, sketch_size,
                                        k, seed, algo, interpret)
        for (p, _g), s in zip(buf, sketches):
            yield p, s


def _emit_sketch_occupancy(wall: float, wait_s: float,
                           ingest_s: list) -> float:
    """Refresh the sketch/ingest occupancy gauges mid-stream (the
    heartbeat thread samples them into its time-series)."""
    from galah_tpu.obs import metrics as obs_metrics

    wall = max(wall, 1e-9)
    occ = 1.0 - wait_s / wall
    obs_metrics.pipeline_occupancy(occ, stage="sketch")
    obs_metrics.pipeline_occupancy(sum(ingest_s) / wall, stage="ingest")
    return occ


def iter_path_sketches(
    paths: Sequence[str],
    store,
    threads: int = 1,
    strategy: Optional[str] = None,
) -> Iterator[Tuple[str, MinHashSketch]]:
    """The streaming sketch stage: yield (path, sketch) for the UNIQUE
    paths, in path order, overlapping ingest, staging, and sketch
    compute. Cache hits (store.get_cached) yield without any IO;
    misses stream through the resolved strategy and are inserted into
    the store on this (consumer) thread — the single-writer rule the
    sketching backends share.
    """
    from galah_tpu.io.prefetch import probe_and_prefetch, process_stream
    from galah_tpu.resilience import dispatch as rdispatch

    if strategy is None:
        strategy, _explicit = resolve_sketch_strategy()
    if strategy == "fused" and store.sketch_size > _CAND // 4:
        # candidate capacity cannot certify completeness at this
        # sketch_size — route to the exact batched path
        strategy = "xla"
    timing.counter(f"sketch-strategy-{strategy}", 1)

    t0 = time.monotonic()
    bp_total = 0

    # per-read ingest wall, appended from the prefetch workers (list
    # append is atomic); its sum over the stage wall is the ingest
    # stage's occupancy gauge
    ingest_s: list = []

    def _timed_ingest(path):
        ti = time.monotonic()
        g = _ingest_read(path)
        ingest_s.append(time.monotonic() - ti)
        return g

    hits, miss_iter = probe_and_prefetch(
        paths, store.get_cached, _timed_ingest,
        depth=ingest_depth(threads))

    def counting(it):
        nonlocal bp_total
        for p, g in it:
            bp_total += int(g.codes.shape[0])
            yield p, g

    miss_iter = counting(miss_iter)

    # Ingest-time prefilter (ops/prefilter.py): provably conservative
    # duplicate/degenerate screening ahead of the batched sketcher,
    # plus the HLL pre-warm the bucketed pass reuses. Screened paths
    # never reach `computed`; the merge loop resolves them instead.
    from galah_tpu.ops import prefilter as _prefilter

    pre = _prefilter.maybe_prefilter(store)
    if pre is not None:
        miss_iter = pre.screen(miss_iter)

    if strategy == "fused":
        computed = _iter_fused_sketches(
            miss_iter, store.sketch_size, store.k, store.seed,
            store.algo)
    elif strategy == "xla":
        def sketch_batch(buf):
            # Guarded device dispatch: retries transient failures and,
            # after repeated ones, demotes this site to the per-genome
            # CPU sketch path for the rest of the run.
            return rdispatch.run(
                "dispatch.sketch-minhash",
                lambda: store.sketch_batch_only(buf),
                fallback=lambda: [store.sketch_only(g)
                                  for _p, g in buf],
                validate=rdispatch.expect_len(len(buf)))

        computed = process_stream(
            miss_iter, lambda g: g.codes.shape[0],
            hashing.BATCH_BUDGET, sketch_batch,
            lambda _path, g: store.sketch_only(g),
            batched=True, workers=threads)
    elif strategy == "c":
        computed = process_stream(
            miss_iter, lambda g: g.codes.shape[0],
            hashing.BATCH_BUDGET, None,
            lambda _path, g: store.sketch_only(g),
            batched=False, workers=threads)
    else:
        raise ValueError(f"unknown sketch strategy {strategy!r}")

    # Misses stream back in submission order == path order restricted
    # to misses, so a single merge walk yields every unique path in
    # original order — the property the overlapped pair pass needs.
    wait_s = 0.0
    yielded = 0
    # One-slot pushback: when the compute pipeline runs ahead of the
    # merge walk (its look-ahead pulled paths the prefilter screened
    # out), the next computed sketch parks here until its path comes
    # up in the walk.
    parked: Optional[tuple] = None
    for p in dict.fromkeys(paths):
        s = hits.get(p)
        if s is None and pre is not None:
            ps = pre.resolve(p)
            if ps is not None:
                s = store.insert_prefiltered(p, ps)
        if s is None and parked is not None and parked[0] == p:
            s = store.insert(p, parked[1])
            parked = None
        if s is None:
            # time blocked on the producer = consumer starvation; the
            # complement is the occupancy the overlap is meant to buy
            # (obs/flow records it as the sketch stage's
            # upstream-empty wait for `galah-tpu flow analyze`)
            with obs_flow.blocked("sketch", "upstream-empty") as bw:
                try:
                    cp, cs = next(computed)
                except StopIteration:
                    cp, cs = None, None
            wait_s += bw.seconds
            if cp == p:
                s = store.insert(p, cs)
            else:
                # p was screened while the pipeline looked ahead to
                # cp (or to exhaustion): the skip record exists now.
                assert parked is None, \
                    f"sketch stream out of order: {cp} != {p}"
                if cp is not None:
                    parked = (cp, cs)
                ps = pre.resolve(p) if pre is not None else None
                assert ps is not None, \
                    f"sketch stream out of order: {cp} != {p}"
                s = store.insert_prefiltered(p, ps)
        yield p, s
        yielded += 1
        # live gauge refresh so the heartbeat samples a moving
        # occupancy time-series, not only the quiesce value
        if bp_total and yielded % 64 == 0:
            _emit_sketch_occupancy(time.monotonic() - t0, wait_s,
                                   ingest_s)

    wall = max(time.monotonic() - t0, 1e-9)
    if bp_total:
        from galah_tpu.obs import metrics as obs_metrics

        obs_metrics.gauge(
            "workload.ingest_mbp",
            help="megabases ingested by the streaming sketch stage",
            unit="Mbp").set(bp_total / 1e6)
        obs_metrics.gauge(
            "workload.ingest_mbp_s",
            help="end-to-end ingest+sketch throughput of the streaming "
                 "sketch stage", unit="Mbp/s").set(bp_total / 1e6 / wall)
        occ = _emit_sketch_occupancy(wall, wait_s, ingest_s)
        # the unlabelled gauge keeps its historical meaning (this
        # stage's occupancy) until the overlapped engine overwrites it
        # with the whole-pipeline mean at quiesce (cluster/engine.py)
        obs_metrics.pipeline_occupancy(occ)
        obs_flow.record_service("sketch", max(wall - wait_s, 0.0),
                                items=yielded)
        obs_flow.record_service("ingest", sum(ingest_s),
                                items=len(ingest_s))


def iter_sketch_row_blocks(
    paths: Sequence[str],
    store,
    threads: int = 1,
    strategy: Optional[str] = None,
    block: int = 256,
):
    """Row-block consumer of the sketch stream for the overlapped pair
    pass: yield (r0, rows) with rows an (b, sketch_size) uint64
    sentinel-padded matrix over the unique paths in order, while the
    stream keeps ingesting ahead on the pool threads."""
    from galah_tpu.ops.minhash import sketch_matrix

    buf: list = []
    r0 = 0
    for _p, s in iter_path_sketches(paths, store, threads=threads,
                                    strategy=strategy):
        buf.append(s)
        if len(buf) == block:
            fid = obs_flow.begin("sketch_block")
            rows = sketch_matrix(buf, sketch_size=store.sketch_size)
            obs_flow.emit("sketch", fid)
            yield r0, rows
            r0 += len(buf)
            buf = []
    if buf:
        fid = obs_flow.begin("sketch_block")
        rows = sketch_matrix(buf, sketch_size=store.sketch_size)
        obs_flow.emit("sketch", fid)
        yield r0, rows
