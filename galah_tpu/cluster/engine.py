"""Two-stage greedy clustering engine.

Re-implements the reference's engine semantics exactly (reference:
src/clusterer.rs:14-125) with one structural change: every per-genome
candidate ANI set is evaluated as ONE batched backend call instead of the
reference's per-pair threads with `find_any` early exit. The greedy
decisions are identical — "is any candidate ANI >= threshold" does not
depend on which subset the early exit happened to compute — but here they
are deterministic, and the ANI cache is a superset of the reference's.

Semantics preserved:
  * genomes arrive pre-sorted by quality; rep selection scans them in
    order, so earlier (higher-quality) genomes become representatives
    (reference: src/clusterer.rs:164-223).
  * candidate reps for genome i = current reps with a precluster-cache
    hit against i (reference: src/clusterer.rs:167-177).
  * when precluster and cluster methods match, precluster ANIs are reused
    instead of recomputed (reference: src/clusterer.rs:29-33,180-186).
  * membership: each non-rep is assigned to the argmax-ANI rep over all
    cached/computed rep ANIs — NO threshold filter at this stage, ties
    to the lowest rep index (reference: src/clusterer.rs:371-403).
  * rep-phase ANIs carry into the membership phase via the shared cache
    (reference: src/clusterer.rs:160-162,211,321-334).
"""

from __future__ import annotations

import logging
import time
from typing import (TYPE_CHECKING, Dict, List, Optional, Sequence, Set,
                    Tuple)

if TYPE_CHECKING:
    from galah_tpu.cluster.checkpoint import ClusterCheckpoint

from galah_tpu.backends.base import ClusterBackend, PreclusterBackend
from galah_tpu.cluster.cache import PairDistanceCache, pair_key
from galah_tpu.cluster.partition import partition_preclusters
from galah_tpu.resilience import interrupt
from galah_tpu.utils import timing

logger = logging.getLogger(__name__)

# GL10xx pipeline-discipline contract (analysis/pipeline_check.py):
# the overlapped dataflow consumes the precluster pair stream
# incrementally and must report how busy it kept each downstream stage
# (speculative fragment-ANI, eager greedy rounds).
PIPELINE_STAGE = {
    "streaming": ["_cluster_overlapped"],
    "occupancy_gauge": "workload.pipeline_occupancy",
}


DENSE_PRECLUSTER_CAP = 64

# Materialization sub-rounds per device-strategy window: each sub-round
# batches one new frontier rep per precluster segment against its later
# window neighbors, so this bounds the rep-chain depth a window resolves
# on device; deeper windows are conflict windows and finish on the
# host-order scan (greedy_select.FOLD_ITERS is kept at 2x this).
MAX_SUBROUNDS = 16

# Unique-genome cap per device-strategy backend dispatch: bounds the
# profile heap one chunk pins at once (see the batch() closure in
# _cluster_pending_rounds). Matches DENSE_PRECLUSTER_CAP, and stays
# under the ProfileStore's default LRU bound (128) so a chunk never
# thrashes its own working set.
ROUND_BATCH_GENOMES = 64

# Host-strategy speculative rep-scan batch width: genomes per window
# evaluated against all current reps in one backend call. Configurable
# via cluster(rep_scan_window=...) / --rep-scan-window; the waste it
# buys (ANIs computed but never consulted by a decision) is measured
# per run as the exact-ani-wasted counter in the stage report.
REP_SCAN_WINDOW = 128


def cluster(
    genomes: Sequence[str],
    preclusterer: PreclusterBackend,
    clusterer: ClusterBackend,
    checkpoint: Optional["ClusterCheckpoint"] = None,
    dense_precluster_cap: int = DENSE_PRECLUSTER_CAP,
    rep_scan_window: Optional[int] = None,
    rep_rounds: Optional[int] = None,
) -> List[List[int]]:
    """Cluster quality-ordered genome paths -> list of index clusters.

    Each cluster lists its representative first; clusters are ordered by
    precluster processing order (biggest precluster first) then by
    representative index — deterministic, unlike the reference's
    thread-completion order.

    With a `checkpoint` (cluster/checkpoint.py), the distance pass and
    each finished precluster persist to disk; an interrupted run resumes
    from the last completed precluster.

    Preclusters up to `dense_precluster_cap` members compute exact ANI
    for ALL their precluster-hit pairs in one batched dispatch before
    the greedy loop (every pair the loop could consult is a hit pair),
    so the sequential rep scan touches no device at all. The extra ANIs
    beyond what early exits would have needed are the same waste class
    as the reference's find_any computing an unpredictable candidate
    subset (reference: src/clusterer.rs:242-262) — traded here for one
    round trip per precluster instead of one per genome.

    Waste is measured, not assumed: the exact-ani-computed /
    exact-ani-wasted counters in the stage report count backend-computed
    pairs never read by any decision (exact-ani-wasted-rep /
    -membership / -warm split the total by the phase that paid for the
    speculation). On the 18-MAG abisko campaign (2026-07-30, fast mode,
    99% ANI) the windowed path computed 62 ANIs
    with 0 wasted — the membership argmax consults every (non-rep, rep)
    pair, consuming the speculation — while the dense-warm path computed
    153 with 91 unconsulted (59%), the price of one-dispatch-per-
    precluster. `rep_scan_window` (CLI --rep-scan-window) tunes the
    speculative width; tests/test_campaign_abisko18.py bounds the waste.

    Strategy: GALAH_TPU_GREEDY_STRATEGY pins the greedy scan to the
    round-based `device` path (K-genome rounds across ALL pending
    preclusters, one batched dispatch per round, jitted segmented
    selection — ops/greedy_select.py, `rep_rounds` / --rep-rounds sets
    K) or the per-precluster `host` scan; unset AUTO runs the device
    path and demotes to the host scan on failure
    (greedy-device-demoted). Decisions are bit-identical either way
    (docs/cluster_engine.md).
    """
    from galah_tpu.ops import pallas_fragment
    from galah_tpu.resilience import dispatch as rdispatch

    rdispatch.begin_run()
    # device planes of an earlier run's profiles are dead weight in HBM
    pallas_fragment.clear_plane_cache()
    skip_clusterer = preclusterer.method_name() == clusterer.method_name()
    if skip_clusterer:
        logger.info(
            "Preclustering and clustering methods are the same, "
            "so reusing ANI values")

    # Workload fingerprint gauges: the perf ledger (obs/ledger.py) keys
    # cross-run comparison on them, so a run is only compared against
    # history with the same N and sketch K.
    from galah_tpu.obs import metrics as obs_metrics
    from galah_tpu.obs import profile as obs_profile

    obs_metrics.gauge(
        "workload.n_genomes",
        help="Genomes in this clustering run").set(float(len(genomes)))
    sketch_k = getattr(preclusterer, "sketch_size", None)
    if sketch_k:
        obs_metrics.gauge(
            "workload.sketch_k",
            help="MinHash sketch size of the precluster backend").set(
            float(sketch_k))
    ingest_threads = getattr(preclusterer, "threads", None)
    if ingest_threads:
        from galah_tpu.ops.sketch_stream import ingest_depth

        obs_metrics.gauge(
            "workload.ingest_depth",
            help="Streaming ingest look-ahead depth "
                 "(GALAH_TPU_INGEST_DEPTH or max(2, threads))").set(
            float(ingest_depth(int(ingest_threads))))

    # Bucketed pair-pass entry: record whether the HLL cardinality
    # bands prune this run's lattice (the preclusterer routes itself;
    # the gauge keys the funnel and the perf-report narrative).
    from galah_tpu.ops.bucketing import (
        bucketing_engaged,
        resolve_hll_buckets,
    )

    hll_buckets = (bucketing_engaged(len(genomes))
                   and preclusterer.method_name() == "finch")
    obs_metrics.gauge(
        "workload.hll_buckets",
        help="1 when the HLL cardinality-bucketed precluster pass is "
             "engaged for this run (GALAH_TPU_HLL_BUCKETS)").set(
        float(hll_buckets))
    if hll_buckets:
        logger.info(
            "HLL cardinality bucketing engaged for the precluster "
            "pair pass (GALAH_TPU_HLL_BUCKETS=%s)",
            resolve_hll_buckets())

    pre_cache = checkpoint.load_distances() if checkpoint else None
    overlap_state = None
    if pre_cache is None:
        # overlapped end-to-end dataflow (docs/dataflow.md): the
        # streaming phase runs the pair pass, speculative fragment-ANI
        # and eager greedy rounds together, quiescing before any
        # durable write below
        overlap_state = _maybe_cluster_overlapped(
            genomes, preclusterer, clusterer, skip_clusterer,
            checkpoint, rep_rounds)
        if overlap_state is not None:
            pre_cache = overlap_state.pre_cache
            obs_profile.sample_memory("overlap-dataflow")
            if checkpoint:
                checkpoint.save_distances(pre_cache)
    if pre_cache is None:
        with timing.stage("precluster-distances"):
            pre_cache = preclusterer.distances(genomes)
        obs_profile.sample_memory("precluster-distances")
        if checkpoint:
            checkpoint.save_distances(pre_cache)
    # safe boundary: the distance pass (the single biggest recompute)
    # has just reached disk — a preemption here resumes past it
    interrupt.check("distances-saved")

    logger.info("Preclustering ..")
    with timing.stage("partition"):
        preclusters = partition_preclusters(len(genomes), pre_cache.keys())
    obs_profile.sample_memory("partition")
    logger.info("Found %d preclusters. The largest contained %d genomes",
                len(preclusters), len(preclusters[0]) if preclusters else 0)

    done = checkpoint.load_completed() if checkpoint else {}

    logger.info(
        "Finding representative genomes and assigning all genomes ..")
    all_clusters: List[List[int]] = []
    with timing.stage("greedy-cluster"):
        from galah_tpu.ops.greedy_select import resolve_greedy_strategy

        strategy, explicit = resolve_greedy_strategy()
        timing.counter(f"greedy-strategy-{strategy}", 1)
        from galah_tpu.ops.megakernel import resolve_megakernel

        mk_mode, _mk_explicit = resolve_megakernel()
        if mk_mode == "1" and strategy != "device":
            raise RuntimeError(
                "GALAH_TPU_MEGAKERNEL=1 requires the device greedy "
                f"strategy; GALAH_TPU_GREEDY_STRATEGY pins {strategy!r}"
                " — the fused slab fold only exists inside device "
                "rounds")
        pending = [(i, m) for i, m in enumerate(preclusters)
                   if i not in done]
        device_done: Optional[Dict[int, List[List[int]]]] = None
        if overlap_state is not None and pending:
            try:
                device_done = _finish_overlapped(
                    overlap_state, genomes, clusterer, pending,
                    skip_clusterer, checkpoint)
            except interrupt.PreemptionRequested:
                raise  # a stop request is never a demotion signal
            except Exception as e:  # noqa: BLE001 - AUTO demotes
                if _overlap_mode() == "1":
                    raise
                logger.warning(
                    "overlapped finish failed (%s: %s); falling back "
                    "to the host scan", type(e).__name__, e)
                timing.counter("overlap-demoted", 1)
                from galah_tpu.obs import events

                events.record("overlap-demoted",
                              error=f"{type(e).__name__}: {e}")
                device_done = None
        elif strategy == "device" and pending:
            try:
                device_done = _cluster_pending_rounds(
                    clusterer, genomes, pre_cache, pending,
                    skip_clusterer, checkpoint, rep_rounds)
            except interrupt.PreemptionRequested:
                raise  # a stop request is never a demotion signal
            except Exception as e:  # noqa: BLE001 - AUTO demotes
                if explicit:
                    raise
                logger.warning(
                    "device greedy selection failed (%s: %s); falling "
                    "back to the host scan", type(e).__name__, e)
                timing.counter("greedy-device-demoted", 1)
                from galah_tpu.obs import events

                events.record("greedy-demoted",
                              error=f"{type(e).__name__}: {e}")
                device_done = None
        if device_done is not None:
            for pc_index, global_clusters in sorted(
                    device_done.items()):
                if checkpoint:
                    checkpoint.save_precluster(
                        pc_index, global_clusters)
                done[pc_index] = global_clusters
            if checkpoint:
                checkpoint.clear_greedy_rounds()
        for pc_index, members in enumerate(preclusters):
            if pc_index in done:
                all_clusters.extend(done[pc_index])
                continue
            local_cache = pre_cache.transform_ids(members)
            local_genomes = [genomes[g] for g in members]
            warm_cache = None
            if (not skip_clusterer
                    and len(members) <= dense_precluster_cap):
                warm_cache = _warm_all_hit_pairs(
                    clusterer, local_cache, local_genomes)
            reps, ani_cache, computed, consulted = _find_representatives(
                clusterer, local_cache, local_genomes, skip_clusterer,
                warm_cache, rep_scan_window)
            n_rep_computed = len(computed)
            local_clusters = _find_memberships(
                clusterer, reps, local_cache, local_genomes, ani_cache,
                skip_clusterer, warm_cache, computed, consulted)
            # Speculative waste accounting: backend-computed pairs no
            # decision (rep scan or membership argmax) ever read —
            # covering both the windowed speculative batches and the
            # upfront dense-warm pass. The reference has the same waste
            # class via find_any computing an unpredictable candidate
            # subset (reference: src/clusterer.rs:242-262); here it is
            # measured and reported in the stage report, split by the
            # phase that paid for each pair.
            rep_keys = {pair_key(*p) for p in computed[:n_rep_computed]}
            mem_keys = {pair_key(*p)
                        for p in computed[n_rep_computed:]} - rep_keys
            warm_keys = (set(warm_cache.keys()) - rep_keys - mem_keys
                         if warm_cache is not None else set())
            computed_keys = rep_keys | mem_keys | warm_keys
            _emit_waste_counters(
                len(computed_keys),
                rep=len(rep_keys - consulted),
                membership=len(mem_keys - consulted),
                warm=len(warm_keys - consulted),
                label=f"precluster {pc_index}")
            global_clusters = [[members[i] for i in c]
                               for c in local_clusters]
            all_clusters.extend(global_clusters)
            if checkpoint:
                checkpoint.save_precluster(pc_index, global_clusters)
            # safe boundary: this precluster's clusters are durable —
            # a resume recomputes only the preclusters after it
            interrupt.check("precluster-saved")
    obs_profile.sample_memory("greedy-cluster")
    logger.info("Found %d clusters", len(all_clusters))
    return all_clusters


def _emit_waste_counters(n_computed: int, rep: int, membership: int,
                         warm: int, label: str) -> None:
    """Computed/wasted counters, the waste split by paying phase."""
    wasted = rep + membership + warm
    timing.counter("exact-ani-computed", n_computed)
    timing.counter("exact-ani-wasted", wasted)
    timing.counter("exact-ani-wasted-rep", rep)
    timing.counter("exact-ani-wasted-membership", membership)
    timing.counter("exact-ani-wasted-warm", warm)
    from galah_tpu.obs import metrics as obs_metrics

    obs_metrics.counter(
        "ani.exact_computed",
        help="Exact ANI pairs the backend computed",
        unit="pairs").inc(n_computed)
    obs_metrics.counter(
        "ani.exact_wasted",
        help="Backend-computed ANI pairs no greedy decision "
             "ever consulted (speculation waste)",
        unit="pairs").inc(wasted)
    if n_computed:
        logger.debug(
            "%s: %d exact ANIs computed, %d never consulted "
            "(%.1f%% waste; rep %d / membership %d / warm %d)",
            label, n_computed, wasted, 100.0 * wasted / n_computed,
            rep, membership, warm)


def _backend_ani_batch(
    clusterer: ClusterBackend,
    path_pairs: List[Tuple[str, str]],
) -> List[Optional[float]]:
    """One backend ANI batch, host-split on multi-host runs.

    Every process reaches this with the IDENTICAL pair list (the
    engine is deterministic and its caches are identical across
    hosts); the shared exchange (distributed.sharded_optional_floats)
    splits it with pairs OWNED BY their second endpoint's path hash —
    a genome's pairs against the (few, everywhere-profiled) reps land
    on one host, so per-host profiling stays near unique/P instead of
    every host touching every endpoint. A failing host propagates its
    error to every peer instead of stranding them in the collective.
    Single-process: a plain call.

    Both paths route the batched backend call through the dispatch
    supervisor (resilience/dispatch.py): transient device failures are
    retried with backoff, garbage-shaped or out-of-range results are
    rejected, and a persistently failing batch dispatch demotes this
    site to a per-pair fallback loop for the rest of the run — recorded
    in the stage report as ``demoted[dispatch.ani]``.
    """
    from galah_tpu.parallel import distributed

    n_proc = distributed.process_count()
    if n_proc <= 1 or len(path_pairs) < n_proc:
        return _guarded_ani_batch(clusterer, path_pairs)

    import zlib

    owners = [zlib.crc32(b.encode()) for _a, b in path_pairs]
    return distributed.sharded_optional_floats(
        len(path_pairs),
        lambda idxs: _guarded_ani_batch(
            clusterer, [path_pairs[k] for k in idxs]),
        owner=lambda k: owners[k])


def _guarded_ani_batch(
    clusterer: ClusterBackend,
    path_pairs: List[Tuple[str, str]],
) -> List[Optional[float]]:
    """The retry/validate/demote wrapper around one batched ANI call.

    The fallback computes each pair in its own single-pair batch — the
    smallest dispatch the backend exposes, so one poisoned batch (or a
    wedged batched kernel) degrades throughput instead of killing the
    run. Fallback results still flow through the batch validator.
    """
    from galah_tpu.obs import metrics as obs_metrics
    from galah_tpu.resilience import dispatch as rdispatch

    def fallback() -> List[Optional[float]]:
        return [clusterer.calculate_ani_batch([p])[0]
                for p in path_pairs]

    obs_metrics.counter(
        "ani.batch_pairs",
        help="Genome pairs submitted to batched exact-ANI dispatches",
        unit="pairs").inc(len(path_pairs))
    with obs_metrics.histogram(
            "ani.batch_seconds",
            help="Wall-clock latency of one guarded batched exact-ANI "
                 "dispatch (retries and fallback included)",
            unit="s").time():
        return rdispatch.run(
            "dispatch.ani",
            lambda: clusterer.calculate_ani_batch(path_pairs),
            fallback=fallback,
            validate=rdispatch.expect_ani_values(len(path_pairs)))


def _batch_ani(
    clusterer: ClusterBackend,
    skip_clusterer: bool,
    pre_cache: PairDistanceCache,
    genomes: Sequence[str],
    pairs: Sequence[Tuple[int, int]],
    warm_cache: Optional[PairDistanceCache] = None,
    computed_log: Optional[List[Tuple[int, int]]] = None,
) -> List[Optional[float]]:
    """ANI for local index pairs: precluster reuse or batched backend call.

    With matching methods, a precluster-cache hit is authoritative (same
    algorithm, same parameters — reference: src/clusterer.rs:264-279);
    a `warm_cache` of upfront-computed exact ANIs is consulted next;
    only missing pairs go to the backend. Pairs that actually hit the
    backend (the only ones that cost compute) are appended to
    `computed_log` when given — the waste accounting's input.
    """
    out: List[Optional[float]] = [None] * len(pairs)
    to_compute: List[Tuple[int, Tuple[str, str]]] = []
    for n, (i, j) in enumerate(pairs):
        if skip_clusterer and pre_cache.contains((i, j)):
            out[n] = pre_cache.get((i, j))
        elif warm_cache is not None and warm_cache.contains((i, j)):
            out[n] = warm_cache.get((i, j))
        else:
            to_compute.append((n, (genomes[i], genomes[j])))
            if computed_log is not None:
                computed_log.append(pairs[n])
    if to_compute:
        anis = _backend_ani_batch(clusterer,
                                  [p for _, p in to_compute])
        for (n, _), ani in zip(to_compute, anis):
            out[n] = ani
    return out


def _warm_all_hit_pairs(
    clusterer: ClusterBackend,
    pre_cache: PairDistanceCache,
    genomes: Sequence[str],
) -> PairDistanceCache:
    """Exact ANI for every precluster-hit pair in ONE batched dispatch."""
    keys = sorted(pre_cache.keys())
    warm = PairDistanceCache()
    if keys:
        anis = _backend_ani_batch(
            clusterer,
            [(genomes[i], genomes[j]) for i, j in keys])
        for key, ani in zip(keys, anis):
            warm.insert(key, ani)
    return warm


def _greedy_digest(pending: List[Tuple[int, Sequence[int]]]) -> str:
    """Digest of the pending-precluster sequence a greedy-round
    checkpoint is valid for. A resume whose pending set differs (more
    preclusters finished, different partition, different genome list —
    the run fingerprint guards the rest) drops the round records
    instead of replaying pairs into a differently-shaped scan."""
    import hashlib
    import json

    ident = json.dumps([[pc, list(m)] for pc, m in pending])
    return hashlib.sha256(ident.encode()).hexdigest()


class _OverlapState:
    """What the overlapped streaming phase hands to the post-quiesce
    finish phase: the completed pair cache, the greedy decisions
    already made over the arrived prefix, and the shared batch/value
    closures so the membership pass reuses the same dedup + chunking
    + waste accounting (docs/dataflow.md)."""

    def __init__(self, n: int) -> None:
        self.pre_cache = PairDistanceCache()
        self.adj: Dict[int, List[int]] = {g: [] for g in range(n)}
        self.ani_cache = PairDistanceCache()
        self.computed: List[Tuple[int, int]] = []
        self.consulted: Set[Tuple[int, int]] = set()
        self.rep_order: List[int] = []
        self.rep_set: Set[int] = set()
        self.batch = None
        self.value = None
        self.eager_rounds = 0


class _MegaCtx:
    """Run-scoped megakernel strategy state (ops/megakernel.py).

    ``active`` drops to False when an AUTO run demotes — the rest of
    the run takes the per-window dense fold. ``dev_busy`` accumulates
    the device-dispatch bracket wall so the greedy stage's recorded
    service stays net of device time (the flow host-blame share keys
    off this split)."""

    def __init__(self, explicit: bool, cap: int, queue) -> None:
        self.explicit = explicit
        self.active = True
        self.cap = cap
        self.queue = queue
        self.dev_busy = 0.0


def _megakernel_ctx(stage_serial: bool = False) -> Optional[_MegaCtx]:
    """The megakernel context for one clustering run, or None when it
    should not engage. Callers are device-round engines; forced-mode
    ineligibility (host greedy strategy) is enforced at the strategy
    dispatch in cluster().

    AUTO engages only in the overlapped engine: that is the e2e path
    whose host round-trips the megakernel removes, and its eager-round
    cadence is already arrival-driven. The stage-serial engine keeps
    its round-per-window cadence under AUTO — one durable checkpoint
    record, one preemption boundary, and one backend-call pattern per
    round window is a contract resume tooling observes — and opts into
    slab-fused rounds only under an explicit GALAH_TPU_MEGAKERNEL=1
    (still durable and replayable, per slab)."""
    from galah_tpu.ops import device_queue
    from galah_tpu.ops.megakernel import resolve_megakernel

    mode, _explicit = resolve_megakernel()
    if mode == "0" or (stage_serial and mode != "1"):
        return None
    cap = device_queue.resolve_queue_cap()
    return _MegaCtx(mode == "1", cap, device_queue.PairQueue(cap))


def _grow_slab(seq, pos: int, width: int, adj: Dict[int, List[int]],
               cap: int, ready_limit: Optional[int] = None) -> List[int]:
    """Fuse up to megakernel.SLAB_WINDOWS consecutive round windows
    starting at ``pos`` into one slab, while the intra-slab hit-edge
    count stays within the queue capacity (the estimate counts every
    hit pair; the enqueued set — non-None values only — is a subset,
    so a fitting estimate can never overflow). Width invariance of the
    round machinery makes the slab's decisions bit-identical to its
    sequential windows. ``ready_limit`` (the overlapped engine's
    resolved-prefix frontier) stops growth at windows not yet final."""
    from galah_tpu.ops.megakernel import SLAB_WINDOWS

    n = len(seq)
    window = list(seq[pos:pos + width])
    # membership-test only (never iterated): hash order cannot leak
    slab_members = set(window)
    edges = sum(1 for g in window
                for t in adj[g] if t in slab_members) // 2
    k = 1
    while k < SLAB_WINDOWS:
        nstart = pos + len(window)
        if nstart >= n:
            break
        nend = min(nstart + width, n)
        if ready_limit is not None and ready_limit < nend:
            break
        nxt = list(seq[nstart:nend])
        nxt_members = set(nxt)
        grown = sum(1 for g in nxt for t in adj[g] if t in slab_members)
        grown += sum(1 for g in nxt for t in adj[g]
                     if t in nxt_members) // 2
        if edges + grown > cap:
            break
        edges += grown
        window += nxt
        slab_members |= nxt_members
        k += 1
    return window


def _megakernel_fold(mega: _MegaCtx, window: List[int],
                     win_pos: Dict[int, int],
                     adj: Dict[int, List[int]], ext, value, thr: float,
                     np):
    """Queue-fed slab fold: enqueue the slab's materialized hit edges
    into the on-device pair queue and run the fused fold program
    (ops/megakernel.slab_select) in place of one dense window fold per
    round window. Returns ``(rep_flags, converged)``, or
    ``(None, False)`` when the slab spilled (queue capacity) or an
    AUTO run demoted — the caller then takes the exact dense path."""
    from galah_tpu.obs import flow as obs_flow
    from galah_tpu.ops import megakernel as mk

    ei: List[int] = []
    ej: List[int] = []
    ev: List[float] = []
    for wi, g in enumerate(window):
        for t in adj[g]:
            ti = win_pos.get(t)
            if ti is None or ti <= wi:
                continue
            v = value(g, t)
            if v is None:
                continue
            ei.append(wi)
            ej.append(ti)
            ev.append(v)
    if len(ei) > mega.cap:
        timing.counter("megakernel-overflow-spills", 1)
        return None, False
    try:
        with obs_flow.blocked("greedy", "device-dispatch") as bdev:
            rep, converged = mk.slab_select(
                mega.queue, np.asarray(ei, dtype=np.int32),
                np.asarray(ej, dtype=np.int32),
                np.asarray(ev, dtype=np.float64),
                np.asarray(ext, dtype=bool), thr)
        mega.dev_busy += bdev.seconds
    except interrupt.PreemptionRequested:
        raise  # a stop request is never a demotion signal
    except Exception as e:  # noqa: BLE001 - AUTO demotes
        if mega.explicit:
            raise
        _demote_megakernel(mega, f"{type(e).__name__}: {e}")
        return None, False
    if rep is None:
        timing.counter("megakernel-overflow-spills", 1)
        return None, False
    timing.counter("megakernel-slab-folds", 1)
    return rep, converged


def _demote_megakernel(mega: _MegaCtx, error: str) -> None:
    """AUTO demotion: the rest of the run takes the per-window dense
    fold; the demotion is counted and event-logged like the overlap
    and greedy-strategy demotions."""
    logger.warning(
        "megakernel slab fold failed (%s); demoting to the per-window "
        "dense fold for this run", error)
    timing.counter("megakernel-demoted", 1)
    from galah_tpu.obs import events

    events.record("megakernel-demoted", error=error)
    mega.active = False


def _overlap_mode() -> str:
    from galah_tpu.config import env_value

    mode = (env_value("GALAH_TPU_OVERLAP") or "auto").strip().lower()
    if mode not in ("auto", "0", "1"):
        logger.warning("ignoring malformed GALAH_TPU_OVERLAP=%r "
                       "(want auto/0/1)", mode)
        return "auto"
    return mode


def _overlap_depth() -> int:
    from galah_tpu.config import env_value

    try:
        return max(1, int(env_value("GALAH_TPU_OVERLAP_DEPTH") or 512))
    except ValueError:
        logger.warning("ignoring malformed GALAH_TPU_OVERLAP_DEPTH")
        return 512


def _maybe_cluster_overlapped(
    genomes: Sequence[str],
    preclusterer: PreclusterBackend,
    clusterer: ClusterBackend,
    skip_clusterer: bool,
    checkpoint: Optional["ClusterCheckpoint"],
    rep_rounds: Optional[int],
) -> Optional[_OverlapState]:
    """Run the overlapped end-to-end dataflow when it is engaged,
    returning its state, or None for the stage-serial engine.

    Engagement (GALAH_TPU_OVERLAP=auto/1) requires a fresh run — no
    checkpointed distances or completed preclusters; a resume always
    takes the stage-serial path, where the saved distance pass and the
    greedy-round replay make the recompute free — plus a preclusterer
    exposing `distances_streamed` that accepts the workload, and the
    device greedy strategy (the eager rounds ARE device rounds).
    Forced mode (=1) propagates ineligibility of the preclusterer/
    strategy and any runtime failure; auto falls back to the
    stage-serial engine from scratch (sketches are disk-cached, so the
    retried prologue is cheap).
    """
    mode = _overlap_mode()
    if mode == "0":
        return None
    forced = mode == "1"
    if checkpoint and checkpoint.load_completed():
        # a resume is stage-serial by design (see docstring), even
        # when forced — this is ineligibility, not failure
        return None
    from galah_tpu.ops.greedy_select import resolve_greedy_strategy

    strategy, _explicit = resolve_greedy_strategy()
    if strategy != "device":
        if forced:
            raise RuntimeError(
                "GALAH_TPU_OVERLAP=1 requires the device greedy "
                f"strategy; GALAH_TPU_GREEDY_STRATEGY pins {strategy!r}")
        return None
    streamed = getattr(preclusterer, "distances_streamed", None)
    stream = streamed(genomes) if streamed is not None else None
    if stream is None:
        if forced:
            raise RuntimeError(
                "GALAH_TPU_OVERLAP=1 but the precluster backend "
                f"({preclusterer.method_name()}) did not engage its "
                "streamed pair pass for this workload")
        return None
    try:
        with timing.stage("overlap-dataflow"):
            st = _cluster_overlapped(genomes, clusterer, stream,
                                     skip_clusterer, rep_rounds)
        timing.counter("overlap-engaged", 1)
        return st
    except interrupt.PreemptionRequested:
        raise  # a stop request is never a demotion signal
    except Exception as e:  # noqa: BLE001 - AUTO demotes
        if forced:
            raise
        logger.warning(
            "overlapped dataflow failed (%s: %s); falling back to the "
            "stage-serial engine", type(e).__name__, e)
        timing.counter("overlap-demoted", 1)
        from galah_tpu.obs import events

        events.record("overlap-demoted",
                      error=f"{type(e).__name__}: {e}")
        return None


def _cluster_overlapped(
    genomes: Sequence[str],
    clusterer: ClusterBackend,
    stream,
    skip_clusterer: bool,
    rep_rounds: Optional[int],
) -> _OverlapState:
    """Consume the streamed pair pass as ONE overlapped dataflow:
    while the sketch stream's worker threads keep ingest+sketch
    running ahead, this (consumer) thread interleaves three downstream
    stages between block arrivals — the pair-screen stripes (inside
    the stream generator), speculative fragment-ANI batches over
    survivor pairs with a committed-rep endpoint, and eager greedy
    rounds over the resolved prefix.

    Frontier soundness (why eager decisions are bit-identical to the
    stage-serial engine): genome g's rep decision consults exactly the
    hit edges (i, g) with i < g and the rep status of those i. When
    the stream has screened rows [0, r1), every such edge for every
    g < r1 is known — the stripe covering block(j) evaluates rows
    [0, r1) x cols [r0, r1) — so rep decisions over the prefix are
    FINAL; no genome still being sketched can change them. Windows
    therefore run at fixed absolute boundaries [0,w), [w,2w), ... as
    soon as r1 reaches each window's end, grouped by the live
    union-find component of the hit graph (a hit pair's endpoints are
    already unioned when the edge arrives, so the current roots cover
    every candidate edge a decision can consult). Membership and the
    final cluster assembly wait for stream completion: a later rep
    can still win a non-rep's argmax.

    Speculation rule (zero extra waste): a survivor pair is offered to
    the fragment-ANI buffer iff one endpoint is already a committed
    rep — at edge arrival for the earlier endpoint, and via back-offer
    when a window commits new reps. Every backend pair the greedy/
    membership passes compute has a rep endpoint, so the offered set
    is exactly the stage-serial computed set: the speculation moves
    dispatches earlier, it never adds any. The buffer launches at
    GALAH_TPU_OVERLAP_DEPTH pending pairs (bounded in-flight window,
    memory O(depth)).

    No durable write happens while the stream is live; the caller
    quiesces (this function returns only once the stream is drained
    and every window resolved) before `save_distances` and the single
    greedy-round checkpoint record (_finish_overlapped).
    """
    import numpy as np

    from galah_tpu.obs import metrics as obs_metrics
    from galah_tpu.ops import greedy_select

    thr = clusterer.ani_threshold
    width = (int(rep_rounds) if rep_rounds is not None
             else greedy_select.DEFAULT_ROUND_WIDTH)
    if width < 1:
        raise ValueError(f"rep_rounds must be >= 1, got {width}")
    from galah_tpu.obs import flow as obs_flow
    depth = _overlap_depth()
    n = len(genomes)

    st = _OverlapState(n)
    pre_cache, adj = st.pre_cache, st.adj
    ani_cache, computed = st.ani_cache, st.computed
    consulted, rep_set = st.consulted, st.rep_set

    # tiny union-find over arrived hit edges: current roots group the
    # window genomes with every rep a candidate edge can reach
    parent = list(range(n))

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:
            parent[x], x = root, parent[x]
        return root

    frag_busy = [0.0]   # batch-closure (fragment-ANI dispatch) wall
    greedy_busy = [0.0]  # window wall net of nested fragment time

    def batch(pairs: List[Tuple[int, int]]) -> None:
        """Same dedup + ROUND_BATCH_GENOMES chunking as the
        stage-serial batch closure (_cluster_pending_rounds), plus
        fragment-stage busy accounting for the occupancy gauge."""
        t0 = time.monotonic()
        fid = obs_flow.begin("fragment_batch")
        seen: Set[Tuple[int, int]] = set()
        uniq: List[Tuple[int, int]] = []
        for p in pairs:
            k = pair_key(*p)
            if k in seen or ani_cache.contains(p):
                continue
            seen.add(k)
            uniq.append(p)
        chunk: List[Tuple[int, int]] = []
        chunk_genomes: Set[int] = set()

        def flush() -> None:
            if not chunk:
                return
            anis = _batch_ani(clusterer, skip_clusterer, pre_cache,
                              genomes, chunk, None,
                              computed_log=computed)
            for p, ani in zip(chunk, anis):
                ani_cache.insert(p, ani)
            chunk.clear()
            chunk_genomes.clear()

        for p in uniq:
            if chunk and len(chunk_genomes | set(p)) > \
                    ROUND_BATCH_GENOMES:
                flush()
            chunk.append(p)
            chunk_genomes.update(p)
        flush()
        dt = time.monotonic() - t0
        frag_busy[0] += dt
        obs_flow.record_service("fragment", dt, items=len(uniq))
        obs_flow.complete(fid)

    def value(i: int, j: int) -> Optional[float]:
        if skip_clusterer and pre_cache.contains((i, j)):
            return pre_cache.get((i, j))
        return ani_cache.get((i, j))

    st.batch, st.value = batch, value

    hist = obs_metrics.histogram(
        "greedy.round_seconds",
        help="Wall-clock of one device-strategy selection round "
             "(speculative dispatch + frontier sub-rounds + jitted "
             "window fold)",
        unit="s")
    rounds_c = obs_metrics.counter(
        "greedy.rounds",
        help="Device-strategy selection rounds run", unit="rounds")
    conflicts_c = obs_metrics.counter(
        "greedy.conflict_windows",
        help="Round windows whose rep-chain depth exceeded the device "
             "resolution budget", unit="windows")
    fallback_c = obs_metrics.counter(
        "greedy.fallback_windows",
        help="Round windows finished by the exact host-order scan",
        unit="windows")
    eager_c = obs_metrics.counter(
        "overlap.eager_rounds",
        help="Greedy device rounds run while the sketch stream was "
             "still producing (the overlapped engine's eager windows)",
        unit="rounds")
    spec_c = obs_metrics.counter(
        "overlap.spec_pairs",
        help="Survivor pairs offered to the speculative fragment-ANI "
             "buffer", unit="pairs")

    # speculative fragment-ANI buffer: survivor pairs with a committed
    # rep endpoint, launched when `depth` accumulate
    spec: List[Tuple[int, int]] = []
    offered: Set[Tuple[int, int]] = set()
    stats = {"offered": 0, "batches": 0, "peak": 0}

    def flush_spec() -> None:
        if not spec:
            return
        stats["batches"] += 1
        batch(spec)
        spec.clear()

    def offer(pair: Tuple[int, int]) -> None:
        k = pair_key(*pair)
        if k in offered or ani_cache.contains(pair):
            return
        if skip_clusterer and pre_cache.contains(pair):
            return  # precluster reuse — never hits the backend
        offered.add(k)
        spec.append(pair)
        stats["offered"] += 1
        stats["peak"] = max(stats["peak"], len(spec))
        if len(spec) >= depth:
            flush_spec()

    frontier = [0]  # next undecided window start: prefix is FINAL
    mega = _megakernel_ctx()
    seq_all = range(n)

    def run_ready_windows(r1: int) -> None:
        while frontier[0] < n:
            end = min(frontier[0] + width, n)
            if r1 < end:
                return
            if mega is not None and mega.active:
                # fuse every already-ready consecutive window into one
                # queue-fed slab round (bit-identical by width
                # invariance) — eagerness is unchanged because growth
                # stops at the resolved prefix (r1), never waiting for
                # windows the stream has not finalized
                window = _grow_slab(seq_all, frontier[0], width, adj,
                                    mega.cap, ready_limit=r1)
                end = frontier[0] + len(window)
            else:
                window = list(range(frontier[0], end))
            n_windows = (len(window) + width - 1) // width
            t0 = time.monotonic()
            fid = obs_flow.begin("greedy_round")
            fb0 = frag_busy[0]
            db0 = mega.dev_busy if mega is not None else 0.0
            pc_of = {g: find(g) for g in window}
            reps_by_pc: Dict[int, List[int]] = {}
            for r in st.rep_order:
                reps_by_pc.setdefault(find(r), []).append(r)
            for g in window:
                reps_by_pc.setdefault(pc_of[g], [])
            with hist.time():
                _device_round(window, pc_of, adj, reps_by_pc, rep_set,
                              batch, value, consulted, thr,
                              greedy_select, np, conflicts_c,
                              fallback_c, mega=mega)
                timing.counter("greedy-rounds", 1)
                rounds_c.inc()
            timing.counter("overlap-eager-rounds", n_windows)
            eager_c.inc(n_windows)
            st.eager_rounds += n_windows
            # _device_round appends reps in window order; every window
            # genome was undecided before, so the in-rep_set window
            # genomes ARE this round's commits, in commit order
            new_reps = [g for g in window if g in rep_set]
            st.rep_order.extend(new_reps)
            # back-offer: every hit pair of a fresh rep is one a later
            # phase-1 candidate row or the membership argmax will read
            for r in new_reps:
                for t in adj[r]:
                    offer((r, t))
            frontier[0] = end
            dev_dt = ((mega.dev_busy - db0)
                      if mega is not None else 0.0)
            dt = ((time.monotonic() - t0) - (frag_busy[0] - fb0)
                  - dev_dt)
            greedy_busy[0] += dt
            obs_flow.record_service("greedy", dt)
            obs_flow.complete(fid)
            # live gauge refresh so the heartbeat samples a moving
            # occupancy time-series, not only the quiesce value
            wall_now = max(time.monotonic() - t_start, 1e-9)
            obs_metrics.pipeline_occupancy(
                min(1.0, greedy_busy[0] / wall_now), stage="greedy")
            if not skip_clusterer:
                obs_metrics.pipeline_occupancy(
                    min(1.0, frag_busy[0] / wall_now),
                    stage="fragment")

    t_start = time.monotonic()
    stream_it = iter(stream)
    while True:
        # blocked on the upstream pair-screen stream (obs/flow records
        # it as the greedy stage's upstream-empty wait — the signal
        # `galah-tpu flow analyze` forwards to the producer's blame)
        with obs_flow.blocked("greedy", "upstream-empty"):
            try:
                r1, inc = next(stream_it)
            except StopIteration:
                break
        obs_flow.absorb("pairs", "greedy")
        for (a, b), v in inc.items():
            pre_cache.insert((a, b), v)
            adj[a].append(b)
            adj[b].append(a)
            parent[find(a)] = find(b)
            if a in rep_set:
                offer((a, b))
        run_ready_windows(r1)
    if frontier[0] < n:
        raise RuntimeError(
            f"overlapped stream ended with the greedy frontier at "
            f"{frontier[0]} of {n} genomes")
    flush_spec()

    timing.counter("overlap-spec-pairs", stats["offered"])
    timing.counter("overlap-spec-batches", stats["batches"])
    spec_c.inc(stats["offered"])
    obs_metrics.gauge(
        "overlap.spec_pending_peak",
        help="High-water mark of the speculative fragment-ANI buffer "
             "(bounded by GALAH_TPU_OVERLAP_DEPTH)",
        unit="pairs").set(float(stats["peak"]))

    # per-stage occupancy over the streaming phase's wall, plus the
    # whole-pipeline value (mean of the per-stage gauges this run
    # emitted) as the unlabelled gauge
    wall = max(time.monotonic() - t_start, 1e-9)
    obs_metrics.pipeline_occupancy(greedy_busy[0] / wall,
                                   stage="greedy")
    if not skip_clusterer:
        obs_metrics.pipeline_occupancy(frag_busy[0] / wall,
                                       stage="fragment")
    prefix = obs_metrics.PIPELINE_OCCUPANCY_GAUGE + "["
    vals = [m["value"] for name, m in obs_metrics.snapshot().items()
            if name.startswith(prefix) and m.get("value") is not None]
    if vals:
        obs_metrics.pipeline_occupancy(sum(vals) / len(vals))
    return st


def _finish_overlapped(
    st: _OverlapState,
    genomes: Sequence[str],
    clusterer: ClusterBackend,
    pending: List[Tuple[int, Sequence[int]]],
    skip_clusterer: bool,
    checkpoint: Optional["ClusterCheckpoint"],
) -> Dict[int, List[List[int]]]:
    """Post-quiesce finish of the overlapped dataflow: persist every
    overlap-computed ANI as ONE digest-bound greedy-round record (a
    kill after this boundary resumes stage-serial and replays them
    with zero dispatches), then run the membership pass and per-
    precluster assembly exactly as the stage-serial device strategy
    does — decisions were already made during streaming."""
    import numpy as np

    from galah_tpu.ops import greedy_select

    pc_of: Dict[int, int] = {}
    for pc, members in pending:
        for g in members:
            pc_of[g] = pc
    reps_by_pc: Dict[int, List[int]] = {pc: [] for pc, _ in pending}
    for r in st.rep_order:
        if r in pc_of:
            reps_by_pc[pc_of[r]].append(r)

    digest = _greedy_digest(pending)
    if checkpoint and st.computed:
        checkpoint.save_greedy_round(
            digest,
            [(i, j, st.ani_cache.get((i, j))) for i, j in st.computed])
    # safe boundary: the streaming phase's ANI pairs are durable — a
    # stage-serial resume replays them and re-derives every greedy
    # decision for free
    interrupt.check("greedy-round-saved")

    # -- membership: one global batched dispatch + jitted argmax ------
    todo: List[Tuple[int, int]] = []
    for a, b in st.pre_cache.keys():
        if a not in pc_of:
            continue
        a_rep, b_rep = a in st.rep_set, b in st.rep_set
        if a_rep == b_rep:
            continue  # rep-rep / non-rep pairs never decide membership
        r, i = (a, b) if a_rep else (b, a)
        if not (skip_clusterer and st.pre_cache.contains((i, r))) \
                and not st.ani_cache.contains((i, r)):
            todo.append((r, i))
    todo.sort(key=lambda p: (p[1], p[0]))
    n_rep_computed = len(st.computed)
    st.batch(todo)

    results: Dict[int, List[List[int]]] = {}
    for pc, members in pending:
        rep_list = reps_by_pc[pc]
        rep_col = {r: c for c, r in enumerate(rep_list)}
        nonreps = [g for g in members if g not in st.rep_set]
        clusters: List[List[int]] = [[r] for r in rep_list]
        if nonreps:
            mat = np.full((len(nonreps), len(rep_list)), np.nan,
                          dtype=np.float64)
            for gi, g in enumerate(nonreps):
                for r in st.adj[g]:
                    c = rep_col.get(r)
                    if c is None:
                        continue
                    v = st.value(g, r)
                    if v is not None:
                        mat[gi, c] = v
            best, has = greedy_select.membership_argmax(mat)
            for gi, g in enumerate(nonreps):
                if not has[gi]:
                    raise RuntimeError(
                        f"genome {genomes[g]} passed the representative "
                        "test but has no ANI to any representative — "
                        "inconsistent backend")
                clusters[int(best[gi])].append(g)
        results[pc] = clusters

    # -- waste accounting, split by paying phase ----------------------
    computed_keys = {pair_key(*p) for p in st.computed}
    mem_consulted = {k for k in computed_keys
                     if (k[0] in st.rep_set) != (k[1] in st.rep_set)}
    live = st.consulted | mem_consulted
    rep_keys = {pair_key(*p) for p in st.computed[:n_rep_computed]}
    mem_keys = {pair_key(*p) for p in st.computed[n_rep_computed:]} \
        - rep_keys
    _emit_waste_counters(
        len(computed_keys),
        rep=len(rep_keys - live),
        membership=len(mem_keys - live),
        warm=0,
        label=f"overlapped rounds ({len(pending)} preclusters)")
    return results


def _cluster_pending_rounds(
    clusterer: ClusterBackend,
    genomes: Sequence[str],
    pre_cache: PairDistanceCache,
    pending: List[Tuple[int, Sequence[int]]],
    skip_clusterer: bool,
    checkpoint: Optional["ClusterCheckpoint"],
    rep_rounds: Optional[int],
) -> Dict[int, List[List[int]]]:
    """The round-based device greedy strategy over ALL pending
    preclusters at once: {precluster index -> its global clusters}.

    Each round takes the next K genomes of the concatenated pending
    sequence (partition order; within a precluster that IS quality
    order), evaluates their ANIs against every existing same-precluster
    rep in one batched dispatch, materializes the intra-window hit
    pairs that decisions need (one small frontier dispatch per
    sub-round, all segments batched together), and resolves the
    window's rep/member status with ONE jitted segmented fold
    (ops/greedy_select.window_select). Decisions are bit-identical to
    the per-precluster host scan; windows whose rep-chain depth
    exceeds the sub-round/fold budget are conflict windows and finish
    on the exact host-order scan (rare, measured:
    greedy-conflict-windows / greedy-host-fallback-windows).

    The win over the host path is dispatch count: the 1000-genome
    bench rung runs ~250 preclusters, which the host path walks one at
    a time (>=1 profile build + ANI dispatch each); here every round
    spans all of them, so dispatches drop to O(N / K) and the backend's
    batched profile build touches each genome group once.

    With a checkpoint, each round's backend-computed pairs append to
    greedy_rounds.jsonl (digest-bound to the pending sequence): a
    resume replays the values into the cache and re-derives every
    decision with zero dispatches up to the crash point.
    """
    import numpy as np

    from galah_tpu.obs import metrics as obs_metrics
    from galah_tpu.ops import greedy_select

    thr = clusterer.ani_threshold
    width = (int(rep_rounds) if rep_rounds is not None
             else greedy_select.DEFAULT_ROUND_WIDTH)
    if width < 1:
        raise ValueError(f"rep_rounds must be >= 1, got {width}")

    seq: List[int] = []
    pc_of: Dict[int, int] = {}
    for pc, members in pending:
        for g in members:
            seq.append(g)
            pc_of[g] = pc
    # precluster-hit adjacency restricted to pending genomes: the hit
    # graph's components ARE the preclusters, so any key with one
    # pending endpoint has both in the same pending precluster
    adj: Dict[int, List[int]] = {g: [] for g in seq}
    for a, b in pre_cache.keys():
        if a in pc_of:
            adj[a].append(b)
            adj[b].append(a)
    for v in adj.values():
        v.sort()

    ani_cache = PairDistanceCache()
    computed: List[Tuple[int, int]] = []   # pairs that hit the backend
    consulted: Set[Tuple[int, int]] = set()  # pairs a rep decision read
    reps_by_pc: Dict[int, List[int]] = {pc: [] for pc, _ in pending}
    rep_set: Set[int] = set()

    digest = _greedy_digest(pending)
    if checkpoint:
        for i, j, ani in checkpoint.load_greedy_rounds(digest):
            ani_cache.insert((i, j), ani)
            computed.append((i, j))
    n_replayed = len(computed)

    def batch(pairs: List[Tuple[int, int]]) -> None:
        """Compute pairs missing from the cache, chunked so no single
        dispatch pins more than ROUND_BATCH_GENOMES genome profiles at
        once. One monolithic batch would keep the whole window's
        profile heap resident together (~1 MB/genome), and that
        allocator pressure measurably slows the per-pair host merges
        (~2x on the 1000-genome rung) — the pair lists arrive grouped
        by precluster segment, so capping the working set keeps each
        chunk's profiles cache-warm and lets the profile store's LRU
        evict between chunks. Chunking preserves pair order, so the
        computed log and every ANI value are bit-identical."""
        seen: Set[Tuple[int, int]] = set()
        uniq: List[Tuple[int, int]] = []
        for p in pairs:
            k = pair_key(*p)
            if k in seen or ani_cache.contains(p):
                continue
            seen.add(k)
            uniq.append(p)
        chunk: List[Tuple[int, int]] = []
        chunk_genomes: Set[int] = set()

        def flush() -> None:
            if not chunk:
                return
            anis = _batch_ani(clusterer, skip_clusterer, pre_cache,
                              genomes, chunk, None,
                              computed_log=computed)
            for p, ani in zip(chunk, anis):
                ani_cache.insert(p, ani)
            chunk.clear()
            chunk_genomes.clear()

        for p in uniq:
            if chunk and len(chunk_genomes | set(p)) > \
                    ROUND_BATCH_GENOMES:
                flush()
            chunk.append(p)
            chunk_genomes.update(p)
        flush()

    def value(i: int, j: int) -> Optional[float]:
        """The decision value for a hit pair, same precedence as
        _batch_ani: precluster reuse when methods match, else the
        computed exact ANI (None when absent or gated)."""
        if skip_clusterer and pre_cache.contains((i, j)):
            return pre_cache.get((i, j))
        return ani_cache.get((i, j))

    hist = obs_metrics.histogram(
        "greedy.round_seconds",
        help="Wall-clock of one device-strategy selection round "
             "(speculative dispatch + frontier sub-rounds + jitted "
             "window fold)",
        unit="s")
    rounds_c = obs_metrics.counter(
        "greedy.rounds",
        help="Device-strategy selection rounds run", unit="rounds")
    conflicts_c = obs_metrics.counter(
        "greedy.conflict_windows",
        help="Round windows whose rep-chain depth exceeded the device "
             "resolution budget", unit="windows")
    fallback_c = obs_metrics.counter(
        "greedy.fallback_windows",
        help="Round windows finished by the exact host-order scan",
        unit="windows")

    mega = _megakernel_ctx(stage_serial=True)
    n = len(seq)
    pos = 0
    while pos < n:
        if mega is not None and mega.active:
            # fuse consecutive ready windows into one queue-fed slab
            # round (bit-identical by width invariance; capacity- and
            # SLAB_WINDOWS-bounded). Checkpoint records stay per
            # round, so resume replay is granularity-agnostic.
            window = _grow_slab(seq, pos, width, adj, mega.cap)
        else:
            window = seq[pos:pos + width]
        pos += len(window)
        with hist.time():
            rstart = len(computed)
            _device_round(window, pc_of, adj, reps_by_pc, rep_set,
                          batch, value, consulted, thr, greedy_select,
                          np, conflicts_c, fallback_c, mega=mega)
            timing.counter("greedy-rounds", 1)
            rounds_c.inc()
            if checkpoint and len(computed) > rstart:
                checkpoint.save_greedy_round(
                    digest,
                    [(i, j, ani_cache.get((i, j)))
                     for i, j in computed[rstart:]])
        # safe boundary: this round's ANI pairs are durable — a
        # resume replays them and re-derives the decisions for free
        interrupt.check("greedy-round-saved")

    # -- membership: one global batched dispatch + jitted argmax ------
    todo: List[Tuple[int, int]] = []
    for a, b in pre_cache.keys():
        if a not in pc_of:
            continue
        a_rep, b_rep = a in rep_set, b in rep_set
        if a_rep == b_rep:
            continue  # rep-rep / non-rep pairs never decide membership
        # orient (rep, non-rep); the (genome, rep)-ascending sort below
        # keeps the host scan's deterministic batch order
        r, i = (a, b) if a_rep else (b, a)
        if not (skip_clusterer and pre_cache.contains((i, r))) \
                and not ani_cache.contains((i, r)):
            todo.append((r, i))
    todo.sort(key=lambda p: (p[1], p[0]))
    n_rep_computed = len(computed)
    batch(todo)

    results: Dict[int, List[List[int]]] = {}
    for pc, members in pending:
        rep_list = reps_by_pc[pc]
        rep_col = {r: c for c, r in enumerate(rep_list)}
        nonreps = [g for g in members if g not in rep_set]
        clusters: List[List[int]] = [[r] for r in rep_list]
        if nonreps:
            mat = np.full((len(nonreps), len(rep_list)), np.nan,
                          dtype=np.float64)
            for gi, g in enumerate(nonreps):
                for r in adj[g]:
                    c = rep_col.get(r)
                    if c is None:
                        continue
                    v = value(g, r)
                    if v is not None:
                        mat[gi, c] = v
            best, has = greedy_select.membership_argmax(mat)
            for gi, g in enumerate(nonreps):
                if not has[gi]:
                    raise RuntimeError(
                        f"genome {genomes[g]} passed the representative "
                        "test but has no ANI to any representative — "
                        "inconsistent backend")
                clusters[int(best[gi])].append(g)
        results[pc] = clusters

    # -- waste accounting, split by paying phase ----------------------
    # the membership argmax consults every cached (non-rep, rep) pair,
    # so any computed key joining a rep and a non-rep was consumed
    computed_keys = {pair_key(*p) for p in computed}
    mem_consulted = {k for k in computed_keys
                     if (k[0] in rep_set) != (k[1] in rep_set)}
    live = consulted | mem_consulted
    rep_keys = {pair_key(*p) for p in computed[:n_rep_computed]}
    mem_keys = {pair_key(*p) for p in computed[n_rep_computed:]} \
        - rep_keys
    _emit_waste_counters(
        len(computed_keys),
        rep=len(rep_keys - live),
        membership=len(mem_keys - live),
        warm=0,
        label=f"device rounds ({len(pending)} preclusters)")
    if n_replayed:
        timing.counter("greedy-replayed-pairs", n_replayed)
    return results


def _device_round(
    window: List[int],
    pc_of: Dict[int, int],
    adj: Dict[int, List[int]],
    reps_by_pc: Dict[int, List[int]],
    rep_set: Set[int],
    batch,
    value,
    consulted: Set[Tuple[int, int]],
    thr: float,
    greedy_select,
    np,
    conflicts_c,
    fallback_c,
    mega: Optional[_MegaCtx] = None,
) -> None:
    """Resolve one K-genome window; commits new reps into reps_by_pc.

    Three phases, mirroring the docstring of _cluster_pending_rounds:
    (1) one speculative batch of window x existing-rep hit pairs and
    the derived already-clustered flags; (2) bounded frontier
    sub-rounds that materialize exactly the intra-window pairs greedy
    decisions depend on (the first undecided genome of every segment
    is provably the next rep — all its earlier neighbors are decided
    and none claimed it); (3) the jitted segmented fold over the
    materialized matrix as the authoritative device decision pass,
    cross-checked against the sub-round bookkeeping. Windows the
    budget cannot finish fall back to the host-order scan for their
    undecided tail — decisions stay exact, only the dispatch pattern
    degrades.
    """
    w = len(window)
    win_pos = {g: wi for wi, g in enumerate(window)}
    hits = {g: set(adj[g]) for g in window}

    # (1) window x existing same-precluster reps, ONE dispatch, then
    # the already-clustered flags. The batched decision reads the whole
    # candidate row (no early exit to skip pairs the batch computed
    # anyway), so every candidate pair counts as consulted.
    batch([(r, g) for g in window for r in reps_by_pc[pc_of[g]]
           if r in hits[g]])
    ext = np.zeros(w, dtype=bool)
    for wi, g in enumerate(window):
        for r in reps_by_pc[pc_of[g]]:
            if r not in hits[g]:
                continue
            consulted.add(pair_key(r, g))
            v = value(r, g)
            if v is not None and v >= thr:
                ext[wi] = True

    # (2) frontier sub-rounds. The first undecided genome of a segment
    # is exactly the next greedy rep: every earlier same-precluster
    # genome is decided and none of the decided reps claimed it (prior
    # rounds via ext, in-window reps via earlier claim applications).
    # Each sub-round batches ALL segments' frontier-vs-later-hit pairs
    # into one dispatch and applies the claims.
    decided = ext.copy()
    tentative = np.zeros(w, dtype=bool)
    n_sub = 0
    for _ in range(MAX_SUBROUNDS):
        frontier: List[int] = []
        seen_seg: Set[int] = set()
        for wi in range(w):
            if decided[wi]:
                continue
            s = pc_of[window[wi]]
            if s in seen_seg:
                continue
            seen_seg.add(s)
            frontier.append(wi)
        if not frontier:
            break
        n_sub += 1
        pairs: List[Tuple[int, int]] = []
        claims: List[Tuple[int, int]] = []
        for fi in frontier:
            f = window[fi]
            for t in adj[f]:
                ti = win_pos.get(t)
                if ti is None or ti <= fi or decided[ti]:
                    continue
                pairs.append((f, t))
                claims.append((fi, ti))
        batch(pairs)
        for fi in frontier:
            decided[fi] = True
            tentative[fi] = True
        for fi, ti in claims:
            consulted.add(pair_key(window[fi], window[ti]))
            v = value(window[fi], window[ti])
            if v is not None and v >= thr:
                decided[ti] = True
    timing.counter("greedy-subrounds", n_sub)

    # (3) the jitted fold as the authoritative device decision pass.
    # Soundness gate: a fold is only authoritative when bookkeeping
    # is COMPLETE — over an incompletely materialized matrix, missing
    # edges read as no-edge and a converged fold can still be wrong.
    # With the megakernel engaged, a complete slab folds via the
    # queue-fed fused program (2 dispatches per slab instead of one
    # dense fold per window); spills/demotions fall through to the
    # dense path, so decisions stay exact either way.
    complete = bool(decided.all())
    rep_flags = None
    if complete and mega is not None and mega.active:
        rep_flags, converged = _megakernel_fold(
            mega, window, win_pos, adj, ext, value, thr, np)
        if rep_flags is not None and (
                not converged
                or not np.array_equal(rep_flags, tentative)):
            if mega.explicit:
                raise RuntimeError(
                    "megakernel slab fold disagreed with the exact "
                    "sub-round bookkeeping — refusing speculative "
                    "greedy decisions")
            _demote_megakernel(
                mega, "slab fold disagreed with sub-round bookkeeping")
            rep_flags = None
    if rep_flags is None:
        mat = np.full((w, w), np.nan, dtype=np.float64)
        for wi, g in enumerate(window):
            for t in adj[g]:
                ti = win_pos.get(t)
                if ti is None or ti <= wi:
                    continue
                v = value(g, t)
                if v is not None:
                    mat[wi, ti] = v
        rep_flags, converged = greedy_select.window_select(mat, ext,
                                                          thr)
    if complete:
        if not converged or not np.array_equal(rep_flags, tentative):
            raise RuntimeError(
                "device window fold disagreed with the exact sub-round "
                "bookkeeping — refusing speculative greedy decisions")
    else:
        # conflict window: rep-chain depth exceeded the sub-round
        # budget; finish the undecided tail with the exact host-order
        # scan (small per-genome batches), decisions unchanged.
        timing.counter("greedy-conflict-windows", 1)
        conflicts_c.inc()
        timing.counter("greedy-host-fallback-windows", 1)
        fallback_c.inc()
        for ti in range(w):
            if decided[ti]:
                continue
            t = window[ti]
            cands = [fi for fi in range(ti)
                     if tentative[fi] and window[fi] in hits[t]]
            batch([(window[fi], t) for fi in cands])
            is_rep = True
            for fi in cands:
                consulted.add(pair_key(window[fi], t))
                v = value(window[fi], t)
                if v is not None and v >= thr:
                    is_rep = False
                    break
            decided[ti] = True
            if is_rep:
                tentative[ti] = True

    for wi in range(w):
        if tentative[wi]:
            g = window[wi]
            reps_by_pc[pc_of[g]].append(g)
            rep_set.add(g)


def _find_representatives(
    clusterer: ClusterBackend,
    pre_cache: PairDistanceCache,
    genomes: Sequence[str],
    skip_clusterer: bool,
    warm_cache: Optional[PairDistanceCache] = None,
    rep_scan_window: Optional[int] = None,
) -> Tuple[Set[int], PairDistanceCache,
           List[Tuple[int, int]], Set[Tuple[int, int]]]:
    """Greedy quality-ordered representative selection.

    Reference: src/clusterer.rs:155-225 (find_dashing_fastani_
    representatives). Genome i becomes a representative iff no existing
    rep with a precluster hit has exact ANI >= threshold.

    Dispatch strategy: the scan is inherently sequential (genome i's
    candidate set is the reps chosen before it), but ANI VALUES are
    order-independent, so a window of upcoming genomes is evaluated
    against all current reps in ONE batched call; only pairs against
    reps that emerge inside the window need follow-up batches. Device
    round trips drop from O(N) to O(N/window + #new-reps) per
    precluster, with decisions identical to the per-genome scan (the
    extra ANIs computed for window genomes that join a cluster first
    are the same waste class as the reference's find_any computing an
    unpredictable candidate subset, reference: src/clusterer.rs:242-262).
    """
    reps: Set[int] = set()
    ani_cache = PairDistanceCache()
    thr = clusterer.ani_threshold
    n = len(genomes)
    window_size = (int(rep_scan_window) if rep_scan_window is not None
                   else REP_SCAN_WINDOW)
    if window_size < 1:
        raise ValueError(
            f"rep_scan_window must be >= 1, got {window_size}")
    computed: List[Tuple[int, int]] = []   # pairs that hit the backend
    consulted: Set[Tuple[int, int]] = set()  # pairs a decision read
    # Device-blocked backends (TPU pairlist kernel) evaluate pairs in
    # blocks of this size; the windowed speculative batches below top
    # up to a multiple of it with next-window pairs so the final block
    # of a dispatch runs full instead of padded. Pure cache fill —
    # decisions are identical, and the topped-up pairs are ones the
    # next window's batch would have computed anyway.
    quantum = max(1, int(getattr(clusterer, "pair_block_multiple", 1)))

    def ensure_anis(pairs: List[Tuple[int, int]],
                    lookahead=()) -> None:
        """Compute (rep, genome) ANIs not already in ani_cache."""
        missing = [(j, g) for j, g in pairs
                   if not ani_cache.contains((j, g))]
        if not missing:
            return
        if quantum > 1 and len(missing) % quantum:
            want = quantum - len(missing) % quantum
            have = set(missing)
            for p in lookahead:
                if want == 0:
                    break
                if p in have or ani_cache.contains(p):
                    continue
                missing.append(p)
                have.add(p)
                want -= 1
        anis = _batch_ani(clusterer, skip_clusterer, pre_cache, genomes,
                          missing, warm_cache, computed_log=computed)
        for (j, g), ani in zip(missing, anis):
            ani_cache.insert((j, g), ani)

    for w0 in range(0, n, window_size):
        window = range(w0, min(w0 + window_size, n))
        # speculative batch: every window genome vs every CURRENT rep
        # (order is irrelevant here — ensure_anis just fills the cache)
        rep_list = list(reps)
        nxt = range(window.stop, min(window.stop + window_size, n))
        ensure_anis([(j, g) for g in window for j in rep_list
                     if pre_cache.contains((g, j))],
                    lookahead=((j, g) for g in nxt for j in rep_list
                               if pre_cache.contains((g, j))))
        for i in window:
            cands = [(j, pre_cache.get((i, j))) for j in sorted(reps)
                     if pre_cache.contains((i, j))]
            # ascending by precluster ANI — preserved from the reference
            # (its comment says "highest first" but the sort is
            # ascending, reference: src/clusterer.rs:167-177)
            cands.sort(key=lambda t: t[1] if t[1] is not None else -1.0)
            # reps that emerged inside the window: their pairs weren't
            # in the speculative batch
            ensure_anis([(j, i) for j, _ in cands])
            is_rep = True
            for j, _ in cands:
                ani = ani_cache.get((j, i))
                consulted.add(pair_key(j, i))
                if ani is not None and ani >= thr:
                    is_rep = False
                    break
            if is_rep:
                logger.debug("Genome designated representative: %d %s",
                             i, genomes[i])
                reps.add(i)
                # speculate forward: the new rep is a candidate for the
                # REST of the window — batch those pairs now instead of
                # one small dispatch per subsequent genome
                ensure_anis([(i, gx) for gx in window if gx > i
                             and pre_cache.contains((gx, i))])
    return reps, ani_cache, computed, consulted


def _find_memberships(
    clusterer: ClusterBackend,
    reps: Set[int],
    pre_cache: PairDistanceCache,
    genomes: Sequence[str],
    ani_cache: PairDistanceCache,
    skip_clusterer: bool,
    warm_cache: Optional[PairDistanceCache] = None,
    computed: Optional[List[Tuple[int, int]]] = None,
    consulted: Optional[Set[Tuple[int, int]]] = None,
) -> List[List[int]]:
    """Assign every non-rep to its best (argmax exact ANI) representative.

    Reference: src/clusterer.rs:316-406 (find_dashing_fastani_
    memberships). Candidates needing computation are precluster hits not
    already in the ANI cache; the batched call covers ALL non-reps at
    once (one device dispatch), then argmax with ties to the lowest rep
    index.
    """
    rep_list = sorted(reps)
    rep_to_cluster = {r: n for n, r in enumerate(rep_list)}
    clusters: List[List[int]] = [[r] for r in rep_list]

    # Collect every (genome, rep) pair that still needs exact ANI.
    # Candidates are by definition precluster hits, so ONE pass over
    # the hit keys replaces the old O(non-reps x reps) double loop over
    # contains() probes (hit graphs are sparse: at the 1000-genome
    # bench rung this is ~2.7k keys vs ~560k probes); the (genome,
    # rep)-ascending sort reproduces the old loop's batch order
    # exactly, so dispatch contents are byte-identical.
    todo: List[Tuple[int, int]] = []
    for a, b in pre_cache.keys():
        a_rep, b_rep = a in reps, b in reps
        if a_rep == b_rep:
            continue  # rep-rep / non-rep pairs never decide membership
        r, i = (a, b) if a_rep else (b, a)
        if not ani_cache.contains((i, r)):
            todo.append((r, i))
    todo.sort(key=lambda p: (p[1], p[0]))
    anis = _batch_ani(clusterer, skip_clusterer, pre_cache, genomes, todo,
                      warm_cache, computed_log=computed)
    for (r, i), ani in zip(todo, anis):
        ani_cache.insert((r, i), ani)  # None recorded too, as the ref does

    for i in range(len(genomes)):
        if i in reps:
            continue
        best_rep = None
        best_ani = None
        for r in rep_list:
            ani = ani_cache.get((i, r))
            if consulted is not None:
                consulted.add(pair_key(i, r))
            if ani is not None and (best_ani is None or ani > best_ani):
                best_rep = r
                best_ani = ani
        if best_rep is None:
            raise RuntimeError(
                f"genome {genomes[i]} passed the representative test but "
                "has no ANI to any representative — inconsistent backend")
        clusters[rep_to_cluster[best_rep]].append(i)
    return clusters
