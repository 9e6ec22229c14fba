"""Exact-ANI cluster backends + skani-style preclusterer on the
fragment-containment kernel (ops/fragment_ani.py).

Three backends, mirroring the reference's surface:

  * FastANIEquivalentClusterer — the reference's fastANI wrapper semantics
    (reference: src/fastani.rs:26-73): bidirectional, fragment-fraction
    gate in either direction, None when gated out, max-ANI result,
    fragment length configurable (--fragment-length).
  * SkaniEquivalentClusterer — the reference's skani wrapper semantics
    (reference: src/skani.rs:108-129): always returns a value (a gated
    pair yields ANI 0.0 rather than None), min-aligned-fraction honored
    internally.
  * SkaniPreclusterer — all-pairs screening by marker-sketch containment
    on device, then exact fragment ANI on screened pairs only
    (reference: src/skani.rs:33-106).

All sketches/profiles are computed once per genome and cached in an LRU
ProfileStore (the reference re-sketches from disk on every pair,
reference: src/skani.rs:171-172 — deliberately not replicated).

K-mer size is 15 for both cluster backends: calibrated so the abisko4
golden clusterings (reference: src/clusterer.rs:481-663) reproduce with
margin; see tests/test_golden_clusters.py.
"""

from __future__ import annotations

import collections
import contextlib
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from galah_tpu.backends.base import ClusterBackend, PreclusterBackend
from galah_tpu.cluster.cache import PairDistanceCache
from galah_tpu.config import Defaults
from galah_tpu.io import diskcache
from galah_tpu.io.fasta import read_genome
from galah_tpu.ops import fragment_ani
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.fragment_ani import GenomeProfile
from galah_tpu.ops.pairwise import screen_pairs
from galah_tpu.utils import timing

logger = logging.getLogger(__name__)

ANI_KMER = 15


class ProfileStore:
    """LRU cache: genome path -> GenomeProfile (profile once, reuse).

    With an on-disk cache (io/diskcache.py), the expensive profile
    arrays (positional hashes, distinct-set, markers) also persist
    across runs keyed by file identity + (k, fraglen).
    """

    def __init__(self, k: int = ANI_KMER,
                 fraglen: int = Defaults.FRAGMENT_LENGTH,
                 maxsize: int = 128,
                 cache: Optional[diskcache.CacheDir] = None,
                 subsample_c: int = Defaults.ANI_SUBSAMPLE,
                 threads: int = 1,
                 hash_algorithm: str = "murmur3") -> None:
        self.k = k
        self.fraglen = fraglen
        self.subsample_c = int(subsample_c)
        self.hash_algorithm = hash_algorithm
        self.threads = max(int(threads), 1)
        self.maxsize = maxsize
        self.disk = cache or diskcache.get_cache()
        self._cache: "collections.OrderedDict[str, GenomeProfile]" = (
            collections.OrderedDict())

    def _params(self) -> dict:
        p = {"k": self.k, "fraglen": self.fraglen}
        # only key the cache on non-default knobs, so default-path
        # entries from before each flag existed stay valid
        if self.subsample_c != 1:
            p["subsample_c"] = self.subsample_c
        if self.hash_algorithm != "murmur3":
            p["hash_algorithm"] = self.hash_algorithm
        return p

    @contextlib.contextmanager
    def reserve(self, n: int):
        """Temporarily grow the LRU to a batch's working set (a batch
        referencing more genomes than maxsize would otherwise rebuild
        profiles mid-batch), restoring the bound — and evicting the
        overflow — when the batch is done, so long-running processes
        don't keep every profile of a 50k-genome run resident."""
        old = self.maxsize
        self.maxsize = max(self.maxsize, n)
        try:
            yield
        finally:
            self.maxsize = old
            while len(self._cache) > self.maxsize:
                self._cache.popitem(last=False)

    def _insert(self, path: str, prof: GenomeProfile) -> None:
        self._cache[path] = prof
        if len(self._cache) > self.maxsize:
            self._cache.popitem(last=False)

    def _load_disk(self, path: str) -> Optional[GenomeProfile]:
        entry = self.disk.load(path, "profile", self._params())
        if entry is None:
            return None
        return GenomeProfile(
            path=path, k=self.k, fraglen=self.fraglen,
            flat_hashes=entry["flat_hashes"],
            ref_set=entry["ref_set"], markers=entry["markers"],
            subsample_c=self.subsample_c)

    def _store_disk(self, path: str, prof: GenomeProfile) -> None:
        from galah_tpu.obs import metrics as obs_metrics

        obs_metrics.counter(
            "sketch.profiles_computed",
            help="Fragment-ANI genome profiles computed (not served "
                 "from any cache)", unit="genomes").inc()
        self.disk.store(path, "profile", self._params(), {
            "flat_hashes": prof.flat_hashes,
            "ref_set": prof.ref_set,
            "markers": prof.markers,
        })

    def _read(self, path: str):
        """One genome's FASTA read: the span `profile.read`."""
        with timing.stage("profile.read"):
            return read_genome(path)

    def _build(self, genome) -> GenomeProfile:
        """One genome's profile: the span `profile.build`; its bases
        count in `profile-bp`, the k-mer positions its FracMinHash
        sample keeps for exact ANI in `profile-kept-kmers`."""
        timing.counter("profile-bp", genome.length)
        with timing.stage("profile.build"):
            prof = fragment_ani.build_profile(
                genome, k=self.k, fraglen=self.fraglen,
                subsample_c=self.subsample_c,
                hash_algorithm=self.hash_algorithm)
        timing.counter("profile-kept-kmers", prof.query_positions())
        return prof

    def _build_batch(self, genomes) -> "list[GenomeProfile]":
        """A group's profiles in grouped hash dispatches: one
        `profile.build` span for the group."""
        timing.counter("profile-bp", sum(g.length for g in genomes))
        with timing.stage("profile.build"):
            profs = fragment_ani.build_profiles_batch(
                genomes, k=self.k, fraglen=self.fraglen,
                subsample_c=self.subsample_c,
                hash_algorithm=self.hash_algorithm)
        timing.counter("profile-kept-kmers",
                       sum(p.query_positions() for p in profs))
        return profs

    def get(self, path: str) -> GenomeProfile:
        prof = self._cache.get(path)
        if prof is not None:
            self._cache.move_to_end(path)
            return prof
        prof = self._load_disk(path)
        if prof is None:
            prof = self._build(self._read(path))
            self._store_disk(path, prof)
        self._insert(path, prof)
        return prof

    def get_many(self, paths: Sequence[str]) -> "list[GenomeProfile]":
        """Profiles for many paths; cache misses are ingested with the
        prefetch pool and hashed in grouped batch dispatches
        (ops/fragment_ani.build_profiles_batch) instead of one dispatch
        per genome."""
        from galah_tpu.io.prefetch import iter_prefetched, process_stream

        by_path: "dict[str, GenomeProfile]" = {}
        misses = []
        for p in dict.fromkeys(paths):
            prof = self._cache.get(p)
            if prof is not None:
                self._cache.move_to_end(p)
                by_path[p] = prof
                continue
            prof = self._load_disk(p)
            if prof is not None:
                self._insert(p, prof)
                by_path[p] = prof
            else:
                misses.append(p)
        # per-genome C builds on `threads` workers, else grouped device
        # hashing batches
        batched = not fragment_ani._c_profile_available(self.k)
        for p, prof in process_stream(
                iter_prefetched(misses, self._read,
                                depth=max(2, self.threads)),
                lambda g: g.codes.shape[0],
                fragment_ani.PROFILE_BATCH_BUDGET,
                lambda buf: self._build_batch([g for _, g in buf]),
                lambda _path, g: self._build(g),
                batched=batched, workers=self.threads):
            self._store_disk(p, prof)
            self._insert(p, prof)
            by_path[p] = prof
        return [by_path[p] for p in paths]


class _FragmentANIMixin:
    """Shared bidirectional-ANI plumbing for the two cluster backends."""

    store: ProfileStore
    min_aligned_fraction: float

    def _pair_result(
        self, a: str, b: str
    ) -> Tuple[Optional[float], fragment_ani.DirectedANI,
               fragment_ani.DirectedANI]:
        pa = self.store.get(a)
        pb = self.store.get(b)
        return fragment_ani.bidirectional_ani(
            pa, pb, min_aligned_frac=self.min_aligned_fraction)

    def _batch_results(
        self, pairs: Sequence[tuple[str, str]]
    ) -> List[Optional[float]]:
        """ANI for every path pair via coalesced device dispatches."""
        with timing.stage("profile-genomes"):
            # each unique genome is profiled at most once per batch: the
            # LRU is grown to the batch's working set and paths are
            # fetched deduplicated before pair assembly
            unique = list(dict.fromkeys(p for pair in pairs for p in pair))
            with self.store.reserve(len(unique)):
                by_path = dict(zip(unique, self.store.get_many(unique)))
            profs = [(by_path[a], by_path[b]) for a, b in pairs]
        with timing.stage("fragment-ani"):
            return _guarded_ani_values(
                profs, self.min_aligned_fraction, self.store.threads)


def _guarded_ani_values(profs, min_aligned_frac: float,
                        threads: int) -> List[Optional[float]]:
    """Guarded batched bidirectional-ANI dispatch, shared by the
    cluster backends and the skani preclusterer. The per-pair fallback
    trades the coalesced batch for N tiny dispatches, so a persistently
    failing batched kernel degrades throughput, not the run (stage
    report: demoted[dispatch.fragment-ani]).

    Two fallback layers compose here: INSIDE the batch call,
    fragment_ani resolves the membership strategy
    (GALAH_TPU_FRAGMENT_STRATEGY: blocked Mosaic kernel / vmapped XLA
    / C merge, see docs/fragment_kernel.md) and an AUTO-chosen Pallas
    path already demotes to its XLA twin on Mosaic failure
    (fragment-pallas-demoted counter); this OUTER guard catches
    whole-batch failures of whatever strategy won and retries
    per-pair."""
    from galah_tpu.resilience import dispatch as rdispatch

    return rdispatch.run(
        "dispatch.fragment-ani",
        lambda: fragment_ani.bidirectional_ani_values(
            profs, min_aligned_frac=min_aligned_frac, threads=threads),
        fallback=lambda: [
            fragment_ani.bidirectional_ani_values(
                [pp], min_aligned_frac=min_aligned_frac,
                threads=threads)[0]
            for pp in profs],
        validate=rdispatch.expect_ani_values(len(profs)))


def _device_pair_block() -> int:
    """Backend batch-size hint (ClusterBackend.pair_block_multiple):
    on a TPU backend the device evaluates pairs in P-pair blocks
    (ops/pallas_pairlist.py), so the engine's speculative batches are
    sized to fill them; host backends report 1 (no blocking)."""
    from galah_tpu.ops.sparse_device import pair_block_quantum

    return pair_block_quantum()


class FastANIEquivalentClusterer(ClusterBackend, _FragmentANIMixin):
    def __init__(self, threshold: float, min_aligned_fraction: float,
                 fraglen: int = Defaults.FRAGMENT_LENGTH,
                 store: Optional[ProfileStore] = None) -> None:
        self._threshold = float(threshold)
        self.min_aligned_fraction = float(min_aligned_fraction)
        self.store = store or ProfileStore(k=ANI_KMER, fraglen=fraglen)
        if self.store.fraglen != fraglen:
            raise ValueError(
                f"fragment length mismatch: backend wants {fraglen}, "
                f"shared ProfileStore was built with {self.store.fraglen}")

    def method_name(self) -> str:
        return "fastani"

    @property
    def pair_block_multiple(self) -> int:
        return _device_pair_block()

    @property
    def ani_threshold(self) -> float:
        return self._threshold

    def calculate_ani_batch(
        self, pairs: Sequence[tuple[str, str]]
    ) -> List[Optional[float]]:
        return self._batch_results(pairs)


class SkaniEquivalentClusterer(ClusterBackend, _FragmentANIMixin):
    def __init__(self, threshold: float, min_aligned_fraction: float,
                 store: Optional[ProfileStore] = None) -> None:
        self._threshold = float(threshold)
        self.min_aligned_fraction = float(min_aligned_fraction)
        self.store = store or ProfileStore(k=ANI_KMER)

    def method_name(self) -> str:
        return "skani"

    @property
    def pair_block_multiple(self) -> int:
        return _device_pair_block()

    @property
    def ani_threshold(self) -> float:
        return self._threshold

    def calculate_ani_batch(
        self, pairs: Sequence[tuple[str, str]]
    ) -> List[Optional[float]]:
        # A gated-out pair is ANI 0.0, not None — the reference's skani
        # wrapper always returns Some (reference: src/skani.rs:126-129).
        return [ani if ani is not None else 0.0
                for ani in self._batch_results(pairs)]


class SkaniPreclusterer(PreclusterBackend):
    """Marker screening on device + exact fragment ANI on screened pairs."""

    SCREEN_IDENTITY = 0.80  # reference: src/skani.rs:59 screen_refs(0.80,..)

    def __init__(self, threshold: float, min_aligned_fraction: float,
                 store: Optional[ProfileStore] = None) -> None:
        self.threshold = float(threshold)
        self.min_aligned_fraction = float(min_aligned_fraction)
        self.store = store or ProfileStore(k=ANI_KMER)

    def method_name(self) -> str:
        return "skani"

    def _marker_matrix(self, profiles, n: int, width: int = 0):
        """Pad per-genome marker sketches to a common-width matrix
        (`width` forces the column count; 0 = fit to these profiles —
        the multihost path forces the allgather-agreed global width so
        both paths share this one padding loop)."""
        m = width or -(-max(max(
            (p.markers.shape[0] for p in profiles), default=1), 1)
            // 64) * 64
        mat = np.full((n, m), np.uint64(SENTINEL), dtype=np.uint64)
        counts = np.zeros(n, dtype=np.int64)
        for i, p in enumerate(profiles):
            cnt = min(p.markers.shape[0], m)
            mat[i, :cnt] = p.markers[:cnt]
            counts[i] = cnt
        return mat, counts

    def _marker_matrix_multihost(self, genome_paths: Sequence[str]):
        """Per-host profiling for the marker screen: each host profiles
        only its strided shard and exchanges the (small) marker rows —
        the global width is agreed with one scalar allgather first.
        Returns (mat, counts, warm) where `warm` maps this host's
        global genome index -> its built profile, handed to phase B so
        the shard's profiles survive regardless of LRU capacity or
        disk-cache availability."""
        from jax.experimental import multihost_utils

        from galah_tpu.parallel import distributed

        n = len(genome_paths)
        mine_idx = distributed.host_shard(list(range(n)))
        with timing.stage("profile-genomes"):
            with self.store.reserve(max(len(mine_idx), 1)):
                mine = self.store.get_many(
                    [genome_paths[i] for i in mine_idx])
        local_max = max(
            max((p.markers.shape[0] for p in mine), default=1), 1)
        maxes = np.asarray(multihost_utils.process_allgather(
            np.array([local_max], dtype=np.int64), tiled=False))
        m = -(-int(maxes.max()) // 64) * 64

        local_mat, local_counts = self._marker_matrix(
            mine, len(mine), width=m)
        local = np.concatenate(
            [local_mat, local_counts.astype(np.uint64)[:, None]], axis=1)
        full = distributed.allgather_host_rows(
            n, local, fill=np.uint64(SENTINEL))
        warm = dict(zip(mine_idx, mine))
        return (np.ascontiguousarray(full[:, :m]),
                full[:, m].astype(np.int64), warm)

    def _exact_ani_multihost(self, genome_paths, pairs, warm):
        """Exact ANI over the screened pairs, sharded by host: each
        host owns the pairs whose SECOND endpoint is in its phase-A
        genome shard (owner j % P composes with host_shard's stride),
        reuses `warm` profiles for those and profiles only cross-host
        first endpoints (the shared disk cache makes them warm too
        when enabled), then the per-pair ANIs are exchanged through
        the shared protocol — which also propagates a host failure to
        every peer instead of stranding them in the collective. Every
        host ends with the identical result vector."""
        from galah_tpu.parallel import distributed

        def compute_mine(idxs):
            my_pairs = [pairs[k] for k in idxs]
            endpoints = list(dict.fromkeys(
                g for pair in my_pairs for g in pair))
            missing = [g for g in endpoints if g not in warm]
            with timing.stage("profile-genomes"):
                with self.store.reserve(max(len(missing), 1)):
                    prof = dict(zip(missing, self.store.get_many(
                        [genome_paths[g] for g in missing])))
            prof.update(
                (g, warm[g]) for g in endpoints if g in warm)
            with timing.stage("fragment-ani"):
                return _guarded_ani_values(
                    [(prof[i], prof[j]) for i, j in my_pairs],
                    self.min_aligned_fraction, self.store.threads)

        return distributed.sharded_optional_floats(
            len(pairs), compute_mine, owner=lambda k: pairs[k][1])

    def distances(self, genome_paths: Sequence[str]) -> PairDistanceCache:
        from galah_tpu.parallel import distributed

        n = len(genome_paths)
        n_proc = distributed.process_count()
        logger.info("Profiling %d genomes for skani-style preclustering ..",
                    n)
        warm = {}
        if n_proc > 1:
            mat, counts, warm = self._marker_matrix_multihost(
                genome_paths)
            profiles = None
        else:
            with timing.stage("profile-genomes"):
                with self.store.reserve(n):
                    profiles = self.store.get_many(genome_paths)
            mat, counts = self._marker_matrix(profiles, n)

        # Blocked screening: ONE device dispatch per row block (the same
        # extraction pattern as threshold_pairs — dispatch count scales
        # O(N / row_tile), not O((N / tile)^2); auto-shards the columns
        # over a multi-device mesh). Above the sparse crossover the
        # host collision screen runs instead (exact, any backend).
        logger.info("Screening all pairs by marker containment ..")
        c_floor = self.SCREEN_IDENTITY ** self.store.k
        with timing.stage("marker-screen"):
            pairs = screen_pairs(mat, counts, c_floor)
        logger.info("%d pairs passed screening; computing exact ANI ..",
                    len(pairs))

        cache = PairDistanceCache()
        if n_proc > 1:
            if pairs:
                anis = self._exact_ani_multihost(genome_paths, pairs,
                                                 warm)
                for (i, j), ani in zip(pairs, anis):
                    if ani is not None and ani >= self.threshold:
                        cache.insert((i, j), float(ani))
        else:
            with timing.stage("fragment-ani"):
                anis = _guarded_ani_values(
                    [(profiles[i], profiles[j]) for i, j in pairs],
                    self.min_aligned_fraction, self.store.threads)
            for (i, j), ani in zip(pairs, anis):
                if ani is not None and ani >= self.threshold:
                    cache.insert((i, j), ani)
        logger.info("Found %d pairs passing precluster threshold %.4f",
                    len(cache), self.threshold)
        return cache

    def distances_subset(self, genome_paths: Sequence[str],
                         keep) -> PairDistanceCache:
        """Single-host distances() restricted to screened pairs with
        ``keep(i, j)`` true. The fleet merge computes only CROSS-shard
        pairs through this: same profile, screen and exact-ANI code
        path as the full run, only the pair list is filtered, so the
        kept values are bit-identical to a full distances() run's
        (the merge-determinism argument in docs/resilience.md)."""
        n = len(genome_paths)
        logger.info("Profiling %d genomes for cross-shard merge ..", n)
        with timing.stage("profile-genomes"):
            with self.store.reserve(n):
                profiles = self.store.get_many(genome_paths)
        mat, counts = self._marker_matrix(profiles, n)
        c_floor = self.SCREEN_IDENTITY ** self.store.k
        with timing.stage("marker-screen"):
            pairs = [p for p in screen_pairs(mat, counts, c_floor)
                     if keep(p[0], p[1])]
        logger.info("%d cross-shard pairs passed screening; "
                    "computing exact ANI ..", len(pairs))
        cache = PairDistanceCache()
        with timing.stage("fragment-ani"):
            anis = _guarded_ani_values(
                [(profiles[i], profiles[j]) for i, j in pairs],
                self.min_aligned_fraction, self.store.threads)
        for (i, j), ani in zip(pairs, anis):
            if ani is not None and ani >= self.threshold:
                cache.insert((i, j), ani)
        return cache
