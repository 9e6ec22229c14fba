"""HyperLogLog precluster backend — the dashing-equivalent.

The reference spawns the dashing C++ binary and parses its full N x N
distance matrix from stdout (reference: src/dashing.rs:11-100). Here the
HLL sketches are built and compared on device (ops/hll.py); only the
sparse thresholded pairs reach the host cache.
"""

from __future__ import annotations

import logging
from typing import Sequence

from galah_tpu.backends.base import PreclusterBackend
from galah_tpu.cluster.cache import PairDistanceCache
from galah_tpu.config import Defaults
from galah_tpu.io import diskcache
from galah_tpu.ops import hll
from galah_tpu.utils import timing

logger = logging.getLogger(__name__)


class HLLPreclusterer(PreclusterBackend):
    """All-pairs HLL Mash-ANI pass producing the sparse pair cache."""

    def __init__(self, min_ani: float, p: int = hll.DEFAULT_P,
                 k: int = Defaults.MINHASH_KMER,
                 seed: int = Defaults.MINHASH_SEED,
                 hash_algo: str = Defaults.HASH_ALGO,
                 cache: "diskcache.CacheDir | None" = None,
                 threads: int = 1) -> None:
        self.min_ani = float(min_ani)
        self.p = int(p)
        self.k = int(k)
        self.seed = int(seed)
        self.algo = hash_algo
        self.threads = max(int(threads), 1)
        self.cache = cache or diskcache.get_cache()

    def method_name(self) -> str:
        return "dashing"

    def _sketch_paths(self, paths: Sequence[str]) -> dict:
        """path -> (2^p,) register row for (deduped) paths: cache probe
        + prefetch + batched device sketching; the consumer loop is the
        single writer into the disk cache."""
        from galah_tpu.io.fasta import read_genome
        from galah_tpu.io.prefetch import (
            probe_and_prefetch,
            process_stream,
        )
        from galah_tpu.ops.hashing import (
            BATCH_BUDGET,
            device_transfer_bound,
        )

        params = {"p": self.p, "k": self.k, "seed": self.seed,
                  "algo": self.algo}

        def probe(path):
            entry = self.cache.load(path, "hll", params)
            return entry["regs"] if entry is not None else None

        from galah_tpu.resilience import dispatch as rdispatch

        def sketch_batch(buf):
            # Guarded device dispatch: retry transients, demote to the
            # per-genome path on persistent failure (stage report:
            # demoted[dispatch.sketch-hll]).
            return rdispatch.run(
                "dispatch.sketch-hll",
                lambda: hll.hll_sketch_genomes_batch(
                    [g for _, g in buf], p=self.p, k=self.k,
                    seed=self.seed, algo=self.algo),
                fallback=lambda: [hll.hll_sketch_genome(
                    g, p=self.p, k=self.k, seed=self.seed,
                    algo=self.algo) for _p, g in buf],
                validate=rdispatch.expect_len(len(buf)))

        by_path, miss_iter = probe_and_prefetch(
            paths, probe, read_genome, depth=max(2, self.threads))
        for path, row in process_stream(
                miss_iter, lambda g: g.codes.shape[0], BATCH_BUDGET,
                sketch_batch,
                lambda _path, g: hll.hll_sketch_genome(
                    g, p=self.p, k=self.k, seed=self.seed,
                    algo=self.algo),
                batched=(device_transfer_bound()
                         and not hll.c_hll_available(self.k, self.p)),
                workers=self.threads):
            by_path[path] = row
            from galah_tpu.obs import metrics as obs_metrics

            obs_metrics.counter(
                "sketch.hll_computed",
                help="HLL register rows computed (not served from any "
                     "cache)", unit="genomes").inc()
            self.cache.store(path, "hll", params, {"regs": row})
        return by_path

    def distances(self, genome_paths: Sequence[str]) -> PairDistanceCache:
        import numpy as np

        from galah_tpu.parallel import distributed

        n = len(genome_paths)
        logger.info("Sketching HLL registers of %d genomes on device ..", n)
        regs = np.zeros((n, 1 << self.p), dtype=np.uint8)
        index: "dict[str, list[int]]" = {}
        for i, path in enumerate(genome_paths):
            index.setdefault(path, []).append(i)
        with timing.stage("sketch-hll"):
            unique = list(index)
            if distributed.process_count() > 1:
                # Per-host ingestion, same shape as the MinHash
                # backend: sketch only this host's strided shard,
                # exchange the (tiny) register rows via the shared
                # protocol, reassemble identically on every host.
                mine = distributed.host_shard(unique)
                by_path = self._sketch_paths(mine)
                local = (np.stack([by_path[p] for p in mine])
                         if mine else
                         np.zeros((0, 1 << self.p), dtype=np.uint8))
                full = distributed.allgather_host_rows(
                    len(unique), local, fill=np.uint8(0))
                for row_i, path in enumerate(unique):
                    regs[index[path]] = full[row_i]
            else:
                by_path = self._sketch_paths(genome_paths)
                for path, row in by_path.items():
                    regs[index[path]] = row

        logger.info("Computing tiled all-pairs HLL ANI ..")
        with timing.stage("pairwise-hll"):
            pairs = hll.hll_threshold_pairs(regs, k=self.k,
                                            min_ani=self.min_ani)
        cache = PairDistanceCache()
        for (i, j), ani in pairs.items():
            cache.insert((i, j), ani)
        logger.info("Found %d pairs passing precluster threshold %.4f",
                    len(cache), self.min_ani)
        return cache
