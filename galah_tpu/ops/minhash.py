"""Device MinHash sketching: chunked k-mer hashing + running bottom-k.

Produces bit-identical sketches to ops/minhash_np.py (the numpy semantic
reference), validated in tests/test_minhash.py, but runs the hash + top-k
work on the accelerator. Genomes are processed in fixed-size chunks (with
k-1 overlap) so XLA compiles one kernel per (chunk, k) and reuses it across
all genomes and contigs regardless of length.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from galah_tpu.config import Defaults
from galah_tpu.io.fasta import Genome
from galah_tpu.ops import hashing
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.minhash_np import MinHashSketch
from galah_tpu.utils import timing

# Chunk/budget policy lives with the chunk iterator (ops/hashing.py);
# re-exported here for existing importers.
DEFAULT_CHUNK = hashing.DEFAULT_CHUNK
BATCH_BUDGET = hashing.BATCH_BUDGET


def sketch_genome_device(
    genome: Genome,
    sketch_size: int = Defaults.MINHASH_SKETCH_SIZE,
    k: int = Defaults.MINHASH_KMER,
    seed: int = Defaults.MINHASH_SEED,
    chunk: int = DEFAULT_CHUNK,
    algo: str = Defaults.HASH_ALGO,
) -> MinHashSketch:
    """Bottom-k distinct canonical k-mer sketch, computed on device.

    On a single-device CPU backend the compiled-C sketcher
    (csrc/sketch.c) runs instead — bit-identical output, ~an order of
    magnitude faster than the XLA-CPU chunk pipeline on one core."""
    # An explicit non-default chunk pins the JAX chunk pipeline (the
    # C path has no chunking; parity tests drive the JAX path this way).
    if (jax.default_backend() == "cpu" and k <= 32
            and chunk == DEFAULT_CHUNK):
        try:
            from galah_tpu.ops import _csketch

            hashes = _csketch.sketch_bottomk(
                genome.codes, genome.contig_offsets, k=k,
                sketch_size=sketch_size, seed=seed, algo=algo)
            return MinHashSketch(hashes=hashes, sketch_size=sketch_size,
                                 kmer=k)
        except ImportError:
            pass  # no C toolchain: fall through to the JAX path

    running = jnp.full((sketch_size,), hashing.HASH_SENTINEL)
    for hashes, _pos, _n_new in hashing.iter_chunk_hashes(
            genome.codes, genome.contig_offsets, k=k, chunk=chunk,
            seed=seed, algo=algo):
        running = hashing.bottom_k_update(
            running, hashes, sketch_size=sketch_size)
        timing.dispatch()

    timing.dispatch(sync=True)
    out = np.asarray(running)
    out = out[out != np.uint64(SENTINEL)]
    return MinHashSketch(hashes=out, sketch_size=sketch_size, kmer=k)


@functools.partial(
    jax.jit, static_argnames=("k", "seed", "algo", "sketch_size"))
def _batch_sketch_kernel(packed, ambits, offsets, k, seed, algo,
                         sketch_size):
    """(G, C/4) packed genome rows -> (G, sketch_size) sorted distinct
    bottom-k hashes (SENTINEL-padded). One dispatch for the whole group."""
    h = hashing.canonical_kmer_hashes_batch(
        packed, ambits, offsets, k, seed, algo)
    h = jnp.sort(h, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((h.shape[0], 1), bool), h[:, 1:] == h[:, :-1]], axis=1)
    h = jnp.where(dup, hashing.HASH_SENTINEL, h)
    h = jnp.sort(h, axis=-1)
    return h[:, : min(sketch_size, h.shape[1])]


def sketch_genomes_device_batch(
    genomes: Sequence[Genome],
    sketch_size: int = Defaults.MINHASH_SKETCH_SIZE,
    k: int = Defaults.MINHASH_KMER,
    seed: int = Defaults.MINHASH_SEED,
    algo: str = Defaults.HASH_ALGO,
    budget: int = BATCH_BUDGET,
) -> List[MinHashSketch]:
    """Sketch many genomes in a handful of dispatches, bit-identical to
    sketch_genome_device per genome.

    Genomes are bucketed by 64 Ki-padded length (bounding compile
    variants) and packed into (G, L) groups of at most `budget` total
    positions; each group is one device dispatch (hash + row-wise
    distinct bottom-k). The per-dispatch round trip otherwise
    dominates small-genome sketching (reference analog:
    finch sketch_files, src/finch.rs:47, a host-parallel per-file loop).
    Genomes longer than DEFAULT_CHUNK fall back to the chunked
    single-genome path.
    """
    out: List[MinHashSketch] = [None] * len(genomes)  # type: ignore
    skipped, group_iter = hashing.iter_genome_groups(
        genomes, budget=budget, max_len=DEFAULT_CHUNK)
    for i in skipped:
        out[i] = sketch_genome_device(
            genomes[i], sketch_size=sketch_size, k=k, seed=seed,
            algo=algo)
    for chunk_idxs, packed, ambits, offs in group_iter:
        timing.dispatch()
        timing.dispatch(sync=True)
        mat = np.asarray(_batch_sketch_kernel(
            jnp.asarray(packed), jnp.asarray(ambits),
            jnp.asarray(offs), k=k, seed=seed, algo=algo,
            sketch_size=sketch_size))
        for row, gi in enumerate(chunk_idxs):
            hs = mat[row]
            hs = hs[hs != np.uint64(SENTINEL)]
            out[gi] = MinHashSketch(
                hashes=hs, sketch_size=sketch_size, kmer=k)
    return out


def sketch_matrix(
    sketches: Sequence[MinHashSketch],
    sketch_size: int = Defaults.MINHASH_SKETCH_SIZE,
) -> np.ndarray:
    """Stack sketches into a SENTINEL-padded (N, sketch_size) uint64 matrix.

    This is the dense device-facing layout for the all-pairs kernel; rows
    sorted ascending with trailing sentinels for genomes that yielded fewer
    than sketch_size distinct k-mers.
    """
    n = len(sketches)
    mat = np.full((n, sketch_size), np.uint64(SENTINEL), dtype=np.uint64)
    for i, s in enumerate(sketches):
        m = min(s.size, sketch_size)
        mat[i, :m] = s.hashes[:m]
    return mat
