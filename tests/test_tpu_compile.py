"""The main path's kernels compile for a v5e chip.

Each test asks the TPU compiler, which is installed without a chip, to
compile one kernel of the main path at the widths production uses, for
a described v5e:2x2 topology. That catches what interpret mode cannot:
tiling misalignment, fast-memory overuse, programs that do not fit.
Nothing runs, so results and times are out of scope (chip_smoke.py
covers those on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU runtime, and every test worker
imports this file.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

K = 1000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_pair_stats_tile(one_chip):
    from galah_tpu.ops.pallas_pairwise import tile_stats_pallas

    c = _compile(functools.partial(tile_stats_pallas, sketch_size=K),
                 one_chip, ((256, K), jnp.uint64), ((256, K), jnp.uint64))
    _assert_kernel(c)


def test_pairlist_blocked(one_chip):
    from galah_tpu.ops.pallas_pairlist import pair_stats_pairs_pallas

    c = _compile(functools.partial(pair_stats_pairs_pallas, sketch_size=K),
                 one_chip, ((1024, K), jnp.uint64),
                 ((1024, K), jnp.uint64))
    _assert_kernel(c)


def test_hll_union_stats(one_chip):
    from galah_tpu.ops.pallas_hll import hll_union_stats_tile

    c = _compile(functools.partial(hll_union_stats_tile, chunk=1024),
                 one_chip, ((32, 4096), jnp.float32),
                 ((32, 4096), jnp.float32))
    _assert_kernel(c)


@pytest.mark.parametrize("jobs,blocks", [(8, 32), (4096, 4096)])
def test_fragment_window_hits(one_chip, jobs, blocks):
    """A toy pair and a 3.5 Mbp genome against another."""
    from galah_tpu.ops import pallas_fragment as pf

    span = 4
    plan = ((jobs,), jnp.int32)
    q = ((jobs * pf.A_SUB, pf.QLA), jnp.int32)
    r = ((blocks * pf.RSB, pf.B_LANE), jnp.int32)
    c = _compile(functools.partial(pf._window_hits_jit, span=span,
                                   interpret=False),
                 one_chip, plan, plan, q, q, r, r)
    _assert_kernel(c)


@pytest.mark.parametrize("algo,n_words", [("murmur3", 3), ("tpufast", 1)])
def test_fused_sketch(one_chip, algo, n_words):
    from galah_tpu.ops import sketch_stream as ss

    jobs, span = 8, 16
    width = span * ss.BLOCK_SUB * ss.LANES

    def fused(valid, *words):
        return ss.fused_sketch_candidates(words, valid, algo=algo)

    c = _compile(fused, one_chip, ((jobs, width), jnp.bool_),
                 *[((jobs, width), jnp.uint64)] * n_words)
    _assert_kernel(c)


@pytest.mark.parametrize("mbp", [3.5])
def test_fused_sketch_launch_of_a_real_genome(one_chip, mbp):
    """The whole fused launch (unpack + key words + kernel + candidate
    post-pass) as _pack_fused lays out a genome of MAG size: it must
    fit a v5e chip's HBM (eight padded jobs of a 2 Mbp genome did not).
    """
    import numpy as np

    from galah_tpu.io.fasta import Genome, GenomeStats
    from galah_tpu.ops import sketch_stream as ss

    n = int(mbp * 1e6)
    genome = Genome(path="g.fna", codes=np.zeros(n, dtype=np.uint8),
                    contig_offsets=np.arange(0, n + 1, n // 40),
                    stats=GenomeStats(40, 0, 0))
    (_, packed, ambits, offs, span), = ss._pack_fused([genome])[1]
    fn = functools.partial(ss._fused_group_sketch_jit, k=21, seed=0,
                           algo="murmur3", sketch_size=K, span=span,
                           interpret=False)
    c = _compile(fn, one_chip, (packed.shape, jnp.uint8),
                 (ambits.shape, jnp.uint8), (offs.shape, jnp.int32))
    _assert_kernel(c)


def test_greedy_window_fold_f64(one_chip):
    from galah_tpu.ops.greedy_select import _window_select_jit

    w = 64
    _compile(_window_select_jit, one_chip, ((w, w), jnp.float64),
             ((w,), jnp.bool_), ((w,), jnp.bool_), ((), jnp.float64))


def test_xla_tile_ani(one_chip):
    from galah_tpu.ops.pairwise import tile_ani

    _compile(functools.partial(tile_ani, sketch_size=K, k=21), one_chip,
             ((64, K), jnp.uint64), ((64, K), jnp.uint64))


def test_sharded_pairlist_four_chips(topo):
    """The screened pair pass of a four-chip host: candidate batches
    sharded over a 4-device mesh, the sketch matrix replicated, the
    blocked Mosaic pairlist kernel on every device."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from galah_tpu.ops.sparse_device import _make_sharded_batch_stats

    mesh = Mesh(np.array(topo.devices), ("i",))
    assert mesh.devices.size == 4
    fn = _make_sharded_batch_stats(mesh, K, use_pallas=True)
    mat = jax.ShapeDtypeStruct((256, K), jnp.uint64,
                               sharding=NamedSharding(mesh, P(None, None)))
    idx = jax.ShapeDtypeStruct((4096,), jnp.int32,
                               sharding=NamedSharding(mesh, P("i")))
    compiled = fn.lower(mat, idx, idx).compile()
    _assert_kernel(compiled)
    assert "all-gather" in compiled.as_text()
