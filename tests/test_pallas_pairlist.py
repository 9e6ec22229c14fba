"""Mosaic pairlist kernel: bit-parity with the XLA pair stats, in
interpreter mode on the CPU test mesh (the v5e compile is covered by
tests/test_tpu_compile.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.pairwise import _pair_stats
from galah_tpu.ops.pallas_pairlist import pair_stats_pairs_pallas


def _rand_sketches(rng, n, width):
    mat = np.full((n, width), np.uint64(SENTINEL), dtype=np.uint64)
    for i in range(n):
        cut = int(rng.integers(1, width + 1))
        vals = rng.choice(1 << 62, size=cut, replace=False)
        mat[i, :cut] = np.sort(vals.astype(np.uint64))
    return mat


def _xla_pairs(a, b, sketch_size):
    c, t = jax.vmap(
        lambda x, y: _pair_stats(x, y, sketch_size)
    )(jnp.asarray(a), jnp.asarray(b))
    return np.asarray(c), np.asarray(t)


# Interpret-mode tracing of this kernel is expensive (K_pad=1024 =>
# 128 unrolled lane columns; the range_skip variant adds 1024 pl.when
# branches), so the full parity matrix lives in the slow tier; the
# default tier keeps edge_rows (both variants) + one random-matrix
# case as the per-commit smoke parity.
@pytest.mark.parametrize("range_skip", [
    False, pytest.param(True, marks=pytest.mark.slow)])
@pytest.mark.slow
@pytest.mark.parametrize("n_pairs,width", [
    (21, 256), (130, 256), (64, 1024)])
def test_pairlist_matches_xla(n_pairs, width, range_skip):
    """Random-list parity across widths. Slow tier: each (shape,
    width) pays a ~5 s interpret-mode trace regardless of pair count;
    tier-1 parity for this kernel lives in test_pairlist_edge_rows and
    test_blocked_pair_axis_boundaries (width 128, shared traces)."""
    rng = np.random.default_rng(n_pairs)
    mat = _rand_sketches(rng, 80, width)
    # overlapping families so commons are non-trivial
    for i in range(0, 80, 4):
        mat[i + 1, : width // 2] = mat[i, : width // 2]
        mat[i + 1].sort()
    pi = rng.integers(0, 80, size=n_pairs)
    pj = rng.integers(0, 80, size=n_pairs)
    a, b = mat[pi], mat[pj]
    want_c, want_t = _xla_pairs(a, b, width)
    got_c, got_t = pair_stats_pairs_pallas(
        jnp.asarray(a), jnp.asarray(b), width, interpret=True,
        range_skip=range_skip)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)


@pytest.mark.parametrize("range_skip", [
    False,
    # the skip variant costs ~3x in interpret mode and its default is
    # decided (OFF, 2026-08-01 hardware data) — slow tier keeps the
    # coverage without taxing the default loop
    pytest.param(True, marks=pytest.mark.slow),
])
def test_pairlist_edge_rows(range_skip):
    """Empty rows, identical rows, all-sentinel pads, tiny batch."""
    rng = np.random.default_rng(3)
    width = 128
    mat = _rand_sketches(rng, 8, width)
    mat[2] = np.uint64(SENTINEL)            # empty
    mat[5] = mat[4]                         # identical pair
    pi = np.array([0, 2, 4, 5, 2])
    pj = np.array([1, 3, 5, 5, 2])
    a, b = mat[pi], mat[pj]
    want_c, want_t = _xla_pairs(a, b, width)
    got_c, got_t = pair_stats_pairs_pallas(
        jnp.asarray(a), jnp.asarray(b), width, interpret=True,
        range_skip=range_skip)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)


@pytest.mark.slow
def test_pairlist_respects_sketch_size_cap():
    """sketch_size below the row width caps `total` identically."""
    rng = np.random.default_rng(11)
    width = 256
    mat = _rand_sketches(rng, 16, width)
    pi = rng.integers(0, 16, size=40)
    pj = rng.integers(0, 16, size=40)
    a, b = mat[pi], mat[pj]
    want_c, want_t = _xla_pairs(a, b, 100)
    got_c, got_t = pair_stats_pairs_pallas(
        jnp.asarray(a), jnp.asarray(b), 100, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)


@pytest.mark.parametrize("n_pairs", [7, 8, 9])
def test_blocked_pair_axis_boundaries(n_pairs):
    """P-1 / P / P+1 pairs at the default block (P=8): the pair-axis
    sentinel padding must fill partial blocks without leaking into
    real outputs, and a full block plus one must spill into a second
    grid step correctly."""
    rng = np.random.default_rng(40 + n_pairs)
    width = 128
    mat = _rand_sketches(rng, 12, width)
    pi = rng.integers(0, 12, size=n_pairs)
    pj = rng.integers(0, 12, size=n_pairs)
    a, b = mat[pi], mat[pj]
    want_c, want_t = _xla_pairs(a, b, width)
    got_c, got_t = pair_stats_pairs_pallas(
        jnp.asarray(a), jnp.asarray(b), width, interpret=True,
        block_pairs=8)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)


# Default tier already covers the production P=8 blocked kernel
# (boundaries above + the random-matrix case); the cross-P sweep is
# tracing-bound in interpret mode, so it rides the slow tier.
@pytest.mark.slow
@pytest.mark.parametrize("block_pairs", [1, 2, 4, 8])
def test_blocked_matches_xla_across_block_sizes(block_pairs):
    """Every supported P yields the same integers (P=1 is the retired
    round-5 one-pair grid; a ragged 13-pair list is partial for every
    P here)."""
    rng = np.random.default_rng(50 + block_pairs)
    width = 256
    mat = _rand_sketches(rng, 20, width)
    mat[4] = np.uint64(SENTINEL)            # empty row in the list
    pi = rng.integers(0, 20, size=13)
    pj = rng.integers(0, 20, size=13)
    a, b = mat[pi], mat[pj]
    want_c, want_t = _xla_pairs(a, b, width)
    got_c, got_t = pair_stats_pairs_pallas(
        jnp.asarray(a), jnp.asarray(b), width, interpret=True,
        block_pairs=block_pairs)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)


def test_block_env_knob(monkeypatch):
    """GALAH_TPU_PAIRLIST_BLOCK tunes P; it is resolved OUTSIDE the jit
    cache so a change takes effect on the next call."""
    from galah_tpu.ops.pallas_pairlist import (
        PAIRLIST_BLOCK_DEFAULT,
        pairlist_block_pairs,
    )

    monkeypatch.delenv("GALAH_TPU_PAIRLIST_BLOCK", raising=False)
    assert pairlist_block_pairs() == PAIRLIST_BLOCK_DEFAULT
    monkeypatch.setenv("GALAH_TPU_PAIRLIST_BLOCK", "4")
    assert pairlist_block_pairs() == 4
    monkeypatch.setenv("GALAH_TPU_PAIRLIST_BLOCK", "0")
    assert pairlist_block_pairs() == 1


def test_wired_sparse_batch_path_interpret():
    """The production wiring (pair_stats_for_pairs with the pallas
    route, batch pad/trim included) matches the XLA route — interpret
    mode stands in for Mosaic on the CPU mesh."""
    from galah_tpu.ops.sparse_device import pair_stats_for_pairs

    rng = np.random.default_rng(21)
    # width 128 (one lane quantum) keeps the interpret-mode trace
    # cheap; 56 pairs / batch 48 gives two batches, the second ragged,
    # covering the pad/trim seam
    mat = _rand_sketches(rng, 60, 128)
    pi = rng.integers(0, 60, size=56)
    pj = rng.integers(0, 60, size=56)
    c_xla, t_xla = pair_stats_for_pairs(mat, pi, pj, 128,
                                        use_pallas=False)
    c_pl, t_pl = pair_stats_for_pairs(mat, pi, pj, 128,
                                      use_pallas=True, interpret=True,
                                      batch=48)
    np.testing.assert_array_equal(c_pl, c_xla)
    np.testing.assert_array_equal(t_pl, t_xla)
