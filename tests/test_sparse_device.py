"""Device-path sparse screen: bit-identity with the dense tiled path,
sharded batch evaluation, and gate selection on device backends."""

import numpy as np
import pytest

import galah_tpu.ops.collision as collision
import galah_tpu.ops.sparse_device as sparse_device
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.pairwise import (
    _threshold_pairs_single,
    screen_pairs,
    threshold_pairs,
)
from galah_tpu.ops.sparse_device import (
    pair_stats_for_pairs,
    threshold_pairs_sparse,
)


def _family_sketches(n=1100, width=48, n_fam=80, seed=91,
                     mutations=25):
    """Family-structured sorted sketch matrix with ragged/empty rows."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 1 << 62, size=(n_fam, width), dtype=np.uint64)
    mat = np.empty((n, width), dtype=np.uint64)
    for i in range(n):
        row = base[i % n_fam].copy()
        n_mut = int(rng.integers(0, mutations))
        idx = rng.choice(width, size=n_mut, replace=False)
        row[idx] = rng.integers(0, 1 << 62, size=n_mut, dtype=np.uint64)
        row.sort()
        mat[i] = row
    mat[3, 10:] = np.uint64(SENTINEL)   # ragged
    mat[9] = np.uint64(SENTINEL)        # empty
    mat.sort(axis=1)
    return mat


def test_sparse_equals_dense_threshold_sweep():
    mat = _family_sketches()
    for thr in (0.90, 0.95, 0.99):
        dense = _threshold_pairs_single(
            mat, k=21, min_ani=thr, sketch_size=mat.shape[1],
            row_tile=64, col_tile=128, use_pallas=False, cap_per_row=64)
        sparse = threshold_pairs_sparse(mat, k=21, min_ani=thr)
        assert sparse == dense, thr


def test_sparse_batched_partial_batches():
    """A batch size that does not divide the candidate count exercises
    the pad-and-trim path; results unchanged."""
    mat = _family_sketches(n=300, n_fam=30, seed=17)
    full = threshold_pairs_sparse(mat, k=21, min_ani=0.95)
    small = threshold_pairs_sparse(mat, k=21, min_ani=0.95, batch=37)
    assert small == full
    assert len(full) > 0


def test_pair_stats_for_pairs_sharded_equals_single():
    from galah_tpu.parallel.mesh import make_mesh

    mat = _family_sketches(n=200, n_fam=20, seed=23)
    rng = np.random.default_rng(5)
    pi = rng.integers(0, 199, size=501).astype(np.int64)
    pj = np.minimum(pi + 1 + rng.integers(0, 50, size=501), 199)
    c1, t1 = pair_stats_for_pairs(mat, pi, pj, mat.shape[1])
    mesh = make_mesh()
    c2, t2 = pair_stats_for_pairs(mat, pi, pj, mat.shape[1], mesh=mesh)
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(t1, t2)


def test_no_collisions_returns_empty():
    rng = np.random.default_rng(7)
    n = 64
    mat = np.sort(
        rng.choice(1 << 62, size=(n, 32), replace=False)
        .astype(np.uint64), axis=1)
    assert threshold_pairs_sparse(mat, k=21, min_ani=0.95) == {}


def test_public_gate_selects_sparse_path(monkeypatch):
    """Above the crossover with no knobs pinned, threshold_pairs routes
    to the sparse device pipeline (with the auto mesh on a multi-device
    runtime) and returns the dense-identical result."""
    mat = _family_sketches(n=160, n_fam=16, seed=29)
    monkeypatch.setattr(collision, "SPARSE_SCREEN_MIN_N", 100)

    calls = {}
    real = sparse_device.threshold_pairs_sparse

    def spy(*args, **kwargs):
        calls["mesh"] = kwargs.get("mesh")
        calls["hit"] = True
        return real(*args, **kwargs)

    monkeypatch.setattr(sparse_device, "threshold_pairs_sparse", spy)
    got = threshold_pairs(mat, k=21, min_ani=0.95)
    assert calls.get("hit"), "sparse path must be selected"
    import jax

    if jax.device_count() > 1:
        assert calls["mesh"] is not None and calls["mesh"].devices.size > 1

    dense = _threshold_pairs_single(
        mat, k=21, min_ani=0.95, sketch_size=mat.shape[1],
        row_tile=64, col_tile=128, use_pallas=False, cap_per_row=64)
    assert got == dense


def test_public_gate_dense_env_pins_dense(monkeypatch):
    mat = _family_sketches(n=160, n_fam=16, seed=29)
    monkeypatch.setattr(collision, "SPARSE_SCREEN_MIN_N", 100)
    monkeypatch.setenv("GALAH_TPU_DENSE_PAIRS", "1")

    def boom(*a, **k):  # the sparse path must NOT be taken
        raise AssertionError("sparse path selected despite env pin")

    monkeypatch.setattr(sparse_device, "threshold_pairs_sparse", boom)
    got = threshold_pairs(mat, k=21, min_ani=0.95)
    assert len(got) > 0


def test_screen_pairs_sparse_on_any_backend(monkeypatch):
    """The marker screen's collision path is exact and now engages on
    every backend (the conftest runtime is an 8-device CPU mesh)."""
    rng = np.random.default_rng(41)
    n, m = 150, 40
    n_fam = 15
    base = rng.integers(0, 1 << 62, size=(n_fam, m), dtype=np.uint64)
    mat = np.empty((n, m), dtype=np.uint64)
    for i in range(n):
        row = base[i % n_fam].copy()
        n_mut = int(rng.integers(0, 12))
        idx = rng.choice(m, size=n_mut, replace=False)
        row[idx] = rng.integers(0, 1 << 62, size=n_mut, dtype=np.uint64)
        row.sort()
        mat[i] = row
    counts = np.full(n, m, dtype=np.int64)

    monkeypatch.setattr(collision, "SPARSE_SCREEN_MIN_N", 100)
    sparse = screen_pairs(mat, counts, 0.8)

    from galah_tpu.ops.pairwise import _screen_pairs_single

    dense = _screen_pairs_single(mat, counts, 0.8, 64, 128, 256, False)
    assert sorted(sparse) == sorted(dense)
    assert len(sparse) > 0


def test_sparse_device_equals_c_kernel_at_scale():
    """Cross-implementation equivalence on a large family matrix: the
    screened device pipeline (collision screen + gathered XLA pair
    stats) and the compiled-C merged walk (its own screen + C walk)
    must produce the identical pair dict — two independent
    implementations of the same contract."""
    cps = pytest.importorskip("galah_tpu.ops._cpairstats")

    mat = _family_sketches(n=5000, width=64, n_fam=250, seed=101,
                           mutations=30)
    via_device = threshold_pairs_sparse(mat, k=21, min_ani=0.95,
                                        sketch_size=mat.shape[1])
    via_c = cps.threshold_pairs_c(mat, mat.shape[1], 21, 0.95)
    # identical pair SETS exactly (the keep-check is rational f64 on
    # both sides); ANI values via approx — np.log and libm log are
    # independent transcendental implementations (repo precedent:
    # tests/test_cpairstats.py)
    assert set(via_device) == set(via_c)
    for key, v in via_device.items():
        assert via_c[key] == pytest.approx(v, abs=1e-12), key
    assert len(via_c) > 1000


class _LazyFail:
    """A device-future stand-in whose host materialization raises — the
    settle-time Mosaic failure shape (dispatch enqueues fine; the error
    surfaces when the ordered sync reads the buffer back)."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("injected Mosaic runtime failure (settle)")


def _inject_mosaic_failure(monkeypatch, mode, fail_at, log):
    """Patch the module-level _batch_pair_stats with a fake whose pallas
    path computes the real XLA integers (the two paths are bit-identical
    by contract) but fails on the fail_at-th pallas dispatch — raising at
    enqueue, or returning lazily-failing buffers for the settle site.
    Every call appends ("pallas"|"xla") to `log`."""
    real = sparse_device._batch_pair_stats

    def fake(jmat, pi, pj, sketch_size, use_pallas=False, interpret=False):
        exact = real(jmat, pi, pj, sketch_size=sketch_size,
                     use_pallas=False, interpret=False)
        if not use_pallas:
            log.append("xla")
            return exact
        n_before = log.count("pallas")
        log.append("pallas")
        if n_before == fail_at:
            if mode == "enqueue":
                raise RuntimeError(
                    "injected Mosaic runtime failure (enqueue)")
            return _LazyFail(), _LazyFail()
        return exact

    monkeypatch.setattr(sparse_device, "_batch_pair_stats", fake)
    import galah_tpu.ops.hll as hll

    monkeypatch.setattr(hll, "use_pallas_default", lambda: True)


def _fault_pairs(n=240, n_pairs=600, seed=3):
    mat = _family_sketches(n=n, n_fam=24, seed=seed)
    rng = np.random.default_rng(seed)
    pi = rng.integers(0, n - 1, size=n_pairs).astype(np.int64)
    pj = np.minimum(pi + 1 + rng.integers(0, 40, size=n_pairs), n - 1)
    return mat, pi, pj


@pytest.mark.parametrize("mode", ["enqueue", "settle"])
def test_mosaic_midstream_failure_propagates(monkeypatch, mode):
    """A Mosaic runtime failure mid-pipeline — at dispatch enqueue or at
    host materialization of an in-flight batch — on the default path
    propagates: no batch is quietly redone on the XLA twin, and every
    dispatch before the fault ran the Mosaic kernel."""
    mat, pi, pj = _fault_pairs()
    log = []
    _inject_mosaic_failure(monkeypatch, mode, fail_at=5, log=log)
    with pytest.raises(RuntimeError, match="injected Mosaic"):
        pair_stats_for_pairs(mat, pi, pj, mat.shape[1], batch=32)
    assert "xla" not in log
    assert log.count("pallas") >= 6  # the pipeline up to the fault


@pytest.mark.parametrize("mode", ["enqueue", "settle"])
def test_mosaic_midstream_failure_explicit_pin_raises(monkeypatch, mode):
    """With use_pallas pinned explicitly, a mid-stream Mosaic failure
    must propagate — parity tests must never silently compare XLA to
    XLA."""
    mat, pi, pj = _fault_pairs(seed=11)
    log = []
    _inject_mosaic_failure(monkeypatch, mode, fail_at=3, log=log)
    with pytest.raises(RuntimeError, match="injected Mosaic"):
        pair_stats_for_pairs(mat, pi, pj, mat.shape[1], batch=32,
                             use_pallas=True)


def test_host_strategy_matches_device_integers():
    """The host strategy for tiny survivor lists (numpy, no JAX backend
    at all — a TPU-pinned process has no CPU one) computes the same
    (common, total) integers as the device path, including
    sentinel-padded rows and identical rows."""
    mat = _family_sketches(n=40, width=48, n_fam=5, seed=23,
                           mutations=8)
    mat[3, 30:] = np.uint64(SENTINEL)
    mat[9] = mat[8]
    rng = np.random.default_rng(23)
    pi = rng.integers(0, 39, size=120).astype(np.int32)
    pj = np.minimum(pi + 1 + rng.integers(0, 10, size=120),
                    39).astype(np.int32)
    pi[:2], pj[:2] = (3, 8), (4, 9)
    want_c, want_t = pair_stats_for_pairs(mat, pi, pj, mat.shape[1],
                                          use_pallas=False)
    got_c, got_t = sparse_device._cpu_pair_stats(mat, pi, pj,
                                                 mat.shape[1])
    np.testing.assert_array_equal(got_c, want_c)
    np.testing.assert_array_equal(got_t, want_t)


@pytest.mark.parametrize("shape", ["padded", "ragged"])
def test_gather_dense_strategy_parity(shape, monkeypatch):
    """The gather-dense strategy (dense tiles over permuted survivor
    rows, ops/sparse_device._gather_dense_pair_stats) is bit-identical
    to the XLA route, for a survivor list that exactly fills the tile
    caps and for a ragged one spanning multiple row blocks and column
    pieces."""
    from galah_tpu.utils import timing

    rng = np.random.default_rng(17)
    n = 80
    mat = _family_sketches(n=n, width=48, n_fam=10, seed=17,
                           mutations=8)
    if shape == "padded":
        # one full tile: GATHER_ROWS distinct a's, each paired once
        pi = np.arange(sparse_device.GATHER_ROWS, dtype=np.int64) % n
        pj = (pi + 1) % n
    else:
        # > GATHER_ROWS unique a's (second row block) and pair counts
        # that are not multiples of anything convenient
        pi = rng.integers(0, n - 1, size=333).astype(np.int64)
        pj = np.minimum(pi + 1 + rng.integers(0, 30, size=333), n - 1)
    want_c, want_t = pair_stats_for_pairs(mat, pi, pj, mat.shape[1],
                                          use_pallas=False)
    import jax
    import jax.numpy as jnp

    timing.reset()
    got = sparse_device._gather_dense_pair_stats(
        jax.device_put(jnp.asarray(mat)),
        pi.astype(np.int32), pj.astype(np.int32), mat.shape[1],
        interpret=True)
    np.testing.assert_array_equal(got[0], want_c)
    np.testing.assert_array_equal(got[1], want_t)
    counters = timing.GLOBAL.counters()
    assert counters["pairlist-gather-used"] == pi.shape[0]
    assert counters["pairlist-gather-cells"] >= pi.shape[0]


@pytest.mark.slow
def test_strategy_env_pins_every_route(monkeypatch):
    """GALAH_TPU_PAIRLIST_STRATEGY pins each route end-to-end through
    pair_stats_for_pairs with identical integers, and the decision
    counter records the pick. Slow tier: three interpret-mode kernel
    traces; tier-1 keeps per-route bit-identity (boundaries/gather
    parity tests) and the AUTO selection test below."""
    from galah_tpu.utils import timing

    mat = _family_sketches(n=90, width=48, n_fam=9, seed=23,
                           mutations=8)
    rng = np.random.default_rng(23)
    # 40 pairs: enough for multiple blocked grid steps while keeping
    # the interpret-mode grid walk short (gather parity across tile
    # shapes is pinned separately above)
    pi = rng.integers(0, 89, size=40).astype(np.int64)
    pj = np.minimum(pi + 1 + rng.integers(0, 20, size=40), 89)
    monkeypatch.delenv("GALAH_TPU_PAIRLIST_STRATEGY", raising=False)
    want_c, want_t = pair_stats_for_pairs(mat, pi, pj, mat.shape[1],
                                          use_pallas=False)
    for strat in ("cpu", "gather", "blocked"):
        monkeypatch.setenv("GALAH_TPU_PAIRLIST_STRATEGY", strat)
        timing.reset()
        got_c, got_t = pair_stats_for_pairs(
            mat, pi, pj, mat.shape[1], use_pallas=True, interpret=True)
        np.testing.assert_array_equal(got_c, want_c)
        np.testing.assert_array_equal(got_t, want_t)
        counters = timing.GLOBAL.counters()
        assert counters.get(f"pairlist-strategy-{strat}") == 1, strat


def test_auto_strategy_selection_regimes(monkeypatch):
    """The AUTO heuristic (ops/sparse_device._resolve_pairlist_strategy)
    picks cpu for tiny lists, gather only for duplication-heavy lists
    whose planned tile fill clears the rate crossover, blocked
    otherwise — and never deviates when the caller pinned a shape."""
    monkeypatch.delenv("GALAH_TPU_PAIRLIST_STRATEGY", raising=False)
    resolve = sparse_device._resolve_pairlist_strategy

    tiny = np.arange(10, dtype=np.int32)
    assert resolve(tiny, tiny + 1, True, False, None, None) == "cpu"
    assert resolve(tiny, tiny + 1, False, False, None, None) == "xla"
    # caller pins (explicit use_pallas / batch) keep the batched path
    assert resolve(tiny, tiny + 1, True, True, None, None) == "blocked"
    assert resolve(tiny, tiny + 1, True, False, None, 64) == "blocked"

    # low duplication at scale: scattered pairs over many rows
    rng = np.random.default_rng(7)
    pi = np.arange(2000, dtype=np.int32)
    pj = (pi + 1 + rng.integers(0, 5, size=2000).astype(np.int32))
    assert resolve(pi, pj, True, False, None, None) == "blocked"

    # 32-member family cliques: dup ~15.5 but each planned tile is
    # only ~12% full — not enough to beat the blocked kernel's design
    # rate, so AUTO stays blocked despite the duplication
    m = 32
    ii, jj = np.meshgrid(np.arange(m, dtype=np.int32),
                         np.arange(m, dtype=np.int32), indexing="ij")
    keep = ii < jj
    cpi = np.concatenate([ii[keep] + f * m for f in range(8)])
    cpj = np.concatenate([jj[keep] + f * m for f in range(8)])
    assert resolve(cpi, cpj, True, False, None, None) == "blocked"
    # ...unless the blocked kernel were slow enough that even 12%-full
    # dense tiles out-run it (rate crossover is live, not vestigial)
    monkeypatch.setattr(sparse_device, "BLOCKED_RATE_EST", 20_000.0)
    assert resolve(cpi, cpj, True, False, None, None) == "gather"
    monkeypatch.setattr(sparse_device, "BLOCKED_RATE_EST", 200_000.0)

    # dense bipartite block (GATHER_ROWS x GATHER_COLS all-pairs):
    # fill 1.0 — the regime gather-dense exists for
    ga = np.repeat(np.arange(sparse_device.GATHER_ROWS,
                             dtype=np.int32),
                   sparse_device.GATHER_COLS)
    gb = np.tile(np.arange(sparse_device.GATHER_COLS,
                           dtype=np.int32)
                 + sparse_device.GATHER_ROWS,
                 sparse_device.GATHER_ROWS)
    assert resolve(ga, gb, True, False, None, None) == "gather"

    monkeypatch.setenv("GALAH_TPU_PAIRLIST_STRATEGY", "gather")
    assert resolve(tiny, tiny + 1, True, False, None, None) == "gather"


def test_dispatch_counters_recorded(monkeypatch):
    """The sparse device pipeline records disp/sync counters under the
    active stage — the per-stage round-trip visibility the TPU e2e
    analysis relies on (utils/timing.dispatch)."""
    from galah_tpu.utils import timing

    mat = _family_sketches(n=64, width=48, n_fam=8, mutations=6)
    monkeypatch.setenv("GALAH_TPU_SPARSE_MIN_N", "2")
    timing.reset()
    with timing.stage("unit-pairwise"):
        threshold_pairs_sparse(mat, k=21, min_ani=0.90)
    counters = timing.GLOBAL.counters()
    assert counters.get("disp[unit-pairwise]", 0) >= 1
    assert counters.get("sync[unit-pairwise]", 0) >= 1
    assert counters["screen-candidates"] >= counters["screen-kept-pairs"]
