"""Blocked marker-containment screening (ops/pairwise.screen_pairs).

The skani-equivalent candidate screen (reference: src/skani.rs:54-70)
must (a) match a straightforward numpy reference on containment
semantics, (b) agree between the single-device and column-sharded
implementations, and (c) issue ONE device dispatch per row block —
the O((N/tile)^2)-dispatch host loop it replaced is the pattern the
round-1 review flagged as latency-bound.
"""

import numpy as np
import pytest

from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops import pairwise
from galah_tpu.parallel import make_mesh
from galah_tpu.parallel.mesh import sharded_screen_pairs


def _marker_fixture(n=50, m=128, seed=5):
    """Random sorted marker rows with planted high-containment pairs."""
    rng = np.random.default_rng(seed)
    mat = np.full((n, m), np.uint64(SENTINEL), dtype=np.uint64)
    counts = np.zeros(n, dtype=np.int64)
    for i in range(n):
        cnt = int(rng.integers(m // 2, m))
        vals = rng.choice(1 << 20, size=cnt, replace=False).astype(
            np.uint64) * 7919
        mat[i, :cnt] = np.sort(vals)
        counts[i] = cnt
    # plant: 12 subset of 3 (full containment), 30 shares 90% with 8
    sub = mat[3, :counts[3] // 2].copy()
    mat[12] = np.uint64(SENTINEL)
    mat[12, :sub.shape[0]] = sub
    counts[12] = sub.shape[0]
    take = int(counts[8] * 0.9)
    shared = mat[8, :take]
    extra = (np.arange(counts[30] - take, dtype=np.uint64) * 7919 + 3)
    row = np.sort(np.concatenate([shared, extra]))
    mat[30] = np.uint64(SENTINEL)
    mat[30, :row.shape[0]] = row
    return mat, counts


def _numpy_screen(mat, counts, c_floor):
    n = mat.shape[0]
    out = []
    for i in range(n):
        a = mat[i, :counts[i]]
        for j in range(i + 1, n):
            b = mat[j, :counts[j]]
            inter = np.intersect1d(a, b).shape[0]
            denom = min(counts[i], counts[j])
            if denom > 0 and inter >= c_floor * denom:
                out.append((i, j))
    return out


@pytest.mark.parametrize("c_floor", [0.5, 0.8**15])
def test_screen_pairs_matches_numpy(c_floor):
    mat, counts = _marker_fixture()
    got = pairwise.screen_pairs(mat, counts, c_floor, row_tile=16,
                                col_tile=32, mesh=make_mesh(1))
    assert got == _numpy_screen(mat, counts, c_floor)
    assert (3, 12) in got  # planted full-containment pair


def test_sharded_screen_pairs_matches_single_device():
    mat, counts = _marker_fixture(n=70, seed=9)
    c_floor = 0.6
    ref = pairwise.screen_pairs(mat, counts, c_floor, row_tile=16,
                                col_tile=32, mesh=make_mesh(1))
    got = sharded_screen_pairs(mat, counts, c_floor, mesh=make_mesh(8),
                               row_tile=16, col_tile=32)
    assert got == ref


def test_screen_dispatch_count_scales_with_row_blocks(monkeypatch):
    """One device dispatch per row block: N=128 rows at row_tile=32 must
    issue exactly 4 dispatches (not the 16+ a per-tile loop would)."""
    calls = []
    real = pairwise._rowblock_screen

    def counting(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(pairwise, "_rowblock_screen", counting)
    mat, counts = _marker_fixture(n=128, seed=2)
    pairwise.screen_pairs(mat, counts, 0.8, row_tile=32, col_tile=32,
                          mesh=make_mesh(1))
    assert len(calls) == 128 // 32


@pytest.mark.slow
def test_skani_preclusterer_uses_blocked_screen(ref_data):
    """The backend end-to-end: screening via the blocked path still finds
    the known closely-related abisko4 MAG pairs."""
    from galah_tpu.backends.fragment_backend import SkaniPreclusterer

    paths = [
        str(ref_data / "abisko4" / n) for n in (
            "73.20120800_S1X.13.fna",
            "73.20120600_S2D.19.fna",
            "73.20120700_S3X.12.fna",
            "73.20110800_S2D.13.fna",
        )
    ]
    pre = SkaniPreclusterer(threshold=0.95, min_aligned_fraction=0.15)
    cache = pre.distances(paths)
    # the 95%-ANI golden cluster [[0,1,3],[2]] implies 0-1, 0-3, 1-3 hits
    assert cache.contains((0, 1))
    assert cache.contains((0, 3))
    assert cache.contains((1, 3))


def test_screen_pairs_pallas_interpret_matches_xla(monkeypatch):
    """screen_pairs with the Mosaic intersect kernel (interpret mode on
    the CPU mesh) must equal the XLA searchsorted path exactly."""
    import galah_tpu.ops.pallas_pairwise as pp

    orig = pp.tile_intersect_pallas
    monkeypatch.setattr(
        pp, "tile_intersect_pallas",
        lambda rows, cols, interpret=False: orig(rows, cols,
                                                 interpret=True))
    mat, counts = _marker_fixture(n=60, seed=13)
    via_pallas = pairwise.screen_pairs(
        mat, counts, 0.6, row_tile=16, col_tile=32,
        mesh=make_mesh(1), use_pallas=True)
    via_xla = pairwise.screen_pairs(
        mat, counts, 0.6, row_tile=16, col_tile=32,
        mesh=make_mesh(1), use_pallas=False)
    assert via_pallas == via_xla


def test_sparse_marker_screen_matches_dense():
    """The CPU inverted-index marker screen returns exactly the tiled
    XLA screen's pairs on family-structured marker sets (runs in a
    single-device subprocess; the suite itself holds 8 devices)."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.ops.pairwise import screen_pairs

assert jax.device_count() == 1
rng = np.random.default_rng(51)
n, m_width = 1100, 64
n_fam = 90
base = rng.integers(0, 1 << 62, size=(n_fam, m_width), dtype=np.uint64)
mat = np.full((n, m_width), np.uint64(SENTINEL), dtype=np.uint64)
counts = np.zeros(n, dtype=np.int64)
for i in range(n):
    fam = i % n_fam
    cnt = int(rng.integers(20, m_width))
    row = base[fam, :cnt].copy()
    n_mut = int(rng.integers(0, 10))
    idx = rng.choice(cnt, size=n_mut, replace=False)
    row[idx] = rng.integers(0, 1 << 62, size=n_mut, dtype=np.uint64)
    mat[i, :cnt] = np.sort(row)
    counts[i] = cnt
mat[5] = np.uint64(SENTINEL)   # zero-marker genome
counts[5] = 0

sparse = screen_pairs(mat, counts, 0.8 ** 15)
os.environ["GALAH_TPU_DENSE_PAIRS"] = "1"
dense = screen_pairs(mat, counts, 0.8 ** 15)
assert sorted(sparse) == sorted(dense), (
    len(sparse), len(dense),
    set(map(tuple, sparse)) ^ set(map(tuple, dense)))
assert len(dense) > 100
assert not any(5 in p for p in dense)  # zero-marker genome never pairs
print("OK")
"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=300,
                          cwd=repo, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_marker_screen_picks_mosaic_only_for_widths_it_compiles(
        monkeypatch):
    """On a TPU backend the marker screen defaults to the Mosaic kernel
    only up to MAX_DEFAULT_WIDTH markers per row: the kernel's compile
    time grows with the square of the width, and real MAGs carry ~8k
    markers. Wider rows take the host collision screen, not the XLA
    twin; off the TPU the dense screen stays the default."""
    import galah_tpu.ops.hll as hll
    from galah_tpu.ops.pairwise import (_dense_marker_screen_default,
                                        _marker_pallas_default)
    from galah_tpu.ops.pallas_pairwise import MAX_DEFAULT_WIDTH

    monkeypatch.setattr(hll, "use_pallas_default", lambda: True)
    assert _marker_pallas_default(MAX_DEFAULT_WIDTH)
    assert not _marker_pallas_default(MAX_DEFAULT_WIDTH + 64)
    assert _dense_marker_screen_default(MAX_DEFAULT_WIDTH)
    assert not _dense_marker_screen_default(MAX_DEFAULT_WIDTH + 64)
    monkeypatch.setattr(hll, "use_pallas_default", lambda: False)
    assert not _marker_pallas_default(64)
    assert _dense_marker_screen_default(MAX_DEFAULT_WIDTH + 64)


def test_wide_markers_on_tpu_take_the_host_collision_screen(monkeypatch):
    """Rows wider than the Mosaic kernel compiles for, on a TPU, are
    screened on the host: same pairs as numpy, no device tile."""
    import galah_tpu.ops.hll as hll
    from galah_tpu.ops.pallas_pairwise import MAX_DEFAULT_WIDTH
    from galah_tpu.utils import timing

    mat, counts = _marker_fixture(n=40, m=MAX_DEFAULT_WIDTH + 64)
    monkeypatch.setattr(hll, "use_pallas_default", lambda: True)
    monkeypatch.setattr(pairwise, "_screen_pairs_single", None)
    before = timing.GLOBAL.counters().get("screen-candidates", 0)
    got = pairwise.screen_pairs(mat, counts, 0.5)
    assert timing.GLOBAL.counters().get("screen-candidates", 0) > before
    assert sorted(got) == _numpy_screen(mat, counts, 0.5)
    assert (3, 12) in got
