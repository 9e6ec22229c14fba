"""Native C pair-stats kernel: parity with the numpy Mash reference and
the device extraction (reference analog: the compiled pair loop of
src/finch.rs:53-73)."""

import numpy as np
import pytest

from galah_tpu.ops.constants import SENTINEL

cps = pytest.importorskip("galah_tpu.ops._cpairstats")


def _mat(n, k, seed, ragged=False):
    rng = np.random.default_rng(seed)
    mat = rng.integers(0, 1 << 62, size=(n, k), dtype=np.uint64)
    mat.sort(axis=1)
    # plant near-duplicate rows so some pairs pass the threshold
    mat[3] = mat[0]
    if n > 7:
        mat[7, : k - 5] = mat[2, : k - 5]
        mat[7].sort()
    if ragged:
        mat[1, k // 2:] = np.uint64(SENTINEL)
        mat[5, 10:] = np.uint64(SENTINEL)
    return mat


@pytest.mark.parametrize("ragged", [False, True])
def test_c_matches_numpy_reference(ragged):
    from galah_tpu.ops import minhash_np

    k_sketch, kmer = 64, 21
    mat = _mat(12, k_sketch, seed=4, ragged=ragged)
    got = cps.threshold_pairs_c(mat, k_sketch, kmer, 0.9, threads=2)
    assert got, "planted duplicates must pass"
    for i in range(12):
        for j in range(i + 1, 12):
            ha = mat[i][mat[i] != np.uint64(SENTINEL)]
            hb = mat[j][mat[j] != np.uint64(SENTINEL)]
            a = minhash_np.MinHashSketch(ha, k_sketch, kmer)
            b = minhash_np.MinHashSketch(hb, k_sketch, kmer)
            ani = minhash_np.mash_ani(a, b)
            if ani >= 0.9:
                assert (i, j) in got
                assert got[(i, j)] == pytest.approx(ani, abs=1e-12)
            else:
                assert (i, j) not in got


def test_c_matches_device_extraction():
    from galah_tpu.ops.pairwise import threshold_pairs

    k_sketch, kmer = 128, 21
    mat = _mat(16, k_sketch, seed=9)
    got_c = cps.threshold_pairs_c(mat, k_sketch, kmer, 0.95)
    got_dev = threshold_pairs(mat, k=kmer, min_ani=0.95,
                              sketch_size=k_sketch)
    assert set(got_c) == set(got_dev)
    for key, ani in got_c.items():
        assert ani == pytest.approx(float(got_dev[key]), abs=1e-5)


def test_c_overflow_regrows():
    """A tiny initial capacity forces the overflow-retry path; the
    result must still be complete."""
    k_sketch = 32
    rng = np.random.default_rng(1)
    row = np.sort(rng.integers(0, 1 << 62, size=k_sketch,
                               dtype=np.uint64))
    mat = np.tile(row, (64, 1))  # all 2016 pairs pass
    got = cps.threshold_pairs_c(mat, k_sketch, 21, 0.95, initial_cap=8)
    assert len(got) == 64 * 63 // 2
    assert all(v == pytest.approx(1.0) for v in got.values())
    full = cps.threshold_pairs_c(mat, k_sketch, 21, 0.95)
    assert got == full


def test_c_empty_sketch_rows_never_pair():
    """Two all-SENTINEL rows (empty sketches) are not emitted, matching
    the device extraction's behavior on degenerate genomes."""
    k_sketch = 16
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 1 << 62, size=(4, k_sketch), dtype=np.uint64)
    mat.sort(axis=1)
    mat[1] = np.uint64(SENTINEL)
    mat[2] = np.uint64(SENTINEL)
    got = cps.threshold_pairs_c(mat, k_sketch, 21, 0.0)
    assert (1, 2) not in got


def test_threshold_pairs_c_path_single_device(tmp_path):
    """On a single-device CPU runtime with no knobs pinned,
    threshold_pairs takes the C fast path and agrees with the XLA path.
    Runs in a subprocess because the suite itself uses an 8-device
    virtual mesh."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from galah_tpu.ops.pairwise import threshold_pairs

assert jax.device_count() == 1
rng = np.random.default_rng(2)
mat = rng.integers(0, 1 << 62, size=(10, 64), dtype=np.uint64)
mat.sort(axis=1)
mat[4] = mat[1]
c_path = threshold_pairs(mat, k=21, min_ani=0.9)
xla = threshold_pairs(mat, k=21, min_ani=0.9, use_pallas=False)
assert set(c_path) == set(xla), (c_path, xla)
for key in c_path:
    assert abs(c_path[key] - xla[key]) < 1e-6
assert (1, 4) in c_path
print("OK")
"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=240,
                          cwd=repo, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_window_match_counts_matches_jax(tmp_path):
    """C membership counts equal the JAX searchsorted implementation on
    real profile windows."""
    import numpy as np

    from galah_tpu.io import read_genome
    from galah_tpu.ops import fragment_ani

    rng = np.random.default_rng(21)
    seq = "".join(rng.choice(list("ACGT"), size=30_000))
    mut = list(seq)
    for i in rng.choice(len(mut), size=600, replace=False):
        mut[i] = "ACGT"[(("ACGT".index(mut[i])) + 1) % 4]
    pa = tmp_path / "a.fna"
    pb = tmp_path / "b.fna"
    pa.write_text(f">c\n{seq}\n")
    pb.write_text(f">c\n{''.join(mut)}\n")
    q = fragment_ani.build_profile(read_genome(str(pa)), k=15,
                                   fraglen=3000)
    r = fragment_ani.build_profile(read_genome(str(pb)), k=15,
                                   fraglen=3000)

    m_c, t_c = cps.window_match_counts(q.windows(), r.ref_set)
    m_j, t_j = fragment_ani._window_match_counts(
        q.device_windows(), r.device_ref_set())
    w = q.windows().shape[0]
    np.testing.assert_array_equal(m_c, np.asarray(m_j)[:w])
    np.testing.assert_array_equal(t_c, np.asarray(t_j)[:w])

    # and the full directed result agrees through the batch entry
    out = fragment_ani.directed_ani_batch([(q, r), (r, q)])
    one = fragment_ani._directed_from_counts(
        m_c, t_c, q, 0.80, 0.5)
    assert out[0].frags_matching == one.frags_matching
    assert out[0].ani == pytest.approx(one.ani)


def test_sparse_screen_matches_dense(monkeypatch):
    """The inverted-index screened path returns exactly the dense
    result on family-structured sketches above the size cutoff."""
    rng = np.random.default_rng(33)
    n, k_sketch, kmer = 1200, 64, 21
    n_fam = 100
    base = rng.integers(0, 1 << 62, size=(n_fam, k_sketch),
                        dtype=np.uint64)
    mat = np.empty((n, k_sketch), dtype=np.uint64)
    for i in range(n):
        fam = i % n_fam
        row = base[fam].copy()
        # perturb a random subset so within-family jaccard varies
        n_mut = rng.integers(0, 20)
        idx = rng.choice(k_sketch, size=n_mut, replace=False)
        row[idx] = rng.integers(0, 1 << 62, size=n_mut, dtype=np.uint64)
        row.sort()
        mat[i] = row
    # a couple of ragged + empty rows
    mat[7, 32:] = np.uint64(SENTINEL)
    mat[11] = np.uint64(SENTINEL)
    mat.sort(axis=1)

    assert n >= cps.SPARSE_SCREEN_MIN_N
    sparse = cps.threshold_pairs_c(mat, k_sketch, kmer, 0.95)
    monkeypatch.setenv("GALAH_TPU_DENSE_PAIRS", "1")
    dense = cps.threshold_pairs_c(mat, k_sketch, kmer, 0.95)
    assert sparse == dense
    assert len(dense) > 100  # the families really do produce pairs


def test_sparse_screen_low_threshold(monkeypatch):
    """Conservativeness at a low threshold (weak screen bound): a small
    hash space forces genuine chance collisions, so partial overlaps
    near the count bound are actually exercised."""
    rng = np.random.default_rng(35)
    n, k_sketch = 1100, 32
    # 2^13 hash space, distinct within each row: cross-row collisions
    # abound, and at this threshold a single shared hash passes
    mat = np.stack([
        np.sort(rng.choice(1 << 13, size=k_sketch,
                           replace=False)).astype(np.uint64)
        for _ in range(n)
    ])
    sparse = cps.threshold_pairs_c(mat, k_sketch, 21, 0.7)
    monkeypatch.setenv("GALAH_TPU_DENSE_PAIRS", "1")
    dense = cps.threshold_pairs_c(mat, k_sketch, 21, 0.7)
    assert sparse == dense
    assert dense, "collision-rich matrix must produce passing pairs"


def test_sparse_screen_big_runs(monkeypatch):
    """Near-duplicate clusters (collision runs > _BIG_RUN genomes) take
    the dedup-group path: identical results, no O(K*m^2) blowup."""
    rng = np.random.default_rng(37)
    n, k_sketch = 1300, 48
    base = np.sort(rng.integers(0, 1 << 62, size=k_sketch,
                                dtype=np.uint64))
    mat = np.tile(base, (n, 1))
    # 200 rows perturbed lightly; the other 1100 are identical
    for i in range(200):
        row = base.copy()
        idx = rng.choice(k_sketch, size=3, replace=False)
        row[idx] = rng.integers(0, 1 << 62, size=3, dtype=np.uint64)
        row.sort()
        mat[i] = row
    sparse = cps.threshold_pairs_c(mat, k_sketch, 21, 0.9)
    monkeypatch.setenv("GALAH_TPU_DENSE_PAIRS", "1")
    dense = cps.threshold_pairs_c(mat, k_sketch, 21, 0.9)
    assert sparse == dense
    assert len(dense) >= 1100 * 1099 // 2


def test_e2e_clusters_sparse_equals_dense(tmp_path):
    """Above the screen cutoff, full cluster() compositions are
    identical with and without the sparse screen (single-device CPU
    subprocess; N > SPARSE_SCREEN_MIN_N)."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import sys
sys.path.insert(0, os.getcwd())
import bench
from galah_tpu.api import generate_galah_clusterer

paths = bench._synth_families(n_genomes=1100, genome_len=20_000,
                              n_families=275, mut=0.03, seed=43)
values = {"ani": 95.0, "precluster_ani": 90.0,
          "min_aligned_fraction": 15.0, "fragment_length": 3000,
          "precluster_method": "finch", "cluster_method": "skani",
          "threads": 1, "hash_algorithm": "tpufast",
          "ani_subsample": 16}
a = generate_galah_clusterer(paths, values).cluster()
os.environ["GALAH_TPU_DENSE_PAIRS"] = "1"
b = generate_galah_clusterer(paths, values).cluster()
assert sorted(map(sorted, a)) == sorted(map(sorted, b))
assert len(a) == 275, len(a)
print("OK", len(a))
"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "GALAH_TPU_DENSE_PAIRS")}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=repo, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_compact_windows_matches_numpy_layout():
    """The two-pass C window compaction must reproduce the numpy
    stable-argsort layout bit-for-bit (same slots, same order, same
    boundary-k-mer drops) across ragged tails and densities."""
    import numpy as np

    from galah_tpu.ops import _cpairstats
    from galah_tpu.ops.constants import SENTINEL

    rng = np.random.default_rng(51)
    for trial in range(10):
        L = int(rng.integers(8, 200))
        k = int(rng.integers(2, min(L, 32)))
        n_flat = int(rng.integers(1, 6 * L))
        w = -(-n_flat // L)
        flat = rng.integers(0, 1 << 62, size=n_flat, dtype=np.uint64)
        # subsample-style masking at random density
        keep = rng.random(n_flat) < rng.uniform(0.02, 0.4)
        flat = np.where(keep, flat, np.uint64(SENTINEL))

        # numpy reference: the subsample_c > 1 branch of windows()
        pad = np.full(w * L, np.uint64(SENTINEL), dtype=np.uint64)
        pad[:n_flat] = flat
        wins = pad.reshape(w, L).copy()
        wins[:, L - (k - 1):] = np.uint64(SENTINEL)
        order = np.argsort(wins == np.uint64(SENTINEL), axis=1,
                           kind="stable")
        wins = np.take_along_axis(wins, order, axis=1)
        counts = (wins != np.uint64(SENTINEL)).sum(axis=1)
        slots = max(int(counts.max()) if counts.size else 1, 1)
        slots = -(-slots // 64) * 64
        want = wins[:, :slots].copy()

        got = _cpairstats.compact_windows(flat, w, L, k)
        np.testing.assert_array_equal(got, want)


def test_window_match_counts_merge_parity():
    """The sorted-merge membership counter must reproduce the matrix
    walker's matched counts exactly (and the profile totals must match
    its total output) across densities, duplicates, and empty edges."""
    import numpy as np

    from galah_tpu.ops import _cpairstats
    from galah_tpu.ops.constants import SENTINEL

    rng = np.random.default_rng(52)
    for trial in range(10):
        W = int(rng.integers(1, 40))
        slots = int(rng.integers(1, 80))
        wins = rng.integers(0, 200, size=(W, slots)).astype(np.uint64)
        kill = rng.random((W, slots)) < rng.uniform(0.1, 0.9)
        wins[kill] = np.uint64(SENTINEL)
        ref = np.unique(
            rng.integers(0, 200, size=int(rng.integers(1, 150)))
        ).astype(np.uint64)

        want_m, want_t = _cpairstats.window_match_counts(wins, ref)

        mask = wins != np.uint64(SENTINEL)
        totals = mask.sum(axis=1, dtype=np.int32)
        rows, _ = np.nonzero(mask)
        qh = wins[mask]
        order = np.argsort(qh)
        got_m = _cpairstats.window_match_counts_merge(
            qh[order], rows[order].astype(np.int32), W, ref)
        np.testing.assert_array_equal(got_m, want_m)
        np.testing.assert_array_equal(totals, want_t)


def test_windows_from_pairs_matches_compact_windows():
    """The O(n_valid) pair-based window assembly (profile-walk pos
    output) is bit-identical to compact_windows on the same flat
    array — incl. boundary-crossing drops, ragged last window, and
    the slots rounding."""
    import numpy as np

    from galah_tpu.ops import _cpairstats
    from galah_tpu.ops.constants import SENTINEL

    rng = np.random.default_rng(7)
    L, k = 300, 21
    flat = rng.integers(0, 1 << 64, size=2 * L + 57, dtype=np.uint64)
    keep = rng.random(flat.shape[0]) < 0.2
    flat[~keep] = np.uint64(SENTINEL)
    w = -(-flat.shape[0] // L)

    want = _cpairstats.compact_windows(flat, w, L, k)
    pos = np.nonzero(flat != np.uint64(SENTINEL))[0].astype(np.int64)
    got = _cpairstats.windows_from_pairs(
        pos, flat[pos], w, L, k)
    np.testing.assert_array_equal(got, want)


def test_profile_via_c_pairs_path_windows_parity(tmp_path):
    """A profile built by the new positional_hashes_profile walk
    (kept pairs stored) produces the same windows()/sorted_query()
    as one forced through the compact_windows fallback."""
    import numpy as np

    from galah_tpu.io.fasta import read_genome
    from galah_tpu.ops import fragment_ani

    rng = np.random.default_rng(3)
    seq = rng.choice(list(b"ACGT"), size=50_000).astype(np.uint8)
    p = tmp_path / "g.fna"
    p.write_bytes(b">c1\n" + seq.tobytes() + b"\n")
    g = read_genome(str(p))
    prof = fragment_ani.build_profile(g, k=21, fraglen=3000,
                                      subsample_c=16)
    if prof._kept_pos is None:
        pytest.skip("C profile walk unavailable on this backend")
    wins_pairs = prof.windows()
    sq_pairs = prof.sorted_query()

    prof2 = fragment_ani.build_profile(g, k=21, fraglen=3000,
                                       subsample_c=16)
    prof2._kept_pos = None
    prof2._kept_hashes = None
    wins_flat = prof2.windows()
    np.testing.assert_array_equal(wins_pairs, wins_flat)
    for a, b in zip(sq_pairs, prof2.sorted_query()):
        np.testing.assert_array_equal(a, b)


def test_merge_counter_avx512_scalar_identity(monkeypatch):
    """The AVX-512 block merge (csrc/pairstats.c merge_count_avx512)
    must be bit-identical to the scalar walk on BOTH entry points
    (single-pair and batch) across overlap regimes, duplicate-heavy
    queries, and sub-block / odd sizes. On a CPU without AVX-512 both
    runs would take the scalar path and the A/B below would silently
    compare scalar against scalar — so probe the dispatch first and
    SKIP with the reason on hosts where the SIMD path can't run."""
    import numpy as np

    from galah_tpu.ops import _cpairstats

    monkeypatch.delenv("GALAH_TPU_NO_AVX512", raising=False)
    if not _cpairstats.merge_uses_avx512():
        pytest.skip(
            "merge counter dispatches to the scalar kernel here "
            "(no avx512f CPU support or non-AVX-512 build); the "
            "A/B identity would compare scalar against itself")

    rng = np.random.default_rng(99)
    for trial, (nq, H, overlap) in enumerate(
            [(0, 0, 0.0), (3, 5, 1.0), (7, 8, 0.5), (8, 7, 0.5),
             (64, 64, 1.0), (1000, 1000, 0.65), (2000, 16, 0.9),
             (16, 2000, 0.9), (333, 777, 0.3)]):
        nw = max(1, nq // 4)
        ref = np.unique(rng.integers(
            0, 1 << 50, size=max(2 * H, 1), dtype=np.uint64))[:H]
        n_sh = int(nq * overlap) if H else 0
        qh = np.sort(np.concatenate([
            rng.choice(ref, size=n_sh, replace=True)
            if n_sh else np.empty(0, np.uint64),
            rng.integers(0, 1 << 50, size=nq - n_sh,
                         dtype=np.uint64)]).astype(np.uint64))
        qw = rng.integers(0, nw, size=nq, dtype=np.int32)

        monkeypatch.setenv("GALAH_TPU_NO_AVX512", "1")
        want = _cpairstats.window_match_counts_merge(qh, qw, nw, ref)
        monkeypatch.delenv("GALAH_TPU_NO_AVX512")
        got = _cpairstats.window_match_counts_merge(qh, qw, nw, ref)
        np.testing.assert_array_equal(got, want, err_msg=f"trial {trial}")

    # batch entry point: 200 random pairs over 8 genomes
    ng, nq, nw = 8, 500, 25
    pool = np.unique(rng.integers(0, 1 << 50, size=2000,
                                  dtype=np.uint64))
    qhs, qws, refs = [], [], []
    for _ in range(ng):
        qh = np.sort(np.concatenate([
            rng.choice(pool, size=nq // 2, replace=True),
            rng.integers(0, 1 << 50, size=nq - nq // 2,
                         dtype=np.uint64)]).astype(np.uint64))
        qhs.append(qh)
        qws.append(rng.integers(0, nw, size=nq, dtype=np.int32))
        refs.append(np.unique(np.concatenate([
            rng.choice(pool, size=300, replace=False),
            rng.integers(0, 1 << 50, size=100, dtype=np.uint64)])
            .astype(np.uint64)))
    qh_cat, qw_cat = np.concatenate(qhs), np.concatenate(qws)
    q_off = np.arange(ng + 1, dtype=np.int64) * nq
    ref_cat = np.concatenate(refs)
    r_off = np.zeros(ng + 1, dtype=np.int64)
    np.cumsum([len(r) for r in refs], out=r_off[1:])
    n_pairs = 200
    pair_q = rng.integers(0, ng, size=n_pairs, dtype=np.int32)
    pair_r = rng.integers(0, ng, size=n_pairs, dtype=np.int32)
    m_off = np.arange(n_pairs, dtype=np.int64) * nw

    monkeypatch.setenv("GALAH_TPU_NO_AVX512", "1")
    want = _cpairstats.window_match_counts_merge_batch(
        qh_cat, qw_cat, q_off, ref_cat, r_off, pair_q, pair_r,
        m_off, n_pairs * nw, threads=2)
    monkeypatch.delenv("GALAH_TPU_NO_AVX512")
    got = _cpairstats.window_match_counts_merge_batch(
        qh_cat, qw_cat, q_off, ref_cat, r_off, pair_q, pair_r,
        m_off, n_pairs * nw, threads=2)
    np.testing.assert_array_equal(got, want)


# -- sorted exact-ANI query: C build vs the numpy twin -----------------

_SQ_K, _SQ_FRAGLEN = 21, 1000


def _sq_codes(case):
    """(codes, contig_offsets) of a genome shaped for one parity case."""
    rng = np.random.default_rng(sum(map(ord, case)))

    def acgt(n):
        return rng.integers(0, 4, size=n).astype(np.uint8)

    if case == "empty":
        parts = [np.zeros(0, dtype=np.uint8)]
    elif case == "shorter_than_k":
        parts = [acgt(_SQ_K - 1)]
    elif case == "one_partial_window":
        parts = [acgt(700)]
    elif case == "last_partial_window":
        # two contigs: k-mers spanning the contig boundary are masked
        parts = [acgt(3000), acgt(2300)]
    elif case == "sentinel_windows":
        # windows 1-3 hold only ambiguous bases: every slot SENTINEL
        parts = [np.concatenate([acgt(1000),
                                 np.full(3000, 255, dtype=np.uint8),
                                 acgt(1500)])]
    elif case == "heavy_repeats":
        # equal hashes across many windows: a tandem repeat, a
        # homopolymer run (one hash tens of thousands of times) and a
        # segment copied twice
        seg = acgt(20_000)
        parts = [np.concatenate([
            acgt(60_000), np.tile(acgt(37), 1500),
            np.zeros(30_000, dtype=np.uint8), seg, acgt(5_000), seg,
            acgt(7_777)])]
    else:
        raise ValueError(case)
    codes = np.concatenate(parts)
    offsets = np.concatenate(
        [[0], np.cumsum([p.shape[0] for p in parts])]).astype(np.int64)
    return codes, offsets


def _sq_profile(case, subsample_c, build):
    from galah_tpu.io.fasta import Genome, GenomeStats
    from galah_tpu.ops import fragment_ani as fa

    if build == "direct":
        # hashes sharing their top bits land in one radix bucket and
        # sub-bucket, so the stable merge sort orders distinct hashes
        # and ties there; all sit below 2^64/125 and survive the mask
        rng = np.random.default_rng(5)
        flat = (np.uint64(1 << 56)
                + rng.integers(0, 500, size=50_000).astype(np.uint64))
        flat[rng.random(flat.shape[0]) < 0.1] = np.uint64(SENTINEL)
        return fa.GenomeProfile(
            path=case, k=_SQ_K, fraglen=_SQ_FRAGLEN, flat_hashes=flat,
            ref_set=np.unique(flat[flat != np.uint64(SENTINEL)]),
            markers=np.zeros(0, dtype=np.uint64),
            subsample_c=subsample_c)
    codes, offsets = _sq_codes(case)
    g = Genome(path=f"{case}.fna", codes=codes, contig_offsets=offsets,
               stats=GenomeStats(len(offsets) - 1,
                                 int((codes == 255).sum()), codes.shape[0]))
    if build == "flat":
        return fa._profile_from_flat(
            g.path, fa.positional_hashes(g, _SQ_K), _SQ_K, _SQ_FRAGLEN,
            subsample_c)
    prof = fa._profile_via_c(g, _SQ_K, _SQ_FRAGLEN, subsample_c)
    # the C walk keeps (pos, hash) pairs for subsampled profiles, and
    # windows() then assembles its rows from them
    assert (prof._kept_pos is not None) == (subsample_c > 1)
    return prof


_SQ_CASES = [
    (case, c, build)
    for case in ("empty", "shorter_than_k", "one_partial_window",
                 "last_partial_window", "sentinel_windows",
                 "heavy_repeats")
    for c in (1, 125)
    for build in ("flat", "kept_pairs")
] + [("near_ties", c, "direct") for c in (1, 125)]


@pytest.mark.parametrize("case,subsample_c,build", _SQ_CASES)
def test_sorted_query_native_matches_numpy_twin(case, subsample_c, build):
    """GenomeProfile.sorted_query's C build (csrc/pairstats.c::
    galah_sorted_query, over flat_hashes) equals the numpy twin over
    windows() element for element — hashes, window ids with equal
    hashes in ascending window order, and per-window totals — for the
    flat and the kept-pairs profile builds, dense and subsampled."""
    from galah_tpu.ops import fragment_ani as fa
    from galah_tpu.utils import timing

    native = _sq_profile(case, subsample_c, build)
    twin = _sq_profile(case, subsample_c, build)
    before = timing.GLOBAL.counters().get("fragment-sorted-query-native", 0)
    got = native.sorted_query()
    assert timing.GLOBAL.counters()["fragment-sorted-query-native"] \
        == before + 1
    assert native._np_windows is None
    want = fa.sorted_query_numpy(twin.windows())
    assert got[2].shape == (native.n_windows,)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    if case == "sentinel_windows":
        assert (got[2][1:4] == 0).all() and got[2][0] > 0
    if case in ("heavy_repeats", "near_ties") and subsample_c == 1:
        # ties are exercised: some hash recurs in several windows
        qh, qw, _ = got
        tie = qh[1:] == qh[:-1]
        assert (tie & (qw[1:] != qw[:-1])).any()
