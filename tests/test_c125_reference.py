"""Galah's own species job — skani preclustering and skani ANI at 95%
on a FracMinHash sample of c (Galah runs c=125) — through
`galah_tpu.cli.main(["cluster", ...])` against the plain reference
(benchmark/reference.py, which imports nothing of galah_tpu), on a small
seeded catalogue with skewed species sizes: one species of 4, one of 2,
two singletons, ~300 kb genomes.

The job's TSV rows, its representatives and every precluster pair with
its ANI (from the job's checkpoint) must equal the reference's, ANI to
1e-9, under the C merge and under the Pallas kernel (interpret mode on
the CPU), pinned with GALAH_TPU_FRAGMENT_STRATEGY. The job's
`profile-kept-kmers` counter must equal the reference's count of
sampled query positions."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import corpus  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

from galah_tpu import cli  # noqa: E402

TRAFFIC = {
    "species": [
        {"count": 1, "strain_groups": 1, "group_size": 4,
         "group_rate": 0.0, "member_rate": 0.01},
        {"count": 1, "strain_groups": 1, "group_size": 2,
         "group_rate": 0.0, "member_rate": 0.01},
        {"count": 2, "strain_groups": 1, "group_size": 1,
         "group_rate": 0.0, "member_rate": 0.01},
    ],
    "length": {"median_bp": 300_000, "sigma": 0.3,
               "min_bp": 200_000, "max_bp": 450_000},
    "contig_bp": 50_000,
    "completeness": [50.0, 100.0],
    "contamination": [0.0, 10.0],
}
SEED = 2**31 + 27
ANI_GAP = 1e-9
# the benchmark's configuration of Galah's job, and its test-only c=16
# twin
CONFIGS = {125: BENCH / "configs" / "species95_skani_c125.json",
           16: BENCH / "tests" / "configs" / "species95_skani_c16.json"}


def _config(c):
    config = json.loads(CONFIGS[c].read_text())
    harness.check_config(config)
    assert reference.ani_subsample(config) == c
    return config


@pytest.fixture(scope="module")
def catalogue():
    return corpus.generate(TRAFFIC, SEED, 1)


@pytest.fixture(scope="module")
def references(catalogue):
    """The reference's result of the catalogue, per c."""
    records, contigs = harness.reference_inputs(catalogue)
    out = {}
    for c in (16, 125):
        job = reference.Job(_config(c), records, contigs, threads=2)
        try:
            out[c] = job.solve()
        finally:
            job.close()
    return out


def _run_job(catalogue, c, tmp_path):
    files = corpus.write(catalogue, tmp_path / "input")
    out = tmp_path / "output"
    out.mkdir()
    argv = harness.cluster_argv(_config(c), files, out, 2, None)
    assert argv[argv.index("--ani-subsample") + 1] == str(c)
    assert cli.main(argv) == 0
    rows, pre, _clusterer, report = harness.read_outputs(out, [])
    return rows, pre, report


@pytest.fixture(scope="module")
def jobs(catalogue, tmp_path_factory):
    """The job's outputs per (c, strategy), run once each."""
    done = {}

    def get(c, strategy):
        if (c, strategy) not in done:
            mp = pytest.MonkeyPatch()
            try:
                mp.setenv("GALAH_TPU_FRAGMENT_STRATEGY", strategy)
                mp.delenv("GALAH_TPU_CACHE", raising=False)
                done[(c, strategy)] = _run_job(
                    catalogue, c,
                    tmp_path_factory.mktemp(f"c{c}_{strategy}"))
            finally:
                mp.undo()
        return done[(c, strategy)]

    return get


@pytest.mark.parametrize("strategy", ["c", "pallas"])
@pytest.mark.parametrize("c", [16, 125])
def test_job_matches_the_reference(c, strategy, jobs, references,
                                   catalogue):
    rows, pre, report = jobs(c, strategy)
    ref = references[c]
    assert report["counters"][f"fragment-strategy-{strategy}"] > 0
    assert rows == ref.rows()
    assert [r for r, m in rows if r == m] == [cl[0] for cl in
                                              ref.clusters]
    # the planted species are the clusters: pairs link genomes within
    # a species (6 + 1 of them), none across
    assert {frozenset(cl) for cl in ref.clusters} == set(
        catalogue.truth("species"))
    assert set(pre) == set(ref.pre) and len(pre) == 6 + 1
    for pair, v in ref.pre.items():
        assert pre[pair] == pytest.approx(v, abs=ANI_GAP), pair


def test_kept_kmers_count_the_reference_query_positions(jobs,
                                                        references):
    _rows, _pre, report = jobs(125, "c")
    sizes = references[125].sizes
    kept = report["counters"]["profile-kept-kmers"]
    assert kept == sum(q for q, _distinct in sizes.values())
    # about 1/125 of the bases, as the sample promises
    assert 0.5 < kept * 125 / report["counters"]["profile-bp"] < 2.0
