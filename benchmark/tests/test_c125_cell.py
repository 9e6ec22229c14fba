"""The cell `species95c125.skewed`: Galah's own species job, skani at
FracMinHash c=125 (`species95_skani_c125`), on a catalogue with skewed
species sizes (`skewed`). It resolves from files and the manifest
alone, runs end to end and traced through the whole harness on the
CPU, on a test-only traffic of the same shape (tests/traffic/
tiny_skewed.json), and its check sees a job run at c=1 under the c=125
label."""

import time
from pathlib import Path

import pytest

import corpus
import harness

pytest.importorskip("galah_tpu")

CELL = "species95c125.skewed"
TINY = Path(__file__).parent / "traffic" / "tiny_skewed.json"
READERS = ("sampled_ani.host_ms_per_direction",
           "sampled_ani.kernel_roofline",
           "sampled_profile.genome_ms_per_mbp",
           "sampled_job.device_idle_share")
# read from the device trace: a CPU profile has no chip plane
DEVICE_READERS = {"sampled_ani.kernel_roofline",
                  "sampled_job.device_idle_share"}


@pytest.fixture(scope="module")
def counter():
    return harness.configure_jax()


def _cell():
    cell = harness.resolve_cell(harness.load_manifest(), CELL)
    cell.traffic = corpus.load_traffic(TINY)
    return cell


def _run(counter, trace=False, seed=2**33 + 125):
    return harness.run_cell(_cell(), seed, 0.3, trace, time.monotonic(),
                            counter, threads=2)


def test_cell_resolves_with_its_configuration_and_metrics():
    cell = harness.resolve_cell(harness.load_manifest(), CELL)
    harness.check_config(cell.config)
    assert cell.chips == 1
    assert cell.config["widths"]["ani_subsample"] == 125
    assert cell.config["reduced"] == ["genomes_per_job"]
    assert {m["name"] for m in cell.end_to_end} == {
        "genomes_per_s", "peak_host_rss_mb", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    plan = corpus.species_plan(cell.traffic)
    sizes = [sp["group_size"] for sp in plan]
    assert len(plan) == 14 and sum(sizes) == 192
    assert sum(s * (s - 1) // 2 for s in sizes) == 1696


def test_argv_asks_for_the_configured_sample():
    argv = harness.cluster_argv(_cell().config, {"genome_list": "g",
                                                 "quality": "q"},
                                Path("out"), 1, None)
    assert argv[argv.index("--ani-subsample") + 1] == "125"


def test_cell_end_to_end(counter):
    res = _run(counter)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"genomes_per_s", "peak_host_rss_mb",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_cell_traced_reads_every_new_metric(counter):
    res = _run(counter, trace=True, seed=2**33 + 126)
    assert res["correct"] is True, res["check"]
    for name in READERS:
        if name in DEVICE_READERS:
            assert name not in res["metrics"]
        else:
            assert res["metrics"][name]["value"] > 0, name


def test_job_run_at_c1_under_the_c125_label_is_caught(counter,
                                                      monkeypatch):
    orig = harness.cluster_argv

    def at_c1(*a, **kw):
        argv = orig(*a, **kw)
        argv[argv.index("--ani-subsample") + 1] = "1"
        return argv

    monkeypatch.setattr(harness, "cluster_argv", at_c1)
    res = _run(counter, seed=2**33 + 127)
    assert res["correct"] is False
    assert res["check"]["precluster_ani_gap"]["value"] > \
        res["check"]["precluster_ani_gap"]["limit"]
