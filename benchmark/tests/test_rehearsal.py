"""Tiny CPU rehearsals of each cell through the whole run (set-up,
window, check, metric readers), with test-only traffic files; and the
same runs with the timed path broken underneath, which the check must
catch. The harness's look for a chip is skipped: `run_cell` is called
directly, as `run.py` calls it once it has found the chip.

`species95_c16.tiny` is a test-only cell made of files alone (the
configuration under tests/configs/, the traffic under tests/traffic/,
and a manifest entry): the species job with its exact fragment ANI at
FracMinHash compression 16, checked against the reference's own
subsample."""

import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

import calibrate
import harness

pytest.importorskip("galah_tpu")

TESTS = Path(__file__).parent
TRAFFIC = TESTS / "traffic"
C16 = "species95_c16.tiny"
TINY = {"species95.families": "tiny_families.json",
        "strain99.bigfamily": "tiny_bigfamily.json",
        C16: "tiny_families.json"}


@pytest.fixture(scope="module")
def counter():
    return harness.configure_jax()


def _manifest_with_c16():
    """BENCHMARK.json with the test-only subsampled cell added as a
    later PR would add one: a configuration, a cell, and the cell named
    in the per-layer metrics of the species cell."""
    m = harness.load_manifest()
    m["configs"].append({"name": "species95_skani_c16", "source": "x",
                         "file": "benchmark/tests/configs/"
                                 "species95_skani_c16.json",
                         "reduced": ["genomes_per_job"], "why": "test"})
    m["workloads"].append({"name": C16, "config": "species95_skani_c16",
                           "traffic": "tiny_families", "chips": 1,
                           "why": "test"})
    for x in m["per_layer"]:
        if "species95.families" in x.get("workloads", []):
            x["workloads"].append(C16)
    return m


def _cell(name):
    if name == C16:
        return harness.resolve_cell(_manifest_with_c16(), C16,
                                    bench_dir=TESTS)
    cell = harness.resolve_cell(harness.load_manifest(), name)
    cell.traffic = json.loads((TRAFFIC / TINY[name]).read_text())
    return cell


def _run(name, counter, trace=False, seed=2**32 + 5):
    return harness.run_cell(_cell(name), seed, 0.3, trace,
                            time.monotonic(), counter, threads=2)


@pytest.mark.parametrize("name", sorted(TINY))
def test_rehearsal_end_to_end(name, counter):
    res = _run(name, counter)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"genomes_per_s", "peak_host_rss_mb",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    numbers = {"jobs_failed", "tsv_rows_wrong", "planted_clusters_wrong",
               "precluster_pairs_wrong", "precluster_ani_gap"}
    if name == "strain99.bigfamily":
        numbers.add("clusterer_ani_gap")
    assert set(res["check"]) == numbers


@pytest.mark.parametrize("name", sorted(TINY))
def test_rehearsal_traced(name, counter):
    res = _run(name, counter, trace=True, seed=2**32 + 6)
    assert res["correct"] is True, res["check"]
    assert "genomes_per_s" not in res["metrics"]
    assert res["metrics"]["compiles_in_window"]["unit"] == "count"
    if name.startswith("species95"):
        for m in ("stage_s.profile_genomes", "stage_s.marker_screen",
                  "stage_s.fragment_ani", "stage_s.greedy_cluster"):
            assert res["metrics"][m]["value"] >= 0
    # a CPU profile has no chip plane: device metrics are left out
    for m in ("device.idle_share", "fragment_kernel_roofline",
              "fused_sketch.ms_per_mbp"):
        assert m not in res["metrics"]
    assert "busy_s" not in res["device"]


def _alter_values(values):
    out = list(values)
    for i, v in enumerate(out):
        if v is not None:
            out[i] = v + 1e-6
            break
    return out


def _drop_half(values):
    return [None if i % 2 else v for i, v in enumerate(values)]


def _patch_precluster(monkeypatch, name, fault):
    """Break the precluster answer where it is produced: the skani
    screen's exact ANI values, or finch's pair cache."""
    if name.startswith("species95"):
        from galah_tpu.backends import fragment_backend

        orig = fragment_backend._guarded_ani_values

        def broken(*a, **kw):
            return fault(orig(*a, **kw))

        monkeypatch.setattr(fragment_backend, "_guarded_ani_values",
                            broken)
    else:
        from galah_tpu.backends.minhash_backend import MinHashPreclusterer
        from galah_tpu.cluster.cache import PairDistanceCache

        orig = MinHashPreclusterer.distances

        def broken(self, genome_paths):
            cache = orig(self, genome_paths)
            keys = sorted(cache.keys())
            values = fault([cache.get(k) for k in keys])
            out = PairDistanceCache()
            for k, v in zip(keys, values):
                if v is not None:
                    out.insert(k, v)
            return out

        monkeypatch.setattr(MinHashPreclusterer, "distances", broken)


@pytest.mark.parametrize("name", sorted(TINY))
def test_altered_answer_is_caught(name, counter, monkeypatch):
    _patch_precluster(monkeypatch, name, _alter_values)
    res = _run(name, counter, seed=2**32 + 7)
    assert res["correct"] is False
    assert res["check"]["precluster_ani_gap"]["value"] > \
        res["check"]["precluster_ani_gap"]["limit"]


@pytest.mark.parametrize("name", sorted(TINY))
def test_half_the_pairs_left_out_is_caught(name, counter, monkeypatch):
    _patch_precluster(monkeypatch, name, _drop_half)
    res = _run(name, counter, seed=2**32 + 8)
    assert res["correct"] is False
    assert res["check"]["precluster_pairs_wrong"]["value"] > 0
    assert res["check"]["tsv_rows_wrong"]["value"] > 0


def test_altered_clusterer_answer_is_caught(counter, monkeypatch):
    """The strain job's exact ANI (the skani clusterer, after the finch
    precluster) altered where it is produced."""
    from galah_tpu.backends.fragment_backend import SkaniEquivalentClusterer

    orig = SkaniEquivalentClusterer.calculate_ani_batch

    def broken(self, pairs):
        return _alter_values(orig(self, pairs))

    monkeypatch.setattr(SkaniEquivalentClusterer, "calculate_ani_batch",
                        broken)
    res = _run("strain99.bigfamily", counter, seed=2**32 + 10)
    assert res["correct"] is False
    assert res["check"]["clusterer_ani_gap"]["value"] > \
        res["check"]["clusterer_ani_gap"]["limit"]


@pytest.mark.parametrize("name", sorted(TINY))
def test_demoted_job_is_caught(name, counter, monkeypatch):
    """A job whose device greedy path fails and demotes to the host
    scan: its answer is right, but it is not the path the cell times."""
    from galah_tpu.cluster import engine

    def fail(*a, **kw):
        raise RuntimeError("planted device failure")

    monkeypatch.setattr(engine, "_cluster_pending_rounds", fail)
    monkeypatch.setattr(engine, "_finish_overlapped", fail)
    res = _run(name, counter, seed=2**32 + 11)
    assert res["correct"] is False
    assert res["check"]["jobs_failed"]["value"] >= 1
    assert res["check"]["tsv_rows_wrong"]["value"] == 0


@pytest.mark.parametrize("name", sorted(TINY))
def test_unchanged_output_is_caught(name, counter, monkeypatch):
    """A job that leaves its output as it found it (nothing written)."""
    from galah_tpu import outputs

    def nothing(handles, clusters, genomes):
        if handles.cluster_definition is not None:
            handles.cluster_definition.close()

    monkeypatch.setattr(outputs, "write_outputs", nothing)
    res = _run(name, counter, seed=2**32 + 9)
    assert res["correct"] is False
    assert res["check"]["tsv_rows_wrong"]["value"] > 0


def test_c16_job_runs_at_the_configured_subsample(counter, monkeypatch):
    """A job run at c=1 under the c=16 configuration is the mislabel
    the check has to see: its ANIs are not the reference's at c=16."""
    orig = harness.cluster_argv

    def at_c1(*a, **kw):
        argv = orig(*a, **kw)
        argv[argv.index("--ani-subsample") + 1] = "1"
        return argv

    monkeypatch.setattr(harness, "cluster_argv", at_c1)
    res = _run(C16, counter, seed=2**32 + 12)
    assert res["correct"] is False
    assert res["check"]["precluster_ani_gap"]["value"] > \
        res["check"]["precluster_ani_gap"]["limit"]


@pytest.mark.parametrize("name", ["species95.families", C16])
def test_float32_control_fails_the_check(name, counter):
    """calibrate.py's readings on one tiny job: the program passes; the
    reference in float32 in its place, half the pairs left out, and one
    ANI moved by 1e-6 each fail, at c=1 and at c=16 alike."""
    cell = _cell(name)
    seed = 2**32 + 13
    harness.WORK_DIR.mkdir(parents=True, exist_ok=True)
    try:
        rec = harness.run_job(cell, seed, 1, 2)
        assert rec.ok
        got = calibrate.readings(cell, seed, rec, 2, control=True)
    finally:
        shutil.rmtree(harness.WORK_DIR, ignore_errors=True)
    limits = cell.config["check_limits"]

    def fails(reading):
        return any(v > limits[k] for k, v in reading.items())

    assert not fails(got["program"])
    assert got["control"]["precluster_ani_gap"] > \
        limits["precluster_ani_gap"]
    assert np.isfinite(got["control"]["precluster_ani_gap"])
    assert fails(got["half"]) and fails(got["altered"])
