"""Tiny CPU rehearsals of each cell through the whole run (set-up,
window, check, metric readers), with test-only traffic files; and the
same runs with the timed path broken underneath, which the check must
catch. The harness's look for a chip is skipped: `run_cell` is called
directly, as `run.py` calls it once it has found the chip."""

import json
import time
from pathlib import Path

import pytest

import harness

pytest.importorskip("galah_tpu")

TRAFFIC = Path(__file__).parent / "traffic"
TINY = {"species95.families": "tiny_families.json",
        "strain99.bigfamily": "tiny_bigfamily.json"}


@pytest.fixture(scope="module")
def counter():
    return harness.configure_jax()


def _run(name, counter, trace=False, seed=2**32 + 5):
    cell = harness.resolve_cell(harness.load_manifest(), name)
    cell.traffic = json.loads((TRAFFIC / TINY[name]).read_text())
    return harness.run_cell(cell, seed, 0.3, trace, time.monotonic(),
                            counter, threads=2)


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
    if name == "species95.families":
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
    if name == "species95.families":
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
