"""BENCHMARK.json against the benchmark's contract, and a new cell made
of new files and a manifest entry alone."""

import json
import re
import shutil
from pathlib import Path

import pytest

import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_manifest_shape():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"]
    assert 1 <= m["run_seconds"] <= 51
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        used.add(w["config"])
    assert used == set(configs)
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))
    assert any(x["name"] == "setup_s" for x in m["end_to_end"])
    e2e = {x["name"] for x in m["end_to_end"]}
    for x in m["end_to_end"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower",
                                                         "higher")
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert UNIT.match(x["unit"]) and NAME.match(x["name"])
        assert x["moves"] in e2e
        assert set(x["workloads"]) <= cells
        assert (BENCH / "metrics" / f"{x['name']}.py").is_file()
    for w in m["workloads"]:
        cell = harness.resolve_cell(m, w["name"])
        assert "setup_s" in {x["name"] for x in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_new_cell_needs_only_files_and_a_manifest_entry(tmp_path):
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    config = json.loads(
        (BENCH / "configs" / "species95_skani.json").read_text())
    config["name"] = "species97_skani"
    config["methods"]["ani"] = 97
    (bench / "configs" / "species97_skani.json").write_text(
        json.dumps(config))
    traffic = json.loads((BENCH / "traffic" / "families.json").read_text())
    traffic["species"][0]["count"] = 4
    (bench / "traffic" / "fourfamilies.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "jobs_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.jobs))\n")
    m = _manifest()
    m["configs"].append({"name": "species97_skani", "source": "x",
                         "file": "benchmark/configs/species97_skani.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "species97.fourfamilies",
                           "config": "species97_skani",
                           "traffic": "fourfamilies", "chips": 1,
                           "why": "test"})
    m["per_layer"].append({"name": "jobs_in_window", "unit": "count",
                           "better": "higher",
                           "source": "program_counter", "layer": "entry",
                           "moves": "genomes_per_s"})
    cell = harness.resolve_cell(m, "species97.fourfamilies",
                                bench_dir=bench)
    assert cell.config["methods"]["ani"] == 97
    assert cell.traffic["species"][0]["count"] == 4
    assert cell.chips == 1
    assert {x["name"] for x in cell.end_to_end} == {
        "genomes_per_s", "peak_host_rss_mb", "setup_s"}
    # a metric without `workloads` goes to every cell reporting what it
    # moves; one with `workloads` only to those cells
    layer = {x["name"] for x in cell.per_layer}
    assert "jobs_in_window" in layer
    assert "stage_s.marker_screen" not in layer
    read = harness.load_reader("jobs_in_window", bench_dir=bench)

    class Ctx:
        jobs = [1, 2, 3]

    assert read(Ctx()) == 3.0


def _files():
    return {"genome_list": "list.txt", "quality": "quality.tsv"}


def _flag(argv, flag):
    assert argv.count(flag) == 1
    return argv[argv.index(flag) + 1]


def test_cluster_argv_carries_the_ani_subsample(tmp_path):
    config = json.loads(
        (BENCH / "configs" / "species95_skani.json").read_text())
    config["widths"]["ani_subsample"] = 125
    argv = harness.cluster_argv(config, _files(), tmp_path, 4, None)
    assert _flag(argv, "--ani-subsample") == "125"
    del config["widths"]["ani_subsample"]
    argv = harness.cluster_argv(config, _files(), tmp_path, 4, None)
    assert _flag(argv, "--ani-subsample") == "1"


def test_committed_configs_run_every_kmer(tmp_path):
    """Both committed configurations hold c=1, so their jobs get the
    program's own default and nothing they run changes."""
    for c in _manifest()["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        argv = harness.cluster_argv(config, _files(), tmp_path, 4, None)
        assert _flag(argv, "--ani-subsample") == "1"


MISLABELS = [
    ("methods", "precluster", "dashing", "methods.precluster"),
    ("methods", "cluster", "fastani", "methods.cluster"),
    ("widths", "fragment_length", 2000, "widths.fragment_length"),
    ("widths", "skani_kmer", 16, "widths.skani_kmer"),
    ("widths", "marker_compression", 500, "widths.marker_compression"),
    ("widths", "screen_identity", 0.9, "widths.screen_identity"),
    ("widths", "minhash_kmer", 15, "widths.minhash_kmer"),
    ("widths", "minhash_sketch_size", 2000, "widths.minhash_sketch_size"),
    ("widths", "ani_subsample", 0, "widths.ani_subsample"),
    ("widths", "ani_subsample", 1001, "widths.ani_subsample"),
    ("widths", "ani_subsample", 12.5, "widths.ani_subsample"),
    ("widths", "hll_precision", 12, "widths.hll_precision"),
]


@pytest.mark.parametrize("group,key,value,named", MISLABELS,
                         ids=[f"{m[1]}={m[2]}" for m in MISLABELS])
def test_resolve_cell_refuses_a_config_it_would_not_run(
        tmp_path, group, key, value, named):
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic"):
        shutil.copytree(BENCH / sub, bench / sub)
    config = json.loads(
        (BENCH / "configs" / "strain99_finch.json").read_text())
    config[group][key] = value
    (bench / "configs" / "strain99_finch.json").write_text(
        json.dumps(config))
    with pytest.raises(ValueError, match=re.escape(named)):
        harness.resolve_cell(_manifest(), "strain99.bigfamily",
                             bench_dir=bench)


def _report(**kw):
    report = {"device": {"backend": "tpu"}, "resilience": {"demotions": []},
              "events": [], "counters": {"greedy-rounds": 3}}
    report.update(kw)
    return report


def test_path_problems_accepts_the_device_path():
    assert harness.path_problems(_report(), "tpu", ["greedy-rounds"]) == []


def test_path_problems_names_each_way_off_the_device_path():
    assert harness.path_problems(_report(device={"backend": "cpu"}),
                                 "tpu", [])
    assert harness.path_problems(
        _report(resilience={"demotions": [{"site": "x"}]}), "tpu", [])
    assert harness.path_problems(
        _report(events=[{"kind": "overlap-demoted"}]), "tpu", [])
    assert harness.path_problems(
        _report(counters={"greedy-device-demoted": 1}), "tpu", [])
    assert harness.path_problems(_report(), "tpu", ["overlap-engaged"])


def test_counters_needed_only_on_the_chip():
    m = _manifest()
    cell = harness.resolve_cell(m, "strain99.bigfamily")
    assert harness.counters_needed(cell, "cpu") == []
    needed = harness.counters_needed(cell, "tpu")
    assert {"sketch-fused-launches", "overlap-engaged",
            "megakernel-slab-folds"} <= set(needed)
