"""The host-plane span loader and the readers of exact ANI's and the
profiles' spans: on synthetic run reports, on a small recorded CPU
trace (the CPU backend runs its ops on host threads, which
`trace_reduce.load` reads as device lines when told their name), on
reports of a program without the spans (the parent's), and in a traced
CPU rehearsal."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import host_spans
import trace_reduce as tr

pytest.importorskip("galah_tpu")

CPU_OPS = "tf_XLAPjRtCpuClient"


def _job(report, rc=0):
    return SimpleNamespace(rc=rc, report=report)


def _node(name, total_s, children=()):
    return {"name": name, "total_s": total_s, "count": 1,
            "children": list(children)}


def _report(tree, counters):
    return {"stages": {"tree": tree}, "counters": counters}


def _ctx(*reports, trace=None):
    return SimpleNamespace(jobs=[_job(r) for r in reports], trace=trace)


def test_host_ms_per_direction_leaves_out_the_fetch_waits():
    read = harness.load_reader("fragment_ani.host_ms_per_direction")
    ani = _node("fragment-ani", 4.0, [
        _node("fragment-ani.sort-queries", 0.5),
        _node("fragment-ani.launch", 1.0),
        _node("fragment-ani.fetch", 2.0),
        _node("fragment-ani.fold", 0.25)])
    species = _report([_node("precluster-distances", 5.0, [ani])],
                      {"fragment-ani-directions": 400})
    # two outermost nodes, a fetch one level further down
    strain = _report([
        _node("greedy-cluster", 9.0, [
            _node("fragment-ani", 1.0, [
                _node("x", 0.6, [_node("fragment-ani.fetch", 0.5)])]),
            _node("fragment-ani", 1.0)])],
        {"fragment-ani-directions": 100})
    # (4 - 2) s over 400 and (2 - 0.5) s over 100 directions
    assert read(_ctx(species)) == pytest.approx(5.0)
    assert read(_ctx(strain)) == pytest.approx(15.0)
    assert read(_ctx(species, strain)) == pytest.approx(10.0)


def test_genome_ms_per_mbp_sums_thread_seconds():
    read = harness.load_reader("profile_genomes.genome_ms_per_mbp")
    rep = _report([_node("profile-genomes", 1.0, [
        _node("profile.read", 0.8), _node("profile.build", 2.4)])],
        {"profile-bp": 64_000_000})
    assert read(_ctx(rep)) == pytest.approx(3.2e3 / 64)


def test_readers_give_nothing_for_a_program_without_the_spans():
    """The parent's reports: no per-direction counter, no profile
    spans, a `fragment-ani` stage only in the clusterer."""
    old = _report([_node("precluster-distances", 5.0, [
        _node("profile-genomes", 1.0), _node("marker-screen", 0.1)]),
        _node("greedy-cluster", 2.0, [_node("fragment-ani", 1.0)])],
        {"fragment-pallas-launches": 448})
    for name in ("fragment_ani.host_ms_per_direction",
                 "profile_genomes.genome_ms_per_mbp"):
        assert harness.load_reader(name)(_ctx(old)) is None, name
        assert harness.load_reader(name)(_ctx()) is None, name


def _record(tmp_path, busy_in_ani):
    """A CPU profile of two jobs: in each, a `fragment-ani` stage that
    runs `busy_in_ani` matmuls around a host sleep, then host work
    outside it."""
    import jax
    import jax.numpy as jnp

    from galah_tpu.utils import timing

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "profile"),
                             profiler_options=opts)
    for job in (1, 2):
        with jax.profiler.TraceAnnotation(f"{tr.JOB_PREFIX}{job}"):
            with timing.stage("fragment-ani"):
                for _ in range(busy_in_ani):
                    f(x).block_until_ready()
                with timing.stage("fragment-ani.fold",
                                  {"direction": job}):
                    time.sleep(0.03)
            time.sleep(0.01)
    jax.profiler.stop_trace()
    return tr.find_xplane(str(tmp_path / "profile"))


def test_load_reads_program_spans_with_their_arguments(tmp_path):
    path = _record(tmp_path, 1)
    spans = host_spans.load(path, ["fragment-ani", "fragment-ani.fold",
                                   "bench_job:1"])
    assert len(spans["fragment-ani"]) == 2
    assert [a["direction"] for _s, _e, a in
            spans["fragment-ani.fold"]] == [1, 2]
    # on the jobs' clock: each span inside its job's mark
    (job1,) = spans["bench_job:1"]
    s, e, _a = spans["fragment-ani"][0]
    assert job1[0] <= s < e <= job1[1]
    assert host_spans.from_run(["fragment-ani"],
                               tmp_path / "profile") == \
        host_spans.load(path, ["fragment-ani"])
    assert host_spans.from_run(["fragment-ani"], tmp_path / "none") == {}


def test_device_idle_share_of_the_exact_ani_spans(tmp_path, monkeypatch):
    read = harness.load_reader("fragment_ani.device_idle_share")
    path = _record(tmp_path, 2)
    monkeypatch.setattr(harness, "WORK_DIR", tmp_path)
    trace = tr.load(path, device_line_prefix=CPU_OPS)
    share = read(_ctx(trace=trace))
    # the 30 ms sleep in each span is idle, the matmuls are not
    assert share is not None and 0 < share < 100
    spans = host_spans.load(path, ["fragment-ani"])["fragment-ani"]
    wall = sum(e - s for s, e, _a in spans)
    assert share >= 100 * 0.06 / wall - 5
    # no chip plane, no spans, or no profile: nothing to read
    assert read(_ctx(trace=tr.load(path))) is None
    assert read(_ctx(trace=None)) is None
    monkeypatch.setattr(harness, "WORK_DIR", tmp_path / "none")
    assert read(_ctx(trace=trace)) is None


def test_rehearsal_traced_reads_the_program_spans():
    counter = harness.configure_jax()
    cell = harness.resolve_cell(harness.load_manifest(),
                                "species95.families")
    cell.traffic = json.loads((Path(__file__).parent / "traffic"
                               / "tiny_families.json").read_text())
    res = harness.run_cell(cell, 2**33 + 1, 0.3, True, time.monotonic(),
                           counter, threads=2)
    assert res["correct"] is True, res["check"]
    for m in ("fragment_ani.host_ms_per_direction",
              "profile_genomes.genome_ms_per_mbp"):
        assert res["metrics"][m]["value"] > 0, m
    # a CPU profile has no chip plane
    assert "fragment_ani.device_idle_share" not in res["metrics"]
