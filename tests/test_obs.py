"""Unified telemetry layer tests (galah_tpu/obs + utils/timing).

Covers the metrics registry, worker-thread stage attribution (the
dispatch-from-a-pool-thread regression), warn_once dedup, Chrome-trace
output, run-report assembly against the committed JSON Schema, the
`galah-tpu report` subcommand (render + --diff), and fault-injected
resilience events landing in the report.
"""

import json
import threading

import pytest

from galah_tpu import obs
from galah_tpu.obs import events as obs_events
from galah_tpu.obs import metrics as obs_metrics
from galah_tpu.obs import report as report_mod
from galah_tpu.obs import trace as obs_trace
from galah_tpu.utils import timing
from galah_tpu.utils.logging import reset_warn_once, warn_once


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    timing.reset()
    obs.reset_run()
    reset_warn_once()
    yield
    obs_trace.stop()
    timing.reset()
    obs.reset_run()
    reset_warn_once()


# -- metrics registry -----------------------------------------------


def test_counter_gauge_histogram_basics():
    c = obs_metrics.counter("t.count", help="h", unit="u")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = obs_metrics.gauge("t.gauge")
    g.set(0.25)
    assert g.value == 0.25
    h = obs_metrics.histogram("t.hist", unit="s")
    for v in (1.0, 3.0):
        h.observe(v)
    h.observe(float("nan"))  # skipped, must not poison aggregates
    assert h.count == 2 and h.min == 1.0 and h.max == 3.0
    assert h.mean == 2.0
    snap = obs_metrics.snapshot()
    assert snap["t.count"] == {"kind": "counter", "unit": "u",
                               "help": "h", "value": 5}
    assert snap["t.hist"]["mean"] == 2.0


def test_registry_is_get_or_create_and_kind_checked():
    a = obs_metrics.counter("t.same")
    b = obs_metrics.counter("t.same")
    assert a is b
    with pytest.raises(TypeError):
        obs_metrics.gauge("t.same")


def test_histogram_time_context():
    h = obs_metrics.histogram("t.timer", unit="s")
    with h.time():
        pass
    assert h.count == 1 and h.min >= 0.0


def test_counter_thread_safety():
    c = obs_metrics.counter("t.mt")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


# -- worker-thread stage attribution (the regression satellite) ------


def test_dispatch_from_worker_thread_inherits_spawning_stage():
    """A pool thread with an empty thread-local stack used to count its
    dispatches under "?"; it must inherit the stage open on the
    spawning thread."""
    t = timing.StageTimer()
    done = threading.Event()

    def worker():
        t.dispatch()
        t.dispatch(sync=True)
        done.set()

    with t.stage("sketch"):
        th = threading.Thread(target=worker)
        th.start()
        assert done.wait(5)
        th.join()
    counters = t.counters()
    assert counters.get("disp[sketch]") == 1
    assert counters.get("sync[sketch]") == 1
    assert "disp[?]" not in counters


def test_dispatch_with_no_stage_anywhere_is_unattributed():
    t = timing.StageTimer()
    t.dispatch()
    assert t.counters() == {"disp[?]": 1}


def test_stage_token_adopt_passthrough():
    t = timing.StageTimer()
    results = {}

    def worker(token):
        with t.adopt(token):
            results["stage"] = t.current_stage()
            t.dispatch()

    with t.stage("outer"):
        with t.stage("inner"):
            token = t.stage_token()
            th = threading.Thread(target=worker, args=(token,))
            th.start()
            th.join()
    assert results["stage"] == "inner"
    assert t.counters().get("disp[inner]") == 1


def test_stage_tree_nests_and_accumulates():
    t = timing.StageTimer()
    with t.stage("a"):
        with t.stage("b"):
            pass
        with t.stage("b"):
            pass
    with t.stage("c"):
        pass
    tree = t.tree()
    assert [n["name"] for n in tree] == ["a", "c"]
    (a, c) = tree
    assert [ch["name"] for ch in a["children"]] == ["b"]
    assert a["children"][0]["count"] == 2
    assert c["children"] == []
    assert a["total_s"] >= a["children"][0]["total_s"]


# -- warn_once (dedup satellite) -------------------------------------


def test_warn_once_dedupes_and_counts_suppressed(caplog):
    import logging

    lg = logging.getLogger("galah_tpu.test_warn_once")
    msg = ("Since CheckM input is missing, genomes are not being "
           "ordered by quality. Instead the order of their input is "
           "being used")
    with caplog.at_level(logging.WARNING,
                         logger="galah_tpu.test_warn_once"):
        for _ in range(3):
            warn_once(lg, msg)
    emitted = [r for r in caplog.records if r.getMessage() == msg]
    assert len(emitted) == 1
    suppressed = [e for e in obs_events.snapshot()
                  if e["kind"] == "warn-once-suppressed"]
    assert len(suppressed) == 2
    assert suppressed[0]["message"] == msg


def test_warn_once_distinct_messages_both_emit(caplog):
    import logging

    lg = logging.getLogger("galah_tpu.test_warn_once2")
    with caplog.at_level(logging.WARNING,
                         logger="galah_tpu.test_warn_once2"):
        warn_once(lg, "first %s", "a")
        warn_once(lg, "second")
    assert {r.getMessage() for r in caplog.records} == {"first a",
                                                        "second"}


# -- trace recorder --------------------------------------------------


def test_trace_file_is_valid_json_with_stage_spans(tmp_path):
    path = tmp_path / "trace.json"
    obs_trace.start(str(path))
    with timing.stage("traced-stage"):
        pass
    obs_events.record("demotion", site="dispatch.test")
    obs_trace.stop()
    events = json.loads(path.read_text())
    assert isinstance(events, list)
    names = {e.get("name") for e in events}
    assert "traced-stage" in names
    assert "demotion" in names
    span = next(e for e in events if e.get("name") == "traced-stage")
    assert span["ph"] == "X" and span["dur"] >= 0
    inst = next(e for e in events if e.get("name") == "demotion")
    assert inst["ph"] == "i"
    assert inst["args"]["site"] == "dispatch.test"


def test_trace_emission_noop_when_inactive():
    # must not raise with no recorder installed
    obs_trace.emit_complete("x", 0.0, 1.0)
    obs_trace.emit_instant("y")
    assert obs_trace.active() is False


# -- run report ------------------------------------------------------


def _populate_run_state():
    with timing.stage("precluster-distances"):
        timing.dispatch(3)
        timing.dispatch(sync=True)
    with timing.stage("greedy-cluster"):
        with timing.stage("write-outputs"):
            pass
    timing.counter("screen-possible-pairs", 100)
    timing.counter("screen-candidates", 40)
    timing.counter("screen-kept-pairs", 10)
    timing.counter("exact-ani-computed", 10)
    timing.counter("exact-ani-wasted", 2)
    obs_metrics.counter("cache.hits").inc(3)
    obs_metrics.counter("cache.misses").inc(1)
    obs_metrics.histogram("ani.batch_seconds", unit="s").observe(0.5)


def test_assembled_report_is_schema_valid():
    jsonschema = pytest.importorskip("jsonschema")
    _populate_run_state()
    rep = report_mod.assemble("cluster", argv=["galah-tpu", "cluster"],
                              started_at=1.0)
    problems = report_mod.validate(rep)
    assert problems == []
    # cross-check validate() against a direct jsonschema pass
    with open(report_mod.SCHEMA_PATH) as fh:
        schema = json.load(fh)
    jsonschema.Draft7Validator(schema).validate(rep)
    assert rep["funnel"]["possible_pairs"] == 100
    assert rep["funnel"]["cache"]["hit_rate"] == 0.75
    assert rep["dispatch"]["total_dispatches"] == 3
    assert rep["dispatch"]["dispatches"][
        "precluster-distances"] == 3
    names = [n["name"] for n in rep["stages"]["tree"]]
    assert names == ["precluster-distances", "greedy-cluster"]


def test_validate_flags_broken_report():
    rep = report_mod.assemble("cluster")
    rep.pop("funnel")
    rep["version"] = 99
    problems = report_mod.validate(rep)
    assert problems  # both defects reported by the schema pass
    assert any("funnel" in p for p in problems)


def test_report_write_load_roundtrip_and_render(tmp_path):
    _populate_run_state()
    rep = report_mod.assemble("cluster", started_at=0.0)
    path = tmp_path / "run_report.json"
    report_mod.write(str(path), rep)
    loaded = report_mod.load(str(path))
    assert loaded == json.loads(json.dumps(rep))  # JSON-clean
    page = report_mod.render(loaded)
    assert "precluster funnel" in page
    assert "greedy-cluster" in page


def test_finalize_writes_validated_report(tmp_path):
    _populate_run_state()
    path = tmp_path / "report.json"
    out = obs.finalize("cluster", report_path=str(path), started_at=0.0)
    assert out is not None
    assert report_mod.validate(report_mod.load(str(path))) == []


# -- `galah-tpu report` subcommand -----------------------------------


def _write_two_reports(tmp_path):
    _populate_run_state()
    a = report_mod.assemble("cluster", started_at=0.0)
    b = json.loads(json.dumps(a))
    b["run"]["duration_s"] = a["run"]["duration_s"] + 2.0
    b["funnel"]["kept_pairs"] += 5
    b["metrics"]["cache.hits"]["value"] = 9
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    report_mod.write(str(pa), a)
    report_mod.write(str(pb), b)
    return str(pa), str(pb)


def test_report_subcommand_renders(tmp_path, capsys):
    from galah_tpu.cli import main

    pa, _ = _write_two_reports(tmp_path)
    assert main(["report", pa]) == 0
    out = capsys.readouterr().out
    assert "galah-tpu run report" in out


def test_report_subcommand_diff_roundtrip(tmp_path, capsys):
    from galah_tpu.cli import main

    pa, pb = _write_two_reports(tmp_path)
    assert main(["report", "--diff", pa, pb]) == 0
    out = capsys.readouterr().out
    assert "(+2.00s)" in out
    assert "kept_pairs" in out and "(+5)" in out
    assert "cache.hits" in out


def test_report_subcommand_rejects_invalid(tmp_path):
    from galah_tpu.cli import main

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 1}))
    assert main(["report", str(bad)]) == 1
    missing = tmp_path / "missing.json"
    assert main(["report", str(missing)]) == 1
    pa, pb = _write_two_reports(tmp_path)
    assert main(["report", "--diff", pa]) == 1  # needs exactly two


# -- fault-injected resilience events land in the report -------------


@pytest.mark.fault_injection
def test_injected_faults_appear_in_report(monkeypatch):
    from galah_tpu.resilience import dispatch as rdispatch
    from galah_tpu.resilience import faults
    from galah_tpu.resilience.policy import RetryPolicy

    monkeypatch.setenv("GALAH_FI", "site=dispatch.ani;kind=raise")
    faults.reset()
    sup = rdispatch.DispatchSupervisor(
        RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0))
    try:
        # make the module-level GLOBAL the one assemble() reads
        monkeypatch.setattr(rdispatch, "GLOBAL", sup)
        out = sup.run("dispatch.ani", lambda: [0.5],
                      fallback=lambda: [0.25])
        assert out == [0.25]
        rep = report_mod.assemble("cluster")
    finally:
        # env first: faults.reset() re-reads GALAH_FI, and resetting
        # with it still set would leak the injector into later tests
        monkeypatch.delenv("GALAH_FI", raising=False)
        faults.reset()
    sites = [d["site"] for d in rep["resilience"]["demotions"]]
    assert sites == ["dispatch.ani"]
    kinds = [e["kind"] for e in rep["events"]]
    assert "retry" in kinds and "demotion" in kinds
    demo = next(e for e in rep["events"] if e["kind"] == "demotion")
    assert demo["site"] == "dispatch.ani"
    assert rep["resilience"]["retries"].get("dispatch.ani") == 1
    assert report_mod.validate(rep) == []


def test_flag_snapshot_marks_env_set(monkeypatch):
    monkeypatch.setenv("GALAH_OBS_REPORT", "/tmp/r.json")
    monkeypatch.delenv("GALAH_OBS_TRACE_EVENTS", raising=False)
    snap = report_mod.flag_snapshot()
    assert snap["GALAH_OBS_REPORT"]["set"] is True
    assert snap["GALAH_OBS_REPORT"]["value"] == "/tmp/r.json"
    assert snap["GALAH_OBS_TRACE_EVENTS"]["set"] is False
    assert snap["GALAH_OBS_TRACE_EVENTS"]["section"] == "observability"


# -- schema v3: device_costs section ---------------------------------


def test_report_v3_carries_populated_device_costs():
    """A run that dispatched through a @profiled entry point must land
    cost/wall numbers in the report's device_costs section — the
    section the perf ledger reads its profile.* metrics from."""
    jsonschema = pytest.importorskip("jsonschema")
    import jax.numpy as jnp

    from galah_tpu.obs import profile as obs_profile
    from galah_tpu.obs.profile import profiled

    import jax

    fn = profiled("test.v3_entry")(jax.jit(lambda x: x * 2.0 + 1.0))
    with timing.stage("precluster-distances"):
        for _ in range(3):
            fn(jnp.ones((8, 8), jnp.float32))
    rep = report_mod.assemble("cluster", started_at=0.0)
    assert rep["version"] == 10
    dc = rep["device_costs"]
    assert dc["profiling_enabled"] is True
    entry = dc["entries"]["test.v3_entry"]
    assert entry["calls"] == 3
    assert entry["signatures"] == 1
    assert entry["flops"] > 0
    assert dc["hbm"]["peak_bytes"] > 0
    assert dc["hbm"]["source"] in ("memory_stats", "live_arrays")
    assert report_mod.validate(rep) == []
    with open(report_mod.SCHEMA_PATH) as fh:
        jsonschema.Draft7Validator(json.load(fh)).validate(rep)
    page = report_mod.render(rep)
    assert "device costs" in page
    assert "test.v3_entry" in page
    # drop the one registry entry this test added
    obs_profile._REGISTRY[:] = [
        f for f in obs_profile._REGISTRY if f.name != "test.v3_entry"]


def test_profile_disabled_flag_yields_plain_calls(monkeypatch):
    import jax.numpy as jnp

    from galah_tpu.obs import profile as obs_profile
    from galah_tpu.obs.profile import profiled

    monkeypatch.setenv("GALAH_OBS_PROFILE", "0")
    fn = profiled("test.disabled_entry")(lambda x: x + 1)
    assert float(fn(jnp.float32(1.0))) == 2.0  # still correct
    snap = obs_profile.snapshot()
    assert snap["profiling_enabled"] is False
    assert "test.disabled_entry" not in snap["entries"]
    obs_profile._REGISTRY[:] = [
        f for f in obs_profile._REGISTRY
        if f.name != "test.disabled_entry"]


def test_report_diff_v2_v3_is_additive_compatible(tmp_path, capsys):
    """`report --diff` across a v2 report (no device_costs) and a v3
    report must not crash — the section is optional and additive."""
    from galah_tpu.cli import main

    _populate_run_state()
    v3 = report_mod.assemble("cluster", started_at=0.0)
    v3.setdefault("device_costs", {"profiling_enabled": True,
                                   "entries": {}, "hbm": {
                                       "peak_bytes": 0, "source": None,
                                       "per_stage": {}}})
    v2 = json.loads(json.dumps(v3))
    del v2["device_costs"]
    v2["version"] = 2
    pa, pb = tmp_path / "v2.json", tmp_path / "v3.json"
    pa.write_text(json.dumps(v2))
    pb.write_text(json.dumps(v3))
    # v2 stays schema-valid (the enum admits both) and diff runs both
    # directions without touching the missing section
    assert report_mod.validate(v2) == []
    assert main(["report", "--diff", str(pa), str(pb)]) == 0
    assert main(["report", "--diff", str(pb), str(pa)]) == 0
    out = capsys.readouterr().out
    assert "galah-tpu report diff" in out or out  # rendered, no crash
