"""Cross-run perf ledger tests (galah_tpu/obs/ledger.py + `perf` CLI).

Covers the JSONL record/history/check round-trip, the median±MAD
verdict classes (regression, improvement, drift, insufficient
history), torn-tail recovery after a mid-append crash, key isolation
across device topologies, report-driven entry construction, and the
jax-free `galah-tpu perf` subcommand including the --soft CI mode.
No accelerator work: everything here is file I/O and arithmetic.
"""

import json

import pytest

from galah_tpu.obs import ledger


def _entry(value, *, n_devices=1, backend="cpu", metric="run.duration_s",
           ts=0.0, sha="abc1234", extra=None):
    metrics = {metric: value}
    if extra:
        metrics.update(extra)
    return {
        "v": ledger.LEDGER_VERSION, "ts": ts, "sha": sha,
        "key": {"backend": backend, "device_kind": backend,
                "n_devices": n_devices,
                "workload": {"n": 100, "k": 1000, "p": None},
                "strategy": "auto/auto/auto", "source": "test"},
        "metrics": metrics,
    }


# -- file format ------------------------------------------------------


def test_append_read_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    for i in range(4):
        ledger.append(path, _entry(10.0 + i, ts=float(i)))
    entries, skipped = ledger.read(path)
    assert skipped == 0
    assert [e["metrics"]["run.duration_s"] for e in entries] == \
        [10.0, 11.0, 12.0, 13.0]
    # every line is one complete checksum-framed JSON object
    # (<compact-json>\t<crc32hex>, the io/atomic.py append framing)
    from galah_tpu.io import atomic

    with open(path) as fh:
        for line in fh:
            payload, sep, _crc = line.rstrip("\n").rpartition(
                atomic.FRAME_SEP)
            assert sep == atomic.FRAME_SEP
            assert isinstance(json.loads(payload), dict)


def test_read_missing_file_is_empty_ledger(tmp_path):
    entries, skipped = ledger.read(str(tmp_path / "absent.jsonl"))
    assert entries == [] and skipped == 0


def test_torn_tail_and_junk_lines_recovered(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    for i in range(3):
        ledger.append(path, _entry(5.0 + i))
    with open(path, "a") as fh:
        fh.write('{"v": 1, "ts": 99, "key": {}, "metri')  # torn append
    with open(path, "a") as fh:
        fh.write('\n[1, 2, 3]\n')      # parseable but not an entry
        fh.write('{"no_metrics": 1}\n')
    entries, skipped = ledger.read(path)
    assert len(entries) == 3
    assert skipped == 3
    # and the ledger is still appendable after the tear
    ledger.append(path, _entry(8.0))
    entries, skipped = ledger.read(path)
    assert len(entries) == 4 and skipped == 3


def test_append_keeps_newline_values_on_one_line(tmp_path):
    # json.dumps escapes control characters, so a newline inside a
    # value must still serialize to exactly one physical line
    path = str(tmp_path / "l.jsonl")
    entry = _entry(1.0)
    entry["note"] = "line one\nline two"
    ledger.append(path, entry)
    with open(path) as fh:
        lines = fh.readlines()
    assert len(lines) == 1
    entries, skipped = ledger.read(path)
    assert skipped == 0
    assert entries[0]["note"] == "line one\nline two"


# -- direction inference ---------------------------------------------


def test_metric_direction_families():
    assert ledger.metric_direction("bench.pairs_per_sec") == "higher"
    assert ledger.metric_direction("cache.hit_rate") == "higher"
    assert ledger.metric_direction("run.duration_s") == "lower"
    assert ledger.metric_direction(
        "profile.hbm_peak_bytes") == "lower"
    assert ledger.metric_direction("bench.errors") == "lower"
    assert ledger.metric_direction("funnel.kept") == "neutral"
    # exact-name overrides: host-blame share is the megakernel's
    # headline gauge — "share" matches no substring family, but host
    # orchestration migrating back up is a regression
    assert ledger.metric_direction("flow.host.share") == "lower"
    assert ledger.metric_direction("flow.host.blame_s") == "lower"
    assert ledger.metric_direction(
        "bench.megakernel_host_share") == "lower"
    # the per-stage blame shares stay neutral (blame moving between
    # stages is drift to look at, not a regression by itself)
    assert ledger.metric_direction("flow.pairs.share") == "neutral"


# -- check(): verdict classes ----------------------------------------


def test_check_ok_on_unchanged_history():
    hist = [_entry(10.0, ts=float(i)) for i in range(5)]
    verdicts = ledger.check(hist, _entry(10.0))
    assert [v["verdict"] for v in verdicts] == ["ok"]


def test_check_regression_and_improvement_lower_better():
    hist = [_entry(10.0 + 0.01 * i, ts=float(i)) for i in range(6)]
    worse = ledger.check(hist, _entry(20.0))
    assert worse[0]["verdict"] == "regression"
    better = ledger.check(hist, _entry(5.0))
    assert better[0]["verdict"] == "improvement"
    assert ledger.regressions(worse) and not ledger.regressions(better)


def test_check_regression_higher_better_flips():
    m = "bench.production_pairs_per_sec"
    hist = [_entry(1000.0, metric=m, ts=float(i)) for i in range(5)]
    assert ledger.check(hist, _entry(500.0, metric=m))[0][
        "verdict"] == "regression"
    assert ledger.check(hist, _entry(2000.0, metric=m))[0][
        "verdict"] == "improvement"


def test_check_neutral_metric_drifts_but_never_gates():
    m = "funnel.kept"
    hist = [_entry(40.0, metric=m, ts=float(i)) for i in range(5)]
    verdicts = ledger.check(hist, _entry(400.0, metric=m))
    assert verdicts[0]["verdict"] == "drift"
    assert ledger.regressions(verdicts) == []


def test_check_insufficient_history():
    hist = [_entry(10.0), _entry(11.0)]  # below MIN_HISTORY
    verdicts = ledger.check(hist, _entry(99.0))
    assert verdicts[0]["verdict"] == "insufficient-history"
    assert verdicts[0]["band"] is None
    assert ledger.regressions(verdicts) == []


def test_check_mad_floor_tolerates_epsilon_on_flat_history():
    # identical history => MAD 0; the 1%-of-median floor must keep a
    # tiny wobble inside the band instead of calling it a regression
    hist = [_entry(100.0, ts=float(i)) for i in range(5)]
    verdicts = ledger.check(hist, _entry(100.5))
    assert verdicts[0]["verdict"] == "ok"
    verdicts = ledger.check(hist, _entry(102.0))
    assert verdicts[0]["verdict"] == "regression"


def test_check_window_limits_history():
    old = [_entry(100.0, ts=float(i)) for i in range(10)]
    recent = [_entry(10.0, ts=float(10 + i)) for i in range(8)]
    verdicts = ledger.check(old + recent, _entry(10.0), window=8)
    assert verdicts[0]["verdict"] == "ok"  # old regime aged out


def test_check_key_isolation_across_topologies():
    # 1-device history must not gate an 8-device run, and vice versa
    hist = ([_entry(10.0, n_devices=1, ts=float(i)) for i in range(5)]
            + [_entry(50.0, n_devices=8, ts=float(i)) for i in range(5)])
    v1 = ledger.check(hist, _entry(10.0, n_devices=1))
    v8 = ledger.check(hist, _entry(50.0, n_devices=8))
    assert v1[0]["verdict"] == "ok" and v8[0]["verdict"] == "ok"
    # 8-device band applied to the 1-device value would regress; the
    # key split is what keeps it ok
    cross = ledger.check(hist, _entry(50.0, n_devices=1))
    assert cross[0]["verdict"] == "regression"
    few = ledger.check(hist, _entry(1.0, backend="tpu"))
    assert few[0]["verdict"] == "insufficient-history"


# -- entries from run reports ----------------------------------------


def _report(duration=12.0, n=256, extra_metrics=None):
    rep = {
        "version": 5,
        "run": {"subcommand": "cluster", "duration_s": duration},
        "device": {"backend": "cpu", "device_count": 1,
                   "devices": [{"device_kind": "cpu"}]},
        "flags": {"GALAH_TPU_PAIRLIST_BLOCK": {"value": "8"},
                  "GALAH_TPU_GREEDY_STRATEGY": {"value": "device"}},
        "metrics": {"workload.n_genomes": {"value": n},
                    "workload.sketch_k": {"value": 1000}},
        "stages": {"tree": [
            {"name": "precluster-distances", "total_s": 7.5,
             "children": [{"name": "sketch", "total_s": 3.0}]},
            {"name": "greedy-cluster", "total_s": 4.0},
        ]},
        "dispatch": {"total_dispatches": 42, "total_syncs": 2},
        "device_costs": {
            "profiling_enabled": True,
            "entries": {"pairwise.tile_stats_pallas": {
                "calls": 5, "signatures": 1,
                "dispatch_wall_s": 1.25, "compile_wall_s": 0.5}},
            "hbm": {"peak_bytes": 1 << 20, "source": "live_arrays",
                    "per_stage": {}},
        },
    }
    if extra_metrics:
        rep["metrics"].update(extra_metrics)
    return rep


def test_entry_from_report_key_and_metrics():
    entry = ledger.entry_from_report(_report(), "cluster", ts=1.0,
                                     sha="deadbee")
    key = entry["key"]
    assert key["backend"] == "cpu" and key["n_devices"] == 1
    assert key["workload"] == {"n": 256, "k": 1000, "p": 8}
    # pairlist / fragment / greedy / sketch / overlap / mesh-shape
    # pins, in order
    assert key["strategy"] == "auto/auto/device/auto/auto/auto"
    assert key["source"] == "cluster"
    m = entry["metrics"]
    assert m["run.duration_s"] == 12.0
    assert m["stage.precluster-distances_s"] == 7.5
    assert m["stage.precluster-distances/sketch_s"] == 3.0
    assert m["dispatch.total_dispatches"] == 42.0
    assert m["profile.pairwise.tile_stats_pallas.dispatch_wall_s"] \
        == 1.25
    assert m["profile.hbm_peak_bytes"] == float(1 << 20)
    # the sha is recorded but NOT part of the comparison key
    assert "deadbee" not in ledger.key_of(entry)


def test_workload_fingerprint_nulls_when_unsaid():
    rep = _report()
    rep["metrics"] = {}
    rep["flags"] = {}
    assert ledger.workload_fingerprint(rep) == \
        {"n": None, "k": None, "p": None}


def test_record_report_never_raises(tmp_path, caplog):
    # an unwritable path must log, not crash the finalizing run
    bad_path = str(tmp_path / "dir")
    (tmp_path / "dir").mkdir()
    assert ledger.record_report(bad_path, _report(), "cluster") is False
    ok_path = str(tmp_path / "ok.jsonl")
    assert ledger.record_report(ok_path, _report(), "cluster") is True
    entries, _ = ledger.read(ok_path)
    assert len(entries) == 1


# -- `galah-tpu perf` subcommand (jax-free) --------------------------


def _cli(tmp_path):
    from galah_tpu.cli import main
    return main


def _write_report(tmp_path, name, duration):
    p = tmp_path / name
    p.write_text(json.dumps(_report(duration=duration)))
    return str(p)


def test_perf_record_history_check_roundtrip(tmp_path, capsys):
    main = _cli(tmp_path)
    led = str(tmp_path / "ledger.jsonl")
    for i, dur in enumerate((10.0, 10.2, 9.9, 10.1)):
        rp = _write_report(tmp_path, f"r{i}.json", dur)
        assert main(["perf", "--ledger", led, "record", rp,
                     "--source", "cluster"]) == 0
    capsys.readouterr()

    assert main(["perf", "--ledger", led, "history",
                 "run.duration_s"]) == 0
    out = capsys.readouterr().out
    assert out.count("\n") == 4  # one row per entry
    assert "10.2" in out

    # unchanged rerun: newest vs the prior three => all ok, exit 0
    assert main(["perf", "--ledger", led, "check"]) == 0
    out = capsys.readouterr().out
    assert "regression=0" not in out  # no regression bucket at all
    assert "ok=" in out


def test_perf_check_gates_on_seeded_regression(tmp_path, capsys):
    main = _cli(tmp_path)
    led = str(tmp_path / "ledger.jsonl")
    for i, dur in enumerate((10.0, 10.2, 9.9, 10.1)):
        rp = _write_report(tmp_path, f"r{i}.json", dur)
        assert main(["perf", "--ledger", led, "record", rp,
                     "--source", "cluster"]) == 0
    slow = _write_report(tmp_path, "slow.json", 30.0)
    # --report checks without appending
    assert main(["perf", "--ledger", led, "check", "--report", slow,
                 "--source", "cluster"]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION: run.duration_s" in out
    entries, _ = ledger.read(led)
    assert len(entries) == 4  # check --report appended nothing
    # --soft reports but exits 0 (the CI mode)
    assert main(["perf", "--ledger", led, "check", "--report", slow,
                 "--source", "cluster", "--soft"]) == 0
    assert "not gated" in capsys.readouterr().out


def test_perf_check_empty_and_missing_ledger(tmp_path, capsys):
    main = _cli(tmp_path)
    led = str(tmp_path / "never_written.jsonl")
    assert main(["perf", "--ledger", led, "check"]) == 0
    assert "empty" in capsys.readouterr().out
    # no ledger anywhere => error exit, not a crash
    assert main(["perf", "check"]) == 1


def test_perf_record_rejects_bad_report(tmp_path):
    main = _cli(tmp_path)
    led = str(tmp_path / "ledger.jsonl")
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert main(["perf", "--ledger", led, "record", str(bad)]) == 1
    assert main(["perf", "--ledger", led, "record",
                 str(tmp_path / "missing.json")]) == 1


def test_finalize_feeds_ledger_when_flag_set(tmp_path, monkeypatch):
    from galah_tpu import obs
    from galah_tpu.utils import timing

    led = str(tmp_path / "auto.jsonl")
    monkeypatch.setenv("GALAH_OBS_LEDGER", led)
    timing.reset()
    obs.reset_run()
    with timing.stage("precluster-distances"):
        timing.dispatch(1)
    out = obs.finalize("cluster", started_at=0.0)
    assert out is not None
    entries, skipped = ledger.read(led)
    assert skipped == 0 and len(entries) == 1
    assert entries[0]["key"]["source"] == "cluster"
    assert "stage.precluster-distances_s" in entries[0]["metrics"]
