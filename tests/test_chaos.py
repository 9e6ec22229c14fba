"""Bounded kill-anywhere chaos smoke (scripts/chaos_run.py).

Runs the same harness as the full 25-iteration acceptance pass at ~10
kill points: seeded subprocess clustering runs interrupted by SIGTERM,
a GALAH_FI ``kill`` fault (os._exit at a random dispatch or durable-
write site), or a filesystem fault (enospc / eio / torn-write inside
io/atomic.py), then resumed until complete. Every iteration asserts
the resumed cluster definition is byte-identical to the uninterrupted
reference, no corrupt artifact or ``.tmp`` debris remains in the
checkpoint dir, and the run report records the interruption/resume
chain.

Slow tier (each iteration is 2-3 subprocess runs with a fresh
interpreter): select with ``-m chaos`` or ``GALAH_RUN_SLOW=1``.
"""

import importlib.util
import os
import pathlib

import pytest

_SCRIPT = (pathlib.Path(__file__).parent.parent / "scripts"
           / "chaos_run.py")


def _load_chaos_run():
    spec = importlib.util.spec_from_file_location("chaos_run",
                                                  str(_SCRIPT))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_smoke_ten_kill_points(tmp_path):
    chaos_run = _load_chaos_run()
    failures = chaos_run.run_harness(iterations=10, seed=11,
                                     workdir=str(tmp_path),
                                     verbose=False)
    assert failures == 0


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_covers_every_interruption_mode():
    """The 10-iteration schedule must include every mode at least
    once — a smoke that only ever drew sigterm proves nothing about
    the fault kinds."""
    chaos_run = _load_chaos_run()
    schedule = [chaos_run.MODES[i % len(chaos_run.MODES)]
                for i in range(10)]
    assert set(schedule) == set(chaos_run.MODES)


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_fleet_smoke(tmp_path):
    """Bounded fleet chaos: one iteration per FLEET_MODES entry —
    SIGKILL a worker group, SIGKILL the scheduler, SIGTERM the
    scheduler — each resumed and held to byte-identical clusters,
    zero debris, and a coherent reassignment chain (the full
    10-iteration gate is scripts/chaos_run.py --workload fleet)."""
    chaos_run = _load_chaos_run()
    failures = chaos_run.run_fleet_harness(iterations=3, seed=11,
                                           workdir=str(tmp_path),
                                           verbose=False)
    assert failures == 0


def test_fleet_schedule_covers_scheduler_kills():
    """Any 3+ fleet iterations must kill the scheduler itself at
    least once — worker kills alone never exercise event-log replay
    or orphan adoption."""
    chaos_run = _load_chaos_run()
    for n in (3, 10):
        schedule = [chaos_run.FLEET_MODES[i % len(chaos_run.FLEET_MODES)]
                    for i in range(n)]
        assert "sched-kill" in schedule
        assert set(schedule) == set(chaos_run.FLEET_MODES)


def test_scan_artifacts_flags_debris_and_corruption(tmp_path):
    """The artifact audit itself (fast, not marked chaos): .tmp debris
    and unparseable json are findings; checksum-rejected torn jsonl
    lines are recoverable-by-design and are NOT."""
    chaos_run = _load_chaos_run()
    ck = tmp_path / "ck"
    ck.mkdir()
    assert chaos_run.scan_artifacts(str(ck)) == []

    from galah_tpu.io import atomic

    atomic.append_jsonl(str(ck / "clusters.jsonl"), {"i": 0})
    with open(ck / "clusters.jsonl", "ab") as f:
        f.write(b'{"torn')  # torn tail: readable-with-recovery, fine
    atomic.write_json(str(ck / "fingerprint.json"), {"ok": True})
    assert chaos_run.scan_artifacts(str(ck)) == []

    (ck / "fingerprint.json.123.tmp").write_bytes(b"debris")
    (ck / "bad.json").write_bytes(b"{not json")
    problems = chaos_run.scan_artifacts(str(ck))
    assert len(problems) == 2
    assert any(".tmp" in p for p in problems)
    assert any("bad.json" in p for p in problems)


def test_fault_env_specs_parse(monkeypatch):
    """Every GALAH_FI spec the harness generates must parse into an
    injector (a typo here would silently chaos-test nothing)."""
    chaos_run = _load_chaos_run()
    from galah_tpu.resilience import faults

    for mode in chaos_run.MODES:
        env = chaos_run.fault_env(mode, seed=3)
        if mode == "sigterm":
            assert env is None
            continue
        monkeypatch.setenv("GALAH_FI", env["GALAH_FI"])
        faults.reset()
        inj = faults.get_injector()
        assert inj is not None, mode
        kinds = {s.kind for s in inj._specs}
        assert kinds == {"kill" if mode == "kill" else mode}
    monkeypatch.delenv("GALAH_FI")
    faults.reset()
