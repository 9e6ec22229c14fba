"""GalahSan runtime concurrency sanitizer: deterministic two-thread
reproducers for every finding kind (on synthetic modules with isolated
Sanitizer instances), the report/summary shapes, and the tier-1 gate
that the repo's own threaded modules run violation-free under the real
workload (conftest arms the process-wide GLOBAL via GALAH_SAN=1)."""

import json
import threading
import types

import pytest

from galah_tpu.analysis import sanitizer
from galah_tpu.analysis.concurrency_check import THREADED_MODULES
from galah_tpu.analysis.sanitizer import (SanDict, SanList, SanLock,
                                          Sanitizer)


def make_module(name="synth_mod", **attrs):
    mod = types.ModuleType(name)
    for k, v in attrs.items():
        setattr(mod, k, v)
    return mod


def kinds(san):
    return sorted(f["kind"] for f in san.findings())


def errors_by_kind(san):
    out = {}
    for f in san.errors():
        out.setdefault(f["kind"], []).append(f)
    return out


# ---------------------------------------------------------------------------
# SanLock mechanics
# ---------------------------------------------------------------------------


def test_sanlock_wraps_once_and_upgrades_to_declared():
    san = Sanitizer()
    raw = threading.Lock()
    a = san._wrap_lock(raw, "m.py:_A", declared=False)
    assert isinstance(a, SanLock) and not a.declared
    # same inner object -> same proxy; a declared wrap upgrades it
    b = san._wrap_lock(raw, "m.py:_A", declared=True)
    assert b is a and a.declared
    assert san._wrap_lock(a, "m.py:_A", declared=True) is a
    with a:
        assert a.locked()
    assert not a.locked()
    assert a.acquisitions == 1


def test_reentrant_same_name_pair_records_no_edge():
    """Two SanLocks sharing a canonical name (per-instance locks of
    one class) must not produce a self-edge."""
    san = Sanitizer()
    a = san._wrap_lock(threading.Lock(), "m.py:C._lock", declared=True)
    b = san._wrap_lock(threading.Lock(), "m.py:C._lock", declared=True)
    with a:
        with b:
            pass
    assert san.edges == {}


# ---------------------------------------------------------------------------
# Lock-order reproducers (synthetic modules)
# ---------------------------------------------------------------------------


def test_inversion_reproducer():
    mod = make_module(LOCK_ORDER=["_A", "_B"],
                      _A=threading.Lock(), _B=threading.Lock())
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    with mod._B:
        with mod._A:  # declared order says _A before _B
            pass
    by = errors_by_kind(san)
    assert list(by) == ["inversion"]
    (f,) = by["inversion"]
    assert f["locks"] == ["synth_mod.py:_B", "synth_mod.py:_A"]
    assert "tests/test_sanitizer.py:" in f["where"]
    assert "declares synth_mod.py:_A before" in f["detail"]
    # the declared pair itself was never exercised in order
    assert san.summary()["inversions"] == 1
    assert san.summary()["unexercised"] == 1


def test_declared_order_exercised_is_clean():
    mod = make_module(LOCK_ORDER=["_A", "_B"],
                      _A=threading.Lock(), _B=threading.Lock())
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    with mod._A:
        with mod._B:
            pass
    assert san.errors() == []
    assert san.summary()["unexercised"] == 0
    assert san.summary()["edges_observed"] == 1


def test_undeclared_edge_reproducer():
    """Two DECLARED locks nested with no LOCK_ORDER pair covering
    them: an ordering obligation the annotations never took."""
    mod = make_module(GUARDED_BY={"_X": "_A", "_Y": "_C"},
                      _A=threading.Lock(), _C=threading.Lock(),
                      _X={}, _Y={})
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    with mod._A:
        with mod._C:
            pass
    by = errors_by_kind(san)
    assert list(by) == ["undeclared_edge"]
    (f,) = by["undeclared_edge"]
    assert f["locks"] == ["synth_mod.py:_A", "synth_mod.py:_C"]
    assert "no LOCK_ORDER declares this pair" in f["detail"]


def test_undeclared_acquisition_reproducer():
    """A nested acquisition involving a lock absent from every
    annotation is an error; a BARE acquisition of the same lock is
    not (the repo keeps helper locks that never nest)."""
    mod = make_module(GUARDED_BY={"_X": "_A"},
                      _A=threading.Lock(), _U=threading.Lock(),
                      _X={})
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    with mod._U:  # bare: no finding
        pass
    assert san.errors() == []
    with mod._A:
        with mod._U:  # nested involvement: finding
            pass
    by = errors_by_kind(san)
    assert list(by) == ["undeclared_acquisition"]
    (f,) = by["undeclared_acquisition"]
    assert "synth_mod.py:_U" in f["detail"]
    assert "tests/test_sanitizer.py:" in f["where"]


# ---------------------------------------------------------------------------
# Race reproducers (GUARDED_BY mutation checks)
# ---------------------------------------------------------------------------


def _registry_module():
    class Registry:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def good_add(self, item):
            with self._lock:
                self._items.append(item)

        def bad_add(self, item):
            self._items.append(item)

    return make_module(
        GUARDED_BY={"Registry._items": "Registry._lock"},
        Registry=Registry), Registry


def test_unguarded_instance_mutation_from_worker_is_a_race():
    mod, Registry = _registry_module()
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    reg = mod.Registry()
    assert isinstance(reg._lock, SanLock)
    assert isinstance(reg._items, SanList)
    reg.good_add(1)          # locked: clean
    reg.bad_add(2)           # owner thread, lock never foreign: clean
    assert san.errors() == []
    t = threading.Thread(target=reg.bad_add, args=(3,))
    t.start()
    t.join()
    by = errors_by_kind(san)
    assert list(by) == ["race"]
    (f,) = by["race"]
    assert f["locks"] == ["synth_mod.py:Registry._lock"]
    assert "Registry._items mutated (append)" in f["detail"]
    assert "tests/test_sanitizer.py:" in f["where"]


def test_owner_rebind_after_foreign_touch_is_a_race():
    mod, Registry = _registry_module()
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    reg = mod.Registry()
    reg._items = []          # still single-owner: clean
    assert san.errors() == []
    t = threading.Thread(target=reg.good_add, args=(1,))
    t.start()
    t.join()
    reg._items = []          # lock is now shared: rebind needs it
    by = errors_by_kind(san)
    assert list(by) == ["race"]
    assert "_items rebind" in by["race"][0]["detail"]
    # ... and rebinding WITH the lock held is clean
    san2 = Sanitizer()
    mod2, _ = _registry_module()
    san2.install_module(mod2, "synth_mod.py")
    reg2 = mod2.Registry()
    t = threading.Thread(target=reg2.good_add, args=(1,))
    t.start()
    t.join()
    with reg2._lock:
        reg2._items = []
    assert san2.errors() == []


def test_unguarded_global_container_mutation_is_a_race():
    mod = make_module(GUARDED_BY={"_CACHE": "_LOCK"},
                      _LOCK=threading.Lock(), _CACHE={})
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    assert isinstance(mod._CACHE, SanDict)

    def locked_write():
        with mod._LOCK:
            mod._CACHE["a"] = 1

    def bare_write():
        mod._CACHE["b"] = 2

    t = threading.Thread(target=locked_write)
    t.start()
    t.join()
    assert san.errors() == []
    t = threading.Thread(target=bare_write)
    t.start()
    t.join()
    by = errors_by_kind(san)
    assert list(by) == ["race"]
    (f,) = by["race"]
    assert "synth_mod.py:_CACHE mutated (__setitem__)" in f["detail"]


def test_duplicate_races_dedup_by_site():
    mod = make_module(GUARDED_BY={"_CACHE": "_LOCK"},
                      _LOCK=threading.Lock(), _CACHE={})
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")

    def bare_write(i):
        mod._CACHE[i] = i  # same site every iteration

    for i in range(3):
        t = threading.Thread(target=bare_write, args=(i,))
        t.start()
        t.join()
    assert san.summary()["races"] == 1


# ---------------------------------------------------------------------------
# Report / summary shapes
# ---------------------------------------------------------------------------


def test_summary_and_report_shape(tmp_path, monkeypatch):
    mod = make_module(LOCK_ORDER=["_A", "_B"],
                      _A=threading.Lock(), _B=threading.Lock())
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    with mod._A:
        with mod._B:
            pass
    s = san.summary()
    assert s == {"enabled": True, "modules": 1, "locks": 2,
                 "declared_locks": 2, "acquisitions": 2,
                 "edges_observed": 1, "edges_declared": 1,
                 "undeclared_acquisitions": 0, "undeclared_edges": 0,
                 "inversions": 0, "races": 0, "unexercised": 0}
    rep = san.report()
    assert rep["version"] == 1
    assert rep["modules"] == ["synth_mod.py"]
    assert rep["locks"]["synth_mod.py:_A"]["declared"]
    assert rep["edges"][0]["held"] == "synth_mod.py:_A"
    assert rep["declared_order"] == [{"outer": "synth_mod.py:_A",
                                     "inner": "synth_mod.py:_B",
                                     "module": "synth_mod.py"}]
    out = tmp_path / "san.json"
    assert san.write_report(str(out)) == str(out)
    assert json.loads(out.read_text())["summary"] == s
    # env-var default path
    env_out = tmp_path / "env.json"
    monkeypatch.setenv("GALAH_SAN_REPORT", str(env_out))
    san.write_report()
    assert env_out.exists()


def test_reset_observations_keeps_instrumentation():
    mod = make_module(LOCK_ORDER=["_A", "_B"],
                      _A=threading.Lock(), _B=threading.Lock())
    san = Sanitizer()
    san.install_module(mod, "synth_mod.py")
    with mod._B:
        with mod._A:
            pass
    assert san.errors()
    san.reset_observations()
    assert san.errors() == []
    assert san.summary()["acquisitions"] == 0
    with mod._A:  # still instrumented
        pass
    assert san.summary()["acquisitions"] == 1


def test_enabled_flag_parsing(monkeypatch):
    monkeypatch.delenv("GALAH_SAN", raising=False)
    assert not sanitizer.enabled()
    monkeypatch.setenv("GALAH_SAN", "0")
    assert not sanitizer.enabled()
    monkeypatch.setenv("GALAH_SAN", "1")
    assert sanitizer.enabled()


# ---------------------------------------------------------------------------
# The tier-1 gate: the repo's own threaded modules, under load
# ---------------------------------------------------------------------------


def _exercise_threaded_modules():
    """A bounded cross-module workload touching the instrumented
    locks from several threads: metrics registry + instances, events
    warn-once, stage timing with adoption, dispatch demotion state."""
    import logging

    from galah_tpu.obs import events, metrics
    from galah_tpu.utils import timing

    log = logging.getLogger("galah.san.gate")
    token = timing.stage_token()

    def work(i):
        with timing.adopt(token):
            with timing.stage(f"san_gate_{i % 2}"):
                timing.counter("san_gate", 1)
                metrics.counter("san.gate.count").inc()
                metrics.gauge("san.gate.gauge").set(i)
                metrics.histogram("san.gate.hist").observe(float(i))
                metrics.pipeline_occupancy(0.5, stage="san_gate")
                events.warn_once(log, "san gate warning",
                                 key=f"san-gate-{i % 2}")

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    work(99)
    metrics.snapshot()
    timing.GLOBAL.items()


def test_repo_runs_violation_free_under_sanitizer():
    """THE GATE: with GALAH_SAN=1 (conftest), the repo's declared
    lock annotations must hold under a real multi-threaded workload —
    zero undeclared acquisitions, zero unordered edges, zero
    inversions, zero races. This test failing means an annotation
    drifted from runtime behavior; fix the code or the annotation,
    don't relax the gate."""
    if not sanitizer.GLOBAL.installed:
        pytest.skip("GALAH_SAN=0: process-wide sanitizer not armed")
    _exercise_threaded_modules()
    errs = sanitizer.GLOBAL.errors()
    assert errs == [], json.dumps(errs, indent=1)
    s = sanitizer.GLOBAL.summary()
    assert s["modules"] == len(THREADED_MODULES)
    assert s["acquisitions"] > 0
    assert (s["undeclared_acquisitions"] == s["undeclared_edges"]
            == s["inversions"] == s["races"] == 0)


def test_global_summary_feeds_run_report():
    if not sanitizer.GLOBAL.installed:
        pytest.skip("GALAH_SAN=0: process-wide sanitizer not armed")
    assert sanitizer.summary_if_enabled() == sanitizer.GLOBAL.summary()

    from galah_tpu.obs import report as report_mod

    rep = report_mod.assemble("test", argv=["galah-tpu", "test"])
    assert rep["version"] == report_mod.REPORT_VERSION
    assert rep["sanitizer"]["enabled"] is True
    rendered = report_mod.render(rep)
    assert "concurrency sanitizer (GalahSan):" in rendered
    rep2 = json.loads(json.dumps(rep))
    rep2["sanitizer"]["races"] = 2
    out = report_mod.diff(rep, rep2)
    assert "sanitizer drift:" in out
    assert "races: 0 -> 2 (+2)" in out
