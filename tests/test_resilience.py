"""Resilience layer units (galah_tpu/resilience/).

Retry policy, deterministic fault injector, and the dispatch
supervisor's retry -> validate -> demote machinery — all exercised on
CPU with seeded faults, no hardware misbehavior required.
"""

import threading
import time

import pytest

from galah_tpu.resilience import dispatch as rdispatch
from galah_tpu.resilience import faults
from galah_tpu.resilience.dispatch import (
    DispatchSupervisor,
    expect_ani_values,
    expect_len,
)
from galah_tpu.resilience.faults import FaultInjector, FaultSpec, parse_spec
from galah_tpu.resilience.policy import (
    DeadlineExceeded,
    DeviceLostError,
    GarbageResultError,
    RetryPolicy,
    TransientDispatchError,
    call_with_retry,
    is_retryable,
    run_with_deadline,
)
from galah_tpu.utils import timing

pytestmark = pytest.mark.fault_injection


@pytest.fixture(autouse=True)
def _clean_state():
    faults.reset()
    rdispatch.reset(RetryPolicy(max_attempts=3, base_delay=0.0,
                                jitter=0.0))
    timing.reset()
    yield
    faults.reset()
    rdispatch.reset()
    timing.reset()


# -- RetryPolicy ----------------------------------------------------


def test_delay_schedule_exponential_capped():
    p = RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=0.5,
                    jitter=0.0)
    assert p.delay(0) == pytest.approx(0.1)
    assert p.delay(1) == pytest.approx(0.2)
    assert p.delay(2) == pytest.approx(0.4)
    assert p.delay(3) == pytest.approx(0.5)  # capped
    assert p.delay(10) == pytest.approx(0.5)


def test_seeded_jitter_is_deterministic_per_site_attempt():
    a = RetryPolicy(seed=7, jitter=0.5)
    b = RetryPolicy(seed=7, jitter=0.5)
    assert a.delay(1, "dispatch.ani") == b.delay(1, "dispatch.ani")
    # different site or attempt decorrelates, same bounds hold
    d = a.delay(1, "dispatch.ani")
    lo, hi = 0.05, 0.15  # base 0.05 * 2^1 = 0.1, jitter 0.5
    assert lo <= d <= hi


def test_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)


def test_from_env_overrides(monkeypatch):
    monkeypatch.setenv("GALAH_RETRY_MAX_ATTEMPTS", "7")
    monkeypatch.setenv("GALAH_RETRY_BASE_DELAY", "0.25")
    monkeypatch.setenv("GALAH_RETRY_SEED", "3")
    p = RetryPolicy.from_env()
    assert p.max_attempts == 7
    assert p.base_delay == 0.25
    assert p.seed == 3
    # explicit keyword wins over env
    assert RetryPolicy.from_env(max_attempts=2).max_attempts == 2


def test_is_retryable_classes():
    assert is_retryable(TransientDispatchError("x"))
    assert is_retryable(DeviceLostError("x"))
    assert is_retryable(GarbageResultError("x"))
    assert is_retryable(OSError("flake"))
    assert is_retryable(DeadlineExceeded("slow"))
    assert not is_retryable(FileNotFoundError("gone"))
    assert not is_retryable(ValueError("deterministic"))

    class XlaRuntimeError(Exception):  # matched by NAME, not import
        pass

    assert is_retryable(XlaRuntimeError("jax runtime"))


# -- call_with_retry ------------------------------------------------


def test_retry_recovers_after_transients():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientDispatchError("flaky")
        return "ok"

    pol = RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0)
    assert call_with_retry(fn, pol, sleep=lambda _d: None) == "ok"
    assert calls["n"] == 3


def test_retry_exhaustion_reraises_last():
    pol = RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise TransientDispatchError(f"attempt {calls['n']}")

    with pytest.raises(TransientDispatchError, match="attempt 2"):
        call_with_retry(fn, pol, sleep=lambda _d: None)


def test_non_retryable_propagates_immediately():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise ValueError("deterministic bug")

    pol = RetryPolicy(max_attempts=5, base_delay=0.0)
    with pytest.raises(ValueError):
        call_with_retry(fn, pol, sleep=lambda _d: None)
    assert calls["n"] == 1


def test_total_budget_stops_retry_loop():
    pol = RetryPolicy(max_attempts=10, base_delay=10.0, jitter=0.0,
                      total_budget=0.5)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise TransientDispatchError("flaky")

    with pytest.raises(TransientDispatchError):
        call_with_retry(fn, pol, sleep=lambda _d: None)
    # first delay (10 s) already exceeds the 0.5 s budget: one attempt
    assert calls["n"] == 1


def test_on_retry_fires_per_backoff():
    seen = []
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientDispatchError("flaky")
        return 1

    pol = RetryPolicy(max_attempts=5, base_delay=0.0, jitter=0.0)
    call_with_retry(fn, pol, on_retry=lambda a, e: seen.append(a),
                    sleep=lambda _d: None)
    assert seen == [0, 1]


def test_attempt_deadline_abandons_hang():
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        run_with_deadline(lambda: time.sleep(5.0), deadline=0.05)
    assert time.monotonic() - t0 < 2.0


def test_deadline_passthrough_value_and_error():
    assert run_with_deadline(lambda: 42, deadline=1.0) == 42
    with pytest.raises(KeyError):
        run_with_deadline(lambda: {}["x"], deadline=1.0)


# -- fault injector -------------------------------------------------


def test_parse_spec_grammar():
    specs = parse_spec(
        "site=dispatch.ani;kind=raise;prob=0.5;seed=7;max=2"
        "|site=collective.;kind=hang;hang=1.5")
    assert len(specs) == 2
    assert specs[0] == FaultSpec(site="dispatch.ani", kind="raise",
                                 prob=0.5, seed=7, max_faults=2)
    assert specs[1].kind == "hang"
    assert specs[1].hang_seconds == 1.5


def test_parse_spec_rejects_garbage():
    with pytest.raises(ValueError):
        parse_spec("site=x;frequency=9")
    with pytest.raises(ValueError):
        parse_spec("kind=explode")
    with pytest.raises(ValueError):
        FaultSpec(prob=1.5)


def test_injector_deterministic_and_capped():
    def fire_log(inj, n=20):
        log = []
        for _ in range(n):
            try:
                inj.before_dispatch("dispatch.ani")
                log.append(0)
            except TransientDispatchError:
                log.append(1)
        return log

    spec = FaultSpec(site="dispatch.ani", kind="raise", prob=0.4,
                     seed=11)
    a = fire_log(FaultInjector([spec]))
    b = fire_log(FaultInjector([spec]))
    assert a == b and sum(a) > 0
    capped = FaultInjector([FaultSpec(site="dispatch.ani", prob=1.0,
                                      max_faults=3)])
    assert sum(fire_log(capped)) == 3
    assert capped.fired() == 3


def test_injector_site_prefix_match():
    inj = FaultInjector([FaultSpec(site="dispatch.", prob=1.0)])
    with pytest.raises(TransientDispatchError):
        inj.before_dispatch("dispatch.sketch-minhash")
    inj.before_dispatch("collective.host-rows")  # no fault


def test_injector_kinds():
    with pytest.raises(DeviceLostError):
        FaultInjector([FaultSpec(kind="device-lost")]).before_dispatch("x")
    slept = []
    inj = FaultInjector([FaultSpec(kind="hang", hang_seconds=7.0)],
                        sleep=slept.append)
    inj.before_dispatch("x")
    assert slept == [7.0]
    garb = FaultInjector([FaultSpec(kind="garbage")])
    garb.before_dispatch("x")  # garbage never raises pre-dispatch
    assert garb.corrupt("x", [1, 2, 3]) == [1, 2]


def test_env_discovery(monkeypatch):
    monkeypatch.setenv("GALAH_FI", "site=dispatch.ani;kind=raise")
    faults.reset()
    inj = faults.get_injector()
    assert inj is not None
    with pytest.raises(TransientDispatchError):
        inj.before_dispatch("dispatch.ani")
    faults.reset()
    monkeypatch.delenv("GALAH_FI")
    assert faults.get_injector() is None


# -- dispatch supervisor --------------------------------------------


def test_supervisor_transient_fault_retried_to_success():
    faults.install(FaultInjector(
        [FaultSpec(site="s", kind="raise", prob=1.0, max_faults=2)]))
    sup = DispatchSupervisor(RetryPolicy(max_attempts=3, base_delay=0.0,
                                         jitter=0.0))
    out = sup.run("s", lambda: [0.5], validate=expect_ani_values(1))
    assert out == [0.5]
    assert not sup.demotions()
    assert timing.GLOBAL.counters().get("retries[s]") == 2


def test_supervisor_persistent_fault_demotes_to_fallback():
    faults.install(FaultInjector(
        [FaultSpec(site="s", kind="raise", prob=1.0)]))
    sup = DispatchSupervisor(RetryPolicy(max_attempts=2, base_delay=0.0,
                                         jitter=0.0))
    primary_calls = {"n": 0}

    def primary():
        primary_calls["n"] += 1
        return [0.5]

    out = sup.run("s", primary, fallback=lambda: [0.25])
    assert out == [0.25]
    dems = sup.demotions()
    assert [d.site for d in dems] == ["s"]
    assert "TransientDispatchError" in dems[0].reason
    assert timing.GLOBAL.counters().get("demoted[s]") == 1
    # demoted site routes straight to the fallback; the primary (and
    # the injector) are never consulted again
    out2 = sup.run("s", primary, fallback=lambda: [0.75])
    assert out2 == [0.75]
    assert primary_calls["n"] == 0


def test_supervisor_no_fallback_reraises():
    faults.install(FaultInjector([FaultSpec(site="s", prob=1.0)]))
    sup = DispatchSupervisor(RetryPolicy(max_attempts=2, base_delay=0.0,
                                         jitter=0.0))
    with pytest.raises(TransientDispatchError):
        sup.run("s", lambda: 1)
    assert not sup.demotions()  # nothing to demote TO


def test_supervisor_garbage_result_caught_by_validator():
    faults.install(FaultInjector(
        [FaultSpec(site="s", kind="garbage", prob=1.0, max_faults=1)]))
    sup = DispatchSupervisor(RetryPolicy(max_attempts=3, base_delay=0.0,
                                         jitter=0.0))
    out = sup.run("s", lambda: [0.1, 0.2], validate=expect_len(2))
    assert out == [0.1, 0.2]  # truncated result rejected, retry clean
    assert timing.GLOBAL.counters().get("retries[s]") == 1


def test_supervisor_hang_caught_by_attempt_deadline():
    faults.install(FaultInjector(
        [FaultSpec(site="s", kind="hang", hang_seconds=30.0,
                   max_faults=1)]))
    sup = DispatchSupervisor(RetryPolicy(
        max_attempts=2, base_delay=0.0, jitter=0.0,
        attempt_deadline=0.1))
    t0 = time.monotonic()
    assert sup.run("s", lambda: "done") == "done"
    assert time.monotonic() - t0 < 5.0
    assert timing.GLOBAL.counters().get("retries[s]") == 1


def test_validators():
    expect_len(2)([1, 2])
    with pytest.raises(GarbageResultError):
        expect_len(2)([1])
    with pytest.raises(GarbageResultError):
        expect_len(1)(object())
    v = expect_ani_values(3)
    v([None, 0.0, 1.0])
    with pytest.raises(GarbageResultError):
        v([None, 0.5, 1.5])  # out of range
    with pytest.raises(GarbageResultError):
        v([None, float("nan"), 0.5])  # NaN
    with pytest.raises(GarbageResultError):
        v([0.5, 0.5])  # wrong length


def test_supervisor_thread_safety_single_demotion():
    faults.install(FaultInjector([FaultSpec(site="s", prob=1.0)]))
    sup = DispatchSupervisor(RetryPolicy(max_attempts=1, base_delay=0.0))
    results = []

    def worker():
        results.append(sup.run("s", lambda: "p", fallback=lambda: "f"))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == ["f"] * 8
    assert len(sup.demotions()) == 1  # demoted exactly once
