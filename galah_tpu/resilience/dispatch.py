"""Resilient dispatch: retry, validate, and degrade instead of dying.

The one wrapper every hot-path device dispatch goes through
(cluster/engine.py ANI batches, the backends' batched sketch dispatches,
parallel/distributed.py collectives). Semantics per call:

  1. consult the fault injector (resilience/faults.py) — testability;
  2. run the primary under the retry policy (backoff + per-attempt
     deadline + total budget, resilience/policy.py);
  3. validate the result (garbage-shape returns are a fault class of
     their own) — a failed validation retries like any transient;
  4. on exhausted retries with a fallback available, DEMOTE the site:
     log it, count it into the stage report (``demoted[<site>]``), run
     the fallback, and route every later call at that site straight to
     the fallback — one wedged device must cost seconds, not the run.

Fallbacks are the smaller-blast-radius twin of each dispatch (per-item
CPU sketching for the batched sketch dispatch, a per-pair loop for the
batched ANI call); they run OUTSIDE fault injection so a test that
wedges the primary proves the run completes on the fallback.

Retries are visible in the stage report as ``retries[<site>]``; the
demotion registry is queryable (`demotions()`) and is appended to the
quarantine/stage summary by the CLI.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Callable, Dict, List, Optional, TypeVar

from galah_tpu.obs import events as obs_events
from galah_tpu.obs import metrics as obs_metrics
from galah_tpu.resilience import faults
from galah_tpu.resilience.policy import (
    GarbageResultError,
    RetryPolicy,
    call_with_retry,
)
from galah_tpu.utils import timing

logger = logging.getLogger(__name__)

T = TypeVar("T")

# Concurrency contract, machine-checked by `galah-tpu lint` (GL8xx).
# _demote deliberately emits telemetry AFTER releasing the lock —
# obs/timing take their own locks and must not nest inside this one.
GUARDED_BY = {
    "DispatchSupervisor._demoted": "DispatchSupervisor._lock",
}
LOCK_ORDER = ["DispatchSupervisor._lock"]


@dataclasses.dataclass(frozen=True)
class Demotion:
    """One site's fall from device dispatch to its CPU fallback."""

    site: str
    reason: str


class DispatchSupervisor:
    """Retry policy and per-run demotion state for named dispatch
    sites."""

    def __init__(self, policy: Optional[RetryPolicy] = None) -> None:
        self.policy = policy or RetryPolicy.from_env()
        self._demoted: Dict[str, Demotion] = {}
        self._lock = threading.Lock()

    def demotions(self) -> List[Demotion]:
        with self._lock:
            return list(self._demoted.values())

    def clear_demotions(self) -> None:
        with self._lock:
            self._demoted.clear()

    def is_demoted(self, site: str) -> bool:
        with self._lock:
            return site in self._demoted

    def _demote(self, site: str, exc: BaseException) -> None:
        with self._lock:
            if site in self._demoted:
                return
            self._demoted[site] = Demotion(
                site=site,
                reason=f"{type(exc).__name__}: {exc}")
        timing.counter(f"demoted[{site}]", 1)
        obs_metrics.counter(
            "dispatch.demotions",
            help="Dispatch sites demoted to their CPU fallback").inc()
        obs_events.record("demotion", site=site,
                          reason=f"{type(exc).__name__}: {exc}")
        logger.error(
            "%s: persistent dispatch failure (%s: %s); demoting to "
            "the fallback path for the rest of the run",
            site, type(exc).__name__, exc)

    def run(
        self,
        site: str,
        primary: Callable[[], T],
        fallback: Optional[Callable[[], T]] = None,
        validate: Optional[Callable[[T], None]] = None,
        policy: Optional[RetryPolicy] = None,
    ) -> T:
        """One guarded dispatch at `site`. See the module docstring."""
        if fallback is not None and self.is_demoted(site):
            return fallback()
        pol = policy or self.policy
        injector = faults.get_injector()

        def attempt() -> T:
            if injector is not None:
                injector.before_dispatch(site)
            out = primary()
            if injector is not None:
                out = injector.corrupt(site, out)
            if validate is not None:
                validate(out)
            return out

        def on_retry(attempt_n: int, exc: BaseException) -> None:
            timing.counter(f"retries[{site}]", 1)
            obs_metrics.counter(
                "dispatch.retries",
                help="Dispatch attempts retried after a transient "
                     "failure").inc()
            obs_events.record("retry", site=site, attempt=attempt_n,
                              error=f"{type(exc).__name__}: {exc}")

        try:
            return call_with_retry(attempt, pol, site=site,
                                   on_retry=on_retry)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 - demote-or-reraise
            if fallback is None:
                raise
            self._demote(site, e)
            return fallback()


def expect_len(n: int) -> Callable[[object], None]:
    """Validator: the dispatch must return exactly n results."""

    def check(out) -> None:
        try:
            got = len(out)  # type: ignore[arg-type]
        except TypeError:
            raise GarbageResultError(
                f"dispatch returned non-sequence {type(out).__name__}")
        if got != n:
            raise GarbageResultError(
                f"dispatch returned {got} results for {n} inputs")

    return check


def expect_ani_values(n: int) -> Callable[[object], None]:
    """Validator for ANI batches: n results, each None or a finite
    fraction in [0, 1] — out-of-range values are the garbage-return
    signature of a corrupted device result."""
    check_len = expect_len(n)

    def check(out) -> None:
        check_len(out)
        for v in out:  # type: ignore[union-attr]
            if v is None:
                continue
            f = float(v)
            if not 0.0 <= f <= 1.0:  # NaN fails both comparisons
                raise GarbageResultError(
                    f"dispatch returned out-of-range ANI {v!r}")

    return check


# The supervisor call sites share through these module-level helpers.
# Its retry policy is set per process; its demotions belong to one run,
# so begin_run() clears them at the start of every cluster() and CLI
# run — a site demoted in one run must not reroute the next.
GLOBAL = DispatchSupervisor()


def run(site: str, primary: Callable[[], T],
        fallback: Optional[Callable[[], T]] = None,
        validate: Optional[Callable[[T], None]] = None,
        policy: Optional[RetryPolicy] = None) -> T:
    return GLOBAL.run(site, primary, fallback=fallback,
                      validate=validate, policy=policy)


def demotions() -> List[Demotion]:
    return GLOBAL.demotions()


def begin_run() -> None:
    """Start a run with no site demoted (the retry policy stays)."""
    GLOBAL.clear_demotions()


def reset(policy: Optional[RetryPolicy] = None) -> None:
    """Fresh supervisor (tests; also re-reads the env policy)."""
    global GLOBAL
    GLOBAL = DispatchSupervisor(policy)
