"""Retry policy: exponential backoff + jitter, per-attempt deadline,
overall budget.

The failure mode this guards against: a wedged device turns one stuck
dispatch into an hours-long hang that a watchdog can only kill from
outside.
Every device dispatch and collective in the pipeline is wrapped in
`call_with_retry` via resilience/dispatch.py, so a transient fault costs
one backoff sleep instead of the run, and a hang is abandoned at the
per-attempt deadline instead of holding the process hostage.

Jitter is deterministic per (site, attempt) when the policy carries a
seed — reproducibility of retry schedules is what makes the fault-
injection tests (tests/test_resilience.py) bit-stable.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import threading
import time
from typing import Callable, Optional, Tuple, Type, TypeVar

from galah_tpu.utils import timing

logger = logging.getLogger(__name__)

T = TypeVar("T")

# Concurrency contract, machine-checked by `galah-tpu lint` (GL8xx):
# this module spawns attempt threads but owns no locked shared state —
# run_with_deadline's result box is per-call and handed off through a
# threading.Event.
GUARDED_BY = {}
LOCK_ORDER = []


class TransientDispatchError(RuntimeError):
    """A dispatch fault worth retrying (injected or classified)."""


class DeviceLostError(RuntimeError):
    """The accelerator went away mid-run (lost host, preemption)."""


class GarbageResultError(RuntimeError):
    """A dispatch returned a result that fails shape/range validation."""


class DeadlineExceeded(TimeoutError):
    """An attempt outlived its per-attempt deadline and was abandoned."""


#: Exception types retried by default. ValueError/KeyError and friends
#: are deterministic — retrying them only delays the real traceback.
RETRYABLE_TYPES: Tuple[Type[BaseException], ...] = (
    OSError,
    ConnectionError,
    TimeoutError,          # includes DeadlineExceeded
    TransientDispatchError,
    DeviceLostError,
    GarbageResultError,
)

#: Exception type NAMES retried by default — jax runtime errors are
#: matched by name so this module never imports jaxlib.
RETRYABLE_NAMES = frozenset(
    {"XlaRuntimeError", "InternalError", "UnavailableError"})


def is_retryable(exc: BaseException) -> bool:
    if isinstance(exc, FileNotFoundError):
        return False  # a missing path will not appear on retry
    return (isinstance(exc, RETRYABLE_TYPES)
            or type(exc).__name__ in RETRYABLE_NAMES)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Backoff schedule + deadlines for one class of dispatch.

    delay(attempt) = min(max_delay, base_delay * 2^attempt), scaled by
    a deterministic jitter factor in [1 - jitter, 1 + jitter]. The
    per-attempt deadline bounds a single hang; total_budget bounds the
    whole retry loop (sleeps included) so N faulty attempts can never
    exceed the caller's time box.
    """

    max_attempts: int = 3
    base_delay: float = 0.05
    max_delay: float = 2.0
    jitter: float = 0.5
    attempt_deadline: Optional[float] = None
    total_budget: Optional[float] = None
    seed: Optional[int] = None

    def __post_init__(self) -> None:
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1, got {self.max_attempts}")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError(
                f"jitter must be in [0, 1], got {self.jitter}")

    @classmethod
    def from_env(cls, prefix: str = "GALAH_RETRY",
                 defaults: Optional[dict] = None,
                 **overrides) -> "RetryPolicy":
        """Policy with env-var overrides: <prefix>_MAX_ATTEMPTS,
        _BASE_DELAY, _MAX_DELAY, _JITTER, _ATTEMPT_DEADLINE,
        _TOTAL_BUDGET, _SEED. `defaults` seeds values the env may
        override (a caller's site-specific baseline, e.g. the IO
        policy's 0.1 s base delay); explicit keyword overrides win
        over both."""
        spec = {
            "max_attempts": int,
            "base_delay": float,
            "max_delay": float,
            "jitter": float,
            "attempt_deadline": float,
            "total_budget": float,
            "seed": int,
        }
        kwargs = dict(defaults or {})
        for name, conv in spec.items():
            raw = os.environ.get(f"{prefix}_{name.upper()}")
            if raw is not None and raw != "":
                kwargs[name] = conv(raw)
        kwargs.update(overrides)
        return cls(**kwargs)

    def delay(self, attempt: int, site: str = "") -> float:
        """Backoff sleep after failed attempt `attempt` (0-based)."""
        d = min(self.max_delay, self.base_delay * (2.0 ** attempt))
        if self.jitter:
            if self.seed is not None:
                # string seeding is hash-randomization-proof (seeded
                # via sha512 of the bytes), so schedules reproduce
                # across processes
                u = random.Random(
                    f"{self.seed}:{site}:{attempt}").random()
            else:
                # an unseeded policy asked for nondeterministic jitter:
                # this randomizes retry SCHEDULING, never numerics
                u = random.random()  # galah-lint: ignore[GL904]
            d *= 1.0 - self.jitter + 2.0 * self.jitter * u
        return d


def run_with_deadline(fn: Callable[[], T],
                      deadline: Optional[float]) -> T:
    """Run fn, abandoning it (DeadlineExceeded) after `deadline` seconds.

    The attempt runs on a daemon worker thread; on expiry the thread is
    ABANDONED, not cancelled — a dispatch wedged inside a native
    extension cannot be interrupted from Python, and abandoning it is
    exactly what the bench watchdog does from outside the process. The
    leaked thread holds only the attempt's closure; callers retry or
    fall back on a fresh one.
    """
    if deadline is None:
        return fn()
    box: dict = {}
    done = threading.Event()
    # adopt the spawning thread's stage context so any telemetry the
    # attempt emits (dispatch counts, retries) attributes to the stage
    # that issued the dispatch, not to a bare worker thread (GL804)
    token = timing.stage_token()

    def target() -> None:
        try:
            with timing.adopt(token):
                box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=target, daemon=True,
                         name="galah-attempt")
    t.start()
    if not done.wait(deadline):
        raise DeadlineExceeded(
            f"dispatch attempt exceeded {deadline:.1f}s deadline")
    if "error" in box:
        raise box["error"]
    return box["value"]


def call_with_retry(
    fn: Callable[[], T],
    policy: RetryPolicy,
    site: str = "",
    classify: Callable[[BaseException], bool] = is_retryable,
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> T:
    """fn() with the policy's retry schedule.

    Retries only exceptions `classify` accepts; re-raises the last
    error once attempts, the total budget, or the classifier say stop.
    `on_retry(attempt, exc)` fires before each backoff sleep (the
    dispatch supervisor counts retries into the stage report there).
    """
    t0 = time.monotonic()
    last: Optional[BaseException] = None
    for attempt in range(policy.max_attempts):
        try:
            return run_with_deadline(fn, policy.attempt_deadline)
        except BaseException as e:  # noqa: BLE001 - filtered below
            if isinstance(e, (KeyboardInterrupt, SystemExit)):
                raise
            last = e
            if not classify(e) or attempt == policy.max_attempts - 1:
                raise
            d = policy.delay(attempt, site)
            if (policy.total_budget is not None
                    and time.monotonic() - t0 + d > policy.total_budget):
                logger.warning(
                    # the budget is config, not a measurement; retry
                    # counts land in the registry via on_retry
                    # galah-lint: ignore[GL702]
                    "%s: retry budget %.1fs exhausted after attempt "
                    "%d", site or "dispatch", policy.total_budget,
                    attempt + 1)
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            logger.warning(
                # the delay is the policy's schedule, not a measured
                # duration
                # galah-lint: ignore[GL702]
                "%s: attempt %d/%d failed (%s: %s); retrying in "
                "%.2fs", site or "dispatch", attempt + 1,
                policy.max_attempts, type(e).__name__, e, d)
            sleep(d)
    raise last if last is not None else RuntimeError("unreachable")
