"""Deterministic fault injector for the dispatch layer.

Every retry/timeout/demotion path in this pipeline exists because a
device can wedge, drop, and return garbage — but none of those paths
can wait for hardware to misbehave to be tested. The
injector plants faults at named dispatch sites so the full
retry -> deadline -> demote -> quarantine machinery is exercised on CPU,
seeded and bit-reproducible.

Fault classes (the failure signatures observed on hardware):

  * ``raise``       — TransientDispatchError before the dispatch runs
  * ``device-lost`` — DeviceLostError, the lost-device signature
  * ``hang``        — sleep `hang_seconds` before dispatching (the
                      per-attempt deadline is what must catch this)
  * ``garbage``     — let the dispatch run, then truncate its result so
                      shape validation must reject it

Filesystem fault classes, consulted by io/atomic.py at ``io.atomic.*``
sites (the durable-write layer is where crash consistency must be
proven, so that is where the faults live):

  * ``enospc``      — OSError(ENOSPC) before the write starts
  * ``eio``         — OSError(EIO) before the write starts
  * ``torn-write``  — half the payload reaches disk, then the write
                      fails (the readers' checksum/recovery paths must
                      treat the debris as absent, never as data)
  * ``slow-io``     — sleep `hang_seconds` before the write

And the chaos primitive, firing at ANY registered site (dispatch or
filesystem):

  * ``kill``        — ``os._exit(KILL_EXIT_CODE)``: the process dies
                      mid-operation with no cleanup, unwinding, or
                      flushing — a preemption/SIGKILL stand-in the
                      chaos harness (scripts/chaos_run.py) uses to
                      prove resume-to-identical-clusters

Configuration is programmatic (`install`) or env-driven via GALAH_FI:

    GALAH_FI="site=dispatch.ani;kind=raise;prob=0.3;seed=7;max=2"

Multiple specs are separated by "|". `site` prefix-matches the dispatch
site name ("" matches everything); `max` caps how many faults a spec
fires (so "fail twice then recover" is expressible); `seed` makes the
per-call coin flips reproducible.
"""

from __future__ import annotations

import dataclasses
import errno
import logging
import os
import random
import threading
import time
from typing import List, Optional, Sequence

from galah_tpu.resilience.policy import (
    DeviceLostError,
    TransientDispatchError,
)

logger = logging.getLogger(__name__)

#: Kinds that fire inside io/atomic.py (plus "kill", which fires
#: everywhere).
FS_FAULT_KINDS = ("enospc", "eio", "torn-write", "slow-io")

FAULT_KINDS = (("raise", "device-lost", "hang", "garbage", "kill")
               + FS_FAULT_KINDS)

#: Exit status used by the "kill" kind — the classic SIGKILL status, so
#: harnesses treat an injected kill exactly like a real preemption.
KILL_EXIT_CODE = 137

#: Kinds eligible at dispatch sites (before_dispatch).
_DISPATCH_KINDS = frozenset({"raise", "device-lost", "hang", "kill"})
#: Kinds eligible at filesystem sites (io/atomic.py).
_FS_KINDS = frozenset(FS_FAULT_KINDS) | {"kill"}

# Concurrency contract, machine-checked by `galah-tpu lint` (GL8xx):
# fault draws arrive from prefetch worker threads; the fired counts
# and the install/env-discovery globals each stay under their lock.
GUARDED_BY = {
    "FaultInjector._fired": "FaultInjector._lock",
    "_INSTALLED": "_LOCK",
    "_ENV_CHECKED": "_LOCK",
}
LOCK_ORDER = ["_LOCK"]


@dataclasses.dataclass
class FaultSpec:
    """One fault source: where, how often, what, for how long."""

    site: str = ""               # prefix match against dispatch sites
    kind: str = "raise"
    prob: float = 1.0
    seed: int = 0
    max_faults: Optional[int] = None
    hang_seconds: float = 30.0

    def __post_init__(self) -> None:
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; "
                f"choices: {FAULT_KINDS}")
        if not 0.0 <= self.prob <= 1.0:
            raise ValueError(
                f"fault prob must be in [0, 1], got {self.prob}")


def parse_spec(text: str) -> List[FaultSpec]:
    """Parse the GALAH_FI grammar: ';'-separated key=value fields,
    '|'-separated specs."""
    specs: List[FaultSpec] = []
    for chunk in text.split("|"):
        chunk = chunk.strip()
        if not chunk:
            continue
        kwargs: dict = {}
        for field in chunk.split(";"):
            field = field.strip()
            if not field:
                continue
            if "=" not in field:
                raise ValueError(
                    f"bad GALAH_FI field {field!r} (want key=value)")
            key, value = field.split("=", 1)
            key = key.strip()
            value = value.strip()
            if key == "site":
                kwargs["site"] = value
            elif key == "kind":
                kwargs["kind"] = value
            elif key == "prob":
                kwargs["prob"] = float(value)
            elif key == "seed":
                kwargs["seed"] = int(value)
            elif key == "max":
                kwargs["max_faults"] = int(value)
            elif key == "hang":
                kwargs["hang_seconds"] = float(value)
            else:
                raise ValueError(f"unknown GALAH_FI key {key!r}")
        specs.append(FaultSpec(**kwargs))
    return specs


class FaultInjector:
    """Seeded fault source consulted by the dispatch supervisor.

    Thread-safe: dispatch sites fire from prefetch worker threads too.
    Each spec draws from its own seeded RNG, so whether call k faults
    depends only on (spec seed, how many matching calls preceded it) —
    not on wall clock or thread interleaving of OTHER sites.
    """

    def __init__(self, specs: Sequence[FaultSpec],
                 sleep=time.sleep) -> None:
        self._specs = list(specs)
        self._rngs = [random.Random(f"galah-fi:{s.seed}:{s.site}")
                      for s in self._specs]
        self._fired = [0] * len(self._specs)
        self._sleep = sleep
        self._lock = threading.Lock()

    def fired(self) -> int:
        """Total faults injected so far (all specs)."""
        with self._lock:
            return sum(self._fired)

    def _draw(self, site: str, kinds) -> Optional[FaultSpec]:
        """One seeded coin flip per matching spec of an eligible kind.

        Specs of other kinds are skipped WITHOUT advancing their RNG,
        so a spec's fault schedule depends only on the sequence of
        sites where it was eligible — the property the chaos harness's
        seed sweep relies on."""
        with self._lock:
            for n, spec in enumerate(self._specs):
                if spec.kind not in kinds:
                    continue
                if not site.startswith(spec.site):
                    continue
                if (spec.max_faults is not None
                        and self._fired[n] >= spec.max_faults):
                    continue
                if self._rngs[n].random() < spec.prob:
                    self._fired[n] += 1
                    return spec
        return None

    @staticmethod
    def _kill(site: str) -> None:
        logger.error("fault injector: KILL at %s (exit %d)", site,
                     KILL_EXIT_CODE)
        # os._exit on purpose: no atexit, no finally blocks, no stream
        # flushing — the whole point is to die the way a preemption
        # does, mid-operation.
        os._exit(KILL_EXIT_CODE)

    def before_dispatch(self, site: str) -> None:
        """Called before the real dispatch: may raise, stall, or die."""
        spec = self._draw(site, _DISPATCH_KINDS)
        if spec is None:
            return
        logger.warning("fault injector: %s at %s", spec.kind, site)
        if spec.kind == "kill":
            self._kill(site)
        if spec.kind == "raise":
            raise TransientDispatchError(
                f"injected transient fault at {site}")
        if spec.kind == "device-lost":
            raise DeviceLostError(f"injected device loss at {site}")
        if spec.kind == "hang":
            self._sleep(spec.hang_seconds)

    def filesystem(self, site: str) -> Optional[str]:
        """Called by io/atomic.py before a durable write: may raise
        OSError, stall, die, or ask the writer to tear its own write.

        Returns "torn-write" when the writer should half-write and
        fail (only the writer knows its record layout), else None.
        """
        spec = self._draw(site, _FS_KINDS)
        if spec is None:
            return None
        logger.warning("fault injector: %s at %s", spec.kind, site)
        if spec.kind == "kill":
            self._kill(site)
        if spec.kind == "enospc":
            raise OSError(errno.ENOSPC,
                          f"injected ENOSPC at {site}")
        if spec.kind == "eio":
            raise OSError(errno.EIO, f"injected EIO at {site}")
        if spec.kind == "slow-io":
            self._sleep(spec.hang_seconds)
            return None
        return "torn-write"

    def corrupt(self, site: str, result):
        """Called on the real dispatch's result: may mangle it.

        Only "garbage" specs fire here, from their own draw — a spec
        that raised in before_dispatch never also corrupts.
        """
        with self._lock:
            for n, spec in enumerate(self._specs):
                if spec.kind != "garbage":
                    continue
                if not site.startswith(spec.site):
                    continue
                if (spec.max_faults is not None
                        and self._fired[n] >= spec.max_faults):
                    continue
                if self._rngs[n].random() < spec.prob:
                    self._fired[n] += 1
                    logger.warning(
                        "fault injector: garbage at %s", site)
                    try:
                        return result[:-1]  # wrong length
                    except TypeError:
                        return None
        return result


_INSTALLED: Optional[FaultInjector] = None
_ENV_CHECKED = False
_LOCK = threading.Lock()


def install(injector: Optional[FaultInjector]) -> None:
    """Set (or with None, clear) the process-wide injector."""
    global _INSTALLED, _ENV_CHECKED
    with _LOCK:
        _INSTALLED = injector
        _ENV_CHECKED = True  # explicit install wins over env


def reset() -> None:
    """Drop any installed injector and re-arm env discovery."""
    global _INSTALLED, _ENV_CHECKED
    with _LOCK:
        _INSTALLED = None
        _ENV_CHECKED = False


def get_injector() -> Optional[FaultInjector]:
    """The installed injector, else one built from GALAH_FI, else None."""
    global _INSTALLED, _ENV_CHECKED
    with _LOCK:
        if not _ENV_CHECKED:
            _ENV_CHECKED = True
            text = os.environ.get("GALAH_FI")
            if text:
                _INSTALLED = FaultInjector(parse_spec(text))
                logger.warning(
                    "fault injection ACTIVE from GALAH_FI=%r", text)
        return _INSTALLED
