"""Resilient dispatch layer: retry/backoff, fault injection, graceful
degradation, and bad-input quarantine.

See docs/resilience.md for the operator-facing knobs. Import surface:

  * policy — RetryPolicy, call_with_retry, the exception classes
  * faults — deterministic FaultInjector (GALAH_FI env grammar)
  * dispatch — the DispatchSupervisor every hot-path dispatch routes
    through (retry + validate + demote-to-fallback)
  * quarantine — QuarantineManifest + the --on-bad-genome preflight
"""

from galah_tpu.resilience.policy import (  # noqa: F401
    DeadlineExceeded,
    DeviceLostError,
    GarbageResultError,
    RetryPolicy,
    TransientDispatchError,
    call_with_retry,
)
from galah_tpu.resilience.faults import (  # noqa: F401
    FaultInjector,
    FaultSpec,
)
from galah_tpu.resilience.dispatch import (  # noqa: F401
    DispatchSupervisor,
)
from galah_tpu.resilience.quarantine import (  # noqa: F401
    QuarantineManifest,
)
