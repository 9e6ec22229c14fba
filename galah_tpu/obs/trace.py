"""Chrome-trace-format span/event recorder (`--trace-events PATH`).

Writes one JSON trace event per line in the Chrome Trace Event "JSON
array" dialect — the file opens with ``[`` and every event line ends
with a comma. Chrome's trace viewer and Perfetto both accept the
unterminated form, and ``close()`` appends a terminator anyway so the
artifact is also plain valid JSON. The recorder is intentionally
append-only and line-buffered: a crashed run still leaves a loadable
trace up to the crash.

What lands in the trace:
  * every StageTimer span (utils/timing.py emits on stage exit) as a
    complete ("ph": "X") event, named by stage and categorized
    "stage";
  * structured events (retries, demotions, quarantines — obs/events.py)
    as instant ("ph": "i") events;
  * JAX compile/lowering activity via ``jax.monitoring`` listeners
    ("cat": "jax"), so compile storms are visible on the same timeline
    as the stages that triggered them;
  * flow events ("ph": "s"/"t"/"f", "cat": "flow" — obs/flow.py) tying
    a pipeline item's producer span to its consumer span across the
    stage-token-adopting worker threads, so a starved handoff shows up
    as a long arrow in Perfetto.

This is complementary to --profile-trace-dir (the XLA profiler): that
captures device timelines below the dispatch boundary; this captures
the host-side pipeline structure above it. Both load in Perfetto.

The same spans also reach the XLA profiler: `annotation(name)` opens a
``jax.profiler.TraceAnnotation`` around every StageTimer stage and
flow span, so inside any profiler session (`--profile-trace-dir`, or a
harness's own `jax.profiler.start_trace`) they land on the xplane's
``/host:CPU`` plane on the device ops' clock. Outside a session a
TraceMe costs one inactive-recorder check.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time
from typing import ContextManager, Optional

logger = logging.getLogger(__name__)

# Concurrency contract, machine-checked by `galah-tpu lint` (GL8xx).
# The module globals RECORDER/_JAX_HOOKS are deliberately NOT guarded:
# start()/stop() run in the single-threaded CLI lifecycle, and the
# emit_* helpers take a local snapshot (`rec = RECORDER`) so a
# concurrent stop() cannot null the reference mid-emit.
GUARDED_BY = {
    "TraceRecorder._fh": "TraceRecorder._lock",
    "TraceRecorder._closed": "TraceRecorder._lock",
}
LOCK_ORDER = ["TraceRecorder._lock"]


class TraceRecorder:
    """Streaming Chrome-trace writer; all emission is lock-serialized."""

    def __init__(self, path: str) -> None:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self.path = path
        self._lock = threading.Lock()
        self._fh = open(path, "w")
        self._fh.write("[\n")
        self._pid = os.getpid()
        # All timestamps are microseconds since recorder start, on the
        # same clock the StageTimer uses (perf_counter).
        self._t0 = time.perf_counter()
        self._closed = False
        self._emit({"ph": "M", "name": "process_name", "pid": self._pid,
                    "tid": 0,
                    "args": {"name": "galah-tpu host pipeline"}})

    def _emit(self, event: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._fh.write(json.dumps(event, sort_keys=True) + ",\n")
            self._fh.flush()

    def _ts(self, perf_t: float) -> float:
        return max(0.0, (perf_t - self._t0) * 1e6)

    def complete(self, name: str, start: float, duration: float,
                 cat: str = "stage", args: Optional[dict] = None) -> None:
        """A finished span: `start` is its time.perf_counter() value."""
        ev = {"ph": "X", "name": name, "cat": cat, "pid": self._pid,
              "tid": threading.get_ident() & 0xFFFFFFFF,
              "ts": round(self._ts(start), 3),
              "dur": round(duration * 1e6, 3)}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, cat: str = "event",
                args: Optional[dict] = None) -> None:
        ev = {"ph": "i", "s": "p", "name": name, "cat": cat,
              "pid": self._pid,
              "tid": threading.get_ident() & 0xFFFFFFFF,
              "ts": round(self._ts(time.perf_counter()), 3)}
        if args:
            ev["args"] = args
        self._emit(ev)

    def flow(self, ph: str, name: str, flow_id: int,
             cat: str = "flow") -> None:
        """A Chrome flow event: ``ph`` is "s" (start), "t" (step) or
        "f" (finish). Events sharing (cat, id, name) are drawn as one
        arrow chain across threads — the producer emits "s" when an
        item enters a boundary queue, the consumer emits "f" when it
        dequeues it, and the viewer links the two slices even though
        they ran on different stage-token-adopting threads."""
        ev = {"ph": ph, "name": name, "cat": cat, "id": int(flow_id),
              "pid": self._pid,
              "tid": threading.get_ident() & 0xFFFFFFFF,
              "ts": round(self._ts(time.perf_counter()), 3)}
        if ph == "f":
            # bind to the enclosing slice's END, so the arrow lands on
            # the consuming span rather than the next unrelated one
            ev["bp"] = "e"
        self._emit(ev)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            # terminate the array so the file is also plain valid JSON
            self._fh.write("{}\n]\n")
            self._fh.close()


# The active recorder, None when --trace-events was not given. The
# emit_* helpers below are the no-op-when-inactive forms every hot
# call site uses (utils/timing.py, obs/events.py).
RECORDER: Optional[TraceRecorder] = None

_JAX_HOOKS = {"installed": False}


def start(path: str) -> TraceRecorder:
    """Open the trace file and route all emission to it."""
    global RECORDER
    if RECORDER is not None:
        RECORDER.close()
    RECORDER = TraceRecorder(path)
    _install_jax_hooks()
    logger.info("Writing Chrome-trace events to %s (load in Perfetto)",
                path)
    return RECORDER


def stop() -> None:
    global RECORDER
    if RECORDER is not None:
        RECORDER.close()
        RECORDER = None


def active() -> bool:
    return RECORDER is not None


def emit_complete(name: str, start_t: float, duration: float,
                  cat: str = "stage",
                  args: Optional[dict] = None) -> None:
    rec = RECORDER
    if rec is not None:
        rec.complete(name, start_t, duration, cat=cat, args=args)


def emit_instant(name: str, cat: str = "event",
                 args: Optional[dict] = None) -> None:
    rec = RECORDER
    if rec is not None:
        rec.instant(name, cat=cat, args=args)


def emit_flow(ph: str, name: str, flow_id: int,
              cat: str = "flow") -> None:
    rec = RECORDER
    if rec is not None:
        rec.flow(ph, name, flow_id, cat=cat)


def annotation(name: str,
               args: Optional[dict] = None) -> ContextManager:
    """A ``jax.profiler.TraceAnnotation`` named `name` (with `args` as
    its trace arguments), so the span shows in any profiler session on
    the device's clock; a no-op where jax was never imported (obs stays
    importable without it)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name, **(args or {}))


def _install_jax_hooks() -> None:
    """Forward jax.monitoring events into the trace, once per process.

    The listener registry has no public unregister, so the listeners
    stay installed and write to whatever recorder is active — a later
    `start()` keeps receiving compile events without re-registering.
    Durations arrive as (event, seconds): jax reports them at
    completion, so the span is drawn ending "now".
    """
    if _JAX_HOOKS["installed"]:
        return
    try:
        from jax import monitoring
    except Exception:  # jax absent/too old: trace still works
        return

    def _on_duration(event: str, duration: float, **kw) -> None:
        try:
            emit_complete(event, time.perf_counter() - float(duration),
                          float(duration), cat="jax")
        except Exception:  # telemetry must never take down a dispatch
            logger.debug("jax duration listener failed", exc_info=True)

    def _on_event(event: str, **kw) -> None:
        try:
            emit_instant(event, cat="jax")
        except Exception:
            logger.debug("jax event listener failed", exc_info=True)

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _JAX_HOOKS["installed"] = True
    except Exception:
        logger.debug("jax.monitoring hook install failed", exc_info=True)
