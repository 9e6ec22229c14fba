"""Device-cost attribution for jitted/Pallas entry points.

Wall-clock telemetry (utils/timing.py, obs/metrics.py) says how long a
stage took; it cannot say whether the stage was compute-, memory-, or
transfer-bound — the question every sketch-sizing and communication-
avoidance decision needs answered (ROADMAP autotuning item). This
module closes that gap with three measurements per registered entry
point, all landing in the ``device_costs`` section of run_report.json
(schema v3) and, through obs/ledger.py, in the cross-run perf ledger:

  * ``Compiled.cost_analysis()`` — XLA's static FLOP and bytes-accessed
    estimate per executable, captured once per (shape, dtype, static)
    signature at compile time;
  * compile walls — both our own lower+compile timing and the
    jax.monitoring compile-event durations attributed to whichever
    entry is compiling (the same hook stream obs/trace.py records);
  * HBM high-water — ``device.memory_stats()`` where the backend
    provides it (TPU), with a ``jax.live_arrays()`` fallback where it
    does not (CPU), sampled at compiles, periodically at calls, and at
    stage boundaries (``sample_memory``).

No roofline is derived here: the dispatch wall is a host clock and the
static costs are empty for Pallas kernels. A kernel's share of its
roofline comes from the device trace (the benchmark's readers).

Registration is the ``profiled(name)`` decorator stacked ABOVE
``jax.jit`` (the jit decorator stays visible to the GL2xx/GL3xx AST
checkers). The wrapper is the dispatch path itself: it routes calls
through a per-signature AOT ``Compiled`` cache, so cost capture adds no
second trace (tracing tile_stats at K=1000 costs ~25 s — doing it twice
per signature would be a real regression). Anything the AOT path cannot
faithfully express falls back to the plain jitted call, permanently for
that signature:

  * tracer arguments (the entry is being traced inside an outer jit /
    shard_map / eval_shape) — passed straight through;
  * a lower()/compile() failure — plain call, fallback counted;
  * a ``Compiled`` call rejecting our dynamic/static argument split
    (static-declared Python scalars are stripped; a dynamic Python
    scalar would mismatch the pytree) — plain call for that signature,
    with the compile-time costs kept.

Everything here must stay importable without jax (obs/__init__.py's
import discipline): jax is only touched through ``sys.modules.get``.

Profiling is on by default (``GALAH_OBS_PROFILE=0`` disables it); the
fallbacks above mean the worst case of a surprising call pattern is the
exact pre-profiler dispatch behavior, minus the cost rows.
"""

from __future__ import annotations

import functools
import sys
import threading
import time as _time
from typing import Any, Dict, List, Optional

# Concurrency contract, machine-checked by `galah-tpu lint` (GL8xx).
# Per-entry counters live under the entry's own lock; the module-level
# HBM/compile accumulators under _LOCK; the entry registry under
# _REGISTRY_LOCK. None of them nest.
GUARDED_BY = {
    "ProfiledFunction.calls": "ProfiledFunction._lock",
    "ProfiledFunction.plain_calls": "ProfiledFunction._lock",
    "ProfiledFunction.aot_fallbacks": "ProfiledFunction._lock",
    "ProfiledFunction.dispatch_wall_s": "ProfiledFunction._lock",
    "ProfiledFunction.compile_wall_s": "ProfiledFunction._lock",
    "ProfiledFunction.monitored_compile_s": "ProfiledFunction._lock",
    "ProfiledFunction.flops_total": "ProfiledFunction._lock",
    "ProfiledFunction.bytes_total": "ProfiledFunction._lock",
    "ProfiledFunction.signatures": "ProfiledFunction._lock",
    "_REGISTRY": "_REGISTRY_LOCK",
    "_HBM": "_LOCK",
    "_TOTALS": "_LOCK",
}
LOCK_ORDER = ["_REGISTRY_LOCK", "ProfiledFunction._lock", "_LOCK"]

_REGISTRY_LOCK = threading.Lock()
_REGISTRY: List["ProfiledFunction"] = []

_LOCK = threading.Lock()
#: Process-wide HBM high-water state: global peak plus one peak per
#: stage label handed to sample_memory().
_HBM: Dict[str, Any] = {"peak_bytes": None, "source": None,
                        "per_stage": {}}
#: Cross-entry accumulators (compile seconds seen by the jax.monitoring
#: hook that no entry was compiling for, e.g. outer-jit compiles).
_TOTALS: Dict[str, float] = {"monitored_compile_s": 0.0,
                             "unattributed_compile_s": 0.0}

# Entries currently inside lower()+compile(), per thread, innermost
# last — the attribution target for monitoring compile events.
_ACTIVE = threading.local()

_HOOK_INSTALLED = False
_HOOK_LOCK = threading.Lock()

def enabled() -> bool:
    """GALAH_OBS_PROFILE gate (default on; '0'/'false' disables)."""
    from galah_tpu.config import env_value

    raw = (env_value("GALAH_OBS_PROFILE") or "1").strip().lower()
    return raw not in ("0", "false", "no", "off")


def _is_arraylike(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _is_tracer(x: Any) -> bool:
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    tracer = getattr(getattr(jax, "core", None), "Tracer", None)
    return tracer is not None and isinstance(x, tracer)


def _any_tracer(args, kwargs) -> bool:
    return any(_is_tracer(a) for a in args) or \
        any(_is_tracer(v) for v in kwargs.values())


def _descriptor(x: Any):
    """Hashable signature atom: shapes/dtypes for arrays, reprs for
    statics; None when the value defeats signature hashing."""
    if _is_arraylike(x):
        return ("a", tuple(x.shape), str(x.dtype))
    if isinstance(x, (bool, int, float, str, bytes, type(None))):
        return ("s", x)
    r = repr(x)
    return ("s", r) if len(r) <= 200 else None


def _sig_key(args, kwargs):
    parts = []
    for a in args:
        d = _descriptor(a)
        if d is None:
            return None
        parts.append(d)
    for k in sorted(kwargs):
        d = _descriptor(kwargs[k])
        if d is None:
            return None
        parts.append((k, d))
    return tuple(parts)


def _merge_cost_analysis(raw) -> Dict[str, float]:
    """cost_analysis() returns a list of per-computation dicts on this
    jax; sum the numeric keys we care about across entries."""
    if raw is None:
        return {}
    entries = raw if isinstance(raw, (list, tuple)) else [raw]
    out: Dict[str, float] = {}
    for ca in entries:
        if not isinstance(ca, dict):
            continue
        for key in ("flops", "bytes accessed"):
            v = ca.get(key)
            if isinstance(v, (int, float)):
                out[key] = out.get(key, 0.0) + float(v)
    return out


def _memory_analysis_dict(compiled) -> Dict[str, int]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for attr, key in (("argument_size_in_bytes", "argument_bytes"),
                      ("output_size_in_bytes", "output_bytes"),
                      ("temp_size_in_bytes", "temp_bytes"),
                      ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)):
            out[key] = int(v)
    return out


def _on_compile_event(event: str, duration: float, **_kw) -> None:
    """jax.monitoring duration listener: attribute compile seconds to
    whichever entry this thread is compiling, else to the module-wide
    unattributed bucket."""
    if "compil" not in event:
        return
    stack = getattr(_ACTIVE, "stack", None)
    entry = stack[-1] if stack else None
    if entry is not None:
        with entry._lock:
            entry.monitored_compile_s += float(duration)
    with _LOCK:
        _TOTALS["monitored_compile_s"] += float(duration)
        if entry is None:
            _TOTALS["unattributed_compile_s"] += float(duration)


def _install_hook() -> None:
    global _HOOK_INSTALLED
    with _HOOK_LOCK:
        if _HOOK_INSTALLED:
            return
        jax = sys.modules.get("jax")
        if jax is None:
            return
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _on_compile_event)
            _HOOK_INSTALLED = True
        except Exception:  # profiling must never break dispatch
            _HOOK_INSTALLED = True  # don't retry a broken hook API


class _Signature:
    """One compiled specialization of an entry (or its fallback)."""

    __slots__ = ("compiled", "flops", "bytes_accessed", "memory",
                 "plain_call", "compile_s")

    def __init__(self, compiled=None, flops=None, bytes_accessed=None,
                 memory=None, plain_call=False, compile_s=0.0):
        self.compiled = compiled
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.memory = memory or {}
        self.plain_call = plain_call
        self.compile_s = compile_s


class ProfiledFunction:
    """The registered wrapper around one jitted entry point."""

    def __init__(self, name: str, fn) -> None:
        self.name = name
        self.fn = fn
        self._lock = threading.Lock()
        self.signatures: Dict[Any, _Signature] = {}
        self.calls = 0
        self.plain_calls = 0
        self.aot_fallbacks = 0
        self.dispatch_wall_s = 0.0
        self.compile_wall_s = 0.0
        self.monitored_compile_s = 0.0
        self.flops_total = 0.0
        self.bytes_total = 0.0
        functools.update_wrapper(self, fn,
                                 updated=())  # keep fn's __dict__ off

    # -- bookkeeping -------------------------------------------------

    def reset(self) -> None:
        """Zero the counters for a new run; the compiled cache is kept
        (recompiling identical signatures would charge run N+1 for
        run N's compiles)."""
        with self._lock:
            self.calls = 0
            self.plain_calls = 0
            self.aot_fallbacks = 0
            self.dispatch_wall_s = 0.0
            self.compile_wall_s = 0.0
            self.monitored_compile_s = 0.0
            self.flops_total = 0.0
            self.bytes_total = 0.0

    def _account(self, sig: Optional[_Signature], wall: float,
                 plain: bool) -> int:
        with self._lock:
            self.calls += 1
            calls = self.calls
            self.dispatch_wall_s += wall
            if plain:
                self.plain_calls += 1
            if sig is not None:
                if sig.flops is not None:
                    self.flops_total += sig.flops
                if sig.bytes_accessed is not None:
                    self.bytes_total += sig.bytes_accessed
        return calls

    # -- compile path ------------------------------------------------

    def _compile_signature(self, key, args, kwargs) -> _Signature:
        stack = getattr(_ACTIVE, "stack", None)
        if stack is None:
            stack = _ACTIVE.stack = []
        _install_hook()
        stack.append(self)
        t0 = _time.perf_counter()
        try:
            compiled = self.fn.lower(*args, **kwargs).compile()
            dt = _time.perf_counter() - t0
            costs = _merge_cost_analysis(compiled.cost_analysis())
            sig = _Signature(
                compiled=compiled,
                flops=costs.get("flops"),
                bytes_accessed=costs.get("bytes accessed"),
                memory=_memory_analysis_dict(compiled),
                compile_s=dt)
        except Exception:
            dt = _time.perf_counter() - t0
            sig = _Signature(plain_call=True, compile_s=dt)
            with self._lock:
                self.aot_fallbacks += 1
        finally:
            stack.pop()
        with self._lock:
            self.compile_wall_s += dt
            cached = self.signatures.setdefault(key, sig)
        return cached

    def _mark_plain(self, key, sig: _Signature) -> None:
        with self._lock:
            sig.plain_call = True
            self.aot_fallbacks += 1
            self.signatures[key] = sig

    # -- dispatch ----------------------------------------------------

    def __call__(self, *args, **kwargs):
        if not enabled() or _any_tracer(args, kwargs):
            return self.fn(*args, **kwargs)
        key = _sig_key(args, kwargs)
        if key is None:
            return self.fn(*args, **kwargs)
        with self._lock:
            sig = self.signatures.get(key)
        if sig is None:
            sig = self._compile_signature(key, args, kwargs)
            sample_memory(self.name)
        t0 = _time.perf_counter()
        plain = sig.plain_call
        if plain:
            out = self.fn(*args, **kwargs)
        else:
            dyn_args = [a for a in args if _is_arraylike(a)]
            dyn_kwargs = {k: v for k, v in kwargs.items()
                          if _is_arraylike(v)}
            try:
                out = sig.compiled(*dyn_args, **dyn_kwargs)
            except TypeError:
                # our dynamic/static split mismatched the pytree —
                # permanent per-signature fallback, costs kept
                self._mark_plain(key, sig)
                plain = True
                out = self.fn(*args, **kwargs)
        calls = self._account(sig, _time.perf_counter() - t0, plain)
        if calls <= 4 or calls % 16 == 0:
            sample_memory(self.name)
        return out

    # -- reporting ---------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            memory: Dict[str, int] = {}
            for sig in self.signatures.values():
                for k, v in sig.memory.items():
                    memory[k] = max(memory.get(k, 0), v)
            return {
                "calls": self.calls,
                "plain_calls": self.plain_calls,
                "signatures": len(self.signatures),
                "aot_fallbacks": self.aot_fallbacks,
                "flops": self.flops_total or None,
                "bytes_accessed": self.bytes_total or None,
                "dispatch_wall_s": self.dispatch_wall_s,
                "compile_wall_s": self.compile_wall_s,
                "monitored_compile_s": self.monitored_compile_s,
                "memory": memory,
            }


def profiled(name: str):
    """Register a jitted entry point for device-cost attribution:

        @profiled("pairwise.tile_stats")
        @functools.partial(jax.jit, static_argnames=(...))
        def tile_stats_pallas(...): ...

    Stacks above jax.jit (the jit decorator stays visible to the
    GL2xx/GL3xx checkers); also usable as a plain call on a jit object:
    ``_window_hits = profiled("fragment.window_hits")(jax.jit(f))``."""
    def wrap(fn):
        pf = ProfiledFunction(name, fn)
        with _REGISTRY_LOCK:
            _REGISTRY.append(pf)
        return pf
    return wrap


# ---------------------------------------------------------------------------
# HBM high-water sampling
# ---------------------------------------------------------------------------


def sample_memory(stage: Optional[str] = None) -> Optional[int]:
    """Record the current device-memory footprint (bytes, summed over
    local devices) into the global and per-stage high-water marks.

    TPU backends report allocator truth via ``device.memory_stats()``;
    backends without it (CPU) fall back to summing ``jax.live_arrays()``
    — an under-count of allocator slack, but a faithful live-buffer
    high-water. Returns the sampled byte count, or None when jax is not
    up. Never raises."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    current: Optional[int] = None
    source = None
    try:
        stats = []
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            stats.append(ms() if ms is not None else None)
        if any(s for s in stats):
            current = sum(int(s.get("peak_bytes_in_use",
                                    s.get("bytes_in_use", 0)))
                          for s in stats if s)
            source = "memory_stats"
        else:
            current = sum(int(getattr(a, "nbytes", 0))
                          for a in jax.live_arrays())
            source = "live_arrays"
    except Exception:
        return None
    with _LOCK:
        if _HBM["peak_bytes"] is None or current > _HBM["peak_bytes"]:
            _HBM["peak_bytes"] = current
            _HBM["source"] = source
        if stage is not None:
            prev = _HBM["per_stage"].get(stage)
            if prev is None or current > prev:
                _HBM["per_stage"][stage] = current
    return current


# ---------------------------------------------------------------------------
# Snapshot
# ---------------------------------------------------------------------------


def snapshot() -> dict:
    """The ``device_costs`` section of run_report.json (schema v3)."""
    with _REGISTRY_LOCK:
        registry = list(_REGISTRY)
    entries = {pf.name: pf.snapshot() for pf in registry
               if pf.calls or pf.signatures}
    with _LOCK:
        hbm = {"peak_bytes": _HBM["peak_bytes"],
               "source": _HBM["source"],
               "per_stage": dict(_HBM["per_stage"])}
        totals = dict(_TOTALS)
    return {
        "profiling_enabled": enabled(),
        "entries": entries,
        "hbm": hbm,
        "compile": totals,
    }


def reset() -> None:
    """Per-run counter reset (obs.reset_run): compiled caches survive,
    counters and high-water marks do not."""
    with _REGISTRY_LOCK:
        registry = list(_REGISTRY)
    for pf in registry:
        pf.reset()
    with _LOCK:
        _HBM["peak_bytes"] = None
        _HBM["source"] = None
        _HBM["per_stage"] = {}
        _TOTALS["monitored_compile_s"] = 0.0
        _TOTALS["unattributed_compile_s"] = 0.0
