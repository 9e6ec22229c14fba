"""Per-stage timing + optional XLA profiler traces.

The reference has no profiling subsystem at all (SURVEY.md §5 —
observability is leveled logging only). Here every pipeline stage is
wrapped in a `stage(...)` span; spans nest, accumulate by name, and the
final report logs one line per stage so a 50k-genome run shows where
wall-clock went (sketching vs pairwise vs ANI refinement vs host
clustering).

The StageTimer is the emission surface of the telemetry layer
(galah_tpu/obs/): every closed span also records into the stage
wall-clock TREE the run report serializes (obs/report.py) and, when a
trace recorder is active (--trace-events), lands as a Chrome-trace
span on the Perfetto timeline (obs/trace.py). While open, a stage also
holds a jax.profiler TraceAnnotation of its name, so any profiler
session shows it on the device ops' clock.

Worker-thread attribution: the active-stage stack is thread-local, but
dispatches can arrive from worker threads (IO prefetch pools, per-
genome sketching workers). A thread with an empty local stack inherits
the innermost stage any thread currently has open (the shared fallback
stack), and thread pools that want exact attribution capture
``stage_token()`` in the spawning thread and run workers under
``adopt(token)``.

`trace_context(dir)` additionally captures a TensorBoard-loadable XLA
profile via jax.profiler (device timelines, HLO cost, HBM traffic) when
the user passes --profile-trace-dir.
"""

from __future__ import annotations

import contextlib
import logging
import threading as _threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from galah_tpu.obs import trace as _obs_trace

logger = logging.getLogger(__name__)

# Concurrency contract, machine-checked by `galah-tpu lint` (GL8xx):
# every post-construction mutation of these must hold the timer's
# lock — spans close and counters arrive from prefetch/sketching
# worker threads. `_active` is thread-local by design and `_t0` is
# construction-only, so neither is locked shared state.
GUARDED_BY = {
    "StageTimer._acc": "StageTimer._lock",
    "StageTimer._counts": "StageTimer._lock",
    "StageTimer._order": "StageTimer._lock",
    "StageTimer._counters": "StageTimer._lock",
    "StageTimer._counter_order": "StageTimer._lock",
    "StageTimer._tree": "StageTimer._lock",
    "StageTimer._tree_order": "StageTimer._lock",
    "StageTimer._shared": "StageTimer._lock",
}
LOCK_ORDER = ["StageTimer._lock"]


class StageTimer:
    """Accumulating named wall-clock spans (nesting allowed)."""

    def __init__(self) -> None:
        self._acc: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._order: List[str] = []
        self._counters: Dict[str, int] = {}
        self._counter_order: List[str] = []
        # wall-clock tree: stage path tuple -> [seconds, count], in
        # first-appearance order (the run report serializes this)
        self._tree: Dict[Tuple[str, ...], List[float]] = {}
        self._tree_order: List[Tuple[str, ...]] = []
        self._t0 = time.perf_counter()
        self._active = _threading.local()
        # Shared fallback stack: mirrors every open stage across ALL
        # threads so dispatch() from a bare worker thread (whose
        # thread-local stack is empty) inherits the spawning stage
        # instead of landing under "?".
        self._shared: List[str] = []
        self._lock = _threading.Lock()

    def _stack(self) -> List[str]:
        st = getattr(self._active, "stack", None)
        if st is None:
            st = self._active.stack = []
        return st

    def current_stage(self) -> Optional[str]:
        """Innermost stage for THIS thread, falling back to the
        innermost stage open on any thread."""
        st = self._stack()
        if st:
            return st[-1]
        with self._lock:
            return self._shared[-1] if self._shared else None

    def stage_token(self) -> Tuple[str, ...]:
        """Capture the current stage path for a worker thread to
        `adopt` — the pass-through form of worker attribution (the
        shared-stack fallback is the implicit one)."""
        st = self._stack()
        if st:
            return tuple(st)
        with self._lock:
            return tuple(self._shared[-1:])

    @contextlib.contextmanager
    def adopt(self, token: Tuple[str, ...]) -> Iterator[None]:
        """Run this thread with `token` as its stage context; restores
        the thread's own stack on exit."""
        st = self._stack()
        saved = st[:]
        st[:] = list(token)
        try:
            yield
        finally:
            st[:] = saved

    def dispatch(self, n: int = 1, sync: bool = False) -> None:
        """Record `n` device dispatches (jit executions / uploads)
        attributed to the innermost active stage — with sync=True they
        are host materializations (each a device->host round trip).
        On a remote-attached device every round trip costs real RTT;
        these counters let the stage report show round trips alongside
        wall-clock, so dispatch-bound stages are visible as such."""
        where = self.current_stage() or "?"
        self.counter(f"{'sync' if sync else 'disp'}[{where}]", n)

    def counter(self, name: str, delta: int) -> None:
        """Accumulate a named integer (work counts, waste counts, ...);
        counters appear at the end of the stage report."""
        with self._lock:  # dispatch counts arrive from worker threads
            if name not in self._counters:
                self._counters[name] = 0
                self._counter_order.append(name)
            self._counters[name] += int(delta)

    def counters(self) -> Dict[str, int]:
        return dict(self._counters)

    @contextlib.contextmanager
    def stage(self, name: str,
              args: Optional[dict] = None) -> Iterator[None]:
        """A named span. `args` (e.g. a pair's index) go on its trace
        events only, not into the tree, which sums spans by name."""
        start = time.perf_counter()
        st = self._stack()
        st.append(name)
        with self._lock:
            self._shared.append(name)
        try:
            with _obs_trace.annotation(name, args):
                yield
        finally:
            st.pop()
            path = tuple(st) + (name,)
            with self._lock:
                # drop the most recent matching entry — concurrent
                # stages on other threads may have pushed above it
                for k in range(len(self._shared) - 1, -1, -1):
                    if self._shared[k] == name:
                        del self._shared[k]
                        break
            dt = time.perf_counter() - start
            with self._lock:
                if name not in self._acc:
                    self._acc[name] = 0.0
                    self._counts[name] = 0
                    self._order.append(name)
                self._acc[name] += dt
                self._counts[name] += 1
                if path not in self._tree:
                    self._tree[path] = [0.0, 0]
                    self._tree_order.append(path)
                self._tree[path][0] += dt
                self._tree[path][1] += 1
            _obs_trace.emit_complete(name, start, dt, cat="stage",
                                     args=args)
            logger.debug("stage %s: %.3fs", name, dt)

    def items(self) -> List[Tuple[str, float, int]]:
        return [(n, self._acc[n], self._counts[n]) for n in self._order]

    def elapsed(self) -> float:
        """Wall-clock seconds since this timer was created/reset."""
        return time.perf_counter() - self._t0

    def tree(self) -> List[dict]:
        """The nested stage wall-clock tree, JSON-ready: each node is
        {name, total_s, count, children}, in first-entry order."""
        with self._lock:
            paths = list(self._tree_order)
            data = {p: tuple(v) for p, v in self._tree.items()}
        nodes: Dict[Tuple[str, ...], dict] = {}
        roots: List[dict] = []

        def node_for(path: Tuple[str, ...]) -> dict:
            # Inner stages close (and register) before their parents,
            # so a parent may not exist yet when its child arrives:
            # create it on demand — its totals are in `data` already
            # if it ever closed, zero if it is still open (crash).
            node = nodes.get(path)
            if node is None:
                acc, count = data.get(path, (0.0, 0))
                node = {"name": path[-1], "total_s": round(acc, 6),
                        "count": count, "children": []}
                nodes[path] = node
                if len(path) == 1:
                    roots.append(node)
                else:
                    node_for(path[:-1])["children"].append(node)
            return node

        for path in paths:
            node_for(path)
        return roots

    def report(self, log: Optional[logging.Logger] = None) -> str:
        log = log or logger
        total = self.elapsed()
        lines = []
        for name, acc, count in self.items():
            share = 100.0 * acc / total if total > 0 else 0.0
            suffix = f" x{count}" if count > 1 else ""
            lines.append(f"{name}: {acc:.2f}s ({share:.0f}%){suffix}")
        text = "; ".join(lines) + f"; total {total:.2f}s"
        if self._counters:
            text += "; " + "; ".join(
                f"{n}={self._counters[n]}" for n in self._counter_order)
        log.info("Stage timings: %s", text)
        return text


# Process-wide timer: backends and the engine record into this by
# default so the CLI gets a full report without threading a timer
# through every constructor.
GLOBAL = StageTimer()


def stage(name: str, args: Optional[dict] = None):
    return GLOBAL.stage(name, args)


def counter(name: str, delta: int) -> None:
    GLOBAL.counter(name, delta)


def dispatch(n: int = 1, sync: bool = False) -> None:
    GLOBAL.dispatch(n, sync=sync)


def stage_token() -> Tuple[str, ...]:
    return GLOBAL.stage_token()


def adopt(token: Tuple[str, ...]):
    return GLOBAL.adopt(token)


def reset() -> None:
    global GLOBAL
    GLOBAL = StageTimer()


@contextlib.contextmanager
def trace_context(trace_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler trace of the enclosed block when trace_dir is set."""
    if not trace_dir:
        yield
        return
    import jax

    logger.info("Writing XLA profiler trace to %s", trace_dir)
    with jax.profiler.trace(trace_dir):
        yield
