"""Reduce a JAX profiler trace to the benchmark's device numbers.

Input is the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData`. The harness marks each job of the window
with a host annotation named `bench_job:<n>`; device work counts only
inside those intervals, so the paused catalogue writes between jobs are
neither busy nor idle.

  device events  every event on a chip's plane (`/device:TPU:<n>`) line
                 named "XLA Ops" (individual HLO ops, a loop's body ops
                 nested inside the loop's event; other lines of the
                 plane repeat them as modules or steps), or, where a
                 plane has no such line, on all its lines.
  modules        events of the plane's "XLA Modules" line: one per
                 program launch, named after the jitted function, which
                 is how a kernel is found by a stable name.
  busy           the union of device-event intervals, clipped to the
                 job intervals, averaged over the devices.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]   # [start, end) in seconds

JOB_PREFIX = "bench_job:"

# a chip's own plane; the profile also holds other `/device:` planes
# (e.g. `/device:CUSTOM:...`) that are not devices running ops
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval],
         windows: Sequence[Interval]) -> List[Interval]:
    """Parts of (merged) `intervals` inside (merged) `windows`."""
    out = []
    j = 0
    for s, e in intervals:
        while j < len(windows) and windows[j][1] <= s:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < e:
            lo, hi = max(s, windows[k][0]), min(e, windows[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


class Trace:
    """Events of one profiler session, in seconds on its own clock."""

    def __init__(self, jobs: Dict[int, Interval],
                 device_ops: Dict[str, List[Tuple[str, Interval]]],
                 modules: Dict[str, List[Tuple[str, Interval]]]) -> None:
        self.jobs = jobs                  # job number -> interval
        self.device_ops = device_ops      # device -> [(op name, iv)]
        self.modules = modules            # device -> [(module name, iv)]

    @property
    def job_windows(self) -> List[Interval]:
        return union(self.jobs.values())

    def busy_s(self) -> Optional[float]:
        """Device-busy seconds inside the jobs, averaged over devices;
        None where the trace holds no device events."""
        if not self.device_ops:
            return None
        windows = self.job_windows
        per_dev = [total(clip(union(iv for _n, iv in evs), windows))
                   for evs in self.device_ops.values()]
        return sum(per_dev) / len(per_dev)

    def window_s(self) -> float:
        return total(self.job_windows)

    def module_time(self, pattern: str,
                    jobs: Optional[Sequence[int]] = None) -> Optional[float]:
        """Device seconds of launches whose module name holds `pattern`,
        inside the given jobs (all by default), summed over devices;
        None where no such launch ran there."""
        windows = union(self.jobs[j] for j in (jobs if jobs is not None
                                               else self.jobs))
        found = False
        secs = 0.0
        for evs in self.modules.values():
            hits = [iv for name, iv in evs if pattern in name]
            if hits:
                part = total(clip(union(hits), windows))
                if part > 0:
                    found = True
                    secs += part
        return secs if found else None

    def top_ops(self, n: int = 10) -> List[List]:
        """The device ops that took most time inside the jobs, summed
        over devices, by short name (overlapping events of one name
        count once)."""
        windows = self.job_windows
        by_name: Dict[str, float] = {}
        for evs in self.device_ops.values():
            groups: Dict[str, List[Interval]] = {}
            for name, iv in evs:
                groups.setdefault(short_name(name), []).append(iv)
            for k, v in groups.items():
                by_name[k] = by_name.get(k, 0.0) + total(
                    clip(union(v), windows))
        by_name = {k: v for k, v in by_name.items() if v > 0}
        return [[k, v] for k, v in sorted(by_name.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, label, n: int = 10) -> List[List]:
        """The longest device-idle gaps inside the jobs, on the first
        device, each named by `label(start, end)` (what the host was
        doing) and summed by that name."""
        if not self.device_ops:
            return []
        evs = next(iter(self.device_ops.values()))
        busy = union(iv for _n, iv in evs)
        gaps = []
        for ws, we in self.job_windows:
            t = ws
            for s, e in clip(busy, [(ws, we)]):
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if we > t:
                gaps.append((t, we))
        by_label: Dict[str, float] = {}
        for s, e in gaps:
            key = label(s, e)
            by_label[key] = by_label.get(key, 0.0) + (e - s)
        return [[k, v] for k, v in sorted(by_label.items(),
                                          key=lambda kv: -kv[1])[:n]]


def short_name(name: str) -> str:
    """An HLO op event's instruction name without its `%`, numeric
    suffix and text (`%fusion.3 = f32[8] fusion(...)` -> `fusion`); a
    module event's name without its fingerprint."""
    head = name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def load(path: str, device_line_prefix: Optional[str] = None) -> Trace:
    """Read an xplane file. `device_line_prefix` selects device lines
    by name on the host plane instead (the CPU backend runs its ops on
    host threads, which is how the reduction is tested without a
    chip)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    jobs: Dict[int, Interval] = {}
    device_ops: Dict[str, List[Tuple[str, Interval]]] = {}
    modules: Dict[str, List[Tuple[str, Interval]]] = {}
    for plane in data.planes:
        lines = list(plane.lines)
        if device_line_prefix is None and DEVICE_PLANE.match(plane.name):
            op_lines = ([line for line in lines if line.name == "XLA Ops"]
                        or [line for line in lines
                            if line.name not in ("XLA Modules", "Steps")])
            device_ops[plane.name] = [
                (e.name, (e.start_ns * 1e-9, e.end_ns * 1e-9))
                for line in op_lines for e in line.events]
            modules[plane.name] = [
                (e.name, (e.start_ns * 1e-9, e.end_ns * 1e-9))
                for line in lines if line.name == "XLA Modules"
                for e in line.events]
            continue
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in lines:
            on_device = (device_line_prefix is not None
                         and line.name.startswith(device_line_prefix))
            for e in line.events:
                iv = (e.start_ns * 1e-9, e.end_ns * 1e-9)
                if e.name.startswith(JOB_PREFIX):
                    jobs[int(e.name[len(JOB_PREFIX):])] = iv
                elif on_device:
                    device_ops.setdefault(line.name, []).append(
                        (e.name, iv))
    return Trace(jobs, device_ops, modules)
