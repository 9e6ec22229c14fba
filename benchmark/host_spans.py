"""The program's own spans, read from the profiler's host plane.

The program holds a `jax.profiler.TraceAnnotation` for each of its
stage spans (`galah_tpu/utils/timing.py`), so in a traced window they
sit on the xplane's `/host:CPU` plane on the same clock as the device
ops and the `bench_job:<n>` marks, which is all `trace_reduce.load`
keeps of that plane. This reads the spans by name, from the profile the
harness writes under `.bench_work/profile` (still there while the
per-layer readers run). A program without such spans gives none.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from trace_reduce import find_xplane

Span = Tuple[float, float, dict]   # start, end (s), trace arguments


def load(path: str, names: Iterable[str]) -> Dict[str, List[Span]]:
    """Every host-plane event named in `names`, by name, in seconds on
    the session's clock, in start order."""
    from jax.profiler import ProfileData

    wanted = set(names)
    found: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    found.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9,
                         dict(e.stats)))
    for spans in found.values():
        spans.sort(key=lambda s: s[0])
    return found


def profile_dir() -> Path:
    import harness

    return harness.WORK_DIR / "profile"


def from_run(names: Iterable[str],
             trace_dir: Optional[Path] = None) -> Dict[str, List[Span]]:
    """`load` on the traced window's profile; empty where there is
    none."""
    path = find_xplane(str(trace_dir or profile_dir()))
    return load(path, names) if path else {}
