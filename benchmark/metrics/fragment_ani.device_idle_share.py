"""Share of exact ANI's wall in which no operation ran on the device:
the union of the program's `fragment-ani` spans (read from the
profiler's host plane, on the device ops' clock, host_spans.py),
clipped to the window's jobs, less the device-busy time inside it,
averaged over devices, as a share of that wall. What stays idle there
is host work between the kernel's launches."""

import host_spans
from trace_reduce import clip, total, union


def read(ctx):
    if ctx.trace is None or not ctx.trace.device_ops:
        return None
    spans = host_spans.from_run(["fragment-ani"]).get("fragment-ani")
    if not spans:
        return None
    ani = clip(union((s, e) for s, e, _a in spans), ctx.trace.job_windows)
    wall = total(ani)
    if wall <= 0:
        return None
    busy = [total(clip(union(iv for _n, iv in ops), ani))
            for ops in ctx.trace.device_ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / wall)
