"""Share of the window's job time in which no operation ran on the
device: 1 - (union of device-op intervals inside the jobs) / (sum of
the job intervals), from the profiler trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy_s()
    window = ctx.trace.window_s()
    if busy is None or window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
