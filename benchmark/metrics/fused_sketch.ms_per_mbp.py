"""Device milliseconds of the fused sketch kernel's launches (modules
`jit__fused_group_sketch_jit`) per Mbp of the window's genomes, from the
profiler trace, inside the jobs."""


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.trace.module_time("fused_group_sketch")
    if not secs:
        return None
    mbp = sum(j.bp for j in ctx.jobs if j.rc == 0) / 1e6
    return 1e3 * secs / mbp
