"""Share of its memory roofline that the exact-ANI membership kernel
reached in the checked job: the least time, the bytes the membership
test needs for the job's exact-ANI pairs (kernel_bytes.py, from the
reference's sizes) over the chip's HBM bandwidth (peaks.json), divided
by the kernel's device time in the profiler trace. Memory-bound by
construction: no published peak covers the VPU's int32 compares."""

from kernel_bytes import fragment_membership_bytes
from readers import exact_ani_pairs


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.trace.module_time("window_hits", jobs=[ctx.sample.job])
    if not secs:
        return None
    sizes = ctx.sample_reference.sizes
    need = sum(fragment_membership_bytes(sizes[q][0], sizes[r][1])
               for q, r in exact_ani_pairs(ctx))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
