"""Milliseconds of profile work per Mbp profiled (host clock, from
each job's run report): the thread-seconds of the per-genome
`profile.read` and `profile.build` spans over the job's `profile-bp`
counter; the mean over the window's jobs. Against the `profile-genomes`
stage's wall it tells a starved stage from a compute-bound one."""

from readers import stage_seconds


def read(ctx):
    values = []
    for j in ctx.jobs:
        bp = j.report.get("counters", {}).get("profile-bp")
        if j.rc != 0 or not bp:
            continue
        parts = [stage_seconds(j.report, name)
                 for name in ("profile.read", "profile.build")]
        if all(p is None for p in parts):
            continue
        values.append(1e3 * sum(p or 0.0 for p in parts) / (bp / 1e6))
    return sum(values) / len(values) if values else None
