"""Mean seconds per job of exact fragment ANI (host clock, from each
job's run report): the program's `fragment-ani` stage where the
clusterer computes it, plus the part of `precluster-distances` that
none of its child stages covers, which is where the skani preclusterer
computes exact ANI for its screened pairs."""

from readers import stage_seconds, self_seconds


def read(ctx):
    values = []
    for j in ctx.jobs:
        if j.rc != 0:
            continue
        parts = [stage_seconds(j.report, "fragment-ani"),
                 self_seconds(j.report, "precluster-distances")]
        parts = [p for p in parts if p is not None]
        if parts:
            values.append(sum(parts))
    return sum(values) / len(values) if values else None
