"""Host milliseconds of exact ANI per directed pair (host clock, from
each job's run report): the outermost `fragment-ani` stages less their
`fragment-ani.fetch` descendants (the blocking waits for the kernel's
flags), over the job's `fragment-ani-directions` counter; the mean over
the window's jobs."""

from readers import _stages


def _named_below(node, name):
    """Seconds of the descendants of `node` named `name`, outermost."""
    secs = 0.0
    for child in node.get("children", []):
        if child.get("name") == name:
            secs += float(child.get("total_s", 0.0))
        else:
            secs += _named_below(child, name)
    return secs


def read(ctx):
    values = []
    for j in ctx.jobs:
        directions = j.report.get("counters", {}).get(
            "fragment-ani-directions")
        nodes = _stages(j.report, "fragment-ani")
        if j.rc != 0 or not directions or not nodes:
            continue
        host_s = sum(float(n.get("total_s", 0.0))
                     - _named_below(n, "fragment-ani.fetch")
                     for n in nodes)
        values.append(1e3 * host_s / directions)
    return sum(values) / len(values) if values else None
