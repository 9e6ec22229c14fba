"""Helpers shared by the per-layer metric readers in benchmark/metrics/."""

from __future__ import annotations

from typing import List, Optional, Tuple


def _stages(report: dict, name: str) -> List[dict]:
    """The stage-tree nodes named `name`, outermost only."""
    found = []
    stack = list(report.get("stages", {}).get("tree", []))
    while stack:
        node = stack.pop()
        if node.get("name") == name:
            found.append(node)
        else:
            stack.extend(node.get("children", []))
    return found


def stage_seconds(report: dict, name: str) -> Optional[float]:
    """Seconds a run report's stage tree spent in stage `name`; None
    where it has no such stage."""
    nodes = _stages(report, name)
    return sum(float(n.get("total_s", 0.0)) for n in nodes) \
        if nodes else None


def self_seconds(report: dict, name: str) -> Optional[float]:
    """Seconds of stage `name` not covered by its child stages."""
    nodes = _stages(report, name)
    return sum(float(n.get("total_s", 0.0))
               - sum(float(c.get("total_s", 0.0))
                     for c in n.get("children", []))
               for n in nodes) if nodes else None


def mean_stage_seconds(ctx, name: str) -> Optional[float]:
    """Mean seconds per window job of stage `name`."""
    values = [stage_seconds(j.report, name) for j in ctx.jobs
              if j.rc == 0]
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def exact_ani_pairs(ctx) -> List[Tuple[str, str]]:
    """Directed (query, reference) pairs whose exact ANI the checked
    job needed, from its reference result: every precluster pair where
    the precluster method is the clusterer, else every precluster pair
    with a representative on one side."""
    ref = ctx.sample_reference
    methods = ctx.cell.config["methods"]
    if methods["precluster"] == methods["cluster"]:
        keep = list(ref.pre)
    else:
        reps = {c[0] for c in ref.clusters}
        keep = [p for p in ref.pre if p & reps]
    out = []
    for pair in keep:
        a, b = sorted(pair)
        out += [(a, b), (b, a)]
    return out
