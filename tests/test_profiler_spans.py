"""Program spans on the profiler's clock, and the spans and counters of
exact ANI and profiles.

Every StageTimer stage and flow span also holds a jax.profiler
TraceAnnotation, so a profiler session shows it on the xplane's host
plane beside the device ops. Exact ANI names its host work per directed
pair (`fragment-ani.launch/fetch/fold`, under `.pipeline`) and per
batch or group (`.sort-queries`, `.plan`, `.collect` with `.reduce`
inside it, `.combine`), and counts its directions
(`fragment-ani-directions`, every strategy);
profiles name each genome's read and build (`profile.read`,
`profile.build`) and count the bases profiled (`profile-bp`) and the
k-mer positions their sample keeps (`profile-kept-kmers`).
"""

import glob
import os
import sys

import numpy as np
import pytest

from galah_tpu.backends.fragment_backend import (ProfileStore,
                                                 SkaniPreclusterer)
from galah_tpu.io import diskcache
from galah_tpu.io.fasta import Genome, GenomeStats
from galah_tpu.obs import flow as obs_flow
from galah_tpu.obs import trace as obs_trace
from galah_tpu.ops import fragment_ani as fa
from galah_tpu.ops import pallas_fragment as pf
from galah_tpu.utils import timing

K, FRAGLEN = 15, 500


def _genome(codes, name):
    n = codes.shape[0]
    return Genome(path=f"{name}.fna", codes=codes,
                  contig_offsets=np.array([0, n], dtype=np.int64),
                  stats=GenomeStats(1, int((codes == 255).sum()), n))


def _span_counts():
    return {name: count for name, _s, count in timing.GLOBAL.items()}


def _delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0) for k in after
            if after.get(k, 0) != before.get(k, 0)}


def _find(tree, name):
    """Stage-tree nodes named `name`, outermost only."""
    out, stack = [], list(tree)
    while stack:
        node = stack.pop()
        if node["name"] == name:
            out.append(node)
        else:
            stack.extend(node["children"])
    return out


# -- the shared clock --------------------------------------------------


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    timing.reset()
    obs_flow.reset()
    assert obs_flow.enabled()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("enclosing"):
            with timing.stage("outer-stage", {"direction": 7}):
                with obs_flow.span("fragment"):
                    with obs_flow.blocked("fragment", "device-dispatch"):
                        jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**",
                                     "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, (e.start_ns, e.end_ns,
                                           dict(e.stats)))
    names = ["enclosing", "outer-stage", "fragment:service",
             "fragment:blocked[device-dispatch]"]
    for name in names:
        assert name in events, (name, sorted(events)[:40])
    # each span lies inside the one before it
    for outer, inner in zip(names, names[1:]):
        assert events[outer][0] <= events[inner][0]
        assert events[inner][1] <= events[outer][1]
    assert events["outer-stage"][2].get("direction") == 7


def test_annotation_is_a_noop_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    with obs_trace.annotation("x", {"direction": 1}):
        pass
    timing.reset()
    with timing.stage("no-jax"):
        pass
    assert _span_counts()["no-jax"] == 1


# -- exact ANI ---------------------------------------------------------


@pytest.fixture(scope="module")
def direction_pairs():
    """Pairs over four related genomes and one shorter than k, whose
    query holds no hash (a direction with nothing to launch)."""
    rng = np.random.default_rng(5)
    base = rng.integers(0, 4, size=4_000).astype(np.uint8)
    genomes = [_genome(base, "base")]
    for i, rate in enumerate((0.01, 0.03, 0.2)):
        v = base.copy()
        mut = rng.random(v.shape[0]) < rate
        v[mut] = rng.integers(0, 4, size=int(mut.sum())).astype(np.uint8)
        genomes.append(_genome(v, f"m{i}"))
    genomes.append(_genome(base[:10].copy(), "tiny"))
    profs = [fa.build_profile(g, K, FRAGLEN) for g in genomes]
    return [(profs[i], profs[j]) for i in range(len(profs))
            for j in range(i + 1, len(profs))]


@pytest.mark.parametrize("strategy", ["pallas", "xla", "c"])
def test_directions_counted_and_spanned_per_strategy(
        strategy, direction_pairs, monkeypatch):
    if strategy == "c" and not fa._c_merge_available():
        pytest.skip("no C toolchain")
    monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", strategy)
    pf.clear_plane_cache()
    timing.reset()
    with timing.stage("fragment-ani"):
        values = fa.bidirectional_ani_values(direction_pairs,
                                             min_aligned_frac=0.15)
    assert len(values) == len(direction_pairs)
    directions = 2 * len(direction_pairs)
    assert timing.GLOBAL.counters()["fragment-ani-directions"] == \
        directions
    spans = _span_counts()
    # the per-pair combine of both directions, once per batch
    assert spans["fragment-ani.combine"] == 1
    (node,) = _find(timing.GLOBAL.tree(), "fragment-ani")
    children = {c["name"]: c for c in node["children"]}
    if strategy == "pallas":
        live = sum(1 for a, b in direction_pairs for q in (a, b)
                   if q.sorted_query()[0].shape[0])
        assert 0 < live < directions
        for name in ("fragment-ani.launch", "fragment-ani.fetch",
                     "fragment-ani.fold"):
            assert spans[name] == live, name
        for name in ("fragment-ani.reduce", "fragment-ani.sort-queries",
                     "fragment-ani.plan", "fragment-ani.pipeline",
                     "fragment-ani.collect"):
            assert spans[name] == 1, name
        # every stretch of the batch's host work is under a named child
        assert set(children) == {
            "fragment-ani.sort-queries", "fragment-ani.plan",
            "fragment-ani.pipeline", "fragment-ani.collect",
            "fragment-ani.combine"}
        assert {c["name"] for c in
                children["fragment-ani.pipeline"]["children"]} == {
            "fragment-ani.launch", "fragment-ani.fetch",
            "fragment-ani.fold"}
        assert [c["name"] for c in
                children["fragment-ani.collect"]["children"]] == [
            "fragment-ani.reduce"]
    else:
        assert "fragment-ani.launch" not in spans
        assert "fragment-ani.pipeline" not in spans


def test_directions_counted_on_the_batched_c_path(monkeypatch):
    """The C strategy's array shortcut (64+ directions) bypasses
    directed_ani_batch and counts its directions itself."""
    if not fa._c_merge_available():
        pytest.skip("no C toolchain")
    monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", "c")
    rng = np.random.default_rng(9)
    profs = [fa.build_profile(_genome(
        rng.integers(0, 4, size=1_500).astype(np.uint8), f"g{i}"),
        K, FRAGLEN) for i in range(9)]
    pairs = [(profs[i], profs[j]) for i in range(9)
             for j in range(i + 1, 9)]
    timing.reset()
    fa.bidirectional_ani_values(pairs, min_aligned_frac=0.15)
    assert timing.GLOBAL.counters()["fragment-ani-directions"] == \
        2 * len(pairs)


def _write_family(tmp_path, n=4, size=6_000):
    rng = np.random.default_rng(3)
    base = rng.integers(0, 4, size=size)
    paths = []
    for i in range(n):
        seq = base.copy()
        mut = rng.random(size) < 0.01 * i
        seq[mut] = rng.integers(0, 4, size=int(mut.sum()))
        p = tmp_path / f"g{i}.fna"
        p.write_text(f">g{i}\n" + "".join("ACGT"[c] for c in seq) + "\n")
        paths.append(str(p))
    return paths


def test_skani_precluster_exact_ani_is_its_own_stage(tmp_path):
    """`fragment-ani` sits under `precluster-distances`, and it plus
    the parent's self time is what the parent's self time was before
    the span: the parent's total less profiles and the screen."""
    paths = _write_family(tmp_path)
    pre = SkaniPreclusterer(
        0.9, 0.15, store=ProfileStore(cache=diskcache.CacheDir(None)))
    timing.reset()
    with timing.stage("precluster-distances"):
        cache = pre.distances(paths)
    assert len(cache) > 0
    (parent,) = _find(timing.GLOBAL.tree(), "precluster-distances")
    children = {c["name"]: c["total_s"] for c in parent["children"]}
    assert {"profile-genomes", "marker-screen",
            "fragment-ani"} <= set(children)
    self_s = parent["total_s"] - sum(children.values())
    assert children["fragment-ani"] + self_s == pytest.approx(
        parent["total_s"] - children["profile-genomes"]
        - children["marker-screen"], abs=1e-5)
    assert timing.GLOBAL.counters()["fragment-ani-directions"] == \
        2 * len(cache)


@pytest.mark.parametrize("threads", [1, 3])
def test_profile_spans_per_genome(tmp_path, threads):
    paths = _write_family(tmp_path, n=5)
    store = ProfileStore(cache=diskcache.CacheDir(None), threads=threads)
    timing.reset()
    with timing.stage("profile-genomes"):
        store.get_many(paths)
    spans = _span_counts()
    assert spans["profile.read"] == len(paths)
    assert spans["profile.build"] == len(paths)
    counters = timing.GLOBAL.counters()
    assert counters["profile-bp"] == 5 * 6_000
    # at c=1 the sample keeps every valid position inside its fragment
    kept = sum(store.get(p).query_positions() for p in paths)
    assert counters["profile-kept-kmers"] == kept
    assert 0 < kept < 5 * 6_000
    # the workers adopt the submitting stage: both nest under it
    (node,) = _find(timing.GLOBAL.tree(), "profile-genomes")
    assert {c["name"] for c in node["children"]} == {"profile.read",
                                                     "profile.build"}
    # a cached genome is neither read nor built again
    store.get(paths[0])
    assert _span_counts()["profile.read"] == len(paths)
