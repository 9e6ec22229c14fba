"""Blocked fragment-ANI Pallas kernel: parity, selection, packing.

The kernel (ops/pallas_fragment.py) must be undetectable from the
results side: per-element membership flags identical to numpy's
definition over a bucket-boundary lattice, per-window matched counts
bit-identical to the XLA searchsorted and compiled-C merge strategies,
and DirectedANI / cluster compositions byte-for-byte equal under every
GALAH_TPU_FRAGMENT_STRATEGY pin. The launch contract (one launch per
directed pair, each profile's planes uploaded once, the device cache
bounded) is pinned through the timing counters.

All kernel executions here run interpret=True on the CPU; the kernel
compiles for v5e in tests/test_tpu_compile.py and runs on the chip in
chip_smoke.py.
"""

import logging

import numpy as np
import pytest

from galah_tpu.io.fasta import Genome, GenomeStats
from galah_tpu.ops import fragment_ani as fa
from galah_tpu.ops import pallas_fragment as pf
from galah_tpu.ops.constants import SENTINEL
from galah_tpu.utils import timing

K, FRAGLEN, SUB_C = 15, 500, 2
FLOOR = 0.80
FRAC = fa.DEFAULT_MIN_WINDOW_VALID_FRAC


def _genome(codes, name):
    n = codes.shape[0]
    return Genome(path=f"{name}.fna", codes=codes,
                  contig_offsets=np.array([0, n], dtype=np.int64),
                  stats=GenomeStats(1, int((codes == 255).sum()), n))


def _element_hits(items):
    """Per-item 0/1 flags, in item order, from the interpret-mode
    kernel."""
    return [flags.astype(np.int32)
            for _i, flags in pf.iter_element_hits(items, interpret=True)]


def _span_counts():
    return {name: count for name, _s, count in timing.GLOBAL.items()}


def _counter_delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)
            if after.get(k, 0) != before.get(k, 0)}


# -- kernel-level membership lattice ---------------------------------


def test_kernel_hits_match_numpy_membership_lattice():
    """Per-element flags == np.isin over job/ref pow2 boundaries,
    duplicates, empty sides, all-hit and no-hit extremes — every item
    in the SAME iter_element_hits call, so pipelined launches and a
    reference shared between items are what gets exercised."""
    rng = np.random.default_rng(11)
    qb = pf.A_SUB * pf.QLA          # 1024: the job quantum
    rb = pf.RSB * pf.B_LANE         # 1024: the ref block quantum

    def u64s(n, hi=1 << 62):
        return np.unique(rng.integers(0, hi, size=n + 64,
                                      dtype=np.uint64))[:n]

    ref_small = np.sort(u64s(1000))
    ref_edge = np.sort(u64s(4 * rb + 1))   # pads 4097 -> 8192 (8 blocks)
    cases = []
    # (qh, ref) lattice: job boundary sizes x ref sets
    for n_q in (1, qb - 1, qb, qb + 1):
        mix = np.concatenate([
            rng.choice(ref_edge, size=max(n_q // 2, 1)),
            u64s(n_q)[:n_q - max(n_q // 2, 1)]])
        cases.append((np.sort(mix[:n_q]), ref_edge))
    cases.append((np.zeros(0, dtype=np.uint64), ref_small))  # empty q
    cases.append((np.sort(u64s(300)),
                  np.zeros(0, dtype=np.uint64)))             # empty ref
    cases.append((np.sort(ref_small[:200]), ref_small))      # all hit
    dup = np.sort(np.repeat(ref_small[:64], 8))              # dup q vals
    cases.append((dup, ref_small))
    cases.append((np.sort(u64s(500) | np.uint64(1 << 63)),
                  ref_small))                                # no hit
    # every case its own reference array, except two items SHARING one
    # (uploaded once)
    items = [(qh, ref.copy()) for qh, ref in cases]
    shared = ref_edge.copy()
    items.append((np.sort(u64s(700)), shared))
    items.append((np.sort(u64s(900)), shared))

    before = timing.GLOBAL.counters()
    spans_before = _span_counts()
    hits = _element_hits(items)
    delta = _counter_delta(before, timing.GLOBAL.counters())
    spans = _counter_delta(spans_before, _span_counts())

    for (qh, ref), h in zip(items, hits):
        expect = np.isin(qh, ref).astype(np.int32)
        np.testing.assert_array_equal(h, expect)
    # one launch per live item; the empty-query item short-circuits
    # without one, and the shared reference is uploaded once
    live = [it for it in items if it[0].shape[0]]
    assert delta.get("fragment-pallas-launches") == len(live)
    # directions are counted a layer up (fragment_ani); here each live
    # item's launch and fetch close one span each
    assert spans.get("fragment-ani.launch") == len(live)
    assert spans.get("fragment-ani.fetch") == len(live)
    refs = {id(ref) for _q, ref in live}
    assert len(refs) == len(live) - 1
    assert delta.get("fragment-pallas-uploads") == len(live) + len(refs)


def test_kernel_sentinel_queries_never_match():
    """SENTINEL-valued query slots (the packer's tail padding value)
    are masked even when the reference padding carries the same
    sentinel pattern."""
    ref = np.sort(np.unique(np.random.default_rng(3).integers(
        0, 1 << 62, size=500, dtype=np.uint64)))
    qh = np.concatenate([ref[:10],
                         np.full(5, np.uint64(SENTINEL))])
    qh = np.sort(qh)
    (h,) = _element_hits([(qh, ref)])
    np.testing.assert_array_equal(h, np.isin(qh, ref).astype(np.int32))
    assert int(h.sum()) == 10


# -- profile-level strategy parity -----------------------------------


@pytest.fixture(scope="module")
def profiles():
    """Six profiles spanning the hazard space: near-identical mutants,
    an ambiguous-base run, a repeat-tiled genome, and a larger genome
    that lands in a different pow2 ref bucket."""
    rng = np.random.default_rng(7)
    size = 8_000
    base = rng.integers(0, 4, size=size).astype(np.uint8)
    variants = [("base", base)]
    for rate in (0.01, 0.05):
        v = base.copy()
        mut = rng.random(size) < rate
        v[mut] = rng.integers(0, 4, size=int(mut.sum())).astype(np.uint8)
        variants.append((f"mut{rate}", v))
    amb = base.copy()
    amb[2000:2600] = 255
    variants.append(("ambig", amb))
    seg = rng.integers(0, 4, size=1_000).astype(np.uint8)
    variants.append(("repeat", np.tile(seg, 8)))
    variants.append(("big", rng.integers(0, 4,
                                         size=17_000).astype(np.uint8)))
    return [fa.build_profile(_genome(codes, name), K, FRAGLEN,
                             subsample_c=SUB_C)
            for name, codes in variants]


@pytest.fixture(scope="module")
def pairs(profiles):
    return [(profiles[i], profiles[j])
            for i in range(len(profiles))
            for j in range(len(profiles)) if i != j]


@pytest.fixture(scope="module")
def strategy_results(pairs):
    """Each strategy's DirectedANI list over the same pairs, plus the
    pallas run's launch-counter deltas (the dispatch-count acceptance
    evidence) — computed once for the whole module."""
    pf.clear_plane_cache()
    before = timing.GLOBAL.counters()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("GALAH_TPU_FRAGMENT_STRATEGY", "pallas")
        res = {"pallas": fa.directed_ani_batch(
            pairs, FLOOR, FRAC, threads=1)}
    counters = _counter_delta(before, timing.GLOBAL.counters())
    res["xla"] = fa._directed_ani_batch_xla(pairs, FLOOR, FRAC)
    if fa._c_merge_available():
        res["c"] = fa._directed_ani_batch_cmerge(pairs, FLOOR, FRAC, 1)
    return res, counters


def test_per_window_counts_bit_identical(profiles):
    """The raw per-window matched integers — not just the reduced
    floats — agree across pallas / xla / C for representative pairs,
    including the repeat-tiled and ambiguous-run genomes."""
    sel = [(profiles[0], profiles[1]), (profiles[1], profiles[0]),
           (profiles[4], profiles[0]), (profiles[3], profiles[5]),
           (profiles[5], profiles[3])]
    items = [(q.sorted_query()[0], r.ref_set) for q, r in sel]
    hits = _element_hits(items)
    for (q, r), h in zip(sel, hits):
        qh, qw, totals = q.sorted_query()
        w = q.n_windows
        pallas_m = np.bincount(qw[h != 0], minlength=w).astype(np.int32)
        xm, xt = fa._window_match_counts(q.device_windows(),
                                         r.device_ref_set())
        np.testing.assert_array_equal(pallas_m, np.asarray(xm)[:w])
        np.testing.assert_array_equal(totals, np.asarray(xt)[:w])
        if fa._c_merge_available():
            from galah_tpu.ops._cpairstats import \
                window_match_counts_merge

            cm = window_match_counts_merge(qh, qw, w, r.ref_set,
                                           validate=False)
            np.testing.assert_array_equal(pallas_m, np.asarray(cm))


def test_flag_fold_matches_bincount(profiles):
    """The host fold of a pair's flags (C where built) gives numpy's
    per-window counts."""
    q, r = profiles[1], profiles[0]
    qh, qw, totals = q.sorted_query()
    (flags,) = [f for _i, f in pf.iter_element_hits([(qh, r.ref_set)],
                                                     interpret=True)]
    np.testing.assert_array_equal(
        fa._fold_flags(flags, qw, totals.shape[0]),
        np.bincount(qw[flags != 0], minlength=totals.shape[0]))


def test_directed_ani_bit_identical_across_strategies(strategy_results):
    res, _ = strategy_results
    assert len(res) >= 2
    ref = res["pallas"]
    # parity must not be vacuous: mutant pairs align with high identity
    assert any(d.ani > 0.9 and d.frags_matching > 0 for d in ref)
    for name, got in res.items():
        assert len(got) == len(ref)
        for i, (a, b) in enumerate(zip(ref, got)):
            assert a == b, (name, i, a, b)


def test_one_launch_per_pair_planes_uploaded_once(profiles, pairs,
                                                  strategy_results):
    """Acceptance: one launch per directed pair, and each profile's
    query and reference planes cross to the device once for all of
    them."""
    _, counters = strategy_results
    assert counters["fragment-pallas-launches"] == len(pairs)
    assert counters["fragment-ani-directions"] == len(pairs)
    assert counters["fragment-pallas-uploads"] == 2 * len(profiles)
    assert counters["fragment-pallas-jobs"] <= \
        counters["fragment-pallas-job-slots"]
    assert counters["fragment-pallas-ref-blocks-needed"] <= \
        counters["fragment-pallas-ref-blocks"]


def test_plane_cache_budget_evicts(profiles, monkeypatch):
    """A device cache too small for two planes uploads each profile's
    planes again once evicted — and the results stay identical."""
    sub = [(profiles[0], profiles[1]), (profiles[1], profiles[2]),
           (profiles[2], profiles[0])]
    pf.clear_plane_cache()
    for cache in (pf._QUERY_CACHE, pf._REF_CACHE):
        monkeypatch.setattr(cache, "budget", 1)
    before = timing.GLOBAL.counters()
    tight = fa._directed_ani_batch_pallas(sub, FLOOR, FRAC)
    delta = _counter_delta(before, timing.GLOBAL.counters())
    assert delta["fragment-pallas-uploads"] == 2 * len(sub)
    monkeypatch.undo()
    assert tight == fa._directed_ani_batch_pallas(sub, FLOOR, FRAC)


def test_zero_window_query_parity(profiles):
    """A shorter-than-k genome (zero windows, empty query) flows
    through the pallas path's short-circuit and matches XLA."""
    tiny = fa.build_profile(
        _genome(np.array([0, 1, 2, 3] * 2, dtype=np.uint8), "tiny"),
        K, FRAGLEN, subsample_c=SUB_C)
    batch = [(tiny, profiles[0]), (profiles[0], tiny),
             (profiles[0], profiles[1])]
    got = fa._directed_ani_batch_pallas(batch, FLOOR, FRAC)
    assert got[0] == fa.DirectedANI(0.0, 0.0, 0, 0)
    assert got == fa._directed_ani_batch_xla(batch, FLOOR, FRAC)


def test_bidirectional_values_parity_under_env_pins(pairs, monkeypatch):
    """The public bidirectional entry point returns identical gated
    values under every strategy pin."""
    sub = pairs[:4]
    outs = {}
    for s in ("pallas", "xla") + (("c",)
                                  if fa._c_merge_available() else ()):
        monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", s)
        outs[s] = fa.bidirectional_ani_values(sub, 0.15)
    assert all(v == outs["pallas"] for v in outs.values())
    assert any(v is not None for v in outs["pallas"])


def _fresh_profiles(subsample_c):
    """New profiles on every call, so no sorted query is cached yet:
    a base genome, a 2% mutant, a repeat-tiled and an ambiguous one."""
    rng = np.random.default_rng(31)
    base = rng.integers(0, 4, size=6_000).astype(np.uint8)
    mut = base.copy()
    sites = rng.random(base.shape[0]) < 0.02
    mut[sites] = (mut[sites] + 1) % 4
    amb = base.copy()
    amb[1_000:2_200] = 255
    tiled = np.tile(rng.integers(0, 4, size=400).astype(np.uint8), 12)
    return [fa.build_profile(_genome(codes, name), K, FRAGLEN,
                             subsample_c=subsample_c)
            for name, codes in (("base", base), ("mut", mut),
                                ("amb", amb), ("tiled", tiled))]


@pytest.mark.parametrize("subsample_c", [1, SUB_C])
def test_native_sorted_query_keeps_directed_ani_bit_identical(
        subsample_c, monkeypatch):
    """directed_ani_batch gives the same DirectedANI values from the C
    sorted-query build, under the C merge and the Pallas strategy, as
    from the numpy twin (GALAH_TPU_NO_CPAIRSTATS=1, Pallas);
    `fragment-sorted-query-native` counts each distinct query genome
    built in C, and none under the fallback."""
    import sys

    import galah_tpu.ops as ops_pkg

    results, native = {}, {}
    for name, strategy, no_c in (("c", "c", False),
                                 ("pallas", "pallas", False),
                                 ("fallback", "pallas", True)):
        profiles = _fresh_profiles(subsample_c)
        pairs = [(q, r) for q in profiles for r in profiles if q is not r]
        with monkeypatch.context() as mp:
            mp.setenv("GALAH_TPU_FRAGMENT_STRATEGY", strategy)
            if no_c:
                # a fresh import of the binding honours the switch
                mp.setenv("GALAH_TPU_NO_CPAIRSTATS", "1")
                mp.delitem(sys.modules, "galah_tpu.ops._cpairstats",
                           raising=False)
                mp.delattr(ops_pkg, "_cpairstats", raising=False)
            before = timing.GLOBAL.counters()
            results[name] = fa.directed_ani_batch(pairs, FLOOR, FRAC,
                                                  threads=2)
            delta = _counter_delta(before, timing.GLOBAL.counters())
        native[name] = delta.get("fragment-sorted-query-native", 0)
        # the C build never assembles the windows() matrix
        assert all((p._np_windows is None) != no_c for p in profiles)
    assert native == {"c": 4, "pallas": 4, "fallback": 0}
    assert any(d.ani > 0.9 and d.frags_matching > 0
               for d in results["c"])
    assert results["c"] == results["pallas"] == results["fallback"]


# -- strategy resolution ---------------------------------------------


def test_auto_selection_heuristic(monkeypatch):
    monkeypatch.delenv("GALAH_TPU_FRAGMENT_STRATEGY", raising=False)
    r = fa._resolve_fragment_strategy
    assert r(backend="cpu", n_devices=1, c_ok=True) == ("c", False)
    assert r(backend="cpu", n_devices=1, c_ok=False) == ("xla", False)
    # multi-device CPU mesh: the sharded XLA batch path wins
    assert r(backend="cpu", n_devices=8, c_ok=True) == ("xla", False)
    monkeypatch.setattr("galah_tpu.ops.hll.use_pallas_default",
                        lambda: True)
    assert r(backend="tpu", n_devices=4, c_ok=True) == ("pallas", False)
    monkeypatch.setattr("galah_tpu.ops.hll.use_pallas_default",
                        lambda: False)
    assert r(backend="tpu", n_devices=4, c_ok=True) == ("xla", False)


def test_env_pin_beats_auto(monkeypatch):
    for s in fa.FRAGMENT_STRATEGIES:
        monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", s)
        # the pin wins over every injected runtime shape
        assert fa._resolve_fragment_strategy(
            backend="cpu", n_devices=1, c_ok=True) == (s, True)
    monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", "")
    assert fa._resolve_fragment_strategy(
        backend="cpu", n_devices=1, c_ok=True) == ("c", False)


def test_strategy_counter_records_resolution(pairs, monkeypatch):
    monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", "xla")
    before = timing.GLOBAL.counters()
    fa.directed_ani_batch(pairs[:2], FLOOR, FRAC)
    delta = _counter_delta(before, timing.GLOBAL.counters())
    assert delta.get("fragment-strategy-xla") == 1


# -- fallback / demotion policy --------------------------------------


def _broken_kernel(*_a, **_k):
    raise RuntimeError("forced fragment kernel failure")


def test_auto_pallas_failure_propagates(pairs, monkeypatch):
    """AUTO-chosen pallas that fails at runtime propagates the failure:
    no quiet rerun on the XLA twin, no demotion counter."""
    sub = pairs[:3]
    monkeypatch.delenv("GALAH_TPU_FRAGMENT_STRATEGY", raising=False)
    monkeypatch.setattr(fa, "_resolve_fragment_strategy",
                        lambda *a, **k: ("pallas", False))
    monkeypatch.setattr(pf, "iter_element_hits", _broken_kernel)
    before = timing.GLOBAL.counters()
    with pytest.raises(RuntimeError, match="forced fragment kernel"):
        fa.directed_ani_batch(sub, FLOOR, FRAC)
    delta = _counter_delta(before, timing.GLOBAL.counters())
    assert delta.get("fragment-strategy-pallas") == 1
    assert "fragment-pallas-demoted" not in delta


def test_explicit_pin_propagates_kernel_failure(pairs, monkeypatch):
    """A pinned pallas run must fail loudly — parity captures must
    never silently compare the fallback to itself."""
    monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", "pallas")
    monkeypatch.setattr(pf, "iter_element_hits", _broken_kernel)
    with pytest.raises(RuntimeError, match="forced fragment"):
        fa.directed_ani_batch(pairs[:2], FLOOR, FRAC)


# -- end-to-end cluster-composition parity ---------------------------


def _write_family(tmp_path):
    rng = np.random.default_rng(23)
    base = rng.integers(0, 4, size=20_000)
    seqs = [base]
    mut = base.copy()
    sites = rng.random(mut.shape[0]) < 0.01
    mut[sites] = (mut[sites]
                  + rng.integers(1, 4, size=int(sites.sum()))) % 4
    seqs.append(mut)
    seqs.append(rng.integers(0, 4, size=20_000))  # unrelated
    paths = []
    for i, s in enumerate(seqs):
        p = tmp_path / f"g{i}.fna"
        p.write_text(">c\n" + "".join("ACGT"[c] for c in s) + "\n")
        paths.append(str(p))
    return paths


def test_cluster_composition_parity_across_strategies(tmp_path,
                                                      monkeypatch):
    """Full pipeline under each strategy pin produces the same
    clusters: the 1%-mutant joins its base, the unrelated genome
    stays a singleton."""
    from galah_tpu.api import generate_galah_clusterer

    paths = _write_family(tmp_path)
    values = {"ani": 95.0, "precluster_ani": 90.0,
              "min_aligned_fraction": 15.0, "fragment_length": 3000,
              "precluster_method": "skani", "cluster_method": "skani",
              "threads": 1}
    strategies = ["pallas", "xla"]
    if fa._c_merge_available():
        strategies.append("c")
    outs = {}
    for s in strategies:
        monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", s)
        clusters = generate_galah_clusterer(paths, values).cluster()
        outs[s] = sorted(sorted(c) for c in clusters)
    assert outs["pallas"] == [[0, 1], [2]]
    assert all(v == outs["pallas"] for v in outs.values())


@pytest.mark.parametrize("strategy", ["pallas", "xla"])
def test_abisko_golden_clusters_per_strategy(ref_data, monkeypatch,
                                             strategy):
    """Reference-data golden (reference: src/clusterer.rs:481-533 pins
    [[0,1,3],[2]] at 98): the campaign clustering is invariant under
    the membership strategy pin."""
    from galah_tpu.api import generate_galah_clusterer

    names = ["abisko4/73.20120800_S1X.13.fna",
             "abisko4/73.20120600_S2D.19.fna",
             "abisko4/73.20120700_S3X.12.fna",
             "abisko4/73.20110800_S2D.13.fna"]
    monkeypatch.setenv("GALAH_TPU_FRAGMENT_STRATEGY", strategy)
    values = {"ani": 98.0, "precluster_ani": 90.0,
              "min_aligned_fraction": 20.0, "fragment_length": 3000,
              "precluster_method": "skani", "cluster_method": "skani",
              "threads": 1}
    clusterer = generate_galah_clusterer(
        [str(ref_data / n) for n in names], values)
    assert sorted(sorted(c) for c in clusterer.cluster()) == \
        [[0, 1, 3], [2]]
