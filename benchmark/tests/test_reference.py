"""The plain reference against the program's own primitives, at small
sizes: the same hashes, sketches and ANI values; and its float32 control
lands between the check's limit and 1e-5."""

import numpy as np
import pytest

import corpus
import reference

pytest.importorskip("galah_tpu")


@pytest.fixture(scope="module")
def genomes(tmp_path_factory):
    t = corpus.load_traffic(
        __import__("pathlib").Path(__file__).parent / "traffic"
        / "tiny_families.json")
    cat = corpus.generate(t, 2**31 + 99, 1)
    files = corpus.write(cat, tmp_path_factory.mktemp("cat"))
    from galah_tpu.io.fasta import read_genome

    return cat, [read_genome(p) for p in files["paths"]]


def test_hashes_match_program(genomes):
    from galah_tpu.ops import _csketch
    from galah_tpu.ops.constants import SENTINEL

    cat, progs = genomes
    for g, pg in list(zip(cat.genomes, progs))[:3]:
        for k in (15, 21):
            view = reference.KmerView(g.contigs, k)
            ours = view.hashes()
            theirs = _csketch.positional_hashes(pg.codes, pg.contig_offsets,
                                                k=k)
            assert np.array_equal(view.valid, theirs != SENTINEL)
            assert np.array_equal(ours[view.valid], theirs[view.valid])


def test_sketch_and_markers_match_program(genomes):
    from galah_tpu.ops import fragment_ani, minhash_np

    cat, progs = genomes
    g, pg = cat.genomes[0], progs[0]
    data = reference.GenomeData(g.name, g.contigs, True, True, 3000)
    assert np.array_equal(data.sketch,
                          minhash_np.sketch_genome(pg).hashes)
    prof = fragment_ani.build_profile(pg, k=15, fraglen=3000)
    assert np.array_equal(data.markers, prof.markers)
    assert data.distinct.shape[0] == prof.ref_set.shape[0]
    assert data.qids.shape[0] == prof.sorted_query()[0].shape[0]


def test_fragment_ani_matches_program(genomes):
    from galah_tpu.ops import fragment_ani

    cat, progs = genomes
    names = [g.name for g in cat.genomes]
    data = reference.build_genomes(
        [(g.name, g.contigs) for g in cat.genomes], False, True, 3000, 2)
    frag = reference.FragmentANI(data, 0.15, 3000, 2)
    profs = [fragment_ani.build_profile(p, k=15, fraglen=3000)
             for p in progs]
    pairs = [(0, 1), (0, 5), (2, 3), (4, 11)]
    try:
        ours = frag.pairs([(names[a], names[b]) for a, b in pairs])
        ours32 = frag.pairs([(names[a], names[b]) for a, b in pairs],
                            dtype=np.float32)
    finally:
        frag.close()
    theirs = fragment_ani.bidirectional_ani_values(
        [(profs[a], profs[b]) for a, b in pairs], min_aligned_frac=0.15)
    for a, b in zip(ours, theirs):
        assert (a is None) == (b is None)
        if a is not None:
            assert abs(a - b) < 1e-12
    gaps = [abs(a - b) for a, b in zip(ours, ours32) if a is not None]
    assert gaps and 1e-9 < max(gaps) < 1e-5


def _same_species_pairs(cat):
    by = {}
    for i, g in enumerate(cat.genomes):
        by.setdefault(g.species, []).append(i)
    return [(m[0], m[1 + n % (len(m) - 1)]) for n, m in
            enumerate(by.values()) if len(m) > 1] + [(0, len(cat.genomes) - 1)]


@pytest.mark.parametrize("c", [16, 125])
def test_subsampled_fragment_ani_matches_program(genomes, c):
    """At FracMinHash compression c the reference's sample, per-fragment
    (matched, total) counts and directed ANI are the program's:
    integers exactly, ANI to 1e-12."""
    from galah_tpu.ops import fragment_ani

    cat, progs = genomes
    names = [g.name for g in cat.genomes]
    data = reference.build_genomes(
        [(g.name, g.contigs) for g in cat.genomes], False, True, 3000, 2,
        subsample=c)
    profs = [fragment_ani.build_profile(p, k=15, fraglen=3000,
                                        subsample_c=c) for p in progs]
    def hashes(ids):
        return np.sort(reference.murmur3_h1(
            reference.ascii_words(ids, 15), 15))

    for name, prof in zip(names, profs):
        g = data[name]
        assert np.array_equal(hashes(g.distinct), prof.ref_set)
        qh, _qw, totals = prof.sorted_query()
        assert np.array_equal(hashes(g.qids), qh)
        assert np.array_equal(g.totals, totals)
    pairs = _same_species_pairs(cat)
    frag = reference.FragmentANI(data, 0.15, 3000, 2, subsample=c)
    try:
        ours = frag.pairs([(names[a], names[b]) for a, b in pairs])
        ours32 = frag.pairs([(names[a], names[b]) for a, b in pairs],
                            dtype=np.float32)
        aligned = 0
        for a, b in pairs:
            ani, ab, ba = fragment_ani.bidirectional_ani(
                profs[a], profs[b], 0.15)
            for (q, r), theirs in (((a, b), ab), ((b, a), ba)):
                matched, total = fragment_ani._window_match_counts(
                    profs[q].device_windows(), profs[r].device_ref_set())
                n = profs[q].n_windows
                d = frag.counts[(names[q], names[r])]
                assert np.array_equal(np.asarray(matched)[:n], d.matched)
                assert np.array_equal(np.asarray(total)[:n], d.totals)
                got, fm, ft = frag.directed(names[q], names[r])
                assert (fm, ft) == (theirs.frags_matching,
                                    theirs.frags_total)
                assert abs(got - theirs.ani) < 1e-12
                aligned += fm
            ref_ani = frag.ani(names[a], names[b])
            assert (ani is None) == (ref_ani is None)
            if ani is not None:
                assert abs(ani - ref_ani) < 1e-12
    finally:
        frag.close()
    assert aligned > 0
    # the float32 control still misses the check's 1e-9 limit
    gaps = [abs(x - y) for x, y in zip(ours, ours32) if x is not None]
    assert gaps and 1e-9 < max(gaps) < 1e-5


def test_unsampled_arrays_are_unchanged(genomes):
    """At c=1 (given or left out) every array the reference builds is
    the one the unsampled formula gives."""
    cat, _ = genomes
    for g in cat.genomes[:3]:
        view = reference.KmerView(g.contigs, 15)
        distinct = np.unique(view.canon[view.valid])
        pos = np.flatnonzero(view.valid
                             & (np.arange(view.n_pos) % 3000 < 3000 - 14))
        totals = np.bincount(pos // 3000, minlength=-(-view.n_pos // 3000))
        for data in (reference.GenomeData(g.name, g.contigs, False, True,
                                          3000),
                     reference.GenomeData(g.name, g.contigs, False, True,
                                          3000, subsample=1)):
            assert np.array_equal(data.distinct, distinct.astype(np.uint32))
            assert np.array_equal(data.qids,
                                  view.canon[pos].astype(np.uint32))
            assert np.array_equal(data.totals, totals)
            assert np.array_equal(data.qwins, np.flatnonzero(totals))
            h = view.hashes(distinct)
            assert np.array_equal(data.markers, np.sort(
                h[h < np.uint64((1 << 64) // 1000)]))


def test_mash_ani_matches_program(genomes):
    from galah_tpu.ops import minhash_np

    cat, progs = genomes
    same = [i for i, g in enumerate(cat.genomes)
            if g.species == cat.genomes[0].species]
    a = minhash_np.sketch_genome(progs[same[0]])
    b = minhash_np.sketch_genome(progs[same[1]])
    common, total = reference.mash_counts(a.hashes, b.hashes)
    assert reference.mash_ani(common, total) == minhash_np.mash_ani(a, b)
    f32 = reference.mash_ani(common, total, dtype=np.float32)
    assert 1e-9 < abs(f32 - minhash_np.mash_ani(a, b)) < 1e-5


def test_quality_order_filters_and_ties():
    records = [
        {"name": "a", "completeness": "60.00", "contamination": "1.00",
         "num_contigs": 10, "num_ambiguous": 0},
        {"name": "b", "completeness": "49.99", "contamination": "0.00",
         "num_contigs": 1, "num_ambiguous": 0},
        {"name": "c", "completeness": "90.00", "contamination": "10.01",
         "num_contigs": 1, "num_ambiguous": 0},
        {"name": "d", "completeness": "60.00", "contamination": "1.00",
         "num_contigs": 10, "num_ambiguous": 0},
        {"name": "e", "completeness": "70.00", "contamination": "2.00",
         "num_contigs": 200, "num_ambiguous": 0},
    ]
    assert reference.quality_order(records, 0.5, 0.1) == ["a", "d", "e"]
