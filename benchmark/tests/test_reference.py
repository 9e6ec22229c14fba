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
