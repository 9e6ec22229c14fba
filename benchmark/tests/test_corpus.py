"""The generator: deterministic per seed, the same work on every seed."""

from collections import Counter
from pathlib import Path

import numpy as np

import corpus

BENCH = Path(__file__).resolve().parents[1]


def _traffic(name):
    return corpus.load_traffic(BENCH / "traffic" / f"{name}.json")


def _small(name):
    t = _traffic(name)
    t["length"] = {"median_bp": 30000, "sigma": 0.5, "min_bp": 10000,
                   "max_bp": 80000}
    t["contig_bp"] = 5000
    return t


def test_same_seed_same_catalogue():
    t = _small("bigfamily")
    a = corpus.generate(t, 2**33 + 7, 3)
    b = corpus.generate(t, 2**33 + 7, 3)
    assert [g.name for g in a.genomes] == [g.name for g in b.genomes]
    for x, y in zip(a.genomes, b.genomes):
        assert (x.completeness, x.contamination) == (y.completeness,
                                                    y.contamination)
        assert len(x.contigs) == len(y.contigs)
        assert all(np.array_equal(p, q) for p, q in zip(x.contigs,
                                                         y.contigs))


def test_jobs_and_seeds_differ():
    t = _small("families")
    a = corpus.generate(t, 5, 1)
    b = corpus.generate(t, 5, 2)
    c = corpus.generate(t, 6, 1)
    seq = lambda cat: np.concatenate(cat.genomes[0].contigs)  # noqa: E731
    assert not np.array_equal(seq(a), seq(b))
    assert not np.array_equal(seq(a), seq(c))


def _work(cat):
    by_species = {}
    for g in cat.genomes:
        by_species.setdefault(g.species, []).append(g)
    return Counter((len(gs), gs[0].length, len(gs[0].contigs))
                   for gs in by_species.values())


def test_every_seed_does_the_same_work():
    for name in ("families", "bigfamily"):
        t = _traffic(name)
        plan = corpus.species_plan(t)
        works = []
        for seed in (1, 2**31 + 11, 2**40 + 3):
            cat = corpus.generate(t, seed, 1)
            assert len(cat.genomes) == sum(
                p["groups"] * p["group_size"] for p in plan)
            works.append((_work(cat), cat.total_bp))
        assert works[0] == works[1] == works[2]


def test_lengths_at_fixed_quantiles():
    plan = corpus.species_plan(_traffic("families"))
    lengths = sorted(p["length"] for p in plan)
    assert len(lengths) == 8
    assert 1_000_000 <= lengths[0] < lengths[-1] <= 8_000_000
    median = (lengths[3] + lengths[4]) / 2
    assert 2_500_000 < median < 3_500_000


def test_truth_levels_and_names():
    t = _small("bigfamily")
    cat = corpus.generate(t, 9, 1)
    strains = cat.truth("strain")
    species = cat.truth("species")
    assert len(strains) == 5 and len(species) == 3
    assert sorted(len(s) for s in strains) == [8, 8, 24, 24, 24]
    names = [g.name for g in cat.genomes]
    assert names == sorted(names) and len(set(names)) == len(names)
    # the input order does not follow the species
    assert [g.species for g in cat.genomes] != sorted(
        g.species for g in cat.genomes)


def test_written_files(tmp_path):
    t = _small("families")
    cat = corpus.generate(t, 4, 1)
    files = corpus.write(cat, tmp_path)
    paths = Path(files["genome_list"]).read_text().split()
    assert len(paths) == len(cat.genomes)
    g = cat.genomes[0]
    text = Path(paths[0]).read_bytes().split(b"\n")
    assert text[0] == b">contig_0"
    seq = b"".join(line for line in text if line and not
                   line.startswith(b">"))
    assert seq == corpus.ASCII[np.concatenate(g.contigs)].tobytes()
    rows = Path(files["quality"]).read_text().splitlines()
    assert rows[0].split("\t")[:3] == ["Name", "Completeness",
                                       "Contamination"]
    assert len(rows) == len(cat.genomes) + 1
    for row in rows[1:]:
        _name, comp, cont = row.split("\t")[:3]
        assert 50.0 <= float(comp) <= 100.0
        assert 0.0 <= float(cont) <= 10.0
