"""Seeded MAG catalogues for dereplication jobs, drawn from a traffic file.

A traffic file (benchmark/traffic/<mix>.json) describes the catalogue
one job clusters:

  species       list of {"count", "strain_groups", "group_size",
                "group_rate", "member_rate"}: `count` species of this
                kind, each with `strain_groups` groups of `group_size`
                genomes. A species base is a random sequence; a group
                base carries `group_rate` point substitutions from it
                (none where there is one group); a member carries
                `member_rate` substitutions from its group base.
  length        {"median_bp", "sigma", "min_bp", "max_bp"}: log-normal
                genome lengths. They are taken at fixed quantiles, one
                per species of a size class (species with the same
                genome count), and only permuted among that class by
                the seed, so every seed clusters the same multiset of
                (genomes, length): the same work and the same shapes.
  contig_bp     mean contig length; each genome is cut into
                length // contig_bp contigs at seeded positions.
  completeness, contamination
                [low, high] ranges (percent) of the seeded CheckM2-style
                quality report; values are uniform and independent of
                the input order, so the quality ordering does real work.

Genome names are a seeded permutation, so neither the file names nor
the input order follow the species. Sequences are uppercase ACGT only.

The generator started from the one in the repository's chip smoke test
(planted families, log-normal lengths, point mutations, 100 kb contigs)
and adds the fixed quantiles, strain groups and the quality report.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

ASCII = np.frombuffer(b"ACGT", dtype=np.uint8)

QUALITY_HEADER = ("Name\tCompleteness\tContamination\t"
                  "Completeness_Model_Used\tTranslation_Table_Used\t"
                  "Additional_Notes\n")


@dataclasses.dataclass
class Genome:
    name: str
    species: int
    group: int              # global strain-group id
    contigs: List[np.ndarray]   # uint8 codes 0..3 per contig
    completeness: str       # as written to the quality report
    contamination: str

    @property
    def length(self) -> int:
        return sum(c.shape[0] for c in self.contigs)


@dataclasses.dataclass
class Catalogue:
    genomes: List[Genome]   # in input (file list) order

    @property
    def total_bp(self) -> int:
        return sum(g.length for g in self.genomes)

    def truth(self, level: str) -> List[frozenset]:
        """Planted clusters as sets of genome names: one per species or
        one per strain group."""
        key = {"species": lambda g: g.species,
               "strain": lambda g: g.group}[level]
        groups: Dict[int, set] = {}
        for g in self.genomes:
            groups.setdefault(key(g), set()).add(g.name)
        return [frozenset(s) for s in groups.values()]


def load_traffic(path) -> dict:
    return json.loads(Path(path).read_text())


def _lognormal_quantile(q: float, median: float, sigma: float) -> float:
    return median * math.exp(sigma * statistics.NormalDist().inv_cdf(q))


def species_plan(traffic: dict) -> List[dict]:
    """One entry per species, in traffic-file order: its genome count,
    groups, rates and its length before the seed's permutation."""
    spec = traffic["length"]
    plan = []
    for kind in traffic["species"]:
        for _ in range(int(kind["count"])):
            plan.append({
                "groups": int(kind["strain_groups"]),
                "group_size": int(kind["group_size"]),
                "group_rate": float(kind["group_rate"]),
                "member_rate": float(kind["member_rate"]),
            })
    classes: Dict[int, List[int]] = {}
    for i, sp in enumerate(plan):
        classes.setdefault(sp["groups"] * sp["group_size"], []).append(i)
    for members in classes.values():
        m = len(members)
        for r, i in enumerate(members):
            q = (r + 0.5) / m
            length = _lognormal_quantile(q, spec["median_bp"],
                                         spec["sigma"])
            plan[i]["length"] = int(min(max(length, spec["min_bp"]),
                                        spec["max_bp"]))
    return plan


def _mutate(base: np.ndarray, rate: float,
            rng: np.random.Generator) -> np.ndarray:
    codes = base.copy()
    if rate > 0:
        hit = np.flatnonzero(rng.random(base.shape[0]) < rate)
        codes[hit] = (codes[hit] + rng.integers(
            1, 4, hit.size, dtype=np.uint8)) % 4
    return codes


def _cut(codes: np.ndarray, contig_bp: int,
         rng: np.random.Generator) -> List[np.ndarray]:
    n_contigs = max(1, codes.shape[0] // contig_bp)
    cuts = np.sort(rng.choice(np.arange(1, codes.shape[0]),
                              n_contigs - 1, replace=False))
    return np.split(codes, cuts)


def _quality(rng: np.random.Generator, lo_hi: Sequence[float]) -> str:
    lo, hi = lo_hi
    return f"{rng.uniform(lo, hi):.2f}"


def generate(traffic: dict, seed: int, job: int) -> Catalogue:
    """The catalogue of one job: a pure function of (traffic, seed,
    job)."""
    rng = np.random.default_rng([int(seed), int(job)])
    plan = species_plan(traffic)
    classes: Dict[int, List[int]] = {}
    for i, sp in enumerate(plan):
        classes.setdefault(sp["groups"] * sp["group_size"], []).append(i)
    lengths = [sp["length"] for sp in plan]
    for members in classes.values():
        perm = rng.permutation(len(members))
        shuffled = [lengths[members[p]] for p in perm]
        for i, length in zip(members, shuffled):
            plan[i]["length"] = length

    n_total = sum(sp["groups"] * sp["group_size"] for sp in plan)
    ids = rng.permutation(n_total)
    genomes: List[Genome] = []
    group_id = 0
    for s, sp in enumerate(plan):
        base = rng.integers(0, 4, sp["length"], dtype=np.uint8)
        for _g in range(sp["groups"]):
            gbase = (_mutate(base, sp["group_rate"], rng)
                     if sp["groups"] > 1 else base)
            for _m in range(sp["group_size"]):
                codes = _mutate(gbase, sp["member_rate"], rng)
                genomes.append(Genome(
                    name=f"mag_{ids[len(genomes)]:04d}", species=s,
                    group=group_id,
                    contigs=_cut(codes, int(traffic["contig_bp"]), rng),
                    completeness=_quality(rng, traffic["completeness"]),
                    contamination=_quality(rng,
                                           traffic["contamination"])))
            group_id += 1
    genomes.sort(key=lambda g: g.name)
    return Catalogue(genomes)


def write(catalogue: Catalogue, job_dir: Path) -> dict:
    """FASTA files, the genome list and the quality report of a
    catalogue under `job_dir`; returns their paths."""
    job_dir.mkdir(parents=True, exist_ok=True)
    paths = []
    for g in catalogue.genomes:
        path = job_dir / f"{g.name}.fna"
        with open(path, "wb") as fh:
            for i, contig in enumerate(g.contigs):
                fh.write(b">contig_%d\n" % i)
                fh.write(ASCII[contig].tobytes())
                fh.write(b"\n")
        paths.append(str(path))
    genome_list = job_dir / "genomes.txt"
    genome_list.write_text("".join(p + "\n" for p in paths))
    quality = job_dir / "quality_report.tsv"
    quality.write_text(QUALITY_HEADER + "".join(
        f"{g.name}\t{g.completeness}\t{g.contamination}\t"
        "Neural Network (Specific Model)\t11\tNone\n"
        for g in catalogue.genomes))
    return {"genome_list": str(genome_list), "quality": str(quality),
            "paths": paths}
