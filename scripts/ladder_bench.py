"""Measurement ladder: end-to-end cluster() wall-clock.

Runs the first rungs of the BASELINE.json config ladder on the current
backend (TPU via the default interpreter; CPU with --cpu) and prints a
markdown table row per rung with stage breakdowns:

  rung 1: the abisko4 fixture set (18 real MAGs, 29 MB) — full two-stage
          pipeline, CheckM quality ordering;
  rung 2: N synthetic genomes with planted family structure
          (default 100; --n to scale), precluster+cluster at 95/90.

Usage: python scripts/ladder_bench.py [--cpu] [--n 100] [--hash tpufast]
"""

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--n", type=int, default=100,
                    help="rung-2 synthetic genome count")
    ap.add_argument("--genome-len", type=int, default=500_000)
    ap.add_argument("--hash", default="murmur3",
                    choices=("murmur3", "tpufast"))
    ap.add_argument("--skip-rung1", action="store_true")
    ap.add_argument("--ani-subsample", type=int, default=1,
                    help="FracMinHash c for the exact-ANI stage")
    ap.add_argument("--rung4", action="store_true",
                    help="also run the quality-ordered rung: synthetic "
                         "CheckM2 report + Parks2020_reduced ranking "
                         "(BASELINE.json rung-4 semantics)")
    ap.add_argument("--repeat-frac", type=float, default=0.0,
                    help="rung 2 becomes the adversarial repeat rung: "
                         "UNRELATED genomes sharing this fraction of "
                         "mobile-element content from one pool "
                         "(bench._synth_repeat_genomes) — the "
                         "collision screen's worst case, for "
                         "wall-clock comparison against the uniform "
                         "rung at equal N*bp")
    ap.add_argument("--mega", action="store_true",
                    help="dense-similarity worst case: ONE planted "
                         "mega-family (every pair >95%% ANI) through "
                         "the DEFAULT skani+skani path — the 'many "
                         "closely related genomes' regime the "
                         "reference advertises "
                         "(reference: README.md:18-26). Replaces "
                         "rung 2; --n sets the family size.")
    args = ap.parse_args()
    if args.mega and args.repeat_frac > 0:
        ap.error("--mega and --repeat-frac are mutually exclusive "
                 "(each replaces rung 2 with a different workload)")

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("JAX_ENABLE_X64", "1")
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax

    from galah_tpu.api import generate_galah_clusterer
    from galah_tpu.utils import timing

    backend = jax.default_backend()
    results = []

    def run(name, paths, values):
        timing.reset()
        t0 = time.perf_counter()
        clusterer = generate_galah_clusterer(paths, values)
        clusters = clusterer.cluster()
        dt = time.perf_counter() - t0
        stages = {name: round(secs, 2)
                  for name, secs, _count in timing.GLOBAL.items()}
        results.append({
            "rung": name, "backend": backend, "n_genomes": len(paths),
            "wall_s": round(dt, 2), "n_clusters": len(clusters),
            "genomes_per_s": round(len(paths) / dt, 3),
            "stages": stages,
            "counters": timing.GLOBAL.counters(),
        })
        print(json.dumps(results[-1]), flush=True)

    base_values = {
        "ani": 95.0, "precluster_ani": 90.0,
        "min_aligned_fraction": 15.0, "fragment_length": 3000,
        "precluster_method": "finch", "cluster_method": "skani",
        "threads": 4, "hash_algorithm": args.hash,
        "ani_subsample": args.ani_subsample,
    }

    if not args.skip_rung1:
        DATA = "/root/reference/tests/data/abisko4"
        paths = sorted(glob.glob(f"{DATA}/*.fna"))
        values = dict(base_values)
        values["checkm_tab_table"] = f"{DATA}/abisko4.csv"
        values["quality_formula"] = "Parks2020_reduced"
        run("rung1-abisko18", paths, values)

    # rung 2: synthetic planted families
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import importlib

    bench = importlib.import_module("bench")
    if args.mega:
        # All N genomes are ~2%-mutated copies of ONE base, so every
        # pair sits near 96% ANI and NOTHING screens out: the collision
        # screen's mega-run dedup, the single giant precluster's
        # transform_ids, and the greedy phase on one huge candidate
        # list are all on the hot path. Default config (skani+skani).
        paths = bench._synth_families(
            n_genomes=args.n, genome_len=args.genome_len,
            n_families=1, mut=0.02, seed=11)
        values = dict(base_values)
        values["precluster_method"] = "skani"
        values["cluster_method"] = "skani"
        run(f"rung-mega-{args.n}", paths, values)
    elif args.repeat_frac > 0:
        paths = bench._synth_repeat_genomes(
            n_genomes=args.n, genome_len=args.genome_len,
            repeat_frac=args.repeat_frac, seed=23)
        run(f"rung-repeat{args.repeat_frac:g}-{args.n}", paths,
            dict(base_values))
    else:
        n_fam = max(args.n // 4, 1)
        paths = bench._synth_families(
            n_genomes=args.n, genome_len=args.genome_len,
            n_families=n_fam, mut=0.03, seed=11)
        run(f"rung2-synthetic-{args.n}", paths, dict(base_values))

    if args.rung4:
        # rung 4 semantics: quality-ordered greedy rep selection from a
        # CheckM2-style quality report (BASELINE.json rung 4 uses 10k
        # MAGs + CheckM2; this synthesizes the same pipeline shape at
        # --n genomes so the quality path is measured, not just the
        # distance path).
        import numpy as np

        rng = np.random.default_rng(13)
        qpath = os.path.join(os.path.dirname(paths[0]),
                             "quality_report.tsv")
        with open(qpath, "w") as fh:
            fh.write("Name\tCompleteness\tContamination\n")
            for p in paths:
                stem = os.path.splitext(os.path.basename(p))[0]
                comp = rng.uniform(60.0, 100.0)
                cont = rng.uniform(0.0, 8.0)
                fh.write(f"{stem}\t{comp:.2f}\t{cont:.2f}\n")
        values = dict(base_values)
        values["checkm2_quality_report"] = qpath
        values["quality_formula"] = "Parks2020_reduced"
        values["min_completeness"] = 50.0
        values["max_contamination"] = 10.0
        run(f"rung4-quality-{args.n}", paths, values)

    print("\n| rung | backend | N | wall (s) | genomes/s | clusters |")
    print("|---|---|---|---|---|---|")
    for r in results:
        print(f"| {r['rung']} | {r['backend']} | {r['n_genomes']} | "
              f"{r['wall_s']} | {r['genomes_per_s']} | "
              f"{r['n_clusters']} |")


if __name__ == "__main__":
    main()
