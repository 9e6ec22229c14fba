#!/usr/bin/env python3
"""Smoke test of `galah-tpu cluster` on TPU chips at real MAG sizes.

Runs the CLI's main path on a seeded synthetic catalogue and checks it
against a CPU run of the same command:

  device     this process pins the platform and finds its chips; the
             CPU reference children below never load the TPU runtime,
             so they do not contend for the chips it holds.
  corpus     planted families of log-normal length, median --genome-mbp
             Mbp (clipped to 1/3..8/3 of the median), ~1% point mutations
             per member, written as FASTA contigs: families of 8 fill
             --genomes, and from 256 genomes on one family of 80 (past
             the engine's DENSE_PRECLUSTER_CAP of 64) takes the place of
             ten of them.
  reference  one `galah-tpu cluster --platform cpu` child per method
             pair (skani+skani, finch+skani), one at a time: each holds
             the whole corpus's fragment profiles in host memory.
  chip       in-process `galah_tpu.cli.main` runs on the TPU:
             (a) skani+skani; (b) finch+skani (overlapped engine, fused
             sketch kernel, and the megakernel where a family passes 64
             members); (c) finch+skani with GALAH_TPU_SPARSE_MIN_N at
             min(128, --genomes) (host collision screen + the device
             pairlist kernel, the path above 1024 genomes).
  checks     each chip TSV is byte-identical to its CPU reference and
             holds exactly the planted families; each run report names
             the TPU backend, records no demotion, and carries the
             counters that prove the device paths ran.

With --chips 4 only run (c) and its reference run, on all four chips,
and every device must have held work.

Usage:
    python chip_smoke.py [--seed 0] [--genomes 256] [--genome-mbp 3]
                         [--out chip_smoke_out] [--chips 1|4]

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}
with "ok": false (and an "error") on any failure. Without a TPU it exits
non-zero. With JAX_PLATFORMS set to a platform other than tpu, a corpus
of at most REHEARSAL_MAX_MBP runs every phase on that platform as a
rehearsal and then fails the device check; a larger one stops there.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

FAMILY_SIZE = 8
LARGE_FAMILY_SIZE = 80
LARGE_FAMILY_MIN_GENOMES = 256
SPARSE_MIN_N = 128
MUTATION_RATE = 0.01
LENGTH_SIGMA = 0.5
CONTIG_MEAN_BP = 100_000
REHEARSAL_MAX_MBP = 32.0

METHOD_PAIRS = {"skani": ("skani", "skani"), "finch": ("finch", "skani")}



def chip_runs(sizes: list) -> list:
    """(label, method pair, extra environment, counters that must be
    > 0) for each chip run over a corpus of these family sizes."""
    from galah_tpu.cluster.engine import DENSE_PRECLUSTER_CAP

    common = ["fragment-strategy-pallas", "greedy-rounds"]
    b_required = common + ["sketch-fused-launches", "overlap-engaged"]
    if max(sizes) > DENSE_PRECLUSTER_CAP:
        b_required.append("megakernel-slab-folds")
    c_env = {"GALAH_TPU_SPARSE_MIN_N": str(min(SPARSE_MIN_N,
                                              sum(sizes)))}
    return [("a", "skani", {}, common),
            ("b", "finch", {}, b_required),
            ("c", "finch", c_env, common)]


class SmokeFailure(Exception):
    pass


def emit(phase: str, wall: float, **findings) -> None:
    print(json.dumps({"phase": phase, "wall_s": wall, **findings}),
          flush=True)


def family_sizes(n_genomes: int) -> list:
    sizes = []
    if n_genomes >= LARGE_FAMILY_MIN_GENOMES:
        sizes.append(LARGE_FAMILY_SIZE)
        n_genomes -= LARGE_FAMILY_SIZE
    sizes += [FAMILY_SIZE] * (n_genomes // FAMILY_SIZE)
    if n_genomes % FAMILY_SIZE:
        sizes.append(n_genomes % FAMILY_SIZE)
    return sizes


def write_fasta(path: Path, codes: np.ndarray,
                rng: np.random.Generator) -> None:
    n_contigs = max(1, codes.size // CONTIG_MEAN_BP)
    cuts = np.sort(rng.choice(np.arange(1, codes.size), n_contigs - 1,
                              replace=False))
    bounds = np.concatenate([[0], cuts, [codes.size]])
    text = np.frombuffer(b"ACGT", dtype=np.uint8)[codes]
    with open(path, "wb") as fh:
        for i in range(n_contigs):
            fh.write(b">contig_%d\n" % i)
            fh.write(text[bounds[i]:bounds[i + 1]].tobytes())
            fh.write(b"\n")


def write_corpus(corpus: Path, seed: int, n_genomes: int,
                 median_mbp: float) -> tuple:
    """Planted families as FASTA files; returns (families, total bp)
    where families is a list of lists of file paths."""
    corpus.mkdir(parents=True, exist_ok=True)
    for old in corpus.glob("*.fna"):
        old.unlink()
    rng = np.random.default_rng(seed)
    median = median_mbp * 1e6
    families, total = [], 0
    for f, size in enumerate(family_sizes(n_genomes)):
        length = int(np.clip(rng.lognormal(np.log(median), LENGTH_SIGMA),
                             median / 3, median * 8 / 3))
        base = rng.integers(0, 4, length, dtype=np.uint8)
        members = []
        for m in range(size):
            codes = base.copy()
            hit = np.flatnonzero(rng.random(length) < MUTATION_RATE)
            codes[hit] = (codes[hit] + rng.integers(
                1, 4, hit.size, dtype=np.uint8)) % 4
            path = corpus / f"fam{f:02d}_{m:02d}.fna"
            write_fasta(path, codes, rng)
            members.append(str(path))
            total += length
        families.append(members)
    return families, total


def cluster_argv(platform: str, method: str, genome_list: Path,
                 tsv: Path, report: Path, threads: int) -> list:
    pre, cl = METHOD_PAIRS[method]
    return ["cluster", "--platform", platform, "--threads", str(threads),
            "--genome-fasta-list", str(genome_list),
            "--precluster-method", pre, "--cluster-method", cl,
            "--output-cluster-definition", str(tsv),
            "--run-report", str(report)]


def run_reference(method: str, out: Path, genome_list: Path,
                  threads: int, procs: list) -> None:
    """One CPU reference run in a child process; its wall and peak RSS
    go on its phase line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for name in ("GALAH_TPU_SPARSE_MIN_N", "GALAH_TPU_CACHE"):
        env.pop(name, None)
    argv = cluster_argv("cpu", method, genome_list,
                        out / f"ref_{method}.tsv",
                        out / f"ref_{method}_report.json", threads)
    t0 = time.monotonic()
    with open(out / f"ref_{method}.log", "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "galah_tpu.cli", *argv], cwd=ROOT,
            env=env, stdout=log, stderr=subprocess.STDOUT)
    procs.append(proc)
    _, status, usage = os.wait4(proc.pid, 0)
    proc.returncode = os.waitstatus_to_exitcode(status)
    if proc.returncode != 0:
        tail = (out / f"ref_{method}.log").read_bytes()[-2000:]
        raise SmokeFailure(f"CPU reference {method} exited "
                           f"{proc.returncode}: "
                           f"{tail.decode(errors='replace')}")
    emit(f"reference-{method}", time.monotonic() - t0,
         peak_rss_mb=usage.ru_maxrss / 1024,
         tsv=str(out / f"ref_{method}.tsv"))


def tsv_clusters(tsv: Path) -> set:
    groups: dict = {}
    for line in tsv.read_text().splitlines():
        rep, member = line.split("\t")
        groups.setdefault(rep, set()).add(member)
    return {frozenset(g) for g in groups.values()}


def check_run(label: str, method: str, report: dict, required: list,
              tsv: Path, ref_tsv: Path, planted: set) -> dict:
    """The checks one chip run must pass; returns its findings."""
    problems = []
    if tsv.read_bytes() != ref_tsv.read_bytes():
        problems.append(f"TSV differs from the {method} CPU reference")
    if tsv_clusters(tsv) != planted:
        problems.append("clusters are not the planted families")
    backend = report.get("device", {}).get("backend")
    if backend != "tpu":
        problems.append(f"report backend is {backend!r}")
    demotions = report.get("resilience", {}).get("demotions")
    if demotions:
        problems.append(f"demotions: {demotions}")
    demoted_events = [e.get("kind") for e in report.get("events", [])
                      if e.get("kind") == "demotion"
                      or str(e.get("kind", "")).endswith("-demoted")]
    if demoted_events:
        problems.append(f"demotion events: {demoted_events}")
    counters = report.get("counters", {})
    for name in required:
        if not counters.get(name):
            problems.append(f"counter {name} is zero")
    if label == "c":
        pairlist = {k: v for k, v in counters.items()
                    if k.startswith("pairlist-strategy-")}
        if not (pairlist.get("pairlist-strategy-blocked")
                or pairlist.get("pairlist-strategy-gather")):
            problems.append(f"no Mosaic pairlist strategy: {pairlist}")
        for bad in ("pairlist-strategy-cpu", "pairlist-strategy-xla"):
            if pairlist.get(bad):
                problems.append(f"{bad} ran")
    shown = {k: v for k, v in counters.items()
             if k in required or k.startswith(("pairlist-strategy-",
                                               "sketch-strategy-",
                                               "fragment-strategy-",
                                               "greedy-strategy-"))}
    return {"problems": problems, "counters": shown,
            "clusters": len(tsv_clusters(tsv)), "backend": backend}


def chip_run(label: str, method: str, extra_env: dict, platform: str,
             out: Path, genome_list: Path, threads: int) -> tuple:
    from galah_tpu import cli

    tsv = out / f"chip_{label}.tsv"
    report = out / f"chip_{label}_report.json"
    saved = {k: os.environ.get(k) for k in extra_env}
    os.environ.update(extra_env)
    try:
        rc = cli.main(cluster_argv(platform, method, genome_list, tsv,
                                   report, threads))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if rc != 0:
        raise SmokeFailure(f"chip run ({label}) exited {rc}")
    return tsv, json.loads(report.read_text())


def devices_with_work(jax) -> list:
    """Per-device peak bytes in use, where the backend reports them."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--genomes", type=int, default=256)
    ap.add_argument("--genome-mbp", type=float, default=3.0,
                    help="median genome length in Mbp")
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"))
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    out = Path(args.out).resolve()
    out.mkdir(parents=True, exist_ok=True)
    device = {"platform": None, "kind": None, "count": 0}
    procs: list = []
    try:
        from galah_tpu.utils.compile_cache import configure_compile_cache

        # An explicit JAX_PLATFORMS other than tpu is a rehearsal.
        platform = os.environ.get("JAX_PLATFORMS") or "tpu"
        import jax

        jax.config.update("jax_platforms", platform)
        cache_dir = configure_compile_cache()
        t0 = time.monotonic()
        try:
            devs = jax.devices()
        except RuntimeError as e:
            raise SmokeFailure(f"no {platform} device: {e}") from e
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        device_ok = (device["platform"] == "tpu"
                     and device["count"] == args.chips)
        emit("device", time.monotonic() - t0, **device,
             jax=jax.__version__, compile_cache=cache_dir)
        if not device_ok and (platform == "tpu" or args.genomes
                              * args.genome_mbp > REHEARSAL_MAX_MBP):
            raise SmokeFailure(
                f"device check: want {args.chips} tpu device(s), got "
                f"{device}")

        t0 = time.monotonic()
        families, total_bp = write_corpus(out / "corpus", args.seed,
                                          args.genomes, args.genome_mbp)
        planted = {frozenset(f) for f in families}
        genome_list = out / "genomes.txt"
        genome_list.write_text(
            "".join(p + "\n" for f in families for p in f))
        emit("corpus", time.monotonic() - t0, genomes=args.genomes,
             families=len(families), total_bp=total_bp)

        runs = chip_runs(family_sizes(args.genomes))
        if args.chips == 4:
            runs = [r for r in runs if r[0] == "c"]
        threads = os.cpu_count() or 1
        for method in sorted({r[1] for r in runs}, reverse=True):
            run_reference(method, out, genome_list, threads, procs)

        problems = []
        for label, method, extra_env, required in runs:
            t_run = time.monotonic()
            tsv, report = chip_run(label, method, extra_env, platform,
                                   out, genome_list, threads)
            found = check_run(label, method, report, required, tsv,
                              out / f"ref_{method}.tsv", planted)
            emit(f"chip-{label}", time.monotonic() - t_run,
                 method=method, env=extra_env,
                 run_s=report.get("run", {}).get("duration_s"),
                 peak_rss_mb=resource.getrusage(
                     resource.RUSAGE_SELF).ru_maxrss / 1024, **found)
            problems += [f"({label}) {p}" for p in found["problems"]]
        if args.chips == 4:
            peaks = devices_with_work(jax)
            emit("devices", 0.0, peak_bytes_in_use=peaks)
            if not all(peaks):
                problems.append(f"a device held no work: {peaks}")
        if not device_ok:
            problems.append(f"device check: want {args.chips} tpu "
                            f"device(s), got {device}")
        if problems:
            raise SmokeFailure("; ".join(problems))
    except Exception as e:  # noqa: BLE001 - every failure ends the run
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    finally:
        for proc in procs:
            if proc.returncode is None:
                proc.kill()
                proc.wait()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
