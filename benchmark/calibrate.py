#!/usr/bin/env python3
"""Readings that the check's limits are set from (not part of a run).

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,...
                                   [--control-seeds k] [--checkpoint-ab n]

In one process on the chip: one warm-up job, then per seed one job of
the cell's traffic through the CLI and the reference, giving the
numbers the check compares (the lower readings). For the first k seeds
also, against the same reference:

  control   the reference computed with its ANI arithmetic in float32,
            one step below the float64 the configurations state, put in
            the program's place (its clusterer ANIs on the pairs the
            job computed, in float32 too);
  half      the reference with every second precluster pair left out;
  altered   the reference with one precluster ANI, and one clusterer
            ANI, moved by 1e-6.

Prints one JSON line per seed and reading. With --checkpoint-ab n, then
n pairs of jobs of the first seed's traffic, with and without
`--checkpoint-dir` in turn, one line per job with its wall: what the
checkpoint that the check reads costs a timed job.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import harness
import reference


def as_record(rec: harness.JobRecord, res: reference.JobResult,
              clusterer: dict) -> harness.JobRecord:
    """A job record whose answer is `res`, for comparing the control or
    a fault like the program."""
    return harness.JobRecord(
        job=rec.job, genomes=rec.genomes, bp=rec.bp, wall=0.0, rc=0,
        rows=res.rows(), pre=dict(res.pre), report={},
        clusterer=clusterer)


def drop_half(pre: dict) -> dict:
    return {p: v for n, (p, v) in enumerate(sorted(pre.items()))
            if n % 2 == 0}


def alter_one(pre: dict) -> dict:
    out = dict(pre)
    if out:
        key = sorted(out)[0]
        out[key] = out[key] + 1e-6
    return out


def readings(cell: harness.Cell, seed: int, rec: harness.JobRecord,
             threads: int, control: bool) -> dict:
    job, ref, truth = harness.reference_job(cell, seed, rec, threads)
    try:
        out = {"program": harness.compare(cell.config, rec, ref, truth)}
        if control:
            pairs = harness.clusterer_pairs(rec)
            control_cl = {frozenset(p): v for p, v in zip(
                pairs, job.clusterer_values(pairs, dtype=np.float32))}
            for name, kw, cl in (
                    ("control", {"dtype": np.float32}, control_cl),
                    ("half", {"alter_pre": drop_half}, ref.clusterer),
                    ("altered", {"alter_pre": alter_one},
                     alter_one(ref.clusterer))):
                res = job.solve(**kw)
                out[name] = harness.compare(
                    cell.config, as_record(rec, res, cl), ref, truth)
    finally:
        job.close()
    return {k: {n: c["value"] for n, c in v.items()}
            for k, v in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--checkpoint-ab", type=int, default=0)
    args = ap.parse_args(argv)
    started = time.monotonic()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = harness.resolve_cell(harness.load_manifest(), args.workload)
    sys.path.insert(0, str(harness.ROOT))
    harness.configure_jax()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibration needs the chip", file=sys.stderr)
        return 2
    threads = harness.host_cores()
    seeds = [int(s) for s in args.seeds.split(",")]
    harness.WORK_DIR.mkdir(parents=True, exist_ok=True)
    harness.run_job(cell, seeds[0], 0, threads)
    for n, seed in enumerate(seeds):
        rec = harness.run_job(cell, seed, 1, threads)
        harness.release_memory()
        if rec.rc != 0:
            print(json.dumps({"seed": seed, "rc": rec.rc}), flush=True)
            continue
        r = readings(cell, seed, rec, threads, n < args.control_seeds)
        print(json.dumps({"seed": seed, "wall": rec.wall,
                          "elapsed": time.monotonic() - started, **r}),
              flush=True)
        harness.release_memory()
    for n in range(2 * args.checkpoint_ab):
        # with, without, without, with, ...: neither side always first
        with_ckpt = n % 4 in (0, 3)
        rec = harness.run_job(cell, seeds[0], 2 + n, threads,
                              checkpoint=with_ckpt)
        print(json.dumps({"checkpoint": with_ckpt, "job": rec.job,
                          "rc": rec.rc, "wall": rec.wall}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
