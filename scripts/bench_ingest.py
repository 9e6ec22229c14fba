"""Host FASTA ingestion at multi-Gbp: measure the named north-star risk.

A 50k-genome extrapolation named host-side FASTA ingestion (~175 Gbp)
as the open risk on an assumed ~100 MB/s/core. This bench
replaces the assumption with measurements at real scale:

  1. single-thread C-parser throughput (csrc/ingest.c via
     io/fasta.read_genome) over a generated multi-Gbp corpus;
  2. thread-pool ingestion (the ctypes call releases the GIL, so a
     multicore host parses that many files concurrently — measured
     with the machine's actual core count, recorded in the output);
  3. gzipped-input throughput (the reference ingests .gz via
     needletail the same way, reference: src/genome_stats.rs:1-51);
  4. the REAL per-host ingestion split (parallel/distributed.host_shard)
     driven by two actual jax.distributed processes, each ingesting
     >= 1 Gbp of its own file slice.

Usage: python scripts/bench_ingest.py [--gbp 10] [--files 24]
       [--keep] [--skip-dist]
Prints one JSON line per measurement and INGEST_JSON with the summary.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_DIST_WORKER = r"""
import os, sys, time
coord, n_proc, pid, listfile = sys.argv[1:5]
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=coord,
                           num_processes=int(n_proc),
                           process_id=int(pid))
from galah_tpu.io.fasta import read_genome
from galah_tpu.parallel import distributed

paths = [line.strip() for line in open(listfile) if line.strip()]
mine = distributed.host_shard(paths)
t0 = time.perf_counter()
total_bp = 0
for p in mine:
    total_bp += read_genome(p).codes.shape[0]
dt = time.perf_counter() - t0
print(f"RESULT pid={pid} files={len(mine)} bp={total_bp} "
      f"wall={dt:.2f}", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_corpus(outdir: str, gbp: float, n_files: int) -> list:
    """Write n_files FASTA files totaling ~gbp Gbp.

    One 64 Mbp random block is generated once and written at rotating
    offsets (content repetition is irrelevant to parser throughput;
    generation at numpy speed would otherwise dominate the setup).
    Contigs are 4 Mbp with 80-col-free long lines plus a sprinkling of
    N's so the ambiguity counter is exercised."""
    os.makedirs(outdir, exist_ok=True)
    rng = np.random.default_rng(0)
    block_bp = 64 << 20
    lut = np.frombuffer(b"ACGT", dtype=np.uint8)
    block = lut[rng.integers(0, 4, size=block_bp)]
    block[rng.integers(0, block_bp, size=1000)] = ord("N")
    blk = block.tobytes()

    per_file = int(gbp * 1e9 / n_files)
    contig = 4 << 20
    paths = []
    for f in range(n_files):
        p = os.path.join(outdir, f"g{f:03d}.fna")
        paths.append(p)
        if os.path.exists(p) and os.path.getsize(p) > per_file:
            continue  # --keep rerun
        with open(p, "wb") as fh:
            written = 0
            c = 0
            while written < per_file:
                n = min(contig, per_file - written)
                off = (f * 7919 + c * 104729) % (block_bp - n) \
                    if block_bp > n else 0
                fh.write(b">contig%d\n" % c)
                fh.write(blk[off:off + n])
                fh.write(b"\n")
                written += n
                c += 1
    return paths


def _gzip_subset(paths: list, n: int) -> list:
    """Gzip the first n corpus files (idempotent), return the .gz paths."""
    out = []
    for p in paths[:n]:
        gz = p + ".gz"
        if not os.path.exists(gz):
            subprocess.run(["gzip", "-1", "-k", "-f", p], check=True)
        out.append(gz)
    return out


def run_ingest_variants(args) -> dict:
    """The ingest_variants bench stage: end-to-end ingest+sketch Mbp/s
    by strategy x workers x gzip, against the serial-prologue baseline
    (read everything, then sketch everything — the pipeline shape
    before the streaming subsystem), with the host/device cost split.

    The full >= --sketch-gbp corpus runs through the streamed AUTO
    pipeline (the headline + speedup_vs_serial number); the variant
    matrix and the baselines run on a subset so the stage fits its
    budget. Self-budgeting: once --budget seconds elapse, remaining
    variants are skipped (recorded in "skipped")."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from galah_tpu.backends.minhash_backend import SketchStore
    from galah_tpu.io.diskcache import CacheDir
    from galah_tpu.io.fasta import read_genome
    from galah_tpu.ops import sketch_stream

    t_start = time.perf_counter()

    def remaining() -> float:
        if not args.budget:
            return float("inf")
        return args.budget - (time.perf_counter() - t_start)

    # ~4.3 Mbp per file (a realistic microbial assembly, and an
    # awkward size for pow2 chunk padding) -> multi-file corpus
    per_file_bp = 4_300_000
    n_files = max(8, int(args.sketch_gbp * 1e9 / per_file_bp))
    paths = make_corpus(args.dir, args.sketch_gbp, n_files)
    total_bp_est = int(args.sketch_gbp * 1e9)
    subset = paths[:max(4, int(args.variants_mbp * 1e6
                               // per_file_bp))]
    out = {
        "sketch_gbp": args.sketch_gbp,
        "n_files": len(paths),
        "per_file_mbp": round(per_file_bp / 1e6, 1),
        "subset_files": len(subset),
        "n_cores": os.cpu_count() or 1,
        "variants": {},
        "skipped": [],
    }

    def fresh_store() -> SketchStore:
        import tempfile

        return SketchStore(1000, 21,
                           cache=CacheDir(tempfile.mkdtemp()))

    def streamed(ps, workers, strategy=None):
        store = fresh_store()
        t0 = time.perf_counter()
        bp = 0
        for _p, _s in sketch_stream.iter_path_sketches(
                ps, store, threads=workers, strategy=strategy):
            pass
        bp = sum(read_bp.get(p, 0) for p in ps) or None
        dt = time.perf_counter() - t0
        return dt, bp

    read_bp: dict = {}

    # 1. serial-prologue baseline (subset): read ALL files, then one
    # batched device sketch pass — the historical device-pipeline
    # shape this PR replaces. Host/device split = read wall vs rest.
    label = "serial_prologue_xla"
    if remaining() > 0:
        store = fresh_store()
        t0 = time.perf_counter()
        gs = [(p, read_genome(p)) for p in subset]
        t_read = time.perf_counter() - t0
        for p, g in gs:
            read_bp[p] = int(g.codes.shape[0])
        store.sketch_batch_only(gs)
        dt = time.perf_counter() - t0
        bp = sum(read_bp[p] for p in subset)
        out["variants"][label] = {
            "mbp_s": round(bp / 1e6 / dt, 2),
            "host_read_s": round(t_read, 2),
            "device_sketch_s": round(dt - t_read, 2),
            "wall_s": round(dt, 2), "workers": 1}
        del gs
    else:
        out["skipped"].append(label)

    # 2. serial-prologue C baseline (subset): the historical
    # single-device-CPU shape (per-genome C bottom-k after the read).
    label = "serial_prologue_c"
    if remaining() > 0 and sketch_stream._c_sketcher_available():
        store = fresh_store()
        t0 = time.perf_counter()
        gs = [(p, read_genome(p)) for p in subset]
        t_read = time.perf_counter() - t0
        for _p, g in gs:
            store.sketch_only(g)
        dt = time.perf_counter() - t0
        bp = sum(read_bp[p] for p in subset)
        out["variants"][label] = {
            "mbp_s": round(bp / 1e6 / dt, 2),
            "host_read_s": round(t_read, 2),
            "host_sketch_s": round(dt - t_read, 2),
            "wall_s": round(dt, 2), "workers": 1}
        del gs
    else:
        out["skipped"].append(label)

    # 3. streamed variant matrix (subset): strategy x workers. AUTO
    # resolves per backend (the C bottom-k on this single-device CPU
    # box); the xla pin records the chunked device path for the
    # speedup denominator's sanity.
    matrix = [("auto", None, 1), ("auto", None, 2),
              ("xla", "xla", 2)]
    for name, strat, workers in matrix:
        label = f"streamed_{name}_w{workers}"
        if remaining() <= 0:
            out["skipped"].append(label)
            continue
        dt, _ = streamed(subset, workers, strat)
        bp = sum(read_bp.get(p, 0) for p in subset)
        out["variants"][label] = {
            "mbp_s": round(bp / 1e6 / dt, 2) if bp else None,
            "wall_s": round(dt, 2), "workers": workers,
            "strategy": name}
        print(json.dumps({label: out["variants"][label]}), flush=True)

    # 4. gzip subset through the streamed AUTO pipeline: byte-identical
    # sketches at whatever the decompressor adds to the host cost.
    label = "streamed_auto_gzip"
    if remaining() > 0:
        gz = _gzip_subset(subset, min(8, len(subset)))
        plain_bp = sum(read_bp.get(p, 0)
                       for p in subset[:len(gz)])
        dt, _ = streamed(gz, 2, None)
        out["variants"][label] = {
            "mbp_s": round(plain_bp / 1e6 / dt, 2) if plain_bp else None,
            "wall_s": round(dt, 2), "workers": 2, "files": len(gz)}
    else:
        out["skipped"].append(label)

    # 5. the >= 1 Gbp headline: the whole corpus through the streamed
    # AUTO pipeline, overlapped ingest + sketch.
    label = "overlapped_full_corpus"
    if remaining() > 0:
        dt, _ = streamed(paths, 2, None)
        out["variants"][label] = {
            "mbp_s": round(total_bp_est / 1e6 / dt, 2),
            "wall_s": round(dt, 2), "workers": 2,
            "gbp": args.sketch_gbp}
        base = out["variants"].get("serial_prologue_xla")
        if base and base["mbp_s"]:
            out["speedup_vs_serial"] = round(
                out["variants"][label]["mbp_s"] / base["mbp_s"], 2)
        out["overlapped_mbp_s"] = out["variants"][label]["mbp_s"]
    else:
        out["skipped"].append(label)
    if "serial_prologue_xla" in out["variants"]:
        out["serial_prologue_mbp_s"] = \
            out["variants"]["serial_prologue_xla"]["mbp_s"]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gbp", type=float, default=10.0)
    ap.add_argument("--files", type=int, default=24)
    ap.add_argument("--dir", default="/tmp/galah_ingest_bench")
    ap.add_argument("--keep", action="store_true")
    ap.add_argument("--skip-dist", action="store_true")
    ap.add_argument("--variants", action="store_true",
                    help="run the ingest_variants sketch matrix "
                         "instead of the raw-parser measurements")
    ap.add_argument("--budget", type=float, default=0.0,
                    help="self-budget in seconds for --variants")
    ap.add_argument("--sketch-gbp", type=float, default=1.1,
                    help="--variants corpus size (>= 1 Gbp for the "
                         "acceptance headline)")
    ap.add_argument("--variants-mbp", type=float, default=90.0,
                    help="--variants subset size for the matrix and "
                         "baselines")
    args = ap.parse_args()

    if args.variants:
        out = run_ingest_variants(args)
        print("INGEST_JSON " + json.dumps(out), flush=True)
        if not args.keep:
            import shutil

            shutil.rmtree(args.dir, ignore_errors=True)
        return

    import jax

    jax.config.update("jax_platforms", "cpu")
    from galah_tpu.io.fasta import read_genome

    ncores = os.cpu_count() or 1
    out = {"gbp": args.gbp, "n_files": args.files, "n_cores": ncores}

    t0 = time.perf_counter()
    paths = make_corpus(args.dir, args.gbp, args.files)
    total_bytes = sum(os.path.getsize(p) for p in paths)
    print(json.dumps({"setup_s": round(time.perf_counter() - t0, 1),
                      "corpus_gb": round(total_bytes / 1e9, 2)}),
          flush=True)

    # 1. single-thread sequential ingest
    t0 = time.perf_counter()
    total_bp = 0
    for p in paths:
        total_bp += read_genome(p).codes.shape[0]
    dt = time.perf_counter() - t0
    out["single_thread_mb_per_s"] = round(total_bytes / dt / 1e6, 1)
    out["single_thread_bp_per_s"] = round(total_bp / dt, 0)
    out["single_thread_wall_s"] = round(dt, 2)
    print(json.dumps({"single_thread": out["single_thread_mb_per_s"],
                      "unit": "MB/s"}), flush=True)

    # 2. thread-pool ingest (ctypes releases the GIL during the C call)
    from concurrent.futures import ThreadPoolExecutor

    workers = max(2, ncores)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=workers) as pool:
        bps = list(pool.map(
            lambda p: read_genome(p).codes.shape[0], paths))
    dt = time.perf_counter() - t0
    assert sum(bps) == total_bp
    out["threaded_workers"] = workers
    out["threaded_mb_per_s"] = round(total_bytes / dt / 1e6, 1)
    out["threaded_wall_s"] = round(dt, 2)
    print(json.dumps({"threaded": out["threaded_mb_per_s"],
                      "workers": workers, "unit": "MB/s"}), flush=True)

    # 3. gzip ingest on the first file
    gz = paths[0] + ".gz"
    if not os.path.exists(gz):
        subprocess.run(["gzip", "-1", "-k", "-f", paths[0]], check=True)
    gz_bytes = os.path.getsize(gz)
    t0 = time.perf_counter()
    bp = read_genome(gz).codes.shape[0]
    dt = time.perf_counter() - t0
    out["gzip_mb_per_s_compressed"] = round(gz_bytes / dt / 1e6, 1)
    out["gzip_bp_per_s"] = round(bp / dt, 0)
    print(json.dumps({"gzip_bp_per_s": out["gzip_bp_per_s"]}),
          flush=True)

    # 4. the real per-host split: 2 jax.distributed processes
    if not args.skip_dist:
        listfile = os.path.join(args.dir, "files.txt")
        with open(listfile, "w") as fh:
            fh.write("\n".join(paths))
        coord = f"127.0.0.1:{_free_port()}"
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.perf_counter()
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _DIST_WORKER, coord, "2",
                 str(pid), listfile],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env, cwd=REPO)
            for pid in range(2)
        ]
        lines = []
        ok = True
        for p in procs:
            so, se = p.communicate(timeout=3600)
            ok &= p.returncode == 0
            lines += [ln for ln in so.splitlines()
                      if ln.startswith("RESULT")]
            if p.returncode != 0:
                print(se[-500:], file=sys.stderr)
        dt = time.perf_counter() - t0
        out["dist_2proc_ok"] = ok
        out["dist_2proc_wall_s"] = round(dt, 2)
        out["dist_2proc_mb_per_s"] = round(total_bytes / dt / 1e6, 1)
        for ln in lines:
            print(ln, flush=True)

    print("INGEST_JSON " + json.dumps(out), flush=True)
    if not args.keep:
        import shutil

        shutil.rmtree(args.dir, ignore_errors=True)


if __name__ == "__main__":
    main()
