"""Command-line interface: `galah-tpu cluster` / `galah-tpu cluster-validate`.

Flag surface mirrors the reference CLI (reference: src/main.rs:53-118 and
src/cluster_argument_parsing.rs:1265-1375); percentage arguments accept
either 1-100 or 0-1 and normalize to fractions (reference:
src/cluster_argument_parsing.rs:1160-1182). The compute path underneath is
the TPU-native pipeline.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time

import galah_tpu
from galah_tpu.api import add_cluster_arguments, generate_galah_clusterer
from galah_tpu.config import (Defaults, HASH_ALGORITHMS,
                              QUALITY_FORMULAS, parse_percentage)
from galah_tpu.utils import timing
from galah_tpu.utils.logging import set_log_level

logger = logging.getLogger("galah_tpu")


def _add_verbosity(p: argparse.ArgumentParser) -> None:
    p.add_argument("-v", "--verbose", action="store_true",
                   help="Print extra debugging information")
    p.add_argument("-q", "--quiet", action="store_true",
                   help="Unless there is an error, do not print log messages")
    p.add_argument("--platform", default=None,
                   help="Pin the JAX platform (e.g. cpu, tpu); a pinned "
                        "platform that cannot start is an error, never "
                        "a quiet fallback to another one. Env "
                        "equivalent: GALAH_TPU_PLATFORM. Default: "
                        "JAX_PLATFORMS, else JAX's own choice, which "
                        "falls back to the CPU when no accelerator "
                        "starts — the first log line names the "
                        "platform in use")
    p.add_argument("--full-help", action="store_true",
                   help="Display an extended man-style help page and exit")
    p.add_argument("--full-help-roff", action="store_true",
                   help="Print the extended help as raw roff man source "
                        "and exit (pipe through `man -l -`)")


def _add_genome_inputs(p: argparse.ArgumentParser) -> None:
    p.add_argument("-f", "--genome-fasta-files", nargs="+",
                   help="Path(s) to FASTA files of each genome")
    p.add_argument("--genome-fasta-list",
                   help="File containing FASTA file paths, one per line")
    p.add_argument("-d", "--genome-fasta-directory",
                   help="Directory containing FASTA files of each genome")
    p.add_argument("-x", "--genome-fasta-extension", default="fna",
                   help="File extension of genomes in the directory "
                        "(default: fna)")


def _add_index_quality(p: argparse.ArgumentParser) -> None:
    """Quality-ordering inputs for `index build`/`index insert` — the
    same surface `cluster` carries, because insert order IS the greedy
    quality order the persisted decisions are sound under."""
    p.add_argument("--checkm-tab-table",
                   help="Output of `checkm qa .. --tab_table`")
    p.add_argument("--checkm2-quality-report",
                   help="CheckM2 quality_report.tsv output")
    p.add_argument("--genome-info",
                   help="dRep-style genome info CSV "
                        "(genome,completeness,contamination)")
    p.add_argument("--quality-formula",
                   default=Defaults.QUALITY_FORMULA,
                   choices=QUALITY_FORMULAS,
                   help="Quality formula for ranking genomes "
                        "(default: Parks2020_reduced)")
    p.add_argument("--min-completeness", type=float,
                   help="Ignore genomes with less completeness than "
                        "this percentage")
    p.add_argument("--max-contamination", type=float,
                   help="Ignore genomes with more contamination than "
                        "this percentage")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="galah-tpu",
        description="Metagenome assembled genome (MAG) dereplicator / "
                    "clusterer, TPU-native")
    parser.add_argument("--version", action="version",
                        version=galah_tpu.__version__)
    sub = parser.add_subparsers(dest="subcommand")

    c = sub.add_parser(
        "cluster",
        help="Cluster genomes by ANI, choosing quality-ranked "
             "representatives",
        description="Cluster genomes by average nucleotide identity "
                    "(ANI), choosing one quality-ranked representative "
                    "genome per cluster, on TPU")
    _add_verbosity(c)
    _add_genome_inputs(c)
    # Shared clustering/quality flags come from the embeddable API
    # factory (api.py) so the CLI and embedding tools stay in lockstep.
    add_cluster_arguments(c)
    c.add_argument("--sketch-cache",
                   help="Directory for the persistent sketch/profile "
                        "cache (also via GALAH_TPU_CACHE); sketches are "
                        "reused across runs when genome files are "
                        "unchanged")
    c.add_argument("--profile-trace-dir",
                   help="Capture an XLA profiler trace of the run into "
                        "this directory (TensorBoard-loadable)")
    c.add_argument("--trace-events",
                   help="Write a Chrome-trace-format event timeline "
                        "(stage spans, JAX compile events, resilience "
                        "events; Perfetto-loadable) to this file. Env "
                        "equivalent: GALAH_OBS_TRACE_EVENTS")
    c.add_argument("--run-report",
                   help="Write the machine-readable run_report.json "
                        "(stage tree, dispatch counts, precluster "
                        "funnel, flag snapshot, resilience events) to "
                        "this file at run end; render or diff it with "
                        "`galah-tpu report`. Env equivalent: "
                        "GALAH_OBS_REPORT")
    c.add_argument("--checkpoint-dir",
                   help="Persist the distance pass and finished "
                        "preclusters here; an interrupted run resumes "
                        "from the last completed precluster")
    c.add_argument("--resume", action="store_true",
                   help="Require resuming from --checkpoint-dir: fail "
                        "if the checkpoint is missing or belongs to a "
                        "different configuration instead of silently "
                        "starting fresh. Without this flag a matching "
                        "checkpoint still auto-resumes; --resume makes "
                        "\"no checkpoint\" an error. The run report's "
                        "preemption section records the resume chain")
    c.add_argument("--output-cluster-definition",
                   help="Output file of rep<TAB>member lines")
    c.add_argument("--output-representative-fasta-directory",
                   help="Symlink representative genomes into this directory")
    c.add_argument("--output-representative-fasta-directory-copy",
                   help="Copy representative genomes into this directory")
    c.add_argument("--output-representative-list",
                   help="Output file with one representative path per line")

    v = sub.add_parser(
        "cluster-validate", help="Verify clustering results",
        description="Re-check a cluster output file: every member must "
                    "reach the ANI threshold to its representative, and "
                    "no two representatives may reach it to each other")
    _add_verbosity(v)
    v.add_argument("--cluster-file",
                   help="Output of 'cluster' subcommand (required)")
    v.add_argument("--ani", type=float, default=99.0,
                   help="ANI to validate against (default: 99)")
    v.add_argument("--min-aligned-fraction", type=float, default=50.0,
                   help="Min aligned fraction of two genomes "
                        "(default: 50)")
    v.add_argument("--fragment-length", type=int,
                   default=Defaults.FRAGMENT_LENGTH,
                   help="Length of fragment used in fastANI-style "
                        "calculation (default: 3000)")
    v.add_argument("--ani-subsample", type=int,
                   default=Defaults.ANI_SUBSAMPLE,
                   help="FracMinHash compression of the exact ANI "
                        "re-check (see `cluster --full-help`; "
                        "default: 1)")
    v.add_argument("--hash-algorithm", default=Defaults.HASH_ALGO,
                   choices=sorted(HASH_ALGORITHMS),
                   help="k-mer hash for the validation profiles — use "
                        "the same value the clustering ran with so "
                        "near-threshold pairs score identically "
                        "(default: murmur3)")
    v.add_argument("--threads", "-t", type=int, default=1)

    dd = sub.add_parser(
        "dist",
        help="Calculate pairwise MinHash ANI between a set of genomes",
        description="All-pairs sketch-based ANI as a TSV — the "
                    "reference carries this subcommand disabled "
                    "(reference: src/main.rs:88-114); here the pair "
                    "matrix is one tiled device computation")
    _add_verbosity(dd)
    _add_genome_inputs(dd)
    dd.add_argument("--num-hashes", type=int,
                    default=Defaults.MINHASH_SKETCH_SIZE,
                    help="MinHash sketch size (default: 1000)")
    dd.add_argument("--kmer-length", type=int,
                    default=Defaults.MINHASH_KMER,
                    help="k-mer length (default: 21)")
    dd.add_argument("--hash-algorithm", default=Defaults.HASH_ALGO,
                    choices=HASH_ALGORITHMS,
                    help="Sketch hash (default: murmur3)")
    dd.add_argument("--min-ani", type=float, default=0.0,
                    help="Only report pairs at or above this ANI "
                         "(percent or fraction; default: report every "
                         "pair with any sketch overlap)")
    dd.add_argument("--output", help="Output TSV (default: stdout)")
    dd.add_argument("--sketch-cache",
                    help="Directory for the persistent sketch cache "
                         "(also via GALAH_TPU_CACHE)")
    dd.add_argument("--threads", "-t", type=int, default=1)

    li = sub.add_parser(
        "lint",
        help="Static analysis of the codebase: Pallas kernel "
             "contracts, tracer leaks, flag registry, shape "
             "contracts, lock discipline, numeric determinism, "
             "interprocedural effect auditors (GalahIR)",
        description="Run the galah-tpu static-analysis suite "
                    "(equivalent to `python -m galah_tpu.analysis`); "
                    "exits 1 on any unsuppressed finding at WARNING "
                    "or above")
    from galah_tpu.analysis import add_lint_arguments

    add_lint_arguments(li)

    rp = sub.add_parser(
        "report",
        help="Render or diff run_report.json files from past runs",
        description="Human-readable rendering of the machine-readable "
                    "run report a `cluster --run-report` run wrote "
                    "(stage wall-clock tree, dispatch/sync counts, "
                    "precluster funnel, flag snapshot, resilience "
                    "events); with --diff, per-stage and per-metric "
                    "deltas between two reports")
    _add_verbosity(rp)
    rp.add_argument("paths", nargs="+", metavar="REPORT",
                    help="run_report.json file(s) to render")
    rp.add_argument("--diff", action="store_true",
                    help="Compare exactly two reports: per-stage "
                         "wall-clock, dispatch/funnel, and per-metric "
                         "deltas")
    pf = sub.add_parser(
        "perf",
        help="Record, inspect, and gate on the cross-run performance "
             "ledger",
        description="The append-only perf ledger (JSONL, fed "
                    "automatically by runs with GALAH_OBS_LEDGER set) "
                    "keys every entry by backend, device topology, "
                    "workload fingerprint (N/K/P), and strategy. "
                    "`record` appends a run report's metrics, "
                    "`history` prints one metric's trajectory, and "
                    "`check` compares the newest entry against a "
                    "median±MAD noise band over the last entries of "
                    "the same key, exiting 1 on regression "
                    "(docs/observability.md)")
    _add_verbosity(pf)
    pf.add_argument("--ledger", default=None,
                    help="Ledger file (default: GALAH_OBS_LEDGER)")
    pfsub = pf.add_subparsers(dest="perf_action")
    pfr = pfsub.add_parser(
        "record", help="Append a run report's metrics to the ledger")
    pfr.add_argument("report", metavar="REPORT",
                     help="run_report.json to ingest")
    pfr.add_argument("--source", default="manual",
                     help="Key component naming what produced the "
                          "report (default: manual)")
    pfh = pfsub.add_parser(
        "history", help="Print one metric's cross-run trajectory")
    pfh.add_argument("metric", metavar="METRIC",
                     help="Metric name (e.g. run.duration_s, "
                          "bench.e2e_1000_genomes_per_sec)")
    pfh.add_argument("--key", default=None,
                     help="Only entries whose canonical key contains "
                          "this substring")
    pfc = pfsub.add_parser(
        "check",
        help="Gate: newest entry vs the same-key noise band "
             "(exit 1 on regression)")
    pfc.add_argument("--report", default=None,
                     help="Check this run_report.json against the "
                          "ledger instead of the ledger's own newest "
                          "entry (nothing is appended)")
    pfc.add_argument("--source", default="manual",
                     help="Key source component for --report entries")
    pfc.add_argument("--window", type=int, default=None,
                     help="Same-key history window (default: "
                          "GALAH_OBS_LEDGER_WINDOW)")
    pfc.add_argument("--mad-k", type=float, default=None,
                     help="Noise-band width in MADs (default: "
                          "GALAH_OBS_LEDGER_MAD_K)")
    pfc.add_argument("--min-history", type=int, default=None,
                     help="Entries required before a verdict "
                          "(default: 3)")
    pfc.add_argument("--soft", action="store_true",
                     help="Report regressions but exit 0 — the CI "
                          "mode while a key is still accumulating "
                          "trustworthy history")
    fl = sub.add_parser(
        "flow",
        help="Critical-path analysis of a run's flow telemetry",
        description="Item-level flow tracing over the overlapped "
                    "pipeline (GALAH_OBS_FLOW, on by default) records "
                    "per-stage service/wait time and inter-stage queue "
                    "latencies into the run report's `flow` section; "
                    "`analyze` recomputes the critical path from a "
                    "report and prints per-stage blame shares that sum "
                    "to the end-to-end wall "
                    "(docs/observability.md). Exit codes: 0 analysis "
                    "printed, 1 unreadable report or no flow "
                    "telemetry, 2 usage error")
    _add_verbosity(fl)
    flsub = fl.add_subparsers(dest="flow_action")
    fla = flsub.add_parser(
        "analyze",
        help="Print the critical path of one run report's flow "
             "telemetry")
    fla.add_argument("report", metavar="REPORT",
                     help="run_report.json carrying a `flow` section")
    fla.add_argument("--json", action="store_true",
                     help="Emit the critical-path attribution as JSON "
                          "instead of the rendered table")
    tp = sub.add_parser(
        "top",
        help="Live pipeline view from a run's heartbeat.jsonl (or a "
             "whole fleet dir)",
        description="Render the newest record of the heartbeat file a "
                    "run with GALAH_OBS_HEARTBEAT_S set writes beside "
                    "its run report: per-stage occupancy bars, queue "
                    "depths, and item throughput. Pointed at a fleet "
                    "dir (auto-detected from fleet_plan.json / "
                    "fleet_events.jsonl) it renders the per-shard "
                    "grid — state, attempt chain, beat age, occupancy, "
                    "rss — plus the scheduler event tail. Safe against "
                    "a run killed mid-write — a torn tail line is "
                    "skipped, never an error (docs/observability.md). "
                    "Exit codes: 0 rendered, 1 no heartbeat/fleet "
                    "data, 2 usage error")
    _add_verbosity(tp)
    tp.add_argument("directory", metavar="DIR",
                    help="Run artifact directory, a heartbeat.jsonl "
                         "path directly, or a fleet dir")
    tp.add_argument("--follow", action="store_true",
                    help="Keep refreshing until interrupted")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="Refresh period in seconds with --follow "
                         "(default: 2.0)")
    tp.add_argument("--json", action="store_true",
                    help="Emit the latest beat (or, for a fleet dir, "
                         "the fleet grid) as JSON instead of the "
                         "rendered page")
    ix = sub.add_parser(
        "index",
        help="Build and incrementally maintain a persistent versioned "
             "sketch index (insert/query/remove without re-clustering)",
        description="Persistent versioned sketch index over a "
                    "dereplicated corpus: `build` clusters once and "
                    "persists the sketches, thresholded pairs, and "
                    "greedy decisions; `insert` adds new genomes "
                    "sketching only them and commits a new generation; "
                    "`query` answers which cluster a genome would join "
                    "without mutating anything; `remove` tombstones a "
                    "genome and locally re-elects; `fsck` audits the "
                    "on-disk state (docs/index.md)")
    _add_verbosity(ix)
    ix.add_argument("--index-dir",
                    help="Index directory (also via "
                         "GALAH_TPU_INDEX_DIR); created by `build`, "
                         "required by every action")
    ix.add_argument("--trace-events",
                    help="Write a Chrome-trace-format event timeline "
                         "to this file. Env equivalent: "
                         "GALAH_OBS_TRACE_EVENTS")
    ix.add_argument("--run-report",
                    help="Write run_report.json (with its `index` "
                         "section) to this file at run end. Env "
                         "equivalent: GALAH_OBS_REPORT")
    ixsub = ix.add_subparsers(dest="index_action")
    ixb = ixsub.add_parser(
        "build",
        help="Dereplicate a corpus once and persist it as generation 1")
    _add_genome_inputs(ixb)
    _add_index_quality(ixb)
    ixb.add_argument("--ani", type=float, default=Defaults.ANI,
                     help="ANI clustering threshold the index is bound "
                          "to (default: 95)")
    ixb.add_argument("--precluster-ani", type=float,
                     default=Defaults.PRETHRESHOLD_ANI,
                     help="Sketch-ANI floor for persisted pairs "
                          "(default: 90)")
    ixb.add_argument("--hash-algorithm", default=Defaults.HASH_ALGO,
                     choices=HASH_ALGORITHMS,
                     help="Sketch hash the index is bound to "
                          "(default: murmur3)")
    ixb.add_argument("--sketch-cache",
                     help="Directory for the persistent sketch cache "
                          "(also via GALAH_TPU_CACHE); index records "
                          "share its content-hash keys")
    ixb.add_argument("--threads", "-t", type=int, default=1)
    ixi = ixsub.add_parser(
        "insert",
        help="Insert new genomes, sketching only them, and commit the "
             "next generation")
    _add_genome_inputs(ixi)
    _add_index_quality(ixi)
    ixi.add_argument("--sketch-cache",
                     help="Directory for the persistent sketch cache "
                          "(also via GALAH_TPU_CACHE)")
    ixi.add_argument("--threads", "-t", type=int, default=1)
    ixi.add_argument("--batch", type=int, default=None,
                     help="Genomes per durable append batch — the "
                          "preemption safe-boundary granularity "
                          "(default: GALAH_TPU_INDEX_BATCH)")
    ixi.add_argument("--resume", action="store_true",
                     help="Continue an interrupted insert: uncommitted "
                          "appends past the last committed generation "
                          "are truncated and the insert redone, "
                          "converging to the same bytes as an "
                          "uninterrupted run. (A matching index "
                          "auto-resumes anyway; --resume records the "
                          "chain in the run report)")
    ixq = ixsub.add_parser(
        "query",
        help="Answer which cluster each genome would join, without "
             "mutating the index")
    _add_genome_inputs(ixq)
    ixq.add_argument("--sketch-cache",
                     help="Directory for the persistent sketch cache "
                          "(also via GALAH_TPU_CACHE)")
    ixq.add_argument("--threads", "-t", type=int, default=1)
    ixq.add_argument("--output",
                     help="Output TSV of query, decision, "
                          "representative, ANI (default: stdout)")
    ixr = ixsub.add_parser(
        "remove",
        help="Tombstone genomes and locally re-elect their clusters")
    _add_genome_inputs(ixr)
    ixsub.add_parser(
        "fsck",
        help="Audit the on-disk index: commit-pointer integrity, log "
             "checksums, cluster invariants (never mutates; jax-free)")
    ft = sub.add_parser(
        "fleet",
        help="Run one dereplication job across preemptible worker "
             "subprocesses (shard, supervise, reassign, merge)",
        description="Elastic preemptible-fleet execution: `run` shards "
                    "the quality-ordered genome set, supervises one "
                    "`galah-tpu cluster` worker per shard (exit 75, "
                    "SIGKILL and stale heartbeats all mean preemption "
                    "-> reassign; retry budget per shard), then merges "
                    "shard checkpoints into clusters byte-identical "
                    "to a single-process run; `status` renders a "
                    "fleet directory's plan/event/heartbeat state "
                    "(jax-free). See docs/resilience.md")
    _add_verbosity(ft)
    ftsub = ft.add_subparsers(dest="fleet_action")
    ftr = ftsub.add_parser(
        "run",
        help="Shard, supervise and merge one dereplication job")
    _add_genome_inputs(ftr)
    add_cluster_arguments(ftr)
    ftr.add_argument("--fleet-dir", required=True,
                     help="Fleet working directory: shard plan, event "
                          "log, per-shard checkpoints/reports live "
                          "here (the resume root)")
    ftr.add_argument("--workers", type=int,
                     help="Max live worker subprocesses (default: "
                          "GALAH_TPU_FLEET_WORKERS)")
    ftr.add_argument("--shards", type=int,
                     help="Shard count (default: GALAH_TPU_FLEET_SHARDS "
                          "or the worker cap)")
    ftr.add_argument("--stale-s", type=float,
                     help="Heartbeat staleness deadline in seconds "
                          "(default: GALAH_TPU_FLEET_STALE_S)")
    ftr.add_argument("--resume", action="store_true",
                     help="Require resuming the fleet at --fleet-dir: "
                          "fail if the plan is missing or belongs to "
                          "a different configuration. Without this "
                          "flag a matching plan still auto-resumes")
    ftr.add_argument("--sketch-cache",
                     help="Shared sketch/profile cache for workers and "
                          "the merge (default: <fleet-dir>/cache; also "
                          "via GALAH_TPU_CACHE)")
    ftr.add_argument("--run-report",
                     help="Write the supervisor's run_report.json "
                          "(with its `fleet` section) to this file. "
                          "Env equivalent: GALAH_OBS_REPORT")
    ftr.add_argument("--output-cluster-definition",
                     help="Output file of rep<TAB>member lines")
    ftr.add_argument("--output-representative-fasta-directory",
                     help="Symlink representative genomes into this "
                          "directory")
    ftr.add_argument("--output-representative-fasta-directory-copy",
                     help="Copy representative genomes into this "
                          "directory")
    ftr.add_argument("--output-representative-list",
                     help="Output file with one representative path "
                          "per line")
    fts = ftsub.add_parser(
        "status",
        help="Render a fleet directory's shard/event/heartbeat state "
             "(jax-free; usable while a fleet is live)")
    fts.add_argument("fleet_dir", help="Fleet working directory")
    fta = ftsub.add_parser(
        "analyze",
        help="Cross-shard critical path of a fleet dir: blame table "
             "(scheduler/compute/straggler/merge) summing to the "
             "fleet wall, and the named bottleneck (jax-free)",
        description="Aggregate fleet_events.jsonl + per-shard run "
                    "reports/heartbeats into the fleet_rollup blame "
                    "table, write fleet_report.json beside the plan, "
                    "and name the bottleneck. Tolerates torn tails, "
                    "shards missing mid-write, and v6-v8 shard "
                    "reports. Exit codes: 0 rollup printed, 1 "
                    "rollup-impossible dir (no event log), 2 usage "
                    "error")
    fta.add_argument("fleet_dir", help="Fleet working directory")
    fta.add_argument("--json", action="store_true",
                     help="Emit the rollup as JSON instead of the "
                          "blame table")
    fta.add_argument("--no-report", action="store_true",
                     help="Skip writing fleet_report.json (print "
                          "only)")
    parser._subcommand_parsers = {"cluster": c, "cluster-validate": v,
                                  "dist": dd, "lint": li, "report": rp,
                                  "perf": pf, "flow": fl, "top": tp,
                                  "index": ix, "fleet": ft}
    return parser


def run_dist(args) -> int:
    """All-pairs sketch ANI -> TSV of genome_a, genome_b, ani lines."""
    import sys as _sys

    from galah_tpu.backends.minhash_backend import SketchStore
    from galah_tpu.genome_inputs import parse_genome_inputs
    from galah_tpu.io import diskcache
    from galah_tpu.ops.minhash import sketch_matrix
    from galah_tpu.ops.pairwise import threshold_pairs

    genomes = parse_genome_inputs(
        genome_fasta_files=args.genome_fasta_files,
        genome_fasta_list=args.genome_fasta_list,
        genome_fasta_directory=args.genome_fasta_directory,
        genome_fasta_extension=args.genome_fasta_extension,
    )
    cache = diskcache.get_cache(getattr(args, "sketch_cache", None))
    store = SketchStore(args.num_hashes, args.kmer_length, cache=cache,
                        algo=args.hash_algorithm)
    logger.info("Sketching %d genomes ..", len(genomes))
    # host threads prefetch FASTA ingestion while the device sketches
    # (same idiom as MinHashPreclusterer.distances)
    from galah_tpu.io.fasta import read_genome
    from galah_tpu.io.prefetch import probe_and_prefetch

    by_path, miss_iter = probe_and_prefetch(
        genomes, store.get_cached, read_genome,
        depth=max(2, getattr(args, "threads", 1)))
    for p, genome in miss_iter:
        by_path[p] = store.put_from_genome(p, genome)
    mat = sketch_matrix([by_path[p] for p in genomes],
                        sketch_size=args.num_hashes)
    min_ani = (parse_percentage(args.min_ani, "--min-ani")
               if args.min_ani else 0.0)
    logger.info("Computing tiled all-pairs ANI ..")
    pairs = threshold_pairs(mat, k=args.kmer_length, min_ani=min_ani,
                            sketch_size=args.num_hashes)
    out = open(args.output, "w") if args.output else _sys.stdout
    try:
        for (i, j) in sorted(pairs):
            out.write(f"{genomes[i]}\t{genomes[j]}\t"
                      f"{pairs[(i, j)]:.6f}\n")
    finally:
        if args.output:
            out.close()
    logger.info("Wrote %d pairs", len(pairs))
    return 0


def run_cluster(args) -> int:
    import time as _time

    from galah_tpu import obs
    from galah_tpu.config import env_value
    from galah_tpu.resilience import interrupt

    # Telemetry lifecycle brackets the whole run: reset shared state,
    # open the trace sink if requested, and always finalize (write the
    # run report, close the trace) even when the run fails — a report
    # of a failed run is exactly when the stage tree matters most.
    # wall-clock stamp for the report header, not a duration measure
    started_at = _time.time()  # galah-lint: ignore[GL701]
    timing.reset()
    obs.reset_run()
    # Cooperative preemption: SIGTERM/SIGINT request a stop at the next
    # safe boundary (engine round edges / checkpoint flushes); the
    # finalize below then drains the report/ledger/trace writers before
    # the process exits with EXIT_PREEMPTED.
    interrupt.reset()
    interrupt.install()
    trace_path = (getattr(args, "trace_events", None)
                  or env_value("GALAH_OBS_TRACE_EVENTS"))
    if trace_path:
        obs.trace.start(trace_path)
    report_path = (getattr(args, "run_report", None)
                   or env_value("GALAH_OBS_REPORT"))
    # Liveness heartbeat beside the report sink, plus crash/preemption
    # flush hooks so an aborted run still leaves a final beat and a
    # closed trace behind.
    obs.install_crash_hooks()
    obs.heartbeat.maybe_start(report_path)
    try:
        return _run_cluster_inner(args)
    finally:
        interrupt.uninstall()
        obs.finalize("cluster", report_path=report_path,
                     started_at=started_at)


def _run_cluster_inner(args) -> int:
    from galah_tpu.genome_inputs import parse_genome_inputs
    from galah_tpu.io import diskcache
    from galah_tpu.outputs import setup_outputs, write_outputs
    from galah_tpu.parallel import distributed

    # Join the multi-host runtime when the standard JAX cluster env
    # vars are set (docs/DISTRIBUTED.md); a no-op otherwise. Every
    # host computes identical clusters; only process 0 writes outputs.
    distributed.initialize()

    from galah_tpu.resilience.quarantine import QuarantineManifest

    on_bad_genome = getattr(args, "on_bad_genome", "error") or "error"
    qmanifest = QuarantineManifest()
    genomes = parse_genome_inputs(
        genome_fasta_files=args.genome_fasta_files,
        genome_fasta_list=args.genome_fasta_list,
        genome_fasta_directory=args.genome_fasta_directory,
        genome_fasta_extension=args.genome_fasta_extension,
        on_bad_genome=on_bad_genome,
        manifest=qmanifest,
    )

    cache = diskcache.get_cache(getattr(args, "sketch_cache", None))
    if cache.enabled:
        logger.info("Using persistent sketch cache at %s", cache.path)

    # Quality filtering/ordering + backend construction live in the
    # embeddable factory (api.py, reference analog:
    # generate_galah_clusterer, src/cluster_argument_parsing.rs:897-1158)
    try:
        clusterer = generate_galah_clusterer(
            genomes, vars(args), cache=cache,
            quarantine_manifest=qmanifest)
    except ValueError as e:
        # User error (conflicting quality inputs, dRep + --genome-info):
        # a logged message and exit 1, not a traceback — the reference's
        # factory bails the same way.
        logger.error("%s", e)
        return 1
    genomes = clusterer.genome_paths

    # Open output handles before compute (fail fast). On multi-host
    # runs only process 0 writes — every host computes the identical
    # clusters, and N processes writing the same files would race.
    # Non-writers still VALIDATE the paths (without touching them) so
    # a bad output path fails every process before the first
    # collective instead of stalling the others in it.
    is_writer = distributed.process_index() == 0
    output_args = dict(
        cluster_definition=args.output_cluster_definition,
        representative_fasta_directory=(
            args.output_representative_fasta_directory),
        representative_fasta_directory_copy=(
            args.output_representative_fasta_directory_copy),
        representative_list=args.output_representative_list,
    )
    if is_writer:
        handles = setup_outputs(**output_args)
    else:
        from galah_tpu.outputs import validate_output_paths

        validate_output_paths(**output_args)
        handles = None

    ckpt = None
    if getattr(args, "resume", False) \
            and not getattr(args, "checkpoint_dir", None):
        logger.error("--resume requires --checkpoint-dir")
        return 1
    if getattr(args, "checkpoint_dir", None):
        from galah_tpu.cluster.checkpoint import (
            ClusterCheckpoint,
            fields_digest,
            fingerprint_fields,
        )
        from galah_tpu.resilience import interrupt

        # Multi-host: each process persists under its own subdirectory
        # — N processes appending to one shared checkpoint would
        # interleave/corrupt it, and gating persistence to one process
        # would desynchronize the collective-participating distance
        # pass on resume (the loader skips it, the others don't).
        ckpt_dir = args.checkpoint_dir
        if distributed.process_count() > 1:
            import os as _os

            ckpt_dir = _os.path.join(
                ckpt_dir, f"proc_{distributed.process_index()}")
        fields = fingerprint_fields(
            genomes, args.precluster_method, args.cluster_method,
            parse_percentage(args.ani, "--ani"),
            parse_percentage(args.precluster_ani, "--precluster-ani"),
            min_aligned_fraction=parse_percentage(
                args.min_aligned_fraction, "--min-aligned-fraction"),
            fragment_length=args.fragment_length,
            backend_params=clusterer.backend_params)
        ckpt = ClusterCheckpoint(
            ckpt_dir, fields_digest(fields), fields=fields,
            require_match=getattr(args, "resume", False))
        # Resume chain for the run report: a matching checkpoint with
        # recorded interruptions means this run continues a preempted
        # one (whether or not --resume was passed).
        prior = ckpt.load_interruptions()
        if ckpt.matched_existing and (prior
                                      or getattr(args, "resume",
                                                 False)):
            from galah_tpu.obs import events

            interrupt.note_resume(ckpt_dir, len(prior))
            events.record("resumed", checkpoint_dir=ckpt_dir,
                          prior_interruptions=len(prior))
        # All-or-nothing resume across hosts: a crash can land between
        # two hosts' checkpoint saves, and resuming from uneven state
        # would deadlock the collective-participating distance pass
        # (the host with a checkpoint skips it, the others enter it).
        # If the per-process states differ, every host drops its
        # resumable state and recomputes symmetrically.
        if distributed.process_count() > 1 and not \
                distributed.tokens_agree(ckpt.state_token()):
            logger.warning(
                "Checkpoint state differs across hosts; dropping it "
                "and recomputing so all hosts stay in lockstep")
            ckpt.reset_state()
        clusterer.checkpoint = ckpt

    from galah_tpu.resilience import interrupt

    logger.info("Clustering %d genomes ..", len(genomes))
    try:
        with timing.trace_context(
                getattr(args, "profile_trace_dir", None)):
            clusters = clusterer.cluster()
    except interrupt.PreemptionRequested as e:
        # Cooperative preemption: everything before the boundary is
        # already durable, so record the interruption, emit the event,
        # and exit EXIT_PREEMPTED — obs.finalize (run_cluster) drains
        # the report/trace/ledger writers on the way out.
        import time as _time

        from galah_tpu.obs import events

        events.record("preempted", signal=e.signame,
                      boundary=e.boundary)
        if ckpt is not None:
            ckpt.record_interruption({
                "signal": e.signame,
                "boundary": e.boundary,
                # wall-clock stamp for the chain record, not a duration
                "ts": _time.time(),  # galah-lint: ignore[GL701]
            })
        logger.warning(
            "Preempted (%s): stopped at safe boundary %r. The "
            "checkpoint%s is consistent; rerun with --resume to "
            "continue. Exiting %d.", e.signame, e.boundary,
            f" at {ckpt.path}" if ckpt is not None else "",
            interrupt.EXIT_PREEMPTED)
        return interrupt.EXIT_PREEMPTED
    logger.info("Found %d genome clusters", len(clusters))

    if is_writer:
        with timing.stage("write-outputs"):
            write_outputs(handles, clusters, genomes)
        logger.info("Finished printing genome clusters")
    else:
        logger.info("Non-zero process: outputs written by process 0")

    # Quarantined inputs (--on-bad-genome skip) land in a manifest next
    # to the outputs. Every host computed the identical quarantine set
    # (resilience/quarantine.py's OR-exchange); only the writer writes.
    if clusterer.quarantine is not None and len(clusterer.quarantine):
        from galah_tpu.resilience.quarantine import manifest_output_dir

        if is_writer:
            clusterer.quarantine.write(manifest_output_dir(
                cluster_definition=args.output_cluster_definition,
                representative_list=args.output_representative_list,
                checkpoint_dir=getattr(args, "checkpoint_dir", None)))

    # Any mid-run demotions (device dispatch -> CPU fallback) belong in
    # the run summary: the run completed, but not on the fast path.
    from galah_tpu.resilience import dispatch as rdispatch

    for dem in rdispatch.demotions():
        logger.warning("Dispatch site %s ran DEMOTED to its fallback "
                       "after persistent failures (%s)",
                       dem.site, dem.reason)
    timing.GLOBAL.report(logger)
    return 0


def run_fleet(args) -> int:
    """`galah-tpu fleet run`: same telemetry lifecycle as run_cluster
    (the supervisor writes its own run report, with a `fleet`
    section)."""
    import time as _time

    from galah_tpu import obs
    from galah_tpu.config import env_value
    from galah_tpu.resilience import interrupt

    # wall-clock stamp for the report header, not a duration measure
    started_at = _time.time()  # galah-lint: ignore[GL701]
    timing.reset()
    obs.reset_run()
    interrupt.reset()
    interrupt.install()
    trace_path = (getattr(args, "trace_events", None)
                  or env_value("GALAH_OBS_TRACE_EVENTS"))
    if trace_path:
        obs.trace.start(trace_path)
    report_path = (getattr(args, "run_report", None)
                   or env_value("GALAH_OBS_REPORT"))
    obs.install_crash_hooks()
    obs.heartbeat.maybe_start(report_path, role="scheduler")
    # Every heartbeat tick's OpenMetrics page carries the live
    # cross-shard rollup when the exporter flag is set (best-effort:
    # a not-yet-rollable dir just omits the fleet series).
    from galah_tpu.obs import fleet_view
    from galah_tpu.obs import openmetrics as obs_openmetrics

    fleet_dir = args.fleet_dir
    obs_openmetrics.set_rollup_provider(
        lambda: fleet_view.rollup(fleet_dir))
    try:
        return _run_fleet_inner(args)
    finally:
        interrupt.uninstall()
        obs.finalize("fleet", report_path=report_path,
                     started_at=started_at)


def _fleet_worker_argv(args, fleet_dir: str, cache_path: str):
    """Worker command-line builder: one `galah-tpu cluster` run per
    shard, genomes passed explicitly in (already quality-ordered)
    shard order so the worker never re-orders them."""
    from galah_tpu.fleet import scheduler as fleet_scheduler

    def worker_argv(spec, resume: bool):
        sid = spec.shard_id
        argv = [sys.executable, "-m", "galah_tpu.cli", "cluster",
                "--genome-fasta-files", *spec.genomes,
                "--ani", str(args.ani),
                "--precluster-ani", str(args.precluster_ani),
                "--min-aligned-fraction",
                str(args.min_aligned_fraction),
                "--fragment-length", str(args.fragment_length),
                "--precluster-method", args.precluster_method,
                "--cluster-method", args.cluster_method,
                "--ani-subsample", str(args.ani_subsample),
                "--hash-algorithm", args.hash_algorithm,
                "--threads", str(getattr(args, "threads", 1) or 1),
                "--checkpoint-dir",
                fleet_scheduler.shard_ckpt_dir(fleet_dir, sid),
                "--run-report",
                fleet_scheduler.shard_report_path(fleet_dir, sid),
                "--output-cluster-definition",
                fleet_scheduler.shard_tsv_path(fleet_dir, sid)]
        if cache_path:
            argv += ["--sketch-cache", cache_path]
        if getattr(args, "platform", None):
            # workers run on the scheduler's platform
            argv += ["--platform", args.platform]
        if resume:
            argv.append("--resume")
        return argv

    return worker_argv


def _run_fleet_inner(args) -> int:
    import time as _time

    from galah_tpu import fleet as fleet_pkg
    from galah_tpu.cluster.checkpoint import fingerprint_fields
    from galah_tpu.config import env_value
    from galah_tpu.fleet import merge as fleet_merge
    from galah_tpu.fleet import plan as fleet_plan
    from galah_tpu.fleet.scheduler import FleetScheduler, append_stamp
    from galah_tpu.genome_inputs import parse_genome_inputs
    from galah_tpu.io import atomic, diskcache
    from galah_tpu.obs import events
    from galah_tpu.outputs import setup_outputs, write_outputs
    from galah_tpu.resilience import interrupt
    from galah_tpu.resilience.quarantine import QuarantineManifest

    # v1 gate: the merge-determinism argument needs shard checkpoints
    # thresholded at the FINAL ANI, which is exactly the skani/skani
    # configuration (api.py pins precluster_ani = ani there). Other
    # method combinations shard correctly but merge approximately —
    # refuse rather than silently weaken the byte-identical contract.
    if (args.precluster_method != "skani"
            or args.cluster_method != "skani"):
        logger.error(
            "fleet run requires --precluster-method skani and "
            "--cluster-method skani (got %s/%s): the cross-shard "
            "merge is only byte-identical when shard checkpoints are "
            "thresholded at the final ANI", args.precluster_method,
            args.cluster_method)
        return 1

    fleet_dir = args.fleet_dir
    on_bad_genome = getattr(args, "on_bad_genome", "error") or "error"
    qmanifest = QuarantineManifest()
    genomes = parse_genome_inputs(
        genome_fasta_files=args.genome_fasta_files,
        genome_fasta_list=args.genome_fasta_list,
        genome_fasta_directory=args.genome_fasta_directory,
        genome_fasta_extension=args.genome_fasta_extension,
        on_bad_genome=on_bad_genome,
        manifest=qmanifest,
    )

    # One shared profile cache across workers and the merge: shard
    # profiling warms it, the merge's cross-shard pass reuses it.
    cache_path = (getattr(args, "sketch_cache", None)
                  or diskcache.default_cache_dir()
                  or os.path.join(fleet_dir, "cache"))
    cache = diskcache.get_cache(cache_path)
    logger.info("Using shared fleet sketch cache at %s", cache.path)

    try:
        clusterer = generate_galah_clusterer(
            genomes, vars(args), cache=cache,
            quarantine_manifest=qmanifest)
    except ValueError as e:
        logger.error("%s", e)
        return 1
    genomes = clusterer.genome_paths
    ani = parse_percentage(args.ani, "--ani")

    # only None means unset: `--workers 0` / `--shards 0` must be
    # rejected below, not silently coerced to the env/default value
    workers = (args.workers if args.workers is not None
               else int(env_value("GALAH_TPU_FLEET_WORKERS") or 2))
    n_shards = (args.shards if args.shards is not None
                else int(env_value("GALAH_TPU_FLEET_SHARDS") or 0)
                or workers)
    if workers < 1:
        logger.error("--workers must be >= 1, got %d", workers)
        return 1
    stale_s = (args.stale_s if args.stale_s is not None
               else float(env_value("GALAH_TPU_FLEET_STALE_S") or 30))
    poll_s = float(env_value("GALAH_TPU_FLEET_POLL_S") or 0.2)
    heartbeat_s = float(
        env_value("GALAH_TPU_FLEET_HEARTBEAT_S") or 1)

    fields = fingerprint_fields(
        genomes, args.precluster_method, args.cluster_method, ani,
        parse_percentage(args.precluster_ani, "--precluster-ani"),
        min_aligned_fraction=parse_percentage(
            args.min_aligned_fraction, "--min-aligned-fraction"),
        fragment_length=args.fragment_length,
        backend_params=clusterer.backend_params)
    try:
        shards = fleet_plan.ensure_plan(
            fleet_dir, genomes, fields, n_shards,
            require_match=getattr(args, "resume", False))
    except ValueError as e:
        logger.error("%s", e)
        return 1
    logger.info("Fleet plan: %d genomes in %d shard(s), %d worker(s)",
                len(genomes), len(shards), workers)

    # Open output handles before compute (fail fast), like cluster.
    handles = setup_outputs(
        cluster_definition=args.output_cluster_definition,
        representative_fasta_directory=(
            args.output_representative_fasta_directory),
        representative_fasta_directory_copy=(
            args.output_representative_fasta_directory_copy),
        representative_list=args.output_representative_list,
    )

    # Resume chain: prior fleet-interrupted events mean this run
    # continues a preempted supervisor.
    prior_records, _torn = atomic.read_jsonl(
        fleet_plan.events_path(fleet_dir))
    prior = [r for r in prior_records if isinstance(r, dict)
             and r.get("ev") == "fleet-interrupted"]
    if prior or getattr(args, "resume", False):
        if prior_records:
            interrupt.note_resume(fleet_dir, len(prior))
            events.record("resumed", fleet_dir=fleet_dir,
                          prior_interruptions=len(prior))

    sched = FleetScheduler(
        fleet_dir, shards,
        _fleet_worker_argv(args, fleet_dir, cache.path or cache_path),
        workers=workers, stale_s=stale_s, poll_s=poll_s,
        heartbeat_s=heartbeat_s)
    try:
        with timing.stage("fleet-supervise"):
            snap = sched.run()
    except interrupt.PreemptionRequested as e:
        events.record("preempted", signal=e.signame,
                      boundary=e.boundary)
        fleet_pkg.set_snapshot(sched.snapshot())
        logger.warning(
            "Fleet preempted (%s) at %r: worker checkpoints are "
            "consistent; rerun with --resume to continue. Exiting %d.",
            e.signame, e.boundary, interrupt.EXIT_PREEMPTED)
        return interrupt.EXIT_PREEMPTED

    if snap["shards_failed"]:
        fleet_pkg.set_snapshot(snap)
        logger.error(
            "%d shard(s) exhausted their retry budget (see "
            "fleet-shard-failed events at %s); not merging a partial "
            "fleet", snap["shards_failed"],
            fleet_plan.events_path(fleet_dir))
        return 1

    merge_t0 = _time.monotonic()
    with timing.stage("fleet-merge"):
        clusters = fleet_merge.merge(fleet_dir, genomes, shards,
                                     clusterer.preclusterer, ani)
    snap["merge_wall_s"] = round(_time.monotonic() - merge_t0, 6)
    snap["n_genomes"] = len(genomes)
    # rollup-ready stamp: fleet_view charges this window to merge
    # blame; appended to the event log (not only the report) so
    # `fleet analyze` works on dirs whose report never landed
    append_stamp(fleet_dir, "fleet-merge-done",
                 wall_s=snap["merge_wall_s"])
    fleet_pkg.set_snapshot(snap)
    logger.info("Found %d genome clusters", len(clusters))

    with timing.stage("write-outputs"):
        write_outputs(handles, clusters, genomes)
    logger.info("Finished printing genome clusters")

    if clusterer.quarantine is not None and len(clusterer.quarantine):
        from galah_tpu.resilience.quarantine import manifest_output_dir

        clusterer.quarantine.write(manifest_output_dir(
            cluster_definition=args.output_cluster_definition,
            representative_list=args.output_representative_list,
            checkpoint_dir=fleet_dir))
    timing.GLOBAL.report(logger)
    return 0


def run_fleet_status(args) -> int:
    """`galah-tpu fleet status`: jax-free rendering of a fleet dir."""
    from galah_tpu.fleet.scheduler import render_status

    sys.stdout.write(render_status(args.fleet_dir))
    return 0


def run_fleet_analyze(args) -> int:
    """`galah-tpu fleet analyze`: cross-shard critical path of a fleet
    dir — blame table summing to the fleet wall, fleet_report.json
    beside the plan, and the named bottleneck. Pure file I/O (jax-free,
    runs against live and half-written fleet dirs alike)."""
    import json as _json
    import time as _time

    from galah_tpu.obs import fleet_view

    # wall-clock stamp for the report header, not a duration measure
    started_at = _time.time()  # galah-lint: ignore[GL701]
    ru = fleet_view.rollup(args.fleet_dir)
    if ru is None:
        logger.error(
            "%s: rollup-impossible — no fleet event log (run "
            "`galah-tpu fleet run --fleet-dir %s` first)",
            args.fleet_dir, args.fleet_dir)
        return 1
    if not getattr(args, "no_report", False):
        try:
            path = fleet_view.write_fleet_report(
                args.fleet_dir, ru, argv=sys.argv,
                started_at=started_at)
            logger.info("Wrote %s", path)
        except Exception:  # rendering still succeeds without the file
            logger.warning("fleet_report.json write failed",
                           exc_info=True)
    if getattr(args, "json", False):
        print(_json.dumps(ru, indent=1, sort_keys=True))
        return 0
    for line in fleet_view.render_rollup(ru):
        print(line)
    return 0


def run_cluster_validate(args) -> int:
    from galah_tpu.backends import FastANIEquivalentClusterer, ProfileStore
    from galah_tpu.validate import validate_clusters

    if not args.cluster_file:
        logger.error("--cluster-file is required")
        return 1
    ani = parse_percentage(args.ani, "--ani")
    min_af = parse_percentage(args.min_aligned_fraction,
                              "--min-aligned-fraction")
    raw = getattr(args, "ani_subsample", None)
    subsample = int(raw if raw is not None else 1)
    if not 1 <= subsample <= 1000:
        logger.error("--ani-subsample must be in [1, 1000], got %s",
                     subsample)
        return 1
    clusterer = FastANIEquivalentClusterer(
        threshold=ani, min_aligned_fraction=min_af,
        fraglen=args.fragment_length,
        store=ProfileStore(fraglen=args.fragment_length,
                           subsample_c=subsample,
                           hash_algorithm=getattr(
                               args, "hash_algorithm",
                               Defaults.HASH_ALGO)))
    validate_clusters(args.cluster_file, clusterer)
    return 0


def run_report_cmd(args) -> int:
    """Render run_report.json files, or diff two of them."""
    from galah_tpu.obs import report as report_mod

    loaded = []
    for path in args.paths:
        try:
            rep = report_mod.load(path)
        except Exception as e:  # noqa: BLE001 — bad JSON, missing file
            logger.error("%s: cannot read run report (%s)", path, e)
            return 1
        problems = report_mod.validate(rep)
        if problems:
            logger.error("%s: not a valid run report: %s", path,
                         problems[0])
            return 1
        loaded.append((path, rep))
    if args.diff:
        if len(loaded) != 2:
            logger.error("report --diff takes exactly two reports, "
                         "got %d", len(loaded))
            return 1
        (pa, ra), (pb, rb) = loaded
        sys.stdout.write(report_mod.diff(ra, rb, label_a=pa, label_b=pb))
        return 0
    for i, (path, rep) in enumerate(loaded):
        if i:
            sys.stdout.write("\n")
        sys.stdout.write(report_mod.render(rep))
    return 0


def run_perf_cmd(args) -> int:
    """`galah-tpu perf record|history|check` over the JSONL ledger.
    Pure file I/O (like `report`): never touches jax."""
    from galah_tpu.config import env_value
    from galah_tpu.obs import ledger as ledger_mod
    from galah_tpu.obs import report as report_mod

    ledger_path = args.ledger or env_value("GALAH_OBS_LEDGER")
    if not ledger_path:
        logger.error("no ledger: pass --ledger or set "
                     "GALAH_OBS_LEDGER")
        return 1
    action = getattr(args, "perf_action", None)
    if action is None:
        logger.error("perf needs an action: record, history, or check")
        return 1

    if action == "record":
        try:
            rep = report_mod.load(args.report)
        except Exception as e:  # noqa: BLE001 — bad JSON, missing file
            logger.error("%s: cannot read run report (%s)",
                         args.report, e)
            return 1
        entry = ledger_mod.entry_from_report(rep, args.source)
        ledger_mod.append(ledger_path, entry)
        print(f"recorded {len(entry['metrics'])} metric(s) to "
              f"{ledger_path}")
        return 0

    entries, skipped = ledger_mod.read(ledger_path)
    if skipped:
        logger.warning("%s: skipped %d torn/corrupt line(s)",
                       ledger_path, skipped)

    if action == "history":
        rows = ledger_mod.history(entries, args.metric)
        if args.key:
            rows = [r for r in rows if args.key in r["key"]]
        if not rows:
            print(f"no entries carry metric {args.metric!r}")
            return 0
        for r in rows:
            ts = time.strftime("%Y-%m-%d %H:%M",
                               time.localtime(r["ts"] or 0))
            print(f"{ts}  {r['sha'] or '-':>9}  {r['value']:<14.6g} "
                  f"{r['key']}")
        return 0

    # check
    if getattr(args, "report", None):
        try:
            rep = report_mod.load(args.report)
        except Exception as e:  # noqa: BLE001
            logger.error("%s: cannot read run report (%s)",
                         args.report, e)
            return 1
        current = ledger_mod.entry_from_report(rep, args.source)
        history = entries
    else:
        if not entries:
            print("ledger is empty; nothing to check")
            return 0
        current, history = entries[-1], entries[:-1]
    window = (args.window if args.window is not None
              else int(env_value("GALAH_OBS_LEDGER_WINDOW")))
    mad_k = (args.mad_k if args.mad_k is not None
             else float(env_value("GALAH_OBS_LEDGER_MAD_K")))
    min_history = (args.min_history if args.min_history is not None
                   else ledger_mod.MIN_HISTORY)
    verdicts = ledger_mod.check(history, current, window=window,
                                mad_k=mad_k, min_history=min_history)
    bad = ledger_mod.regressions(verdicts)
    counts: dict = {}
    for v in verdicts:
        counts[v["verdict"]] = counts.get(v["verdict"], 0) + 1
    for v in verdicts:
        if v["verdict"] in ("ok", "insufficient-history"):
            continue
        band = v.get("band")
        band_s = (f" band=[{band[0]:.6g}, {band[1]:.6g}] "
                  f"(median {v['median']:.6g}, n={v['n_history']})"
                  if band else "")
        print(f"{v['verdict'].upper()}: {v['metric']} = "
              f"{v['value']:.6g}{band_s}")
    summary = ", ".join(f"{k}={counts[k]}" for k in sorted(counts)) \
        or "no comparable metrics"
    print(f"perf check [{ledger_mod.key_of(current)}]: {summary}")
    if bad and args.soft:
        print(f"--soft: {len(bad)} regression(s) reported, not gated")
        return 0
    return 1 if bad else 0


def run_flow_cmd(args) -> int:
    """`galah-tpu flow analyze`: critical-path attribution from a run
    report's flow section. Pure file I/O (like `report`): never
    touches jax."""
    import json as _json

    from galah_tpu.obs import flow as flow_mod
    from galah_tpu.obs import report as report_mod

    action = getattr(args, "flow_action", None)
    if action is None:
        logger.error("flow needs an action: analyze")
        return 1
    try:
        rep = report_mod.load(args.report)
    except Exception as e:  # noqa: BLE001 — bad JSON, missing file
        logger.error("%s: cannot read run report (%s)", args.report, e)
        return 1
    snap = rep.get("flow") or {}
    if not snap.get("stages"):
        logger.error("%s: no flow telemetry (run a pipelined "
                     "subcommand with GALAH_OBS_FLOW=1)", args.report)
        return 1
    wall = rep.get("run", {}).get("duration_s") or 0.0
    cp = flow_mod.critical_path(snap, float(wall))
    if getattr(args, "json", False):
        print(_json.dumps(cp, indent=1, sort_keys=True))
        return 0
    for line in flow_mod.render_critical_path(cp):
        print(line)
    return 0


def run_top_cmd(args) -> int:
    """`galah-tpu top <dir>`: render the newest heartbeat of a live
    (or finished) run — or, for a fleet dir (auto-detected from the
    plan/event log), the per-shard fleet grid. Pure file I/O: never
    touches jax, tolerates a torn tail line from a run killed
    mid-append. Exit codes: 0 rendered, 1 no data."""
    import json as _json

    from galah_tpu.obs import fleet_view
    from galah_tpu.obs import heartbeat as heartbeat_mod

    follow = bool(getattr(args, "follow", False))
    as_json = bool(getattr(args, "json", False))
    interval = max(float(getattr(args, "interval", 2.0) or 2.0), 0.1)
    fleet_mode = (os.path.isdir(args.directory)
                  and fleet_view.is_fleet_dir(args.directory))
    while True:
        if fleet_mode:
            grid = fleet_view.fleet_grid(args.directory)
            ok = bool(grid and (grid["shards"] or grid["events"]))
            if as_json:
                sys.stdout.write(_json.dumps(
                    grid or {}, indent=1, sort_keys=True) + "\n")
            else:
                sys.stdout.write(fleet_view.render_fleet_grid(
                    grid or {"fleet_dir": args.directory}))
        else:
            records, _torn = heartbeat_mod.load(args.directory)
            ok = bool(records)
            if as_json:
                latest = records[-1] if records else None
                sys.stdout.write(_json.dumps(
                    latest, indent=1, sort_keys=True) + "\n")
            else:
                sys.stdout.write(
                    heartbeat_mod.render_latest(args.directory))
        sys.stdout.flush()
        if not follow:
            return 0 if ok else 1
        try:
            time.sleep(interval)
        except KeyboardInterrupt:
            return 0


def _index_order_genomes(genomes, args):
    """Quality-order genomes for index build/insert; with no quality
    input, fall back to input order LOUDLY: a distinct warn_once key,
    a resilience event, and a counter (the run report shows the index
    was grown unranked — representative choice is input-order luck)."""
    from galah_tpu.api import quality_order_genomes

    ordered, used_quality = quality_order_genomes(
        genomes, vars(args),
        threads=int(getattr(args, "threads", 1) or 1),
        missing_key="index-quality-fallback",
        missing_msg="Since CheckM input is missing, genomes enter the "
                    "index in input order, not quality order — "
                    "representative selection is unranked. Pass "
                    "--checkm-tab-table / --checkm2-quality-report / "
                    "--genome-info to rank them")
    if not used_quality:
        from galah_tpu.obs import events
        from galah_tpu.obs import metrics as obs_metrics

        events.record("index-quality-fallback", n_genomes=len(ordered))
        obs_metrics.counter(
            "index.quality_fallback",
            help="Index build/insert batches ordered by input order "
                 "because no quality input was given",
            unit="batches").inc()
    return ordered


def _run_index_fsck(index_dir: str) -> int:
    # Pure file I/O + checksum math: usable on hosts with no
    # accelerator, so it must stay out of the jax-touching path below.
    from galah_tpu.index import store as index_store

    rep = index_store.fsck(index_dir)
    print(f"index {rep['path']}: generation {rep['generation']}, "
          f"{rep['genomes']} genome(s), {rep['clusters']} cluster(s), "
          f"{rep['pairs']} pair(s), {rep['tombstones']} tombstone(s)")
    for w in rep["warnings"]:
        print(f"  warning: {w}")
    for p in rep["problems"]:
        print(f"  PROBLEM: {p}")
    print("fsck: OK" if rep["ok"] else "fsck: FAILED")
    return 0 if rep["ok"] else 1


def run_index(args) -> int:
    import time as _time

    from galah_tpu import obs
    from galah_tpu.config import env_value
    from galah_tpu.resilience import interrupt

    action = getattr(args, "index_action", None)
    if action is None:
        logger.error("index needs an action: build, insert, query, "
                     "remove, or fsck")
        return 1
    index_dir = (getattr(args, "index_dir", None)
                 or env_value("GALAH_TPU_INDEX_DIR"))
    if not index_dir:
        logger.error("no index directory: pass --index-dir or set "
                     "GALAH_TPU_INDEX_DIR")
        return 1
    if action == "fsck":
        return _run_index_fsck(index_dir)
    # Same telemetry lifecycle as run_cluster: reset shared state, arm
    # cooperative preemption, always finalize the report/trace.
    # wall-clock stamp for the report header, not a duration measure
    started_at = _time.time()  # galah-lint: ignore[GL701]
    timing.reset()
    obs.reset_run()
    interrupt.reset()
    interrupt.install()
    trace_path = (getattr(args, "trace_events", None)
                  or env_value("GALAH_OBS_TRACE_EVENTS"))
    if trace_path:
        obs.trace.start(trace_path)
    report_path = (getattr(args, "run_report", None)
                   or env_value("GALAH_OBS_REPORT"))
    # Same heartbeat + crash-flush wiring as run_cluster.
    obs.install_crash_hooks()
    obs.heartbeat.maybe_start(report_path)
    try:
        return _run_index_inner(args, action, index_dir)
    finally:
        interrupt.uninstall()
        obs.finalize("index", report_path=report_path,
                     started_at=started_at)


def _run_index_inner(args, action: str, index_dir: str) -> int:
    import sys as _sys
    import time as _time

    from galah_tpu.genome_inputs import parse_genome_inputs
    from galah_tpu.index import incremental
    from galah_tpu.index.store import IndexStore
    from galah_tpu.resilience import interrupt

    genomes = parse_genome_inputs(
        genome_fasta_files=args.genome_fasta_files,
        genome_fasta_list=getattr(args, "genome_fasta_list", None),
        genome_fasta_directory=getattr(args, "genome_fasta_directory",
                                       None),
        genome_fasta_extension=getattr(args, "genome_fasta_extension",
                                       "fna"),
    )

    if action == "build":
        ordered = _index_order_genomes(genomes, args)
        info = incremental.build(
            index_dir, ordered,
            ani=parse_percentage(args.ani, "--ani"),
            precluster_ani=parse_percentage(args.precluster_ani,
                                            "--precluster-ani"),
            algo=args.hash_algorithm,
            cache_dir=getattr(args, "sketch_cache", None),
            threads=args.threads)
        logger.info("Built index at %s: generation %d, %d genomes in "
                    "%d clusters", index_dir, info["generation"],
                    info["genomes"], info["clusters"])
        return 0

    idx = IndexStore(index_dir)
    if action == "insert":
        ordered = _index_order_genomes(genomes, args)
        prior = idx.load_interruptions()
        if prior or getattr(args, "resume", False):
            from galah_tpu.obs import events

            interrupt.note_resume(index_dir, len(prior))
            events.record("resumed", index_dir=index_dir,
                          prior_interruptions=len(prior))
        try:
            info = incremental.insert(
                idx, ordered,
                cache_dir=getattr(args, "sketch_cache", None),
                threads=args.threads,
                batch=getattr(args, "batch", None))
        except interrupt.PreemptionRequested as e:
            from galah_tpu.obs import events

            events.record("preempted", signal=e.signame,
                          boundary=e.boundary)
            idx.record_interruption({
                "signal": e.signame,
                "boundary": e.boundary,
                # wall-clock stamp for the chain record, not a duration
                "ts": _time.time(),  # galah-lint: ignore[GL701]
            })
            logger.warning(
                "Preempted (%s): stopped at safe boundary %r. The "
                "index at %s is loadable at its last committed "
                "generation; rerun the same insert (--resume) to "
                "converge to the uninterrupted result. Exiting %d.",
                e.signame, e.boundary, index_dir,
                interrupt.EXIT_PREEMPTED)
            return interrupt.EXIT_PREEMPTED
        logger.info("Inserted %d genome(s) (%d skipped as already "
                    "present): generation %d, %d genomes in %d "
                    "clusters, %d new representative(s)",
                    info["inserted"], info["skipped"],
                    info["generation"], info["genomes"],
                    info["clusters"], info.get("new_reps", 0))
        return 0

    if action == "query":
        results = incremental.query(
            idx, genomes,
            cache_dir=getattr(args, "sketch_cache", None),
            threads=args.threads)
        out = open(args.output, "w") if args.output else _sys.stdout
        try:
            out.write("query\tdecision\trepresentative\tani\n")
            for r in results:
                ani = (f"{r['ani'] * 100:.4f}"
                       if r["ani"] is not None else "NA")
                out.write(f"{r['path']}\t{r['decision']}\t"
                          f"{r['rep'] or 'NA'}\t{ani}\n")
        finally:
            if args.output:
                out.close()
        return 0

    # remove
    for p in genomes:
        info = incremental.remove(idx, p)
        logger.info("Removed %s: generation %d, %d genomes in %d "
                    "clusters remain", p, info["generation"],
                    info["genomes"], info["clusters"])
    return 0


def _host_has_tpu() -> bool:
    """Whether a TPU sits on this host's PCI bus (Google's vendor id),
    found without starting a backend."""
    import glob

    for path in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(path) as fh:
                if fh.read().strip() == "0x1ae0":
                    return True
        except OSError:
            continue
    return False


def _start_backend(args) -> int:
    """Pin the platform, place the compile cache, and bring up the
    backend — or, for `fleet run`, check that its workers will not share
    an accelerator and leave the backend to them. Returns an exit code
    on failure, else 0. Must run before the first jax USE (backend
    init), which only happens inside the subcommands."""
    platform = (getattr(args, "platform", None)
                or os.environ.get("GALAH_TPU_PLATFORM"))
    if args.subcommand == "fleet":
        # The scheduler starts no backend before its workers: a chip
        # belongs to one process at a time, and the workers need it.
        platform = platform or os.environ.get("JAX_PLATFORMS")
        workers = args.workers
        if workers is None:
            from galah_tpu.config import env_value

            workers = int(env_value("GALAH_TPU_FLEET_WORKERS") or 2)
        on_chip = platform not in (None, "cpu") or (
            platform is None and _host_has_tpu())
        if workers > 1 and on_chip:
            logger.error(
                "fleet run: %d workers on platform %s would share this "
                "host's accelerator; use --workers 1 or --platform cpu",
                workers, platform or "(JAX default)")
            return 1
        args.platform = platform
    import jax

    from galah_tpu.utils.compile_cache import configure_compile_cache

    if platform:
        jax.config.update("jax_platforms", platform)
    configure_compile_cache()
    if args.subcommand == "fleet":
        return 0
    try:
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 - any init failure
        msg = str(e).splitlines()[0] if str(e) else type(e).__name__
        logger.error("%s: backend failed to initialize (%s)",
                     f"--platform {platform}" if platform
                     else "JAX's default platform", msg)
        return 1
    logger.info("JAX platform %s, device kind %s, %d device(s)",
                devices[0].platform, devices[0].device_kind,
                len(devices))
    return 0


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.subcommand is None:
        parser.print_help()
        return 1
    if getattr(args, "full_help_roff", False):
        from galah_tpu.manpage import render_full_help_roff

        sys.stdout.write(render_full_help_roff(
            parser._subcommand_parsers[args.subcommand],
            args.subcommand))
        return 0
    if getattr(args, "full_help", False):
        from galah_tpu.manpage import print_full_help

        print_full_help(parser._subcommand_parsers[args.subcommand],
                        args.subcommand)
        return 0
    if args.subcommand == "lint":
        # CPU is all the lint needs (the shape harness only abstract-
        # evals); x64 keeps the uint64 ops tracing with real dtypes.
        # Both must land before any jax import.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("JAX_ENABLE_X64", "1")
        from galah_tpu.analysis import main as lint_main

        return lint_main(args=args)
    set_log_level(verbose=getattr(args, "verbose", False),
                  quiet=getattr(args, "quiet", False))
    if args.subcommand == "report":
        # Pure file I/O — never touches jax, so it skips the platform
        # probe and works on hosts with no usable accelerator at all.
        return run_report_cmd(args)
    if args.subcommand == "perf":
        # Same discipline: the ledger gate must run on CI hosts and
        # laptops with no accelerator, so it never imports jax.
        return run_perf_cmd(args)
    if args.subcommand == "flow":
        # Critical-path math over an already-written report — jax-free.
        return run_flow_cmd(args)
    if args.subcommand == "top":
        # Tails heartbeat.jsonl — jax-free, usable while a run is live.
        return run_top_cmd(args)
    if args.subcommand == "fleet" and \
            getattr(args, "fleet_action", None) != "run":
        # `fleet status`/`fleet analyze` read plan/events/heartbeats/
        # shard reports — jax-free, so they work beside a live fleet
        # on accelerator-less hosts too.
        if getattr(args, "fleet_action", None) == "status":
            return run_fleet_status(args)
        if getattr(args, "fleet_action", None) == "analyze":
            return run_fleet_analyze(args)
        parser._subcommand_parsers["fleet"].print_help()
        return 1
    if not (args.subcommand == "index"
            and getattr(args, "index_action", None) == "fsck"):
        # the on-disk index audit stays jax-free, like report/perf/top
        rc = _start_backend(args)
        if rc:
            return rc
    from galah_tpu.resilience import dispatch as rdispatch

    rdispatch.begin_run()
    logger.info("galah-tpu version %s", galah_tpu.__version__)
    # GALAH_SAN=1 arms the runtime concurrency sanitizer for this run
    # (the chaos harness and validation script set it); its summary
    # lands in the run report via obs.report.assemble.
    from galah_tpu.analysis import sanitizer as galah_san

    galah_san.maybe_install()
    try:
        if args.subcommand == "cluster":
            return run_cluster(args)
        elif args.subcommand == "dist":
            return run_dist(args)
        elif args.subcommand == "index":
            return run_index(args)
        elif args.subcommand == "fleet":
            return run_fleet(args)
        else:
            return run_cluster_validate(args)
    except (ValueError, OSError, KeyError) as e:
        # expected user errors: clean message, nonzero exit, no traceback.
        # str(e) for OS errors (args[0] would be the bare errno); args[0]
        # for KeyError/ValueError (str(KeyError) quotes the repr).
        if isinstance(e, OSError):
            logger.error("%s", e)
        else:
            logger.error("%s", e.args[0] if e.args else e)
        return 1


if __name__ == "__main__":
    sys.exit(main())
