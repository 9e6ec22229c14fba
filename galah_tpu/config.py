"""Central configuration and compile-time defaults.

Mirrors the reference's defaults block (reference: src/lib.rs:39-47) — the
code defaults are authoritative (the reference README's 99/95 text is
stale). Sketch parameters mirror the finch/skani parameter sets the
reference hard-codes (reference: src/finch.rs:33-45, src/skani.rs:131-163).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple


class Defaults:
    """Compile-time defaults (reference: src/lib.rs:39-47)."""

    ALIGNED_FRACTION = 0.15          # --min-aligned-fraction 15%
    FRAGMENT_LENGTH = 3000           # --fragment-length
    ANI = 95.0                       # --ani (percent)
    PRETHRESHOLD_ANI = 90.0          # --precluster-ani (percent)
    QUALITY_FORMULA = "Parks2020_reduced"
    PRECLUSTER_METHOD = "skani"      # choices: skani, finch, dashing
    CLUSTER_METHOD = "skani"         # choices: skani, fastani

    # MinHash (finch-equivalent) sketch params (reference: src/finch.rs:33-45)
    MINHASH_KMER = 21
    MINHASH_SKETCH_SIZE = 1000
    MINHASH_SEED = 0
    # Sketch hash: "murmur3" is bit-compatible with the reference's finch
    # contract; "tpufast" is the multiply-free TPU-native mixer
    # (statistically equivalent MinHash/HLL estimates, ~20x faster on the
    # VPU, which has no fast integer multiply). --hash-algorithm.
    HASH_ALGO = "murmur3"

    # FracMinHash (skani-equivalent) params (reference: src/skani.rs:131-163)
    SKANI_C = 125                    # FracMinHash compression factor
    SKANI_MARKER_C = 1000            # marker sketch compression
    SKANI_KMER = 15
    SKANI_SCREEN_CONTAINMENT = 0.80  # candidate screening (src/skani.rs:59)
    # FracMinHash subsampling of the exact fragment-ANI stage: 1 keeps
    # every k-mer (dense; the pinned goldens/accuracy bounds use this);
    # higher values trade a little per-window variance for ~c-fold less
    # membership-test work (the reference's skani runs at c=125).
    ANI_SUBSAMPLE = 1

    # Quality-filter defaults: no filtering unless quality input given
    MIN_COMPLETENESS = None
    MAX_CONTAMINATION = None


# ---------------------------------------------------------------------------
# GALAH_* environment-flag registry
#
# Every environment variable the project reads is declared here, once,
# with its default and one-line documentation. The registry is the
# single source of truth three consumers share:
#   * call sites — read through ``env_value(name)`` (or keep a local
#     ``os.environ`` read, which the lint cross-checks against this
#     table);
#   * ``manpage.py`` — auto-renders the ENVIRONMENT section of every
#     --full-help page from this table (no hand-maintained list);
#   * ``galah_tpu.analysis`` — the flag checker AST-enumerates every
#     GALAH_* read in the tree and fails on flags missing from this
#     table or carrying a conflicting literal default at the read site.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Flag:
    """One registered environment variable."""

    name: str                       # full env var name, GALAH_*
    help: str                       # one-line doc (manpage ENVIRONMENT)
    default: Optional[str] = None   # None == unset; always the string form
    kind: str = "str"               # str | int | float | bool | grammar
    # runtime|kernel|resilience|observability|bench|test|scripts
    section: str = "runtime"
    choices: Tuple[str, ...] = ()
    # Where the read happens outside the python tree the linter scans
    # (C sources, shell scripts) — suppresses the unread-flag notice.
    external_reader: Optional[str] = None


def _retry_family(prefix: str, section_help: str) -> Tuple[Flag, ...]:
    """The seven knobs RetryPolicy.from_env reads under `prefix`_*."""
    spec = (
        ("MAX_ATTEMPTS", "int", "attempts per dispatch before giving up"),
        ("BASE_DELAY", "float", "first backoff delay, seconds"),
        ("MAX_DELAY", "float", "backoff cap, seconds"),
        ("JITTER", "float", "+- fraction of each delay, in [0, 1]"),
        ("ATTEMPT_DEADLINE", "float",
         "seconds per attempt; a wedged attempt is abandoned"),
        ("TOTAL_BUDGET", "float",
         "overall retry wall-clock budget per call, seconds"),
        ("SEED", "int", "makes the backoff jitter bit-reproducible"),
    )
    return tuple(
        Flag(name=f"{prefix}_{suffix}", kind=kind, section="resilience",
             help=f"{section_help}: {doc}",
             external_reader="resilience/policy.py RetryPolicy.from_env "
                             "(dynamic f-string read)")
        for suffix, kind, doc in spec)


_FLAG_DEFS: Tuple[Flag, ...] = (
    # -- runtime / IO ------------------------------------------------------
    Flag("GALAH_TPU_PLATFORM", section="runtime",
         help="Force the JAX platform (cpu, tpu, ...); the --platform "
              "flag's env twin and loses to it"),
    Flag("GALAH_TPU_CACHE", section="runtime",
         help="Directory for the persistent sketch/profile cache; the "
              "--sketch-cache flag's env twin and loses to it. Unset "
              "disables caching"),
    Flag("GALAH_TPU_IR_CACHE", section="runtime",
         help="Directory for the lint IR cache (per-file GalahIR "
              "entries and the GL5xx shapes verdict, content-hash "
              "keyed); the `galah-tpu lint --ir-cache-dir` flag's env "
              "twin and loses to it. Unset disables caching",
         external_reader="analysis/ir.py default_cache_dir"),
    Flag("GALAH_TPU_INDEX_DIR", section="runtime",
         help="Directory of the persistent versioned sketch index; "
              "the --index-dir flag's env twin and loses to it"),
    Flag("GALAH_TPU_INDEX_BATCH", kind="int", default="32",
         section="resilience",
         help="Genomes per durable append batch of `index insert` "
              "(the preemption safe-boundary granularity: a kill "
              "loses at most one batch of uncommitted appends)"),
    # -- kernel / device policy -------------------------------------------
    Flag("GALAH_TPU_DENSE_PAIRS", kind="bool", section="kernel",
         help="Force the dense O(N^2) pairwise pass (skip the sparse "
              "collision screen) regardless of problem size"),
    Flag("GALAH_TPU_SPARSE_MIN_N", kind="int", default="1024",
         section="kernel",
         help="Genome count at which the sparse collision screen "
              "replaces dense all-pairs passes; malformed values are "
              "logged and ignored"),
    Flag("GALAH_TPU_PAIR_BATCH", kind="int", section="kernel",
         help="Candidate pairs per device dispatch of the screened "
              "pipeline; unset picks 8192 (CPU) or 32768 (TPU)"),
    Flag("GALAH_TPU_PAIRLIST_STRATEGY", section="kernel",
         choices=("blocked", "gather", "xla", "cpu"),
         help="Pin the survivor-evaluation strategy instead of the "
              "AUTO heuristic"),
    Flag("GALAH_TPU_PAIRLIST_BLOCK", kind="int", default="8",
         section="kernel",
         help="Pairs per program (P) for the blocked Mosaic pairlist "
              "kernel"),
    Flag("GALAH_TPU_FRAGMENT_STRATEGY", section="kernel",
         choices=("pallas", "xla", "c"),
         help="Pin the exact fragment-ANI membership strategy "
              "(blocked Mosaic kernel / vmapped searchsorted / "
              "compiled-C merge) instead of the AUTO heuristic"),
    Flag("GALAH_TPU_GREEDY_STRATEGY", section="kernel",
         choices=("device", "host"),
         help="Pin the greedy representative scan to the round-based "
              "device path or the per-precluster host scan instead of "
              "the AUTO heuristic (decisions are bit-identical; a "
              "pinned strategy's failures propagate instead of "
              "demoting)"),
    Flag("GALAH_TPU_SKETCH_STRATEGY", section="kernel",
         choices=("fused", "xla", "c"),
         help="Pin the sketch-stage strategy (fused Pallas "
              "hash+bottom-k kernel / chunked-XLA device path / C "
              "bottom-k sketcher) instead of the AUTO heuristic "
              "(sketches are bit-identical; a pinned strategy's "
              "failures propagate instead of demoting)"),
    Flag("GALAH_TPU_INGEST_DEPTH", kind="int", section="kernel",
         help="Look-ahead depth of the streaming ingest stage (parsed "
              "genomes in flight ahead of the sketch launches); unset "
              "uses max(2, threads)"),
    Flag("GALAH_TPU_OVERLAP", section="kernel", default="auto",
         choices=("auto", "0", "1"),
         help="Overlapped end-to-end dataflow (docs/dataflow.md): "
              "sketch -> pair screen -> speculative fragment-ANI -> "
              "eager greedy rounds run as one pipeline instead of "
              "four sequential drains. auto engages it where it is "
              "bit-identical to the stage-serial engine and demotes "
              "on failure; 1 forces it (failures propagate); 0 "
              "disables it"),
    Flag("GALAH_TPU_OVERLAP_DEPTH", kind="int", default="512",
         section="kernel",
         help="Survivor pairs buffered before a speculative "
              "fragment-ANI batch launches in the overlapped "
              "dataflow; bounds the in-flight window (memory stays "
              "O(depth))"),
    Flag("GALAH_TPU_MEGAKERNEL", section="kernel", default="auto",
         choices=("auto", "0", "1"),
         help="Fused device-resident greedy rounds (docs/dataflow.md "
              "'Persistent device rounds'): consecutive round windows "
              "fuse into one slab whose surviving pairs enqueue into "
              "the on-device work queue and resolve with one fused "
              "fold program — 2 dispatches per slab instead of one "
              "window fold each, bit-identical decisions. auto "
              "engages inside device greedy rounds and demotes to "
              "the per-window dense fold on failure; 1 forces it "
              "(failures and ineligibility propagate); 0 disables "
              "it"),
    Flag("GALAH_TPU_QUEUE_CAP", kind="int", default="4096",
         section="kernel",
         help="Capacity (pairs) of the on-device megakernel work "
              "queue, rounded up to a power of two. Slabs whose "
              "surviving-pair count exceeds it spill to the exact "
              "per-window dense path (megakernel-overflow-spills "
              "counter) — results are exact at any value; the knob "
              "only moves the spill boundary"),
    Flag("GALAH_TPU_MESH_SHAPE", section="kernel", default="auto",
         help="Device-mesh geometry for the all-pairs distance passes "
              "(docs/DISTRIBUTED.md): 'auto' picks the squarest RxC "
              "factorization of the device count (communication-"
              "avoiding 2D tiling — each sketch row is replicated "
              "along one mesh row and one mesh column instead of to "
              "every device), '1d' pins the single-axis mesh, and an "
              "explicit 'RxC' (e.g. '2x4') pins that shape. A shape "
              "that does not factor the device count demotes to 1-D "
              "with a mesh-demoted event"),
    Flag("GALAH_TPU_HLL_BUCKETS", section="kernel", default="auto",
         choices=("auto", "0", "1"),
         help="HLL cardinality-bucketed hierarchical precluster "
              "(docs/DISTRIBUTED.md): bucket genomes into overlapping "
              "log-cardinality bands sized so no pair that could "
              "reach the precluster threshold lands in disjoint "
              "bands, and schedule only same- and adjacent-band "
              "pairs. auto engages above the sparse-screen crossover; "
              "1 forces it at any N; 0 disables it"),
    Flag("GALAH_TPU_PAGESTORE", section="kernel", default="auto",
         choices=("auto", "0", "1"),
         help="Out-of-core tiered sketch memory (docs/memory.md): "
              "sketch rows live in an mmap-backed page store under "
              "the cache dir and only the active band window is "
              "resident, bounding peak RSS while clusterings stay "
              "bit-identical to the all-resident path. auto engages "
              "when the bucketed precluster is engaged and the "
              "projected sketch matrix exceeds the RAM budget; 1 "
              "forces paging whenever bucketing is engaged; 0 "
              "disables it"),
    Flag("GALAH_TPU_SKETCH_RAM_MB", kind="int", default="512",
         section="kernel",
         help="Hard byte budget, in MiB, for the resident (mmapped "
              "and LRU-pinned) page set of the out-of-core sketch "
              "store (docs/memory.md). Band-pinned pages are never "
              "evicted, so the effective floor is two bands' pages; "
              "malformed values are logged and ignored"),
    Flag("GALAH_TPU_PREFILTER", section="kernel", default="auto",
         choices=("auto", "0", "1"),
         help="Ingest-time probabilistic k-mer prefilter "
              "(docs/memory.md): computes HLL registers during the "
              "streamed ingest (C fast path) and screens exact-"
              "duplicate and degenerate (no valid k-mer window) "
              "genomes before full sketching under a provably "
              "conservative skip rule — pair sets and clusterings "
              "are bit-identical with the prefilter off. auto "
              "engages with the streamed single-process ingest; 1 "
              "forces it; 0 disables it"),
    Flag("GALAH_TPU_PALLAS_HASH", kind="bool", section="kernel",
         help="1 forces the quarantined Mosaic murmur3 kernel, 0 "
              "forces the XLA u64 emulation; unset uses the "
              "data-driven per-backend default"),
    Flag("GALAH_PACKED_TRANSFER", kind="bool", section="kernel",
         help="Force (1) or forbid (0) the packed-upload / batched "
              "transfer policy; unset defers to the backend probe"),
    Flag("GALAH_TPU_NO_CCOLLISION", kind="bool", section="kernel",
         help="Disable the C collision-counting fast path (numpy "
              "fallback)",
         external_reader="utils/cbuild.py (disable_env)"),
    Flag("GALAH_TPU_NO_CPAIRSTATS", kind="bool", section="kernel",
         help="Disable the C pair-stats fast path",
         external_reader="utils/cbuild.py (disable_env)"),
    Flag("GALAH_TPU_NO_CSKETCH", kind="bool", section="kernel",
         help="Disable the C sketch fast path",
         external_reader="utils/cbuild.py (disable_env)"),
    Flag("GALAH_TPU_NO_CINGEST", kind="bool", section="kernel",
         help="Disable the C FASTA-ingest fast path",
         external_reader="utils/cbuild.py (disable_env)"),
    Flag("GALAH_TPU_NO_AVX512", kind="bool", section="kernel",
         help="Keep the C merge counter off its AVX-512 kernel",
         external_reader="csrc/pairstats.c (getenv)"),
    # -- observability -----------------------------------------------------
    Flag("GALAH_OBS_REPORT", section="observability",
         help="Write the end-of-run run_report.json (stage tree, "
              "dispatch counts, precluster funnel, flag snapshot, "
              "resilience events) to this path; the --run-report "
              "flag's env twin and loses to it. Render or diff with "
              "`galah-tpu report` (docs/observability.md)"),
    Flag("GALAH_OBS_TRACE_EVENTS", section="observability",
         help="Write Chrome-trace-format span/events (stage spans, "
              "JAX compile events, resilience events; Perfetto-"
              "loadable) to this path; the --trace-events flag's env "
              "twin and loses to it"),
    Flag("GALAH_OBS_PROFILE", kind="bool", default="1",
         section="observability",
         help="Device-cost attribution for registered jit/Pallas "
              "entry points (XLA cost_analysis FLOPs/bytes, compile "
              "and dispatch walls, HBM high-water) into the "
              "run report's device_costs section; 0 disables the "
              "profiled-dispatch path entirely"),
    Flag("GALAH_OBS_FLOW", kind="bool", default="1",
         section="observability",
         help="Flow-level pipeline tracing (galah_tpu/obs/flow.py): "
              "flow ids on pipeline items, per-stage wait/service "
              "histograms with blocked-on attribution, Chrome-trace "
              "flow arrows, and the run report's flow section behind "
              "`galah-tpu flow analyze`; 0 turns every record call "
              "into a no-op"),
    Flag("GALAH_OBS_HEARTBEAT_S", kind="float", default="0",
         section="observability",
         help="Period in seconds for the liveness heartbeat thread "
              "(galah_tpu/obs/heartbeat.py): each beat durably "
              "appends counters/gauges/queue-depth/occupancy to "
              "heartbeat.jsonl beside the run report, rendered live "
              "by `galah-tpu top <dir>`. 0 (the default) disables it"),
    Flag("GALAH_OBS_OPENMETRICS", section="observability",
         help="Render the metrics registry — and, in a fleet run, the "
              "cross-shard blame rollup — to this path in Prometheus "
              "text exposition format on every heartbeat tick "
              "(galah_tpu/obs/openmetrics.py; atomically swapped, so "
              "a node-exporter textfile collector never reads a torn "
              "page). Needs GALAH_OBS_HEARTBEAT_S > 0 to tick; unset "
              "disables the exporter"),
    Flag("GALAH_OBS_LEDGER", section="observability",
         help="Append one entry per finalized run to this cross-run "
              "perf ledger (JSONL, keyed by backend/topology/"
              "workload/strategy); inspect and gate with the "
              "`galah-tpu perf` subcommand (docs/observability.md). "
              "Unset disables the ledger feed"),
    Flag("GALAH_OBS_LEDGER_WINDOW", kind="int", default="8",
         section="observability",
         help="How many most-recent same-key ledger entries form the "
              "`perf check` noise band"),
    Flag("GALAH_OBS_LEDGER_MAD_K", kind="float", default="4",
         section="observability",
         help="Width of the `perf check` noise band, in MADs around "
              "the window median (the MAD is floored at 1 percent of "
              "the "
              "median so an all-identical history cannot gate on "
              "epsilon)"),
    Flag("GALAH_SAN", kind="bool", section="observability",
         help="1 arms GalahSan, the runtime concurrency sanitizer "
              "(galah_tpu/analysis/sanitizer.py): wraps the threaded "
              "modules' declared locks, diffs the observed "
              "acquisition graph against LOCK_ORDER, and checks "
              "GUARDED_BY mutations for races. Tier-1 pytest and the "
              "chaos harness set it; the summary lands in "
              "run_report.json (docs/sanitizer.md)"),
    Flag("GALAH_SAN_REPORT", section="observability",
         help="Path for the standalone sanitizer_report.json (full "
              "lock graph + findings); default sanitizer_report.json "
              "in the working directory when the sanitizer writes "
              "one"),
    # -- resilience --------------------------------------------------------
    Flag("GALAH_FI", kind="grammar", section="resilience",
         help="Deterministic fault injection, e.g. "
              "'site=dispatch.ani;kind=raise;prob=0.3;seed=7;max=2'. "
              "Dispatch kinds: raise, device-lost, hang, garbage. "
              "Filesystem kinds (fire inside io/atomic.py at "
              "io.atomic.* sites): enospc, eio, torn-write, slow-io. "
              "'kill' fires at any site and os._exit()s the process "
              "mid-operation (the chaos harness primitive, "
              "scripts/chaos_run.py). See docs/resilience.md"),
    Flag("GALAH_TPU_FLEET_WORKERS", kind="int", default="2",
         section="resilience",
         help="Fleet supervisor (galah-tpu fleet run): maximum worker "
              "subprocesses live at once. Shards queue behind the "
              "worker cap and are reassigned on preemption "
              "(docs/resilience.md, Fleet execution)"),
    Flag("GALAH_TPU_FLEET_SHARDS", kind="int", section="resilience",
         help="Fleet shard count: contiguous quality-order slices of "
              "the genome set, one worker run each. Unset defaults to "
              "the worker cap"),
    Flag("GALAH_TPU_FLEET_STALE_S", kind="float", default="30",
         section="resilience",
         help="Heartbeat staleness deadline, seconds: a worker whose "
              "newest heartbeat record is older than this is killed "
              "and its shard reassigned (same treatment as exit 75 "
              "and SIGKILL). Requires a nonzero fleet heartbeat "
              "period"),
    Flag("GALAH_TPU_FLEET_POLL_S", kind="float", default="0.2",
         section="resilience",
         help="Fleet supervisor poll period, seconds"),
    Flag("GALAH_TPU_FLEET_HEARTBEAT_S", kind="float", default="1",
         section="resilience",
         help="GALAH_OBS_HEARTBEAT_S value injected into fleet "
              "workers (their liveness signal); 0 disables worker "
              "heartbeats AND staleness detection"),
    Flag("GALAH_TPU_FLEET_WORKER", section="resilience",
         help="Set BY the fleet supervisor in every worker "
              "subprocess's environment (value: the fleet dir's "
              "absolute path) — the orphan-adoption stamp it matches "
              "against /proc/<pid>/environ, and the marker the "
              "telemetry layer uses to brand worker heartbeats and "
              "shard ledger entries. Never set this by hand: a "
              "process carrying the stamp is killable by any "
              "scheduler supervising that fleet dir"),
) + _retry_family(
    "GALAH_RETRY", "Device-dispatch retry policy"
) + _retry_family(
    "GALAH_IO_RETRY", "FASTA/IO retry policy (defaults: 3 attempts, "
    "0.1 s base delay)"
) + _retry_family(
    "GALAH_TPU_FLEET_RETRY", "Per-shard fleet reassignment budget "
    "(max_attempts bounds worker-fault preemptions per shard before "
    "quarantine; delays pace the relaunch backoff)"
) + (
    # -- bench / test / scripts -------------------------------------------
    Flag("GALAH_BENCH_STAGE_CAP", kind="float", default="3000",
         section="bench",
         help="Wall-clock budget of one bench.py run, seconds; stages "
              "that could not finish inside it are skipped"),
    Flag("GALAH_BENCH_N", kind="int", section="bench",
         help="Override the genome count of the bench.py ladder stage"),
    Flag("GALAH_RUN_SLOW", kind="bool", section="test",
         help="1 runs the slow/hardware test tier the default run "
              "skips"),
    Flag("GALAH_RUN_CAMPAIGN", kind="bool", section="test",
         help="1 runs the full abisko18 campaign combo matrix"),
)

FLAGS: Dict[str, Flag] = {f.name: f for f in _FLAG_DEFS}

#: Dynamic-prefix families (read via f-strings, e.g. RetryPolicy.from_env).
FLAG_FAMILIES: Tuple[str, ...] = ("GALAH_RETRY", "GALAH_IO_RETRY",
                                  "GALAH_TPU_FLEET_RETRY")


def env_value(name: str) -> Optional[str]:
    """The registered flag's current value: the environment when set,
    else the registry default (None for unset). Reading an unregistered
    name raises — new flags must be declared in FLAGS first."""
    flag = FLAGS.get(name)
    if flag is None:
        raise KeyError(f"environment flag {name} is not registered in "
                       "galah_tpu.config.FLAGS")
    raw = os.environ.get(name)
    return raw if raw not in (None, "") else flag.default


PRECLUSTER_METHODS = ("skani", "finch", "dashing")
HASH_ALGORITHMS = ("murmur3", "tpufast")
CLUSTER_METHODS = ("skani", "fastani")
QUALITY_FORMULAS = (
    "Parks2020_reduced",
    "completeness-4contamination",
    "completeness-5contamination",
    "dRep",
)


def parse_percentage(value: float, name: str = "value") -> float:
    """Normalize a percentage argument to a fraction in [0, 1].

    Reference semantics (src/cluster_argument_parsing.rs:1160-1182):
    values in [1, 100] are percent (so exactly 1 means 1%, not 100%);
    values in [0, 1) are already fractions; anything else is an error.
    """
    v = float(value)
    if 1.0 <= v <= 100.0:
        return v / 100.0
    if 0.0 <= v < 1.0:
        return v
    raise ValueError(f"{name} must be within [0, 100], got {value}")


@dataclasses.dataclass
class ClusterConfig:
    """Everything `galah-tpu cluster` needs; the host-side config object.

    Thresholds are stored as *fractions* (0-1); backends that want percent
    units multiply by 100 themselves.
    """

    genome_paths: Sequence[str] = ()
    ani: float = Defaults.ANI / 100.0
    precluster_ani: float = Defaults.PRETHRESHOLD_ANI / 100.0
    min_aligned_fraction: float = Defaults.ALIGNED_FRACTION
    fragment_length: int = Defaults.FRAGMENT_LENGTH
    precluster_method: str = Defaults.PRECLUSTER_METHOD
    cluster_method: str = Defaults.CLUSTER_METHOD
    quality_formula: str = Defaults.QUALITY_FORMULA
    min_completeness: Optional[float] = None   # fraction
    max_contamination: Optional[float] = None  # fraction
    checkm_tab_table: Optional[str] = None
    checkm2_quality_report: Optional[str] = None
    genome_info: Optional[str] = None
    threads: int = 1
    # outputs
    output_cluster_definition: Optional[str] = None
    output_representative_fasta_directory: Optional[str] = None
    output_representative_fasta_directory_copy: Optional[str] = None
    output_representative_list: Optional[str] = None

    def __post_init__(self) -> None:
        if self.precluster_method not in PRECLUSTER_METHODS:
            raise ValueError(
                f"unknown precluster method {self.precluster_method!r}; "
                f"choices: {PRECLUSTER_METHODS}")
        if self.cluster_method not in CLUSTER_METHODS:
            raise ValueError(
                f"unknown cluster method {self.cluster_method!r}; "
                f"choices: {CLUSTER_METHODS}")
        if self.quality_formula not in QUALITY_FORMULAS:
            raise ValueError(
                f"unknown quality formula {self.quality_formula!r}; "
                f"choices: {QUALITY_FORMULAS}")
