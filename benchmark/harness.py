"""One benchmark run of one cell: set-up, the measured window, the check.

A cell (an entry of BENCHMARK.json's `workloads`) names a configuration
and a traffic mix; both are files found by name:

  benchmark/configs/<config>.json   the `galah-tpu cluster` arguments of
                                    the deployment, and the limits of
                                    the check; refused as the cell is
                                    resolved where the job and the
                                    reference would not both run it
                                    (check_config)
  benchmark/traffic/<traffic>.json  the catalogue one job clusters
                                    (benchmark/corpus.py)
  benchmark/metrics/<metric>.py     one reader per per-layer metric,
                                    `read(ctx)` -> number or None

What a run does, in order:

  set-up   JAX's persistent compile cache at <checkout>/.jax_cache (or
           JAX_COMPILATION_CACHE_DIR), then one warm-up job on a
           catalogue of the cell's traffic (job 0: the same multiset of
           genome lengths as every other job).
  window   jobs 1, 2, ... back to back, one at a time, each a fresh
           seeded catalogue written to disk while the clock is stopped
           and clustered by `galah_tpu.cli.main(["cluster", ...])`,
           FASTA to TSV. The window is the sum of the job walls and ends
           with the first job to finish after `seconds`.
  check    after the window: device and host memory are read, then one
           window job drawn from the seed is recomputed by the plain
           reference (benchmark/reference.py) and compared. Every job
           must also have run on the device path the cell is about: its
           run report names this platform's backend, records no
           demotion, and shows the counters that the configuration and
           the traffic list under `device_counters` (on a TPU).

With `trace`, the window runs under the JAX profiler (each job marked
`bench_job:<n>`) and the per-layer metrics are read from the profile,
the jobs' run reports and the compile events; without it, the
end-to-end metrics are reported.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
WORK_DIR = ROOT / ".bench_work"
CACHE_DIR = ROOT / ".jax_cache"

if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

import corpus  # noqa: E402
import reference  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


# the widths that the program and the reference both hold fixed
FIXED_WIDTHS = {"skani_kmer": reference.SKANI_K,
                "marker_compression": reference.MARKER_C,
                "screen_identity": reference.SCREEN_IDENTITY,
                "minhash_kmer": reference.FINCH_K,
                "minhash_sketch_size": reference.SKETCH_SIZE}


def check_config(config: dict) -> None:
    """Refuse a configuration that the job and the reference would not
    both run as it says, naming the key: a method the reference lacks,
    a width that neither side can change, a fragment length that
    disagrees with the job's, or an ANI subsample out of the program's
    range."""
    methods = config["methods"]
    widths = config.get("widths", {})
    if methods["precluster"] not in reference.PRECLUSTER_METHODS:
        raise ValueError(f"methods.precluster {methods['precluster']!r}: "
                         "the reference runs "
                         f"{sorted(reference.PRECLUSTER_METHODS)}")
    if methods["cluster"] not in reference.CLUSTER_METHODS:
        raise ValueError(f"methods.cluster {methods['cluster']!r}: the "
                         f"reference runs {sorted(reference.CLUSTER_METHODS)}")
    for key, value in widths.items():
        if key == "fragment_length":
            if value != methods["fragment_length"]:
                raise ValueError(
                    f"widths.fragment_length {value!r} differs from "
                    f"methods.fragment_length {methods['fragment_length']!r}")
        elif key == "ani_subsample":
            if (isinstance(value, bool) or not isinstance(value, int)
                    or not 1 <= value <= reference.MARKER_C):
                raise ValueError(f"widths.ani_subsample {value!r}: a whole "
                                 f"number in [1, {reference.MARKER_C}]")
        elif key not in FIXED_WIDTHS:
            raise ValueError(f"widths.{key}: neither the job nor the "
                             "reference takes it")
        elif value != FIXED_WIDTHS[key]:
            raise ValueError(f"widths.{key} {value!r}: the program and the "
                             f"reference hold {FIXED_WIDTHS[key]!r}")


def resolve_cell(manifest: dict, name: str,
                 bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell `name` with its configuration, traffic and the metrics
    it reports, all found by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    config_file = bench_dir / "configs" / f"{w['config']}.json"
    config = json.loads(config_file.read_text())
    check_config(config)

    # One rule for both kinds: a metric that lists `workloads` goes to
    # those cells; one that does not goes to every cell (end to end) or
    # to every cell that reports the metric it moves (per layer).
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported
                              else [])]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=corpus.load_traffic(
                    bench_dir / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ------------------------------------------------------------------ jobs

@dataclasses.dataclass
class JobRecord:
    job: int
    genomes: int
    bp: int
    wall: float
    rc: int
    rows: List[tuple]                   # TSV (representative, member)
    pre: Dict[frozenset, Optional[float]]   # precluster pairs -> ANI
    report: dict
    # the clusterer's pair ANIs from the job's greedy-round records
    clusterer: Dict[frozenset, Optional[float]] = dataclasses.field(
        default_factory=dict)
    problems: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.rc == 0 and not self.problems


def cluster_argv(config: dict, files: dict, out: Path, threads: int,
                 trace_events: Optional[Path],
                 checkpoint: bool = True) -> List[str]:
    m, q = config["methods"], config["quality"]
    argv = ["cluster", "-q",
            "--genome-fasta-list", files["genome_list"],
            "--checkm2-quality-report", files["quality"],
            "--min-completeness", str(q["min_completeness"]),
            "--max-contamination", str(q["max_contamination"]),
            "--precluster-method", m["precluster"],
            "--cluster-method", m["cluster"],
            "--ani", str(m["ani"]),
            "--precluster-ani", str(m["precluster_ani"]),
            "--min-aligned-fraction", str(m["min_aligned_fraction"]),
            "--fragment-length", str(m["fragment_length"]),
            "--ani-subsample", str(reference.ani_subsample(config)),
            "--threads", str(threads),
            "--output-cluster-definition", str(out / "clusters.tsv"),
            "--run-report", str(out / "report.json")]
    if checkpoint:
        argv += ["--checkpoint-dir", str(out / "checkpoint")]
    if trace_events is not None:
        argv += ["--trace-events", str(trace_events)]
    return argv


def _stem(path: str) -> str:
    return Path(path).name.rsplit(".", 1)[0]


def read_outputs(out: Path, greedy_pairs: List[tuple]) -> tuple:
    """TSV rows, precluster pairs and clusterer pairs (by genome name,
    each with its ANI) and run report of a finished job.
    `greedy_pairs` are the (i, j, ani) triples of the job's greedy-round
    checkpoint records, in the checkpoint's genome numbering."""
    rows = [tuple(_stem(p) for p in line.split("\t"))
            for line in (out / "clusters.tsv").read_text().splitlines()
            if line]
    report = json.loads((out / "report.json").read_text())
    ckpt = out / "checkpoint"
    if not ckpt.is_dir():
        return rows, {}, {}, report
    fields = json.loads((ckpt / "fingerprint.json").read_text())["fields"]
    names = [_stem(p) for p in fields["genomes"]]
    pre: Dict[frozenset, Optional[float]] = {}
    with np.load(ckpt / "precluster_distances.npz") as z:
        for i, j, v, hv in zip(z["ii"].tolist(), z["jj"].tolist(),
                               z["vals"].tolist(), z["has_val"].tolist()):
            pre[frozenset((names[i], names[j]))] = float(v) if hv else None
    clusterer = {frozenset((names[i], names[j])):
                 None if v is None else float(v)
                 for i, j, v in greedy_pairs}
    return rows, pre, clusterer, report


class GreedyRoundLog:
    """The (i, j, ani) triples a job hands to its checkpoint's
    greedy-round log: the clusterer's ANI of every pair the greedy
    rounds computed. The log itself is deleted once the job's clusters
    are saved, so the triples are kept as they are written."""

    def __init__(self) -> None:
        self.pairs: List[tuple] = []

    def __enter__(self) -> "GreedyRoundLog":
        from galah_tpu.cluster.checkpoint import ClusterCheckpoint

        self._cls = ClusterCheckpoint
        self._orig = ClusterCheckpoint.save_greedy_round
        orig, pairs = self._orig, self.pairs

        def save_greedy_round(ckpt, digest, round_pairs):
            orig(ckpt, digest, round_pairs)
            pairs.extend(tuple(p) for p in round_pairs)

        ClusterCheckpoint.save_greedy_round = save_greedy_round
        return self

    def __exit__(self, *exc) -> None:
        self._cls.save_greedy_round = self._orig


def path_problems(report: dict, platform: str,
                  counters_needed: List[str]) -> List[str]:
    """Why a job did not run on the device path its cell is about: a
    backend other than this platform's, any demotion to a fallback, or
    a needed counter that stayed at zero."""
    problems = []
    backend = report.get("device", {}).get("backend")
    if backend != platform:
        problems.append(f"report backend {backend!r}, not {platform!r}")
    demotions = report.get("resilience", {}).get("demotions")
    if demotions:
        problems.append(f"demotions {demotions}")
    for e in report.get("events", []):
        if "demot" in str(e.get("kind", "")):
            problems.append(f"event {e.get('kind')}")
    counters = report.get("counters", {})
    for name, value in counters.items():
        if "demoted" in name and value:
            problems.append(f"counter {name}={value}")
    for name in counters_needed:
        if not counters.get(name):
            problems.append(f"counter {name} is zero")
    return problems


def counters_needed(cell: "Cell", platform: str) -> List[str]:
    """The device-path counters a job of this cell must show: those of
    its configuration and of its traffic, on a TPU only (the CPU
    backend has no Pallas kernels)."""
    if platform != "tpu":
        return []
    return (list(cell.config.get("device_counters", []))
            + list(cell.traffic.get("device_counters", [])))


def host_cores() -> int:
    """Cores this process may run on: its CPU affinity, capped by a
    cgroup CPU quota where one is set. The jobs get them all as
    `--threads`, as a user on this host would give them."""
    cores = len(os.sched_getaffinity(0))
    try:
        quota, period = Path("/sys/fs/cgroup/cpu.max").read_text().split()
        if quota != "max":
            cores = min(cores, max(1, int(int(quota) // int(period))))
    except (OSError, ValueError):
        pass
    return cores


def release_memory() -> None:
    """Collect garbage and hand freed heap pages back to the system:
    before each job, so that no job starts on the last one's heap, and
    before the reference, which then runs in what the jobs no longer
    use."""
    gc.collect()
    try:
        import ctypes

        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def rss_mb() -> float:
    """The process's resident set now, MB."""
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024 / 1e6
    return 0.0


def log(msg: str) -> None:
    print(f"[bench] {msg} rss_mb={rss_mb():.0f}", file=sys.stderr,
          flush=True)


def run_job(cell: Cell, seed: int, job: int, threads: int,
            trace_events: bool = False, checkpoint: bool = True
            ) -> JobRecord:
    """Write job `job`'s catalogue, cluster it through the CLI (the only
    timed part), read its outputs, check the device path it took and
    remove its files. Without `checkpoint` (a measurement of what the
    checkpoint costs) the job has no pairs to compare."""
    import jax

    from galah_tpu import cli

    job_dir = WORK_DIR / f"job_{job:03d}"
    shutil.rmtree(job_dir, ignore_errors=True)
    cat = corpus.generate(cell.traffic, seed, job)
    files = corpus.write(cat, job_dir / "input")
    out = job_dir / "output"
    out.mkdir(parents=True)
    argv = cluster_argv(cell.config, files, out, threads,
                        out / "trace.json" if trace_events else None,
                        checkpoint)
    # the catalogue reaches the disk, and the last job's garbage the
    # allocator, before the clock starts: neither lands in the job
    os.sync()
    release_memory()
    greedy = GreedyRoundLog()
    t0 = time.perf_counter()
    try:
        with greedy, jax.profiler.TraceAnnotation(f"bench_job:{job}"):
            rc = cli.main(argv)
    except Exception as e:  # noqa: BLE001 - a failed job is counted
        print(f"job {job} raised {type(e).__name__}: {e}",
              file=sys.stderr)
        rc = -1
    wall = time.perf_counter() - t0
    rows, pre, clusterer, report, problems = [], {}, {}, {}, []
    if rc == 0:
        rows, pre, clusterer, report = read_outputs(out, greedy.pairs)
        platform = jax.devices()[0].platform
        problems = path_problems(report, platform,
                                 counters_needed(cell, platform))
        if trace_events:
            report["_trace_events"] = _read_chrome(out / "trace.json")
    shutil.rmtree(job_dir, ignore_errors=True)
    shown = {k: v for k, v in report.get("counters", {}).items()
             if k.endswith(("-engaged", "-folds", "-launches", "-rounds"))
             or "strategy" in k or "demoted" in k}
    log(f"job {job} rc={rc} wall_s={wall:.3f} genomes={len(cat.genomes)} "
        f"clusterer_pairs={len(clusterer)} counters={shown}"
        + (f" PROBLEMS {problems}" if problems else ""))
    return JobRecord(job=job, genomes=len(cat.genomes), bp=cat.total_bp,
                     wall=wall, rc=rc, rows=rows, pre=pre, report=report,
                     clusterer=clusterer, problems=problems)


def _read_chrome(path: Path) -> list:
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return []
    return data.get("traceEvents", data) if isinstance(data, dict) \
        else data


# ------------------------------------------------------------------ check

def _gap(ref: Dict[frozenset, Optional[float]],
         got: Dict[frozenset, Optional[float]]) -> float:
    """The largest ANI gap over the pairs both sides have; 1 (no ANI
    is further from another) where one side has a value and the other
    none."""
    gap = 0.0
    for pair, v in ref.items():
        if pair not in got:
            continue
        w = got[pair]
        if (v is None) != (w is None):
            gap = 1.0
        elif v is not None:
            gap = max(gap, abs(v - w))
    return gap


def compare(config: dict, rec: JobRecord, ref: reference.JobResult,
            truth: List[frozenset], jobs_failed: int = 0
            ) -> Dict[str, dict]:
    """The numbers compared, each with its limit (from the config).
    `clusterer_ani_gap`, where the config has it, covers the pairs whose
    clusterer ANI the job's greedy rounds computed; a job that computed
    none reads 1."""
    limits = config["check_limits"]
    ref_rows = ref.rows()
    n = max(len(rec.rows), len(ref_rows))
    rows_wrong = sum(1 for i in range(n)
                     if i >= len(rec.rows) or i >= len(ref_rows)
                     or rec.rows[i] != ref_rows[i])
    clusters: Dict[str, set] = {}
    for rep, member in rec.rows:
        clusters.setdefault(rep, set()).add(member)
    got = {frozenset(c) for c in clusters.values()}
    planted_wrong = len(got ^ set(truth))
    pairs_wrong = len(set(rec.pre) ^ set(ref.pre))
    values = {"jobs_failed": jobs_failed,
              "tsv_rows_wrong": rows_wrong,
              "planted_clusters_wrong": planted_wrong,
              "precluster_pairs_wrong": pairs_wrong,
              "precluster_ani_gap": _gap(ref.pre, rec.pre)}
    if "clusterer_ani_gap" in limits:
        values["clusterer_ani_gap"] = (
            _gap(ref.clusterer, rec.clusterer) if rec.clusterer else 1.0)
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def passes(check: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in check.values())


def reference_inputs(cat: corpus.Catalogue):
    records = [{"name": g.name, "completeness": g.completeness,
                "contamination": g.contamination,
                "num_contigs": len(g.contigs), "num_ambiguous": 0}
               for g in cat.genomes]
    contigs = {g.name: g.contigs for g in cat.genomes}
    return records, contigs


def clusterer_pairs(rec: JobRecord) -> List[Tuple[str, str]]:
    return sorted(tuple(sorted(p)) for p in rec.clusterer)


def reference_job(cell: Cell, seed: int, rec: JobRecord, threads: int
                  ) -> Tuple[reference.Job, reference.JobResult,
                             List[frozenset]]:
    """The reference prepared for the catalogue of job `rec`; its
    result, with the clusterer's ANI of the pairs the job computed; and
    the catalogue's planted clusters. The caller closes the job."""
    cat = corpus.generate(cell.traffic, seed, rec.job)
    records, contigs = reference_inputs(cat)
    job = reference.Job(cell.config, records, contigs, threads)
    ref = job.solve()
    pairs = clusterer_pairs(rec)
    ref.clusterer = {frozenset(p): v for p, v in
                     zip(pairs, job.clusterer_values(pairs))}
    return job, ref, cat.truth(cell.config["truth_level"])


# ------------------------------------------------------------------ run

class CompileCounter:
    """Backend compiles reported by JAX while `counting` is on."""

    def __init__(self) -> None:
        self.counting = False
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if self.counting and event == COMPILE_EVENT:
            self.count += 1


def configure_jax() -> CompileCounter:
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # every program the cell compiles is kept, so a later run of the
    # cell in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    return counter


def device_info(jax) -> dict:
    devs = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees."""
    cell: Cell
    jobs: List[JobRecord]
    trace: object            # trace_reduce.Trace or None
    compiles_in_window: int
    device_kind: str
    sample: JobRecord
    sample_reference: reference.JobResult

    @property
    def peaks(self) -> dict:
        return load_peaks(self.device_kind)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             started: float, counter: CompileCounter,
             threads: Optional[int] = None) -> dict:
    import jax

    threads = threads or host_cores()
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    WORK_DIR.mkdir(parents=True)
    warm = run_job(cell, seed, 0, threads)
    if warm.rc != 0:
        raise RuntimeError(f"warm-up job exited {warm.rc}")
    setup_s = time.monotonic() - started

    trace_dir = WORK_DIR / "profile"
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.counting = True
    jobs: List[JobRecord] = []
    window = 0.0
    try:
        while True:
            rec = run_job(cell, seed, len(jobs) + 1, threads,
                          trace_events=trace)
            jobs.append(rec)
            window += rec.wall
            if window >= seconds:
                break
    finally:
        counter.counting = False
        if trace:
            jax.profiler.stop_trace()
    compiles = counter.count
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e6
    device = device_info(jax)
    failed = sum(1 for j in jobs if not j.ok)
    genomes = sum(j.genomes for j in jobs)
    release_memory()

    rng = np.random.default_rng([int(seed), 2])
    sample = jobs[int(rng.integers(len(jobs)))]
    log(f"reference of job {sample.job}")
    t_ref = time.monotonic()
    if sample.rc == 0:
        ref_job, ref, truth = reference_job(cell, seed, sample, threads)
        ref_job.close()
        check = compare(cell.config, sample, ref, truth, failed)
    else:
        check = {"jobs_failed": {"value": failed, "limit": 0}}
    ref_s = time.monotonic() - t_ref
    log(f"reference done in {ref_s:.1f} s")
    correct = passes(check)

    result = {"correct": correct, "attempted": len(jobs),
              "failed": failed, "metrics": {}, "device": device}
    if trace:
        from trace_reduce import find_xplane, load

        xplane = find_xplane(str(trace_dir))
        tr = load(xplane) if xplane else None
        ctx = Context(cell=cell, jobs=jobs, trace=tr,
                      compiles_in_window=compiles,
                      device_kind=device["kind"], sample=sample,
                      sample_reference=ref)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if tr is not None and tr.busy_s() is not None:
            result["device"]["busy_s"] = tr.busy_s()
            result["device"]["window_s"] = tr.window_s()
            result["breakdown"] = {
                "device_ops": tr.top_ops(10),
                "idle_gaps": tr.idle_gaps(_gap_labeler(jobs, tr), 10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"genomes_per_s": genomes / window,
                  "peak_host_rss_mb": rss_mb, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["notes"] = {"window_s": window, "reference_s": ref_s,
                       "sample_job": sample.job,
                       "clusterer_pairs": len(sample.clusterer),
                       "compiles_in_window": compiles,
                       "job_walls": [j.wall for j in jobs]}
    result["check"] = check
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    return result


def load_peaks(kind: str) -> dict:
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "benchmark/peaks.json")
    return peaks["devices"][kind]


def _gap_labeler(jobs: List[JobRecord], tr):
    """Name a device-idle gap by the innermost program stage open at its
    middle, from the job's own trace events (their clock starts with the
    job; the job's annotation gives the offset)."""
    spans = []
    for rec in jobs:
        if rec.job not in tr.jobs:
            continue
        t0 = tr.jobs[rec.job][0]
        for e in rec.report.get("_trace_events", []):
            if e.get("ph") == "X" and "dur" in e:
                s = t0 + e["ts"] * 1e-6
                spans.append((s, s + e["dur"] * 1e-6, e.get("name", "?")))

    def label(s: float, e: float) -> str:
        mid = 0.5 * (s + e)
        inner = [sp for sp in spans if sp[0] <= mid < sp[1]]
        if not inner:
            return "host:between-stages"
        return "host:" + min(inner, key=lambda sp: sp[1] - sp[0])[2]

    return label
