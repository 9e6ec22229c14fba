"""Benchmark harness: device throughput vs an honest CPU baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Headline metric: all-pairs MinHash ANI throughput (genome-pairs/sec) —
the production sparse pair extraction (ops/pairwise.threshold_pairs)
replacing the reference's host O(N^2) pair loop (reference:
src/finch.rs:53-73). On TPU this runs the Mosaic pair-stats kernel
(ops/pallas_pairwise.py); the result dict lands on host, so the timing
includes real device->host materialization.

Extra stages (reported under "stages", each guarded so one failure
never loses the line):
  * pairwise_xla — the same extraction on the XLA searchsorted path;
  * sketch_bp_per_sec — MinHash sketching on real FASTA bytes
    (the abisko4 MAGs when available; reference analog: finch
    sketch_files, src/finch.rs:47);
  * e2e — full cluster() (ingest -> sketch -> pairwise -> greedy ->
    exact ANI) on synthetic planted families.

Baseline: the SAME merged-bottom-k pair computation compiled by XLA on
the host CPU (multi-threaded) in a subprocess. There is no Rust
toolchain in this image, so the reference's compiled-Rust path cannot be
timed directly; XLA-CPU is the strongest available stand-in and is
labeled as such ("baseline" field). This replaces round 1's
single-threaded pure-Python loop, which overstated speedups.

One process per chip: this process never touches JAX. The CPU
baselines run in children pinned to the CPU; the device stages run in
one child (`bench.py --device-stages`) pinned to the TPU, and the
scripted stages after it, each in its own child, one at a time. A
device stage that finds no TPU fails — it never reports a CPU number
under a device metric. Every stage has a watchdog, and the JSON line is
always printed — with an "errors" field when something failed.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

K = 21
SKETCH_SIZE = 1000
PRODUCTION_N = 4096  # bench_production workload size, reported as n_genomes

_CPU_BASELINE_CODE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")
import time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from galah_tpu.ops.pairwise import tile_stats

n, K_, kmer = 256, %d, %d
rng = np.random.default_rng(0)
mat = rng.integers(0, 1 << 63, size=(n, K_), dtype=np.uint64)
mat.sort(axis=1)
jm = jnp.asarray(mat)
jax.block_until_ready(tile_stats(jm, jm, K_, kmer))  # compile + warm
best = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    jax.block_until_ready(tile_stats(jm, jm, K_, kmer))
    best = min(best, time.perf_counter() - t0)
print("RESULT", n * n / best)
"""

_C_BASELINE_CODE = r"""
import os, time
os.environ["JAX_PLATFORMS"] = "cpu"   # package imports must not touch
import jax                            # the chip
jax.config.update("jax_platforms", "cpu")
import numpy as np
from galah_tpu.ops._cpairstats import threshold_pairs_c

n, K_, kmer = 256, %d, %d
rng = np.random.default_rng(0)
mat = rng.integers(0, 1 << 63, size=(n, K_), dtype=np.uint64)
mat.sort(axis=1)
threshold_pairs_c(mat, K_, kmer, 0.95)  # warm
best = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    threshold_pairs_c(mat, K_, kmer, 0.95)
    best = min(best, time.perf_counter() - t0)
# Credit the C walk with the full n*n square: it decides every
# unordered pair once where the tiled passes evaluate both orders, and
# the headline uses the n*n convention — same units, conservative for
# the reported speedup.
print("RESULT", n * n / best)
"""

_CPU_PRODUCTION_CODE = r"""
import os, time
os.environ["JAX_PLATFORMS"] = "cpu"   # package imports must not touch
import jax                            # the chip
jax.config.update("jax_platforms", "cpu")
import bench
print("RESULT", bench.bench_production())
"""


class StageTimeout(Exception):
    pass


@contextlib.contextmanager
def watchdog(seconds):
    """SIGALRM guard: a wedged device call raises instead of hanging."""
    def handler(signum, frame):
        raise StageTimeout(f"stage exceeded {seconds}s")

    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(int(seconds))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def run_sub(code, timeout):
    """Run python -c `code` with a hard timeout; return RESULT float."""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=os.path.dirname(os.path.abspath(__file__)))
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            return float(line.split()[1])
    raise RuntimeError(
        f"subprocess rc={proc.returncode}: {proc.stderr[-500:]}")


def _sketches(n, sketch_size, seed):
    rng = np.random.default_rng(seed)
    mat = rng.integers(0, 1 << 63, size=(n, sketch_size), dtype=np.uint64)
    mat.sort(axis=1)
    return mat


def bench_extraction(mat, repeats=3, use_pallas=None, dense=True):
    """Headline: the dense pair-extraction kernel, pairs/s.

    `dense` pins GALAH_TPU_DENSE_PAIRS for the calls so the number
    measures the tiled kernel (Mosaic on TPU, with XLA fallback) at
    any N — above the sparse crossover the AUTO production path is the
    screened pipeline, measured separately by bench_production (on
    random sketches the screen finds ~no collisions, which would turn
    this headline into a host-sort benchmark). The dense kernel is the
    apples-to-apples comparison against the n=256 dense CPU baselines.

    threshold_pairs returns its sparse dict on host, so the timing
    inherently includes device->host materialization.
    """
    from galah_tpu.ops.pairwise import threshold_pairs

    n = mat.shape[0]
    prev = os.environ.get("GALAH_TPU_DENSE_PAIRS")
    if dense:
        os.environ["GALAH_TPU_DENSE_PAIRS"] = "1"
    try:
        threshold_pairs(mat, k=K, min_ani=0.95,
                        use_pallas=use_pallas)  # warmup + compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            pairs = threshold_pairs(mat, k=K, min_ani=0.95,
                                    use_pallas=use_pallas)
            best = min(best, time.perf_counter() - t0)
    finally:
        if dense:
            if prev is None:
                os.environ.pop("GALAH_TPU_DENSE_PAIRS", None)
            else:
                os.environ["GALAH_TPU_DENSE_PAIRS"] = prev
    assert isinstance(pairs, dict)
    return (n * n) / best


def bench_production(n=PRODUCTION_N, repeats=2):
    """The AUTO production path above the sparse crossover, pairs/s:
    host collision screen + batched device evaluation of survivors,
    on family-structured sketches (random rows share no hashes, which
    would make the screen trivially empty and the number misleading).
    """
    from galah_tpu.ops.pairwise import threshold_pairs

    rng = np.random.default_rng(5)
    n_fam = n // 4
    base = rng.integers(0, 1 << 62, size=(n_fam, SKETCH_SIZE),
                        dtype=np.uint64)
    mat = np.empty((n, SKETCH_SIZE), dtype=np.uint64)
    for i in range(n):
        row = base[i % n_fam].copy()
        n_mut = int(rng.integers(0, SKETCH_SIZE // 20))
        idx = rng.choice(SKETCH_SIZE, size=n_mut, replace=False)
        row[idx] = rng.integers(0, 1 << 62, size=n_mut, dtype=np.uint64)
        row.sort()
        mat[i] = row
    # Pin the dense override OFF: this stage must measure the sparse
    # production path even if the ambient env carries the dense knob
    # (bench_extraction pins it ON the same way).
    prev = os.environ.pop("GALAH_TPU_DENSE_PAIRS", None)
    try:
        threshold_pairs(mat, k=K, min_ani=0.95)  # warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            pairs = threshold_pairs(mat, k=K, min_ani=0.95)
            best = min(best, time.perf_counter() - t0)
    finally:
        if prev is not None:
            os.environ["GALAH_TPU_DENSE_PAIRS"] = prev
    assert len(pairs) >= n // 4, "family pairs must survive the screen"
    return (n * n) / best


def pick_n(budget_s=25.0, n_max=8192):
    """Calibrate: time a small pass, then choose the largest n whose
    projected runtime fits the budget (never blows the driver timeout)."""
    n0 = 512
    rate = bench_extraction(_sketches(n0, SKETCH_SIZE, seed=9), repeats=1)
    n = n0
    while n < n_max and (2 * n) ** 2 / rate < budget_s:
        n *= 2
    return n


def bench_genomes(count=6):
    """The shared bench corpus: first `count` abisko4 MAGs, ingested.

    Returns (genomes, total_bp); ([], 0) when the fixtures are absent.
    Single definition used by every sketching bench (bench.py stages and
    scripts/bench_sketch_variants.py).
    """
    import glob

    from galah_tpu.io.fasta import read_genome

    paths = sorted(glob.glob(
        "/root/reference/tests/data/abisko4/*.fna"))[:count]
    genomes = [read_genome(p) for p in paths]
    return genomes, sum(int(g.codes.shape[0]) for g in genomes)


def bench_sketching(algo="murmur3"):
    """MinHash sketching throughput on real FASTA bytes, bp/s."""
    from galah_tpu.ops.minhash import sketch_genome_device

    genomes, total_bp = bench_genomes()
    if not genomes:
        return None
    for g in genomes:  # compile every chunk-bucket variant
        sketch_genome_device(g, sketch_size=SKETCH_SIZE, k=K, seed=0,
                             algo=algo)
    t0 = time.perf_counter()
    acc = 0
    for g in genomes:
        s = sketch_genome_device(g, sketch_size=SKETCH_SIZE, k=K,
                                 seed=0, algo=algo)
        acc += int(s.hashes[0]) & 0xFF  # force host materialization
    dt = time.perf_counter() - t0
    assert acc >= 0
    return total_bp / dt


def bench_sketching_batch(algo="murmur3"):
    """Grouped-dispatch batch sketching throughput on real FASTA bytes."""
    from galah_tpu.ops.minhash import sketch_genomes_device_batch

    genomes, total_bp = bench_genomes()
    if not genomes:
        return None
    sketch_genomes_device_batch(genomes, sketch_size=SKETCH_SIZE, k=K,
                                seed=0, algo=algo)  # compile
    t0 = time.perf_counter()
    out = sketch_genomes_device_batch(genomes, sketch_size=SKETCH_SIZE,
                                      k=K, seed=0, algo=algo)
    dt = time.perf_counter() - t0
    assert all(s.hashes.shape[0] > 0 for s in out)
    return total_bp / dt


def _synth_families(n_genomes=48, genome_len=60_000, n_families=12,
                    mut=0.03, seed=7, outdir=None):
    """Plant n_families mutated-copy families; returns FASTA paths.

    Auto-created temp dirs are removed at process exit (unattended
    fallback runs would otherwise accumulate orphaned /tmp trees)."""
    import atexit
    import shutil
    import tempfile

    rng = np.random.default_rng(seed)
    if outdir is None:
        outdir = tempfile.mkdtemp(prefix="galah_bench_")
        atexit.register(shutil.rmtree, outdir, ignore_errors=True)
    alphabet = np.frombuffer(b"ACGT", dtype=np.uint8)
    paths = []
    per = n_genomes // n_families
    for f in range(n_families):
        base = rng.integers(0, 4, size=genome_len)
        for m in range(per):
            seq = base.copy()
            if m > 0:
                sites = rng.random(genome_len) < mut
                seq[sites] = (seq[sites] + rng.integers(
                    1, 4, size=int(sites.sum()))) % 4
            p = os.path.join(outdir, f"fam{f}_m{m}.fna")
            with open(p, "wb") as fh:
                fh.write(b">contig1\n")
                fh.write(alphabet[seq].tobytes())
                fh.write(b"\n")
            paths.append(p)
    return paths


def _synth_repeat_genomes(n_genomes=64, genome_len=100_000,
                          repeat_frac=0.3, n_elements=8,
                          element_len=2000, seed=23, outdir=None):
    """UNRELATED genomes sharing mobile-element-like repeat content —
    the collision screen's adversarial case (uniform-random rungs are
    its best case). Every genome is an independent random backbone
    with ~repeat_frac of its length replaced by elements drawn from
    ONE shared pool of n_elements sequences (element_len bp each), at
    random positions. Genomes therefore share k-mers (the screen sees
    collisions) without sharing ancestry (true ANI across genomes is
    driven by the repeat fraction alone). Returns FASTA paths.
    """
    import atexit
    import shutil
    import tempfile

    rng = np.random.default_rng(seed)
    if outdir is None:
        outdir = tempfile.mkdtemp(prefix="galah_repeat_")
        atexit.register(shutil.rmtree, outdir, ignore_errors=True)
    alphabet = np.frombuffer(b"ACGT", dtype=np.uint8)
    pool = [rng.integers(0, 4, size=element_len)
            for _ in range(n_elements)]
    n_ins = max(int(round(genome_len * repeat_frac / element_len)), 0)
    paths = []
    for g in range(n_genomes):
        backbone_len = genome_len - n_ins * element_len
        backbone = rng.integers(0, 4, size=max(backbone_len, 0))
        # splice elements between backbone chunks at random cut points
        cuts = np.sort(rng.integers(0, max(backbone.shape[0], 1),
                                    size=n_ins))
        parts, prev = [], 0
        for c, e in zip(cuts, rng.integers(0, n_elements, size=n_ins)):
            parts.append(backbone[prev:c])
            parts.append(pool[int(e)])
            prev = c
        parts.append(backbone[prev:])
        seq = np.concatenate(parts) if parts else backbone
        p = os.path.join(outdir, f"rep{g}.fna")
        with open(p, "wb") as fh:
            fh.write(b">contig1\n")
            fh.write(alphabet[seq].tobytes())
            fh.write(b"\n")
        paths.append(p)
    return paths


def bench_e2e(fast=False, paths=None):
    """Full cluster() wall-clock on planted families -> genomes/s.

    With `fast`, runs the validated fast mode (--hash-algorithm tpufast
    --ani-subsample 16), which reproduces the dense goldens on the
    18-MAG campaign (tests/test_campaign_abisko18.py).
    """
    from galah_tpu.api import generate_galah_clusterer

    paths = paths or _synth_families()
    values = {"ani": 95.0, "precluster_ani": 90.0,
              "min_aligned_fraction": 15.0, "fragment_length": 3000,
              "precluster_method": "finch", "cluster_method": "skani",
              "threads": 1}
    if fast:
        values.update(hash_algorithm="tpufast", ani_subsample=16)
    t0 = time.perf_counter()
    clusterer = generate_galah_clusterer(paths, values)
    clusters = clusterer.cluster()
    dt = time.perf_counter() - t0
    assert 1 <= len(clusters) <= len(paths)
    return len(paths) / dt, len(clusters), paths


_T0 = time.monotonic()
# Self-budgeting against GALAH_BENCH_STAGE_CAP: a run killed from
# outside loses EVERY stage's data, so each optional stage is admitted
# only if its WORST-CASE watchdog cost fits in the remaining budget,
# and the JSON line always prints.
_STAGE_CAP_S = float(os.environ.get("GALAH_BENCH_STAGE_CAP", 3000))
_HEADROOM_S = 60  # JSON print + interpreter teardown margin


def _remaining() -> float:
    return _STAGE_CAP_S - _HEADROOM_S - (time.monotonic() - _T0)


def _admit(cost_s, label, errors) -> bool:
    """True iff a stage whose watchdog allows up to cost_s seconds
    still fits; records the skip otherwise."""
    rem = _remaining()
    if rem < cost_s:
        errors.append(f"{label}: skipped, worst-case {cost_s:.0f}s "
                      f"> {rem:.0f}s remaining budget")
        return False
    return True


def _admitted_watchdog(cost_s, label, errors):
    """One cost figure drives BOTH the admission check and the
    watchdog, so the two cannot drift apart: returns a watchdog
    context for cost_s, or None when the stage does not fit (the
    skip is recorded)."""
    if not _admit(cost_s, label, errors):
        return None
    return watchdog(cost_s)


def _run_pairlist_variants_stage(stages, errors):
    """Per-strategy pairlist throughput + per-term cost breakdown in a
    subprocess (scripts/bench_pairlist_variants.py). The script is
    self-budgeting under the cost we pass, and the subprocess timeout
    adds slack for interpreter startup — a wedge mid-variant cannot
    take down the bench line."""
    _PAIRLIST_COST = 300   # hard <=5 min cap
    if not _admit(_PAIRLIST_COST, "pairlist_variants", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        cmd = [sys.executable,
               os.path.join(here, "scripts",
                            "bench_pairlist_variants.py"),
               "--budget", str(_PAIRLIST_COST - 30)]
        proc = subprocess.run(
            cmd, capture_output=True, text=True,
            timeout=_PAIRLIST_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("PAIRLIST_JSON "):
                data = json.loads(line[len("PAIRLIST_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["pairlist_variants"] = data
    except Exception as e:  # noqa: BLE001
        errors.append(f"pairlist_variants: {type(e).__name__}: {e}")


def _run_fragment_variants_stage(stages, errors):
    """Per-strategy fragment-ANI throughput + packing-waste breakdown
    in a subprocess (scripts/bench_fragment_variants.py) — the
    exact-stage twin of the pairlist matrix: pallas pack sweep with
    launch/occupancy counters, the xla and C paths on the same pair
    list, and the bare-kernel amortized dispatch cost. Same isolation
    rationale: self-budgeting script, subprocess timeout."""
    _FRAGMENT_COST = 300   # hard <=5 min cap
    if not _admit(_FRAGMENT_COST, "fragment_variants", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        cmd = [sys.executable,
               os.path.join(here, "scripts",
                            "bench_fragment_variants.py"),
               "--budget", str(_FRAGMENT_COST - 30)]
        proc = subprocess.run(
            cmd, capture_output=True, text=True,
            timeout=_FRAGMENT_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("FRAGMENT_JSON "):
                data = json.loads(line[len("FRAGMENT_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["fragment_variants"] = data
    except Exception as e:  # noqa: BLE001
        errors.append(f"fragment_variants: {type(e).__name__}: {e}")


def _run_engine_rounds_stage(stages, errors):
    """Host-vs-device greedy-selection throughput on the e2e_1000 rung
    in a subprocess (scripts/bench_engine_rounds.py): the same planted-
    family workload run once with GALAH_TPU_GREEDY_STRATEGY=host and
    once with the round-based device fold, with a cluster-parity check
    and the round/conflict/fallback counters in the payload. Same
    isolation rationale as the variant matrices: self-budgeting script,
    subprocess timeout."""
    _ROUNDS_COST = 600
    if not _admit(_ROUNDS_COST, "engine_rounds", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_engine_rounds.py"),
             "--budget", str(_ROUNDS_COST - 30)],
            capture_output=True, text=True,
            timeout=_ROUNDS_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("ENGINE_ROUNDS_JSON "):
                data = json.loads(line[len("ENGINE_ROUNDS_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["engine_rounds"] = data
        # Flatten the verdict numbers (rates + round/conflict/fallback
        # counters) to scalar stages so _finalize_obs mirrors them into
        # run_report.json gauges alongside the ladder rungs.
        for k in ("device_genomes_per_sec", "host_genomes_per_sec",
                  "speedup"):
            if isinstance(data.get(k), (int, float)):
                stages[f"engine_rounds_{k}"] = data[k]
        for k, v in (data.get("counters") or {}).items():
            stages[f"engine_rounds_{k}"] = v
    except Exception as e:  # noqa: BLE001
        errors.append(f"engine_rounds: {type(e).__name__}: {e}")


def _run_e2e_overlap_stage(stages, errors):
    """Stage-serial vs fully overlapped dataflow on the e2e_1000 rung
    in a subprocess (scripts/bench_overlap.py): the same planted-
    family workload run once with GALAH_TPU_OVERLAP=0 (four sequential
    drains) and once with the fused sketch -> pair-screen ->
    speculative fragment-ANI -> eager greedy pipeline, with a cluster-
    parity check, the overlap counters, and the per-stage
    workload.pipeline_occupancy gauges in the payload. Same isolation
    rationale as the variant matrices: self-budgeting script,
    subprocess timeout."""
    _OVERLAP_COST = 600
    if not _admit(_OVERLAP_COST, "e2e_overlap", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_overlap.py"),
             "--budget", str(_OVERLAP_COST - 30)],
            capture_output=True, text=True,
            timeout=_OVERLAP_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("OVERLAP_JSON "):
                data = json.loads(line[len("OVERLAP_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["e2e_overlap"] = data
        # Flatten the verdict numbers (rates, speedup, occupancy) to
        # scalar stages so _finalize_obs mirrors them into
        # run_report.json gauges alongside the ladder rungs.
        one_core = isinstance(data.get("host_cores"), int) \
            and data["host_cores"] <= 1
        for k in ("overlapped_genomes_per_sec",
                  "serial_genomes_per_sec", "speedup", "host_cores"):
            # A 1-core host caps the overlap at ~1x by construction:
            # keep its speedup out of the flattened gauges so the
            # perf ledger never bands a capacity ceiling as a
            # regression (the nested payload still carries it).
            if k == "speedup" and one_core:
                continue
            if isinstance(data.get(k), (int, float)):
                stages[f"e2e_overlap_{k}"] = data[k]
        for stage_name, v in (data.get("occupancy") or {}).items():
            stages[f"e2e_overlap_occupancy_{stage_name}"] = v
        for k, v in (data.get("counters") or {}).items():
            stages[f"e2e_overlap_{k}"] = v
        # critical-path blame shares -> bench.flow_* gauges, so a
        # migrated bottleneck shows in the ledger like any perf drift
        flow = data.get("flow") or {}
        for stage_name, v in (flow.get("shares") or {}).items():
            if isinstance(v, (int, float)):
                stages[f"flow_{stage_name}_share"] = v
    except Exception as e:  # noqa: BLE001
        errors.append(f"e2e_overlap: {type(e).__name__}: {e}")


def _run_megakernel_stage(stages, errors):
    """Fused megakernel rounds vs per-window dense folds on the e2e
    rung in a subprocess (scripts/bench_megakernel.py): the same
    overlapped workload run with GALAH_TPU_MEGAKERNEL=1 and =0, with a
    cluster-parity check, the off/mega greedy-select dispatch ratio
    (gate >= 4x), and the critical path's host-blame share for the
    megakernel run — the gauge the fused rounds exist to drive down.
    Same isolation rationale as the variant matrices: self-budgeting
    script, subprocess timeout."""
    _MEGA_COST = 900
    if not _admit(_MEGA_COST, "megakernel", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_megakernel.py"),
             "--budget", str(_MEGA_COST - 30)],
            capture_output=True, text=True,
            timeout=_MEGA_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("MEGAKERNEL_JSON "):
                data = json.loads(line[len("MEGAKERNEL_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["megakernel"] = data
        # Flatten the verdict numbers to scalar stages so
        # _finalize_obs mirrors them into run_report.json gauges
        # alongside the ladder rungs.
        one_core = isinstance(data.get("host_cores"), int) \
            and data["host_cores"] <= 1
        for k in ("mega_genomes_per_sec", "off_genomes_per_sec",
                  "speedup", "dispatch_ratio", "host_share",
                  "host_blame_s", "host_cores"):
            # Same capacity-ceiling discipline as e2e_overlap: a
            # 1-core host caps the wall-clock speedup at ~1x by
            # construction, so keep it out of the flattened gauges
            # (the nested payload still carries it). dispatch_ratio
            # and host_share stay in — they measure structure, not
            # spare-core throughput.
            if k == "speedup" and one_core:
                continue
            if isinstance(data.get(k), (int, float)) \
                    and not isinstance(data.get(k), bool):
                stages[f"megakernel_{k}"] = data[k]
        for k, v in (data.get("counters") or {}).items():
            stages[f"megakernel_{k}"] = v
    except Exception as e:  # noqa: BLE001
        errors.append(f"megakernel: {type(e).__name__}: {e}")


def _run_allpairs_scale_stage(stages, errors):
    """1-D vs 2D tiled mesh all-pairs scaling in a subprocess
    (scripts/bench_allpairs_scale.py): candidate pairs/s and the
    modeled mesh.dcn_bytes_per_row for both mesh geometries at
    N in {1k, 5k, 20k} synthetic sketch rungs (pair-set parity
    gated), plus the cardinality-band prefilter's pruned fraction.
    Same isolation rationale as the variant matrices: self-budgeting
    script, subprocess timeout."""
    _ALLPAIRS_COST = 600
    if not _admit(_ALLPAIRS_COST, "allpairs_scale", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_allpairs_scale.py"),
             "--budget", str(_ALLPAIRS_COST - 30)],
            capture_output=True, text=True,
            timeout=_ALLPAIRS_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("ALLPAIRS_JSON "):
                data = json.loads(line[len("ALLPAIRS_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["allpairs_scale"] = data
        # Flatten the per-rung verdict numbers to scalar stages so
        # _finalize_obs mirrors them into run_report.json gauges and
        # the perf ledger gates DCN-ratio / speedup / pruning drift.
        for rung in data.get("rungs") or []:
            n = rung.get("n")
            for k in ("1d_pairs_per_sec", "2d_pairs_per_sec",
                      "speedup_2d", "dcn_ratio",
                      "bucket_pruned_fraction"):
                if isinstance(rung.get(k), (int, float)):
                    stages[f"allpairs_n{n}_{k}"] = rung[k]
    except Exception as e:  # noqa: BLE001
        errors.append(f"allpairs_scale: {type(e).__name__}: {e}")


def _run_ingest_variants_stage(stages, errors):
    """Storage-bound ingest->sketch matrix in a subprocess
    (scripts/bench_ingest.py --variants): end-to-end Mbp/s by
    strategy x workers x gzip over a >= 1 Gbp multi-file corpus,
    against the serial-prologue baseline (read everything, then one
    batched sketch pass — the pre-streaming pipeline shape), with the
    host/device cost split. The headline scalars are flattened into
    stages so _finalize_obs mirrors them into bench.* gauges and the
    perf ledger gates ingest-rate regressions. Same isolation
    rationale as the other matrices: self-budgeting script,
    subprocess timeout; the corpus is CPU-pinned host work either
    way."""
    _INGEST_COST = 420
    if not _admit(_INGEST_COST, "ingest_variants", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_ingest.py"),
             "--variants", "--budget", str(_INGEST_COST - 90)],
            capture_output=True, text=True,
            timeout=_INGEST_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("INGEST_JSON "):
                data = json.loads(line[len("INGEST_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["ingest_variants"] = data
        for k in ("overlapped_mbp_s", "serial_prologue_mbp_s",
                  "speedup_vs_serial"):
            if isinstance(data.get(k), (int, float)):
                stages[f"ingest_{k}"] = data[k]
    except Exception as e:  # noqa: BLE001
        errors.append(f"ingest_variants: {type(e).__name__}: {e}")


def _run_ingest_tiered_stage(stages, errors):
    """Out-of-core sketch tier vs all-resident in a subprocess
    (scripts/bench_ingest_tiered.py): peak-RSS delta and ingest rate
    at N in {1k, 20k, 100k} synthetic genomes, paged band walk vs the
    resident matrix, pair-dict parity gated per rung. The headline
    ``pagestore_*`` scalars flatten into stages so _finalize_obs
    mirrors them into bench.pagestore_* gauges and the perf ledger
    gates the RSS bound (paged/resident delta ratio; the tentpole's
    acceptance is <= 1/8 at the 100k rung) and the paged ingest rate.
    Self-budgeting script, subprocess timeout, host-side work — as
    real on the cpu-fallback branch as on the device one."""
    _TIERED_COST = 480
    if not _admit(_TIERED_COST, "ingest_tiered", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_ingest_tiered.py"),
             "--budget", str(_TIERED_COST - 60)],
            capture_output=True, text=True,
            timeout=_TIERED_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("TIERED_JSON "):
                data = json.loads(line[len("TIERED_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["ingest_tiered"] = data
        for k in ("pagestore_delta_rss_ratio",
                  "pagestore_paged_genomes_per_sec",
                  "pagestore_resident_genomes_per_sec",
                  "pagestore_page_ins", "pagestore_page_outs",
                  "pagestore_parity_ok"):
            if isinstance(data.get(k), (int, float)):
                stages[k] = data[k]
        if not data.get("parity_ok", False):
            errors.append("ingest_tiered: paged pair dict diverged "
                          "from the all-resident pass")
    except Exception as e:  # noqa: BLE001
        errors.append(f"ingest_tiered: {type(e).__name__}: {e}")


def _run_index_stage(stages, errors):
    """Incremental-index service numbers in a subprocess
    (scripts/bench_index.py): build the persistent index once over
    90% of a planted-family corpus, then time the two operations the
    subsystem exists for — insert of the remaining 10% (genomes/s,
    plus the sketch.minhash_computed delta proving only the new
    genomes were resketched) and the warm per-genome query sweep
    (p50/p95 ms; acceptance is warm p50 < 50 ms on CPU). Headline
    scalars flatten into stages so _finalize_obs mirrors them into
    bench.* gauges; workload.index_* gauges fingerprint the corpus so
    the perf ledger only compares like-sized index runs."""
    _INDEX_COST = 240
    if not _admit(_INDEX_COST, "index_service", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_index.py"),
             "--budget", str(_INDEX_COST - 60)],
            capture_output=True, text=True,
            timeout=_INDEX_COST, cwd=here)
        data = None
        for line in proc.stdout.splitlines():
            if line.startswith("INDEX_JSON "):
                data = json.loads(line[len("INDEX_JSON "):])
        if data is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["index_service"] = data
        for k in ("build_genomes_per_sec", "insert_genomes_per_sec",
                  "insert_resketched", "query_p50_ms", "query_p95_ms"):
            if isinstance(data.get(k), (int, float)):
                stages[f"index_{k}"] = data[k]
        from galah_tpu import obs

        for k, hlp in (("n_genomes", "Index bench corpus size"),
                       ("n_insert", "Index bench insert-slice size")):
            if isinstance(data.get(k), (int, float)):
                obs.metrics.gauge(
                    f"workload.index_{k}", help=hlp).set(float(data[k]))
    except Exception as e:  # noqa: BLE001
        errors.append(f"index_service: {type(e).__name__}: {e}")


def _run_fleet_stage(stages, errors):
    """Elastic-fleet supervisor scaling (galah_tpu/fleet/): the same
    planted-family corpus through `galah-tpu fleet run` — 3 shards
    across 2 preemptible worker subprocesses plus the cross-shard
    merge — vs ONE single-process `cluster` run. Emits fleet
    genomes/s, the fleet/single wall ratio (worker-interpreter spinup
    + supervision + merge overhead), and the merge wall clock, and
    asserts the byte-identity contract on the way: a throughput
    number for a divergent answer is not a number."""
    _FLEET_COST = 480
    if not _admit(_FLEET_COST, "fleet_scale", errors):
        return
    import shutil
    import tempfile

    try:
        here = os.path.dirname(os.path.abspath(__file__))
        work = tempfile.mkdtemp(prefix="galah_fleetbench_")
        try:
            gdir = os.path.join(work, "genomes")
            os.makedirs(gdir, exist_ok=True)
            paths = _synth_families(n_genomes=24, genome_len=40_000,
                                    n_families=6, mut=0.03, seed=13,
                                    outdir=gdir)
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            # shared profile cache: shard profiling warms what the
            # merge's cross-shard pass reuses, like a real deployment
            env["GALAH_TPU_CACHE"] = os.path.join(work, "cache")
            base = [sys.executable, "-m", "galah_tpu.cli"]
            common = ["--genome-fasta-files", *paths,
                      "--precluster-method", "skani",
                      "--cluster-method", "skani"]
            single_tsv = os.path.join(work, "single.tsv")
            t0 = time.perf_counter()
            proc = subprocess.run(
                base + ["cluster", "--platform", "cpu", *common,
                        "--output-cluster-definition", single_tsv],
                capture_output=True, text=True,
                timeout=_FLEET_COST // 2, cwd=here, env=env)
            single_s = time.perf_counter() - t0
            if proc.returncode != 0:
                raise RuntimeError(f"single-process run rc="
                                   f"{proc.returncode}: "
                                   f"{proc.stderr[-300:]}")
            fleet_tsv = os.path.join(work, "fleet.tsv")
            report = os.path.join(work, "fleet_report.json")
            t0 = time.perf_counter()
            proc = subprocess.run(
                base + ["fleet", "--platform", "cpu", "run", *common,
                        "--fleet-dir", os.path.join(work, "fleet"),
                        "--workers", "2", "--shards", "3",
                        "--output-cluster-definition", fleet_tsv,
                        "--run-report", report],
                capture_output=True, text=True,
                timeout=_FLEET_COST // 2, cwd=here, env=env)
            fleet_s = time.perf_counter() - t0
            if proc.returncode != 0:
                raise RuntimeError(f"fleet run rc={proc.returncode}: "
                                   f"{proc.stderr[-300:]}")
            with open(single_tsv, "rb") as f:
                single_bytes = f.read()
            with open(fleet_tsv, "rb") as f:
                if f.read() != single_bytes:
                    raise RuntimeError(
                        "fleet clusters differ from the "
                        "single-process run")
            stages["fleet_genomes_per_sec"] = round(
                len(paths) / fleet_s, 2)
            stages["fleet_vs_single_wall"] = round(fleet_s / single_s,
                                                   2)
            with open(report) as f:
                rep = json.load(f)
            fl = rep.get("fleet") or {}
            if isinstance(fl.get("merge_wall_s"), (int, float)):
                stages["fleet_merge_wall_s"] = round(
                    fl["merge_wall_s"], 3)
            from galah_tpu import obs

            for k, hlp in (("n_shards", "Fleet bench shard count"),
                           ("workers", "Fleet bench worker cap")):
                if isinstance(fl.get(k), (int, float)):
                    obs.metrics.gauge(
                        f"workload.fleet_{k}",
                        help=hlp).set(float(fl[k]))
            # Fleet critical path (v9 fleet_rollup): flatten the blame
            # decomposition into bench gauges so the driver artifact —
            # and the report --diff between sessions — carries where
            # the fleet wall went (scheduler vs compute vs straggler
            # wait vs merge), not just its total.
            ru = rep.get("fleet_rollup") or {}
            if isinstance(ru.get("fleet_wall_s"), (int, float)):
                obs.metrics.gauge(
                    "bench.fleet_wall_s",
                    unit="s", help="Fleet bench wall from the rollup"
                ).set(float(ru["fleet_wall_s"]))
            for comp, c in sorted((ru.get("components") or {}).items()):
                if not isinstance(c, dict):
                    continue
                if isinstance(c.get("blame_s"), (int, float)):
                    obs.metrics.gauge(
                        f"bench.fleet_{comp}_blame_s", unit="s",
                        help=f"Fleet wall blamed on {comp}"
                    ).set(float(c["blame_s"]))
                if isinstance(c.get("share"), (int, float)):
                    obs.metrics.gauge(
                        f"bench.fleet_{comp}_share",
                        help=f"Share of the fleet wall blamed on "
                             f"{comp}").set(float(c["share"]))
            if ru.get("bottleneck"):
                stages["fleet_bottleneck"] = ru["bottleneck"]
        finally:
            shutil.rmtree(work, ignore_errors=True)
    except Exception as e:  # noqa: BLE001
        errors.append(f"fleet_scale: {type(e).__name__}: {e}")


def run_ladder_stages(stages, errors):
    """North-star-relevant e2e evidence in the driver artifact itself.

    Two rungs, each with a sibling `_workload` key stating exactly what
    the number means (the workload shape changes the number more than
    the code does, so the artifact must say what was run):

      * e2e_1000_genomes_per_sec — cluster() on 1000 synthetic genomes
        with planted family structure (250 families x 4 members, 3%
        mutation, 100 kbp) at the DEFAULT config (murmur3 hashes,
        finch-style precluster + skani-style cluster), inside the
        bench artifact.
      * mega_256_genomes_per_sec — the dense-similarity worst case the
        reference advertises ("many closely related genomes >95% ANI",
        reference: README.md:18-26): ONE planted family of 256, every
        pair ~96% ANI, through the default skani+skani path. Nothing
        screens out; the exact-ANI stage does all-pairs work.

    Runs on whatever backend the caller already initialized (device or
    pinned CPU) — the JSON's `backend` field disambiguates.
    """
    from galah_tpu.api import generate_galah_clusterer

    def run_one(key, paths, values, workload):
        t0 = time.perf_counter()
        clusterer = generate_galah_clusterer(paths, values)
        clusters = clusterer.cluster()
        dt = time.perf_counter() - t0
        stages[key + "_genomes_per_sec"] = round(len(paths) / dt, 2)
        stages[key + "_n_clusters"] = len(clusters)
        stages[key + "_workload"] = workload

    base = {"ani": 95.0, "precluster_ani": 90.0,
            "min_aligned_fraction": 15.0, "fragment_length": 3000,
            "precluster_method": "finch", "cluster_method": "skani",
            "threads": 1}
    wd = _admitted_watchdog(900, "e2e_1000", errors)
    if wd:
        try:
            with wd:
                paths = _synth_families(
                    n_genomes=1000, genome_len=100_000,
                    n_families=250, mut=0.03, seed=11)
                run_one("e2e_1000", paths, dict(base),
                        "1000 synthetic genomes, 250 planted families "
                        "x4, 3% mutation, 100 kbp, default murmur3 "
                        "finch+skani")
        except Exception as e:  # noqa: BLE001
            errors.append(f"e2e_1000: {type(e).__name__}: {e}")
    wd = _admitted_watchdog(900, "mega_256", errors)
    if not wd:
        return
    try:
        with wd:
            paths = _synth_families(n_genomes=256, genome_len=100_000,
                                    n_families=1, mut=0.02, seed=11)
            mega = dict(base)
            mega.update(precluster_method="skani",
                        cluster_method="skani")
            run_one("mega_256", paths, mega,
                    "dense worst case: ONE planted family of 256, "
                    "every pair ~96% ANI, 100 kbp, default skani+skani "
                    "(nothing screens out)")
    except Exception as e:  # noqa: BLE001
        errors.append(f"mega_256: {type(e).__name__}: {e}")


def _finalize_obs(result, started_at):
    """Mirror the bench line into the metrics registry and, when
    GALAH_OBS_REPORT is set, write the same end-of-run run_report.json
    a cluster run produces (docs/observability.md) — so bench numbers
    are diffable with `galah-tpu report --diff` across captures.
    Telemetry must never lose the bench line: failures append to the
    errors field instead of raising."""
    try:
        from galah_tpu import obs
        from galah_tpu.config import env_value

        obs.metrics.gauge(
            "bench." + result["metric"],
            help="Headline bench metric",
            unit=result.get("unit", "")).set(result["value"])
        # Workload fingerprint gauges: the perf ledger keys cross-run
        # comparison on (N, K), so bench history only compares like
        # workloads (obs/ledger.py workload_fingerprint).
        obs.metrics.gauge(
            "workload.n_genomes",
            help="Bench production workload size").set(
            float(result.get("n_genomes", PRODUCTION_N)))
        obs.metrics.gauge(
            "workload.sketch_k",
            help="Bench sketch size").set(float(SKETCH_SIZE))
        if result.get("vs_baseline") is not None:
            obs.metrics.gauge(
                "bench.vs_baseline",
                help="Headline metric over the CPU stand-in "
                     "baseline").set(result["vs_baseline"])
        for name, val in result.get("stages", {}).items():
            if isinstance(val, (int, float)) and not isinstance(
                    val, bool):
                obs.metrics.gauge(f"bench.{name}").set(val)
        obs.metrics.counter(
            "bench.errors",
            help="Bench stages that failed").inc(
            len(result.get("errors", [])))
        report_path = env_value("GALAH_OBS_REPORT") or None
        obs.finalize("bench", report_path=report_path,
                     started_at=started_at)
    except Exception as e:  # noqa: BLE001
        result.setdefault("errors", []).append(
            f"obs: {type(e).__name__}: {e}")


def _run_amortized_stage(stages, errors):
    """Amortized ON-CHIP kernel throughput (device-resident inputs,
    fori_loop repeats inside one dispatch): the measurement that
    separates kernel speed from dispatch/transfer."""
    _AMORT_COST = 900
    if not _admit(_AMORT_COST, "amortized", errors):
        return
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "bench_amortized.py"),
             "--fast"],
            capture_output=True, text=True, timeout=_AMORT_COST,
            cwd=here)
        amort = None
        for line in proc.stdout.splitlines():
            if line.startswith("AMORTIZED_JSON "):
                amort = json.loads(line[len("AMORTIZED_JSON "):])
        if amort is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        stages["amortized_on_chip"] = amort
    except Exception as e:  # noqa: BLE001
        errors.append(f"amortized: {type(e).__name__}: {e}")


def device_stages():
    """The in-process device stages, run in the one child that holds
    the chip; prints DEVICE_JSON {value, backend, device, stages,
    errors}."""
    out = {"stages": {}, "errors": []}
    stages, errors = out["stages"], out["errors"]
    import jax

    devs = jax.devices()
    out["backend"] = devs[0].platform
    out["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs)}
    if devs[0].platform != "tpu":
        errors.append(f"device stages need a TPU, got {out['device']}")
        print("DEVICE_JSON " + json.dumps(out))
        return

    # Headline: the AUTO production pairwise path (host collision
    # screen + batched Mosaic pairlist survivors on device) on
    # family-structured sketches.
    try:
        with watchdog(300):
            out["value"] = round(bench_production(), 1)
    except Exception as e:  # noqa: BLE001
        errors.append(f"production_sparse: {type(e).__name__}: {e}")

    # The dense Mosaic pair-stats kernel at a size fit to the budget —
    # apples-to-apples against the dense CPU baselines.
    try:
        with watchdog(300):
            env_n = os.environ.get("GALAH_BENCH_N")
            n = int(env_n) if env_n else pick_n()
            stages["dense_kernel_n_genomes"] = n
            mat = _sketches(n, SKETCH_SIZE, seed=0)
            stages["dense_kernel_pairs_per_sec"] = round(
                bench_extraction(mat), 1)
    except Exception as e:  # noqa: BLE001
        errors.append(
            f"pairwise_pallas: {type(e).__name__}: {e}")

    # The XLA searchsorted path on a smaller tile, for the record.
    try:
        with watchdog(240):
            mat = _sketches(512, SKETCH_SIZE, seed=0)
            stages["pairwise_xla_pairs_per_sec"] = round(
                bench_extraction(mat, repeats=1, use_pallas=False), 1)
    except Exception as e:  # noqa: BLE001
        errors.append(f"pairwise_xla: {type(e).__name__}: {e}")

    # North-star ladder rungs (N=1000 e2e + dense mega regime).
    run_ladder_stages(stages, errors)

    # Sketching throughput on real FASTA bytes, both hash algos — each
    # with its own watchdog so one failure never loses the other. The
    # budget covers compiling every chunk-bucket variant cold.
    for algo, key in (("murmur3", "sketch_bp_per_sec"),
                      ("tpufast", "sketch_tpufast_bp_per_sec")):
        wd = _admitted_watchdog(600, f"sketching-{algo}", errors)
        if not wd:
            continue
        try:
            with wd:
                bps = bench_sketching(algo)
                if bps:
                    stages[key] = round(bps, 1)
        except Exception as e:  # noqa: BLE001
            errors.append(f"sketching-{algo}: {type(e).__name__}: {e}")
    wd = _admitted_watchdog(600, "sketching-batch", errors)
    if wd:
        try:
            with wd:
                bps = bench_sketching_batch("murmur3")
                if bps:
                    stages["sketch_batch_bp_per_sec"] = round(bps, 1)
        except Exception as e:  # noqa: BLE001
            errors.append(f"sketching-batch: {type(e).__name__}: {e}")

    # End-to-end cluster() on planted families, default and fast mode
    # (each with its own watchdog).
    paths = None
    wd = _admitted_watchdog(300, "e2e", errors)
    if wd:
        try:
            with wd:
                gps, n_clusters, paths = bench_e2e()
                stages["e2e_genomes_per_sec"] = round(gps, 2)
                stages["e2e_n_clusters"] = n_clusters
        except Exception as e:  # noqa: BLE001
            errors.append(f"e2e: {type(e).__name__}: {e}")
    wd = _admitted_watchdog(300, "e2e-fast", errors)
    if wd:
        try:
            with wd:
                gps, n_clusters, _ = bench_e2e(fast=True, paths=paths)
                stages["e2e_fast_genomes_per_sec"] = round(gps, 2)
                stages["e2e_fast_n_clusters"] = n_clusters
        except Exception as e:  # noqa: BLE001
            errors.append(f"e2e-fast: {type(e).__name__}: {e}")
    print("DEVICE_JSON " + json.dumps(out))


def main():
    started_at = time.time()
    result = {
        "metric": "production_pairwise_genome_pairs_per_sec",
        "value": 0.0,
        "unit": "pairs/s",
        "vs_baseline": None,
        "baseline": "strongest of xla-cpu-multicore tile_stats and the "
                    "compiled-C dense merged walk (csrc/pairstats.c) "
                    "over the same all-pairs workload — the stand-ins "
                    "for the reference's compiled dense pair loop "
                    "(src/finch.rs:53-73; no rustc in image). The "
                    "headline is the AUTO production path (host "
                    "collision screen + batched device survivors) on "
                    "family-structured sketches; stages record the "
                    "dense Mosaic kernel apples-to-apples against the "
                    "dense baselines AND this framework's own screened "
                    "CPU path (cpu_production_pairs_per_sec).",
        "stages": {},
        "errors": [],
    }
    stages = result["stages"]
    errors = result["errors"]
    # Children that do not pin a platform themselves get the chip or
    # fail; the CPU baselines below pin the CPU in their own code.
    os.environ.setdefault("JAX_PLATFORMS", "tpu")

    # 1. CPU baselines in subprocesses: the XLA-CPU tiled pass AND the
    # compiled-C merged-bottom-k walk (csrc/pairstats.c, the closest
    # stand-in for the reference's compiled Rust loop). The stronger
    # one becomes the baseline.
    cpu_pps = None
    try:
        xla_pps = run_sub(_CPU_BASELINE_CODE % (SKETCH_SIZE, K),
                          timeout=300)
        stages["cpu_xla_baseline_pairs_per_sec"] = round(xla_pps, 1)
        cpu_pps = xla_pps
    except Exception as e:  # noqa: BLE001
        errors.append(f"cpu_baseline: {type(e).__name__}: {e}")
    try:
        c_pps = run_sub(_C_BASELINE_CODE % (SKETCH_SIZE, K),
                        timeout=300)
        stages["cpu_c_baseline_pairs_per_sec"] = round(c_pps, 1)
        cpu_pps = max(cpu_pps or 0.0, c_pps)
    except Exception as e:  # noqa: BLE001
        errors.append(f"c_baseline: {type(e).__name__}: {e}")
    if cpu_pps:
        stages["cpu_baseline_pairs_per_sec"] = round(cpu_pps, 1)
    # This framework's own screened CPU path on the headline workload —
    # not the vs_baseline denominator (that is the reference stand-in),
    # but required for an honest single-chip-vs-this-box comparison.
    try:
        cpu_prod = run_sub(_CPU_PRODUCTION_CODE, timeout=300)
        stages["cpu_production_pairs_per_sec"] = round(cpu_prod, 1)
    except Exception as e:  # noqa: BLE001
        errors.append(f"cpu_production: {type(e).__name__}: {e}")

    # 2. The in-process device stages, in the one child on the chip.
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--device-stages"], capture_output=True, text=True,
            timeout=max(60.0, _remaining()),
            cwd=os.path.dirname(os.path.abspath(__file__)))
        dev = None
        for line in proc.stdout.splitlines():
            if line.startswith("DEVICE_JSON "):
                dev = json.loads(line[len("DEVICE_JSON "):])
        if dev is None:
            raise RuntimeError(
                f"rc={proc.returncode}: {proc.stderr[-400:]}")
        result["backend"] = dev.get("backend")
        result["device"] = dev.get("device")
        stages.update(dev["stages"])
        errors.extend(dev["errors"])
        if dev.get("value"):
            result["value"] = dev["value"]
            result["n_genomes"] = PRODUCTION_N
            if cpu_pps:
                result["vs_baseline"] = round(dev["value"] / cpu_pps, 2)
    except Exception as e:  # noqa: BLE001
        errors.append(f"device_stages: {type(e).__name__}: {e}")

    # 3. The scripted stages, each in its own child, one at a time:
    # engine rounds, stage-serial vs overlapped dataflow, fused
    # megakernel rounds, 1-D vs 2D mesh scaling, amortized on-chip
    # kernels, the pairlist and fragment strategy matrices, the
    # ingest->sketch matrices, the index service, and the elastic
    # fleet (byte-identity asserted, supervision overhead recorded).
    for stage in (_run_engine_rounds_stage, _run_e2e_overlap_stage,
                  _run_megakernel_stage, _run_allpairs_scale_stage,
                  _run_amortized_stage, _run_pairlist_variants_stage,
                  _run_fragment_variants_stage,
                  _run_ingest_variants_stage, _run_ingest_tiered_stage,
                  _run_index_stage, _run_fleet_stage):
        stage(stages, errors)

    _finalize_obs(result, started_at)
    print(json.dumps(result))


if __name__ == "__main__":
    if sys.argv[1:] == ["--device-stages"]:
        device_stages()
    else:
        main()
