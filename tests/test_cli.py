"""CLI integration tests with golden outputs.

Mirrors the reference's assert_cli suite (reference:
tests/test_cmdline.rs:8-338) through `galah_tpu.cli.main` in-process —
the fixture genomes and expected cluster compositions are the same; the
line ORDER follows this framework's deterministic precluster-size-then-
rep ordering (the reference's order is thread-timing dependent).
"""

import os
import re

import pytest

from galah_tpu.cli import main

DATA = "/root/reference/tests/data"

# The golden fixture genomes live in the reference checkout, which not
# every container bakes in. Where the data exists these tests must
# pass (strict=False only because an xpass is then the healthy state);
# where it doesn't they xfail instead of reporting 12 false failures.
needs_reference_data = pytest.mark.xfail(
    condition=not os.path.isdir(DATA),
    reason=f"reference fixture genomes not present ({DATA})",
    strict=False)


def _run(args):
    return main(args)


@needs_reference_data
def test_completeness_4contamination_quality_score(tmp_path):
    out = tmp_path / "clusters.tsv"
    rc = _run([
        "cluster", "--quality-formula", "completeness-4contamination",
        "--genome-fasta-files",
        f"{DATA}/abisko4/73.20120800_S1D.21.fna",
        f"{DATA}/abisko4/73.20110800_S2M.16.fna",
        "--precluster-method", "finch",
        "--output-cluster-definition", str(out),
        "--checkm-tab-table", f"{DATA}/abisko4/abisko4.csv",
    ])
    assert rc == 0
    assert out.read_text() == (
        f"{DATA}/abisko4/73.20120800_S1D.21.fna\t"
        f"{DATA}/abisko4/73.20120800_S1D.21.fna\n"
        f"{DATA}/abisko4/73.20120800_S1D.21.fna\t"
        f"{DATA}/abisko4/73.20110800_S2M.16.fna\n")


@needs_reference_data
def test_parks2020_reduced_quality_score(tmp_path):
    out = tmp_path / "clusters.tsv"
    rc = _run([
        "cluster", "--quality-formula", "Parks2020_reduced",
        "--genome-fasta-files",
        f"{DATA}/abisko4/73.20120800_S1D.21.fna",
        f"{DATA}/abisko4/73.20110800_S2M.16.fna",
        "--precluster-method", "finch",
        "--output-cluster-definition", str(out),
        "--checkm-tab-table", f"{DATA}/abisko4/abisko4.csv",
    ])
    assert rc == 0
    assert out.read_text() == (
        f"{DATA}/abisko4/73.20110800_S2M.16.fna\t"
        f"{DATA}/abisko4/73.20110800_S2M.16.fna\n"
        f"{DATA}/abisko4/73.20110800_S2M.16.fna\t"
        f"{DATA}/abisko4/73.20120800_S1D.21.fna\n")


@needs_reference_data
def test_output_symlink_directory(tmp_path):
    outdir = tmp_path / "reps"
    rc = _run([
        "cluster", "--quality-formula", "Parks2020_reduced",
        "--genome-fasta-files",
        f"{DATA}/set1/500kb.fna", f"{DATA}/set1/1mbp.fna",
        "--precluster-method", "finch",
        "--output-representative-fasta-directory", str(outdir),
    ])
    assert rc == 0
    link = outdir / "500kb.fna"
    assert link.is_symlink()
    assert not (outdir / "1mbp.fna").exists()


@needs_reference_data
def test_output_symlink_directory_preexisting_empty(tmp_path):
    outdir = tmp_path / "reps"
    outdir.mkdir()
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/set1/500kb.fna", f"{DATA}/set1/1mbp.fna",
        "--precluster-method", "finch",
        "--output-representative-fasta-directory", str(outdir),
    ])
    assert rc == 0
    assert (outdir / "500kb.fna").is_symlink()


@needs_reference_data
def test_output_directory_names_clash_copy(tmp_path):
    outdir = tmp_path / "reps"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/set1_name_clash/500kb.fna",
        f"{DATA}/set1/500kb.fna",
        f"{DATA}/set1/1mbp.fna",
        "--precluster-method", "finch",
        "--output-representative-fasta-directory-copy", str(outdir),
    ])
    assert rc == 0
    assert (outdir / "500kb.fna").exists()
    assert not (outdir / "500kb.fna").is_symlink()
    assert (outdir / "500kb.fna.1.fna").exists()
    assert not (outdir / "1mbp.fna").exists()


@needs_reference_data
def test_output_representative_list(tmp_path):
    out = tmp_path / "reps.txt"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/set1_name_clash/500kb.fna",
        f"{DATA}/set1/500kb.fna",
        f"{DATA}/set1/1mbp.fna",
        "--precluster-method", "finch",
        "--output-representative-list", str(out),
    ])
    assert rc == 0
    # biggest precluster first: {set1/500kb, 1mbp} then the clash genome
    assert out.read_text() == (
        f"{DATA}/set1/500kb.fna\n{DATA}/set1_name_clash/500kb.fna\n")


@needs_reference_data
def test_min_aligned_fraction(tmp_path):
    """Reference: tests/test_cmdline.rs:216-255 — 0.2 clusters the
    half-aligned pair, 0.6 splits it."""
    out = tmp_path / "reps.txt"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/set2/1mbp.fna", f"{DATA}/set2/1mbp.half_aligned.fna",
        "--min-aligned-fraction", "0.2",
        "--precluster-method", "finch",
        "--output-representative-list", str(out),
    ])
    assert rc == 0
    assert out.read_text() == f"{DATA}/set2/1mbp.fna\n"

    out2 = tmp_path / "reps2.txt"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/set2/1mbp.fna", f"{DATA}/set2/1mbp.half_aligned.fna",
        "--min-aligned-fraction", "0.6",
        "--precluster-method", "finch",
        "--output-representative-list", str(out2),
    ])
    assert rc == 0
    assert out2.read_text() == (
        f"{DATA}/set2/1mbp.fna\n{DATA}/set2/1mbp.half_aligned.fna\n")


@needs_reference_data
def test_github7_aligned_fraction_semantics(tmp_path):
    """Reference regression for galah issue #7
    (tests/test_cmdline.rs:316-338): the antonio MAG pair clusters at
    min-aligned-fraction 60."""
    out = tmp_path / "reps.txt"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/antonio_mags/BE_RX_R2_MAG52.fna",
        f"{DATA}/antonio_mags/BE_RX_R3_MAG189.fna",
        "--precluster-method", "finch",
        "--precluster-ani", "90", "--ani", "95",
        "--min-aligned-fraction", "60",
        "--output-representative-list", str(out),
    ])
    assert rc == 0
    assert out.read_text() == f"{DATA}/antonio_mags/BE_RX_R2_MAG52.fna\n"


@pytest.mark.slow
def test_skani_skani_precluster_threshold_override(tmp_path):
    """Reference: tests/test_cmdline.rs test_skani_skani_clusterer —
    with skani+skani, --precluster-ani 99 is overridden by --ani 95 and
    all four MAGs land in one cluster."""
    out = tmp_path / "clusters.tsv"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/abisko4/73.20120800_S1X.13.fna",
        f"{DATA}/abisko4/73.20120600_S2D.19.fna",
        f"{DATA}/abisko4/73.20120700_S3X.12.fna",
        f"{DATA}/abisko4/73.20110800_S2D.13.fna",
        "--precluster-method", "skani", "--cluster-method", "skani",
        "--precluster-ani", "99", "--ani", "95",
        "--output-cluster-definition", str(out),
        "--checkm-tab-table", f"{DATA}/abisko4/abisko4.csv",
    ])
    assert rc == 0
    lines = out.read_text().splitlines()
    assert len(lines) == 4
    rep = f"{DATA}/abisko4/73.20120800_S1X.13.fna"
    assert all(line.startswith(rep + "\t") for line in lines)


@needs_reference_data
def test_cluster_validate_roundtrip(tmp_path):
    clusters = tmp_path / "clusters.tsv"
    rc = _run([
        "cluster",
        "--genome-fasta-files",
        f"{DATA}/set1/500kb.fna", f"{DATA}/set1/1mbp.fna",
        "--precluster-method", "finch", "--cluster-method", "fastani",
        "--output-cluster-definition", str(clusters),
    ])
    assert rc == 0
    rc = _run([
        "cluster-validate", "--cluster-file", str(clusters),
        "--ani", "95", "--min-aligned-fraction", "20",
    ])
    assert rc == 0


def test_cluster_validate_unit_semantics(monkeypatch, tmp_path):
    """--ani 99 and --ani 0.99 both mean fraction 0.99 (PARITY.md).

    The reference parses the flag to a fraction and then multiplies by
    100 because its fastANI wrapper works in percent units (reference:
    src/cluster_validation.rs:13) — the two spellings coincide there
    too, so the CLI contract is identical; this framework simply stays
    in fractions end to end. This test pins that recorded decision.
    """
    import galah_tpu.validate as validate_mod

    clusters = tmp_path / "clusters.tsv"
    g = f"{DATA}/set1/500kb.fna"
    clusters.write_text(f"{g}\t{g}\n")

    seen = []

    def spy(cluster_file, clusterer):
        seen.append(clusterer.ani_threshold)
        return 0

    monkeypatch.setattr(validate_mod, "validate_clusters", spy)
    for spelling in ("99", "0.99"):
        rc = _run([
            "cluster-validate", "--cluster-file", str(clusters),
            "--ani", spelling, "--min-aligned-fraction", "20",
        ])
        assert rc == 0
    assert seen == [0.99, 0.99]


def test_no_genome_input_errors():
    rc = _run(["cluster", "--output-representative-list", "/dev/null"])
    assert rc == 1


def test_missing_quality_entry_clean_error(tmp_path):
    info = tmp_path / "info.csv"
    info.write_text("genome,completeness,contamination\nother,90,1\n")
    rc = _run([
        "cluster", "-f", f"{DATA}/set1/500kb.fna",
        "--genome-info", str(info),
        "--quality-formula", "completeness-4contamination",
    ])
    assert rc == 1


def _write_fraglen_pair(tmp_path):
    """Synthetic pair whose clustering flips with --fragment-length.

    Port of the reference's disabled fraglen test
    (reference: tests/test_cmdline.rs:340-382 — commented out there, so
    its exact fixture outcomes are not a pinned contract): homology
    interleaved at sub-fragment scale (3000 bp homologous + 1500 bp
    random per 4500 bp period). At --fragment-length 3000 every window
    overlaps homology (aligned fraction 1.0 -> merges at 95% ANI); at
    1000 the random stretches resolve (aligned fraction ~0.78, gated
    out by --min-aligned-fraction 80 -> two clusters).
    """
    import numpy as np

    rng = np.random.default_rng(42)
    L = 60_000
    base = rng.integers(0, 4, size=L)
    query = base.copy()
    period, rnd_len = 4500, 1500
    for start in range(0, L, period):
        s = start + period - rnd_len
        e = min(start + period, L)
        if s < L:
            query[s:e] = rng.integers(0, 4, size=e - s)
    alphabet = np.frombuffer(b"ACGT", dtype=np.uint8)
    paths = []
    for name, seq in (("seq_a.fna", base), ("seq_b.fna", query)):
        p = tmp_path / name
        with open(p, "wb") as fh:
            fh.write(b">" + name.encode() + b"\n")
            fh.write(alphabet[seq].tobytes() + b"\n")
        paths.append(str(p))
    return paths


def test_fraglen_flag_flips_clustering(tmp_path):
    a, b = _write_fraglen_pair(tmp_path)
    common = [
        "cluster", "--genome-fasta-files", a, b,
        "--precluster-method", "finch", "--cluster-method", "fastani",
        "--ani", "95", "--min-aligned-fraction", "80",
    ]

    reps_default = tmp_path / "reps_default.txt"
    rc = _run(common + ["--output-representative-list",
                        str(reps_default)])
    assert rc == 0
    assert reps_default.read_text() == f"{a}\n"  # merged: one rep

    reps_1000 = tmp_path / "reps_1000.txt"
    rc = _run(common + ["--fragment-length", "1000",
                        "--output-representative-list", str(reps_1000)])
    assert rc == 0
    assert reps_1000.read_text() == f"{a}\n{b}\n"  # gated: two reps


@needs_reference_data
def test_dist_subcommand_golden_pair(tmp_path):
    """`dist` (the reference ships this subcommand disabled, reference:
    src/main.rs:88-114): all-pairs MinHash ANI TSV, pinning the golden
    set1 sketch ANI 0.9808188 (reference: src/finch.rs:96)."""
    out = tmp_path / "dist.tsv"
    rc = _run([
        "dist", "--genome-fasta-files",
        f"{DATA}/set1/1mbp.fna", f"{DATA}/set1/500kb.fna",
        "--output", str(out),
    ])
    assert rc == 0
    lines = out.read_text().splitlines()
    assert len(lines) == 1
    a, b, ani = lines[0].split("\t")
    assert a.endswith("1mbp.fna") and b.endswith("500kb.fna")
    assert abs(float(ani) - 0.9808188) < 5e-7


@needs_reference_data
def test_dist_min_ani_filters(tmp_path):
    out = tmp_path / "dist.tsv"
    rc = _run([
        "dist", "--genome-fasta-files",
        f"{DATA}/set1/1mbp.fna", f"{DATA}/set1/500kb.fna",
        "--min-ani", "99", "--output", str(out),
    ])
    assert rc == 0
    assert out.read_text() == ""  # 0.98 < 0.99: filtered out


def test_validate_output_paths_mirrors_setup(tmp_path):
    """Non-writer validation must agree with setup_outputs case for
    case — disagreement would stall multi-host runs in the first
    collective (one process exits, the others wait on it)."""
    import pytest as _pytest

    from galah_tpu.outputs import setup_outputs, validate_output_paths

    nonempty = tmp_path / "nonempty"
    nonempty.mkdir()
    (nonempty / "x").write_text("x")
    nested = tmp_path / "a" / "b" / "c"
    filedir = tmp_path / "iamadir"
    filedir.mkdir()

    cases = [
        # (kwargs, should_fail)
        ({"representative_fasta_directory": str(nonempty)}, True),
        ({"representative_fasta_directory": str(nested)}, False),
        ({"cluster_definition": str(filedir)}, True),
        ({"cluster_definition": str(tmp_path / "missing" / "f.tsv")},
         True),
        ({"cluster_definition": str(tmp_path / "ok.tsv")}, False),
    ]
    for kwargs, should_fail in cases:
        if should_fail:
            with _pytest.raises((OSError, ValueError)):
                validate_output_paths(**kwargs)
            with _pytest.raises((OSError, ValueError)):
                setup_outputs(**kwargs)
        else:
            validate_output_paths(**kwargs)  # must not raise
            setup_outputs(**kwargs)          # and setup agrees
            # reset for repeatability of the nested-dir case
            import shutil

            if "representative_fasta_directory" in kwargs:
                shutil.rmtree(tmp_path / "a")


@needs_reference_data
def test_platform_flag_forces_backend(tmp_path):
    """--platform cpu must win over a conflicting JAX_PLATFORMS in the
    environment: the flag goes through jax.config, which the env var
    cannot override. Run in a subprocess with the test env's platform
    pins stripped."""
    import subprocess
    import sys

    out = tmp_path / "dist.tsv"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    # Pin a CONFLICTING platform so the test is not vacuous on
    # CPU-only hosts: without the flag's jax.config override, cuda
    # (absent from this image) would fail backend init; the flag
    # must beat the env pin.
    env["JAX_PLATFORMS"] = "cuda"
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", ""))
    code = (
        "import sys\n"
        "from galah_tpu.cli import main\n"
        f"rc = main(['dist', '--platform', 'cpu',\n"
        f"           '--genome-fasta-files', '{DATA}/set1/1mbp.fna',\n"
        f"           '{DATA}/set1/500kb.fna',\n"
        f"           '--output', '{out}'])\n"
        "import jax\n"
        "assert jax.default_backend() == 'cpu', jax.default_backend()\n"
        "sys.exit(rc)\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=420,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    ani = float(out.read_text().split("\t")[2])
    assert abs(ani - 0.9808188) < 5e-7


def test_platform_flag_bad_value_clean_error(tmp_path):
    """An unavailable --platform is a one-line user error, exit 1 —
    not a RuntimeError traceback at first device use."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", ""))
    code = (
        "import sys\n"
        "from galah_tpu.cli import main\n"
        f"sys.exit(main(['dist', '--platform', 'cuda',\n"
        f"               '--genome-fasta-files', '{DATA}/set1/1mbp.fna',\n"
        f"               '{DATA}/set1/500kb.fna',\n"
        f"               '--output', '{tmp_path / 'd.tsv'}']))\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=420,
                          env=env)
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-500:])
    assert "Traceback" not in proc.stderr
    assert "--platform cuda" in proc.stderr and "failed to initialize" in proc.stderr


# -- preemption / --resume -------------------------------------------
# These run on generated genomes, not the reference fixtures: the
# contract under test is the interruption protocol, not clustering.


def _tiny_genomes(tmp_path, n=4):
    import random as _random

    rng = _random.Random(7)
    paths = []
    base = [rng.choice("ACGT") for _ in range(5000)]
    for i in range(n):
        seq = list(base)
        for _ in range(i * 10):  # small divergence between genomes
            pos = rng.randrange(len(seq))
            seq[pos] = rng.choice("ACGT")
        p = tmp_path / f"g{i}.fna"
        p.write_text(">c\n" + "".join(seq) + "\n")
        paths.append(str(p))
    return paths


def test_resume_requires_checkpoint_dir(tmp_path):
    out = tmp_path / "c.tsv"
    rc = _run(["cluster", "--genome-fasta-files",
               *_tiny_genomes(tmp_path), "--resume",
               "--output-cluster-definition", str(out)])
    assert rc == 1


def test_resume_refuses_empty_checkpoint_dir(tmp_path):
    out = tmp_path / "c.tsv"
    rc = _run(["cluster", "--genome-fasta-files",
               *_tiny_genomes(tmp_path), "--resume",
               "--checkpoint-dir", str(tmp_path / "ck"),
               "--output-cluster-definition", str(out)])
    assert rc == 1  # no fingerprint to resume from


def test_preemption_exits_75_then_resume_completes(tmp_path,
                                                   monkeypatch):
    """A stop requested right after install preempts at the first safe
    boundary (exit 75, no output, interruption recorded); `--resume`
    then completes with the chain in the run report."""
    import json

    from galah_tpu.resilience import interrupt

    genomes = _tiny_genomes(tmp_path)
    out = tmp_path / "c.tsv"
    ck = tmp_path / "ck"
    report = tmp_path / "report.json"

    real_install = interrupt.install

    def install_and_stop():
        real_install()
        interrupt.request_stop("TEST")

    monkeypatch.setattr(interrupt, "install", install_and_stop)
    rc = _run(["cluster", "--genome-fasta-files", *genomes,
               "--checkpoint-dir", str(ck),
               "--output-cluster-definition", str(out),
               "--run-report", str(report)])
    assert rc == interrupt.EXIT_PREEMPTED == 75
    # preempted before write-outputs: the handle exists (setup_outputs
    # opens it up front) but no cluster rows were written
    assert not out.exists() or out.read_bytes() == b""
    rep = json.loads(report.read_text())
    assert rep["preemption"]["stop_requested"] is True
    assert rep["preemption"]["boundary"] is not None
    monkeypatch.undo()

    rc = _run(["cluster", "--genome-fasta-files", *genomes,
               "--resume", "--checkpoint-dir", str(ck),
               "--output-cluster-definition", str(out),
               "--run-report", str(report)])
    assert rc == 0
    assert out.exists()
    rep = json.loads(report.read_text())
    assert rep["preemption"]["resumed_from"] == str(ck)
    assert rep["preemption"]["prior_interruptions"] == 1

    # and the resumed output equals an uninterrupted run's
    out2 = tmp_path / "c2.tsv"
    rc = _run(["cluster", "--genome-fasta-files", *genomes,
               "--checkpoint-dir", str(tmp_path / "ck2"),
               "--output-cluster-definition", str(out2)])
    assert rc == 0
    assert out.read_bytes() == out2.read_bytes()


def test_run_logs_its_platform_first(tmp_path, capsys):
    """Every CLI run names the platform, device kind and device count
    it runs on before any work, so a run that fell back to the CPU
    says so in its first lines."""
    from galah_tpu.cli import main

    fna = tmp_path / "g.fna"
    fna.write_text(">c\n" + "ACGTTGCA" * 500 + "\n")
    rc = main(["cluster", "--platform", "cpu",
               "--genome-fasta-files", str(fna),
               "--output-cluster-definition", str(tmp_path / "out.tsv")])
    assert rc == 0
    first = capsys.readouterr().err.splitlines()[0]
    assert re.search(r"JAX platform cpu, device kind cpu, \d+ "
                     r"device\(s\)$", first), first
