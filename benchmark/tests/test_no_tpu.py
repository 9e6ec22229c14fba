"""A run that finds no TPU, or no program to test, fails and prints no
result."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "species95.families", "--seed", str(2**32 + 1), "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert "genomes_per_s" not in proc.stdout


def test_no_tpu_exits_nonzero_without_result():
    proc = _run(ROOT)
    _no_result(proc)
    assert "TPU" in proc.stderr


def test_only_the_benchmark_files_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
