"""The benchmark's own tests run on the CPU: JAX is pinned to it before
anything imports JAX, and the benchmark directory and the checkout are
importable."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH_DIR = Path(__file__).resolve().parents[1]
for p in (BENCH_DIR, BENCH_DIR.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
