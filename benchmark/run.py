#!/usr/bin/env python3
"""Benchmark entry: one run of one cell of BENCHMARK.json on this machine.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

Runs from the root of a checkout. The last line of standard output is
one JSON object: correct, attempted, failed, metrics, device (with
--trace 1 also breakdown), notes, and last the numbers the check
compared, each with its limit; those numbers are also the last lines of
standard error. It exits non-zero, and prints no result, where JAX finds
no TPU or fewer chips than the cell asks for, or the program under test
is missing. See benchmark/harness.py for what a run does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

STARTED = time.monotonic()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's own logs would go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import harness

    cell = harness.resolve_cell(harness.load_manifest(), args.workload)
    if str(harness.ROOT) not in sys.path:
        sys.path.insert(0, str(harness.ROOT))
    import galah_tpu  # noqa: F401 - the system under test must be here

    counter = harness.configure_jax()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"need {cell.chips} TPU chip(s); JAX has {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), STARTED, counter)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
