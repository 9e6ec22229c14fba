#!/usr/bin/env python
"""Fleet observability gate (host CPU, no chip use).

One small sharded fleet run with the OpenMetrics textfile exporter and
a fast heartbeat enabled, then the three fleet-plane checks
(docs/observability.md "Fleet observability"):

  1. ``galah-tpu fleet analyze`` exits 0 on the completed fleet dir
     and its blame table conserves the fleet wall (components sum to
     fleet_wall_s within 1%) with a named bottleneck.
  2. ``galah-tpu top <fleet_dir> --json`` renders the per-shard grid.
  3. The ``.prom`` textfile the heartbeat exported parses under the
     Prometheus text-format parser and carries the fleet blame series.

Exits 0 on success, 1 on any failed check — the validation harness
wraps this in a soft-warn stage so a telemetry regression is reported
in the capture without discarding the remaining hardware stages.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chaos_run import fleet_argv, make_workload  # noqa: E402


def fail(msg: str) -> None:
    print(f"fleet_observe: FAIL: {msg}")
    sys.exit(1)


def check_prom(path: str) -> None:
    if not os.path.exists(path):
        fail(f"exporter never wrote {path}")
    with open(path) as f:
        page = f.read()
    try:
        from prometheus_client.parser import text_string_to_metric_families
    except ImportError:
        # Degraded check: format shape only (the tests carry the real
        # parser gate; this host just lacks prometheus_client).
        if "# TYPE galah_fleet_wall_seconds gauge" not in page:
            fail("no galah_fleet_wall_seconds TYPE line in .prom")
        print("fleet_observe: prometheus_client absent — "
              "shape-checked .prom only")
        return
    fams = {f.name: f for f in text_string_to_metric_families(page)}
    for name in ("galah_fleet_wall_seconds", "galah_fleet_blame_seconds"):
        if name not in fams:
            fail(f"series {name} missing from {path} "
                 f"(got {sorted(fams)})")
    blame = {s.labels.get("component"): s.value
             for s in fams["galah_fleet_blame_seconds"].samples}
    print(f"fleet_observe: .prom parsed — {len(fams)} families, "
          f"blame components {sorted(k for k in blame if k)}")


def main() -> None:
    work = tempfile.mkdtemp(prefix="fleet_observe_")
    try:
        gdir = os.path.join(work, "genomes")
        os.makedirs(gdir)
        genomes = make_workload(gdir, seed=7, families=2, members=5,
                                length=12_000)
        fleet_dir = os.path.join(work, "fleet")
        out_tsv = os.path.join(work, "clusters.tsv")
        report = os.path.join(work, "report.json")
        prom = os.path.join(work, "galah.prom")
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "GALAH_OBS_OPENMETRICS": prom,
            "GALAH_OBS_HEARTBEAT_S": "0.5",
            "GALAH_TPU_FLEET_HEARTBEAT_S": "0.5",
        })
        proc = subprocess.run(
            fleet_argv(genomes, fleet_dir, out_tsv, report,
                       resume=False, shards=3),
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=600)
        if proc.returncode != 0:
            print(proc.stdout.decode(errors="replace")[-3000:])
            fail(f"fleet run exited {proc.returncode}")

        # -- fleet analyze: blame table + conservation ----------------
        proc = subprocess.run(
            [sys.executable, "-m", "galah_tpu.cli", "fleet", "analyze",
             "--json", fleet_dir],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=120)
        if proc.returncode != 0:
            print(proc.stderr.decode(errors="replace")[-2000:])
            fail(f"fleet analyze exited {proc.returncode}")
        ru = json.loads(proc.stdout)
        wall = ru["fleet_wall_s"]
        blame = sum(c["blame_s"] for c in ru["components"].values())
        if not wall or abs(blame - wall) > 0.01 * wall:
            fail(f"blame sum {blame:.3f}s vs wall {wall:.3f}s")
        print(f"fleet_observe: rollup conserves wall "
              f"({blame:.2f}s / {wall:.2f}s), bottleneck "
              f"{ru.get('bottleneck')!r}")
        subprocess.run(
            [sys.executable, "-m", "galah_tpu.cli", "fleet", "analyze",
             fleet_dir], timeout=120)  # human table into the capture

        # -- top --json fleet grid ------------------------------------
        proc = subprocess.run(
            [sys.executable, "-m", "galah_tpu.cli", "top", fleet_dir,
             "--json"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=120)
        if proc.returncode != 0:
            print(proc.stderr.decode(errors="replace")[-2000:])
            fail(f"top --json exited {proc.returncode}")
        grid = json.loads(proc.stdout)
        if not grid.get("shards"):
            fail("top --json fleet grid has no shards")
        print(f"fleet_observe: fleet grid shows "
              f"{len(grid['shards'])} shard(s)")

        # -- OpenMetrics textfile -------------------------------------
        check_prom(prom)
        print("fleet_observe: OK")
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
