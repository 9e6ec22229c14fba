"""Fleet tests: shard-plan determinism, scheduler lifecycle, merge.

The elastic fleet (galah_tpu/fleet/) runs one dereplication across
preemptible worker subprocesses and must converge byte-identically to
a single-process run. The full kill/resume proof lives in the chaos
harness (scripts/chaos_run.py --workload fleet); this file covers the
deterministic building blocks in-process with fake workers:

  * plan.py — byte-identical shard specs for identical inputs, and a
    --resume against a mismatched plan refuses, NAMING the field;
  * scheduler.py — fake workers driven to done, exit-75 reassignment,
    retry-budget quarantine, and event-log replay adopting a prior
    (killed) scheduler's attempts;
  * merge.py — shard-local caches remapped to global indices, a
    cross-shard pair changing the outcome, replay producing the
    engine's cluster shape;
  * obs/heartbeat.read_latest_beat — the scheduler's liveness probe
    never raises on missing/torn/garbage files;
  * resilience/interrupt — the second signal forwards SIGTERM to
    registered worker process groups before the hard exit 75.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from galah_tpu.fleet import merge as fleet_merge
from galah_tpu.fleet import plan as fleet_plan
from galah_tpu.fleet import scheduler as fleet_scheduler
from galah_tpu.fleet.plan import build_plan, ensure_plan, save_plan
from galah_tpu.fleet.scheduler import FleetScheduler
from galah_tpu.io import atomic
from galah_tpu.obs.heartbeat import read_latest_beat
from galah_tpu.resilience.policy import RetryPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- plan ------------------------------------------------------------


def test_build_plan_contiguous_balanced():
    genomes = [f"g{i}.fna" for i in range(10)]
    shards = build_plan(genomes, 3)
    assert [(s.lo, s.hi) for s in shards] == [(0, 4), (4, 7), (7, 10)]
    assert [s.shard_id for s in shards] == [0, 1, 2]
    for s in shards:
        assert list(s.genomes) == genomes[s.lo:s.hi]
    sizes = [s.hi - s.lo for s in shards]
    assert max(sizes) - min(sizes) <= 1


def test_build_plan_drops_empty_shards():
    shards = build_plan(["a.fna", "b.fna"], 5)
    assert [(s.lo, s.hi) for s in shards] == [(0, 1), (1, 2)]


def test_plan_file_bytes_deterministic(tmp_path):
    genomes = [f"/data/g{i}.fna" for i in range(7)]
    fields = {"ani": 95.0, "n_shards": 3}
    blobs = []
    for d in ("a", "b"):
        fleet_dir = str(tmp_path / d)
        os.makedirs(fleet_dir)
        save_plan(fleet_dir, fields, build_plan(genomes, 3))
        with open(fleet_plan.plan_path(fleet_dir), "rb") as f:
            blobs.append(f.read())
    assert blobs[0] == blobs[1]


def test_ensure_plan_roundtrip_is_stable(tmp_path):
    fleet_dir = str(tmp_path)
    genomes = [f"g{i}.fna" for i in range(5)]
    fields = {"ani": 95.0}
    first = ensure_plan(fleet_dir, genomes, fields, 2)
    with open(fleet_plan.plan_path(fleet_dir), "rb") as f:
        blob = f.read()
    again = ensure_plan(fleet_dir, genomes, fields, 2,
                        require_match=True)
    assert again == first
    with open(fleet_plan.plan_path(fleet_dir), "rb") as f:
        assert f.read() == blob  # loaded, not rewritten


def test_ensure_plan_resume_mismatch_names_the_field(tmp_path):
    fleet_dir = str(tmp_path)
    genomes = [f"g{i}.fna" for i in range(5)]
    ensure_plan(fleet_dir, genomes, {"ani": 95.0}, 2)
    with pytest.raises(ValueError, match="mismatched fields.*ani"):
        ensure_plan(fleet_dir, genomes, {"ani": 99.0}, 2,
                    require_match=True)
    with pytest.raises(ValueError, match="mismatched fields.*n_shards"):
        ensure_plan(fleet_dir, genomes, {"ani": 95.0}, 3,
                    require_match=True)


def test_ensure_plan_fresh_run_rebuilds_on_mismatch(tmp_path):
    fleet_dir = str(tmp_path)
    genomes = [f"g{i}.fna" for i in range(6)]
    ensure_plan(fleet_dir, genomes, {"ani": 95.0}, 2)
    # a stale event log from the superseded configuration must go too
    atomic.append_jsonl(fleet_plan.events_path(fleet_dir),
                        {"ev": "shard-launched", "shard": 0})
    shards = ensure_plan(fleet_dir, genomes, {"ani": 99.0}, 3)
    assert len(shards) == 3
    assert not os.path.exists(fleet_plan.events_path(fleet_dir))
    doc = fleet_plan.load_plan(fleet_dir)
    assert doc["fields"]["ani"] == 99.0


def test_fleet_run_resume_mismatch_exits_1(tmp_path, capsys):
    """CLI-level satellite: `fleet run --resume` against a plan from a
    different configuration exits 1 and names the mismatched field."""
    from galah_tpu.cli import main

    fleet_dir = str(tmp_path / "fleet")
    os.makedirs(fleet_dir)
    genomes = []
    for i in range(2):
        p = str(tmp_path / f"g{i}.fna")
        with open(p, "w") as f:
            f.write(">c1\n" + "ACGT" * 50 + "\n")
        genomes.append(p)
    save_plan(fleet_dir, {"ani": "something-else"},
              build_plan(genomes, 2))
    rc = main(["fleet", "--platform", "cpu", "run",
               "--genome-fasta-files", *genomes,
               "--precluster-method", "skani",
               "--cluster-method", "skani",
               "--fleet-dir", fleet_dir, "--resume",
               "--output-cluster-definition",
               str(tmp_path / "clusters.tsv")])
    assert rc == 1
    assert "mismatched fields" in capsys.readouterr().err


def test_fleet_run_refuses_non_skani_methods(tmp_path, capsys):
    from galah_tpu.cli import main

    p = str(tmp_path / "g0.fna")
    with open(p, "w") as f:
        f.write(">c1\n" + "ACGT" * 50 + "\n")
    rc = main(["fleet", "--platform", "cpu", "run",
               "--genome-fasta-files", p,
               "--precluster-method", "finch",
               "--cluster-method", "skani",
               "--fleet-dir", str(tmp_path / "fleet"),
               "--output-cluster-definition",
               str(tmp_path / "clusters.tsv")])
    assert rc == 1
    assert "fleet run requires" in capsys.readouterr().err


# -- scheduler (fake workers) ----------------------------------------


def test_fleet_run_refuses_workers_sharing_an_accelerator(tmp_path,
                                                         capsys):
    """More than one worker on a non-CPU platform would put several
    processes on this host's chips; the scheduler refuses before it
    plans anything (and before any backend starts)."""
    from galah_tpu.cli import main

    p = str(tmp_path / "g0.fna")
    with open(p, "w") as f:
        f.write(">c1\n" + "ACGT" * 50 + "\n")
    rc = main(["fleet", "--platform", "tpu", "run",
               "--genome-fasta-files", p,
               "--precluster-method", "skani",
               "--cluster-method", "skani", "--workers", "2",
               "--fleet-dir", str(tmp_path / "fleet"),
               "--output-cluster-definition",
               str(tmp_path / "clusters.tsv")])
    assert rc == 1
    assert "would share" in capsys.readouterr().err
    assert not (tmp_path / "fleet").exists()


@pytest.mark.parametrize("has_tpu,rc", [(False, 0), (True, 1)])
def test_fleet_run_unpinned_platform_refuses_only_on_a_tpu_host(
        tmp_path, monkeypatch, has_tpu, rc):
    """With no platform pinned, JAX's default decides: several workers
    are fine on a CPU-only host and refused where a TPU is present."""
    from galah_tpu import cli

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("GALAH_TPU_PLATFORM", raising=False)
    monkeypatch.setattr(cli, "_host_has_tpu", lambda: has_tpu)
    args = cli.build_parser().parse_args(
        ["fleet", "run", "--workers", "2", "--fleet-dir", str(tmp_path)])
    monkeypatch.setattr("jax.config.update", lambda *a, **k: None)
    assert cli._start_backend(args) == rc


def test_fleet_workers_inherit_the_platform(tmp_path):
    from galah_tpu.cli import _fleet_worker_argv, build_parser

    args = build_parser().parse_args(
        ["fleet", "--platform", "cpu", "run", "--fleet-dir",
         str(tmp_path)])
    spec = build_plan(["g.fna"], 1)[0]
    argv = _fleet_worker_argv(args, str(tmp_path), "")(spec, False)
    assert argv[argv.index("--platform") + 1] == "cpu"


def _done_worker_argv(fleet_dir):
    """A fake worker that just leaves the merge artifact and exits 0."""
    def argv(spec, resume):
        path = fleet_scheduler.shard_distances_path(fleet_dir,
                                                    spec.shard_id)
        code = (f"import os; p = {path!r};"
                "os.makedirs(os.path.dirname(p), exist_ok=True);"
                "open(p, 'wb').write(b'npz')")
        return [sys.executable, "-c", code]
    return argv


def _fast_policy(max_attempts=3):
    return RetryPolicy(max_attempts=max_attempts, base_delay=0.01,
                       max_delay=0.02, jitter=0.0, seed=0)


def test_scheduler_drives_fake_workers_to_done(tmp_path):
    fleet_dir = str(tmp_path)
    shards = build_plan([f"g{i}.fna" for i in range(6)], 3)
    sched = FleetScheduler(fleet_dir, shards,
                           _done_worker_argv(fleet_dir), workers=2,
                           poll_s=0.02, heartbeat_s=0,
                           policy=_fast_policy())
    snap = sched.run()
    assert snap["shards_done"] == 3
    assert snap["shards_failed"] == 0
    assert snap["preemptions"] == 0
    assert [s["attempts"] for s in snap["shards"]] == [1, 1, 1]
    events = [r["ev"] for r in
              atomic.read_jsonl(fleet_plan.events_path(fleet_dir))[0]]
    assert events.count("shard-launched") == 3
    assert events.count("shard-done") == 3


def test_scheduler_reassigns_after_exit_75(tmp_path):
    fleet_dir = str(tmp_path)
    shards = build_plan([f"g{i}.fna" for i in range(4)], 2)

    def argv(spec, resume):
        path = fleet_scheduler.shard_distances_path(fleet_dir,
                                                    spec.shard_id)
        marker = os.path.join(fleet_dir, f"seen_{spec.shard_id}")
        code = textwrap.dedent(f"""
            import os, sys
            if not os.path.exists({marker!r}):
                open({marker!r}, 'w').close()
                sys.exit(75)
            p = {path!r}
            os.makedirs(os.path.dirname(p), exist_ok=True)
            open(p, 'wb').write(b'npz')
        """)
        return [sys.executable, "-c", code]

    sched = FleetScheduler(fleet_dir, shards, argv, workers=2,
                           poll_s=0.02, heartbeat_s=0,
                           policy=_fast_policy())
    snap = sched.run()
    assert snap["shards_done"] == 2
    assert snap["preemptions"] == 2
    assert snap["reassignments"] == 2
    for s in snap["shards"]:
        assert s["attempts"] == 2
        assert s["preemptions"] == ["exit-75"]


def test_scheduler_quarantines_on_exhausted_budget(tmp_path):
    fleet_dir = str(tmp_path)
    shards = build_plan(["g0.fna", "g1.fna"], 1)

    def argv(spec, resume):
        return [sys.executable, "-c", "import sys; sys.exit(3)"]

    sched = FleetScheduler(fleet_dir, shards, argv, workers=1,
                           poll_s=0.02, heartbeat_s=0,
                           policy=_fast_policy(max_attempts=2))
    snap = sched.run()
    assert snap["shards_done"] == 0
    assert snap["shards_failed"] == 1
    assert snap["shards"][0]["status"] == "failed"
    assert snap["shards"][0]["preemptions"] == ["exit-3", "exit-3"]
    events = [r["ev"] for r in
              atomic.read_jsonl(fleet_plan.events_path(fleet_dir))[0]]
    assert "fleet-shard-failed" in events


def test_scheduler_replays_prior_event_log(tmp_path):
    """A resumed scheduler adopts a killed predecessor's attempts: the
    pre-act shard-launched record with no matching completion becomes
    an uncharged 'orphaned' preemption, and lifetime attempt counts
    carry across the restart."""
    fleet_dir = str(tmp_path)
    shards = build_plan([f"g{i}.fna" for i in range(4)], 2)
    for sid in (0, 1):
        atomic.append_jsonl(
            fleet_plan.events_path(fleet_dir),
            {"ev": "shard-launched", "shard": sid, "pid": -1,
             "attempt": 1})
    sched = FleetScheduler(fleet_dir, shards,
                           _done_worker_argv(fleet_dir), workers=2,
                           poll_s=0.02, heartbeat_s=0,
                           policy=_fast_policy())
    snap = sched.run()
    assert snap["resumed"] is True
    assert snap["shards_done"] == 2
    for s in snap["shards"]:
        assert s["attempts"] == 2  # replayed launch + the real one
        assert s["preemptions"] == ["orphaned"]
    # 'orphaned' never charges the retry budget
    assert snap["retry_spend_s"] == 0.0


def test_stale_probe_ignores_prior_attempt_beats(tmp_path):
    """heartbeat.jsonl can survive a killed attempt; a resumed
    scheduler must clock staleness from the CURRENT attempt's launch,
    not the dead attempt's last beat, or every relaunched worker is
    stale-killed on the first poll tick before its first beat."""
    fleet_dir = str(tmp_path)
    shards = build_plan(["g0.fna", "g1.fna"], 1)
    sched = FleetScheduler(fleet_dir, shards,
                           _done_worker_argv(fleet_dir), workers=1,
                           poll_s=0.02, heartbeat_s=1, stale_s=30,
                           policy=_fast_policy())
    hb = fleet_scheduler.shard_heartbeat_path(fleet_dir, 0)
    os.makedirs(os.path.dirname(hb), exist_ok=True)
    atomic.append_jsonl(hb, {"beat": 7, "ts": 1.0})  # ancient beat

    class _StillRunning:
        def poll(self):
            return None

    rt = sched.shards[0]
    rt.proc = _StillRunning()
    rt.pgid = None
    rt.status = "running"
    rt.launched_wall = time.time()
    sched._poll_one(rt)
    assert rt.status == "running"
    assert sched.preemptions == 0
    # the probe still fires once the CURRENT attempt has gone quiet
    rt.launched_wall = time.time() - 3600
    sched._poll_one(rt)
    assert rt.status == "pending"
    assert rt.preemptions == ["stale-heartbeat"]


def test_launch_drops_prior_attempt_heartbeat(tmp_path):
    fleet_dir = str(tmp_path)
    shards = build_plan(["g0.fna"], 1)
    sched = FleetScheduler(fleet_dir, shards,
                           _done_worker_argv(fleet_dir), workers=1,
                           poll_s=0.02, heartbeat_s=0,
                           policy=_fast_policy())
    hb = fleet_scheduler.shard_heartbeat_path(fleet_dir, 0)
    os.makedirs(os.path.dirname(hb), exist_ok=True)
    atomic.append_jsonl(hb, {"beat": 1, "ts": 1.0})
    rt = sched.shards[0]
    sched._launch(rt)
    try:
        assert not os.path.exists(hb)
    finally:
        rt.proc.wait(timeout=10)
        fleet_scheduler.interrupt.unregister_worker_group(rt.pgid)


def test_is_our_worker_requires_env_stamp(tmp_path):
    """Orphan sweep must match the fleet's env stamp, never argv: a
    bystander whose cmdline names the shards dir (e.g. `galah-tpu top
    <fleet_dir>/shards/...`) is not ours and must not be killable."""
    fleet_dir = str(tmp_path)
    shards = build_plan(["g0.fna"], 1)
    sched = FleetScheduler(fleet_dir, shards,
                           _done_worker_argv(fleet_dir), workers=1,
                           poll_s=0.02, heartbeat_s=0,
                           policy=_fast_policy())
    shard_path = os.path.join(fleet_dir, "shards", "shard_000")
    clean_env = {k: v for k, v in os.environ.items()
                 if k != "GALAH_TPU_FLEET_WORKER"}
    # the ready line proves the child has exec'd: /proc/<pid>/environ
    # shows the PARENT's image until execve lands
    ready = "print('ready', flush=True); import time; time.sleep(60)"
    bystander = subprocess.Popen(
        [sys.executable, "-c", ready, shard_path, "galah_tpu"],
        env=clean_env, stdout=subprocess.PIPE)
    worker = subprocess.Popen(
        [sys.executable, "-c", ready],
        env=sched.base_env, stdout=subprocess.PIPE)
    try:
        bystander.stdout.readline()
        worker.stdout.readline()
        assert sched._is_our_worker(bystander.pid) is False
        assert sched._is_our_worker(worker.pid) is True
        assert sched._is_our_worker(2 ** 22 + 1234) is False  # gone
    finally:
        for p in (bystander, worker):
            p.kill()
            p.wait()
            p.stdout.close()


def test_fleet_run_rejects_zero_workers(tmp_path, capsys):
    """`--workers 0` is an error, not a silent fall-through to the
    env/default value (0 is falsy; only None means unset)."""
    from galah_tpu.cli import main

    p = str(tmp_path / "g0.fna")
    with open(p, "w") as f:
        f.write(">c1\n" + "ACGT" * 50 + "\n")
    rc = main(["fleet", "--platform", "cpu", "run",
               "--genome-fasta-files", p,
               "--precluster-method", "skani",
               "--cluster-method", "skani",
               "--workers", "0",
               "--fleet-dir", str(tmp_path / "fleet"),
               "--output-cluster-definition",
               str(tmp_path / "clusters.tsv")])
    assert rc == 1
    assert "--workers must be >= 1" in capsys.readouterr().err


# -- merge -----------------------------------------------------------


class _StubPreclusterer:
    """Hands merge.cross_shard_pairs a prebuilt cache and checks the
    keep-predicate really restricts it to cross-shard pairs."""

    def __init__(self, cross):
        self.cross = cross

    def distances_subset(self, genome_paths, keep):
        from galah_tpu.cluster.cache import PairDistanceCache

        cache = PairDistanceCache()
        for (i, j), v in self.cross.items():
            assert keep(i, j), (i, j)
            cache.insert((i, j), v)
        return cache


def _write_shard_npz(fleet_dir, shard_id, local_pairs):
    path = fleet_scheduler.shard_distances_path(fleet_dir, shard_id)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    keys = sorted(local_pairs)
    atomic.write_npz(path, {
        "ii": np.array([k[0] for k in keys], dtype=np.int64),
        "jj": np.array([k[1] for k in keys], dtype=np.int64),
        "vals": np.array([local_pairs[k] or 0.0 for k in keys],
                         dtype=np.float64),
        "has_val": np.array([local_pairs[k] is not None for k in keys],
                            dtype=bool),
    })


def test_load_shard_pairs_remaps_to_global(tmp_path):
    fleet_dir = str(tmp_path)
    shards = build_plan([f"g{i}.fna" for i in range(6)], 2)
    _write_shard_npz(fleet_dir, 0, {(0, 1): 99.0})
    _write_shard_npz(fleet_dir, 1, {(0, 2): 98.0, (1, 2): None})
    pairs = fleet_merge.load_shard_pairs(fleet_dir, shards)
    # shard 1 spans [3, 6): local (0, 2) is global (3, 5); the
    # has_val=False screen-miss is dropped, not merged as 0.0
    assert pairs == {(0, 1): 99.0, (3, 5): 98.0}


def test_merge_replays_cross_shard_join(tmp_path):
    fleet_dir = str(tmp_path)
    genomes = [f"g{i}.fna" for i in range(6)]
    shards = build_plan(genomes, 2)  # [0, 3) and [3, 6)
    _write_shard_npz(fleet_dir, 0, {(0, 1): 99.0, (0, 2): 98.5})
    _write_shard_npz(fleet_dir, 1, {(0, 1): 97.5, (0, 2): 99.2})
    # without the cross pair g3 founds shard 1's cluster; with it, g3
    # first joins rep 0 at 99.1 but is re-homed to the later rep g5
    # (ANI 99.2 beats 99.1, engine best-rep semantics), leaving g4 a
    # singleton — exactly the cross-shard rep/member flip that makes a
    # rep-only hierarchical merge unsafe
    clusters = fleet_merge.merge(fleet_dir, genomes, shards,
                                 _StubPreclusterer({(0, 3): 99.1}),
                                 95.0)
    assert clusters == [[0, 1, 2], [4], [5, 3]]
    without = fleet_merge.merge(fleet_dir, genomes, shards,
                                _StubPreclusterer({}), 95.0)
    assert without == [[0, 1, 2], [3, 4, 5]]


# -- heartbeat probe -------------------------------------------------


def test_read_latest_beat_missing_is_none(tmp_path):
    assert read_latest_beat(str(tmp_path)) is None
    assert read_latest_beat(str(tmp_path / "heartbeat.jsonl")) is None


def test_read_latest_beat_garbage_is_none(tmp_path):
    p = tmp_path / "heartbeat.jsonl"
    p.write_bytes(b"{half a record with no framing")
    assert read_latest_beat(str(p)) is None


def test_read_latest_beat_survives_torn_tail(tmp_path):
    p = str(tmp_path / "heartbeat.jsonl")
    atomic.append_jsonl(p, {"beat": 1, "ts": 10.0})
    atomic.append_jsonl(p, {"beat": 2, "ts": 11.0})
    with open(p, "ab") as f:
        f.write(b'{"beat": 3, "ts": 12.0')  # kill mid-append
    rec = read_latest_beat(p)
    assert rec == {"beat": 2, "ts": 11.0}
    # directory form resolves to the file the worker writes
    assert read_latest_beat(str(tmp_path)) == rec


# -- interrupt forwarding --------------------------------------------


def test_second_signal_forwards_sigterm_to_worker_groups():
    """The supervisor's hard exit must not leave its fleet running:
    signal #1 is cooperative, signal #2 forwards SIGTERM to every
    registered worker process group, then exits 75."""
    child_code = textwrap.dedent(f"""
        import os, subprocess, sys, time
        sys.path.insert(0, {REPO!r})
        from galah_tpu.resilience import interrupt
        interrupt.install()
        worker = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"],
            start_new_session=True)
        interrupt.register_worker_group(worker.pid)
        print(worker.pid, flush=True)
        while True:
            time.sleep(0.05)
    """)
    proc = subprocess.Popen([sys.executable, "-c", child_code],
                            stdout=subprocess.PIPE)
    wpid = None
    try:
        wpid = int(proc.stdout.readline())
        proc.send_signal(signal.SIGTERM)
        time.sleep(0.3)  # let the cooperative first signal settle
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        proc.stdout.close()
    assert rc == 75
    deadline = time.monotonic() + 5
    alive = True
    while time.monotonic() < deadline:
        try:
            os.kill(wpid, 0)
        except ProcessLookupError:
            alive = False
            break
        time.sleep(0.05)
    if alive:  # don't leak the sleeper on failure
        os.kill(wpid, signal.SIGKILL)
    assert not alive, "worker survived the supervisor's hard exit"
