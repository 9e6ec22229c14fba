"""Engine-level fault injection (the tentpole's acceptance criteria).

(a) A transient device-dispatch fault is retried and the run produces
    bit-identical clusters to a fault-free run.
(b) Persistent device faults demote the site to its CPU fallback
    mid-run; the run completes, records the demotion in the stage
    report, and still produces identical clusters.

Uses the checkpoint tests' fake backends so the dispatch.ani site fires
deterministically, plus one real-backend run over tiny FASTA to prove
the fragment-ANI site is guarded end-to-end.
"""

import numpy as np
import pytest

from galah_tpu.cluster import cluster
from galah_tpu.resilience import dispatch as rdispatch
from galah_tpu.resilience import faults
from galah_tpu.resilience.faults import FaultInjector, FaultSpec
from galah_tpu.resilience.policy import RetryPolicy
from galah_tpu.utils import timing
from tests.test_checkpoint import GENOMES, FakeCl, FakePre

pytestmark = pytest.mark.fault_injection

FAST = RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0)


@pytest.fixture(autouse=True)
def _clean_state():
    faults.reset()
    rdispatch.reset(FAST)
    timing.reset()
    yield
    faults.reset()
    rdispatch.reset()
    timing.reset()


def test_transient_ani_fault_retried_bit_identical():
    """Acceptance (a): two injected transient faults at the ANI batch
    dispatch are absorbed by retries; clusters match fault-free."""
    reference = cluster(GENOMES, FakePre(), FakeCl(0.95))

    faults.install(FaultInjector([FaultSpec(
        site="dispatch.ani", kind="raise", prob=1.0, max_faults=2)]))
    out = cluster(GENOMES, FakePre(), FakeCl(0.95))

    assert out == reference
    injector = faults.get_injector()
    assert injector.fired() == 2
    assert timing.GLOBAL.counters().get("retries[dispatch.ani]") == 2
    assert not rdispatch.demotions()


def test_persistent_ani_fault_demotes_and_completes():
    """Acceptance (b): every batched ANI dispatch fails; the site is
    demoted to the per-pair CPU fallback, the run completes, the
    demotion lands in the stage report, clusters match fault-free."""
    reference = cluster(GENOMES, FakePre(), FakeCl(0.95))

    faults.install(FaultInjector([FaultSpec(
        site="dispatch.ani", kind="raise", prob=1.0)]))
    cl = FakeCl(0.95)
    out = cluster(GENOMES, FakePre(), cl)

    assert out == reference
    dems = rdispatch.demotions()
    assert [d.site for d in dems] == ["dispatch.ani"]
    counters = timing.GLOBAL.counters()
    assert counters.get("demoted[dispatch.ani]") == 1
    assert counters.get("retries[dispatch.ani]") == 2
    report = timing.GLOBAL.report()
    assert "demoted[dispatch.ani]=1" in report
    # the fallback actually computed ANI (per-pair, outside injection)
    assert cl.pairs_computed


def test_demotion_does_not_outlive_its_run():
    """Demotions belong to one run: a site demoted in one cluster()
    call is back on its primary path at the start of the next, so a
    persistent fault in one run cannot reroute a later, healthy one."""
    faults.install(FaultInjector([FaultSpec(
        site="dispatch.ani", kind="raise", prob=1.0)]))
    cluster(GENOMES, FakePre(), FakeCl(0.95))
    assert [d.site for d in rdispatch.demotions()] == ["dispatch.ani"]

    faults.reset()
    calls = []
    cl = FakeCl(0.95)
    orig = cl.calculate_ani_batch
    cl.calculate_ani_batch = lambda p: (calls.append(len(p)), orig(p))[1]
    cluster(GENOMES, FakePre(), cl)
    assert not rdispatch.demotions()
    # batched primary dispatches, not the per-pair fallback loop
    assert max(calls) > 1


def test_device_lost_then_recovered():
    """The lost-device signature (DeviceLostError) is retryable too;
    one drop does not demote."""
    faults.install(FaultInjector([FaultSpec(
        site="dispatch.ani", kind="device-lost", prob=1.0,
        max_faults=1)]))
    out = cluster(GENOMES, FakePre(), FakeCl(0.95))
    assert out == cluster(GENOMES, FakePre(), FakeCl(0.95))
    assert not rdispatch.demotions()


def test_garbage_ani_batch_rejected_by_validator():
    """A truncated device result is caught by the shape validator and
    retried — it must never silently mis-cluster."""
    reference = cluster(GENOMES, FakePre(), FakeCl(0.95))
    faults.install(FaultInjector([FaultSpec(
        site="dispatch.ani", kind="garbage", prob=1.0, max_faults=1)]))
    out = cluster(GENOMES, FakePre(), FakeCl(0.95))
    assert out == reference
    assert timing.GLOBAL.counters().get(
        "retries[dispatch.ani]", 0) >= 1


def _write_genomes(tmp_path):
    rng = np.random.default_rng(21)
    base = rng.integers(0, 4, size=30_000)
    paths = []
    for name, seq in [
        ("a", base),
        ("b", _mutate(base, rng, 0.02)),
        ("far", rng.integers(0, 4, size=30_000)),
    ]:
        p = tmp_path / f"{name}.fna"
        p.write_text(">c\n" + "".join("ACGT"[c] for c in seq) + "\n")
        paths.append(str(p))
    return paths


def _mutate(base, rng, rate):
    seq = np.array(base, copy=True)
    sites = rng.random(seq.shape[0]) < rate
    seq[sites] = (seq[sites]
                  + rng.integers(1, 4, size=int(sites.sum()))) % 4
    return seq


def test_real_backend_fragment_ani_site_guarded(tmp_path):
    """End-to-end over real FASTA: persistent faults at the fragment-ANI
    dispatch (skani precluster distances) demote to the per-pair
    fallback and the clustering still matches the fault-free run."""
    from galah_tpu.api import generate_galah_clusterer

    paths = _write_genomes(tmp_path)
    values = {"ani": 95.0, "precluster_ani": 90.0,
              "min_aligned_fraction": 15.0, "fragment_length": 3000,
              "precluster_method": "skani", "cluster_method": "skani",
              "threads": 1}

    def run():
        cl = generate_galah_clusterer(paths, values)
        return sorted(sorted(cl.genome_paths[i] for i in c)
                      for c in cl.cluster())

    reference = run()
    timing.reset()
    rdispatch.reset(FAST)
    faults.install(FaultInjector([FaultSpec(
        site="dispatch.fragment-ani", kind="raise", prob=1.0)]))
    out = run()

    assert out == reference
    assert [d.site for d in rdispatch.demotions()] == [
        "dispatch.fragment-ani"]
    assert timing.GLOBAL.counters().get(
        "demoted[dispatch.fragment-ani]") == 1
