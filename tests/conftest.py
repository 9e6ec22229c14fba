"""Test configuration: force an 8-device CPU mesh before JAX import.

Mirrors SURVEY.md §4's third tier: multi-device semantics are tested on CPU
via --xla_force_host_platform_device_count so no TPU (and no multi-chip
hardware) is needed to exercise the sharded pairwise path.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

# The config update below (before any jax use) pins the CPU backend
# whatever the interpreter's environment selected.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pathlib

import pytest

REFERENCE_DATA = pathlib.Path("/root/reference/tests/data")

# Tier-1 runs under the GalahSan runtime concurrency sanitizer
# (docs/sanitizer.md): the threaded modules' declared locks are
# wrapped so the observed acquisition graph and GUARDED_BY mutations
# are validated under the real workload. GALAH_SAN=0 opts a run out
# (e.g. when bisecting a failure the instrumentation might mask).
# galah-lint: ignore[GL402] tier-1 opts in; the registry default (unset) is for production runs
os.environ.setdefault("GALAH_SAN", "1")

from galah_tpu.analysis import sanitizer as _galah_san  # noqa: E402

_galah_san.maybe_install()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute campaign/scale tests — skipped by default "
        "so the inner dev loop stays under ~5 min; run them with "
        "GALAH_RUN_SLOW=1 (or GALAH_RUN_CAMPAIGN=1, or -m slow)")
    config.addinivalue_line(
        "markers",
        "hardware: tests that require a real TPU — always skipped on "
        "CPU; `galah-tpu lint` (GL601) audits that every "
        "hardware-only test carries this or the slow marker")
    config.addinivalue_line(
        "markers",
        "fault_injection: seeded fault-injection tests of the "
        "resilience layer (retry/demote/quarantine) — fast, CPU-only, "
        "part of the default tier-1 run; select just them with "
        "-m fault_injection")
    config.addinivalue_line(
        "markers",
        "chaos: bounded kill-anywhere chaos smoke (subprocess runs "
        "interrupted by SIGTERM / GALAH_FI kill / fs faults, then "
        "resumed and byte-compared) — slow tier; run with -m chaos "
        "or GALAH_RUN_SLOW=1; the full 25-iteration acceptance pass "
        "is scripts/chaos_run.py")


def pytest_collection_modifyitems(config, items):
    """Default-skip @pytest.mark.slow unless explicitly requested.

    The goldens these tests pin still run in CI tiers and before any
    release claim: GALAH_RUN_SLOW=1 runs everything, and an explicit
    -m expression takes full control."""
    if (os.environ.get("GALAH_RUN_SLOW") == "1"
            or os.environ.get("GALAH_RUN_CAMPAIGN") == "1"
            or config.getoption("-m")):
        return
    skip = pytest.mark.skip(
        reason="slow tier; set GALAH_RUN_SLOW=1 to run")
    for item in items:
        if "slow" in item.keywords or "hardware" in item.keywords:
            item.add_marker(skip)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """One GalahSan line per session: the observed-graph totals and
    the must-be-zero violation counts. tests/test_sanitizer.py's gate
    test is what FAILS the run on violations; this line is where a
    human sees the numbers."""
    if not _galah_san.GLOBAL.installed:
        return
    s = _galah_san.GLOBAL.summary()
    terminalreporter.write_line(
        f"galah-san: {s['acquisitions']} acquisitions / "
        f"{s['locks']} locks, edges {s['edges_observed']} observed / "
        f"{s['edges_declared']} declared "
        f"({s['unexercised']} unexercised); violations: "
        f"{s['undeclared_acquisitions']} undeclared, "
        f"{s['undeclared_edges']} unordered, "
        f"{s['inversions']} inversions, {s['races']} races")


@pytest.fixture(scope="session")
def ref_data() -> pathlib.Path:
    if not REFERENCE_DATA.is_dir():
        pytest.skip("reference fixture data not available")
    return REFERENCE_DATA
