"""Test harness: 8 virtual CPU devices for SPMD tests.

Mirrors the reference's test split (SURVEY.md §4): pure-Python unit tests on a
fake mesh. 8 host devices exercise real dp/tp/cp/ep/pp SPMD semantics without
TPU hardware — strictly more than the reference's 2-GPU cap.

The suite is a CPU suite wherever it runs: the platform is pinned to cpu
before JAX initializes, so on a host that has a chip the tests neither take
it from a process that needs it nor change what they compare against.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
_cpus = jax.devices("cpu")

import pytest  # noqa: E402

# per-test wall budget for the tier-1 (non-slow) suite. The driver gives the
# whole suite 1,470 s over six workers (`-n 6 --dist loadfile`) and a cut run
# is a refused PR; the suite is kept near 1,000 s on an idle machine (PR 46),
# so one runaway non-slow test is a CI outage, not a slow test. 180 s a case,
# written for tests that run in this process. A case that is one subprocess
# compiling whole programs into a cold cache (a cell's rehearsal) states its
# own with `@pytest.mark.budget(seconds)`; anything else that legitimately
# needs longer belongs behind `-m slow` (multi-subprocess elasticity e2es are).
TIER1_TEST_BUDGET_S = float(os.environ.get("AUTOMODEL_TEST_BUDGET_S", "180"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    own = item.get_closest_marker("budget")
    budget = float(own.args[0]) if own else TIER1_TEST_BUDGET_S
    if (
        report.when == "call"
        and report.passed
        and item.get_closest_marker("slow") is None
        and report.duration > budget
    ):
        report.outcome = "failed"
        report.longrepr = (
            f"{item.nodeid} took {report.duration:.1f}s — over its "
            f"{budget:.0f}s tier-1 budget (180 s a case unless the test is "
            "marked `budget(seconds)`; AUTOMODEL_TEST_BUDGET_S moves the "
            "default). Mark it @pytest.mark.slow or make it fit: the whole "
            "non-slow suite has 1,470 s at the driver."
        )


@pytest.fixture(scope="session")
def devices8():
    assert len(_cpus) >= 8, f"expected 8 virtual CPU devices, got {len(_cpus)}"
    return _cpus[:8]


@pytest.fixture(scope="session")
def cpu_devices():
    return _cpus


@pytest.fixture(autouse=True)
def _cwd_is_scratch(tmp_path, monkeypatch):
    """Every test runs from its own tmp dir, so whatever a recipe writes
    relative to the cwd — the per-run ``output_dir`` default
    ``runs/run_<crc>`` with its profiler captures, ``bench_oom_*.json`` —
    lands there and never in the checkout (a tier-1 run used to leave
    hundreds of MB under ``runs/``, and the chip tool copies the tree as it
    stands)."""
    monkeypatch.chdir(tmp_path)
