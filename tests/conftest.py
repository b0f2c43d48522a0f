"""Test fixtures: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's "distributed without a cluster" strategy (reference:
test/ run under ``mpirun -np 2 -H localhost:2``, SURVEY.md §4): collective
semantics, fusion, caching and error propagation are tested on one host by
faking the device topology — here with
``--xla_force_host_platform_device_count=8`` CPU devices instead of
multiple MPI processes. Both variables are set here, before anything
imports jax, so the suite runs the same on a machine that has a chip.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

# Launcher-driven tests spawn `tpurun ... python examples/foo.py`
# subprocesses that import horovod_tpu from PYTHONPATH (pytest's rootdir
# insertion only covers THIS process). Prepend the repo so the tests are
# hermetic whether or not the package is pip-installed.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in os.environ.get("PYTHONPATH", "").split(os.pathsep):
    os.environ["PYTHONPATH"] = (
        _REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)

import atexit  # noqa: E402
import collections  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

# One compilation cache for the whole run and for this run alone. Half of
# an engine case is XLA compiling a program that another case, another
# worker or a subprocess (benchmark/tests, the launcher's workers) has
# compiled before. The controller makes the directory before xdist starts
# its workers, so they and every subprocess inherit it; a worker keeps
# what it inherited. It never outlives the run: a stale entry would hide
# a change. Both thresholds are zero, since what is small and quick here
# alone is neither beside five busy workers. Every executable read back
# logs 11-14 KB at ERROR level (jaxlib 0.9.0's cpu_aot_loader: the
# compiler's own pseudo-features, +prefer-no-gather and +prefer-no-scatter,
# are not among the host's), so a test that starts ranks sends their
# output to files and not to pipes (mp_launch.start).
if "PYTEST_XDIST_WORKER" not in os.environ:
    _CACHE = tempfile.mkdtemp(prefix="hvd_tier1_jax_cache_")
    atexit.register(shutil.rmtree, _CACHE, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); big worlds "
        "and soaks that need a multi-core box")


@pytest.fixture
def hvd():
    """Initialized framework on a 2x4 (cross x local) mesh, torn down after
    the test so each test sees a fresh world."""
    import horovod_tpu as hvd_mod

    hvd_mod.shutdown()
    hvd_mod.init(mesh_shape=(2, 4))
    yield hvd_mod
    hvd_mod.shutdown()


@pytest.fixture
def hvd_flat():
    """Initialized framework on a 1x8 mesh (single-host view)."""
    import horovod_tpu as hvd_mod

    hvd_mod.shutdown()
    hvd_mod.init(mesh_shape=(1, 8))
    yield hvd_mod
    hvd_mod.shutdown()


@pytest.fixture
def ring(monkeypatch):
    """A fresh, enabled span ring behind ``horovod_tpu.tracing``'s
    module-level entry points."""
    from horovod_tpu import tracing

    monkeypatch.delenv("HOROVOD_TRACE", raising=False)
    fresh = tracing.Tracer()
    monkeypatch.setattr(tracing, "_tracer", fresh)
    return fresh


# tier-1's budget (ROADMAP.md, "Tier-1 verify"): the driver's command cuts
# the run at 1,470 s and ``--dist loadfile`` makes a file the unit that is
# scheduled, so the longest file bounds the whole run from below
FILE_BUDGET_S, CASE_BUDGET_S, SUM_BUDGET_S = 300, 120, 4800


def pytest_terminal_summary(terminalreporter):
    """What this run cost against tier-1's budget: the files over 300 s
    and the cases over 120 s (set-up and tear-down with them, as in the
    junit report), so that the log shows a PR what it added."""
    cases = collections.Counter()
    for reports in terminalreporter.stats.values():
        for report in reports:
            if hasattr(report, "when") and hasattr(report, "duration"):
                cases[report.nodeid] += report.duration
    files = collections.Counter()
    for nodeid, seconds in cases.items():
        files[nodeid.split("::")[0]] += seconds
    if not files:
        return
    write = terminalreporter.write_line
    terminalreporter.section("tier-1 budget")
    (slowest, file_s), = files.most_common(1)
    write(f"cases' sum {sum(cases.values()):,.0f} s (budget "
          f"{SUM_BUDGET_S:,}); longest file {slowest} {file_s:,.0f} s "
          f"(budget {FILE_BUDGET_S})")
    for name, seconds in files.most_common():
        if seconds > FILE_BUDGET_S:
            write(f"FILE OVER {FILE_BUDGET_S} s: {name} {seconds:,.0f} s")
    for name, seconds in cases.most_common():
        if seconds > CASE_BUDGET_S:
            write(f"CASE OVER {CASE_BUDGET_S} s: {name} {seconds:,.0f} s")
