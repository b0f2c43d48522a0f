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

import pytest  # noqa: E402


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
