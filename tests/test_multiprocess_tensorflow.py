"""The TensorFlow binding across real worker processes: eager, its error
paths and TF1 graph mode (the torch binding's cases and the fusion win
through both are ``tests/test_multiprocess_bindings.py``, which says why
these have files of their own).
"""

import pytest

from mp_launch import launch as _launch, needs_native

pytestmark = needs_native


@pytest.mark.parametrize("world", [2])
def test_tensorflow_binding_across_processes(world):
    """TF eager binding under a real multi-process world (reference:
    test/test_tensorflow.py under mpirun -np 2): collectives, custom
    gradients, DistributedGradientTape/Optimizer lockstep,
    broadcast_variables, IndexedSlices, object broadcast."""
    pytest.importorskip("tensorflow")
    procs, outs = _launch("tensorflow", world, timeout=300)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK rank=" in out


@pytest.mark.parametrize("world", [2, 3])
def test_tensorflow_error_paths_across_processes(world):
    """Mismatched shape/dtype THROUGH the TF binding raises on all ranks
    and the world stays usable (reference: test_tensorflow.py:314-460)."""
    pytest.importorskip("tensorflow")
    procs, outs = _launch("tensorflow_errors", world, timeout=300)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK rank=" in out


@pytest.mark.parametrize("world", [2])
def test_tensorflow_graph_mode_across_processes(world):
    """TF1 graph-mode surface under a real multi-process world:
    BroadcastGlobalVariablesHook under MonitoredTrainingSession and the
    broadcast_variables graph op (reference:
    horovod/tensorflow/__init__.py:125-192)."""
    pytest.importorskip("tensorflow")
    procs, outs = _launch("tensorflow_graph", world, timeout=300)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK rank=" in out
