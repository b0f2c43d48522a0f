"""Multi-process runtime integration tests.

The reference tests all native code through Python bindings under a real
multi-process launcher (reference: SURVEY.md §4 — ``mpirun -np 2`` /
horovodrun gloo). Here: spawn real worker processes wired together by the
launcher env contract (HOROVOD_RANK/SIZE + rendezvous address), each
driving the TCP SocketController + native ring data plane.
"""

import pytest

from mp_launch import launch as _launch, needs_native

pytestmark = needs_native


@pytest.mark.parametrize("world", [2, 3])
def test_collectives_across_processes(world):
    procs, outs = _launch("collectives", world)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK rank=" in out


@pytest.mark.parametrize("world", [2, 3])
def test_dtype_matrix_across_processes(world):
    """Reference-breadth dtype x op sweep over the real wire (r5;
    reference: test/test_torch.py dtype sweeps, test_tensorflow.py
    fused many-small + variable-size allgather per dtype): 12 dtypes x
    allreduce(sum,min)/broadcast/variable-size allgather/reducescatter/
    alltoall, with 64-bit payloads that corrupt if anything narrows,
    plus a fused many-small burst across every dtype."""
    procs, outs = _launch("dtype_matrix", world, timeout=180)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK rank=" in out


@pytest.mark.parametrize("world", [2, 3])
def test_skewed_arrival_cycles(world):
    """Workers announcing the same tensor in different cycles — the
    scenario per-tensor negotiation exists for (uncached wait, deferred
    cache hits, synchronized invalidation on shape change)."""
    procs, outs = _launch("skewed_arrival", world, timeout=120)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out


def test_shape_mismatch_errors_on_all_ranks():
    procs, outs = _launch("shape_mismatch", 2)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out


def test_lane_hazard_watchdog_diagnoses_user_program_interleave():
    """Named op in flight + silent enqueue side (the caller 'busy in its
    own global program') must print the specific lane-hazard diagnostic
    within one stall-check period — the hazard _lane_check cannot
    intercept (VERDICT r2 ask 8)."""
    procs, outs = _launch(
        "lane_hazard", 2,
        extra_env={"HOROVOD_STALL_CHECK_TIME_SECONDS": "0.5"},
        timeout=120)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
    assert any("interleaved in different orders across ranks" in out
               and "hazard/x" in out for out in outs), outs


def test_stall_triggers_global_shutdown():
    procs, outs = _launch(
        "stall_shutdown", 2,
        extra_env={
            "HOROVOD_STALL_CHECK_TIME_SECONDS": "0.5",
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "1",
        })
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out


def test_peer_death_fails_survivors():
    """An abruptly killed rank must surface as an error on the survivors,
    not a hang (reference: launcher kills the job on any rank failure,
    gloo_run.py:256-262; pending callbacks get SHUT_DOWN_ERROR)."""
    procs, outs = _launch("peer_death", 2, timeout=120)
    assert procs[1].returncode == 17, outs[1]  # the planted death
    assert procs[0].returncode == 0, outs[0]   # survivor observed an error


@pytest.mark.parametrize("world", [2])
def test_debug_locks_witness_clean_run(world):
    """A short training loop under HOROVOD_DEBUG_LOCKS=1: the runtime's
    witness-wrapped locks must record zero violations, the observed
    acquisition order must be consistent with the static lock-order
    graph (hvd-analyze's claim holds at runtime), and lock_* events must
    reach the flight recorder (asserted in-worker, tests/mp_worker.py
    scenario debug_locks). The hold threshold is out of the way: how
    long hvd.init() holds GlobalState.lock is the machine's load, not an
    order (tests/test_analysis.py plants a long hold and expects it)."""
    procs, outs = _launch("debug_locks", world, timeout=180,
                          extra_env={"HOROVOD_DEBUG_LOCKS": "1",
                                     "HOROVOD_LOCK_HOLD_WARN_SECONDS":
                                     "600"})
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK rank=" in out


@pytest.mark.parametrize("world", [2, 3])
def test_unnamed_eager_collectives_communicate(world):
    """Plain hvd.allreduce/allgather/broadcast (no name) in a
    multi-process world must exchange data, not silently return local
    values."""
    procs, outs = _launch("unnamed_eager", world)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out


@pytest.mark.slow
def test_comms_degradation_alert_under_netdelay(tmp_path, capsys):
    """ISSUE 16 acceptance: a 150 ms netdelay window opening 3 s in must
    trip exactly one ``comms_degraded`` flight event per rank naming the
    host_ring lane (asserted in-worker, tests/mp_worker.py scenario
    comms_degraded), and the merged ``tpurun --postmortem`` over the
    shutdown dumps must render the cross-rank comms report."""
    flight_dir = tmp_path / "flight"
    # after=8 grants the workers' fast phase real headroom over a loaded
    # box's init tail; the worker anchors its own wake-up to its
    # scenario-entry stamp (an upper bound on chaos t0), so the window
    # is guaranteed open when the slow phase starts
    procs, outs = _launch(
        "comms_degraded", 2, timeout=180, extra_env={
            "HOROVOD_FAULT_INJECT": "netdelay:150:after=8",
            "COMMS_DELAY_AFTER": "8.5",
            "HOROVOD_FLIGHT_RECORDER_DIR": str(flight_dir),
        })
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "COMMS_DEGRADED_OK" in out
        assert "OK rank=" in out

    from horovod_tpu import flight_recorder
    dumps = flight_recorder.load_dumps(str(flight_dir))
    assert len(dumps) == 2
    for d in dumps:
        lanes = d["state"]["comms"]["lanes"]
        assert lanes["host_ring"]["degraded_count"] == 1, lanes

    from horovod_tpu.run.run import run_commandline
    assert run_commandline(["--postmortem", str(flight_dir)]) == 0
    out = capsys.readouterr().out
    assert "=== comms report (2 ranks) ===" in out
    assert "degraded host_ring allreduce" in out
    assert "slowest lane: host_ring" in out
