"""Elastic fault-injection acceptance test (ISSUE.md PR 2).

World=3 over the real socket/native transport; the pytest process hosts
the rendezvous HTTP KV store (standing in for the tpurun launcher).
``HOROVOD_FAULT_INJECT=kill:rank=1:step=3`` hard-kills rank 1 inside its
step-3 commit; ranks 0 and 2 must catch WorkersDownError, re-form into a
2-worker generation through the store, roll back to the last commit and
finish all 8 steps with the training invariant (w == step) intact.
"""

import os
import socket
import sys

import pytest

from horovod_tpu.run.rendezvous import RendezvousServer
from horovod_tpu.runtime.native import native_built
from mp_launch import collect, start

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "elastic_worker.py")
ZERO_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "zero_elastic_worker.py")
BUCKET_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bucket_elastic_worker.py")

pytestmark = pytest.mark.skipif(
    not native_built(), reason="native transport not built")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_elastic(world: int, extra_env=None, timeout=240,
                    worker=WORKER):
    rendezvous = RendezvousServer(host="127.0.0.1")
    http_port = rendezvous.start()
    socket_port = _free_port()
    procs, logs = [], []
    try:
        for rank in range(world):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update({
                "HOROVOD_RANK": str(rank),
                "HOROVOD_SIZE": str(world),
                "HOROVOD_CONTROLLER": "socket",
                "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
                "HOROVOD_GLOO_RENDEZVOUS_PORT": str(socket_port),
                "HOROVOD_RENDEZVOUS_HTTP_ADDR": "127.0.0.1",
                "HOROVOD_RENDEZVOUS_HTTP_PORT": str(http_port),
                "HOROVOD_ELASTIC": "1",
                # survivors must notice the dead peer quickly, not after
                # the default 30s verb timeout
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "JAX_PLATFORMS": "cpu",
            })
            env.update(extra_env or {})
            start(procs, logs, [sys.executable, worker], env)
        outs = collect(procs, logs, timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        rendezvous.stop()
    return procs, outs


def test_kill_rank1_at_step3_survivors_finish():
    """The ISSUE.md acceptance scenario: rank 1 killed at step 3 of an
    8-step run; ranks 0 and 2 restore from the last commit and complete
    all 8 steps in a 2-worker generation."""
    procs, outs = _launch_elastic(
        3, extra_env={
            "HOROVOD_FAULT_INJECT": "kill:rank=1:step=3:code=17",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        })
    # the planted death exits with the injected code
    assert procs[1].returncode == 17, outs[1]
    for i in (0, 2):
        assert procs[i].returncode == 0, (i, outs[i])
        assert "DONE" in outs[i], (i, outs[i])
        assert "step=8" in outs[i], (i, outs[i])
        assert "w=8" in outs[i], (i, outs[i])
        assert "size=2" in outs[i], (i, outs[i])
        # metrics satellite: the restart was counted
        restarts = float(outs[i].split(
            "elastic_restarts_total=")[1].split()[0])
        assert restarts >= 1, (i, outs[i])


def test_zero_sharded_state_survives_reform():
    """ZeRO-1 acceptance (ISSUE.md PR 5): the SHARDED optimizer state
    must survive rank 1 dying at step 3 — ``ArrayState.sync`` resyncs
    sharded leaves collectively (zero.resync) instead of broadcasting
    rank 0's shard, the state re-shards to the 2-worker layout, and the
    training invariant (w == step, every element) holds through the
    rollback."""
    procs, outs = _launch_elastic(
        3, extra_env={
            "HOROVOD_FAULT_INJECT": "kill:rank=1:step=3:code=17",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        }, worker=ZERO_WORKER)
    assert procs[1].returncode == 17, outs[1]
    for i in (0, 2):
        assert procs[i].returncode == 0, (i, outs[i])
        assert "step=8" in outs[i], (i, outs[i])
        assert "w=8" in outs[i], (i, outs[i])
        assert "size=2" in outs[i], (i, outs[i])
        assert "shard_world=2" in outs[i], (i, outs[i])
        restarts = float(outs[i].split(
            "elastic_restarts_total=")[1].split()[0])
        assert restarts >= 1, (i, outs[i])


def test_kill_mid_backward_with_buckets_in_flight():
    """Bucket-wise gradient release under elastic failure (ISSUE 12):
    rank 1 dies *inside* its second bucket release at step 3 — the first
    bucket's allreduce is already in flight and later buckets never
    arrive. The survivors' gather must fail every orphaned bucket token
    with WorkersDownError, the re-formed 2-worker generation finishes on
    the SAME plan object, and no fusion-buffer lease leaks across the
    failure (the worker exits 4 if any slab stays checked out)."""
    procs, outs = _launch_elastic(
        3, extra_env={
            "BUCKET_KILL_STEP": "3",
            "BUCKET_KILL_RANK": "1",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        }, worker=BUCKET_WORKER)
    assert procs[1].returncode == 17, outs[1]
    for i in (0, 2):
        assert procs[i].returncode == 0, (i, outs[i])
        assert "DONE" in outs[i], (i, outs[i])
        assert "step=6" in outs[i], (i, outs[i])
        assert "w=6" in outs[i], (i, outs[i])
        assert "size=2" in outs[i], (i, outs[i])
        assert "leases_leaked=0" in outs[i], (i, outs[i])
        # the bucketed path really exercised the wire: 3 buckets x steps
        released = int(outs[i].split("wire_released=")[1].split()[0])
        assert released >= 3 * 6, (i, outs[i])


def test_no_fault_runs_clean():
    """Same harness without injection: the elastic wrapper must be
    transparent when nothing fails (no spurious re-forms, generation 0)."""
    procs, outs = _launch_elastic(2, timeout=180)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "step=8" in out, out
        assert "generation=0" in out, out
        assert "elastic_restarts_total=0" in out, out
