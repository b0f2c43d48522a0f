"""ZeRO-2 elastic fault-injection acceptance (ISSUE 20 satellite).

Fast-tier repeat of the chaos-matrix cell ``zero2_kill_mid_reducescatter``:
world=3 over the real socket/native transport, rank 1 hard-killed
*inside* a stage-2 bucket reduce-scatter (bucket 0's reduce-scatter
already in flight, later buckets never released). The survivors'
gather must fail the orphaned stage-2 tokens with WorkersDownError,
``@elastic.run`` re-forms them into a 2-worker generation,
``zero.resync`` rebuilds the sharded AdamW shards under the new world,
and training reaches the expected weights (w == step, every element)
with zero leaked fusion-buffer leases.
"""

import os
import sys

import pytest

from horovod_tpu.run.rendezvous import RendezvousServer
from horovod_tpu.runtime.native import native_built
from mp_launch import collect, free_port, start

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "zero2_elastic_worker.py")

pytestmark = pytest.mark.skipif(
    not native_built(), reason="native transport not built")


def _launch(world, extra_env=None, timeout=90):
    rendezvous = RendezvousServer(host="127.0.0.1")
    http_port = rendezvous.start()
    socket_port = free_port()
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
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "JAX_PLATFORMS": "cpu",
            })
            env.update(extra_env or {})
            start(procs, logs, [sys.executable, WORKER], env)
        # a hang fails within the wait with every rank's output and
        # stacks (mp_launch.collect): that is how PR 38 found the
        # survivors' deadlock that this test used to meet under load
        outs = collect(procs, logs, timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        rendezvous.stop()
    return procs, outs


def test_zero2_kill_mid_reducescatter_survivors_reshard():
    procs, outs = _launch(
        3, extra_env={
            "ZERO2_KILL_STEP": "3",
            "ZERO2_KILL_RANK": "1",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        })
    # the planted mid-reduce-scatter death exits with code 17
    assert procs[1].returncode == 17, outs[1]
    for i in (0, 2):
        assert procs[i].returncode == 0, (i, outs[i])
        assert "DONE" in outs[i], (i, outs[i])
        assert "step=6" in outs[i], (i, outs[i])
        assert "w=6" in outs[i], (i, outs[i])
        assert "size=2" in outs[i], (i, outs[i])
        # resync re-sharded the optimizer for the 2-worker generation
        assert "shard_world=2" in outs[i], (i, outs[i])
        # every failed stage-2 token returned its slab
        assert "leases_leaked=0" in outs[i], (i, outs[i])
        # the stage-2 wire was really exercised: 3 buckets per step
        released = int(outs[i].split("wire_released=")[1].split()[0])
        assert released >= 3 * 6, (i, outs[i])
