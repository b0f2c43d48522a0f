"""Fast-tier repeat of the hierarchy chaos cell (ISSUE 18 satellite).

A four-rank hierarchical world (2 groups of 2, cross hop throttled with
``netdelay:hop=cross``) loses rank 3 at step 2: the survivors re-form
at world 3, where 3 % 2 != 0 — the executor must recompute the plan for
the new world (flat fallback, not a wedge on the stale 2x2 grouping
keyed to the dead transport) and finish with zero lost steps. The
richer cell — kills landing a six-rank world on a REGROUPABLE world 4
where hierarchy re-enables — runs in tools/chaos_matrix.py
(``hier_cross_kill``); this is the tier-1 smoke of the same seam.
"""

import json
import os
import socket
import sys

import pytest

from horovod_tpu.run.rendezvous import RendezvousServer
from horovod_tpu.runtime.native import native_built
from mp_launch import collect, start

pytestmark = [
    pytest.mark.skipif(not native_built(),
                       reason="native transport not built"),
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tools", "chaos_worker.py")
TOTAL = 5


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_rank_killed_mid_cross_exchange_reforms_and_finishes(tmp_path):
    world = 4
    server = RendezvousServer(host="127.0.0.1")
    http_port = server.start()
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
                "HOROVOD_ELASTIC_MIN_WORKERS": "3",
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
                "HOROVOD_HIERARCHY_GROUP_SIZE": "2",
                # the throttled cross hop widens the exchange window so
                # the kill lands while survivors are inside it
                "HOROVOD_FAULT_INJECT":
                    "netdelay:3:hop=cross;kill:rank=3:step=2:code=17",
                "HOROVOD_FLIGHT_RECORDER_DIR": str(tmp_path),
                "CHAOS_TOTAL_STEPS": str(TOTAL),
                "JAX_PLATFORMS": "cpu",
            })
            start(procs, logs, [sys.executable, WORKER], env)
        results = {}
        outs = collect(procs, logs, 180)
        for rank, (proc, out) in enumerate(zip(procs, outs)):
            want = 17 if rank == 3 else 0
            assert proc.returncode == want, \
                f"rank {rank} exited {proc.returncode}:\n{out[-2000:]}"
            for line in out.splitlines():
                if line.startswith("CHAOS_RESULT "):
                    results[rank] = json.loads(
                        line[len("CHAOS_RESULT "):])
        assert sorted(results) == [0, 1, 2]  # rank 3 died before report
        for rank, res in results.items():
            # zero lost steps across the re-form
            assert res["step"] == TOTAL, res
            assert abs(res["w"] - TOTAL) <= 1e-4, res
            assert res["generation"] >= 1, res
            # world 3 cannot split into groups of 2: the recomputed plan
            # fell back flat instead of wedging on the stale grouping
            assert res["hier_enabled"] is False, res
        # the throttled cross hop actually fired before the re-form
        assert sum(r["chaos_injected_total"]
                   for r in results.values()) > 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
