"""Multiprocess network-chaos acceptance test (one fast scenario).

Runs the ``flaky_negotiate`` cell of the chaos matrix inline under
pytest: two real worker processes train over the socket controller
while every control-plane transport op fails with probability 0.3 for
the first seconds of the run. Training must complete with zero lost
steps (``w == step == TOTAL``) and a nonzero
``horovod_net_retries_total`` — proving the retry layer, not luck,
bridged the faults. The full fault-mode × phase matrix (kv outage
during re-form, permanent partition + collective timeout + postmortem
attribution, netdelay) lives in tools/chaos_matrix.py.

Marked slow: tier-1 already runs within a few percent of its wall-clock
budget, and the in-process halves of this coverage (retry/backoff,
kv_outage bridging, chaos grammar) are tier-1 via tests/test_resilience.py.
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
    pytest.mark.slow,
    pytest.mark.skipif(not native_built(),
                       reason="native transport not built"),
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tools", "chaos_worker.py")
TOTAL = 6


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_flaky_negotiate_completes_with_retries(tmp_path):
    world = 2
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
                "HOROVOD_ELASTIC_MIN_WORKERS": str(world),
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "HOROVOD_FAULT_INJECT": "flaky:0.3:seconds=4",
                "HOROVOD_NET_MAX_RETRIES": "12",
                "HOROVOD_FLIGHT_RECORDER_DIR": str(tmp_path),
                "CHAOS_TOTAL_STEPS": str(TOTAL),
                "JAX_PLATFORMS": "cpu",
            })
            start(procs, logs, [sys.executable, WORKER], env)
        results = {}
        outs = collect(procs, logs, 120)
        for rank, (proc, out) in enumerate(zip(procs, outs)):
            assert proc.returncode == 0, \
                f"rank {rank} exited {proc.returncode}:\n{out[-2000:]}"
            for line in out.splitlines():
                if line.startswith("CHAOS_RESULT "):
                    results[rank] = json.loads(
                        line[len("CHAOS_RESULT "):])
        assert sorted(results) == list(range(world))
        for rank, res in results.items():
            assert res["step"] == TOTAL, res
            assert abs(res["w"] - TOTAL) <= 1e-4, res
        # the faults were real and the retry layer absorbed them
        assert sum(r["net_retries_total"] for r in results.values()) > 0
        assert sum(r["net_gave_up_total"] for r in results.values()) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
