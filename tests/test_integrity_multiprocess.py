"""Numerical-integrity acceptance over the real socket transport
(ISSUE.md PR 10).

Fast (tier-1) cells prove the two halves of the integrity plane loop
end to end with real worker processes:

* a one-shot bit flip on rank 1's copy of the 5th allreduce result is
  detected by the per-dispatch digest exchange, every rank rolls back
  IN PLACE (generation stays 0 — no process restart, no re-form) to
  the last checkpoint and replays to the exact final weights;
* a one-shot NaN that reaches every rank's reduced gradient (digests
  off) is skipped in lockstep by the step-level spike guard, costing
  one retried step and nothing else.

The full scenario matrix (postmortem culprit attribution, manifest
verification) lives in tools/chaos_matrix.py; both integrity cells are
repeated from there slow-marked.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from horovod_tpu.run.rendezvous import RendezvousServer
from horovod_tpu.runtime.native import native_built
from mp_launch import collect, start

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tools", "chaos_worker.py")

pytestmark = pytest.mark.skipif(
    not native_built(), reason="native transport not built")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(world, extra_env, timeout=240):
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
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "JAX_PLATFORMS": "cpu",
            })
            env.update(extra_env)
            start(procs, logs, [sys.executable, WORKER], env)
        outs = collect(procs, logs, timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        rendezvous.stop()
    return procs, outs


def _result(out):
    for line in out.splitlines():
        if line.startswith("CHAOS_RESULT "):
            return json.loads(line[len("CHAOS_RESULT "):])
    raise AssertionError("no CHAOS_RESULT line in:\n" + out[-2000:])


def test_bitflip_digest_detects_and_rolls_back_in_place(tmp_path):
    """SDC on the wire: the digest vote fires, every rank restores the
    step-4 checkpoint without leaving its process, and the replay ends
    bit-identical to an uninjected run (w == 8.0 exactly)."""
    procs, outs = _launch(3, {
        "HOROVOD_FAULT_INJECT": "bitflip:1:after=4",
        "HOROVOD_INTEGRITY": "1",
        "HOROVOD_INTEGRITY_INTERVAL": "1",
        "HOROVOD_CKPT_DIR": str(tmp_path / "ckpts"),
        "HOROVOD_CKPT_ASYNC": "0",
        "HOROVOD_ELASTIC_MIN_WORKERS": "3",
    })
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (i, out[-3000:])
        res = _result(out)
        assert res["step"] == 8, (i, res)
        assert res["w"] == 8.0, (i, res)  # bit-identical replay
        assert res["generation"] == 0, (i, res)  # no restart, no re-form
        assert res["integrity_violations"] >= 1, (i, res)
        assert res["rollbacks"] >= 1, (i, res)
        assert res["skipped_steps"] == 0, (i, res)


def test_nan_spike_guard_skips_step_in_lockstep():
    """Non-finite payload with digests off: the EWMA spike guard on the
    reduced gradient skips the poisoned step on every rank (nothing
    applied, nothing committed) and the retry converges exactly."""
    procs, outs = _launch(2, {
        "HOROVOD_FAULT_INJECT": "nan:1:after=4",
        "HOROVOD_INTEGRITY": "1",
        "HOROVOD_INTEGRITY_INTERVAL": "0",
        "CHAOS_INTEGRITY_GUARD": "1",
        "HOROVOD_ELASTIC_MIN_WORKERS": "2",
    }, timeout=180)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (i, out[-3000:])
        res = _result(out)
        assert res["step"] == 8, (i, res)
        assert res["w"] == 8.0, (i, res)
        assert res["skipped_steps"] == 1, (i, res)
        assert res["rollbacks"] == 0, (i, res)


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["integrity_bitflip_rollback",
                                  "integrity_nan_skipstep"])
def test_chaos_matrix_integrity_cells(cell):
    """Full matrix cells: adds manifest verification and the merged
    flight-recorder postmortem naming the flipped rank."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_matrix.py"),
         "--only", cell],
        capture_output=True, text=True, timeout=420,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
