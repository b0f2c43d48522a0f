"""What the multi-process tests of the socket controller plane share (not
collected): ``tests/mp_worker.py`` started once a rank, wired together by
the launcher's env contract (HOROVOD_RANK/SIZE + rendezvous address),
each rank driving the TCP SocketController + native ring data plane."""

import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest

from horovod_tpu.runtime.native import native_built

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "mp_worker.py")

needs_native = pytest.mark.skipif(
    not native_built(), reason="native transport not built")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start(procs, logs, argv, env, **popen):
    """One more rank: ``argv`` under ``env``, what it writes (both
    streams) to a file of its own and not to a pipe. A pipe holds 64 KB,
    and a rank that nobody reads yet blocks on a full one while its
    peers wait for it: every executable read back from the run's compile
    cache logs 11-14 KB (jaxlib 0.9.0's ``cpu_aot_loader``), and a
    launcher that reads rank 0 to its end first hung for its whole wait
    (PR 38). ``collect`` reads the files."""
    logs.append(tempfile.TemporaryFile("w+"))
    procs.append(subprocess.Popen(
        argv, env=dict(env, PYTHONFAULTHANDLER="1"), stdout=logs[-1],
        stderr=subprocess.STDOUT, **popen))


def collect(procs, logs, timeout):
    """Wait for every rank that ``start`` made, ``timeout`` seconds for
    all of them together, and return what each wrote. A rank still alive
    then is sent SIGABRT, so that faulthandler prints every thread's
    stack, and the failure shows what each rank said: a hang names where
    it hangs."""
    deadline = time.monotonic() + timeout
    hung = []
    for rank, p in enumerate(procs):
        try:
            p.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            hung.append(rank)
    for rank in hung:
        procs[rank].send_signal(signal.SIGABRT)
        procs[rank].wait(timeout=10)
    outs = []
    for log in logs:
        log.seek(0)
        outs.append(log.read())
        log.close()
    assert not hung, (
        f"ranks {hung} of {len(procs)} still ran after {timeout} s:\n"
        + "\n".join(f"--- rank {r}\n{out}" for r, out in enumerate(outs)))
    return outs


def launch(scenario: str, world: int, extra_env=None, timeout=180):
    """``timeout`` is a wait for a hang, not a budget: the plain cases
    take 10-30 s beside five busy workers, three ranks importing JAX
    first."""
    port = free_port()
    procs, logs = [], []
    try:
        for rank in range(world):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # workers don't need 8 fake devices
            env.update({
                "HOROVOD_RANK": str(rank),
                "HOROVOD_SIZE": str(world),
                "HOROVOD_CONTROLLER": "socket",
                "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
                "HOROVOD_GLOO_RENDEZVOUS_PORT": str(port),
                "JAX_PLATFORMS": "cpu",
            })
            env.update(extra_env or {})
            start(procs, logs, [sys.executable, WORKER, scenario], env)
        outs = collect(procs, logs, timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs
