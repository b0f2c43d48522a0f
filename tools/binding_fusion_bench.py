#!/usr/bin/env python
"""Does tensor fusion actually engage THROUGH the framework bindings?

VERDICT r3 ask 6: ``tools/control_plane_bench.py`` proves the runtime's
fusion/cache win by driving the named numpy API directly — but a user
reaches the runtime through the torch hook optimizer or the TF gradient
tape, and nothing measured whether those paths arrive at the runtime as
a fusable burst or as serialized one-at-a-time ops (they did serialize
through TF until the grouped-allreduce bridge; this harness is the
regression net).

A ~50-parameter model steps at np=2 through
  (a) the torch path: hvd.DistributedOptimizer, gradient hooks firing
      async in-place allreduces during backward, step() synchronizing
      (torch/__init__.py:60-170), and
  (b) the TF path: tf.GradientTape -> hvd.DistributedGradientTape,
      dense grads riding the grouped-allreduce py_function
      (tensorflow/__init__.py _make_allreduce_grads_fn),
reporting the DETERMINISTIC per-step protocol counters (ring-kernel
exchanges + control-plane bytes from the native transport) for the
default config vs HOROVOD_FUSION_THRESHOLD=0. Wall time on a 1-core CI
box measures the scheduler; the counters are box-independent.

Run:  python tools/binding_fusion_bench.py [--np 2]
Emits one JSON object on stdout.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

# these host-side processes stay on the CPU (and off the chip, which
# belongs to one process): set before anything imports jax
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PARAMS = 50     # small tensors per step (the fusion-relevant regime)
STEPS = 10
WARMUP = 2


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def worker() -> None:
    sys.path.insert(0, REPO)
    import horovod_tpu.torch as thvd
    import torch

    from horovod_tpu.core import state

    thvd.init()
    rank = thvd.rank()
    results = {}

    def measure(label, one_step):
        for _ in range(WARMUP):
            one_step()
        net = state.global_state().runtime.controller.net
        ctrl0, ex0 = net.ctrl_bytes_sent(), net.exchange_calls()
        t0 = time.perf_counter()
        for _ in range(STEPS):
            one_step()
        dt = time.perf_counter() - t0
        d_ex = net.exchange_calls() - ex0
        d_ctrl = net.ctrl_bytes_sent() - ctrl0
        if d_ex < 0 or d_ctrl < 0:
            # counters read 0 from a closed Comm handle — the world shut
            # down mid-measure (a peer died); fail loudly, never report
            # garbage deltas
            raise RuntimeError(
                f"{label}: counter went backwards (d_ex={d_ex}, "
                f"d_ctrl={d_ctrl}) — world shut down mid-measure")
        results[label] = {
            "exchanges_per_step": d_ex / STEPS,
            "ctrl_bytes_per_step": d_ctrl / STEPS,
            "ms_per_step": dt / STEPS * 1e3,
        }

    # (a) torch hook optimizer: N_PARAMS small weights, hooks fire
    # during backward, step() syncs
    torch.manual_seed(0)  # identical init everywhere
    model = torch.nn.ModuleList(
        [torch.nn.Linear(9, 1) for _ in range(N_PARAMS // 2)])
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1e-3),
        named_parameters=model.named_parameters())
    x = torch.randn(4, 9)

    def torch_step():
        opt.zero_grad()
        loss = sum(m(x).sum() for m in model) * (rank + 1)
        loss.backward()
        opt.step()

    measure("torch", torch_step)

    # (b) TF tape: same parameter count through DistributedGradientTape
    import tensorflow as tf

    import horovod_tpu.tensorflow as tfhvd

    weights = [tf.Variable(tf.fill([7 + (i % 5)], float(i + 1)))
               for i in range(N_PARAMS)]

    def tf_step():
        with tf.GradientTape() as tape:
            loss = tf.add_n([tf.reduce_sum(w * w) * (rank + 1)
                             for w in weights])
        dtape = tfhvd.DistributedGradientTape(tape)
        grads = dtape.gradient(loss, weights)
        for w, g in zip(weights, grads):
            w.assign_sub(1e-3 * g)

    measure("tf", tf_step)

    # Quiesce before shutdown: shutdown is NOT a barrier (reference
    # semantics match), so a rank that finishes first and closes its
    # sockets kills a peer whose last burst completion is still in
    # flight — observed as this tool's flaky negative-counter /
    # shut-down-mid-measure failures. A synchronous allreduce returns
    # only once every prior op on the ordered lane completed on ALL
    # ranks, so after it no rank has in-flight work.
    thvd.allreduce(torch.zeros(1), name="fb.quiesce")
    thvd.shutdown()
    if rank == 0:
        print("RESULTS " + json.dumps(results), flush=True)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def launch(world: int, extra_env: dict, timeout: float = 420.0):
    port = _free_port()
    procs, logs = [], []
    for rank in range(world):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(world),
            "HOROVOD_CONTROLLER": "socket",
            "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
            "HOROVOD_GLOO_RENDEZVOUS_PORT": str(port),
            "JAX_PLATFORMS": "cpu",
        })
        env.update(extra_env)
        # a file, not a pipe: the ranks wait on each other, and one
        # that nobody reads yet would block on a full pipe (64 KB)
        logs.append(tempfile.TemporaryFile("w+"))
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            env=env, stdout=logs[-1], stderr=subprocess.STDOUT))
    outs = []
    try:
        for p, log in zip(procs, logs):
            p.wait(timeout=timeout)
            log.seek(0)
            outs.append(log.read())
            if p.returncode != 0:
                raise RuntimeError(
                    f"worker failed rc={p.returncode}:\n{outs[-1]}")
    finally:
        for p, log in zip(procs, logs):
            if p.poll() is None:
                p.kill()
            log.close()
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULTS "):
                return json.loads(line[len("RESULTS "):])
    raise RuntimeError("no RESULTS line from rank 0:\n" + "\n".join(outs))


def main(world: int) -> dict:
    fused = launch(world, {})
    unfused = launch(world, {"HOROVOD_FUSION_THRESHOLD": "0"})
    out = {"world": world, "params_per_step": N_PARAMS}
    for path in ("torch", "tf"):
        f, u = fused[path], unfused[path]
        out[path] = {
            "exchanges_per_step_fused": round(f["exchanges_per_step"], 2),
            "exchanges_per_step_unfused": round(u["exchanges_per_step"], 2),
            "fusion_dispatch_reduction_x": round(
                u["exchanges_per_step"]
                / max(f["exchanges_per_step"], 1e-9), 2),
            "ctrl_bytes_per_step_fused": round(f["ctrl_bytes_per_step"], 1),
            "ctrl_bytes_per_step_unfused": round(
                u["ctrl_bytes_per_step"], 1),
            "ms_per_step_fused": round(f["ms_per_step"], 2),
            "ms_per_step_unfused": round(u["ms_per_step"], 2),
        }
    return out


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--worker", action="store_true")
    parser.add_argument("--np", type=int, default=2)
    cli = parser.parse_args()
    if cli.worker:
        worker()
    else:
        print(json.dumps(main(cli.np)), flush=True)
