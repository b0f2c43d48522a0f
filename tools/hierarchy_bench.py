#!/usr/bin/env python
"""Hierarchy benchmark — flat vs hierarchical host collectives A/B.

ISSUE 18 tentpole evidence: on a multi-host world whose cross-group link
is slower than the intra-group one (here simulated with
``HOROVOD_FAULT_INJECT=netdelay:<ms>:hop=cross`` — the sleep scales with
the number of slow-link crossings each algorithm actually performs, see
utils/resilience.py), the two-level decomposition (intra-group
reduce-scatter -> cross-group exchange over 1/G of the bytes -> intra
allgather) plus an fp16 wire codec on JUST the slow hop should beat the
flat ring end-to-end. Without netdelay (loopback sockets, every hop
equal) flat vs hierarchical should be near parity — the hierarchy only
pays off when the topology is actually lopsided, and the bench reports
both so that claim is checkable.

Phases per payload size (np ranks, group size 2, real multi-process
world over the native wire like tools/control_plane_bench.py):

  * flat            — seed ring allreduce
  * hier            — hierarchical, no compression
  * hier+fp16       — hierarchical, bf16 wire on the cross hop
  * each of the above again under netdelay on the cross hop
  * autotuned       — full mode only: HOROVOD_AUTOTUNE=1 under netdelay
                      for a fixed step budget, then timed; reported as a
                      ratio vs the hand-tuned (hier+fp16) configuration
                      (acceptance: converges within ~5%)

Run:  python tools/hierarchy_bench.py [--np 4] [--tiny]
Emits one JSON object on stdout. The throttled-hop speedup row is
emitted with unit "x": higher is better.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# f32 element counts per payload; tiny = the tier-1 smoke (numbers
# meaningless, shape of the artifact identical)
SIZES = (65536, 1 << 20)
TINY_SIZES = (16384,)
STEPS, WARMUP = 10, 3
TINY_STEPS, TINY_WARMUP = 4, 2
NETDELAY_MS = 3.0
TINY_NETDELAY_MS = 2.0
# fixed autotune step budget: categorical phase (3 knobs x 2 values x 5
# samples) + warmup + BO samples all fit well inside this, and a FIXED
# count keeps every rank's enqueue sequence identical (breaking on the
# locally-observed freeze bit could skew op counts across ranks by a
# cycle and deadlock the collective)
AUTOTUNE_BUDGET_STEPS = 160


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def worker() -> None:
    sys.path.insert(0, REPO)
    import horovod_tpu as hvd
    from horovod_tpu.core import state

    hvd.init()
    rank = hvd.rank()
    sizes = json.loads(os.environ["HIER_BENCH_SIZES"])
    steps = int(os.environ["HIER_BENCH_STEPS"])
    warmup = int(os.environ["HIER_BENCH_WARMUP"])
    tune_budget = int(os.environ.get("HIER_BENCH_TUNE_BUDGET", "0"))

    results = {}
    if tune_budget:
        # drive the tuner through its schedule on the largest payload;
        # the timed windows below then measure the converged config
        a = np.ones(int(sizes[-1]), np.float32)
        for _ in range(tune_budget):
            hvd.allreduce(a, name="tune/x")
        rt = state.global_state().runtime
        results["autotune_frozen"] = not rt._autotune_active
        pm = rt.param_manager
        if pm is not None:  # coordinator
            results["autotune_best"] = {
                "hierarchical_allreduce":
                    bool(pm.best.hierarchical_allreduce),
                "hierarchy_compression": pm.best.hierarchy_compression,
                "score": round(float(pm.best_score), 3),
            }
    for n in sizes:
        a = np.ones(int(n), np.float32)
        name = f"p{n}"
        for _ in range(warmup):
            hvd.allreduce(a, name=name)
        t0 = time.perf_counter()
        for _ in range(steps):
            hvd.allreduce(a, name=name)
        results[str(n)] = (time.perf_counter() - t0) / steps
    hvd.shutdown()
    if rank == 0:
        print("RESULTS " + json.dumps(results), flush=True)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def launch(world: int, extra_env: dict, timeout: float = 600.0):
    port = _free_port()
    procs = []
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
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            if p.returncode != 0:
                raise RuntimeError(
                    f"worker failed rc={p.returncode}:\n{out}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULTS "):
                return json.loads(line[len("RESULTS "):])
    raise RuntimeError("no RESULTS line from rank 0:\n" + "\n".join(outs))


def main(world: int, tiny: bool = False) -> dict:
    if world < 4:
        raise SystemExit("--np must be >= 4 (two groups of two)")
    sizes = TINY_SIZES if tiny else SIZES
    steps, warmup = (TINY_STEPS, TINY_WARMUP) if tiny else (STEPS, WARMUP)
    delay_ms = TINY_NETDELAY_MS if tiny else NETDELAY_MS
    base = {
        "HIER_BENCH_SIZES": json.dumps(list(sizes)),
        "HIER_BENCH_STEPS": str(steps),
        "HIER_BENCH_WARMUP": str(warmup),
    }
    flat_env = {"HOROVOD_HIERARCHICAL_ALLREDUCE": "0", **base}
    hier_env = {"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
                "HOROVOD_HIERARCHY_GROUP_SIZE": "2", **base}
    comp_env = {**hier_env, "HOROVOD_HIERARCHY_COMPRESSION": "fp16"}
    netdelay = {"HOROVOD_FAULT_INJECT": f"netdelay:{delay_ms}:hop=cross"}

    phases = {
        "flat": launch(world, flat_env),
        "hier": launch(world, hier_env),
        "hier_fp16": launch(world, comp_env),
        "flat_netdelay": launch(world, {**flat_env, **netdelay}),
        "hier_netdelay": launch(world, {**hier_env, **netdelay}),
        "hier_fp16_netdelay": launch(world, {**comp_env, **netdelay}),
    }
    big = str(sizes[-1])
    out = {
        "world": world,
        "group_size": 2,
        "netdelay_ms": delay_ms,
        "sizes": list(sizes),
        "us_per_op": {
            ph: {s: round(r[s] * 1e6, 1) for s in map(str, sizes)}
            for ph, r in phases.items()
        },
        # the headline gates: hierarchical win on the throttled hop
        # (higher is better), near-parity on the uniform loopback wire
        "throttled_hop_speedup_x": round(
            phases["flat_netdelay"][big]
            / max(phases["hier_fp16_netdelay"][big], 1e-9), 2),
        "uniform_wire_ratio_x": round(
            phases["flat"][big] / max(phases["hier"][big], 1e-9), 2),
    }
    if tiny:
        out["tiny"] = True
    else:
        # the autotuner, started flat + uncompressed, must find the
        # hierarchical+compressed configuration on its own under the
        # throttled cross hop and land within ~5% of hand-tuned
        tuned = launch(world, {
            **flat_env, **netdelay,
            "HIER_BENCH_TUNE_BUDGET": str(AUTOTUNE_BUDGET_STEPS),
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "4",
        }, timeout=900.0)
        out["autotune_frozen"] = tuned.get("autotune_frozen")
        out["autotune_best"] = tuned.get("autotune_best")
        out["autotuned_vs_hand_tuned_x"] = round(
            phases["hier_fp16_netdelay"][big]
            / max(tuned[big], 1e-9), 2)
    return out


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--worker", action="store_true")
    parser.add_argument("--np", type=int, default=4)
    parser.add_argument("--tiny", action="store_true",
                        help="one small size, few steps, no autotune "
                             "phase — the tier-1 smoke mode")
    cli = parser.parse_args()
    if cli.worker:
        worker()
    else:
        print(json.dumps(main(cli.np, tiny=cli.tiny)), flush=True)
