"""ResNet-50 step-time experiment harness (round-2 perf work).

Sweeps TPU compiler options over the SAME lowered bench program —
``jax.jit(...).lower(...).compile(compiler_options=...)`` forwards the
options to the TPU compiler (verified: unknown options are rejected) —
and times each executable with the measurement protocol from
docs/benchmarks.md (multi-step rounds inside one program, scalar-readback
sync, interleaved A/B).

    python tools/perf_lab.py            # run the experiment matrix
    python tools/perf_lab.py '{"xla_tpu_scoped_vmem_limit_kib": "65536"}'
"""

import json
import os
import sys
import time

import jax
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import training  # noqa: E402
from horovod_tpu.models.resnet import ResNet50  # noqa: E402

BATCH = int(os.environ.get("LAB_BATCH", "128"))
STEPS = int(os.environ.get("LAB_STEPS", "20"))
ROUNDS = int(os.environ.get("LAB_ROUNDS", "4"))

# Options the remote TPU compiler accepted in round-2 probing (unknown
# names are rejected by the remote compile with HTTP 500, so additions
# are cheap to validate).
EXPERIMENTS = [
    ("baseline", {}),
    ("rwb_off", {"xla_tpu_rwb_fusion": "false"}),
    ("rwb_sched", {"xla_tpu_rwb_fusion": "false",
                   "xla_tpu_enable_all_experimental_scheduler_features":
                   "true"}),
    ("rwb_barrier", {"xla_tpu_rwb_fusion": "false",
                     "xla_tpu_aggressive_opt_barrier_removal": "true"}),
    ("sched_only", {"xla_tpu_enable_all_experimental_scheduler_features":
                    "true"}),
]


def main():
    hvd.init()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    state = training.create_train_state(model, opt, (1, 224, 224, 3))
    round_fn, batch_sharding = training.make_train_round(
        model, opt, steps=STEPS, donate=False)

    rng = np.random.RandomState(0)
    images = jax.device_put(
        rng.uniform(-1, 1, (BATCH, 224, 224, 3)).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        rng.randint(0, 1000, (BATCH,)).astype(np.int32), batch_sharding)
    args = (state.params, state.batch_stats, state.opt_state, images, labels)

    print("lowering...", file=sys.stderr, flush=True)
    lowered = round_fn.lower(*args)

    if len(sys.argv) > 1:
        experiments = [("cli", json.loads(sys.argv[1]))]
    else:
        experiments = EXPERIMENTS

    compiled = {}
    for name, options in experiments:
        t0 = time.perf_counter()
        try:
            compiled[name] = lowered.compile(
                compiler_options=options or None)
            print(f"compiled {name} in {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception as e:
            print(f"REJECTED {name}: {str(e)[:160]}", file=sys.stderr,
                  flush=True)

    # Interleave all surviving executables round-robin (A/B protocol:
    # run-to-run drift hits every variant equally). Each executable
    # chains ITS OWN evolving state forward, so no two timed calls see
    # the same inputs, and every timed call ends in a scalar readback as
    # the sync point.
    states = {}
    for name, ex in compiled.items():  # warmup + per-exp state
        t0 = time.perf_counter()
        loss, p, s, o = ex(*args)
        float(loss)
        print(f"warmup {name}: {time.perf_counter() - t0:.2f}s",
              file=sys.stderr, flush=True)
        states[name] = (p, s, o)
    results = {name: [] for name in compiled}
    for r in range(ROUNDS):
        for name, ex in compiled.items():
            p, s, o = states[name]
            t0 = time.perf_counter()
            loss, p, s, o = ex(p, s, o, images, labels)
            float(loss)
            dt = time.perf_counter() - t0
            states[name] = (p, s, o)
            results[name].append(BATCH * STEPS / dt)
    for name in results:
        rates = results[name]
        print(json.dumps({
            "exp": name, "img_per_sec": round(float(np.median(rates)), 1),
            "all": [round(r, 1) for r in rates]}), flush=True)


if __name__ == "__main__":
    main()
