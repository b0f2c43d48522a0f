#!/usr/bin/env python
"""Where does the ResNet-50 step actually go? Forward vs full-step split.

Complements the conv microbenchmarks (tools/pallas_conv_bn.py): isolated
3x3 convs run at 75-100% MFU with free stats epilogues, so the
end-to-end ~15% MFU must live in the backward pass + elementwise
structure. This measures, on the bench model itself (batch 128, bf16):

  * forward-only inference step (train=False, no stats update)
  * forward + loss + BN-stats (train=True forward)
  * the full training step (fwd + bwd + SGD update) — bench.py's op

Same scan-chain + scalar-readback + salted-inputs protocol as the other
tools (fresh inputs per timed call; not re-examined on a local chip).
"""

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, "/root/repo")

from horovod_tpu.models.resnet import ResNet50  # noqa: E402

BATCH = 128
ITERS = 20
ROUNDS = 6
FWD_FLOPS = BATCH * 4.089e9
TRAIN_FLOPS = 3 * FWD_FLOPS


def main():
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.uniform(-1, 1, (BATCH, 224, 224, 3)).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, (BATCH,)).astype(np.int32))
    variables = model.init(jax.random.PRNGKey(0), images[:1], train=False)
    params, stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(p, s, x, y):
        logits, mut = model.apply({"params": p, "batch_stats": s}, x,
                                  train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1)), \
            mut["batch_stats"]

    @partial(jax.jit, static_argnames="iters")
    def infer_chain(p, s, x, salt, iters):
        x = x + salt

        def body(x, _):
            logits = model.apply({"params": p, "batch_stats": s}, x,
                                 train=False)
            return x + 1e-6 * jnp.mean(logits), logits[0, 0]

        x, outs = jax.lax.scan(body, x, None, length=iters)
        return outs[-1]

    @partial(jax.jit, static_argnames="iters")
    def fwd_train_chain(p, s, x, y, salt, iters):
        x = x + salt

        def body(carry, _):
            x, s = carry
            loss, new_s = loss_fn(p, s, x, y)
            return (x + 1e-6 * loss, new_s), loss

        (x, s), losses = jax.lax.scan(body, (x, s), None, length=iters)
        return losses[-1]

    @partial(jax.jit, static_argnames="iters")
    def train_chain(p, s, o, x, y, salt, iters):
        x = x + salt

        def body(carry, _):
            p, s, o = carry
            (loss, new_s), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p, s, x, y)
            upd, o = tx.update(g, o, p)
            p = optax.apply_updates(p, upd)
            return (p, new_s, o), loss

        (p, s, o), losses = jax.lax.scan(body, (p, s, o), None,
                                         length=iters)
        return losses[-1]

    salt_n = [0]

    def fresh_salt():
        salt_n[0] += 1
        return jnp.float32(salt_n[0] * 1e-7)

    def measure(fn, *args):
        for iters in (ITERS, 2 * ITERS):
            float(fn(*args, fresh_salt(), iters=iters))
        slopes = []
        for _ in range(ROUNDS):
            t0 = time.perf_counter()
            float(fn(*args, fresh_salt(), iters=ITERS))
            t1 = time.perf_counter()
            float(fn(*args, fresh_salt(), iters=2 * ITERS))
            t2 = time.perf_counter()
            slopes.append(((t2 - t1) - (t1 - t0)) / ITERS)
        return float(np.median(slopes))

    t_infer = measure(infer_chain, params, stats, images)
    t_fwd = measure(fwd_train_chain, params, stats, images, labels)
    t_full = measure(train_chain, params, stats, opt_state, images, labels)

    print(json.dumps({
        "batch": BATCH,
        "infer_ms": round(t_infer * 1e3, 2),
        "fwd_train_ms": round(t_fwd * 1e3, 2),
        "full_step_ms": round(t_full * 1e3, 2),
        "bwd_plus_update_ms": round((t_full - t_fwd) * 1e3, 2),
        "infer_mfu": round(FWD_FLOPS / t_infer / 197e12, 4),
        "fwd_train_mfu": round(FWD_FLOPS / t_fwd / 197e12, 4),
        "full_step_mfu": round(TRAIN_FLOPS / t_full / 197e12, 4),
        "img_per_sec": round(BATCH / t_full, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
