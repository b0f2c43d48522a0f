#!/usr/bin/env python
"""Where does the Inception-V3 step actually go? (VERDICT r4 weak #3 /
r5 ask 4: the table's worst MFU row — 22.5% — had no independent
evidence.)

Applies the ResNet evidentiary protocol (tools/resnet_decompose.py):
slope-timed scan chains (dispatch cancelled, salted inputs, scalar
readback) on the bench configuration — batch 32, 299x299, bf16.

Two layers of evidence:

  * step split     — infer / fwd_train / full train step (fwd vs bwd)
  * stage split    — the model's five structural segments timed alone,
                     each with XLA's own cost-analysis FLOPs as the MFU
                     basis (the bench convention). This is the
                     "stock-JAX control" at the only level that is
                     meaningful here: every conv in the model IS stock
                     ``flax.linen.Conv`` (horovod_tpu/models/inception.py
                     wraps nn.Conv + BN and nothing else), so a separate
                     stock implementation would re-measure the same XLA
                     programs; what needs independent evidence is WHICH
                     structural segment burns the MFU.

Segments (input shapes at batch 32):
  stem     299² x3  -> 35² x192   (7 convs + 2 maxpools, 3-channel entry)
  blockA   35²  x192 -> 35² x288  (3x InceptionA: 1x1/5x5/3x3 branches)
  blockBC  35²  x288 -> 17² x768  (B reduction + 4x C: 1x7/7x1 factor.)
  blockDE  17²  x768 -> 8²  x2048 (D reduction + 2x E: 1x3/3x1 forks)
  head     8²   x2048 -> logits   (global mean + dense)

Run:  python tools/inception_decompose.py [--only PHASE]
PHASES: infer fwd full stem blockA blockBC blockDE head
Each --only invocation prints one JSON line (a disturbed run loses one
phase; drive the full set from a shell loop).
"""

import argparse
import json
import os
import sys
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import flax.linen as nn  # noqa: E402

from horovod_tpu.models.inception import (  # noqa: E402
    ConvBN, InceptionA, InceptionB, InceptionC, InceptionD, InceptionE,
    InceptionV3)

BATCH = 32
ITERS = 12
ROUNDS = 6
PEAK = 197e12  # v5e bf16 (2xMAC convention, same as bench.py)
FWD_FLOPS = BATCH * 11.137e9  # XLA cost analysis of the full forward


class Stem(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        c = partial(ConvBN, dtype=self.dtype)
        x = x.astype(self.dtype)
        x = c(32, (3, 3), strides=(2, 2), padding="VALID")(x, train)
        x = c(32, (3, 3), padding="VALID")(x, train)
        x = c(64, (3, 3))(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = c(80, (1, 1), padding="VALID")(x, train)
        x = c(192, (3, 3), padding="VALID")(x, train)
        return nn.max_pool(x, (3, 3), strides=(2, 2))


class BlockA(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        x = InceptionA(32, dtype=self.dtype)(x, train)
        x = InceptionA(64, dtype=self.dtype)(x, train)
        return InceptionA(64, dtype=self.dtype)(x, train)


class BlockBC(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        x = InceptionB(dtype=self.dtype)(x, train)
        x = InceptionC(128, dtype=self.dtype)(x, train)
        x = InceptionC(160, dtype=self.dtype)(x, train)
        x = InceptionC(160, dtype=self.dtype)(x, train)
        return InceptionC(192, dtype=self.dtype)(x, train)


class BlockDE(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        x = InceptionD(dtype=self.dtype)(x, train)
        x = InceptionE(dtype=self.dtype)(x, train)
        return InceptionE(dtype=self.dtype)(x, train)


class Head(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(1000, dtype=self.dtype,
                     param_dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


SEGMENTS = {
    # name -> (module, input shape at batch 32)
    "stem": (Stem, (BATCH, 299, 299, 3)),
    "blockA": (BlockA, (BATCH, 35, 35, 192)),
    "blockBC": (BlockBC, (BATCH, 35, 35, 288)),
    "blockDE": (BlockDE, (BATCH, 17, 17, 768)),
    "head": (Head, (BATCH, 8, 8, 2048)),
}


def slope_measure(fn, *args, fresh_salt=None):
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["infer", "fwd", "full"] + sorted(SEGMENTS))
    cli = ap.parse_args()

    salt_n = [0]

    def fresh_salt():
        salt_n[0] += 1
        return jnp.float32(salt_n[0] * 1e-7)

    measure = partial(slope_measure, fresh_salt=fresh_salt)
    rng = np.random.RandomState(0)
    res = {"batch": BATCH}

    def segment_row(name):
        mod_cls, shape = SEGMENTS[name]
        mod = mod_cls()
        x0 = jnp.asarray(rng.uniform(-1, 1, shape).astype(np.float32))
        variables = mod.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1,) + shape[1:], jnp.float32), train=False)
        params = variables["params"]
        stats = variables.get("batch_stats", {})

        def apply_fwd(x):
            out = mod.apply(
                {"params": params, "batch_stats": stats} if stats
                else {"params": params},
                x, train=True,
                **({"mutable": ["batch_stats"]} if stats else {}))
            return out[0] if stats else out

        # fwd-only segment chain: carry the INPUT, perturbed by a scalar
        # of the output (true data dependency, shapes unchanged)
        @partial(jax.jit, static_argnames="iters")
        def seg_chain(x, salt, iters):
            def body(x, _):
                y = apply_fwd(x)
                s = jnp.mean(y.astype(jnp.float32))
                return x + (1e-6 * s + salt).astype(x.dtype), s

            _, outs = jax.lax.scan(body, x, None, length=iters)
            return outs[-1]

        # XLA's own FLOP count for one forward application — the same
        # basis as bench.py's model constants
        flops = jax.jit(apply_fwd).lower(x0).compile() \
            .cost_analysis()["flops"]
        t = measure(seg_chain, x0)
        res[f"{name}_ms"] = round(t * 1e3, 3)
        res[f"{name}_gflops"] = round(float(flops) / 1e9, 2)
        res[f"{name}_mfu"] = round(float(flops) / t / PEAK, 4)

    if cli.only in SEGMENTS:
        segment_row(cli.only)
        print(json.dumps(res), flush=True)
        return

    # ---- whole-model phases (resnet_decompose protocol) ----
    model = InceptionV3(num_classes=1000, dtype=jnp.bfloat16)
    images = jnp.asarray(
        rng.uniform(-1, 1, (BATCH, 299, 299, 3)).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, (BATCH,)).astype(np.int32))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 299, 299, 3), jnp.float32),
                           train=False)
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

    phases = {
        "infer": lambda: measure(infer_chain, params, stats, images),
        "fwd": lambda: measure(fwd_train_chain, params, stats, images,
                               labels),
        "full": lambda: measure(train_chain, params, stats, opt_state,
                                images, labels),
    }
    if cli.only:
        t = phases[cli.only]()
        res[f"{cli.only}_ms"] = round(t * 1e3, 2)
        if cli.only == "infer":
            res["infer_mfu"] = round(FWD_FLOPS / t / PEAK, 4)
        if cli.only == "fwd":
            res["fwd_mfu"] = round(FWD_FLOPS / t / PEAK, 4)
        if cli.only == "full":
            res["full_step_mfu"] = round(3 * FWD_FLOPS / t / PEAK, 4)
            res["img_per_sec"] = round(BATCH / t, 1)
        print(json.dumps(res), flush=True)
        return

    t_infer = phases["infer"]()
    t_fwd = phases["fwd"]()
    t_full = phases["full"]()
    res.update({
        "infer_ms": round(t_infer * 1e3, 2),
        "fwd_train_ms": round(t_fwd * 1e3, 2),
        "full_step_ms": round(t_full * 1e3, 2),
        "bwd_plus_update_ms": round((t_full - t_fwd) * 1e3, 2),
        "infer_mfu": round(FWD_FLOPS / t_infer / PEAK, 4),
        "fwd_train_mfu": round(FWD_FLOPS / t_fwd / PEAK, 4),
        "full_step_mfu": round(3 * FWD_FLOPS / t_full / PEAK, 4),
        "img_per_sec": round(BATCH / t_full, 1),
    })
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
