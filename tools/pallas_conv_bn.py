#!/usr/bin/env python
"""Pallas conv + BN-statistics epilogue prototype (VERDICT r2 ask 1b).

Round 2 argued ResNet-50's ~15% MFU is bounded by BN-statistics HBM
traffic: every conv output is written to HBM, then RE-READ for the
batch-stats reduction — a pass that disappears if the stats are an
epilogue of the conv kernel itself. XLA's reduction-into-conv fusion is
not expressible from JAX; this prototype tests whether it is achievable
from Pallas at all, on ResNet-50's most frequent 3x3 shape (stage 3:
14x14x256 -> 256, batch 128 — six bottleneck blocks carry it).

Measures, same chip / same protocol as bench.py (compiled scan chains,
scalar readback):
  A. XLA conv alone                      (the pure-conv floor)
  B. XLA conv + separate stats reduce    (today's decomposition)
  C. Pallas conv with fused sum/sumsq epilogue (one HBM pass)

If C ~= A while B > A by the stats-pass cost, the round-2 structural
argument is confirmed AND the counter-move exists; if C >> B, Pallas
cannot beat XLA's conv emitter from outside and the gap is confirmed
structural at the toolchain level.

Prints one JSON line with the three times and derived verdict numbers.
"""

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BATCH = 128
H = W = 14
CIN = COUT = 256
BATCH_TILE = 4
# Timing is slope-based to cancel the per-launch latency (it was
# ~100ms/call on the machine this was written on, which would swamp a
# ~100us kernel; not re-measured): each
# chain is compiled at two lengths and the per-iteration time is
# (t_long - t_short) / (ITERS_LONG - ITERS_SHORT).
ITERS_SHORT = 100
ITERS_LONG = 600
ROUNDS = 6

# one 3x3 conv at this shape: H*W*9*CIN*COUT MACs per image
FLOPS_PER_APP = 2 * BATCH * H * W * 9 * CIN * COUT


def _conv_kernel(x_ref, w_ref, y_ref, sum_ref, sumsq_ref, acc_ref):
    """One batch-tile of images: 3x3 conv as 9 channel-contraction
    dot_generals over the padded input block, f32 accumulation in VMEM
    scratch, then (a) bf16 output write and (b) per-channel sum / sumsq
    accumulated across grid steps — the BN-stats epilogue that saves the
    HBM re-read."""
    step = pl.program_id(0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    for dh in range(3):
        for dw in range(3):
            patch = x_ref[:, dh:dh + H, dw:dw + W, :]
            acc_ref[...] += lax.dot_general(
                patch, w_ref[dh, dw],
                dimension_numbers=(((3,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    acc = acc_ref[...]
    y_ref[...] = acc.astype(jnp.bfloat16)

    @pl.when(step == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

    sum_ref[...] += jnp.sum(acc, axis=(0, 1, 2))
    sumsq_ref[...] += jnp.sum(acc * acc, axis=(0, 1, 2))


@jax.jit
def pallas_conv_stats(x_padded, w):
    """x_padded: (BATCH, H+2, W+2, CIN) bf16; w: (3,3,CIN,COUT) bf16.
    Returns (y bf16, channel_sum f32, channel_sumsq f32)."""
    grid = (BATCH // BATCH_TILE,)
    return pl.pallas_call(
        _conv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BATCH_TILE, H + 2, W + 2, CIN),
                         lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, CIN, COUT), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BATCH_TILE, H, W, COUT),
                         lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((COUT,), lambda i: (0,)),
            pl.BlockSpec((COUT,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BATCH, H, W, COUT), jnp.bfloat16),
            jax.ShapeDtypeStruct((COUT,), jnp.float32),
            jax.ShapeDtypeStruct((COUT,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BATCH_TILE, H, W, COUT), jnp.float32)],
    )(x_padded, w)


def xla_conv(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


@functools.partial(jax.jit, static_argnames="iters")
def xla_conv_only_chain(x, w, salt, iters):
    x = x + salt.astype(x.dtype)

    def body(x, _):
        y = xla_conv(x, w)
        # feed a scaled slice back so iterations are data-dependent
        # (no cross-iteration CSE) without changing the measured op
        x = x + 1e-6 * y[:, :, :, :CIN].astype(x.dtype)
        return x, ()

    x, _ = lax.scan(body, x, None, length=iters)
    return jnp.sum(x[0, 0, 0, :8].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames="iters")
def xla_conv_stats_chain(x, w, salt, iters):
    x = x + salt.astype(x.dtype)

    def body(x, _):
        y = xla_conv(x, w)
        yf = y.astype(jnp.float32)
        s = jnp.sum(yf, axis=(0, 1, 2))
        ss = jnp.sum(yf * yf, axis=(0, 1, 2))
        x = x + 1e-6 * y[:, :, :, :CIN].astype(x.dtype) \
            + (1e-9 * (s[0] + ss[0])).astype(x.dtype)
        return x, ()

    x, _ = lax.scan(body, x, None, length=iters)
    return jnp.sum(x[0, 0, 0, :8].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames="iters")
def pallas_chain(x_padded, w, salt, iters):
    x_padded = x_padded + salt.astype(x_padded.dtype)

    def body(x_padded, _):
        y, s, ss = pallas_conv_stats(x_padded, w)
        upd = 1e-6 * y[:, :, :, :CIN].astype(x_padded.dtype) \
            + (1e-9 * (s[0] + ss[0])).astype(x_padded.dtype)
        x_padded = x_padded.at[:, 1:1 + H, 1:1 + W, :].add(upd)
        return x_padded, ()

    x_padded, _ = lax.scan(body, x_padded, None, length=iters)
    return jnp.sum(x_padded[0, 1, 1, :8].astype(jnp.float32))


_salt_counter = [0]


def _fresh_salt():
    """Every timed call gets a distinct input value, so that no layer
    between this process and the chip can answer a repeated call from
    memory (docs/benchmarks.md protocol)."""
    _salt_counter[0] += 1
    return jnp.float32(_salt_counter[0] * 1e-7)


def time_chain(fn, *args):
    """Per-iteration seconds with dispatch latency cancelled: median over
    ROUNDS of (t[ITERS_LONG] - t[ITERS_SHORT]) / (ITERS_LONG -
    ITERS_SHORT)."""
    for iters in (ITERS_SHORT, ITERS_LONG):  # compile + warm both
        float(fn(*args, _fresh_salt(), iters=iters))
    slopes = []
    for _ in range(ROUNDS):
        # float(...) = scalar readback: waits for the device
        t0 = time.perf_counter()
        float(fn(*args, _fresh_salt(), iters=ITERS_SHORT))
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(fn(*args, _fresh_salt(), iters=ITERS_LONG))
        t_long = time.perf_counter() - t0
        slopes.append((t_long - t_short) / (ITERS_LONG - ITERS_SHORT))
    return float(np.median(slopes))


def shape_sweep():
    """XLA conv MFU + stats-epilogue cost per ResNet-50 stage shape
    (batch 128, 3x3 convs). Pins down WHERE the end-to-end 15% MFU
    comes from: if the early large-spatial/low-channel stages run at a
    fraction of stage 3/4's MFU in isolation, the model's MFU is shape
    structure, not framework overhead."""
    rng = np.random.RandomState(0)
    rows = []
    for (h, c) in [(56, 64), (28, 128), (14, 256), (7, 512)]:
        x = jnp.asarray(rng.uniform(-1, 1, (BATCH, h, h, c)),
                        dtype=jnp.bfloat16)
        w = jnp.asarray(rng.uniform(-0.1, 0.1, (3, 3, c, c)),
                        dtype=jnp.bfloat16)
        global CIN  # the chain feedback slice width follows the shape
        CIN = c
        t_conv = time_chain(xla_conv_only_chain, x, w)
        t_stats = time_chain(xla_conv_stats_chain, x, w)
        flops = 2 * BATCH * h * h * 9 * c * c
        rows.append({
            "shape": f"{h}x{h}x{c}",
            "xla_conv_us": round(t_conv * 1e6, 1),
            "stats_cost_us": round((t_stats - t_conv) * 1e6, 1),
            "xla_conv_mfu": round(flops / t_conv / 197e12, 4),
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main():
    print(f"devices: {jax.devices()}", file=sys.stderr, flush=True)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, (BATCH, H, W, CIN)),
                    dtype=jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-0.1, 0.1, (3, 3, CIN, COUT)),
                    dtype=jnp.bfloat16)
    x_padded = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))

    # numeric check vs XLA before timing. The epilogue sums the UNROUNDED
    # f32 accumulator (more accurate than re-reading the rounded bf16
    # output, which is what the separate XLA stats pass does), so the
    # stats reference is an f32 conv of the same bf16 values.
    y_ref = xla_conv(x, w)
    y_pl, s_pl, ss_pl = pallas_conv_stats(x_padded, w)
    np.testing.assert_allclose(np.asarray(y_pl, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    yf32 = lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(s_pl),
                               np.asarray(jnp.sum(yf32, axis=(0, 1, 2))),
                               rtol=1e-2, atol=2.0)
    np.testing.assert_allclose(
        np.asarray(ss_pl),
        np.asarray(jnp.sum(yf32 * yf32, axis=(0, 1, 2))),
        rtol=1e-2)
    print("numerics ok", file=sys.stderr, flush=True)

    t_conv = time_chain(xla_conv_only_chain, x, w)
    t_conv_stats = time_chain(xla_conv_stats_chain, x, w)
    t_pallas = time_chain(pallas_chain, x_padded, w)

    result = {
        "shape": f"{BATCH}x{H}x{W}x{CIN}->{COUT} 3x3",
        "xla_conv_us": round(t_conv * 1e6, 1),
        "xla_conv_plus_stats_us": round(t_conv_stats * 1e6, 1),
        "pallas_fused_us": round(t_pallas * 1e6, 1),
        "stats_pass_cost_us": round((t_conv_stats - t_conv) * 1e6, 1),
        "xla_conv_mfu": round(FLOPS_PER_APP / t_conv / 197e12, 4),
        "pallas_fused_mfu": round(FLOPS_PER_APP / t_pallas / 197e12, 4),
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--sweep", action="store_true",
                        help="per-stage XLA conv shape sweep instead of "
                             "the Pallas comparison")
    if parser.parse_args().sweep:
        shape_sweep()
    else:
        main()
