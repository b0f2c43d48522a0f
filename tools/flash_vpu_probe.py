#!/usr/bin/env python
"""Attack plan for the flash kernel's ~24%-MFU attention term (VERDICT
r4/r5 ask: the last double-digit perf item).

The r4 decomposition (docs/perf_experiments.md) pinned BERT-Large's
attention at ~24% MFU vs the dense trunk's ~65%, and excluded the MXU
side (bf16 operands: flat; block sweep: defaults stand) — leaving the
VPU softmax/layout term at head_dim 64. This probe measures, with the
same slope protocol as tools/bert_decompose.py (dispatch cancelled,
salted inputs, true data dependencies):

  baselines   flash / flash_grad       — the product kernel fwd, fwd+bwd
              xla / xla_grad           — plain XLA attention (unfused)
              stock / stock_grad       — jax.experimental.pallas.ops.tpu
                                         .flash_attention (independent
                                         implementation, same hardware —
                                         the honest external ceiling)
  moves       bf16sm                   — FLASH_MXU_BF16=1: bf16 dot
                                         operands + bf16 p with f32
                                         row-max/lse only (the judge's
                                         move (b); spawn fresh process,
                                         env is trace-time)
              pack2                    — two heads packed into one
                                         128-deep contraction (move (a))
              blocks:BQxBK             — fwd block-size override
                                         (move (c): q-block widening)

Shapes: ``--shape bert-large`` (B8 H16 S512 D64, non-causal) and
``--shape gpt2`` (B16 H12 S1024 D64, causal) — the bench headline
attention shapes — plus ``--shape longseq16k`` (B1 H8 S16384 D128,
causal), the docs/benchmarks.md long-context row on the multi-block
general path (regression guard for the single-block specialization).

Run:  python tools/flash_vpu_probe.py --shape bert-large --only flash
Each invocation measures ONE variant (a disturbed run loses one row;
drive the set from a shell loop). Prints one JSON line.
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu.ops.pallas._backend import (  # noqa: E402
    use_interpret)
from horovod_tpu.ops.pallas.flash_attention import (  # noqa: E402
    LANES, LOG2E, NEG_INF, attention_reference, flash_attention)

SHAPES = {
    # (batch, heads, seq, head_dim, causal) — the bench headline configs
    "bert-large": (8, 16, 512, 64, False),
    "gpt2": (16, 12, 1024, 64, True),
    # the docs/benchmarks.md long-context row (r1): multi-k-block
    # GENERAL path — regression guard for the single-block work
    "longseq16k": (1, 8, 16384, 128, True),
}
ITERS = 8
ROUNDS = 6
PEAK = 197e12  # v5e bf16


def attn_flops(b, h, s, d, causal):
    # fwd QK^T + PV: 2 dots x 2 MACs; causal counts the half matrix
    # (MODEL-FLOPs convention, same as bench.py)
    f = 2 * 2 * b * h * s * s * d
    return f // 2 if causal else f


# ---------------------------------------------------------------------------
# pack2: two heads per kernel step, one 128-deep contraction (move (a)).
# Layout (built outside the kernel):
#   q2[b, hp, 0:S,  0:64 ] = q[b, 2hp];   q2[b, hp, S:2S, 64:128] = q[b, 2hp+1]
#   (zeros elsewhere)  -> QK^T of (2S, 128) x (128, S) stacks BOTH heads'
#   score tiles with a full 128-lane contraction.
#   k2/v2[b, hp] = concat(k[b, 2hp], k[b, 2hp+1], lanes)
# PV runs (2S, S) x (S, 128); rows 0:S keep lanes 0:64, rows S:2S keep
# 64:128 (static per q-block since S % block_q == 0). The packing DOUBLES
# the MAC volume of both dots (the zero half of q2 and the discarded half
# of PV), so it wins only if the 64-deep contraction ran below half rate
# or per-step overhead dominates — exactly what this row measures.
# ---------------------------------------------------------------------------


def _pack2_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, block_q, seq):
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
    k = k_ref[0, 0, :, :].astype(jnp.float32)   # (S, 128)
    v = v_ref[0, 0, :, :].astype(jnp.float32)   # (S, 128)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, S)
    m = jnp.max(s, axis=-1)
    p = jnp.exp2(s - m[:, None])
    l = jnp.sum(p, axis=-1)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq,128)
    o = o / l[:, None]
    # rows of head A (global row < S) keep lanes 0:64; head B rows keep
    # 64:128. block_q divides S, so the choice is uniform per block —
    # but q-block ids are dynamic, so select with a where on the block id.
    first_half = (qi * block_q) < seq
    lo = o[:, :64]
    hi = o[:, 64:]
    o_ref[0, 0, :, :] = jnp.where(first_half, lo, hi).astype(o_ref.dtype)


def pack2_attention(q, k, v, sm_scale, block_q=512):
    b, h, s, d = q.shape
    assert d == 64 and h % 2 == 0
    hp = h // 2
    # build packed operands (XLA ops; counted inside the measured chain —
    # the packing cost is part of the move's honest price)
    qp = q.reshape(b, hp, 2, s, d)
    zeros = jnp.zeros_like(qp)
    top = jnp.concatenate([qp[:, :, 0], zeros[:, :, 0]], axis=-1)
    bot = jnp.concatenate([zeros[:, :, 1], qp[:, :, 1]], axis=-1)
    q2 = jnp.concatenate([top, bot], axis=2)            # (b, hp, 2S, 128)
    k2 = jnp.concatenate([k.reshape(b, hp, 2, s, d)[:, :, 0],
                          k.reshape(b, hp, 2, s, d)[:, :, 1]], axis=-1)
    v2 = jnp.concatenate([v.reshape(b, hp, 2, s, d)[:, :, 0],
                          v.reshape(b, hp, 2, s, d)[:, :, 1]], axis=-1)

    block_q = min(block_q, s)
    grid = (b, hp, (2 * s) // block_q)
    q_spec = pl.BlockSpec((1, 1, block_q, 128), lambda b_, h_, i: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, 128), lambda b_, h_, i: (b_, h_, 0, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0))
    o2 = pl.pallas_call(
        functools.partial(_pack2_kernel, sm_scale=sm_scale,
                          block_q=block_q, seq=s),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, 2 * s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=use_interpret(),
    )(q2, k2, v2)
    return o2.reshape(b, hp, 2, s, d).reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# simple1: the pack2 kernel WITHOUT packing — one head per step, d=64,
# single k-block, no online-softmax scratch, no lse output. Isolates how
# much of pack2's win is the 128-deep contraction vs the single-block
# simplification (direct softmax, no m/l scratch, no lse write).
# ---------------------------------------------------------------------------


def _simple1_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale):
    q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m = jnp.max(s, axis=-1)
    p = jnp.exp2(s - m[:, None])
    l = jnp.sum(p, axis=-1)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0, :, :] = (o / l[:, None]).astype(o_ref.dtype)


def _simple1_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale):
    q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m = jnp.max(s, axis=-1)
    p = jnp.exp2(s - m[:, None])
    l = jnp.sum(p, axis=-1)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0, :, :] = (o / l[:, None]).astype(o_ref.dtype)
    lse = m * (1.0 / LOG2E) + jnp.log(l)
    lse_ref[0, 0, :, :] = jax.lax.broadcast_in_dim(
        lse, lse_ref.shape[2:], (0,))


def simple1_lse_attention(q, k, v, sm_scale, block_q=512):
    b, h, s, d = q.shape
    block_q = min(block_q, s)
    grid = (b, h, s // block_q)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, LANES),
                            lambda b_, h_, i: (b_, h_, i, 0))
    o, lse = pl.pallas_call(
        functools.partial(_simple1_lse_kernel, sm_scale=sm_scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=use_interpret(),
    )(q, k, v)
    return o


def simple1_attention(q, k, v, sm_scale, block_q=512):
    b, h, s, d = q.shape
    block_q = min(block_q, s)
    grid = (b, h, s // block_q)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0))
    return pl.pallas_call(
        functools.partial(_simple1_kernel, sm_scale=sm_scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=use_interpret(),
    )(q, k, v)


# ---------------------------------------------------------------------------
# slope measurement (protocol of tools/bert_decompose.py)
# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="bert-large", choices=sorted(SHAPES))
    ap.add_argument("--only", required=True,
                    help="flash|flash_grad|xla|xla_grad|stock|stock_grad|"
                         "pack2|simple1|simple1_lse|blocks:BQxBK|"
                         "blocks_grad:BQxBK")
    cli = ap.parse_args()
    b, h, s, d, causal = SHAPES[cli.shape]
    sm = 1.0 / float(np.sqrt(d))

    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(
        rng.randn(b, h, s, d).astype(np.float32) * 0.3, jnp.bfloat16)
    q0, k0, v0 = mk(), mk(), mk()

    name = cli.only
    blocks = None
    if name.startswith("blocks"):
        kind, spec = name.split(":")
        bq, bk = (int(x) for x in spec.split("x"))
        blocks = (bq, bk)
        name = "flash_grad" if kind.endswith("_grad") else "flash"

    def attn(qc):
        if name in ("flash", "flash_grad"):
            kw = {}
            if blocks:
                kw = {"block_q": blocks[0], "block_k": blocks[1],
                      "bwd_block_q": blocks[0], "bwd_block_k": blocks[1]}
            return flash_attention(qc, k0, v0, causal=causal, **kw)
        if name in ("xla", "xla_grad"):
            return attention_reference(qc, k0, v0, causal=causal)
        if name in ("stock", "stock_grad"):
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as stock)
            return stock(qc, k0, v0, causal=causal, sm_scale=sm)
        if name == "pack2":
            assert not causal, "pack2 probe is non-causal (bert shape)"
            return pack2_attention(qc, k0, v0, sm)
        if name == "simple1":
            assert not causal, "simple1 probe is non-causal (bert shape)"
            return simple1_attention(qc, k0, v0, sm)
        if name == "simple1_lse":
            assert not causal
            return simple1_lse_attention(qc, k0, v0, sm)
        raise SystemExit(f"unknown variant {cli.only}")

    grad_mode = name.endswith("_grad")
    # LAYERS amplifies per-iteration work above the host clock's timing
    # noise, same as bert_decompose's 24-layer chains; the reported ms
    # is per single attention call.
    LAYERS = 12

    def stack(x):
        for _ in range(LAYERS):
            x = attn(x)
        return x

    @functools.partial(jax.jit, static_argnames="iters")
    def chain(qc, salt, iters):
        if grad_mode:
            def loss(x):
                return jnp.mean(stack(x).astype(jnp.float32))

            def body(x, _):
                out, g = jax.value_and_grad(loss)(x)
                return (x - 1e-6 * g.astype(x.dtype)
                        + jnp.asarray(salt * 1e-12, x.dtype)), out
        else:
            def body(x, _):
                o = stack(x)
                out = jnp.mean(o[:, 0, 0, :].astype(jnp.float32))
                return x + (1e-6 * out + salt).astype(x.dtype), out

        xf, outs = jax.lax.scan(body, qc, None, length=iters)
        return outs[-1]

    salt_n = [0]

    def fresh_salt():
        salt_n[0] += 1
        return jnp.float32(salt_n[0] * 1e-7)

    for iters in (ITERS, 2 * ITERS):
        float(chain(q0, fresh_salt(), iters=iters))
    slopes = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        float(chain(q0, fresh_salt(), iters=ITERS))
        t1 = time.perf_counter()
        float(chain(q0, fresh_salt(), iters=2 * ITERS))
        t2 = time.perf_counter()
        slopes.append(((t2 - t1) - (t1 - t0)) / ITERS)
    t = float(np.median(slopes))

    t /= LAYERS  # per single attention call
    flops = attn_flops(b, h, s, d, causal)
    if grad_mode:
        flops *= 3  # bwd recomputes s + 4 dots ~= 2x fwd
    print(json.dumps({
        "shape": cli.shape, "variant": cli.only,
        "ms": round(t * 1e3, 3),
        "mfu": round(flops / t / PEAK, 4),
        "mxu_bf16_env": os.environ.get("FLASH_MXU_BF16", "0"),
    }), flush=True)


if __name__ == "__main__":
    main()
