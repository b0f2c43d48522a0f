#!/usr/bin/env python
"""Flash-attention block-size sweep at the bench shapes (round 4).

The kernel's default blocks (fwd q512/k1024, bwd 1024²) were tuned on
head_dim 128; the transformer headlines run head_dim 64 (BERT-Large
B8 H16 S512 non-causal, GPT-2 B16 H12 S1024 causal). Causal shapes are
the interesting case: the kernel skips k-blocks entirely in a q-block's
future (flash_attention.py `interior` predicate), so SMALLER k-blocks
skip more masked work — at seq 1024 a single 1024-wide k block can
never be skipped.

Protocol: the house slope timing (salted chains, t(2N)-t(N)) on the
isolated 24-layer (BERT) / 12-layer (GPT-2) attention stack, fwd and
fwd+bwd, per block config. One config per invocation (--shape, --blocks
"bq,bk,bbq,bbk") so a disturbed run loses one point; drive from a shell
loop.
"""

import argparse
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402

SHAPES = {
    # label: (batch, heads, seq, head_dim, layers, causal)
    "bert-large": (8, 16, 512, 64, 24, False),
    "gpt2": (16, 12, 1024, 64, 12, True),
}
ITERS = 10
ROUNDS = 6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), required=True)
    ap.add_argument("--blocks", required=True,
                    help="bq,bk,bwd_bq,bwd_bk")
    ap.add_argument("--grad", action="store_true",
                    help="time fwd+bwd instead of fwd")
    args = ap.parse_args()
    b, h, s, d, layers, causal = SHAPES[args.shape]
    bq, bk, bbq, bbk = (int(x) for x in args.blocks.split(","))

    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k0 = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    v0 = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)

    attn = partial(flash_attention, causal=causal, block_q=bq, block_k=bk,
                   bwd_block_q=bbq, bwd_block_k=bbk)

    @partial(jax.jit, static_argnames="iters")
    def fwd_chain(q, k, v, salt, iters):
        def body(q_c, _):
            x = q_c
            for _ in range(layers):
                x = attn(x, k, v)
            out = jnp.mean(x[:, 0, 0, :].astype(jnp.float32))
            return q_c + (1e-6 * out + salt).astype(q_c.dtype), out

        _, outs = jax.lax.scan(body, q, None, length=iters)
        return outs[-1]

    @partial(jax.jit, static_argnames="iters")
    def grad_chain(q, k, v, salt, iters):
        def attn_loss(q_c):
            x = q_c
            for _ in range(layers):
                x = attn(x, k, v)
            return jnp.mean(x.astype(jnp.float32))

        def body(q_c, _):
            out, g = jax.value_and_grad(attn_loss)(q_c)
            return (q_c - 1e-6 * g.astype(q_c.dtype)
                    + jnp.asarray(salt * 1e-12, q_c.dtype)), out

        _, outs = jax.lax.scan(body, q, None, length=iters)
        return outs[-1]

    fn = grad_chain if args.grad else fwd_chain
    salt_n = [0]

    def fresh_salt():
        salt_n[0] += 1
        return jnp.float32(salt_n[0] * 1e-7)

    for iters in (ITERS, 2 * ITERS):
        float(fn(q0, k0, v0, fresh_salt(), iters=iters))
    slopes = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        float(fn(q0, k0, v0, fresh_salt(), iters=ITERS))
        t1 = time.perf_counter()
        float(fn(q0, k0, v0, fresh_salt(), iters=2 * ITERS))
        t2 = time.perf_counter()
        slopes.append(((t2 - t1) - (t1 - t0)) / ITERS)
    ms = float(np.median(slopes)) * 1e3
    print(json.dumps({"shape": args.shape, "blocks": args.blocks,
                      "phase": "fwd+bwd" if args.grad else "fwd",
                      f"{layers}x_ms": round(ms, 2)}), flush=True)


if __name__ == "__main__":
    main()
