#!/usr/bin/env python
"""Where does the BERT-Large MLM step actually go? (VERDICT r3 ask 1.)

Applies the ResNet evidentiary protocol (tools/resnet_decompose.py) to
the transformer headline: slope-timed chains (dispatch cancelled, salted
inputs so that no two timed calls are identical, true data dependencies
between scan iterations against loop-invariant hoisting) on the bench
configuration —
BERT-Large, batch 8/chip, seq 512, bf16, Pallas flash attention.

Phases measured:
  * trunk        — embed + 24 layers + final norm (NO vocab projection)
  * fwd          — trunk + tied vocab projection + masked-LM loss
  * grad         — jax.value_and_grad of fwd (fwd + bwd)
  * full         — grad + adamw update (bench.py's op)
  * attn         — 24 isolated flash-attention calls fwd (bench shapes)
  * attn_grad    — the same 24 calls fwd + bwd

Derived:  vocab+loss = fwd - trunk;  bwd = grad - fwd;  opt = full - grad;
MLP+LN+embed trunk time = trunk - attn.

``--only PHASE`` measures a single phase (a disturbed run then only
loses one variant; drive the set from a shell loop). The counter-moves
themselves (masked-position gather, bf16 adam moments, fused qkv) live
as model/bench options — ``masked_lm_loss_gathered`` +
``Transformer(..., output="hidden")``, ``BENCH_MLM_GATHER``,
``BENCH_ADAM_MU_BF16`` in bench.py — and are A/B-measured there, where
the headline protocol already runs.

Every number is a median of slope rounds: t(2N chains) - t(N chains)
over N extra iterations, so compile, dispatch, and readback cancel.
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
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu.models.transformer import BertLarge, masked_lm_loss  # noqa: E402
from horovod_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402

BATCH = 8
SEQ = 512
VOCAB = 30522
D_MODEL, N_LAYERS, N_HEADS, D_FF = 1024, 24, 16, 4096
HEAD_DIM = D_MODEL // N_HEADS
PREDICTIONS_PER_SEQ = 76  # BERT's max_predictions_per_seq for seq 512
ITERS = 10
ROUNDS = 6
PEAK = 197e12  # v5e bf16


def flops_per_token(n_params):
    attn = 12 * N_LAYERS * SEQ * D_MODEL
    return 6 * n_params + attn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["vocab", "fwd", "grad", "full", "attn",
                             "attn_grad", "opt"],
                    help="measure ONE phase (a disturbed run then only "
                         "loses one variant; drive the set from a shell "
                         "loop)")
    args = ap.parse_args()

    model = BertLarge(vocab_size=VOCAB, max_seq=SEQ, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, VOCAB, (BATCH, SEQ)).astype(np.int32))
    mask = jnp.asarray((rng.rand(BATCH, SEQ) < 0.15).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), tokens[:1], train=False)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    step_flops = flops_per_token(n_params) * BATCH * SEQ
    fwd_flops = step_flops / 3.0

    # -- chained variants (each iteration depends on the previous one's
    # scalar output, so XLA cannot hoist the body out of the scan) -----

    def shift_from(x):
        # data-dependent roll: cheap (16 KB gather) but a true dependency
        return (jnp.abs(x) * 1e4).astype(jnp.int32) % SEQ

    def loss_fn(p, toks, msk):
        logits = model.apply(p, toks, train=True)
        return masked_lm_loss(logits, toks, msk)

    # isolate the vocab projection + MLM loss on a FIXED hidden-state
    # tensor (the model's tied projection is hidden @ E^T with E the
    # token embedding, models/transformer.py:178): trunk time falls out
    # as fwd - vocab_loss without re-entering flax
    embed_matrix = params["params"]["token_embed"]["embedding"]
    hidden0 = jnp.asarray(rng.randn(BATCH, SEQ, D_MODEL), jnp.bfloat16)

    @partial(jax.jit, static_argnames="iters")
    def vocab_loss_chain(emb, h, toks, msk, salt, iters):
        def body(h_c, _):
            logits = (h_c @ emb.astype(jnp.bfloat16).T).astype(jnp.float32)
            loss = masked_lm_loss(logits, toks, msk)
            return h_c * (1 + 1e-9 * (loss + salt)).astype(h_c.dtype), loss

        _, losses = jax.lax.scan(body, h, None, length=iters)
        return losses[-1]

    @partial(jax.jit, static_argnames="iters")
    def fwd_chain(p, toks, msk, salt, iters):
        def body(carry, _):
            toks_c = carry
            loss = loss_fn(p, toks_c, msk)
            return jnp.roll(toks_c, shift_from(loss + salt), axis=1), loss

        _, losses = jax.lax.scan(body, toks, None, length=iters)
        return losses[-1]

    @partial(jax.jit, static_argnames="iters")
    def grad_chain(p, toks, msk, salt, iters):
        def body(carry, _):
            p_c = carry
            loss, g = jax.value_and_grad(loss_fn)(p_c, toks, msk)
            # consume the gradient without an optimizer: fold a scaled
            # copy back into the params (keeps the whole bwd alive)
            p_c = jax.tree_util.tree_map(
                lambda a, b: a - 1e-9 * b.astype(a.dtype), p_c, g)
            return p_c, loss + salt

        _, losses = jax.lax.scan(body, params, None, length=iters)
        return losses[-1]

    @partial(jax.jit, static_argnames="iters")
    def full_chain(p, o, toks, msk, salt, iters):
        def body(carry, _):
            p_c, o_c = carry
            loss, g = jax.value_and_grad(loss_fn)(p_c, toks, msk)
            upd, o_c = tx.update(g, o_c, p_c)
            p_c = optax.apply_updates(p_c, upd)
            return (p_c, o_c), loss + salt

        _, losses = jax.lax.scan(body, (p, o), None, length=iters)
        return losses[-1]

    # isolated attention at the bench shape (all 24 layers' worth)
    q0 = jnp.asarray(rng.randn(BATCH, N_HEADS, SEQ, HEAD_DIM),
                     jnp.bfloat16)
    k0 = jnp.asarray(rng.randn(BATCH, N_HEADS, SEQ, HEAD_DIM),
                     jnp.bfloat16)
    v0 = jnp.asarray(rng.randn(BATCH, N_HEADS, SEQ, HEAD_DIM),
                     jnp.bfloat16)

    @partial(jax.jit, static_argnames="iters")
    def opt_chain(p, o, g0, salt, iters):
        # adamw update alone, chained through the params (grads fixed):
        # isolates the optimizer's HBM traffic (read p+mu+nu+g, write
        # p+mu+nu) without the model in the program, so the compile is
        # small and quick. bwd then falls out of
        # full - fwd - opt when the grad phase is unavailable.
        def body(carry, _):
            p_c, o_c = carry
            upd, o_c = tx.update(g0, o_c, p_c)
            p_c = optax.apply_updates(p_c, upd)
            p_c = jax.tree_util.tree_map(
                lambda a: a + jnp.asarray(salt * 1e-12, a.dtype), p_c)
            return (p_c, o_c), 0.0
        (p_f, _), _ = jax.lax.scan(body, (p, o), None, length=iters)
        # reduce over EVERY element — adamw is elementwise, so returning
        # a single element would let XLA slice the whole update to one
        # lane (measured: the step collapses to ~0)
        return sum(jnp.sum(leaf) for leaf in jax.tree_util.tree_leaves(p_f))

    @partial(jax.jit, static_argnames="iters")
    def attn_chain(q, k, v, salt, iters):
        def body(q_c, _):
            x = q_c
            for _ in range(N_LAYERS):
                x = flash_attention(x, k, v, causal=False)
            out = jnp.mean(x[:, 0, 0, :].astype(jnp.float32))
            return q_c + (1e-6 * out + salt).astype(q_c.dtype), out

        _, outs = jax.lax.scan(body, q, None, length=iters)
        return outs[-1]

    @partial(jax.jit, static_argnames="iters")
    def attn_grad_chain(q, k, v, salt, iters):
        def attn_loss(q_c):
            x = q_c
            for _ in range(N_LAYERS):
                x = flash_attention(x, k, v, causal=False)
            return jnp.mean(x.astype(jnp.float32))

        def body(q_c, _):
            out, g = jax.value_and_grad(attn_loss)(q_c)
            # salt must survive into the executable (an arg XLA drops
            # would make the timed calls identical again)
            return (q_c - 1e-6 * g.astype(q_c.dtype)
                    + jnp.asarray(salt * 1e-12, q_c.dtype)), out

        _, outs = jax.lax.scan(body, q, None, length=iters)
        return outs[-1]

    salt_n = [0]

    def fresh_salt():
        salt_n[0] += 1
        return jnp.float32(salt_n[0] * 1e-7)

    def measure(fn, *fnargs):
        for iters in (ITERS, 2 * ITERS):  # compile both lengths
            float(fn(*fnargs, fresh_salt(), iters=iters))
        slopes = []
        for _ in range(ROUNDS):
            t0 = time.perf_counter()
            float(fn(*fnargs, fresh_salt(), iters=ITERS))
            t1 = time.perf_counter()
            float(fn(*fnargs, fresh_salt(), iters=2 * ITERS))
            t2 = time.perf_counter()
            slopes.append(((t2 - t1) - (t1 - t0)) / ITERS)
        return float(np.median(slopes))

    res = {"batch": BATCH, "seq": SEQ, "n_params_m": round(n_params / 1e6, 1)}

    variants = {
        "vocab": lambda: measure(vocab_loss_chain, embed_matrix, hidden0,
                                 tokens, mask),
        "fwd": lambda: measure(fwd_chain, params, tokens, mask),
        "grad": lambda: measure(grad_chain, params, tokens, mask),
        "full": lambda: measure(full_chain, params, opt_state, tokens,
                                mask),
        "opt": lambda: measure(
            opt_chain, params, opt_state,
            jax.tree_util.tree_map(
                lambda a: jnp.full_like(a, 1e-6), params)),
        "attn": lambda: measure(attn_chain, q0, k0, v0),
        "attn_grad": lambda: measure(attn_grad_chain, q0, k0, v0),
    }
    if args.only:
        t = variants[args.only]()
        res[f"{args.only}_ms"] = round(t * 1e3, 2)
        if args.only == "full":
            res["full_step_mfu"] = round(step_flops / t / PEAK, 4)
            res["tokens_per_sec"] = round(BATCH * SEQ / t, 1)
        if args.only == "fwd":
            res["fwd_mfu"] = round(fwd_flops / t / PEAK, 4)
        print(json.dumps(res), flush=True)
        return

    t_vocab = variants["vocab"]()
    t_fwd = variants["fwd"]()
    t_grad = variants["grad"]()
    t_full = variants["full"]()
    t_attn = variants["attn"]()
    t_attn_grad = variants["attn_grad"]()

    res.update({
        "vocab_loss_fwd_ms": round(t_vocab * 1e3, 2),
        "trunk_fwd_ms": round((t_fwd - t_vocab) * 1e3, 2),
        "fwd_ms": round(t_fwd * 1e3, 2),
        "grad_ms": round(t_grad * 1e3, 2),
        "full_step_ms": round(t_full * 1e3, 2),
        "attn_fwd_24x_ms": round(t_attn * 1e3, 2),
        "attn_grad_24x_ms": round(t_attn_grad * 1e3, 2),
        "bwd_ms": round((t_grad - t_fwd) * 1e3, 2),
        "opt_update_ms": round((t_full - t_grad) * 1e3, 2),
        "fwd_mfu": round(fwd_flops / t_fwd / PEAK, 4),
        "full_step_mfu": round(step_flops / t_full / PEAK, 4),
        "tokens_per_sec": round(BATCH * SEQ / t_full, 1),
    })
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
