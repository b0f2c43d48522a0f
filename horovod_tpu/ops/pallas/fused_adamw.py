"""AdamW as a single fused Pallas pass per parameter.

The round-4 BERT-Large decomposition (tools/bert_decompose.py,
docs/perf_experiments.md) measured the optax adamw update at 16.2 ms of a
77.6 ms step — 21%, entirely HBM-bandwidth-bound: the minimum traffic is
read p, mu, nu, g and write p, mu, nu (28 bytes/param in f32), ~11.4 ms at
the chip's ~819 GB/s for 334M params. optax's composed transform chain
(scale_by_adam -> add_decayed_weights -> scale -> apply_updates) leaves
XLA several fusion seams; this module expresses the whole update as ONE
elementwise Pallas kernel per leaf, so every byte is touched exactly once.

MEASURED OUTCOME (docs/perf_experiments.md round 4): on the BERT-Large
bench this loses ~27% end-to-end vs the optax chain (38.8k vs 53.7k
tokens/s; 1 MB and 256 KB blocks alike) — ~400 sequential per-leaf
pallas_calls forfeit XLA's cross-leaf scheduling, which the isolated
16.2 ms optax pass (~70% of its HBM roofline) was already exploiting.
Kept as a correctness-tested counter-move exemplar and for future work
(multi-leaf batched grids); NOT the default anywhere. The winning
optimizer-amortization move is gradient accumulation (BENCH_ACCUM).

The API is step-level — ``opt.apply(params, state, grads) -> (new_params,
new_state)`` — NOT an optax GradientTransformation: the optax contract
(update returns deltas, apply_updates adds them) would force two extra
full passes over the parameters, which is the very traffic being
eliminated. The state is optax's ScaleByAdamState (count, mu, nu), so
checkpoints interoperate with optax.adamw both ways.

Semantics follow optax.adamw: bias-corrected moments, decoupled weight
decay folded into the learning-rate step
(p -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)).

The reference framework has no optimizer kernels (its DistributedOptimizer
wraps the host framework's optimizer — reference horovod/torch/optimizer.py);
this is part of the TPU-first performance layer, like the flash kernel.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import row_blocks, use_interpret
from horovod_tpu.utils import env as env_mod

# Leaves smaller than this skip Pallas (a kernel launch isn't worth it for
# a LayerNorm scale; XLA fuses tiny elementwise chains fully on its own).
_MIN_PALLAS = 16 * 1024
# elements per grid step (tunable for A/B; 64k elements = 256 KB blocks,
# 7 live blocks x double buffering ~ 3.5 MB VMEM)
_BLOCK = env_mod._get_int("FUSED_ADAMW_BLOCK", 64 * 1024)


def _adamw_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref, p_out, m_out, v_out,
                  *, eps):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    # scalars in SMEM: b1, b2, 1/(1-b1^t), 1/(1-b2^t), lr, wd
    b1 = sc_ref[0]
    b2 = sc_ref[1]
    inv_bc1 = sc_ref[2]
    inv_bc2 = sc_ref[3]
    lr = sc_ref[4]
    wd = sc_ref[5]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    p = p - lr * ((m * inv_bc1) / (jnp.sqrt(v * inv_bc2) + eps) + wd * p)
    p_out[...] = p.astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def _jnp_leaf(p, m, v, g, scalars, eps):
    b1, b2, inv_bc1, inv_bc2, lr, wd = (scalars[i] for i in range(6))
    gf = g.astype(jnp.float32)
    mf = b1 * m.astype(jnp.float32) + (1.0 - b1) * gf
    vf = b2 * v.astype(jnp.float32) + (1.0 - b2) * gf * gf
    pf = p.astype(jnp.float32)
    pf = pf - lr * ((mf * inv_bc1)
                    / (jnp.sqrt(vf * inv_bc2) + eps) + wd * pf)
    return pf.astype(p.dtype), mf.astype(m.dtype), vf.astype(v.dtype)


def _leaf_update(p, m, v, g, scalars, *, eps):
    """One fused read-modify-write pass over a single leaf."""
    n = int(np.prod(p.shape))
    if n < _MIN_PALLAS or n % 128:
        return _jnp_leaf(p, m, v, g, scalars, eps)

    rows = n // 128
    block_rows, grid = row_blocks(rows, _BLOCK // 128)
    flat = lambda a: a.reshape((rows, 128))
    spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    p2, m2, v2 = pl.pallas_call(
        functools.partial(_adamw_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), p.dtype),
                   jax.ShapeDtypeStruct((rows, 128), m.dtype),
                   jax.ShapeDtypeStruct((rows, 128), v.dtype)],
        interpret=use_interpret(),
    )(scalars, flat(p), flat(m), flat(v), flat(g))
    return p2.reshape(p.shape), m2.reshape(m.shape), v2.reshape(v.shape)


class FusedAdamW(NamedTuple):
    """Step-level fused AdamW: ``apply(params, state, grads)``.

    ``init``/``apply`` instead of optax's update/apply_updates — returning
    deltas would re-read and re-write every parameter just to add them.
    """

    init: callable
    apply: callable


def fused_adamw(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
                eps: float = 1e-8,
                weight_decay: float = 1e-4) -> FusedAdamW:
    """Fused-pass AdamW; state is optax ScaleByAdamState for checkpoint
    interop with ``optax.adamw`` (swap either way mid-training)."""

    def init(params):
        zeros = lambda p: jnp.zeros_like(p)
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params))

    def apply(params, state, grads):
        count = optax.safe_int32_increment(state.count)
        t = count.astype(jnp.float32)
        scalars = jnp.stack([
            jnp.float32(b1), jnp.float32(b2),
            1.0 / (1.0 - jnp.float32(b1) ** t),
            1.0 / (1.0 - jnp.float32(b2) ** t),
            jnp.float32(learning_rate), jnp.float32(weight_decay)])

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        flat_g = treedef.flatten_up_to(grads)
        new_p, new_m, new_v = [], [], []
        for p, m, v, g in zip(flat_p, flat_m, flat_v, flat_g):
            p2, m2, v2 = _leaf_update(p, m, v, g, scalars, eps=eps)
            new_p.append(p2)
            new_m.append(m2)
            new_v.append(v2)
        new_state = optax.ScaleByAdamState(
            count=count, mu=treedef.unflatten(new_m),
            nu=treedef.unflatten(new_v))
        return treedef.unflatten(new_p), new_state

    return FusedAdamW(init=init, apply=apply)
