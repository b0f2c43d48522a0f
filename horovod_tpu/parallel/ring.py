"""Ring attention: exact attention over sequence shards via ppermute.

Long-context context parallelism. The sequence is sharded across a mesh axis;
each device holds one query/key/value shard. Key/value shards rotate around
the ring with ``lax.ppermute`` while each device accumulates its queries'
attention against every shard using the blockwise Pallas kernel
(ops/pallas/flash_attention.py) and exact log-sum-exp merging — so the full
``(seq, seq)`` attention is never materialised on any chip, memory stays
O(seq/N · d) per device, and communication overlaps the per-step compute.

The backward pass makes a second ring sweep: with the *final* softmax
normaliser (lse) saved from the forward, each (q-shard, kv-shard) pair's
gradient contribution is independent, so dk/dv accumulators simply ride
around the ring with their chunks.

The reference framework is data-parallel only (SURVEY.md §5.7 — no sequence
parallelism of any kind exists there); this is a TPU-first extension built on
the idioms its survey prescribes (shard_map + collective permute over an ICI
mesh axis).

Causal masking works on *global* sequence positions (each device derives its
shard's offset from ``lax.axis_index``); kv shards that are entirely in a
query shard's future are self-skipping — the kernel predicates those grid
steps to no-ops, so causal ring attention does ~half the FLOPs of the
bidirectional case just like a single-chip causal kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.pallas._backend import use_interpret
from horovod_tpu.ops.pallas.flash_attention import (
    LANES,
    NEG_INF,
    _as_offset,
    _flash_bwd,
    compute_delta,
    flash_attention_partial,
    merge_partials,
)


def _axis_perm(axis_name):
    n = lax.axis_size(axis_name)
    # send to the left neighbour: device i receives the chunk held by i+1,
    # so after s steps device i holds the chunk owned by (i + s) % n.
    return [(j, (j - 1) % n) for j in range(n)]


def _ppermute_tree(xs, axis_name, perm):
    return jax.tree_util.tree_map(
        lambda x: lax.ppermute(x, axis_name, perm), xs)


def _pcast(x, axis_name):
    """Mark a freshly created array as device-varying over ``axis_name`` so
    it can carry through a scan whose outputs vary."""
    return lax.pcast(x, axis_name, to="varying")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None,
                   block_q=512, block_k=1024,
                   bwd_block_q=1024, bwd_block_k=1024):
    """Exact flash attention over a sequence sharded on ``axis_name``.

    Must be called inside ``shard_map`` (or another context binding
    ``axis_name``); ``q``/``k``/``v`` are the local shards, shaped
    ``(batch, heads, seq_local, head_dim)``. Returns the local output shard.

    ``block_q``/``block_k`` tune the forward kernel; ``bwd_block_q``/
    ``bwd_block_k`` the backward sweep (larger square blocks win there).
    """
    o, _ = _ring_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k)
    return o


def _ring_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = k.shape[2]
    q_off = my * q.shape[2]
    perm = _axis_perm(axis_name)

    def compute(o, lse, k_cur, v_cur, s):
        src = (my + s) % n
        o_p, lse_p = flash_attention_partial(
            q, k_cur, v_cur, causal=causal, sm_scale=sm_scale,
            q_offset=q_off, k_offset=src * s_local,
            block_q=block_q, block_k=block_k)
        # float32 accumulation across the ring; cast once at the end.
        return merge_partials(o, lse, o_p.astype(jnp.float32), lse_p)

    def step(carry, s):
        o, lse, k_cur, v_cur = carry
        o, lse = compute(o, lse, k_cur, v_cur, s)
        k_cur, v_cur = _ppermute_tree((k_cur, v_cur), axis_name, perm)
        return (o, lse, k_cur, v_cur), None

    o0 = _pcast(jnp.zeros(q.shape, jnp.float32), axis_name)
    lse0 = _pcast(jnp.full(q.shape[:3], NEG_INF, jnp.float32), axis_name)
    if n > 1:
        # Rotate inside the first n-1 steps only; the last shard's result
        # needs no further ppermute.
        (o, lse, k_cur, v_cur), _ = lax.scan(
            step, (o0, lse0, k, v), jnp.arange(n - 1))
    else:
        o, lse, k_cur, v_cur = o0, lse0, k, v
    o, lse = compute(o, lse, k_cur, v_cur, n - 1)
    return o.astype(q.dtype), lse


def _ring_vjp_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
                  bwd_block_q, bwd_block_k):
    o, lse = _ring_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, sm_scale, block_q, block_k,
                  bwd_block_q, bwd_block_k, res, do):
    q, k, v, o, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = k.shape[2]
    q_off = my * q.shape[2]
    perm = _axis_perm(axis_name)
    lse4 = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    # delta depends only on (o, do) — loop-invariant across the ring sweep,
    # so compute its O(B·H·S·D) reduction once, not once per ring step.
    delta = compute_delta(o, do)
    scale = (1.0 / math.sqrt(q.shape[-1]) if sm_scale is None else sm_scale)

    def step(carry, s):
        dq, k_cur, v_cur, dk_acc, dv_acc = carry
        src = (my + s) % n
        dq_p, dk_p, dv_p = _flash_bwd(
            q, k_cur, v_cur, o, lse4, do,
            _as_offset(q_off), _as_offset(src * s_local),
            sm_scale=float(scale), causal=causal,
            block_q=bwd_block_q, block_k=bwd_block_k,
            interpret=use_interpret(), delta=delta)
        dq = dq + dq_p.astype(dq.dtype)
        dk_acc = dk_acc + dk_p.astype(dk_acc.dtype)
        dv_acc = dv_acc + dv_p.astype(dv_acc.dtype)
        # dk/dv accumulators travel with their chunks; after n rotations
        # every chunk (and its gradient) is back on its owner.
        k_cur, v_cur, dk_acc, dv_acc = _ppermute_tree(
            (k_cur, v_cur, dk_acc, dv_acc), axis_name, perm)
        return (dq, k_cur, v_cur, dk_acc, dv_acc), None

    dq0 = _pcast(jnp.zeros(q.shape, jnp.float32), axis_name)
    dk0 = _pcast(jnp.zeros(k.shape, jnp.float32), axis_name)
    dv0 = _pcast(jnp.zeros(v.shape, jnp.float32), axis_name)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)
