"""Expert parallelism: Switch-style MoE with all_to_all token routing.

TPU-first extension (the reference is DP-only — SURVEY.md §2.4). Experts
live one-per-device along a mesh axis; each device's tokens are routed
top-1, packed into per-expert capacity buffers, exchanged with
``lax.all_to_all`` over ICI (the canonical TPU MoE dispatch), processed by
the local expert, and exchanged back to be combined with the gate
probabilities. Static shapes throughout: tokens beyond an expert's
capacity are dropped (their output is zero), the standard Switch
Transformer contract.

Composes with DP/TP/PP/SP on other mesh axes. The router is caller-owned
(any ``(tokens, n_experts)`` logits); :func:`load_balance_loss` is the
Switch auxiliary loss that keeps routing uniform.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel._util import consume_stage_axis


def switch_moe(x, gate_logits, expert_fn: Callable, expert_params,
               axis_name: str, capacity: int):
    """Top-1 MoE over experts sharded on ``axis_name`` (inside shard_map).

    ``x``: (tokens, d) this device's tokens; ``gate_logits``: (tokens,
    n_experts); ``expert_params``: this device's expert weights (leading
    stage axis of length 1 from the shard_map spec is consumed);
    ``expert_fn(params, h) -> h`` is the expert body; ``capacity`` is the
    per-(device, expert) token budget.

    Takes ONE mesh axis name (the all_to_all routes over a single axis);
    reshape the mesh if experts should span multiple axes.

    Returns ``(y, router_probs)`` where dropped tokens contribute zeros.
    """
    if not isinstance(axis_name, str):
        raise ValueError(
            f"switch_moe takes ONE mesh axis name (got {axis_name!r}); "
            "the all_to_all routes over a single axis — reshape the mesh "
            "if experts should span multiple axes")
    n_exp = lax.axis_size(axis_name)
    d = x.shape[-1]
    if gate_logits.shape[-1] != n_exp:
        raise ValueError(
            f"router has {gate_logits.shape[-1]} experts but axis "
            f"'{axis_name}' has {n_exp} devices; expert parallelism needs "
            "one expert per device on the axis")
    expert_params = consume_stage_axis(expert_params)

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # (T,)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert_idx, n_exp, dtype=jnp.int32)  # (T, E)
    pos_in_expert = jnp.sum(
        (jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)  # (T,)
    keep = pos_in_expert < capacity

    # pack: (E, C, d) dispatch buffer; dropped tokens never land
    safe_pos = jnp.where(keep, pos_in_expert, 0)
    dispatch = jnp.zeros((n_exp, capacity, d), x.dtype)
    dispatch = dispatch.at[expert_idx, safe_pos].add(
        x * keep[:, None].astype(x.dtype))

    # route: chunk e of every device -> device e; received layout is
    # (source_device, C, d) for MY expert
    received = lax.all_to_all(dispatch, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    expert_out = expert_fn(expert_params,
                           received.reshape(n_exp * capacity, d))
    expert_out = expert_out.reshape(n_exp, capacity, d)

    # route back: chunk s returns to source device s
    returned = lax.all_to_all(expert_out, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)

    # unpack + weight by the gate; dropped tokens stay zero
    y = returned[expert_idx, safe_pos]
    y = y * (gate * keep.astype(gate.dtype))[:, None].astype(y.dtype)
    return y, probs


def load_balance_loss(probs, axis_name=None):
    """Switch Transformer auxiliary loss: n_exp * Σ_e f_e · P_e, minimized
    (=1) by uniform routing. ``probs``: (tokens, n_experts) router
    softmax. With ``axis_name``, statistics aggregate across devices."""
    n_exp = probs.shape[-1]
    assignment = jax.nn.one_hot(jnp.argmax(probs, -1), n_exp,
                                dtype=probs.dtype)
    frac_tokens = jnp.mean(assignment, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    if axis_name is not None:
        frac_tokens = lax.pmean(frac_tokens, axis_name)
        frac_probs = lax.pmean(frac_probs, axis_name)
    return n_exp * jnp.sum(frac_tokens * frac_probs)


def default_capacity(tokens_per_device: int, n_experts: int,
                     capacity_factor: float = 1.25) -> int:
    """Per-(device, expert) buffer size: even-split load times the safety
    factor, rounded up so the factor's headroom survives small ratios
    (the Switch convention)."""
    return max(1, math.ceil(tokens_per_device * capacity_factor / n_experts))
