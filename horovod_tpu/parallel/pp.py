"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

TPU-first extension (the reference is DP-only — SURVEY.md §2.4). Stages
live one-per-device along ``axis_name``; activations circulate with
``lax.ppermute`` while ``lax.scan`` runs the schedule. The forward is the
classic GPipe fill-drain pipeline (n_micro + n_stages - 1 ticks), and the
backward comes from autodiff: ppermute's transpose is the reverse
rotation, so the reversed schedule emerges from ``jax.grad`` without any
hand-written backward pass.

The stage function must be shape-preserving ``(stage_params, x) -> y``
(true of transformer blocks: (microbatch, seq, d_model) in and out);
embedding/head layers run outside the pipelined trunk. Per-stage params
are stacked on a leading axis sharded over ``axis_name``, so each device
holds only its stage's weights.

Composes with DP (batch over another axis) and TP (shard stage weights'
inner dims) the usual mesh way.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel._util import (  # noqa: F401 — re-exported API
    consume_stage_axis,
    stack_stage_params,
)


def pipeline_apply(stage_fn: Callable, stage_params, x,
                   axis_name: str):
    """Run the pipeline inside ``shard_map``.

    ``stage_params``: this device's stage weights (the caller shard_maps a
    (n_stages, ...) stack over ``axis_name``, leading axis consumed).
    ``x``: (n_micro, microbatch, ...) microbatched input, replicated over
    the pipeline axis. Returns (n_micro, microbatch, ...) outputs, valid
    on the LAST stage (zeros elsewhere — combine with
    :func:`last_stage_value` or compute the loss per-device and select).
    """
    if not isinstance(axis_name, str):
        raise ValueError(
            "pipeline_apply takes ONE mesh axis name (the ppermute ring "
            f"is a single axis); got {axis_name!r} — reshape the mesh so "
            "the pipeline spans one axis")
    n_stages = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1
    stage_params = consume_stage_axis(stage_params)
    # send to the NEXT stage: device i's output becomes i+1's input
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # first stage feeds microbatch t (clamped; masked out after drain)
        mb = lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        state_in = jnp.where(idx == 0, mb, state)
        out = stage_fn(stage_params, state_in)
        # last stage emits microbatch t - (n_stages - 1)
        out_t = t - (n_stages - 1)
        emit = jnp.logical_and(idx == n_stages - 1, out_t >= 0)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(emit, out, lax.dynamic_index_in_dim(
                outputs, jnp.clip(out_t, 0, n_micro - 1), axis=0,
                keepdims=False)),
            jnp.clip(out_t, 0, n_micro - 1), axis=0)
        state = lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    out_shape = jax.eval_shape(stage_fn, stage_params, x[0])
    state0 = jnp.zeros(out_shape.shape, out_shape.dtype)
    outputs0 = jnp.zeros((n_micro,) + out_shape.shape, out_shape.dtype)
    # mark device-varying over the pipeline axis
    state0 = lax.pcast(state0, (axis_name,), to="varying")
    outputs0 = lax.pcast(outputs0, (axis_name,), to="varying")
    (final_state, outputs), _ = lax.scan(
        tick, (state0, outputs0), jnp.arange(ticks))
    return outputs


def last_stage_value(value, axis_name: str):
    """Select the last pipeline stage's ``value`` on every device — the
    broadcast collective with the last stage as root (differentiable,
    unlike a gather)."""
    from horovod_tpu.ops import collectives

    n_stages = lax.axis_size(axis_name)
    return collectives.broadcast(value, n_stages - 1, axis_name=axis_name)


