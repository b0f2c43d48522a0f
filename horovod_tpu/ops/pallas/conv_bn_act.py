"""Fused batch-norm + activation epilogue for the conv models.

The Inception/ResNet decompositions of the rounds before the ledger
(docs/perf_experiments.md) show the conv stacks spend a measurable slice
of every ConvBN in the *elementwise tail* — normalize, scale/shift, ReLU
— which XLA emits as its own HBM-bound loop over the conv output. A
prototype measured the win of folding that tail into one pass; this
module ships the production half that composes with autodiff and
checkpoints:

* :func:`bn_stats` — one-pass per-channel mean/variance in f32 (sum and
  sum-of-squares in the same sweep, the prototype's epilogue contract).
* :func:`scale_bias_act` — ``relu(x * s + b)`` as a Pallas kernel with
  a ``custom_vjp`` (jnp backward), so the folded BN apply is one
  VMEM-resident pass instead of XLA's normalize → scale → clamp chain.
* :class:`FusedBatchNormAct` — drop-in for ``nn.BatchNorm`` + ``relu``
  with identical variable names/shapes ("scale"/"bias" params,
  "mean"/"var" batch stats, same momentum update), so checkpoints
  interchange with the unfused ConvBN.

Kernel gating is honest about TPU lane tiling: the channel axis must
pack lanes exactly — ``C % 128 == 0``, or ``128 % C == 0`` (lane rows
tile ``128/C`` whole channel groups — covers the stem/reduction convs'
C ∈ {32, 64}). Everything else and non-TPU backends take the jnp path,
which is also the custom_vjp backward everywhere.
``HOROVOD_FUSED_BN_ACT`` forces the kernel on/off (default: auto — on
for a TPU default backend); ``HOROVOD_PALLAS_INTERPRET`` runs the
kernel in interpret mode for tests (same switch as the other kernels).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from horovod_tpu.ops.pallas._backend import (on_tpu, row_blocks,
                                             shard_over_batch, use_interpret)
from horovod_tpu.utils import env as env_mod

# Same launch-worthiness floor as the other kernels.
_MIN_PALLAS = 16 * 1024
_BLOCK_ROWS = 512


def _use_kernel() -> bool:
    return env_mod._get_bool("HOROVOD_FUSED_BN_ACT", on_tpu())


def bn_stats(x):
    """Per-channel (last axis) batch mean and variance in one f32 pass.

    ``var = E[x^2] - E[x]^2`` — the same estimator ``nn.BatchNorm``
    uses, so the fused module is numerically interchangeable with it."""
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(xf, axis=axes)
    var = jnp.mean(xf * xf, axis=axes) - mean * mean
    return mean, var


def _sba_kernel(x_ref, s_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = x * s_ref[...] + b_ref[...]
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _sba_jnp(x, s, b):
    y = x.astype(jnp.float32) * s + b
    return jnp.maximum(y, 0.0).astype(x.dtype)


def _sba_pallas(x, s, b):
    """relu(x*s + b) with per-channel f32 ``s``/``b``; returns None when
    the shape doesn't pack TPU lanes (caller falls back to jnp)."""
    c = x.shape[-1]
    n = x.size
    if n < _MIN_PALLAS:
        return None
    if c % 128 == 0:
        lanes = 128
        reps = 1
    elif c <= 128 and 128 % c == 0:
        # tile 128/c whole channel groups per lane row
        lanes = 128
        reps = 128 // c
    else:
        return None
    if n % lanes:
        return None
    rows = n // lanes
    block_rows, grid = row_blocks(rows, _BLOCK_ROWS)
    if c % 128 == 0:
        # lane rows walk the channel axis in 128-wide slabs: row r covers
        # channels [(r % (c//128))*128, ...) — broadcast s/b to the same
        # (rows, 128) layout
        s2 = s.reshape(1, c // 128, 128)
        s2 = jnp.broadcast_to(s2, (rows // (c // 128), c // 128, 128)) \
            .reshape(rows, 128)
        b2 = b.reshape(1, c // 128, 128)
        b2 = jnp.broadcast_to(b2, (rows // (c // 128), c // 128, 128)) \
            .reshape(rows, 128)
    else:
        tiled_s = jnp.tile(s, reps)
        tiled_b = jnp.tile(b, reps)
        s2 = jnp.broadcast_to(tiled_s, (rows, 128))
        b2 = jnp.broadcast_to(tiled_b, (rows, 128))
    spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    out = pl.pallas_call(
        _sba_kernel,
        grid=(grid,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, 128), x.dtype,
                                       vma=jax.typeof(x).vma),
        interpret=use_interpret(),
    )(x.reshape(rows, 128), s2, b2)
    return out.reshape(x.shape)


def _sba(x, s, b):
    out = _sba_pallas(x, s, b)
    return _sba_jnp(x, s, b) if out is None else out


@jax.custom_vjp
def scale_bias_act(x, s, b):
    """``relu(x * s + b)`` with per-channel f32 scale/bias.

    The forward runs as one Pallas pass when the shape packs TPU lanes
    (see module docstring); the backward is the standard masked chain in
    jnp — XLA fuses it into the surrounding conv backward anyway."""
    if x.ndim >= 1 and _use_kernel():
        # shape gating is static, so this composes with jit/scan traces;
        # on several chips each runs the kernel on its rows of the batch
        return shard_over_batch(_sba, (x,), (s, b))
    return _sba_jnp(x, s, b)


def _sba_fwd(x, s, b):
    return scale_bias_act(x, s, b), (x, s, b)


def _sba_bwd(res, g):
    x, s, b = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mask = (xf * s + b) > 0.0
    gm = jnp.where(mask, gf, 0.0)
    axes = tuple(range(x.ndim - 1))
    dx = (gm * s).astype(x.dtype)
    ds = jnp.sum(gm * xf, axis=axes)
    db = jnp.sum(gm, axis=axes)
    return dx, ds, db


scale_bias_act.defvjp(_sba_fwd, _sba_bwd)


try:  # flax is present in this environment, but keep the ops importable
    import flax.linen as nn
except Exception:  # pragma: no cover - flax-less import of the op layer
    nn = None


if nn is not None:

    class FusedBatchNormAct(nn.Module):
        """``nn.BatchNorm(momentum, epsilon)`` + ``relu`` as one fused
        epilogue, with identical variable names and update rules."""

        momentum: float = 0.9
        epsilon: float = 1e-3
        dtype: Any = jnp.bfloat16

        @nn.compact
        def __call__(self, x, use_running_average: bool = False):
            c = x.shape[-1]
            scale = self.param("scale", nn.initializers.ones, (c,),
                               jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (c,),
                              jnp.float32)
            ra_mean = self.variable("batch_stats", "mean",
                                    lambda: jnp.zeros((c,), jnp.float32))
            ra_var = self.variable("batch_stats", "var",
                                   lambda: jnp.ones((c,), jnp.float32))
            if use_running_average:
                mean, var = ra_mean.value, ra_var.value
            else:
                mean, var = bn_stats(x)
                if not self.is_initializing():
                    m = self.momentum
                    ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
                    ra_var.value = m * ra_var.value + (1.0 - m) * var
            s = scale * jax.lax.rsqrt(var + self.epsilon)
            b = bias - mean * s
            return scale_bias_act(x, s, b)
