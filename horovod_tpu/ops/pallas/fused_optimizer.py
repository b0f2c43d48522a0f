"""Fused flat-buffer optimizer passes for ZeRO-1 shards.

Shaped for the sharded data plane (:mod:`horovod_tpu.parallel.zero`):
instead of one kernel per parameter leaf, ONE kernel runs over the whole
flat fp32 master/moment shard of a dtype group. That removes the per-leaf
launch overhead that sank a per-leaf fused AdamW (docs/perf_experiments.md
round 4 — ~400 sequential pallas_calls forfeit XLA's cross-leaf
scheduling; its code left the tree in PR 29): a BERT-Large f32 group is a
single ~83M-element buffer, a single grid. The minimum HBM traffic per element is read master, mu, nu
(f32) + grad and write all four again — and only 1/N of it happens on
each chip.

The kernel keeps fp32 master weights: ``mw`` carries the authoritative
parameters; the emitted ``p_out`` is the master cast to the parameter
dtype (bf16 master-weight training). Math matches optax.adamw
(bias-corrected moments, decoupled weight decay folded into the lr
step), so the jnp fallback and the kernel agree with the replicated
optax chain at fp32.

``HOROVOD_SHARDED_FUSED_KERNEL`` gates the Pallas path (default: on
when the backend is TPU, off elsewhere). Which path a call takes is
decided from its arguments alone (:func:`jnp_reason`): the jnp chain
serves tracers, stacked 2-D single-controller layouts where the buffer
is sharded across devices, tiny shards and non-multiple-of-128 lengths;
everything else is the kernel, which compiles or raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import (on_tpu, row_blocks,
                                             use_interpret)
from horovod_tpu.utils import env as env_mod
from horovod_tpu.utils import logging as log

# Skip Pallas below this (launch not worth it), and grid-step this many
# elements (256 KB f32 blocks).
_MIN_PALLAS = 16 * 1024
_BLOCK = env_mod._get_int("FUSED_OPTIMIZER_BLOCK", 64 * 1024)


def _use_kernel() -> bool:
    return env_mod._get_bool(env_mod.HOROVOD_SHARDED_FUSED_KERNEL,
                             on_tpu())


def _flat_adamw_kernel(sc_ref, mw_ref, m_ref, v_ref, g_ref,
                       p_out, mw_out, m_out, v_out, *, eps):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    w = mw_ref[...]
    # scalars in SMEM: b1, b2, 1/(1-b1^t), 1/(1-b2^t), lr, wd
    b1 = sc_ref[0]
    b2 = sc_ref[1]
    inv_bc1 = sc_ref[2]
    inv_bc2 = sc_ref[3]
    lr = sc_ref[4]
    wd = sc_ref[5]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    w = w - lr * ((m * inv_bc1) / (jnp.sqrt(v * inv_bc2) + eps) + wd * w)
    p_out[...] = w.astype(p_out.dtype)
    mw_out[...] = w
    m_out[...] = m
    v_out[...] = v


def _jnp_flat(master, mu, nu, grad, scalars, eps, out_dtype):
    b1, b2, inv_bc1, inv_bc2, lr, wd = (scalars[i] for i in range(6))
    gf = grad.astype(jnp.float32)
    m2 = b1 * mu + (1.0 - b1) * gf
    v2 = b2 * nu + (1.0 - b2) * gf * gf
    w2 = master - lr * ((m2 * inv_bc1)
                        / (jnp.sqrt(v2 * inv_bc2) + eps) + wd * master)
    return w2.astype(out_dtype), w2, m2, v2


def jnp_reason(master) -> str | None:
    """Why this call takes the jnp chain, or ``None`` for the kernel."""
    if isinstance(master, jax.core.Tracer):
        # traced under shard_map: Pallas-per-device would need careful
        # vmem accounting inside the spmd body
        return "traced"
    if master.ndim != 1:
        # stacked 2-D single-controller buffer sharded across devices:
        # the XLA elementwise chain is the right program
        return "stacked 2-D buffer"
    if not _use_kernel():
        return "kernel off"
    n = int(master.shape[0])
    if n < _MIN_PALLAS:
        return "shard below the launch-worthiness floor"
    if n % 128:
        return "length not a multiple of 128 lanes"
    return None


@functools.lru_cache(maxsize=None)
def _log_path_once(reason: str | None) -> None:
    log.debug("flat_adamw_shard: %s",
              "pallas kernel" if reason is None else f"jnp ({reason})")


def pallas_flat_adamw(master, mu, nu, grad, scalars, *, eps, out_dtype):
    """The kernel pass over 1-D buffers whose length is a multiple of
    128. Unlike :func:`flat_adamw_shard` it takes tracers, so a test can
    lower it for the TPU (tests/test_tpu_compile.py)."""
    n = int(master.shape[0])
    rows = n // 128
    block_rows, grid = row_blocks(rows, _BLOCK // 128)
    flat = lambda a: a.reshape((rows, 128))
    spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    p2, w2, m2, v2 = pl.pallas_call(
        functools.partial(_flat_adamw_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), out_dtype),
                   jax.ShapeDtypeStruct((rows, 128), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 128), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 128), jnp.float32)],
        interpret=use_interpret(),
    )(scalars, flat(master), flat(mu), flat(nu), flat(grad))
    return (p2.reshape(n), w2.reshape(n), m2.reshape(n), v2.reshape(n))


def flat_adamw_shard(master, mu, nu, grad, scalars, *, eps, out_dtype):
    """One fused AdamW pass over a flat fp32 master shard.

    ``master``/``mu``/``nu`` are f32 buffers, ``grad`` the reduced
    gradient shard (any float dtype), ``scalars`` the 6-vector
    [b1, b2, 1/(1-b1^t), 1/(1-b2^t), lr, wd]. Returns
    ``(params_shard[out_dtype], master', mu', nu')``.
    """
    out_dtype = jnp.dtype(out_dtype)
    reason = jnp_reason(master)
    _log_path_once(reason)
    if reason is not None:
        return _jnp_flat(master, mu, nu, grad, scalars, eps, out_dtype)
    return pallas_flat_adamw(master, mu, nu, grad, scalars, eps=eps,
                             out_dtype=out_dtype)
