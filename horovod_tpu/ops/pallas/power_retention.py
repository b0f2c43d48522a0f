"""Power retention's two reads of its state, without the features ever
reaching memory.

``models/hybrid.py`` keeps, for each key/value head, the state ``S``
(``D x d`` float32, ``D = d (d + 1) / 2``) and its normaliser ``z``
(``D``), laid out by rows of distances: entry ``o d + a`` pairs ``x_a``
with ``x_((a + o) mod d)`` (``power_features``). A query reads them as
``phi(q)^T S`` and ``phi(q)^T z``. XLA has to build ``phi(q)`` - 8,256
numbers for each 128 of a query - in memory first, and that write and
its read back are most of a prefill's time and a seventh of a decode
step's. Here a row of distances of ``phi(q)`` is ``q`` times a lane
rotation of ``q``, made in registers, and goes straight into the product.

:func:`read_state` serves a prefill's chunk: many queries against one
state that does not change (the matrix unit does the work).
:func:`step_state` serves a decode step: one token a slot, whose state is
read, updated in place and read by its queries in one pass over it (the
memory system does the work; XLA's update-then-product passes over the
state three times).

Both take the state *padded* to ``(d/2 + 1) d`` rows, whole lane tiles,
viewed as ``(d/2 + 1, d, d)``: the rows past ``D`` are zeros and stay
zeros, and the features that meet them are never looked at. A prefill's
state has the pairs on the sublanes and the values on the lanes (``[o, a,
e]``: the right operand of features x state). The cache's has them the
other way round (``[o, e, a]``): there a token's key features, which vary
with ``a``, lie along the lanes as the key does, one value a sublane, and
no vector has to be turned from lanes to sublanes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import use_interpret

F32 = jnp.float32
# a decode step's queries a key/value head are padded to whole sublane
# tiles (8 rows of float32)
STEP_ROWS = 8
# the whole state of a slot's key/value head is one block of step_state,
# in and out and each twice for the pipeline: 17 MB at d = 128
STEP_VMEM_BYTES = 48 << 20
# queries a grid step of read_state, and distances a matrix product of it
# (their features side by side are the product's left operand)
READ_ROWS = 256
READ_TURNS = 13


def turns(d: int) -> int:
    """Rows of distances: ``0 .. d/2``."""
    return d // 2 + 1


def turn_weights(d: int):
    """The weight of each row of distances, (turns, 1): 1 for the squares,
    ``sqrt(2)`` for the pairs, both over ``sqrt(d)`` (the score's scale)."""
    first = jnp.arange(turns(d))[:, None] == 0
    return jnp.where(first, 1.0, math.sqrt(2.0)).astype(F32) / math.sqrt(d)


def _turned(x, o):
    """``x`` rotated by ``o`` lanes: lane ``a`` gets ``x[(a + o) % d]``."""
    return x if o == 0 else pltpu.roll(x, x.shape[-1] - o, x.ndim - 1)


def _read_kernel(q_ref, s_ref, z_ref, num_ref, den_ref):
    q = q_ref[0].astype(F32)                              # (rows, d)
    d = q.shape[-1]
    num = jnp.zeros(q.shape, F32)
    den = jnp.zeros(q.shape, F32)
    for first in range(0, turns(d), READ_TURNS):
        features = []
        for o in range(first, min(first + READ_TURNS, turns(d))):
            # rounded as the product takes them, above and below the line
            f = (q * _turned(q, o)).astype(s_ref.dtype)
            den = den + f.astype(F32) * z_ref[0, o][None, :]
            features.append(f)
        block = s_ref[0, first:first + len(features)]     # (n, d, d)
        num = num + jnp.dot(jnp.concatenate(features, axis=1),
                            block.reshape(-1, d),
                            preferred_element_type=F32)
    num_ref[0] = num
    den_ref[0] = den


def read_state(q, state, norm):
    """``phi(q)^T S`` and ``phi(q)^T z`` for many queries a state.

    ``q``: (batch, queries, d) in the matrix operands' dtype; ``state``:
    (batch, turns, d, d) in the same dtype and ``norm``: (batch, turns, d)
    float32, both padded and both already times :func:`turn_weights`.
    Returns (batch, queries, d) float32 and the sums (batch, queries)."""
    batch, rows, d = q.shape
    # all the rows, or the largest whole number of sublane tiles (16 rows
    # of bfloat16) under READ_ROWS that divides them
    block = rows if rows <= READ_ROWS else next(
        (b for b in range(READ_ROWS, 0, -16) if rows % b == 0), None)
    if block is None:
        raise ValueError(f"{rows} queries a state: past {READ_ROWS} the "
                         f"count has to be a multiple of 16")
    num, den = pl.pallas_call(
        _read_kernel,
        grid=(batch, rows // block),
        in_specs=[pl.BlockSpec((1, block, d), lambda b, m: (b, m, 0)),
                  pl.BlockSpec((1, turns(d), d, d), lambda b, m: (b, 0, 0, 0)),
                  pl.BlockSpec((1, turns(d), d), lambda b, m: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, block, d), lambda b, m: (b, m, 0)),
                   pl.BlockSpec((1, block, d), lambda b, m: (b, m, 0))],
        out_shape=[jax.ShapeDtypeStruct((batch, rows, d), F32),
                   jax.ShapeDtypeStruct((batch, rows, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=use_interpret(), name="retention_read",
    )(q, state, norm)
    return num, den.sum(axis=-1)


def _step_kernel(keep_ref, q_ref, k_ref, v_ref, s_ref, z_ref,
                 new_s_ref, new_z_ref, num_ref, den_ref):
    keep = keep_ref[pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)]
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]  # (rows, d) (1, d) (d, d)
    d = q.shape[-1]
    weights = [1.0 / math.sqrt(d)] + [math.sqrt(2.0 / d)] * (turns(d) - 1)
    half = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) < d // 2
    num = jnp.zeros(q.shape, F32)
    den = jnp.zeros(q.shape, F32)
    for o in range(turns(d)):
        f_k = k * _turned(k, o) * weights[o]
        if o == turns(d) - 1:       # each pair once: a < d/2 with a + d/2
            f_k = jnp.where(half, f_k, 0.0)
        state = keep * s_ref[0, 0, o] + v * f_k             # [e, a]
        norm = keep * z_ref[0, 0, o:o + 1] + f_k            # (1, d)
        new_s_ref[0, 0, o] = state
        new_z_ref[0, 0, o:o + 1] = norm
        f_q = q * _turned(q, o) * weights[o]
        # float32 at the highest precision: the pass is bound by the
        # state's bytes, and a sum of 8,256 cancelling products of rounded
        # operands would lose a small normaliser
        num = num + jax.lax.dot_general(
            f_q, state, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
        den = den + f_q * norm
    num_ref[0, 0] = num
    den_ref[0, 0] = den


def step_state(state, norm, q, k, v, keep):
    """One token a row: ``S = keep S + phi(k) v^T``, ``z = keep z +
    phi(k)``, and the new state read by the row's queries, ``phi(q)^T S``
    and ``phi(q)^T z``, in one pass over ``S``, which is updated in place.

    ``state``: (batch, kv_heads, turns, d, d) float32 as ``[o, e, a]``
    and ``norm``: (batch, kv_heads, turns, d), padded (module docstring);
    ``q``: (batch, kv_heads, queries, d), ``k``/``v``: (batch, kv_heads,
    d) and ``keep``: (batch, kv_heads). Everything is float32, the read's
    products at the highest precision. Returns the new state and
    normaliser, (batch, kv_heads, queries, d) and the sums (batch,
    kv_heads, queries)."""
    batch, groups, rows, d = q.shape
    padded = -(-rows // STEP_ROWS) * STEP_ROWS
    q = jnp.pad(q, ((0, 0), (0, 0), (0, padded - rows), (0, 0)))
    mine = lambda *shape: pl.BlockSpec(
        (1, 1) + shape, lambda b, g, keep: (b, g) + (0,) * len(shape))
    state, norm, num, den = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, groups),
            in_specs=[mine(padded, d), mine(1, d), mine(d, d),
                      mine(turns(d), d, d), mine(turns(d), d)],
            out_specs=[mine(turns(d), d, d), mine(turns(d), d),
                       mine(padded, d), mine(padded, d)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct(norm.shape, F32),
                   jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(q.shape, F32)],
        # operands count the prefetched scalars: state 4 -> 0, norm 5 -> 1
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=STEP_VMEM_BYTES),
        interpret=use_interpret(), name="retention_step",
    )(keep.reshape(-1), q, k[:, :, None, :],
      # a value a sublane, the same in every lane
      jnp.broadcast_to(v[..., :, None], v.shape + (d,)), state, norm)
    return state, norm, num[:, :, :rows], den[:, :, :rows].sum(axis=-1)
