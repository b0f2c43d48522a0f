"""In-place write of one new token per row into a KV cache.

The serving cache is stored positions-last, ``(rows, heads, head_dim,
cache_len)``: that is the layout both attention contractions read, and
its (sublane, lane) tiles over ``(head_dim, cache_len)`` are unpadded. A
decode step writes one position per row, each row at its own position.
XLA:TPU has no cheap form for that: a batched scatter becomes one serial
trip per row, a select rewrites the whole cache. This kernel visits one
row per grid step, fetches only the lane tile that holds the row's
position (``heads x head_dim x 128`` elements), replaces one lane and
writes the tile back into the same buffer (the cache operand is aliased
to the result, so with the cache donated nothing else moves).

Who calls it: ``models/hybrid.py`` for the key, value and compressed-key
leaves of its sparse layers (it has its own attention), and
``models/transformer.py`` ``write_cache_rows`` for a one-token step on a
cache whose length is no whole number of lane tiles. The dense decode
step on whole tiles does not: ``ops/pallas/decode_attention`` fetches the
same tile to attend over it and writes the new column from there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import (SERVED_KERNELS, ServedKernel,
                                             use_interpret)

LANES = 128
# writes a decode step's new column and attends nothing
KERNEL = "kv_cache_write"
SERVED_KERNELS[KERNEL] = ServedKernel(writes_step=True)


def _write_kernel(pos_ref, new_ref, cache_ref, out_ref):
    # new_ref: (1, head_dim, heads); cache_ref/out_ref: (1, heads,
    # head_dim, width), the lane tile pos // width of this row
    _, heads, head_dim, width = cache_ref.shape
    lane = pos_ref[pl.program_id(0)] % width
    here = jax.lax.broadcasted_iota(jnp.int32, (head_dim, width), 1) == lane
    for h in range(heads):
        column = jnp.broadcast_to(new_ref[0, :, h:h + 1], (head_dim, width))
        out_ref[0, h] = jnp.where(here, column, cache_ref[0, h])


def write_token(cache, new, positions):
    """``cache`` with ``new[b]`` written at position ``positions[b]`` of
    row ``b``, every other element as it was.

    ``cache``: (rows, heads, head_dim, cache_len); ``new``: (rows, heads,
    head_dim) of the cache's dtype; ``positions``: (rows,) int32, clamped
    into the cache.
    """
    return _write_token(cache, new, positions, interpret=use_interpret())


# jitted so that a model's layers share one trace and one Mosaic
# lowering of the kernel (a second of every start at 12 layers)
@functools.partial(jax.jit, static_argnames="interpret")
def _write_token(cache, new, positions, *, interpret):
    rows, heads, head_dim, cache_len = cache.shape
    # a block's last dimension is a multiple of the lane tile or the
    # whole dimension
    width = LANES if cache_len % LANES == 0 else cache_len
    positions = jnp.clip(positions.astype(jnp.int32), 0, cache_len - 1)
    tile = pl.BlockSpec((1, heads, head_dim, width),
                        lambda b, pos: (b, 0, 0, pos[b] // width))
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            # head_dim on the sublanes, as the cache has it: the kernel
            # then only broadcasts a column along the lanes
            in_specs=[pl.BlockSpec((1, head_dim, heads),
                                   lambda b, pos: (b, 0, 0)), tile],
            out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret, name=KERNEL,
    )(positions, new.transpose(0, 2, 1), cache)
