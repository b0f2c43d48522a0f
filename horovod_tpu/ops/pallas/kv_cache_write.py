"""In-place write of one new token per row into a KV cache, or of one
aligned block of them.

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

A model that generates by blocks (``models/hybrid.py``'s full layers with
``block_len`` > 1) writes ``block_len`` columns a row each pass, the
block's own, from an aligned start: they lie in one lane tile, so
:func:`write_block` is the same visit with ``block_len`` lanes replaced.
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
# the same for a block's columns (:func:`write_block`)
BLOCK_KERNEL = "kv_cache_write_block"
SERVED_KERNELS[BLOCK_KERNEL] = ServedKernel(writes_step=True)


def _write_kernel(pos_ref, new_ref, cache_ref, out_ref):
    # new_ref: (1, head_dim, heads); cache_ref/out_ref: (1, heads,
    # head_dim, width), the lane tile pos // width of this row
    _, heads, head_dim, width = cache_ref.shape
    lane = pos_ref[pl.program_id(0)] % width
    here = jax.lax.broadcasted_iota(jnp.int32, (head_dim, width), 1) == lane
    for h in range(heads):
        column = jnp.broadcast_to(new_ref[0, :, h:h + 1], (head_dim, width))
        out_ref[0, h] = jnp.where(here, column, cache_ref[0, h])


def _write_block_kernel(pos_ref, new_ref, cache_ref, out_ref):
    # new_ref: (1, block, head_dim, heads); cache_ref/out_ref as above;
    # the block's columns lane .. lane + block - 1 lie in this tile
    _, heads, head_dim, width = cache_ref.shape
    lane = pos_ref[pl.program_id(0)] % width
    at = jax.lax.broadcasted_iota(jnp.int32, (head_dim, width), 1) - lane
    for h in range(heads):
        tile = cache_ref[0, h]
        for j in range(new_ref.shape[1]):
            column = jnp.broadcast_to(new_ref[0, j, :, h:h + 1],
                                      (head_dim, width))
            tile = jnp.where(at == j, column, tile)
        out_ref[0, h] = tile


def write_token(cache, new, positions):
    """``cache`` with ``new[b]`` written at position ``positions[b]`` of
    row ``b``, every other element as it was.

    ``cache``: (rows, heads, head_dim, cache_len); ``new``: (rows, heads,
    head_dim) of the cache's dtype; ``positions``: (rows,) int32, clamped
    into the cache.
    """
    return _write_token(cache, new, positions, interpret=use_interpret())


def write_block(cache, new, positions):
    """``cache`` with ``new[b, j]`` written at position ``positions[b] +
    j`` of row ``b``, every other element as it was.

    ``cache``: (rows, heads, head_dim, cache_len); ``new``: (rows, block,
    heads, head_dim) of the cache's dtype; ``positions``: (rows,) int32,
    each a multiple of ``block``, which divides the lane tile (the whole
    cache where that is no whole number of tiles), clamped into the cache.
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
    block = new.shape[1] if new.ndim == 4 else None
    if block and width % block:
        raise ValueError(f"a block of {block} positions does not divide "
                         f"the cache's tile of {width}")
    positions = jnp.clip(positions.astype(jnp.int32), 0,
                         cache_len - (block or 1))
    tile = pl.BlockSpec((1, heads, head_dim, width),
                        lambda b, pos: (b, 0, 0, pos[b] // width))
    # head_dim on the sublanes, as the cache has it: the kernel then only
    # broadcasts a column along the lanes
    if block:
        kernel, name = _write_block_kernel, BLOCK_KERNEL
        new = new.transpose(0, 1, 3, 2)
        column = pl.BlockSpec((1, block, head_dim, heads),
                              lambda b, pos: (b, 0, 0, 0))
    else:
        kernel, name = _write_kernel, KERNEL
        new = new.transpose(0, 2, 1)
        column = pl.BlockSpec((1, head_dim, heads), lambda b, pos: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[column, tile], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret, name=name,
    )(positions, new, cache)
