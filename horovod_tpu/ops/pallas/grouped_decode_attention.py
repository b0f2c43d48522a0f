"""One decode step of grouped-query attention over the live part of a
positions-last key/value cache.

A grouped-query layer keeps ``groups`` key/value heads and ``per`` query
heads to each of them; its cache is the layout ``kv_cache_write`` writes
and ``decode_attention`` reads, ``(rows, groups, head_dim, cache_len)``,
and a decode step's row ``b`` may attend keys ``0 .. positions[b]``.
``decode_attention`` has one query a head and runs on the vector unit;
with ``per`` queries against every key that work is ``per`` times as
much and no longer hides under the copies. Here the ``per`` queries of a
group are the rows of a matrix product: ``per x head_dim`` against a
group's ``head_dim x TILE`` keys, and ``per x TILE`` weights against the
same tile of values transposed, on the matrix unit, with the running
maximum and sum of an online softmax in float32 between the tiles.

The reading is ``latent_attention``'s: the grid is (rows, tiles), the
block index of a dead tile is clamped to the row's last live one, and a
block whose index does not change is not fetched again, so a row costs
the tiles ``0 .. positions[b] // TILE`` of both leaves and a grid step
for each tile past them. The new token's columns are written before the
call (``kv_cache_write``), as the other ``models/hybrid.py`` layers
write theirs.

A model that generates by blocks runs ``block`` positions a row each
pass, every one of them seeing the row's cached keys and the block's own
(:func:`grouped_block_attention`): the same kernel with ``block x per``
query rows a key/value head (32 at a block of 4 and 8 queries a head,
still far under the 240 operations a byte at which the chip's matrix
unit would bound it) and a live length that ends at the block's end. The
block's columns are in the cache before the call, provisional until the
pass that leaves them there (``kv_cache_write.write_block``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas import latent_attention
from horovod_tpu.ops.pallas._backend import (SERVED_KERNELS, ServedKernel,
                                             use_interpret)
from horovod_tpu.ops.pallas.flash_attention import NEG_INF
from horovod_tpu.ops.pallas.kv_cache_write import LANES

# positions a grid step: at 8 groups of 128 a (8 x 128) x 512 bfloat16
# block is 1 MB a leaf, what the chip moves in the time of about four
# grid steps; both leaves, double buffered, are 4 MB of VMEM
TILE = 512
KERNEL = "grouped_decode_attention"


def tile_of(cache_len: int) -> int:
    """The positions a grid step takes (``latent_attention.tile_of``'s
    rule from this kernel's ``TILE``)."""
    return latent_attention.tile_of(cache_len, TILE)


def live_tiles(positions, cache_len: int):
    """Position tiles a decode step at ``positions`` reads of one leaf, the
    tiles of all its rows, the positions it attends (``ServedKernel``)."""
    tile = tile_of(cache_len)
    pos = np.clip(np.asarray(positions), 0, cache_len - 1)
    return (int((pos // tile + 1).sum()), pos.size * (cache_len // tile),
            int((pos + 1).sum()))


SERVED_KERNELS[KERNEL] = ServedKernel(live_tiles)


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc, peak, total, *, scale,
            tile):
    # pos_ref: (rows,) in SMEM; q_ref/o_ref: (1, groups, per, head_dim);
    # k_ref/v_ref: (1, groups, head_dim, tile), this step's tile of the
    # row (its last live one again where the step is past it); acc:
    # (groups, per, head_dim) float32; peak/total: (groups, per, LANES)
    # float32, a query's running maximum and sum broadcast along the lanes
    row, step = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[row]
    groups, per = acc.shape[:2]

    @pl.when(step == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        peak[...] = jnp.full_like(peak, NEG_INF)
        total[...] = jnp.zeros_like(total)

    @pl.when(step * tile <= pos)
    def _():
        at = step * tile + jax.lax.broadcasted_iota(jnp.int32, (per, tile), 1)
        seen = at <= pos
        for g in range(groups):
            keys, values = k_ref[0, g], v_ref[0, g]
            s = jax.lax.dot_general(
                q_ref[0, g], keys, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, NEG_INF)
            # position 0 is always live, so a live tile's maximum is finite
            before = peak[g, :, :1]
            now = jnp.maximum(before, jnp.max(s, axis=-1, keepdims=True))
            kept = jnp.exp(before - now)
            p = jnp.exp(s - now)
            total[g] = jnp.broadcast_to(
                kept * total[g, :, :1] + jnp.sum(p, axis=-1, keepdims=True),
                (per, LANES))
            peak[g] = jnp.broadcast_to(now, (per, LANES))
            acc[g] = kept * acc[g] + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc[...] / total[:, :, :1]).astype(o_ref.dtype)


def grouped_decode_attention(q, k_cache, v_cache, positions, scale):
    """``softmax(q . keys * scale) . values`` over positions ``0 ..
    positions[b]`` of each row, query head ``h`` against key/value head
    ``h // per``: (rows, groups, per, head_dim) in the values' dtype.

    ``q``: (rows, groups, per, head_dim), cast to the cache's dtype;
    ``k_cache``/``v_cache``: (rows, groups, head_dim, cache_len), the new
    token's columns already in them; ``positions``: (rows,) int32,
    clamped into the cache. Scores, softmax and accumulation in float32.
    """
    return _grouped_decode_attention(q, k_cache, v_cache, positions,
                                     scale=float(scale),
                                     interpret=use_interpret())


def grouped_block_attention(q, k_cache, v_cache, starts, scale):
    """One pass of a block: ``softmax(q . keys * scale) . values`` over
    positions ``0 .. starts[b] + block - 1`` of each row for every query
    of the block alike (nothing inside a block is masked): (rows, block,
    groups, per, head_dim).

    ``q``: (rows, block, groups, per, head_dim); ``k_cache``/``v_cache``:
    (rows, groups, head_dim, cache_len), the block's own columns already
    in them; ``starts``: (rows,) int32, the block's first position."""
    rows, block, groups, per, head_dim = q.shape
    o = grouped_decode_attention(
        q.transpose(0, 2, 1, 3, 4).reshape(rows, groups, block * per,
                                           head_dim),
        k_cache, v_cache, starts + (block - 1), scale)
    return o.reshape(rows, groups, block, per, head_dim).transpose(
        0, 2, 1, 3, 4)


# jitted so that a model's layers share one trace and one Mosaic
# lowering of the kernel
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _grouped_decode_attention(q, k_cache, v_cache, positions, *, scale,
                              interpret):
    rows, groups, head_dim, cache_len = k_cache.shape
    per = q.shape[2]
    tile = tile_of(cache_len)
    positions = jnp.clip(positions.astype(jnp.int32), 0, cache_len - 1)
    mine = pl.BlockSpec((1, groups, per, head_dim),
                        lambda b, t, pos: (b, 0, 0, 0))
    live = pl.BlockSpec(
        (1, groups, head_dim, tile),
        lambda b, t, pos: (b, 0, 0, jnp.minimum(t, pos[b] // tile)))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows, cache_len // tile),
            in_specs=[mine, live, live], out_specs=mine,
            scratch_shapes=[
                pltpu.VMEM((groups, per, head_dim), jnp.float32),
                pltpu.VMEM((groups, per, LANES), jnp.float32),
                pltpu.VMEM((groups, per, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, v_cache.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=KERNEL,
    )(positions, q.astype(k_cache.dtype), k_cache, v_cache)
