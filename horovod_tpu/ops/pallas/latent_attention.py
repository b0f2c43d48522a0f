"""One decode step of latent (MLA) attention over the live part of a
positions-last latent cache.

The cache of a latent-attention layer is one ``rank``-wide latent and one
``rope_dim``-wide rotary key a position, shared by every head:
``latent`` ``(rows, rank, cache_len)`` and ``rope_key`` ``(rows,
rope_dim, cache_len)``. In the absorbed form a decode step's query of
head ``h`` is already in the latent's space (``qt_h``, ``rank`` wide,
beside its rotary part), the score of position ``s`` is ``qt_h .
latent[:, s] + q_rope_h . rope_key[:, s]`` and the output is the
softmax-weighted sum of the latents themselves: keys and values are the
same bytes. XLA's masked contractions read all ``cache_len`` positions
of every row, twice (once for the scores, once for the sum). This kernel
reads, for row ``b``, the position tiles ``0 .. positions[b] // TILE``
once and none past them: the grid is (rows, tiles), the block index of a
dead tile is clamped to the row's last live one, and a block whose index
does not change is not fetched again.

All heads of a row share a tile, so both contractions run on the matrix
unit (``heads x rank`` against ``rank x TILE``, and ``heads x TILE``
against the same tile transposed), with the running maximum and sum of
an online softmax in float32 between the tiles. The new token's column
is written before the call (``kv_cache_write``), as the other
``models/hybrid.py`` layers write theirs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import (SERVED_KERNELS, ServedKernel,
                                             use_interpret)
from horovod_tpu.ops.pallas.flash_attention import NEG_INF
from horovod_tpu.ops.pallas.kv_cache_write import LANES

# positions a grid step: a (512 + 64) x 1024 bfloat16 block is 1.2 MB,
# about what the chip moves in the time a grid step costs ten times over
TILE = 1024
KERNEL = "latent_decode_attention"


def tile_of(cache_len: int, tile: int = TILE) -> int:
    """The positions a grid step takes: ``tile``, or the largest halving
    of it (down to a lane tile) that divides ``cache_len``, or all of a
    cache whose length is no whole number of lane tiles (a block is a
    multiple of the lane tile or the whole dimension)."""
    while tile >= LANES:
        if cache_len % tile == 0:
            return tile
        tile //= 2
    return cache_len


def live_tiles(positions, cache_len: int):
    """Position tiles a decode step at ``positions`` reads of a layer's
    leaves, the tiles of all its rows, and the positions it attends
    (numpy, for the engine's counters)."""
    tile = tile_of(cache_len)
    pos = np.clip(np.asarray(positions), 0, cache_len - 1)
    return (int((pos // tile + 1).sum()), pos.size * (cache_len // tile),
            int((pos + 1).sum()))


SERVED_KERNELS[KERNEL] = ServedKernel(live_tiles, counts_positions=True)


def _kernel(pos_ref, qt_ref, qr_ref, latent_ref, rope_ref, o_ref, acc, peak,
            total, *, scale, tile):
    # pos_ref: (rows,) in SMEM; qt_ref: (1, heads, rank); qr_ref: (1,
    # heads, rope_dim); latent_ref: (1, rank, tile) and rope_ref: (1,
    # rope_dim, tile), this step's tile of the row (its last live one
    # again where the step is past it); o_ref: (1, heads, rank); acc:
    # (heads, rank) float32; peak/total: (heads, LANES) float32, a row's
    # running maximum and sum broadcast along the lanes
    row, step = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[row]

    @pl.when(step == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        peak[...] = jnp.full_like(peak, NEG_INF)
        total[...] = jnp.zeros_like(total)

    @pl.when(step * tile <= pos)
    def _():
        latent = latent_ref[0]
        s = (jax.lax.dot_general(qt_ref[0], latent, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], rope_ref[0],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)) \
            * scale
        at = step * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at <= pos, s, NEG_INF)
        # position 0 is always live, so a live tile's maximum is finite
        before = peak[:, :1]
        now = jnp.maximum(before, jnp.max(s, axis=-1, keepdims=True))
        kept = jnp.exp(before - now)
        p = jnp.exp(s - now)
        total[...] = jnp.broadcast_to(
            kept * total[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            total.shape)
        peak[...] = jnp.broadcast_to(now, peak.shape)
        acc[...] = kept * acc[...] + jax.lax.dot_general(
            p.astype(latent.dtype), latent, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc[...] / total[:, :1]).astype(o_ref.dtype)


def latent_decode_attention(qt, q_rope, latent, rope_key, positions, scale):
    """``softmax((qt . latent + q_rope . rope_key) * scale) . latent`` over
    positions ``0 .. positions[b]`` of each row: (rows, heads, rank) in
    the latent's dtype.

    ``qt``: (rows, heads, rank) and ``q_rope``: (rows, heads, rope_dim),
    cast to the cache's dtype; ``latent``: (rows, rank, cache_len) and
    ``rope_key``: (rows, rope_dim, cache_len); ``positions``: (rows,)
    int32, clamped into the cache. Scores, softmax and accumulation in
    float32.
    """
    return _latent_decode_attention(qt, q_rope, latent, rope_key, positions,
                                    scale=float(scale),
                                    interpret=use_interpret())


# jitted so that a model's layers share one trace and one Mosaic
# lowering of the kernel
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_decode_attention(qt, q_rope, latent, rope_key, positions, *,
                             scale, interpret):
    rows, rank, cache_len = latent.shape
    heads, turned = qt.shape[1], q_rope.shape[-1]
    tile = tile_of(cache_len)
    positions = jnp.clip(positions.astype(jnp.int32), 0, cache_len - 1)

    def mine(width):
        return pl.BlockSpec((1, heads, width), lambda b, t, pos: (b, 0, 0))

    def live(width):
        return pl.BlockSpec(
            (1, width, tile),
            lambda b, t, pos: (b, 0, jnp.minimum(t, pos[b] // tile)))

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows, cache_len // tile),
            in_specs=[mine(rank), mine(turned), live(rank), live(turned)],
            out_specs=mine(rank),
            scratch_shapes=[pltpu.VMEM((heads, rank), jnp.float32),
                            pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, rank), latent.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=KERNEL,
    )(positions, qt.astype(latent.dtype), q_rope.astype(latent.dtype),
      latent, rope_key)
