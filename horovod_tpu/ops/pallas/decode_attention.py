"""One decode step of a positions-last KV cache in one kernel: the new
token's key and value columns go into the cache, and its query attends
over the live part of its row, the new column included.

A decode step has one query a row, and row ``b`` may attend keys
``0 .. positions[b]``, of which the last is its own. The cache is
``(rows, heads, head_dim, cache_len)``, so a lane tile of 128 positions
is one ``(heads, head_dim, 128)`` block of a row. XLA's masked
contraction reads all ``cache_len`` positions of every row and masks
afterwards; both of its contractions are at the memory
roofline, so the time is the bytes. This kernel fetches, for each row,
the tiles ``0 .. positions[b] // 128`` of the key leaf and then of the
value leaf and nothing past them: stale keys of an earlier occupant are
masked inside the last live tile and never read in the dead ones.

The leaves stay in HBM. One grid step serves one row; the tiles of all
rows form one stream (row 0's key tiles, its value tiles, row 1's key
tiles, ...) that is copied through a ring of ``RING`` VMEM buffers, so
that the copies run ahead across the rows' boundaries and a row's fixed
work (broadcasting its query, the final reduction) hides under the next
row's copies. Scores of the live tiles are kept in VMEM (one query a
row: ``heads x cache_len`` floats), the maximum is taken while they are
written, and the values are weighted in a second pass: the softmax is
the plain one, in float32, with no rescaling of partial sums. Both
contractions run on the vector unit with ``head_dim`` on the sublanes,
as XLA's do: one query a head leaves the matrix unit nothing to reuse.

The write rides on the read. Tile ``positions[b] // 128`` of either leaf,
the row's last live one, is the tile that takes the new column, and the
stream fetches it anyway. When it arrives the kernel selects the column
into lane ``positions[b] % 128`` (in the cache's dtype, so the score sees
what a later step reads back), scores or weighs the patched tile, and
copies it back to its place in the leaf, which is aliased to the result.
The patched tile leaves from a staging buffer of its own, one a leaf, so
the ring refills the slot at once as it always did; a staging buffer's
copy is waited for when the next row needs the buffer (the other leaf's
tiles and a row's fixed work lie between) and at the end of the last
row. No hazard crosses rows or tiles: the stream runs ahead into other
rows only, and each patched tile is fetched once, before it is written.
A separate write kernel (``kv_cache_write``) moved the same tile in and
out once more for each leaf and paid a grid step a row for it.
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

KERNEL = "decode_attention"
# tile buffers in the ring: one is being computed on, the others' copies
# are in flight
RING = 8


def takes_kernel(new_tokens: int, cache_len: int) -> bool:
    """The shape condition: one new token a row against a cache whose
    length is a whole number of lane tiles."""
    return new_tokens == 1 and cache_len % LANES == 0


def live_tiles(positions, cache_len: int):
    """Lane tiles a decode step at ``positions`` reads of one leaf, the
    tiles of all its rows, the positions it attends (``ServedKernel``)."""
    pos = np.clip(np.asarray(positions), 0, cache_len - 1)
    return (int((pos // LANES + 1).sum()), pos.size * (cache_len // LANES),
            int((pos + 1).sum()))


SERVED_KERNELS[KERNEL] = ServedKernel(live_tiles, writes_step=True)


def _attention_kernel(pos_ref, new_ref, k_hbm, v_hbm, o_ref, k_out, v_out,
                      ring, sems, cursor, q_wide, scores, acc, patched,
                      patched_sems, *, scale):
    # pos_ref: (rows,) in SMEM; new_ref: (1, head_dim, 3 * heads), this
    # row's query, new key and new value, a column a head each; o_ref:
    # (1, head_dim, heads); k_hbm/v_hbm: the whole leaves, in HBM, and
    # k_out/v_out the same buffers as results; ring: (RING, heads,
    # head_dim, LANES); patched: (2, heads, head_dim, LANES), the key and
    # the value tile on their way back; scores: (tiles, heads, 1, LANES);
    # cursor (SMEM): the stream's next item to copy (row, item of the
    # row), items copied, items consumed. Positions are not negative, so lax's
    # truncating div and rem serve. The loops over heads are traced once
    # and unrolled when lowered: written out in Python the kernel took
    # twice as long to trace and lower (paid at every start), and left
    # rolled it ran a third slower.
    rows = pl.num_programs(0)
    row = pl.program_id(0)
    _, heads, head_dim, _ = ring.shape
    div, rem = jax.lax.div, jax.lax.rem

    def tiles_of(r):
        return div(pos_ref[r], LANES) + 1

    def copy(leaf, r, tile, slot):
        start = pl.multiple_of(tile * LANES, LANES)
        return pltpu.make_async_copy(
            leaf.at[r, :, :, pl.ds(start, LANES)], ring.at[slot],
            sems.at[slot])

    def fetch_next():
        """Start the copy of the stream's next tile, if a row is left."""
        r, item, copied = cursor[0], cursor[1], cursor[2]

        @pl.when(r < rows)
        def _():
            n = tiles_of(r)
            slot = rem(copied, RING)

            @pl.when(item < n)
            def _():
                copy(k_hbm, r, item, slot).start()

            @pl.when(item >= n)
            def _():
                copy(v_hbm, r, item - n, slot).start()

            done = item + 1 == 2 * n
            cursor[0] = jax.lax.select(done, r + 1, r)
            cursor[1] = jax.lax.select(done, 0, item + 1)
            cursor[2] = copied + 1

    def wait_next():
        """Block until the stream's next tile is in its buffer."""
        slot = rem(cursor[3], RING)
        copy(k_hbm, 0, 0, slot).wait()    # any copy of this size and slot
        cursor[3] = cursor[3] + 1
        return slot

    def copy_back(which, tile):
        start = pl.multiple_of(tile * LANES, LANES)
        return pltpu.make_async_copy(
            patched.at[which],
            (k_out, v_out)[which].at[row, :, :, pl.ds(start, LANES)],
            patched_sems.at[which])

    def place_column(which, slot, tile):
        """Put this row's new key (``which`` 0) or value (1) into its lane
        of the tile in ``ring[slot]``, and start the tile's way back."""
        @pl.when(row > 0)
        def _():
            copy_back(which, 0).wait()    # the row before's, of this size

        here = jax.lax.broadcasted_iota(
            jnp.int32, (head_dim, LANES), 1) == rem(pos, LANES)
        # a static slice a head, as q_wide's: Mosaic has no dynamic one
        # along the lanes
        for h in range(heads):
            mine = (1 + which) * heads + h
            column = jnp.broadcast_to(
                new_ref[0, :, mine:mine + 1].astype(ring.dtype),
                (head_dim, LANES))
            tile_h = jnp.where(here, column, ring[slot, h])
            ring[slot, h] = tile_h
            patched[which, h] = tile_h
        copy_back(which, tile).start()

    @pl.when(row == 0)
    def _():
        for i in range(4):
            cursor[i] = 0
        jax.lax.fori_loop(0, RING, lambda _, c: fetch_next() or c, 0)

    pos = pos_ref[row]
    n = tiles_of(row)
    q = new_ref[0, :, :heads].astype(jnp.float32)
    for h in range(heads):
        q_wide[h] = jnp.broadcast_to(q[:, h:h + 1], (head_dim, LANES))

    def score_tile(t, peak):
        slot = wait_next()
        pl.when(t == n - 1)(lambda: place_column(0, slot, t))
        live = t * LANES + jax.lax.broadcasted_iota(
            jnp.int32, (1, LANES), 1) <= pos
        dead = jnp.full((1, LANES), NEG_INF, jnp.float32)

        def score_head(h, carry):
            k = ring[slot, h].astype(jnp.float32)
            s = jnp.sum(k * q_wide[h], axis=0, keepdims=True) * scale
            scores[t, h] = jax.lax.select(live, s, dead)
            return carry

        jax.lax.fori_loop(0, heads, score_head, 0, unroll=True)
        fetch_next()
        return jnp.maximum(peak, scores[t])

    peak = jax.lax.fori_loop(
        0, n, score_tile,
        jnp.full((heads, 1, LANES), NEG_INF, jnp.float32))
    peak = jnp.max(peak, axis=-1, keepdims=True)

    def exp_tile(t, total):
        p = jnp.exp(scores[t] - peak)
        scores[t] = p
        return total + p

    total = jax.lax.fori_loop(
        0, n, exp_tile, jnp.zeros((heads, 1, LANES), jnp.float32))
    share = 1.0 / jnp.sum(total, axis=-1, keepdims=True)
    acc[...] = jnp.zeros_like(acc)

    def weigh_tile(t, carry):
        slot = wait_next()
        pl.when(t == n - 1)(lambda: place_column(1, slot, t))
        scores[t] = scores[t] * share

        def weigh_head(h, carry):
            v = ring[slot, h].astype(jnp.float32)
            acc[h] += v * jnp.broadcast_to(scores[t, h], (head_dim, LANES))
            return carry

        jax.lax.fori_loop(0, heads, weigh_head, 0, unroll=True)
        fetch_next()
        return carry

    jax.lax.fori_loop(0, n, weigh_tile, 0)
    for h in range(heads):
        o_ref[0, :, h:h + 1] = jnp.sum(
            acc[h], axis=-1, keepdims=True).astype(o_ref.dtype)

    @pl.when(row == rows - 1)
    def _():
        copy_back(0, 0).wait()
        copy_back(1, 0).wait()


def decode_attention(q, k_new, v_new, k_cache, v_cache, positions):
    """``(o, k_cache, v_cache)``: the caches with ``k_new[b]``/``v_new[b]``
    written at position ``positions[b]`` of row ``b`` and every other
    element as it was, and the attention of one query a row over keys
    ``0 .. positions[b]`` of the written cache.

    ``q``/``k_new``/``v_new``: (rows, heads, head_dim); ``k_cache``/
    ``v_cache``: (rows, heads, head_dim, cache_len) with ``cache_len`` a
    multiple of 128, aliased to the results (in place where the caller
    donates them); ``positions``: (rows,) int32, clamped into the cache.
    The new columns are cast to the cache's dtype before they are scored.
    Scores, softmax and accumulation in float32; ``o`` has the values'
    dtype.
    """
    return _decode_attention(q, k_new, v_new, k_cache, v_cache, positions,
                             interpret=use_interpret())


# jitted so that a model's layers share one trace and one Mosaic
# lowering of the kernel (seconds of every start at 12 layers)
@functools.partial(jax.jit, static_argnames="interpret")
def _decode_attention(q, k_new, v_new, k_cache, v_cache, positions, *,
                      interpret):
    rows, heads, head_dim, cache_len = k_cache.shape
    if cache_len % LANES:
        raise ValueError(f"cache length {cache_len} is not a multiple of "
                         f"{LANES}")
    positions = jnp.clip(positions.astype(jnp.int32), 0, cache_len - 1)
    # the row's three vectors go in as one operand through one transpose,
    # head_dim on the sublanes as the cache has it and the 3 x heads
    # columns side by side along the lanes; the new columns round to the
    # cache's dtype first (widening them again to join q loses nothing)
    new = jnp.concatenate([q, k_new.astype(k_cache.dtype),
                           v_new.astype(v_cache.dtype)], axis=1)
    mine = pl.BlockSpec((1, head_dim, heads), lambda b, pos: (b, 0, 0))
    leaf = pl.BlockSpec(memory_space=pl.ANY)
    out, k_cache, v_cache = pl.pallas_call(
        functools.partial(_attention_kernel,
                          scale=1.0 / float(np.sqrt(head_dim))),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((1, head_dim, 3 * heads),
                                   lambda b, pos: (b, 0, 0)), leaf, leaf],
            out_specs=[mine, leaf, leaf],
            scratch_shapes=[
                pltpu.VMEM((RING, heads, head_dim, LANES), k_cache.dtype),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((heads, head_dim, LANES), jnp.float32),
                pltpu.VMEM((cache_len // LANES, heads, 1, LANES), jnp.float32),
                pltpu.VMEM((heads, head_dim, LANES), jnp.float32),
                pltpu.VMEM((2, heads, head_dim, LANES), k_cache.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((rows, head_dim, heads),
                                        v_cache.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # operands count from the positions
        input_output_aliases={2: 1, 3: 2},
        # the ring's copies run on from one row's step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=KERNEL,
    )(positions, new.transpose(0, 2, 1), k_cache, v_cache)
    return out.transpose(0, 2, 1), k_cache, v_cache
