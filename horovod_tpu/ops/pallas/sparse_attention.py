"""Block-sparse causal attention of a prompt as one fused forward kernel.

A block-sparse layer (``models/hybrid.py`` ``BlockSparseAttention``) lets
each query attend the keys ``s <= t`` whose block of ``block_size`` keys
it selected; the heads of a key/value head share one selection. In XLA
that is masked dense attention whose float32 scores go through memory
once to be written, once to be masked, once for the softmax and once to
be cast (``heads x 128 queries x keys x 4 B`` a block of queries: 537 MB
at 32,768 keys). Here scores, mask, softmax and probabilities stay in
VMEM: an online softmax over key tiles, as in the general flash forward,
under a table of bits ``(batch, kv_heads, queries, key blocks)`` that the
caller's selection wrote.

The grid is (batch, head, step): a step is one (query tile, key tile)
pair that holds an element at or under the diagonal, in row order, from
a schedule made at trace time and handed over as scalars. Tiles wholly
above the diagonal are no steps at all, and only the tiles the diagonal
crosses build the token-level ``s <= t`` mask. A query tile is ``tq``
positions of one head (1,024 where the prompt is that long) against
``tk`` keys (1,024) of the head's key/value head, picked by ``head //
per`` in the index map: nothing is repeated in memory. At those sizes a
step does 537 MFLOP on 512 KB of keys and values, four times the chip's
ridge.

The bits widen from blocks to keys on the matrix unit: a step's 128
lanes of the table (one query a row, one key block a lane) are rolled
so that the tile's own blocks come first, turned into 0 / ``MASKED`` and
multiplied by a constant one-hot ``(128, tk)`` matrix that spreads block
``j`` over keys ``j block_size .. (j + 1) block_size - 1``: a bias the
scores are added to. ``MASKED`` is finite (``-inf x 0`` in that product
would be NaN), so a row whose keys so far are all masked gathers
garbage until its first live key wipes it (``exp2(MASKED - m) = 0``):
every query must have a live key, which its own block always is.

A key tile in which no query of the query tile selected a block is a
dead step: its body does not run, and its block indices are those of the
last live step, so nothing is fetched for it (as ``latent_attention``
clamps its dead tiles). The share of key blocks that live steps ran over
the blocks under the diagonal comes back beside the output
(``sparse.live_block_share``).

The queries are a range that may begin ``q_offset`` positions after the
first key (a Python int: the schedule is static), so that the same
kernel serves a prompt continued from a cache.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.ops.pallas._backend import KERNEL_STATS, use_interpret
from horovod_tpu.ops.pallas.flash_attention import LOG2E
from horovod_tpu.ops.pallas.kv_cache_write import LANES

# a masked score: finite (module docstring), and what models/hybrid.py's
# XLA attention masks with
MASKED = -1e30

# positions a query tile and keys a key tile. A step's float32 scores are
# (TILE_Q, TILE_K), 4 MB; keys and values of a step 512 KB, double
# buffered 1 MB. Read on the chip at 32 heads on 2 of width 128 (my chip
# runs, PR 44; host clock round five calls): 1,024 x 1,024 takes 94.7 ms
# at 32,768 positions and 25.1 at 16,384 (92.9 / 87.6 TFLOP/s of the
# triangle's products), 512 x 1,024 106.4 / 28.1, 256 x 1,024 126.1 /
# 33.3, and 512-key tiles 173.5 / 44.8 and more: what a step does once
# whatever its keys (the table's lanes, the rescale of the accumulator,
# the row statistics) wants many keys a step
TILE_Q, TILE_K = 1024, 1024

_LIVE_BLOCK_SHARE = _metrics().gauge(
    "sparse.live_block_share",
    "Key blocks the steps of the last sparse_prompt_attention call that "
    "was read ran over the key blocks under the diagonal (1.0: no key "
    "tile was dead).")


def note_live_block_share(share: float) -> None:
    """Set ``sparse.live_block_share`` from a call's second result, once
    it is on the host (the serving engine reads it with a prefill's first
    token)."""
    _LIVE_BLOCK_SHARE.set(float(share))


# the name ``models/hybrid.py`` sows the share under
KERNEL_STATS["live_block_share"] = note_live_block_share


# The envelope, stated once: any sequence lengths (padded here to whole
# tiles: a multiple of 128, of 256 ... of TILE_K as the length passes
# each), any number of heads a key/value head, a head width that is a
# multiple of 8, and a block size that is a power of two from 8 keys up
# (a key tile then holds whole blocks, at most 128 of them, and 128 lanes
# of the table hold whole key tiles).
def takes_kernel(heads: int, kv_heads: int, head_dim: int,
                 block_size: int) -> bool:
    """Whether :func:`sparse_prompt_attention` takes a layer of this
    shape (the envelope is the comment above; the sequence length is
    never the reason)."""
    return (heads % kv_heads == 0 and head_dim % 8 == 0
            and block_size >= 8 and block_size & (block_size - 1) == 0)


def tiles_of(q_len: int, kv_len: int, block_size: int):
    """``(tq, tk)``: the tile sides for these lengths. A side is the
    power of two at or above the length, from 128 up to the cap; a key
    tile holds at most 128 blocks."""
    def side(length, cap):
        return min(cap, max(LANES, 1 << (length - 1).bit_length()))
    return side(q_len, TILE_Q), max(
        min(side(kv_len, TILE_K), LANES * block_size), block_size)


def schedule(n_q: int, n_k: int, tq: int, tk: int, q_offset: int):
    """The steps, in row order: ``(qi, kj)`` int32 arrays, one (query
    tile, key tile) pair a step, every pair with a key at or before the
    query tile's last position."""
    qi, kj = [], []
    for i in range(n_q):
        reach = min((q_offset + (i + 1) * tq - 1) // tk + 1, n_k)
        qi += [i] * reach
        kj += range(reach)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32)


def _kernel(qi_ref, kj_ref, live_ref, q_ref, k_ref, v_ref, bits_ref,
            spread_ref, o_ref, acc, peak, total, *, scale, tq, tk, blocks,
            groups, per, q_offset):
    # qi_ref/kj_ref: (steps,) and live_ref: (batch x kv_heads x steps,) in
    # SMEM, a step's live flag over the key tile its blocks were fetched
    # for; q_ref/o_ref: (1, 1, tq, d); k_ref/v_ref: (1, 1, tk, d);
    # bits_ref: (1, 1, tq, LANES) int8; spread_ref: (LANES, tk); acc: (tq,
    # d) float32; peak/total: (tq, LANES) float32, a query's running
    # maximum (of unscaled scores) and sum broadcast along the lanes
    step, steps = pl.program_id(2), pl.num_programs(2)
    qi, kj = qi_ref[step], kj_ref[step]
    row = pl.program_id(0) * groups + pl.program_id(1) // per
    live = live_ref[row * steps + step] >> 16
    # the first key of the tile, and how far the tile's first query lies
    # after it
    gap = q_offset + qi * tq - kj * tk

    @pl.when(kj == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        peak[...] = jnp.full_like(peak, -jnp.inf)
        total[...] = jnp.zeros_like(total)

    def update(crossed):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        chosen = bits_ref[0, 0].astype(jnp.float32)
        if blocks < LANES:
            # this tile's blocks to lanes 0 .. blocks - 1
            first = jax.lax.rem(kj * blocks, LANES)
            chosen = pltpu.roll(chosen, jax.lax.rem(LANES - first, LANES), 1)
        s = s + jax.lax.dot_general(
            ((chosen - 1.0) * -MASKED).astype(q.dtype), spread_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if crossed:
            s = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + gap
                >= jax.lax.broadcasted_iota(jnp.int32, s.shape, 1),
                s, MASKED)
        before = peak[:, :1]
        now = jnp.maximum(before, jnp.max(s, axis=-1, keepdims=True))
        kept = jnp.exp2((before - now) * (scale * LOG2E))
        p = jnp.exp2((s - now) * (scale * LOG2E))
        total[...] = jnp.broadcast_to(
            kept * total[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            total.shape)
        peak[...] = jnp.broadcast_to(now, peak.shape)
        acc[...] = kept * acc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # a tile with a key after its first query is crossed by the diagonal
    crossed = gap < tk - 1
    pl.when(jnp.logical_and(live == 1, jnp.logical_not(crossed)))(
        functools.partial(update, False))
    pl.when(jnp.logical_and(live == 1, crossed))(
        functools.partial(update, True))

    @pl.when(jnp.logical_or(
        step == steps - 1,
        qi_ref[jnp.minimum(step + 1, steps - 1)] != qi))
    def _():
        o_ref[0, 0] = (acc[...] / total[:, :1]).astype(o_ref.dtype)


def _live_steps(bits, qi, kj, tq, blocks):
    """From the padded table ``(batch, kv_heads, queries, lanes)``: for
    every step, is a bit set in its tile, ``(batch, kv_heads, steps)``."""
    batch, groups, q_len, lanes = bits.shape
    tiles = (bits != 0).reshape(
        batch, groups, q_len // tq, tq, lanes // blocks, blocks).any((3, 5))
    return tiles[:, :, qi, kj]


def sparse_prompt_attention(q, k, v, bits, *, block_size, scale=None,
                            q_offset=0):
    """``softmax(q . k * scale) . v`` for each query over the keys at or
    before it whose block is set in ``bits``, and the share of key
    blocks the kernel's live steps ran over the blocks under the
    diagonal (float32 scalar; :func:`note_live_block_share` keeps it).

    ``q``: (batch, queries, heads, d), query ``t`` at position ``q_offset
    + t``; ``k``/``v``: (batch, keys, kv_heads, d) from position 0, at
    least ``q_offset + queries`` of them; ``bits``: (batch, kv_heads,
    queries, key blocks) bool or int8, nonzero where the heads of that
    key/value head attend the block at that query (the query's own block
    always). Products in the inputs' dtype accumulated in float32,
    float32 running maximum, sum and accumulator, probabilities cast to
    the values' dtype; returns (batch, queries, heads, d) in ``q``'s.
    """
    heads, groups, d = q.shape[2], k.shape[2], q.shape[3]
    if not takes_kernel(heads, groups, d, block_size):
        raise ValueError(
            f"sparse_prompt_attention: {heads} heads on {groups} key/value "
            f"heads of width {d} with blocks of {block_size} keys is "
            "outside the kernel's envelope (see takes_kernel)")
    if k.shape[1] < q_offset + q.shape[1]:
        raise ValueError(
            f"sparse_prompt_attention: {q.shape[1]} queries from position "
            f"{q_offset} against {k.shape[1]} keys")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _sparse_prompt_attention(
        q, k, v, bits, block_size=int(block_size), scale=float(scale),
        q_offset=int(q_offset), tiles=None, interpret=use_interpret())


# jitted so that a model's layers share one trace and one Mosaic
# lowering of the kernel
@functools.partial(jax.jit, static_argnames=(
    "block_size", "scale", "q_offset", "tiles", "interpret"))
def _sparse_prompt_attention(q, k, v, bits, *, block_size, scale, q_offset,
                             tiles, interpret):
    batch, q_len, heads, d = q.shape
    kv_len, groups = k.shape[1], k.shape[2]
    per = heads // groups
    tq, tk = tiles or tiles_of(q_len, kv_len, block_size)
    blocks = tk // block_size                  # key blocks a key tile
    n_q, n_k = -(-q_len // tq), -(-kv_len // tk)
    lanes = -(-n_k * blocks // LANES) * LANES
    # whole tiles, heads first; the table's lanes cover every key tile
    q = jnp.pad(q, ((0, 0), (0, n_q * tq - q_len), (0, 0), (0, 0)))
    k, v = (jnp.pad(t, ((0, 0), (0, n_k * tk - kv_len), (0, 0), (0, 0)))
            .transpose(0, 2, 1, 3) for t in (k, v))
    bits = jnp.pad(bits.astype(jnp.int8), (
        (0, 0), (0, 0), (0, n_q * tq - q_len), (0, lanes - bits.shape[-1])))
    qi, kj = schedule(n_q, n_k, tq, tk, q_offset)
    steps = len(qi)
    # the blocks of a step's key tile that begin at or before the query
    # tile's last position: what a live step runs, and all there is
    under = np.clip((q_offset + (qi + 1) * tq - 1) // block_size + 1
                    - kj * blocks, 0, blocks)
    live = _live_steps(bits, qi, kj, tq, blocks)
    share = (live * under).sum(axis=-1) / under.sum()
    # a dead step's blocks are those of the last live step before it (of
    # the first live step, before any): nothing new to fetch
    at = jnp.where(live, jnp.arange(steps, dtype=jnp.int32), -1)
    at = jax.lax.cummax(at, axis=2)
    at = jnp.where(at < 0, jnp.argmax(live, axis=-1)[..., None], at)
    packed = (live.astype(jnp.int32) << 16 | jnp.asarray(kj)[at]).reshape(-1)

    def fetched(b, h, s, live):       # the key tile a step's blocks hold
        return live[(b * groups + h // per) * steps + s] & 0xFFFF

    mine = pl.BlockSpec((1, 1, tq, d),
                        lambda b, h, s, qi, kj, live: (b, h, qi[s], 0))
    keys = pl.BlockSpec(
        (1, 1, tk, d),
        lambda b, h, s, qi, kj, live: (b, h // per, fetched(b, h, s, live),
                                       0))
    table = pl.BlockSpec(
        (1, 1, tq, LANES),
        lambda b, h, s, qi, kj, live: (
            b, h // per, qi[s], fetched(b, h, s, live) * blocks // LANES))
    spread = (np.arange(LANES)[:, None]
              == np.arange(tk)[None, :] // block_size).astype(np.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, tq=tq, tk=tk, blocks=blocks,
                          groups=groups, per=per, q_offset=q_offset),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(batch, heads, steps),
            in_specs=[mine, keys, keys, table,
                      pl.BlockSpec((LANES, tk), lambda *_: (0, 0))],
            out_specs=mine,
            scratch_shapes=[pltpu.VMEM((tq, d), jnp.float32),
                            pltpu.VMEM((tq, LANES), jnp.float32),
                            pltpu.VMEM((tq, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, heads, n_q * tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="sparse_prompt_attention",
    )(jnp.asarray(qi), jnp.asarray(kj), packed, q.transpose(0, 2, 1, 3), k,
      v, bits, jnp.asarray(spread, q.dtype))
    return out.transpose(0, 2, 1, 3)[:, :q_len], share.mean()
