"""Fused blockwise (flash) attention as a Pallas TPU kernel.

The hot op of the transformer model family. Attention that never puts the
``(seq, seq)`` score matrix in HBM, with a custom VJP of matching backward
kernels (dq; dk/dv), in two forms chosen from the shapes:

* **One side resident** (a key sequence no longer than ``block_k``; for
  dk/dv a query sequence no longer than ``bwd_block_q``: every training
  cell of the benchmark): the grid is (batch, head, block of the other
  side), the resident side's q/k/v (and, for dk/dv, do, lse and delta)
  stay in VMEM, the softmax is direct and nothing lives in scratch. When
  causal, the kernel walks its score matrix in square tiles and runs only
  those that hold an element at or under the diagonal, masking only the
  ones the diagonal crosses (``_key_tiles``, ``_query_tiles``). Read on
  the chip at GPT-2-small's (16, 12, 1024, 64) in bfloat16 (my chip
  runs, PR 42; device time of the kernel alone): forward 0.556 -> 0.489
  ms, dq 0.683 -> 0.552, dk/dv 1.243 -> 0.746 against the kernels that
  ran 0.75 / 0.75 / 1.0 of the square; time follows the area computed,
  because with head_dim 64 half filling the matrix unit's contraction
  the products themselves are most of it (PERF.md section 6, PR 42).
* **General** (longer sequences): the grid walks (batch, head, q-block,
  k-block) with the k-block axis innermost, one ``(block, head_dim)``
  tile of each of q/k/v in VMEM at a time while a running (max, sum,
  accumulator) triple lives in VMEM scratch; compute and VMEM stay
  O(block^2 + block x head_dim) a grid step whatever the sequence length.
  Causal calls skip the blocks in a q block's future.

Both forms take a ``block_len``: the causal mask over blocks of that many
positions and open inside one (query ``i`` sees key ``j`` where ``j //
block_len <= i // block_len``; a model that generates by diffusion over
blocks prefills under it). A tile is a whole number of blocks, so the
tiles that run and the ones the mask cuts are the causal ones
(``_key_tiles``, ``_query_tiles``) and only the mask inside the diagonal's
tiles differs: a row counts as the last row of its block
(``_row_less_col``, ``_block_end``). ``block_len`` 1 is the causal
kernels as they were, jaxpr for jaxpr.

This kernel is also the *local* building block of ring attention
(horovod_tpu/parallel/ring.py): it accepts dynamic ``q_offset``/``k_offset``
global position scalars and returns the per-row log-sum-exp, so partial
results computed against one shard of keys/values can be merged exactly
across ppermute steps (see ``merge_partials``). Python-zero offsets (the
models' call) build the causal schedule at trace time; traced ones choose
among bodies built for each number of live tiles (``_on_rungs``).

The reference framework has no attention kernels at all (it is a pure
data-parallel gradient-averaging layer — SURVEY.md §5.7); this module is part
of the TPU-first long-context extension, not a port.

On non-TPU backends (CPU tests) the kernels run in Pallas interpret mode;
set ``HOROVOD_PALLAS_INTERPRET=0/1`` to force either way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.ops.pallas._backend import use_interpret

NEG_INF = float("-inf")

# Per-row scalars (lse, delta) are stored as (B, H, S, LANES) with the value
# broadcast across lanes, satisfying the TPU (8, 128) tiling constraint.
LANES = 128

# Softmax runs in base 2 inside the kernels (exp2 is cheaper than exp on the
# VPU): scores are pre-scaled by log2(e), the log-sum-exp converts back on
# the way out.
LOG2E = float(np.log2(np.e))

# Sides of the square sub-tiles a causal kernel with one side resident walks
# its score matrix in, first fit (``_causal_plan``). Read on the chip at
# (16, 12, 1024, 64) (my chip runs, PR 42): 256 is the fastest or within 5%
# of it in all three kernels; 128 runs fewer elements (0.5625 of the square
# against 0.625) in smaller products and is slower in the forward and dk/dv.
_TILE_SIDES = (256, 128)

# A causal call whose offsets are Python zeros rides the whole of a side no
# longer than this in one grid step, so that every tile's place against the
# diagonal is known at trace time.
_WHOLE_ROWS = 1024

# With traced offsets a body is built for each number of tiles that can run
# (``_on_rungs``); past this many tiles a side the ladder's code outgrows what
# it saves (8 a side read 6.0 ms against 0.93 at 4: my chip runs, PR 42).
_MAX_RUNGS = 4

_LIVE_TILE_SHARE = {
    kind: _metrics().gauge(
        f"flash.live_tile_share.{kind}",
        f"Tiles of the score matrix the last traced flash_{kind} call runs "
        "over the tiles of the whole square (1.0 when not causal).")
    for kind in ("fwd", "dq", "dkv")}


def _vma(*arrays) -> frozenset:
    """Union of the inputs' varying-mesh-axes, so pallas_call outputs carry
    the right vma under ``shard_map(check_vma=True)``."""
    out = frozenset()
    for a in arrays:
        out |= jax.typeof(a).vma
    return out


def _pick_block(seq: int, requested: int) -> int:
    """Largest block ≤ requested that divides seq (power-of-two friendly)."""
    b = min(requested, seq)
    while seq % b:
        b -= 1
    return b


def _compiler_params(grid_len: int):
    # All grid axes are embarrassingly parallel except the innermost, which
    # carries the online-softmax accumulator in scratch.
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


# ---------------------------------------------------------------------------
# The causal schedule: which tiles of the score matrix hold a live element
# ---------------------------------------------------------------------------
#
# With one side of the score matrix resident in VMEM, a causal kernel walks
# it in (tile_q, tile_k) sub-tiles and runs only those that hold an element
# at or under the diagonal; only the tiles the diagonal crosses are masked.
# ``_key_tiles`` and ``_query_tiles`` are that schedule. They take Python
# ints (the counter ``live_tile_share``, and a call whose offsets are Python
# zeros: the kernel is built for the one schedule) or traced int32 scalars
# (the ring's offsets: ``_on_rungs`` builds a body for each number of tiles and
# the kernel chooses among them), so the counter cannot drift from the
# kernels.


def _floor_tiles(x, tile):
    """``floor(x / tile)`` where ``x >= 0`` and 0 below."""
    if isinstance(x, int):
        return max(x, 0) // tile
    return jax.lax.div(jnp.maximum(x, 0), jnp.int32(tile))


def _at_most(x, n):
    return min(x, n) if isinstance(x, int) else jnp.minimum(x, n)


def _key_tiles(gap, tile_q, tile_k, n_k):
    """For a tile of query rows whose first row lies ``gap`` positions
    after the first key: ``(plain, live)``. Key tiles ``[0, plain)`` lie
    wholly at or under the diagonal and need no mask, ``[plain, live)``
    are crossed by it, and the rest hold no live element."""
    plain = _at_most(_floor_tiles(gap + 1, tile_k), n_k)
    live = _at_most(_floor_tiles(gap + tile_q - 1 + tile_k, tile_k), n_k)
    return plain, live


def _query_tiles(gap, tile_q, tile_k, n_q):
    """For a tile of keys whose first key lies ``gap`` positions after
    the first query row: ``(first, plain)``. Query tiles ``[first,
    plain)`` are crossed by the diagonal, ``[plain, n_q)`` lie wholly at
    or under it, and those before ``first`` hold no live element."""
    first = _at_most(_floor_tiles(gap, tile_q), n_q)
    plain = _at_most(
        _floor_tiles(gap + tile_k - 1 + tile_q - 1, tile_q), n_q)
    return first, plain


def live_tile_share(kind, q_seq, kv_seq, causal, tile) -> float:
    """Tiles of the score matrix a kernel runs over the tiles of the
    whole square, at offsets 0. ``kind`` is ``"fwd"``, ``"dq"`` or
    ``"dkv"``; ``tile`` a side or ``(tile_q, tile_k)``. The kernels take
    their loop bounds from the same two functions."""
    if not causal:
        return 1.0
    tile_q, tile_k = (tile, tile) if isinstance(tile, int) else tile
    n_q, n_k = q_seq // tile_q, kv_seq // tile_k
    if kind == "dkv":
        run = sum(n_q - _query_tiles(c * tile_k, tile_q, tile_k, n_q)[0]
                  for c in range(n_k))
    else:
        run = sum(_key_tiles(r * tile_q, tile_q, tile_k, n_k)[1]
                  for r in range(n_q))
    return run / (n_q * n_k)


def _on_rungs(lo, hi, n, tile, ride, body):
    """``body(lo, hi)`` with static ints, under the condition that they
    are the ones to run. ``lo``/``hi`` are ``_key_tiles``' pair (``ride ==
    "q"``: tiles ``[0, lo)`` run plain and ``[lo, hi)`` masked) or
    ``_query_tiles``' pair (``ride == "k"``: ``[lo, hi)`` masked, ``[hi,
    n)`` plain); ``tile`` is ``(riding, resident)``. Python ints are the
    one pair, which holds for the riding side's one block. Traced ones
    give a ladder of rungs, one for each number of tiles that run: a rung
    masks the ``reach`` tiles at the diagonal's end of its range (no more can be crossed
    inside one riding tile, and the mask leaves a tile that is not crossed
    as it is), and one more rung runs the whole range plain where every
    key lies in every row's past (most of a ring's steps)."""
    if isinstance(lo, int) and isinstance(hi, int):
        # always true on the one-block grid. It also keeps the body
        # inside a ``cond`` like every rung's: under ``shard_map`` the CPU
        # interpreter (jax 0.9.0) refuses a kernel's top-level loads,
        # whose constant indices do not vary as the blocks do
        rungs = [(pl.program_id(2) == 0, lo, hi)]
    else:
        reach = -(-tile[0] // tile[1]) + 1
        if ride == "q":
            rungs = [(jnp.logical_and(hi == e, lo < n), max(e - reach, 0), e)
                     for e in range(n + 1)] + [(lo >= n, n, n)]
        else:
            rungs = [(jnp.logical_and(lo == e, hi > 0), e, min(e + reach, n))
                     for e in range(n + 1)] + [(hi <= 0, 0, 0)]
    for when, lo, hi in rungs:
        pl.when(when)(functools.partial(body, lo, hi))


def _key_pieces(plain, live, tile_k, gap):
    """``[(columns, mask_from)]`` for a tile of query rows (``_key_tiles``'
    static pair): the plain key tiles as one piece with no mask, each
    crossed one with the ``_row_less_col`` value its live elements start
    from."""
    pieces = [(pl.ds(0, plain * tile_k), None)] if plain else []
    return pieces + [(pl.ds(j * tile_k, tile_k), j * tile_k - gap)
                     for j in range(plain, live)]


def _block_end(ids, block_len):
    """The last position of the block of ``block_len`` positions that
    holds each of ``ids`` (``ids`` itself at a block length of 1): a
    block-causal query sees every key up to there."""
    if block_len == 1:
        return ids
    return ids + (block_len - 1 - jax.lax.rem(ids, jnp.int32(block_len)))


def _row_less_col(tile_q, tile_k, block_len=1):
    """Row index less column index over a tile. A tile whose first key
    lies ``g`` positions after its first query row keeps the elements
    where this is ``>= g``. With ``block_len`` > 1 a row counts as the
    last row of its block (the tile's first row starts a block): the mask
    is then causal over blocks and open inside one."""
    return (_block_end(jax.lax.broadcasted_iota(
        jnp.int32, (tile_q, tile_k), 0), block_len)
            - jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1))


def _first_positions(q_off_ref, k_off_ref, ride, block, offsets_zero):
    """Global positions of this grid step's first query row and first
    key. ``ride`` names the side the grid's last axis walks in blocks of
    ``block`` rows; the other side is resident. Python zeros where the
    caller's offsets were and the grid has one block."""
    if offsets_zero:
        return 0, 0
    step = pl.program_id(2) * block
    q_first, k_first = q_off_ref[0], k_off_ref[0]
    return (q_first + step, k_first) if ride == "q" else (
        q_first, k_first + step)


def _fwd_causal_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, *, sm_scale, block_q, tile, offsets_zero,
                       block_len=1):
    """Causal forward with the whole key sequence resident. Each
    ``tile_q`` rows of the q block take a direct softmax over the keys
    their rows reach: the tiles wholly under the diagonal as one product,
    those it crosses tile by tile under the mask. Rows no key reaches (the
    ring's future shard) get zeros and ``lse = -inf``."""
    tile_q, tile_k = tile
    n_k = k_ref.shape[2] // tile_k
    q_first, k_first = _first_positions(
        q_off_ref, k_off_ref, "q", block_q, offsets_zero)
    row_less_col = _row_less_col(tile_q, tile_k, block_len)
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    for r in range(block_q // tile_q):
        rows = pl.ds(r * tile_q, tile_q)
        gap = q_first + r * tile_q - k_first

        def run(plain, live, rows=rows, gap=gap):
            if not live:
                o_ref[0, 0, rows, :] = jnp.zeros((tile_q, o_ref.shape[3]),
                                                 o_ref.dtype)
                lse_ref[0, 0, rows, :] = jnp.full((tile_q, LANES), NEG_INF,
                                                  jnp.float32)
                return
            q = q_ref[0, 0, rows, :].astype(jnp.float32) * (
                sm_scale * LOG2E)
            pieces = _key_pieces(plain, live, tile_k, gap)
            scores = []
            for cols, mask_from in pieces:
                s = dot(q, k_ref[0, 0, cols, :].astype(jnp.float32),
                        (((1,), (1,)), ((), ())))
                if mask_from is not None:
                    s = jnp.where(row_less_col >= mask_from, s, NEG_INF)
                scores.append(s)
            m = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=-1, keepdims=True) for s in scores])
            # rows no key reaches: m = -inf; shift by 0 so p is 0, not NaN
            m_safe = jnp.where(m == NEG_INF, 0.0, m)
            l = acc = 0.0
            for s, (cols, _) in zip(scores, pieces):
                p = jnp.exp2(s - m_safe)
                l = l + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc + dot(p, v_ref[0, 0, cols, :].astype(jnp.float32),
                                (((1,), (0,)), ((), ())))
            empty = l == 0.0
            l_safe = jnp.where(empty, 1.0, l)
            o_ref[0, 0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
            lse = jnp.where(empty, NEG_INF,
                            m_safe * (1.0 / LOG2E) + jnp.log(l_safe))
            lse_ref[0, 0, rows, :] = jnp.broadcast_to(lse, (tile_q, LANES))

        plain, live = _key_tiles(gap, tile_q, tile_k, n_k)
        _on_rungs(plain, live, n_k, (tile_q, tile_k), "q", run)


def _bwd_tile(q, k, v, do, lse_safe, delta, mask_from, row_less_col,
              sm_scale):
    """``(p, ds)`` of one piece of the score matrix, recomputed from the
    forward's row statistics. ``mask_from`` is ``None`` for a piece wholly
    at or under the diagonal."""
    s = (sm_scale * LOG2E) * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if mask_from is not None:
        s = jnp.where(row_less_col >= mask_from, s, NEG_INF)
    p = jnp.exp2(s - lse_safe)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _row_stats(lse_ref, delta_ref, rows):
    """The rows' ``(lse, delta)`` as ``(rows, 1)`` columns, the first in
    base 2. Rows that saw no key have ``lse = -inf`` and every ``s =
    -inf``: shifting by 0 keeps ``exp2(s - lse)`` at 0 and not NaN."""
    lse = lse_ref[0, 0, rows, :][:, :1]
    delta = delta_ref[0, 0, rows, :][:, :1]
    return jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E, delta


def _bwd_dq_causal_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dq_ref, *, sm_scale, block_q,
                          tile, offsets_zero, block_len=1):
    """Causal dq with the whole key sequence resident: each ``tile_q``
    rows sum in float32 over the keys their rows reach (the tiles wholly
    under the diagonal as one piece, those it crosses tile by tile under
    the mask) and write their block once."""
    tile_q, tile_k = tile
    n_k = k_ref.shape[2] // tile_k
    q_first, k_first = _first_positions(
        q_off_ref, k_off_ref, "q", block_q, offsets_zero)
    row_less_col = _row_less_col(tile_q, tile_k, block_len)

    for r in range(block_q // tile_q):
        rows = pl.ds(r * tile_q, tile_q)
        gap = q_first + r * tile_q - k_first

        def run(plain, live, rows=rows, gap=gap):
            q = q_ref[0, 0, rows, :].astype(jnp.float32)
            do = do_ref[0, 0, rows, :].astype(jnp.float32)
            lse_safe, delta = _row_stats(lse_ref, delta_ref, rows)
            dq = jnp.zeros(q.shape, jnp.float32)
            for cols, mask_from in _key_pieces(plain, live, tile_k, gap):
                k = k_ref[0, 0, cols, :].astype(jnp.float32)
                v = v_ref[0, 0, cols, :].astype(jnp.float32)
                _, ds = _bwd_tile(q, k, v, do, lse_safe, delta, mask_from,
                                  row_less_col, sm_scale)
                dq = dq + jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)

        plain, live = _key_tiles(gap, tile_q, tile_k, n_k)
        _on_rungs(plain, live, n_k, (tile_q, tile_k), "q", run)


def _bwd_dkv_causal_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref,
                           lse_ref, delta_ref, dk_ref, dv_ref, *, sm_scale,
                           block_k, tile, offsets_zero, block_len=1):
    """Causal dk/dv with q, do, lse and delta resident: each ``tile_k``
    keys of the k block sum in float32 over the query rows from the
    diagonal down (the tiles it crosses tile by tile under the mask, the
    rows below them as one piece) and write their blocks once; keys in
    every row's future get zeros."""
    tile_q, tile_k = tile
    n_q = q_ref.shape[2] // tile_q
    q_first, k_first = _first_positions(
        q_off_ref, k_off_ref, "k", block_k, offsets_zero)
    row_less_col = _row_less_col(tile_q, tile_k, block_len)

    for c in range(block_k // tile_k):
        cols = pl.ds(c * tile_k, tile_k)
        gap = k_first + c * tile_k - q_first

        def run(first, plain, cols=cols, gap=gap):
            k = k_ref[0, 0, cols, :].astype(jnp.float32)
            v = v_ref[0, 0, cols, :].astype(jnp.float32)
            dk = dv = jnp.zeros(k.shape, jnp.float32)
            pieces = [(pl.ds(i * tile_q, tile_q), gap - i * tile_q)
                      for i in range(first, plain)]
            if plain < n_q:
                pieces.append(
                    (pl.ds(plain * tile_q, (n_q - plain) * tile_q), None))
            for rows, mask_from in pieces:
                q = q_ref[0, 0, rows, :].astype(jnp.float32)
                do = do_ref[0, 0, rows, :].astype(jnp.float32)
                lse_safe, delta = _row_stats(lse_ref, delta_ref, rows)
                p, ds = _bwd_tile(q, k, v, do, lse_safe, delta, mask_from,
                                  row_less_col, sm_scale)
                dv = dv + jax.lax.dot_general(
                    p, do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk = dk + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dk_ref[0, 0, cols, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, 0, cols, :] = dv.astype(dv_ref.dtype)

        first, plain = _query_tiles(gap, tile_q, tile_k, n_q)
        _on_rungs(first, plain, n_q, (tile_k, tile_q), "k", run)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, block_q, block_k,
                block_len=1):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = q_off_ref[0] + qi * block_q
    k_start = k_off_ref[0] + kj * block_k
    last_q = q_start + block_q - 1

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        # Scores and the running max are tracked in base 2 (pre-scaled by
        # LOG2E) so the inner loop uses exp2, which is cheaper on the VPU.
        q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_block_end(q_ids, block_len) >= k_ids, s,
                          NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # Rows with every key masked so far have m_new == -inf; subtracting
        # -inf would give NaN, so shift by a safe 0 instead — every exp()
        # argument is then -inf and the row correctly accumulates nothing.
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp2(m_prev - m_safe)
        p = jnp.exp2(s - m_safe[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jax.lax.broadcast_in_dim(m_new, m_ref.shape, (0,))
        l_ref[...] = jax.lax.broadcast_in_dim(l_new, l_ref.shape, (0,))

    if causal:
        # Skip k blocks entirely in this q block's future; mask only blocks
        # straddling the diagonal — interior blocks skip the iota/where.
        # Offsets are dynamic scalars, so this is predicated rather than
        # pruned from the (static) grid.
        interior = k_start + block_k - 1 <= q_start
        pl.when(interior)(lambda: update(False))
        pl.when(jnp.logical_and(k_start <= last_q,
                                jnp.logical_not(interior)))(
            lambda: update(True))
    else:
        update(False)

    @pl.when(kj == nk - 1)
    def _finalize():
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        # Fully-masked rows (l == 0): output 0, lse -inf so a later merge
        # treats this partial as absent.
        empty = l == 0.0
        l_safe = jnp.where(empty, 1.0, l)
        m_fin = jnp.where(empty, 0.0, m)
        o_ref[0, 0, :, :] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse = jnp.where(empty, NEG_INF,
                        m_fin * (1.0 / LOG2E) + jnp.log(l_safe))
        # Row vectors are stored broadcast across LANES lanes to satisfy TPU
        # tiling (same layout as the stock TPU flash kernel's l/m buffers).
        lse_ref[0, 0, :, :] = jax.lax.broadcast_in_dim(
            lse, (block_q, LANES), (0,))


def _fwd_single_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, *, sm_scale, block_q):
    """Non-causal forward with the whole key sequence resident: the
    softmax is direct, with no m/l/acc scratch and no online rescaling.
    This is BERT-Large's S=512 body (both ``bertl-train`` cells): the
    three non-causal bodies together read 33.8% of their roofline there
    (ledger, PR 41; my chip runs, PR 42)."""
    q = q_ref[0, 0, :, :].astype(jnp.float32) * (sm_scale * LOG2E)
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m = jnp.max(s, axis=-1)
    # fully-masked rows: m = -inf; shift by 0 so p is 0, not NaN
    m_safe = jnp.where(m == NEG_INF, 0.0, m)
    p = jnp.exp2(s - m_safe[:, None])
    l = jnp.sum(p, axis=-1)
    empty = l == 0.0
    l_safe = jnp.where(empty, 1.0, l)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0, :, :] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(empty, NEG_INF,
                    m_safe * (1.0 / LOG2E) + jnp.log(l_safe))
    lse_ref[0, 0, :, :] = jax.lax.broadcast_in_dim(
        lse, (block_q, LANES), (0,))


def _single_specs(block_q, block_k, dim, ride):
    """BlockSpecs for the single-block (b, h, i) grids: ``ride`` names
    the operand the grid axis walks ("q" or "k"); the opposite side is
    pinned to block 0 (its whole extent is resident). Returns
    (q_spec, k_spec, q_row_spec) — the row spec follows the q side
    (lse/delta are per-q-row, lane-broadcast)."""
    walk = lambda b, h, i: (b, h, i, 0)
    pin = lambda b, h, i: (b, h, 0, 0)
    q_ix, k_ix = (walk, pin) if ride == "q" else (pin, walk)
    return (pl.BlockSpec((1, 1, block_q, dim), q_ix),
            pl.BlockSpec((1, 1, block_k, dim), k_ix),
            pl.BlockSpec((1, 1, block_q, LANES), q_ix))


def _make_specs(block_q, block_k, dim):
    """BlockSpecs for a (b, h, q-block, k-block) grid: q-side tiles index by
    the q-block id, k-side tiles by the k-block id — one block of each input
    is in VMEM per grid step regardless of sequence length."""
    q_spec = pl.BlockSpec((1, 1, block_q, dim), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, dim), lambda b, h, i, j: (b, h, j, 0))
    qrow_spec = pl.BlockSpec((1, 1, block_q, LANES),
                             lambda b, h, i, j: (b, h, i, 0))
    return q_spec, k_spec, qrow_spec


# The scalar offsets ride as int32 arrays of shape (1,); gridded kernels see
# the whole array in scalar memory, indexed as ref[0].
_OFF_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _causal_plan(ride, ride_seq, ride_block, resident, offsets_zero):
    """How a causal kernel with ``resident`` rows of one side in VMEM walks
    the other (``ride``: ``"q"`` or ``"k"``, ``ride_seq`` long): ``(block,
    (tile_q, tile_k), static)``. ``static``: the offsets are Python zeros
    and the riding side is one block, so the kernel is built for the one
    schedule; else its rungs choose by the offsets and the block's id.
    The tile is the first of ``_TILE_SIDES`` that divides both sides (and
    keeps a ladder within ``_MAX_RUNGS``), else the whole of each."""
    static = offsets_zero and ride_seq <= _WHOLE_ROWS
    block = ride_seq if static else ride_block
    tile = block, resident
    for side in _TILE_SIDES:
        if block % side == 0 and resident % side == 0 and (
                static or max(block, resident) // side <= _MAX_RUNGS):
            tile = side, side
            break
    return block, tile if ride == "q" else tile[::-1], static


def _record_share(kind, q_seq, kv_seq, causal, tile):
    """At trace time: the share of the square this call's kernel runs."""
    _LIVE_TILE_SHARE[kind].set(
        live_tile_share(kind, q_seq, kv_seq, causal, tile))


def _check_block_len(block_len, causal, offsets_zero, *sides):
    """A block length over 1 is a block-causal mask (``flash_attention``):
    it needs a causal call from position 0 (the kernels take a tile's
    first row for a block's first) whose blocks and tiles are whole
    numbers of such blocks."""
    if block_len == 1:
        return
    if not causal or not offsets_zero:
        raise ValueError("block_len > 1 needs causal=True and offsets "
                         "that are the Python number 0")
    if any(side % block_len for side in sides):
        raise ValueError(f"block_len {block_len} does not divide the "
                         f"sequence blocks {sides}")


def _flash_fwd(q, k, v, q_offset, k_offset, *, sm_scale, causal,
               block_q, block_k, interpret, offsets_zero=False, block_len=1):
    batch, heads, q_seq, dim = q.shape
    kv_seq = k.shape[2]
    block_q = _pick_block(q_seq, block_q)
    block_k = _pick_block(kv_seq, block_k)
    _check_block_len(block_len, causal, offsets_zero, block_q, block_k)
    vma = _vma(q, k, v, q_offset, k_offset)
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
        # lse lane-broadcast: (B, H, S, LANES)
        jax.ShapeDtypeStruct((batch, heads, q_seq, LANES), jnp.float32,
                             vma=vma),
    ]

    if kv_seq == block_k:
        # whole key sequence resident: one grid step a (batch, head, q
        # block), no scratch
        tile = None
        if causal:
            block_q, tile, static = _causal_plan(
                "q", q_seq, block_q, kv_seq, offsets_zero)
            _check_block_len(block_len, causal, offsets_zero, *tile)
            kernel = functools.partial(
                _fwd_causal_kernel, sm_scale=sm_scale, block_q=block_q,
                tile=tile, offsets_zero=static, block_len=block_len)
        else:
            kernel = functools.partial(
                _fwd_single_kernel, sm_scale=sm_scale, block_q=block_q)
        _record_share("fwd", q_seq, kv_seq, causal, tile)
        sq_spec, sk_spec, srow_spec = _single_specs(
            block_q, block_k, dim, ride="q")
        return pl.pallas_call(
            kernel,
            grid=(batch, heads, q_seq // block_q),
            in_specs=[_OFF_SPEC, _OFF_SPEC, sq_spec, sk_spec, sk_spec],
            out_specs=[sq_spec, srow_spec],
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=interpret,
            name="flash_fwd",
        )(q_offset, k_offset, q, k, v)

    _record_share("fwd", q_seq, kv_seq, causal, (block_q, block_k))
    grid = (batch, heads, q_seq // block_q, kv_seq // block_k)
    q_spec, k_spec, qrow_spec = _make_specs(block_q, block_k, dim)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, block_len=block_len)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_OFF_SPEC, _OFF_SPEC, q_spec, k_spec, k_spec],
        out_specs=[q_spec, qrow_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dim), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
        name="flash_fwd",
    )(q_offset, k_offset, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc_ref,
                   *, sm_scale, causal, block_q, block_k, block_len=1):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = q_off_ref[0] + qi * block_q
    k_start = k_off_ref[0] + kj * block_k
    last_q = q_start + block_q - 1

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def update(masked):
        cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
        q = cast(q_ref)
        do = cast(do_ref)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        # Fully-masked rows have lse = -inf and all s = -inf; shifting by 0
        # instead of -inf keeps exp(s - lse) at 0 rather than NaN.
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
        k = cast(k_ref)
        v = cast(v_ref)
        s = (sm_scale * LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_block_end(q_ids, block_len) >= k_ids, s,
                          NEG_INF)
        p = jnp.exp2(s - lse_safe[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        interior = k_start + block_k - 1 <= q_start
        pl.when(interior)(lambda: update(False))
        pl.when(jnp.logical_and(k_start <= last_q,
                                jnp.logical_not(interior)))(
            lambda: update(True))
    else:
        update(False)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc_ref,
                    dv_acc_ref, *, sm_scale, causal, block_q, block_k,
                    block_len=1):
    ki = pl.program_id(2)
    qj = pl.program_id(3)
    nq = pl.num_programs(3)

    k_start = k_off_ref[0] + ki * block_k
    q_start = q_off_ref[0] + qj * block_q
    last_q = q_start + block_q - 1

    @pl.when(qj == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def update(masked):
        cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
        k = cast(k_ref)
        v = cast(v_ref)
        q = cast(q_ref)
        do = cast(do_ref)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
        s = (sm_scale * LOG2E) * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_block_end(q_ids, block_len) >= k_ids, s,
                          NEG_INF)
        p = jnp.exp2(s - lse_safe[:, None])
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # q blocks entirely before this k block contribute nothing; blocks
        # entirely past the diagonal need no mask.
        interior = k_start + block_k - 1 <= q_start
        pl.when(interior)(lambda: update(False))
        pl.when(jnp.logical_and(last_q >= k_start,
                                jnp.logical_not(interior)))(
            lambda: update(True))
    else:
        update(False)

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_dq_single_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
                          do_ref, lse_ref, delta_ref, dq_ref, *, sm_scale):
    """Non-causal dq with the whole key sequence resident: no
    accumulator scratch, one pass (BERT-Large's body; see
    ``_fwd_single_kernel``)."""
    cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
    q = cast(q_ref)
    do = cast(do_ref)
    k = cast(k_ref)
    v = cast(v_ref)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
    s = (sm_scale * LOG2E) * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp2(s - lse_safe[:, None])
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * sm_scale
    dq_ref[0, 0, :, :] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _bwd_dkv_single_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
                           do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                           *, sm_scale):
    """Non-causal dk/dv with the whole query sequence resident:
    scratch-free like ``_bwd_dq_single_kernel``."""
    cast = lambda r: r[0, 0, :, :].astype(jnp.float32)
    q = cast(q_ref)
    k = cast(k_ref)
    v = cast(v_ref)
    do = cast(do_ref)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    lse_safe = jnp.where(lse == NEG_INF, 0.0, lse) * LOG2E
    s = (sm_scale * LOG2E) * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp2(s - lse_safe[:, None])
    dv_ref[0, 0, :, :] = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * sm_scale
    dk_ref[0, 0, :, :] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def compute_delta(o, do) -> jax.Array:
    """The backward's per-row correction term, lane-broadcast: delta_i =
    sum_d do[i,d]·o[i,d], shape (B, H, S, LANES). Depends only on the final
    output/cotangent, so callers running many partial backwards against the
    same (o, do) — e.g. the ring sweep — compute it once and pass it in."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))


def _flash_bwd(q, k, v, o, lse, do, q_offset, k_offset, *, sm_scale, causal,
               block_q, block_k, interpret, delta=None, offsets_zero=False,
               block_len=1):
    batch, heads, q_seq, dim = q.shape
    kv_seq = k.shape[2]
    block_q = _pick_block(q_seq, block_q)
    block_k = _pick_block(kv_seq, block_k)
    _check_block_len(block_len, causal, offsets_zero, block_q, block_k)

    if delta is None:
        delta = compute_delta(o, do)

    q_spec, k_spec, qrow_spec = _make_specs(block_q, block_k, dim)

    vma = _vma(q, k, v, do, q_offset, k_offset)
    single = pltpu.CompilerParams(dimension_semantics=("parallel",) * 3)

    if kv_seq == block_k:
        # whole key sequence resident: scratch-free dq, any number of q
        # blocks
        ride, dq_tile = block_q, None
        if causal:
            ride, dq_tile, static = _causal_plan(
                "q", q_seq, block_q, kv_seq, offsets_zero)
            _check_block_len(block_len, causal, offsets_zero, *dq_tile)
            kernel = functools.partial(
                _bwd_dq_causal_kernel, sm_scale=sm_scale, block_q=ride,
                tile=dq_tile, offsets_zero=static, block_len=block_len)
        else:
            kernel = functools.partial(_bwd_dq_single_kernel,
                                       sm_scale=sm_scale)
        _record_share("dq", q_seq, kv_seq, causal, dq_tile)
        sq_spec, sk_spec, srow_spec = _single_specs(
            ride, block_k, dim, ride="q")
        dq = pl.pallas_call(
            kernel,
            grid=(batch, heads, q_seq // ride),
            in_specs=[_OFF_SPEC, _OFF_SPEC, sq_spec, sk_spec, sk_spec,
                      sq_spec, srow_spec, srow_spec],
            out_specs=sq_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            compiler_params=single,
            interpret=interpret,
            name="flash_dq",
        )(q_offset, k_offset, q, k, v, do, lse, delta)
    else:
        _record_share("dq", q_seq, kv_seq, causal, (block_q, block_k))
        dq = None

    if q_seq == block_q:
        # whole query sequence (q, do, lse, delta) resident: scratch-free
        # dk/dv, any number of k blocks
        ride, dkv_tile = block_k, None
        if causal:
            ride, dkv_tile, static = _causal_plan(
                "k", kv_seq, block_k, q_seq, offsets_zero)
            _check_block_len(block_len, causal, offsets_zero, *dkv_tile)
            kernel = functools.partial(
                _bwd_dkv_causal_kernel, sm_scale=sm_scale, block_k=ride,
                tile=dkv_tile, offsets_zero=static, block_len=block_len)
        else:
            kernel = functools.partial(_bwd_dkv_single_kernel,
                                       sm_scale=sm_scale)
        _record_share("dkv", q_seq, kv_seq, causal, dkv_tile)
        gq_spec, gk_spec, grow_spec = _single_specs(
            block_q, ride, dim, ride="k")
        dk, dv = pl.pallas_call(
            kernel,
            grid=(batch, heads, kv_seq // ride),
            in_specs=[_OFF_SPEC, _OFF_SPEC, gq_spec, gk_spec, gk_spec,
                      gq_spec, grow_spec, grow_spec],
            out_specs=[gk_spec, gk_spec],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
            ],
            compiler_params=single,
            interpret=interpret,
            name="flash_dkv",
        )(q_offset, k_offset, q, k, v, do, lse, delta)
    else:
        _record_share("dkv", q_seq, kv_seq, causal, (block_q, block_k))
        dk = dv = None

    if dq is None:
        # multi-k-block: the general accumulating dq kernel
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k, block_len=block_len),
            grid=(batch, heads, q_seq // block_q, kv_seq // block_k),
            in_specs=[_OFF_SPEC, _OFF_SPEC, q_spec, k_spec, k_spec,
                      q_spec, qrow_spec, qrow_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
            compiler_params=_compiler_params(4),
            interpret=interpret,
            name="flash_dq",
        )(q_offset, k_offset, q, k, v, do, lse, delta)

    if dk is None:
        # multi-q-block: general dk/dv — grid over (b, h, k-block,
        # q-block), q-side tiles streaming along the innermost axis
        # while dk/dv accumulate in scratch.
        kq_k_spec = pl.BlockSpec((1, 1, block_k, dim),
                                 lambda b, h, i, j: (b, h, i, 0))
        kq_q_spec = pl.BlockSpec((1, 1, block_q, dim),
                                 lambda b, h, i, j: (b, h, j, 0))
        kq_qrow_spec = pl.BlockSpec((1, 1, block_q, LANES),
                                    lambda b, h, i, j: (b, h, j, 0))

        dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k, block_len=block_len),
            grid=(batch, heads, kv_seq // block_k, q_seq // block_q),
            in_specs=[_OFF_SPEC, _OFF_SPEC, kq_q_spec, kq_k_spec,
                      kq_k_spec, kq_q_spec, kq_qrow_spec, kq_qrow_spec],
            out_specs=[kq_k_spec, kq_k_spec],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, dim), jnp.float32),
                pltpu.VMEM((block_k, dim), jnp.float32),
            ],
            compiler_params=_compiler_params(4),
            interpret=interpret,
            name="flash_dkv",
        )(q_offset, k_offset, q, k, v, do, lse, delta)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API: differentiable flash attention (+ residuals for ring merging)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, q_offset, k_offset, sm_scale, causal, block_q, block_k,
           bwd_block_q, bwd_block_k, offsets_zero, block_len):
    o, _ = _flash_fwd(q, k, v, q_offset, k_offset, sm_scale=sm_scale,
                      causal=causal, block_q=block_q, block_k=block_k,
                      interpret=use_interpret(), offsets_zero=offsets_zero,
                      block_len=block_len)
    return o


def _flash_vjp_fwd(q, k, v, q_offset, k_offset, sm_scale, causal,
                   block_q, block_k, bwd_block_q, bwd_block_k, offsets_zero,
                   block_len):
    o, lse = _flash_fwd(q, k, v, q_offset, k_offset, sm_scale=sm_scale,
                        causal=causal, block_q=block_q, block_k=block_k,
                        interpret=use_interpret(), offsets_zero=offsets_zero,
                        block_len=block_len)
    return o, (q, k, v, o, lse, q_offset, k_offset)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, bwd_block_q,
                   bwd_block_k, offsets_zero, block_len, res, do):
    q, k, v, o, lse, q_offset, k_offset = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, q_offset, k_offset,
                            sm_scale=sm_scale, causal=causal,
                            block_q=bwd_block_q, block_k=bwd_block_k,
                            interpret=use_interpret(),
                            offsets_zero=offsets_zero, block_len=block_len)
    zero = jnp.zeros((1,), jnp.int32)
    return dq, dk, dv, zero, zero


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _as_offset(x) -> jax.Array:
    return jnp.asarray(x, jnp.int32).reshape((1,))


def _python_zeros(*offsets) -> bool:
    """Whether every offset is the Python number 0 at trace time (the
    in-model call), as against a traced or nonzero position (the ring)."""
    return all(isinstance(x, (int, np.integer)) and x == 0 for x in offsets)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: int = 512,
    block_k: int = 1024,
    bwd_block_q: int = 1024,
    bwd_block_k: int = 1024,
    block_len: int = 1,
) -> jax.Array:
    """Fused attention over ``(batch, heads, seq, head_dim)`` inputs.

    ``block_len`` > 1 (with ``causal``, from position 0) makes the mask
    block-causal: query ``i`` sees key ``j`` where ``j // block_len <= i //
    block_len``, every key of its own block and of the blocks before it.
    The tiles that run are the causal ones (a tile is a whole number of
    blocks, so the diagonal's tiles are the only ones the mask cuts);
    ``block_len`` 1 is the causal mask and the same kernels as without it.

    ``q_offset``/``k_offset`` are the global sequence positions of the first
    query/key row — used by ring attention, where each device holds one
    sequence shard and the causal mask depends on global, not local, indices.
    They may be traced scalars (e.g. derived from ``lax.axis_index``).

    A key sequence no longer than ``block_k`` (a query sequence no longer
    than ``bwd_block_q`` for dk/dv) stays resident and the kernel takes one
    grid step a (batch, head, block); longer ones take the general
    online-softmax kernels block by block. Sequences shorter than a block
    fall back to the largest divisor automatically.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("flash_attention expects (batch, heads, seq, dim)")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    return _flash(q, k, v, _as_offset(q_offset), _as_offset(k_offset),
                  float(sm_scale), bool(causal), int(block_q), int(block_k),
                  int(bwd_block_q), int(bwd_block_k),
                  _python_zeros(q_offset, k_offset), int(block_len))


def flash_attention_partial(
    q, k, v, *, causal=False, sm_scale=None, q_offset=0, k_offset=0,
    block_q: int = 512, block_k: int = 1024,
):
    """Forward-only partial attention returning ``(out, lse)``.

    ``out`` is normalised over the *local* keys only; ``lse`` is the per-row
    log-sum-exp normaliser, so partials over disjoint key shards can be
    combined exactly with :func:`merge_partials`. Used by the ring-attention
    forward (the ring backward re-derives gradients through its own loop).
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    o, lse = _flash_fwd(q, k, v, _as_offset(q_offset), _as_offset(k_offset),
                        sm_scale=float(sm_scale), causal=bool(causal),
                        block_q=int(block_q), block_k=int(block_k),
                        interpret=use_interpret(),
                        offsets_zero=_python_zeros(q_offset, k_offset))
    return o, lse[..., 0]


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Exactly combine two attention partials over disjoint key sets.

    Each partial is (normalised output, log-sum-exp). Rows absent from one
    side carry ``lse = -inf`` and contribute nothing.
    """
    lse = jnp.logaddexp(lse_a, lse_b)
    # exp(-inf - -inf) would be NaN; an absent row has weight exactly 0.
    w_a = jnp.where(lse_a == NEG_INF, 0.0, jnp.exp(lse_a - lse))
    w_b = jnp.where(lse_b == NEG_INF, 0.0, jnp.exp(lse_b - lse))
    o = (o_a.astype(jnp.float32) * w_a[..., None]
         + o_b.astype(jnp.float32) * w_b[..., None])
    return o.astype(o_a.dtype), lse


def attention_reference(q, k, v, *, causal=False, sm_scale=None,
                        q_offset=0, k_offset=0, block_len=1):
    """Naive O(seq²) attention — ground truth for kernel tests
    (``block_len`` > 1: the block-causal mask of ``flash_attention``)."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        q_ids = q_offset + jnp.arange(q.shape[2])[:, None]
        k_ids = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(q_ids // block_len >= k_ids // block_len, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
