"""The way back from a grouped expert product, in one pass.

``models/hybrid.py``'s grouped forms sort a prompt's (token, expert)
pairs by expert, multiply each expert's rows by its matrices and get
``y``: one float32 row a pair, in expert order. What a token is owed is
the sum of its pairs' rows, each times its router weight. In XLA that
was, for each of a token's ``top_k`` pairs in turn, one gather of
``tokens`` rows of ``y`` and one select-add over the whole ``(tokens,
d)`` sum: ``top_k`` reads and writes of the sum and ``top_k`` gathered
copies, 3.4 GB of traffic a layer at 4,096 tokens x top-10 x 4,096 wide
where ``y``'s live rows read once and the sum written once are 0.4 GB,
and it wanted each pair's place in the sorted order: the inverse of the
sort, a scatter of one element at a time.

Mosaic copies no single row of a tiled array (a slice of the sublane
dimension is whole tiles of 8), so a token's rows cannot be fetched one
by one from HBM. The kernel walks ``y`` in the order it lies in instead:
the grid is (column block, chunk of rows); a block of ``lanes`` columns
of the *sum* stays in VMEM while the chunks of ``y``'s rows stream under
it through the pipeline, and row ``r`` is added, times ``weights[r]``,
to sublane ``token[r]`` of the block: one strided vector load a row of
1,024 lanes (the row's eight lane tiles become the eight sublanes of a
register), one of the sum's row, a multiply, an add and a store, at
sublanes the scalar unit reads from SMEM. Only the first ``live`` rows
are walked - the pairs that are here sort first - and a chunk past them
is neither fetched nor visited. ``y``'s live rows are read once and the
sum written once; nothing of size ``tokens x top_k x d`` exists, and
nobody needs the inverse of the sort. A token's rows are added in the
order of their experts (XLA's loop added them in the order of ``k``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import use_interpret
from horovod_tpu.ops.pallas.kv_cache_write import LANES

KERNEL = "expert_combine"
# rows of ``y`` a grid step: their tokens and weights are the step's SMEM
# blocks, whole tiles of 1,024 words
CHUNK = 1024
# VMEM the blocks may take, of the chip's 128 MiB
VMEM_BYTES = 96 * 2 ** 20


def _combine_kernel(live_ref, token_ref, w_ref, *refs):
    # live_ref: (1,) int32 in SMEM (scalar prefetch); token_ref / w_ref:
    # (CHUNK,) int32 / float32 in SMEM, this chunk's; y_ref: (CHUNK,
    # lanes); o_ref and (where the caller accumulates) acc_ref: (tokens,
    # lanes), the same block for every chunk of a column block
    *acc_ref, y_ref, o_ref = refs
    chunk = pl.program_id(1)
    rows = y_ref.shape[0]

    @pl.when(chunk == 0)
    def _():
        o_ref[...] = acc_ref[0][...] if acc_ref else jnp.zeros_like(o_ref)

    def row(r):
        return pl.ds(token_ref[r], 1), y_ref[pl.ds(r, 1), :] * w_ref[r]

    def two(i, carry):
        # two rows a trip, both sums loaded before either is stored, so
        # that the second row's chain of address, load, add and store
        # runs beside the first's; where both rows are one token's (an
        # expert's last pair and the next expert's first) the second
        # adds to what the first made
        (a, ya), (b, yb) = row(2 * i), row(2 * i + 1)
        first = o_ref[a, :] + ya
        second = jnp.where(token_ref[2 * i] == token_ref[2 * i + 1], first,
                           o_ref[b, :]) + yb
        o_ref[a, :] = first
        o_ref[b, :] = second
        return carry

    # nothing for a chunk past the live rows (its block is the last live
    # chunk's again: nothing was fetched for it either)
    n = jnp.clip(live_ref[0] - chunk * rows, 0, rows)
    jax.lax.fori_loop(0, n // 2, two, 0)

    @pl.when(n % 2 == 1)
    def _():
        at, add = row(n - 1)
        o_ref[at, :] = o_ref[at, :] + add


def expert_combine(y, token, weights, live, tokens, acc=None):
    """``out[t] = acc[t] + sum of weights[r] * y[r] over the rows r <
    live with token[r] == t``, in float32, r ascending; ``acc`` ``None``
    is zeros (and no operand).

    ``y``: (room, d) float32; ``token``: (room,) int32, the token each
    row belongs to, read for ``r < live`` only and clipped into
    ``tokens`` there; ``weights``: (room,) float32; ``live``: int32
    scalar, at most ``room``; ``acc``: (tokens, d) float32, aliased to
    the result. Returns (tokens, d) float32. Rows from ``live`` on may
    hold anything: they are not read. A token with no live row gets
    ``acc``'s row back."""
    return _expert_combine(y, token, weights, live, acc, tokens=tokens,
                           interpret=use_interpret())


def _lanes(d, tokens, rows, accumulates):
    """Columns a block: the widest whole number of lane tiles that
    divides ``d`` (all of a ``d`` that is no whole number of them: a toy
    model's) and whose blocks fit ``VMEM_BYTES``, the sum's (and
    ``acc``'s) and a chunk of ``y``, each twice for the pipeline. What
    the scalar unit does for a row is done once a column block."""
    for lanes in ([tiles * LANES for tiles in range(d // LANES, 0, -1)]
                  if d % LANES == 0 else [d]):
        if d % lanes == 0 and 2 * 4 * lanes * (
                (1 + accumulates) * tokens + rows) <= VMEM_BYTES:
            return lanes
    raise ValueError(
        f"the sum of {tokens} tokens does not fit {VMEM_BYTES} bytes of "
        f"VMEM {LANES} columns at a time: combine fewer tokens a call")


# jitted so that a model's layers share one trace and one Mosaic lowering
@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def _expert_combine(y, token, weights, live, acc, *, tokens, interpret):
    room, d = y.shape
    if y.dtype != jnp.float32:
        raise ValueError(f"y is {y.dtype}: float32 rows are combined")
    rows = min(room, CHUNK)
    chunks = pl.cdiv(room, rows)
    lanes = _lanes(d, tokens, rows, acc is not None)
    # whole chunks of the small operands; a ragged last chunk of ``y``
    # ends past ``room``, so past ``live``
    whole = lambda a: jnp.pad(a, (0, chunks * rows - room))
    token = whole(jnp.clip(token.astype(jnp.int32), 0, tokens - 1))
    weights = whole(weights.astype(jnp.float32))
    live = jnp.clip(jnp.asarray(live, jnp.int32), 0, room).reshape(1)
    # a chunk past the live rows maps to the last live one: the pipeline
    # fetches nothing for a block it already has
    last = lambda live: jnp.maximum(pl.cdiv(live[0], rows) - 1, 0)
    small = pl.BlockSpec((rows,), lambda c, r, live: (
        jnp.minimum(r, last(live)),), memory_space=pltpu.SMEM)
    total = pl.BlockSpec((tokens, lanes), lambda c, r, live: (0, c))
    sums = () if acc is None else (acc,)
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(d // lanes, chunks),
            in_specs=[small, small, *(total for _ in sums),
                      pl.BlockSpec((rows, lanes), lambda c, r, live: (
                          jnp.minimum(r, last(live)), c))],
            out_specs=total),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        # operands count from the prefetched scalar
        input_output_aliases={3: 0} if sums else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES + 16 * 2 ** 20),
        interpret=interpret, name=KERNEL,
    )(live, token, weights, *sums, y)
