"""A grouped matrix product for rows that lie sorted by expert.

``models/hybrid.py``'s grouped forms sort a prompt's (token, expert)
pairs by expert and multiply each expert's run of rows by that expert's
matrices: ``out[r] = rows[r] @ w[e]`` for the rows ``r`` of expert ``e``,
``sizes[e]`` of them, one run after the other from row 0. Rows from
``sum(sizes)`` on belong to no expert: they cost no product, and what the
result holds there is not defined (nobody reads it).

The grid walks *visits*: a visit is one row tile under one expert, and the
visits are listed in sorted order, so the tiles of one expert follow each
other and the experts that share a tile (one ends in it, the next begins)
visit it one after the other. The list is made once from ``sizes`` by
:func:`group_visits` and prefetched as scalars; both products of a layer
read the same list, because both have the same rows in the same tiles. A
visit's blocks are its row tile and its expert's ``(k, columns)`` block:
the pipeline fetches a block only when its index changes, so an expert's
matrix stays in VMEM across that expert's consecutive tiles, an expert
with no row is in no visit and is never fetched, and a visit past the last
one (the list is as long as the most there can be: tiles + experts - 1)
points at the last one's blocks and runs nothing. Inside a visit the tile
is multiplied ``SUB_ROWS`` rows at a time, only the blocks that hold a row
of the visit's expert, and each is stored under a row mask over what an
earlier visit left there: a visit writes its own rows alone, and what an
expert's first and last tile cost beyond its rows is under ``SUB_ROWS``
rows each, whatever the tile's length. A block's product is itself a loop
over groups of columns (:func:`_width`): Mosaic writes a product's
matrix-unit instructions out in full and XLA keeps a copy of a kernel's
code for every call of it, so one group's product is all the code there is.

Two forms, so that a program holds two kernels and no more:
:func:`grouped_gate_up` reads a row tile once and writes ``silu(rows @
gate[e]) * (rows @ up[e])`` from float32 products that never leave VMEM,
and :func:`grouped_product` is the plain product (the way down, float32
out); :func:`gated_products` is a layer's three products through both.
Operands go to the matrix unit in the dtype they come in (bfloat16 in
every served model), accumulation is float32. Tile sizes are this
module's business: ``ROWS`` and ``SUB_ROWS`` below (measured: PERF.md
section 6, PR 49), columns a block from what fits VMEM beside them
(:func:`_columns`).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas._backend import use_interpret
from horovod_tpu.ops.pallas.kv_cache_write import LANES

KERNEL = "grouped_product"
# rows a tile: what a visit's blocks hold and the pipeline moves at once
ROWS = 512
# rows a product inside a visit: a visit multiplies the blocks of this many
# rows that hold a row of its expert's, so what an expert's first and last
# tile cost beyond its own rows is under this many rows each
SUB_ROWS = 128
# elements of an expert's matrix a product inside a block: the matrix
# unit's instructions are written out for one such product, so this is what
# a kernel's code weighs in its program's executable, once a call of every
# layer and turn (XLA keeps a copy a call); see :func:`_width`
PRODUCT_ELEMENTS = 2 ** 20
# VMEM the blocks may take (each twice, for the pipeline) with the float32
# products beside them, of the chip's 128 MiB
VMEM_BYTES = 64 * 2 ** 20

# One visit each: the row tile, the expert, and the tile's rows [lo, hi)
# that are the expert's (hi == 0: past the last visit, nothing to do).
Visits = collections.namedtuple("Visits", "tile expert lo hi")


def row_tile(m):
    """Rows a tile of ``m`` rows: all of fewer than ``ROWS``."""
    return min(m, ROWS)


def group_visits(sizes, m):
    """The (row tile, expert) visits of a grouped product over ``m`` rows,
    in sorted order: what both of a layer's products walk. ``sizes``:
    (experts,) int32, ``sum(sizes) <= m``. Returns :class:`Visits` of four
    (tiles + experts - 1,) int32 arrays."""
    experts = sizes.shape[0]
    rows = row_tile(m)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // rows                       # an expert's first tile
    count = jnp.where(sizes > 0, (ends - 1) // rows - first + 1, 0)
    stop = jnp.cumsum(count)                     # visits up to each expert
    tiles = pl.cdiv(m, rows)
    at = jnp.arange(tiles + experts - 1, dtype=jnp.int32)
    # a visit past the last is the last again: its blocks are there already
    visit = jnp.minimum(at, jnp.maximum(stop[-1] - 1, 0))
    expert = jnp.minimum(jnp.sum(stop[None, :] <= visit[:, None], axis=1,
                                 dtype=jnp.int32), experts - 1)
    own = expert[:, None] == jnp.arange(experts, dtype=jnp.int32)[None, :]
    of = lambda a: jnp.sum(jnp.where(own, a[None, :], 0), axis=1)
    # (inside the rows whatever ``sizes`` claims: a block index past them
    # would be a copy from outside the array)
    tile = jnp.minimum(of(first) + visit - of(stop - count), tiles - 1)
    lo = jnp.clip(of(starts) - tile * rows, 0, rows)
    hi = jnp.clip(of(ends) - tile * rows, 0, rows)
    return Visits(tile, expert, lo, jnp.where(at < stop[-1], hi, 0))


def _product_kernel(tile_ref, expert_ref, lo_ref, hi_ref, x_ref, *refs):
    # x_ref: (rows, k), the visit's tile; w_refs: one or two (1, k,
    # columns) blocks of the visit's expert; o_ref: (rows, columns), the
    # same block for every visit of a tile
    *w_refs, o_ref = refs
    visit = pl.program_id(1)
    lo, hi = lo_ref[visit], hi_ref[visit]
    rows = o_ref.shape[0]
    sub = SUB_ROWS if rows % SUB_ROWS == 0 else rows

    columns = o_ref.shape[1]
    width = _width(x_ref.shape[1], columns)

    def block(s, carry):
        at = pl.multiple_of(s * sub, sub)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        mine = (row >= lo) & (row < hi)

        def group(c, carry):
            # ``width`` of the block's columns at a time: what Mosaic
            # unrolls, and so the kernel's code, is one group's product
            here = (pl.ds(at, sub), pl.ds(pl.multiple_of(c * width, width),
                                          width))
            x = x_ref[pl.ds(at, sub), :]
            out = jnp.dot(x, w_refs[0][0, :, here[1]],
                          preferred_element_type=jnp.float32)
            if len(w_refs) == 2:
                out = out * jax.nn.sigmoid(out) * jnp.dot(
                    x, w_refs[1][0, :, here[1]],
                    preferred_element_type=jnp.float32)
            # the expert's rows alone, over what an earlier visit left
            o_ref[here] = jnp.where(mine, out.astype(o_ref.dtype),
                                    o_ref[here])
            return carry

        return jax.lax.fori_loop(0, columns // width, group, carry)

    # the blocks of SUB_ROWS rows that hold a row of the visit's: none
    # past the last visit, where hi is 0
    jax.lax.fori_loop(lo // sub, pl.cdiv(hi, sub), block, 0)


def gated_products(rows, gate, up, down, sizes):
    """``(silu(rows @ gate[e]) * (rows @ up[e])) @ down[e]`` for the rows
    of each expert ``e``, ``sizes[e]`` of them: the visits made once and
    walked by both kernels, the hidden rows rounded once to ``rows``'
    dtype, the result float32. ``rows``: (m, k) in expert order;
    ``gate``/``up``: (experts, k, n); ``down``: (experts, n, k);
    ``sizes``: (experts,) int32, ``sum(sizes) <= m``. Returns (m, k)
    float32, defined on the experts' rows alone."""
    return _gated_products(rows, gate, up, down, sizes,
                           interpret=use_interpret())


# ONE jitted callable for all of it: a layer is one call in its program's
# trace, and a model's layers and a layer's turns share one trace of the
# visits and of each kernel and, a program, one Mosaic lowering of each
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_products(rows, gate, up, down, sizes, *, interpret):
    visits = group_visits(sizes, rows.shape[0])
    hidden = _grouped(rows, (gate, up), visits, rows.dtype, interpret)
    return _grouped(hidden, (down,), visits, jnp.dtype(jnp.float32),
                    interpret)


def grouped_product(rows, w, visits, out_dtype=None):
    """One of the two kernels by itself: ``rows[r] @ w[e]`` for the rows
    of each expert ``e``. ``w``: (experts, k, n); ``visits``:
    :func:`group_visits` of the experts' sizes and ``m``. Returns (m, n) of
    ``out_dtype`` (``rows``' own if ``None``)."""
    return _alone(rows, (w,), visits, jnp.dtype(out_dtype or rows.dtype),
                  use_interpret())


def grouped_gate_up(rows, gate, up, visits):
    """The other: ``silu(rows[r] @ gate[e]) * (rows[r] @ up[e])``, both
    products in float32 until the one rounding to ``rows``' dtype."""
    return _alone(rows, (gate, up), visits, jnp.dtype(rows.dtype),
                  use_interpret())


def _width(k, columns):
    """Columns a product inside a block: the most whole lane tiles that
    divide the block's ``columns`` with ``k`` times them within
    ``PRODUCT_ELEMENTS`` (one lane tile at the least; all of a block that
    is no whole number of them)."""
    if columns % LANES:
        return columns
    tiles = columns // LANES
    return LANES * max(t for t in range(1, tiles + 1) if tiles % t == 0
                       and (t == 1 or k * t * LANES <= PRODUCT_ELEMENTS))


def _columns(rows, k, n, matrices, in_bytes, out_bytes):
    """Columns a block: the widest whole number of lane tiles that divides
    ``n`` (all of an ``n`` that is no whole number of them: a toy model's)
    and fits ``VMEM_BYTES`` - the row tile, ``matrices`` expert blocks and
    the result, each twice for the pipeline, and the float32 products."""
    for columns in ([tiles * LANES for tiles in range(n // LANES, 0, -1)]
                    if n % LANES == 0 else [n]):
        if n % columns == 0 and (
                2 * (in_bytes * (rows * k + matrices * k * columns)
                     + out_bytes * rows * columns)
                + 4 * (matrices + 1) * SUB_ROWS * columns) <= VMEM_BYTES:
            return columns
    raise ValueError(f"no block of ({k}, {n}) experts fits {VMEM_BYTES} "
                     f"bytes of VMEM beside {rows} rows")


def _grouped(rows, ws, visits, out_dtype, interpret):
    m, k = rows.shape
    experts, _, n = ws[0].shape
    tile = row_tile(m)
    if visits.tile.shape[0] != pl.cdiv(m, tile) + experts - 1:
        raise ValueError(f"visits of another product: {visits.tile.shape[0]} "
                         f"for {m} rows over {experts} experts")
    columns = _columns(tile, k, n, len(ws), rows.dtype.itemsize,
                       out_dtype.itemsize)
    expert_block = pl.BlockSpec(
        (1, k, columns), lambda j, v, tile, expert, lo, hi: (expert[v], 0, j))
    return pl.pallas_call(
        _product_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // columns, visits.tile.shape[0]),
            in_specs=[pl.BlockSpec((tile, k), lambda j, v, tile, expert, lo,
                                   hi: (tile[v], 0)),
                      *(expert_block for _ in ws)],
            out_specs=pl.BlockSpec((tile, columns), lambda j, v, tile, expert,
                                   lo, hi: (tile[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES + 32 * 2 ** 20),
        interpret=interpret, name=KERNEL,
    )(*visits, rows, *ws)


_alone = jax.jit(_grouped, static_argnames=("out_dtype", "interpret"))
