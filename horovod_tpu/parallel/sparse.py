"""Sparse (embedding) gradient exchange: allgather instead of allreduce.

Capability parity with the reference's sparse path (reference:
horovod/tensorflow/__init__.py:64-75 — ``tf.IndexedSlices`` gradients are
exchanged as allgather(values) + allgather(indices) rather than densified
and allreduced; ``sparse_as_dense`` densifies first,
horovod/tensorflow/__init__.py:200-203).

JAX produces dense gradients, so the sparse representation is explicit: a
:class:`SparseGrad` pytree holds the touched row ids and their gradient
rows. For an embedding table of V rows where a step touches n ≪ V rows,
exchanging ``n·d`` values per worker over ICI beats allreducing ``V·d``
— the same bandwidth argument the reference makes for NCCL.

The exchange is mathematically exact: the dense gradient is
``scatter_add(zeros, ids, rows)`` and scatter-add commutes with
concatenation, so densify(allgather(sparse)) == allreduce(densify(sparse)).

Canonical usage (see also tests/test_sparse.py)::

    value_and_grad = hvd.with_sparse_embedding_grad(
        lambda rows, labels: loss(rows, labels))
    loss, table_grad = value_and_grad(table, ids, labels)
    # table_grad is a SparseGrad; DistributedOptimizer/allreduce_gradients
    # exchange it via allgather and hand the optimizer a dense average.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.core import mesh as mesh_mod


class SparseGrad:
    """Gradient of an embedding table concentrated on ``indices``.

    ``indices``: (nnz,) int32 row ids (duplicates allowed — they add).
    ``values``: (nnz, ...) gradient rows, one per index.
    ``num_rows``: static leading dimension of the dense table.

    Registered as a pytree (static ``num_rows``) so it can cross ``jit``
    boundaries and live inside gradient pytrees.
    """

    def __init__(self, indices, values, num_rows: int):
        self.indices = indices
        self.values = values
        self.num_rows = int(num_rows)

    def densify(self) -> jax.Array:
        """Scatter-add to the dense gradient."""
        dense_shape = (self.num_rows,) + tuple(self.values.shape[1:])
        zeros = jnp.zeros(dense_shape, self.values.dtype)
        return zeros.at[self.indices].add(self.values)

    def __repr__(self):
        return (f"SparseGrad(nnz={self.indices.shape[0]}, "
                f"num_rows={self.num_rows}, values={self.values.shape})")


jax.tree_util.register_pytree_node(
    SparseGrad,
    lambda sg: ((sg.indices, sg.values), sg.num_rows),
    lambda num_rows, children: SparseGrad(children[0], children[1], num_rows),
)


def is_sparse(x: Any) -> bool:
    return isinstance(x, SparseGrad)


def densify_leaf(sg: SparseGrad) -> jax.Array:
    """Densify in either representation: plain ``(nnz,)`` indices, or the
    eager mode's worker-stacked ``(N, nnz)`` components (one dense gradient
    per worker, stacked)."""
    if not isinstance(sg.indices, jax.core.Tracer) and sg.indices.ndim == 2:
        return jax.vmap(
            lambda i, v: SparseGrad(i, v, sg.num_rows).densify())(
                sg.indices, sg.values)
    return sg.densify()


def with_sparse_embedding_grad(apply_fn, extra_argnums=()):
    """Make a value-and-grad function whose embedding-table gradient is a
    :class:`SparseGrad`.

    ``apply_fn(rows, *args)`` computes the scalar loss from the *gathered*
    embedding rows (shape ``ids.shape + (d,)``). The returned function has
    signature ``(table, ids, *args) -> (value, SparseGrad)``. Only the rows
    are differentiated by default — extra args (labels, masks) are treated
    as constants; pass their ``apply_fn`` argnums via ``extra_argnums`` to
    also get their gradients, as ``(value, (SparseGrad, *extra_grads))``.

    This is the TPU-native analogue of the reference relying on TF to emit
    ``IndexedSlices`` for ``tf.gather`` (reference:
    horovod/tensorflow/__init__.py:64-75): the lookup is split out so the
    backward never materialises the dense V×d gradient.
    """
    extra_argnums = tuple(extra_argnums)
    if 0 in extra_argnums:
        raise ValueError("argnum 0 (the rows) is always differentiated")

    def value_and_grads(table, ids, *args):
        flat_ids = ids.reshape(-1)
        rows = jnp.take(table, flat_ids, axis=0).reshape(
            ids.shape + table.shape[1:])
        value, grads = jax.value_and_grad(
            apply_fn, argnums=(0,) + extra_argnums)(rows, *args)
        d_rows = grads[0].reshape((flat_ids.shape[0],) + table.shape[1:])
        sparse = SparseGrad(flat_ids, d_rows, table.shape[0])
        if extra_argnums:
            return value, (sparse,) + tuple(grads[1:])
        return value, sparse

    return value_and_grads


def sparse_allgather(sg: SparseGrad, axis_name=None) -> SparseGrad:
    """Concatenate a per-device SparseGrad across the mesh axes — the
    reference's allgather(values)+allgather(indices) exchange. Must run
    inside ``shard_map`` (axes bound)."""
    axes = axis_name if axis_name is not None else mesh_mod.GLOBAL_AXES
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    indices = lax.all_gather(sg.indices, axes, tiled=True)
    values = lax.all_gather(sg.values, axes, tiled=True)
    return SparseGrad(indices, values, sg.num_rows)


def exchange_sparse_grad(sg: SparseGrad, *, average: bool,
                         compression, axis_name, bound_axes) -> jax.Array:
    """Exchange one SparseGrad leaf across workers; return the dense
    averaged (or summed) gradient for the optimizer.

    In-jit under ``shard_map``: allgather(ids)+allgather(values) over the
    bound axes, then one scatter-add — wire cost O(nnz·N·d), not O(V·d).
    In-jit without bound axes (global-batch pjit): the ids/rows are already
    global, so this is just the scatter-add.
    Eager: components are worker-stacked; densify per worker and allreduce.
    """
    if isinstance(sg.values, jax.core.Tracer) or isinstance(
            sg.indices, jax.core.Tracer):
        if bound_axes:
            world = 1
            for a in bound_axes:
                world *= lax.axis_size(a)
            c_values, ctx = compression.compress(sg.values)
            gathered = sparse_allgather(
                SparseGrad(sg.indices, c_values, sg.num_rows),
                axis_name=bound_axes)
            values = compression.decompress(gathered.values, ctx)
            dense = SparseGrad(gathered.indices, values,
                               sg.num_rows).densify()
            return dense / world if average else dense
        # Global-batch pjit: gradients of a global-mean loss are already
        # the global average once scattered.
        return sg.densify()

    # Eager: leaves are worker-stacked (N, ...) arrays — densify each
    # worker's slice, then ride the dense eager allreduce.
    from horovod_tpu.ops import collectives

    return collectives.allreduce(
        densify_leaf(sg), average=average, compression=compression,
        axis_name=axis_name)
