"""Data-parallel training API: the ``DistributedOptimizer`` family.

TPU-native equivalent of the reference's framework wrappers (reference:
horovod/torch/__init__.py:47-203 ``_DistributedOptimizer``,
horovod/tensorflow/__init__.py:230-263 ``DistributedOptimizer``,
:323-376 ``DistributedGradientTape``). The idiomatic JAX optimizer is an
``optax.GradientTransformation``; ``DistributedOptimizer`` wraps one so that
gradients are averaged across all workers before the inner update:

* Under ``shard_map`` (per-device gradients, explicit SPMD): emits
  ``lax.pmean`` over the mesh axes — compiled into the step as an XLA
  all-reduce over ICI.
* Under plain ``jit``/``pjit`` with a global batch: gradients of a
  global-mean loss are *already* the global average; the wrapper detects
  that no mesh axis is bound and is a no-op, so the same user code runs
  in both styles.
* Eagerly (outside ``jit``): dispatches the cached compiled allreduce.

Gradient accumulation (``backward_passes_per_step``, reference:
horovod/torch/__init__.py:82-143) accumulates in optimizer state and
allreduces once every N steps.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from horovod_tpu.compression import Compression
from horovod_tpu.core import basics, mesh as mesh_mod
from horovod_tpu.ops import collectives
from horovod_tpu.parallel import sparse as sparse_mod


def _bound_axes(axis_name=None) -> tuple:
    """Return the subset of the requested mesh axes bound in the current
    trace (empty outside ``shard_map``)."""
    axes = axis_name if axis_name is not None else mesh_mod.GLOBAL_AXES
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    bound = []
    for a in axes:
        try:
            lax.axis_size(a)
        except NameError:
            continue
        bound.append(a)
    return tuple(bound)


def _reduce_traced(g, axes, average: bool):
    """Sum or mean of a traced per-device gradient over the bound mesh
    ``axes``.

    Under ``jax.shard_map``'s default ``check_vma=True`` the cotangent of
    a replicated input is typed unvarying and arrives already ``psum``-ed
    by autodiff (the transpose of the implicit replicated-to-varying
    cast), so a ``pmean`` on top of it would hand back the sum: N times
    the mean. Reduce only over the axes the gradient still varies over
    and divide by the whole world. With ``check_vma=False`` nothing is
    typed and every gradient is per-device, as before."""
    axes = tuple(axes)
    # axis_index is typed varying over its axis exactly when shard_map
    # tracks varying axes (check_vma=True)
    if axes[0] in jax.typeof(lax.axis_index(axes[0])).vma:
        varying = tuple(a for a in axes if a in jax.typeof(g).vma)
    else:
        varying = axes
    if varying == axes:
        return lax.pmean(g, axes) if average else lax.psum(g, axes)
    r = lax.psum(g, varying) if varying else g
    return r / lax.axis_size(axes) if average else r


def _allreduce_leaf(g, average, compression, axis_name,
                    sparse_as_dense=False):
    if g is None:
        return None
    if sparse_mod.is_sparse(g):
        # Sparse/embedding gradient (reference:
        # horovod/tensorflow/__init__.py:64-75): exchanged via allgather of
        # (indices, values) unless sparse_as_dense densifies first
        # (reference: tensorflow/__init__.py:200-203).
        if sparse_as_dense:
            g = sparse_mod.densify_leaf(g)
        else:
            return sparse_mod.exchange_sparse_grad(
                g, average=average, compression=compression,
                axis_name=axis_name, bound_axes=_bound_axes(axis_name))
    if isinstance(g, jax.core.Tracer):
        axes = _bound_axes(axis_name)
        if not axes:
            # Plain pjit global-batch DP: gradients are already the global
            # average; XLA inserted the collective from the shardings.
            return g
        c, ctx = compression.compress(g)
        with jax.named_scope("grad_exchange"):
            r = _reduce_traced(c, axes, average)
        return compression.decompress(r, ctx)
    return collectives.allreduce(
        g, average=average, compression=compression, axis_name=axis_name
    )


def allreduce_gradients(grads, *, average: bool = True,
                        compression=Compression.none, axis_name=None,
                        sparse_as_dense: bool = False):
    """Average a pytree of gradients across all workers.

    Functional analogue of ``DistributedGradientTape.gradient`` post-
    processing (reference: horovod/tensorflow/__init__.py:323-376).
    ``SparseGrad`` leaves ride the allgather path (or are densified first
    when ``sparse_as_dense``); either way the result is dense.

    Eager dense leaves are exchanged through
    :func:`collectives.grouped_allreduce`, so a whole pytree is one
    fused submission per dtype group instead of one collective per leaf
    (reference: the fusion-buffer batching the per-leaf reference path
    gets from its background coordinator, horovod/common/operations.cc).
    Tracer leaves keep the in-jit ``lax.pmean``/``psum`` path unchanged.

    Inside a :func:`horovod_tpu.parallel.buckets.prereduced` scope the
    tree is returned untouched: a bucket-wise release plan already
    exchanged the gradients during backward, and reducing them a second
    time would divide (or multiply) by the world size twice.
    """
    from horovod_tpu.parallel import buckets as buckets_mod
    from horovod_tpu.parallel import zero as zero_mod

    if isinstance(grads, zero_mod.ShardedGrads):
        raise TypeError(
            "allreduce_gradients got a zero.ShardedGrads: stage-2 gradients "
            "are already the reduced local shard — feed them straight to a "
            "partition-aligned zero.sharded_adamw / zero.sharded_update "
            "instead of re-reducing them")
    if buckets_mod.is_prereduced():
        return grads
    leaves, treedef = jax.tree_util.tree_flatten(
        grads, is_leaf=sparse_mod.is_sparse)
    out = list(leaves)
    dense_eager = []
    for i, g in enumerate(leaves):
        if g is None:
            continue
        if sparse_mod.is_sparse(g):
            if sparse_as_dense:
                g = sparse_mod.densify_leaf(g)
            else:
                out[i] = sparse_mod.exchange_sparse_grad(
                    g, average=average, compression=compression,
                    axis_name=axis_name,
                    bound_axes=_bound_axes(axis_name))
                continue
        if isinstance(g, jax.core.Tracer):
            out[i] = _allreduce_leaf(g, average, compression, axis_name,
                                     False)
            continue
        out[i] = g
        dense_eager.append(i)
    if dense_eager:
        # submit reverse-topological (last layer first): tree-flatten
        # order follows the forward layer order, but backward finalizes
        # gradients back-to-front, so fronting the tail of the tree puts
        # the earliest-ready gradients at the head of the fusion queue —
        # same ordering the bucket-release plan uses
        submit = list(reversed(dense_eager))
        reduced = collectives.grouped_allreduce(
            [out[i] for i in submit], average=average,
            compression=compression, axis_name=axis_name)
        for i, r in zip(submit, reduced):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    compression=Compression.none,
    average: bool = True,
    backward_passes_per_step: int = 1,
    axis_name=None,
    sparse_as_dense: bool = False,
    shard_optimizer_states: bool = False,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so gradients are allreduced across workers
    before each update.

    Usage mirrors the reference (reference: examples/*.py, API
    horovod/torch/__init__.py:205-253):

        opt = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.size()))

    ``compression`` casts gradients to a 16-bit wire type for the
    collective; ``backward_passes_per_step`` accumulates N micro-batches
    between allreduces (reference: torch/__init__.py:82-143);
    ``sparse_as_dense`` densifies ``SparseGrad`` leaves before the
    exchange instead of allgathering them (reference:
    tensorflow/__init__.py:200-203).

    ``shard_optimizer_states=True`` switches to the ZeRO-1 data plane
    (:mod:`horovod_tpu.parallel.zero`): the allreduce decomposes into
    reduce-scatter + update-on-shard + allgather, so the inner
    optimizer's state lives 1/N per chip. Same wire bytes, bit-identical
    updates for elementwise inner transforms. Requires
    ``backward_passes_per_step == 1`` (MultiSteps' internal ``lax.cond``
    would trace the eager sharded data plane).

    Stages 2/3 ride the same wrapper: pass a ``zero.ShardedGrads`` (from
    ``zero.scatter_gradients`` or a ``GradReleasePlan(reduce_scatter=True)``)
    as the grads and the reduce-scatter phase is skipped — the wire cost
    drops to half an allreduce because only the scatter half ran. Params
    sharded at rest (``zero.shard_params``) make the update return a
    ``zero.ShardedParams`` and skip the trailing allgather too (stage 3);
    gather buckets on demand with ``zero.iter_param_buckets``.
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    if shard_optimizer_states:
        if backward_passes_per_step != 1:
            raise ValueError(
                "shard_optimizer_states does not compose with "
                "backward_passes_per_step > 1: accumulate in the training "
                "loop instead")
        from horovod_tpu.parallel import zero

        return zero.sharded_update(
            optimizer, average=average, compression=compression,
            axis_name=axis_name, sparse_as_dense=sparse_as_dense)

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(grads, opt_state, params=None, **extra):
        # step-profiler hook (profiler.py): on the eager path each update
        # is a step boundary, and the inner update is the optimizer phase.
        # Inside jit/shard_map everything is a tracer — the whole step is
        # one program and the profiler attributes it as compute.
        from horovod_tpu import integrity as _integrity
        from horovod_tpu import profiler as _profiler

        traced = any(isinstance(g, jax.core.Tracer)
                     for g in jax.tree_util.tree_leaves(grads))
        eager = _profiler.enabled() and not traced
        if eager:
            _profiler.auto_step()
        if not traced:
            # memory plane: grads/params live-bytes (shape math only);
            # inside jit these are tracers and the step owns the bytes
            from horovod_tpu import memory as _memory

            _t = _memory.tracker()
            if _t.enabled:
                _t.note_tree_bytes("grads", grads)
                if params is not None:
                    _t.note_tree_bytes("params", params)
        reduced = allreduce_gradients(
            grads, average=average, compression=compression,
            axis_name=axis_name, sparse_as_dense=sparse_as_dense,
        )
        if _integrity.enabled() and not traced:
            from horovod_tpu.integrity import guards as _guards

            # the guard observes the globally-reduced grad norm, so every
            # rank sees the same stream and skips the same steps; a skip
            # suppresses the update (zero deltas, state untouched) while
            # the batch stays consumed
            if not _guards.guard_gradients(reduced):
                zeros = jax.tree_util.tree_map(jnp.zeros_like, reduced)
                return zeros, opt_state
        if eager:
            with _profiler.annotate("optimizer"):
                return optimizer.update(reduced, opt_state, params, **extra)
        return optimizer.update(reduced, opt_state, params, **extra)

    tx = optax.GradientTransformationExtraArgs(init_fn, update_fn)
    if backward_passes_per_step > 1:
        multi = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)

        def accum_update(grads, opt_state, params=None, **extra):
            # MultiSteps accumulates into a dense zeros_like(params) tree,
            # so SparseGrad leaves must densify before accumulation (the
            # sparse wire saving doesn't combine with accumulate-then-
            # exchange; correctness first).
            grads = jax.tree_util.tree_map(
                lambda g: sparse_mod.densify_leaf(g)
                if sparse_mod.is_sparse(g) else g,
                grads, is_leaf=sparse_mod.is_sparse)
            return multi.update(grads, opt_state, params, **extra)

        return optax.GradientTransformationExtraArgs(multi.init, accum_update)
    return tx


def DistributedGradientTape(
    grad_fn: Callable[..., Any],
    *,
    compression=Compression.none,
    average: bool = True,
    axis_name=None,
    returns: str = "grads",
    sparse_as_dense: bool = False,
) -> Callable[..., Any]:
    """Wrap a gradient-producing function so its gradients are allreduced.

    JAX has no tape; the analogue of wrapping ``tf.GradientTape``
    (reference: horovod/tensorflow/__init__.py:323-376) is wrapping the
    function returned by ``jax.grad``/``jax.value_and_grad``. Because a
    2-tuple output is ambiguous (grads-over-tuple-params vs (value, grads)
    vs (grads, aux)), the convention is stated explicitly:

    * ``returns="grads"`` (default) — the whole output is the gradient
      pytree (``jax.grad(f)``, including tuple params).
    * ``returns="value_and_grads"`` — output is ``(value, grads)``
      (``jax.value_and_grad(f)``; value may itself be ``(loss, aux)``).
    * ``returns="grads_and_aux"`` — output is ``(grads, aux)``
      (``jax.grad(f, has_aux=True)``).
    """
    if returns not in ("grads", "value_and_grads", "grads_and_aux"):
        raise ValueError(
            "returns must be 'grads', 'value_and_grads' or 'grads_and_aux', "
            f"got {returns!r}")

    def reduce(grads):
        return allreduce_gradients(
            grads, average=average, compression=compression,
            axis_name=axis_name, sparse_as_dense=sparse_as_dense)

    def wrapped(*args, **kwargs):
        out = grad_fn(*args, **kwargs)
        if returns == "value_and_grads":
            value, grads = out
            return value, reduce(grads)
        if returns == "grads_and_aux":
            grads, aux = out
            return reduce(grads), aux
        return reduce(out)

    return wrapped


def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a parameter pytree from ``root_rank`` to all workers, the
    init-sync convention (reference: horovod/torch/__init__.py:255-403
    ``broadcast_parameters``, tensorflow/__init__.py:104-113
    ``broadcast_variables``).

    In single-controller SPMD the parameters are already globally
    consistent; this forces replicated sharding over the mesh (a no-op for
    already-replicated arrays) so later steps see identical layouts — and in
    multi-process mode it is the collective that makes rank 0's values
    authoritative.
    """
    return jax.tree_util.tree_map(
        lambda p: collectives.broadcast(p, root_rank)
        if isinstance(p, (jax.Array,)) or hasattr(p, "shape")
        else p,
        params,
    )


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Broadcast optimizer state from ``root_rank`` (reference:
    horovod/torch/__init__.py:306-403). Array leaves are broadcast;
    non-array leaves (step counters, None, hyperparams) pass through — in
    JAX they are part of the jit-replicated program state already."""
    return broadcast_parameters(opt_state, root_rank=root_rank)


def broadcast_object(obj, root_rank: int = 0, name: Optional[str] = None):
    """Broadcast an arbitrary picklable object from ``root_rank``.

    Single-process: identity. Multi-process: value is shipped through the
    coordination service KV store (the analogue of the reference's
    rendezvous store, reference: gloo/http_store.cc).
    """
    st = basics._ensure_init()
    if st.cross_size <= 1 or jax.process_count() == 1:
        return obj
    from horovod_tpu.runtime import coordination

    return coordination.broadcast_object(obj, root_rank=root_rank, name=name)
