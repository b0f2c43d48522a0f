"""Collective operations: the TPU data plane.

TPU-native replacement for the reference's op layer (reference:
horovod/common/ops/{mpi,nccl,gloo}_operations.cc and the Python op wrappers
horovod/torch/mpi_ops.py, horovod/tensorflow/mpi_ops.py). Where the
reference dispatches to NCCL/MPI/Gloo rings, every collective here is an XLA
collective compiled over the global ``(cross, local)`` device mesh so the
traffic rides ICI (and DCN across slices), fused and scheduled by XLA.

Two call modes, one API:

* **In-jit (hot path)** — called on traced values under ``shard_map``/
  ``pjit``: emits ``lax.psum``/``all_gather``/``psum_scatter``/``all_to_all``
  over the mesh axis names. This is where training-step gradient reduction
  happens, fully fused into the step program.

* **Eager** — called on concrete arrays: dispatches a cached, jit-compiled
  collective program over the mesh. Per-worker data uses the *stacked*
  encoding: an array of shape ``(size, *shape)`` sharded along axis 0, one
  slice per device (see ``stack_per_worker``). A replicated input means
  "every worker holds this same tensor", matching single-controller SPMD
  semantics.

Async semantics come from XLA's async dispatch: eager ops return immediately
with a future-backed ``jax.Array``; ``*_async`` returns a ``Handle`` and
``poll``/``synchronize`` mirror the reference's handle API (reference:
horovod/torch/mpi_ops.py:61-124, torch/handle_manager.cc).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import comms, flight_recorder, tracing
from horovod_tpu.compression import Compression
from horovod_tpu.core import basics, mesh as mesh_mod, state as state_mod

# Reduction ops (reference: common/message.h RequestType + torch mpi_ops v2
# op constants; v0.18 supports sum/average, we add min/max/product as
# first-class TPU extensions).
Average = 0
Sum = 1
Min = 2
Max = 3
Product = 4

_OP_NAMES = {Average: "average", Sum: "sum", Min: "min", Max: "max", Product: "product"}
OPS_BY_NAME = {v: k for k, v in _OP_NAMES.items()}


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _global_axes(axis_name):
    if axis_name is None:
        return mesh_mod.GLOBAL_AXES
    return axis_name


class OrderedLaneError(RuntimeError):
    """A global-mesh collective program was about to be dispatched from a
    caller thread while named async collectives were still in flight on
    the background runtime lane.

    In a multi-process (SPMD) world every rank must issue collective
    programs in the SAME order; the enqueue runtime's background thread is
    the single ordered issuer for dynamically-timed ops (reference
    architecture note: operations.cc:281-300). Interleaving a caller-thread
    global program with in-flight named ops can order programs differently
    per rank — a hang or garbage, which the reference's analogous misuse
    paths turn into errors (tensor_queue.cc:26-29). Synchronize the
    outstanding handles first."""


def _lane_check() -> None:
    """Raise instead of hanging on the documented cross-rank
    program-order hazard (docs/troubleshooting.md: one ordered collective
    lane). Only the multi-process SPMD mode is at risk; the runtime's own
    background thread IS the lane and is exempt."""
    if jax.process_count() <= 1:
        return
    st = state_mod.global_state()
    rt = getattr(st, "runtime", None)
    if rt is None:
        return
    if threading.current_thread() is getattr(rt, "_thread", None):
        return
    n = rt.in_flight()
    if n:
        raise OrderedLaneError(
            f"{n} named async collective(s) are still in flight on the "
            "background runtime lane; dispatching a global-mesh collective "
            "program from the caller thread now can interleave collective "
            "programs differently across ranks (hang/garbage). Call "
            "hvd.synchronize() on the outstanding handles (or "
            "optimizer.step() in the torch binding) first — see "
            "docs/troubleshooting.md, 'one ordered collective lane'.")


def assert_collective_lane_clear() -> None:
    """Public guard for user-owned global programs: call before
    dispatching your own jitted global-mesh step (e.g. a pjit train step)
    in multi-process mode; raises :class:`OrderedLaneError` if named async
    collectives are still in flight instead of risking the documented
    cross-rank interleaving hang."""
    _lane_check()


def _to_plane(tensor):
    """Bring an input onto the data plane WITHOUT narrowing 64-bit numpy
    payloads: ``jnp.asarray`` under default x32 silently casts
    int64/uint64/float64 down (2**40 becomes garbage, 1e300 becomes inf)
    — exactly the corruption the reference's per-dtype op matrix guards
    against (reference: test/test_torch.py dtype sweeps). 64-bit numpy
    arrays stay numpy end-to-end: the host ring reduces them exactly
    (``_widen_for_ring`` passes 64-bit through), and the
    single-controller replicated math (``x * size`` etc.) is exact in
    numpy. Everything else becomes a jax array as before."""
    if isinstance(tensor, jax.Array):
        return tensor
    a = np.asarray(tensor)
    if a.dtype.itemsize == 8 and a.dtype.kind in "iuf":
        return a
    return jnp.asarray(a)


def _replicated_rs_a2a(kind: str, x, world: int, op):
    """Single-controller emulation of reducescatter/alltoall for the
    framework bindings (torch/tf): every worker holds ``x`` (the
    replicated world model the bindings' other ops use), and the binding
    returns worker 0's result — computed exactly in numpy (no device
    round trip, so 64-bit payloads stay exact). Narrow ints widen for
    the arithmetic and cast back, the same wrap-on-overflow semantics as
    the host ring kernels (runtime/executor.py _widen_for_ring)."""
    from horovod_tpu.runtime.executor import _widen_for_ring

    if x.shape[0] % world:
        # bindings check statically where they can; dynamic tf.function
        # shapes bypass that, and flooring here would silently truncate
        raise ValueError(
            f"{kind} dim 0 ({x.shape[0]}) must divide evenly by "
            f"size ({world})")
    shard = x.shape[0] // world
    if kind == "reducescatter":
        head = x[:shard]
        if op == Sum:
            return (_widen_for_ring(head, copy=True) * world).astype(
                head.dtype, copy=False)
        if op == Product:
            return (_widen_for_ring(head, copy=True) ** world).astype(
                head.dtype, copy=False)
        # average/min/max of `world` identical copies is the copy
        return np.array(head, copy=True)
    # alltoall: worker 0 receives chunk 0 from each of `world` identical
    # workers -> tile of the first chunk
    return np.concatenate([x[:shard]] * world, axis=0)


def _resolve_op(average: Optional[bool], op: Optional[int]) -> int:
    if op is not None and average is not None:
        raise ValueError("specify either average or op, not both")
    if op is None:
        # reference default: average=True (torch/mpi_ops.py allreduce)
        return Average if (average is None or average) else Sum
    if op not in _OP_NAMES:
        raise ValueError(f"unknown op {op}")
    return op


# ---------------------------------------------------------------------------
# Stacked / replicated encodings for eager mode
# ---------------------------------------------------------------------------

def stack_per_worker(values) -> jax.Array:
    """Place one tensor per worker: returns a global array of shape
    ``(size, *shape)`` with axis 0 sharded one-slice-per-device.

    This is the single-controller encoding of the reference's
    "each rank holds its own tensor" input model.
    """
    st = basics._ensure_init()
    if isinstance(values, (list, tuple)):
        values = jnp.stack([jnp.asarray(v) for v in values])
    else:
        values = jnp.asarray(values)
    if values.shape[0] != st.size:
        raise ValueError(
            f"stacked input must have leading dim == size ({st.size}), "
            f"got shape {values.shape}"
        )
    return jax.device_put(values, mesh_mod.worker_sharding(st.mesh))


def _is_worker_stacked(x) -> bool:
    """True if ``x`` is a jax array whose axis 0 is sharded across workers
    (the ``stack_per_worker`` layout).

    Detection is purely by sharding spec — including on a 1-device mesh,
    where ``stack_per_worker`` still attaches the worker PartitionSpec, so a
    user array that merely happens to have leading dim == size is never
    silently squeezed.
    """
    st = state_mod.global_state()
    if not isinstance(x, jax.Array) or x.ndim < 1 or x.shape[0] != st.size:
        return False
    sharding = x.sharding
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) == 0:
        return False
    first = spec[0]
    if first is None:
        return False
    axes = first if isinstance(first, tuple) else (first,)
    return set(axes) & set(mesh_mod.GLOBAL_AXES) != set()


# ---------------------------------------------------------------------------
# Cached compiled eager programs
# ---------------------------------------------------------------------------

_jit_cache: dict[tuple, Any] = {}
_jit_cache_lock = threading.Lock()


def _cached(key, builder):
    # Every eager stacked-dispatch site fetches its compiled program here
    # at call time, so this is the one chokepoint for the ordered-lane
    # misuse check (raise instead of the documented cross-rank hang).
    _lane_check()
    with _jit_cache_lock:
        fn = _jit_cache.get(key)
        if fn is None:
            fn = builder()
            _jit_cache[key] = fn
        return fn


def clear_compiled_cache() -> None:
    """Drop cached compiled collective programs (called on shutdown so a
    re-init with a different mesh starts clean)."""
    with _jit_cache_lock:
        _jit_cache.clear()


def _replicated(mesh):
    return mesh_mod.replicated_sharding(mesh)


_noname_counters: dict = {}


def _auto_name(kind: str) -> str:
    """Call-order names for unnamed eager ops in multi-process mode —
    ranks match tensors by identical call sequence, exactly the
    reference's unnamed-op convention (reference: torch/mpi_ops.py
    'allreduce.noname.<handle>' naming)."""
    n = _noname_counters.get(kind, 0) + 1
    _noname_counters[kind] = n
    return f"{kind}.noname.{n}"


def _socket_world(st) -> bool:
    """True when this process is one rank of a multi-process world whose
    data plane is the enqueue runtime (the world is larger than the local
    mesh and jax.distributed isn't forming a global mesh) — a plain local
    array must NOT be treated as replicated there."""
    return st.size > st.mesh.size and jax.process_count() == 1


def _multiprocess_world(st) -> bool:
    """True in ANY multi-process world — socket mode or multi-controller
    jax.distributed. A plain local array is per-process data there and an
    eager collective on it must really communicate."""
    return st.size > st.mesh.size or jax.process_count() > 1


def _runtime_capable(st) -> bool:
    """True when the enqueue runtime has (or will build) a multi-process
    controller to exchange per-process data — the launcher env contract is
    present, or socket mode is active. Eager per-process collectives are
    then routed through the runtime: its background thread is the single
    issuer of dynamically-timed collective programs, so dispatch order is
    the coordinator-agreed order on every rank; issuing directly from the
    caller thread would interleave differently per rank against in-flight
    runtime programs — a distributed program mismatch (the exact hazard
    the reference's single-background-thread architecture prevents,
    reference: operations.cc:281-300).

    Without the launcher contract (externally-initialized jax.distributed)
    the runtime would have no controller — routing would re-enter this
    path from the executor and hang — so callers fall back to a direct
    global-mesh exchange on the caller thread instead."""
    import os

    return _socket_world(st) or (jax.process_count() > 1
                                 and "HOROVOD_RANK" in os.environ)


def _process_local_stacked(x, st) -> jax.Array:
    """Lift one process-local value into the worker-stacked global layout:
    each of this process's devices contributes the process's value, so
    per-worker (= per-device) semantics stay consistent with the
    single-controller replicated model. Multi-controller only — the
    direct-exchange fallback for worlds without a launcher-provided
    controller (see _runtime_capable)."""
    local = np.broadcast_to(
        np.asarray(x)[None], (st.local_size,) + np.shape(x)).copy()
    return jax.make_array_from_process_local_data(
        mesh_mod.worker_sharding(st.mesh), local)


def _is_globally_replicated(x, st) -> bool:
    """True when ``x`` is a jax.Array already replicated across the WHOLE
    mesh — the only case where "every worker holds this value" is a fact
    rather than an assumption in a multi-controller world."""
    return (isinstance(x, jax.Array) and x.sharding.is_fully_replicated
            and len(x.sharding.device_set) == st.size)


def _reduce_stacked_fn(mesh, op: int):
    """Compiled: stacked (W, *S) -> reduced (*S), replicated everywhere.

    The axis-0 reduction over a worker-sharded array compiles to an XLA
    all-reduce over ICI, exactly the role of ``MPI_Allreduce``/
    ``ncclAllReduce`` in the reference (reference: ops/mpi_operations.cc:48,
    ops/nccl_operations.cc:86-90).
    """

    def build():
        def f(x):
            if op == Average:
                return jnp.mean(x, axis=0)
            if op == Sum:
                return jnp.sum(x, axis=0)
            if op == Min:
                return jnp.min(x, axis=0)
            if op == Max:
                return jnp.max(x, axis=0)
            if op == Product:
                return jnp.prod(x, axis=0)
            raise ValueError(f"unknown op {op}")

        return jax.jit(f, out_shardings=_replicated(mesh))

    return _cached(("reduce_stacked", mesh, op), build)


def two_level_reduce_block(v, local: int, world: int, average: bool):
    """Shared RS→AR→AG body for two-level allreduce, called inside a
    shard_map block with a flat per-device vector ``v``: reduce-scatter
    over ``local`` (ICI), allreduce over ``cross`` (DCN — 1/local of the
    bytes), allgather over ``local`` (reference:
    NCCLHierarchicalAllreduce, ops/nccl_operations.cc:150-346). Used by
    both the eager stacked path and the executor's fused program."""
    n = v.shape[0]
    pad = (-n) % local
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    s = lax.psum_scatter(v, mesh_mod.LOCAL_AXIS, scatter_dimension=0,
                         tiled=True)           # ICI: (n/local,)
    s = lax.psum(s, mesh_mod.CROSS_AXIS)       # DCN: 1/local bytes
    g = lax.all_gather(s, mesh_mod.LOCAL_AXIS, axis=0,
                       tiled=True)             # ICI: (n,)
    if average:
        g = g / world
    return g[:n]


def _hierarchical_reduce_stacked_fn(mesh, op: int):
    """Two-level allreduce over a stacked (W, *S) array (knob common.h:75).
    Only SUM/AVERAGE decompose this way (the reference's hierarchical path
    is likewise sum-only); other ops use the flat program."""

    def build():
        cross, local = mesh.devices.shape
        world = cross * local

        def inner(x):
            # per-device block (1, *S) of the stacked (W, *S) input
            return two_level_reduce_block(
                x[0].reshape(-1), local, world, average=(op == Average))

        def f(x):
            out = jax.shard_map(
                inner, mesh=mesh,
                in_specs=P(mesh_mod.GLOBAL_AXES),
                out_specs=P(), check_vma=False)(x)
            return out.reshape(x.shape[1:])

        return jax.jit(f, out_shardings=_replicated(mesh))

    return _cached(("hier_reduce_stacked", mesh, op), build)


def _hierarchical_gather_stacked_fn(mesh):
    """Two-level allgather: gather over ``local`` then over ``cross``
    (reference: MPIHierarchicalAllgather's node-then-cross structure,
    ops/mpi_operations.cc:168-314; knob common.h:76)."""

    def build():
        def inner(x):
            # block (1, s0, *S) -> full (W*s0, *S) on every device
            g = lax.all_gather(x[0], mesh_mod.LOCAL_AXIS, axis=0, tiled=True)
            g = lax.all_gather(g, mesh_mod.CROSS_AXIS, axis=0, tiled=True)
            return g

        def f(x):
            return jax.shard_map(
                inner, mesh=mesh,
                in_specs=P(mesh_mod.GLOBAL_AXES),
                out_specs=P(), check_vma=False)(x)

        return jax.jit(f, out_shardings=_replicated(mesh))

    return _cached(("hier_gather_stacked", mesh), build)


def _hierarchical_enabled(st, op: Optional[int] = None) -> bool:
    """Hierarchical path applies when configured and the mesh actually has
    two levels (reference gates on hierarchical params + homogeneity,
    nccl_operations.cc:348-355)."""
    cross, local = st.mesh.devices.shape
    if cross <= 1 or local <= 1:
        return False
    return op is None or op in (Sum, Average)


def _bcast_stacked_fn(mesh, root: int):
    def build():
        return jax.jit(
            lambda x: lax.index_in_dim(x, root, axis=0, keepdims=False),
            out_shardings=_replicated(mesh),
        )

    return _cached(("bcast_stacked", mesh, root), build)


def _gather_stacked_fn(mesh):
    def build():
        def f(x):
            # (W, s0, *S) -> (W*s0, *S): Horovod allgather concatenates
            # along the first dimension (reference: ops/mpi_operations.cc:83).
            return jnp.reshape(x, (x.shape[0] * x.shape[1],) + x.shape[2:])

        return jax.jit(f, out_shardings=_replicated(mesh))

    return _cached(("gather_stacked", mesh), build)


def _alltoall_stacked_fn(mesh, world: int):
    def build():
        def f(x):
            # (W, m, *S), m = world*k: worker i's j-th chunk goes to worker j.
            w, m = x.shape[0], x.shape[1]
            k = m // world
            y = jnp.reshape(x, (w, world, k) + x.shape[2:])
            y = jnp.swapaxes(y, 0, 1)
            return jnp.reshape(y, (w, m) + x.shape[2:])

        return jax.jit(f, out_shardings=mesh_mod.worker_sharding(mesh))

    return _cached(("alltoall_stacked", mesh, world), build)


def _reducescatter_stacked_fn(mesh, op: int, world: int):
    def build():
        def f(x):
            # (W, m, *S) -> reduce over W, scatter m into W shards:
            # output stacked (W, m/W, *S), worker i owning shard i.
            if op in (Average, Sum):
                r = jnp.sum(x, axis=0)
                if op == Average:
                    r = r / x.shape[0]
            elif op == Min:
                r = jnp.min(x, axis=0)
            elif op == Max:
                r = jnp.max(x, axis=0)
            elif op == Product:
                r = jnp.prod(x, axis=0)
            else:
                raise ValueError(f"unknown op {op}")
            return jnp.reshape(r, (world, r.shape[0] // world) + r.shape[1:])

        return jax.jit(f, out_shardings=mesh_mod.worker_sharding(mesh))

    return _cached(("rs_stacked", mesh, op, world), build)


def _integrity_check_stacked(x, name: str) -> None:
    """Eager worker-stacked payload digest: per-row non-finite counts
    name the contributing worker (row == rank) BEFORE the reduction
    collapses attribution. Tiny jnp ops cached by shape in jax's own
    executable cache; gated to every HOROVOD_INTEGRITY_INTERVAL calls
    per lane, no-op when HOROVOD_INTEGRITY is off."""
    from horovod_tpu.integrity import digest as integ_digest

    if np.dtype(x.dtype).kind not in ("f", "V"):  # V: ml_dtypes bf16
        return
    if not integ_digest.cadence_due(f"eager.{name}"):
        return
    counts = np.asarray(jnp.sum(
        ~jnp.isfinite(jnp.reshape(x, (x.shape[0], -1))), axis=1,
        dtype=jnp.int32))
    bad = np.nonzero(counts)[0]
    integ_digest.verify_local(
        int(counts.sum()), bucket="eager", tensor=name,
        suspect_rank=int(bad[0]) if bad.size else None)


# ---------------------------------------------------------------------------
# Public collectives
# ---------------------------------------------------------------------------

def allreduce(
    tensor,
    average: Optional[bool] = None,
    name: Optional[str] = None,
    op: Optional[int] = None,
    compression=Compression.none,
    axis_name=None,
):
    """Reduce a tensor across all workers; every worker gets the result.

    * In-jit (tracer input): emits ``lax.psum``/``pmean`` over the mesh axes
      — use under ``shard_map`` with the global mesh.
    * Eager: stacked ``(size, *shape)`` input reduces axis 0; a replicated
      input is treated as identical on every worker.

    reference: horovod/torch/mpi_ops.py:126-180 (API), ops chain
    horovod/common/ops/*_operations.cc (execution).
    """
    red_op = _resolve_op(average, op)
    tensor_c, ctx = compression.compress(tensor)

    if _is_tracer(tensor_c):
        axes = _global_axes(axis_name)
        if red_op == Average:
            out = lax.pmean(tensor_c, axes)
        elif red_op == Sum:
            out = lax.psum(tensor_c, axes)
        elif red_op == Min:
            out = lax.pmin(tensor_c, axes)
        elif red_op == Max:
            out = lax.pmax(tensor_c, axes)
        elif red_op == Product:
            if jnp.issubdtype(tensor_c.dtype, jnp.integer):
                # exact integer product: gather then multiply — the fp32
                # log-sum-exp round trip is off by whole units once the
                # product exceeds 2^24 (MPI_PROD is exact). The gathered
                # result is device-varying to shard_map's replication
                # checker, so re-broadcast it with a masked psum (device
                # 0's exact value) to make replication static.
                axes_t = tuple(axes) if isinstance(axes, (tuple, list)) \
                    else (axes,)
                gathered = lax.all_gather(tensor_c, axes_t)
                prod = jnp.prod(gathered, axis=0)
                flat_index = lax.axis_index(axes_t)
                out = lax.psum(
                    jnp.where(flat_index == 0, prod, jnp.zeros_like(prod)),
                    axes_t)
            else:
                # Sign/zero-correct log-sum-exp product: exp(psum(log|x|))
                # NaN-poisons on negatives and mishandles zeros, so track
                # sign parity and zero presence through separate psums
                # (all outputs statically replicated, unlike gather+prod).
                xf = tensor_c
                magnitude = jnp.exp(lax.psum(
                    jnp.log(jnp.where(xf == 0, 1.0, jnp.abs(xf))), axes))
                neg_parity = lax.psum((xf < 0).astype(jnp.int32), axes) % 2
                any_zero = lax.psum((xf == 0).astype(jnp.int32), axes) > 0
                signed = jnp.where(neg_parity == 1, -magnitude, magnitude)
                out = jnp.where(any_zero, jnp.zeros_like(signed), signed)
        else:
            raise ValueError(f"unknown op {red_op}")
        return compression.decompress(out, ctx)

    st = basics._ensure_init()
    x = _to_plane(tensor_c)
    if _is_worker_stacked(x):
        _integrity_check_stacked(x, name or "allreduce")
        if (st.config.hierarchical_allreduce
                and _hierarchical_enabled(st, red_op)):
            out = _op_event(
                "allreduce", st, x,
                lambda: _hierarchical_reduce_stacked_fn(st.mesh, red_op)(x),
                name=name)
        else:
            out = _op_event(
                "allreduce", st, x,
                lambda: _reduce_stacked_fn(st.mesh, red_op)(x),
                name=name)
    elif _multiprocess_world(st) and not _is_globally_replicated(x, st):
        # Multi-process world with a plain local array: the data lives
        # per-rank, so "replicated" math would silently return a
        # local-only result — route through the named enqueue runtime
        # (auto call-order name, like the reference's unnamed torch ops),
        # whose background thread is the single ordered issuer of
        # collective programs (see _runtime_capable).
        if _runtime_capable(st):
            return synchronize(allreduce_async(
                tensor, average=average, op=op, compression=compression,
                name=name or _auto_name("allreduce")))
        # no controller (externally-initialized jax.distributed):
        # direct global-mesh exchange on the caller thread
        stacked = _process_local_stacked(x, st)
        if (st.config.hierarchical_allreduce
                and _hierarchical_enabled(st, red_op)):
            out = _hierarchical_reduce_stacked_fn(st.mesh, red_op)(stacked)
        else:
            out = _reduce_stacked_fn(st.mesh, red_op)(stacked)
    else:
        # Replicated: every worker holds the same value.
        if red_op in (Average, Min, Max):
            # never alias the caller's buffer: for 64-bit numpy inputs
            # _to_plane is the identity, and returning the input object
            # would let later in-place mutation corrupt the "result"
            out = np.array(x, copy=True) \
                if not isinstance(x, jax.Array) else x
        elif red_op == Sum:
            out = x * st.size
        elif red_op == Product:
            out = x ** st.size
        else:
            raise ValueError(f"unknown op {red_op}")
    return compression.decompress(out, ctx)


def grouped_allreduce(
    tensors: Sequence,
    average: Optional[bool] = None,
    name: Optional[str] = None,
    op: Optional[int] = None,
    compression=Compression.none,
    axis_name=None,
):
    """Allreduce a list of tensors as one logical operation (the analogue
    of the reference's explicitly grouped fusion).

    In-jit, XLA fuses the psums. Eager worker-stacked inputs of the same
    dtype genuinely share one dispatch: they are flattened, concatenated
    and reduced as one program, then split back. Everything else (plain
    arrays, mixed cases) falls through to individual allreduce — in the
    multi-process socket world those ride the runtime, whose tensor
    fusion batches them anyway."""
    tensors = list(tensors)
    if not tensors:
        return []
    if _is_tracer(tensors[0]):
        return [allreduce(t, average=average, op=op, compression=compression,
                          axis_name=axis_name) for t in tensors]

    st = basics._ensure_init()
    arrays = [_to_plane(t) for t in tensors]
    out: list = [None] * len(arrays)
    groups: dict = {}
    plain: list = []
    for i, a in enumerate(arrays):
        if _is_worker_stacked(a) and a.ndim >= 1:
            groups.setdefault(str(a.dtype), []).append(i)
        else:
            plain.append(i)
    if plain and _multiprocess_world(st) and _runtime_capable(st):
        # multi-process: enqueue every per-process plain tensor first so
        # they are all in flight in the same cycle — the runtime's tensor
        # fusion then batches them, matching the reference's grouped
        # guarantee. Globally replicated tensors skip the round trip (and
        # keep min/max/product working), same as single allreduce.
        handles = []
        for i in plain:
            if _is_globally_replicated(arrays[i], st):
                out[i] = allreduce(arrays[i], average=average, op=op,
                                   compression=compression,
                                   axis_name=axis_name)
            else:
                handles.append((i, allreduce_async(
                    tensors[i], average=average, op=op,
                    compression=compression,
                    name=_auto_name("grouped_allreduce"))))
        for i, h in handles:
            out[i] = synchronize(h)
    else:
        for i in plain:
            out[i] = allreduce(tensors[i], average=average, op=op,
                               compression=compression, axis_name=axis_name)
    for idxs in groups.values():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = allreduce(arrays[i], average=average, op=op,
                               compression=compression, axis_name=axis_name)
            continue
        world = arrays[idxs[0]].shape[0]
        flat = [arrays[i].reshape(world, -1) for i in idxs]
        fused = allreduce(jnp.concatenate(flat, axis=1), average=average,
                          op=op, compression=compression,
                          axis_name=axis_name)
        offset = 0
        for i, f in zip(idxs, flat):
            n = f.shape[1]
            out[i] = fused[offset:offset + n].reshape(arrays[i].shape[1:])
            offset += n
    return out


def _op_event(op: str, st, x, fn, name: Optional[str] = None):
    """Bracket an eager single-controller collective dispatch with
    flight-recorder ``op_dispatch``/``op_complete`` events (shard index +
    bytes) and a ``collective:<name>`` tracing span, mirroring the
    executor's events on the multi-process path — postmortems attribute a
    stalled sharded step to the right phase, and eager collectives land on
    the same Perfetto lane as the enqueue runtime's (tracing.py)."""
    nbytes = int(np.prod(np.shape(x), dtype=np.int64)
                 * np.dtype(x.dtype).itemsize)
    flight_recorder.emit("op_dispatch", op=op, shard=int(st.rank),
                         bytes=nbytes)
    t0 = time.monotonic()
    t0_epoch = time.time()
    out = fn()
    total = time.monotonic() - t0
    flight_recorder.emit("op_complete", op=op, shard=int(st.rank),
                         bytes=nbytes, seconds=round(total, 6))
    # comms plane: eager single-controller collectives ride the fused
    # XLA "device" lane (docs/comms.md, the lanes)
    comms.record(op, "device", nbytes, total, world=int(st.size))
    if tracing.enabled():
        tracing.record("collective:" + str(name or op), t0_epoch, total,
                       op=op, bytes=nbytes)
    return out


def allgather(tensor, name: Optional[str] = None, axis_name=None):
    """Concatenate each worker's tensor along axis 0; all workers get the
    concatenation.

    Eager stacked input ``(size, s0, *S)`` yields ``(size*s0, *S)``. Ragged
    first dimensions (the reference supports per-rank sizes via negotiated
    recvcounts, reference: ops/collective_operations.cc:87-127) are passed
    as a Python list of per-worker arrays.
    """
    if _is_tracer(tensor):
        return lax.all_gather(tensor, _global_axes(axis_name), axis=0, tiled=True)

    st = basics._ensure_init()
    if isinstance(tensor, (list, tuple)):
        if len(tensor) != st.size:
            raise ValueError(
                f"ragged allgather needs one tensor per worker ({st.size}), "
                f"got {len(tensor)}"
            )
        shapes = {tuple(np.shape(t)[1:]) for t in tensor}
        if len(shapes) > 1:
            # reference: coordinator shape validation raises on mismatched
            # non-first dimensions (controller.cc:320-522).
            raise ValueError(
                f"allgather tensors must match in all but the first "
                f"dimension, got trailing shapes {sorted(shapes)}"
            )
        parts = [_to_plane(t) for t in tensor]
        if any(not isinstance(p, jax.Array) for p in parts):
            # 64-bit payload: concat exactly on host (see _to_plane)
            return np.concatenate([np.asarray(p) for p in parts], axis=0)
        out = jnp.concatenate(parts, axis=0)
        return jax.device_put(out, _replicated(st.mesh))

    x = _to_plane(tensor)
    if _is_worker_stacked(x):
        if x.ndim < 2:
            raise ValueError(
                "allgather concatenates along dim 0, so per-worker tensors "
                "must have rank >= 1 (stacked input rank >= 2); got shape "
                f"{x.shape}"
            )
        if (st.config.hierarchical_allgather
                and _hierarchical_enabled(st)):
            return _op_event(
                "allgather", st, x,
                lambda: _hierarchical_gather_stacked_fn(st.mesh)(x))
        return _op_event("allgather", st, x,
                         lambda: _gather_stacked_fn(st.mesh)(x))
    if x.ndim < 1:
        raise ValueError("allgather requires tensors of rank >= 1")
    if _multiprocess_world(st) and not _is_globally_replicated(x, st):
        # Multi-process world: each rank holds its own tensor — ride the
        # enqueue runtime rather than faking the concat locally (and so
        # the background thread keeps collective-program order agreed).
        if _runtime_capable(st):
            return synchronize(allgather_async(
                tensor, name=name or _auto_name("allgather")))
        stacked = _process_local_stacked(x, st)
        if (st.config.hierarchical_allgather and _hierarchical_enabled(st)):
            return _hierarchical_gather_stacked_fn(st.mesh)(stacked)
        return _gather_stacked_fn(st.mesh)(stacked)
    # Replicated: every worker contributes the same tensor.
    if not isinstance(x, jax.Array):  # 64-bit numpy payload (_to_plane)
        return np.concatenate([x] * st.size, axis=0)
    return jnp.concatenate([x] * st.size, axis=0)


def broadcast(tensor, root_rank: int, name: Optional[str] = None, axis_name=None):
    """Every worker receives worker ``root_rank``'s tensor.

    reference: horovod/torch/mpi_ops.py broadcast / ops/mpi_operations.cc:326.
    """
    if _is_tracer(tensor):
        # Masked psum: only the root contributes, and the psum output is
        # statically replicated over the mesh axes — one collective, no
        # gather+index. (The reference's MPI_Bcast analogue.)
        axes = _global_axes(axis_name)
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        flat_index = lax.axis_index(tuple(axes))
        masked = jnp.where(flat_index == root_rank, tensor,
                           jnp.zeros_like(tensor))
        # psum promotes bool to int32 — restore the input dtype so
        # jit/eager agree
        return lax.psum(masked, tuple(axes)).astype(tensor.dtype)

    st = basics._ensure_init()
    if not 0 <= root_rank < st.size:
        raise ValueError(f"root_rank {root_rank} out of range [0, {st.size})")
    x = _to_plane(tensor)
    if _is_worker_stacked(x):
        return _bcast_stacked_fn(st.mesh, root_rank)(x)
    if _multiprocess_world(st) and not _is_globally_replicated(x, st):
        # Multi-process world: the root's value must actually travel (the
        # reference's MPI_Bcast role in checkpoint restore,
        # torch/__init__.py:255-403) — through the runtime so the
        # background thread keeps collective-program order agreed.
        if _runtime_capable(st):
            return synchronize(broadcast_async(
                tensor, root_rank, name=name or _auto_name("broadcast")))
        return _bcast_stacked_fn(st.mesh, root_rank)(
            _process_local_stacked(x, st))
    # Single-controller: values are already globally consistent; force the
    # replicated layout over the mesh so downstream steps see it.
    if not isinstance(x, jax.Array):  # 64-bit numpy payload (_to_plane)
        return np.array(x, copy=True)
    return jax.device_put(x, _replicated(st.mesh))


def reducescatter(tensor, average: Optional[bool] = None, op: Optional[int] = None,
                  axis_name=None):
    """Reduce across workers and scatter the result: worker i gets shard i
    of the reduced tensor (TPU extension; the building block of the
    hierarchical allreduce, reference: ops/nccl_operations.cc:150-346)."""
    red_op = _resolve_op(average, op)
    if _is_tracer(tensor):
        axes = _global_axes(axis_name)
        if red_op in (Average, Sum):
            out = lax.psum_scatter(tensor, axes, scatter_dimension=0,
                                   tiled=True)
            if red_op == Average:
                # divide by the size of the axes actually reduced, not
                # the global world size (they differ for axis_name='local')
                out = out / lax.axis_size(axes)
            return out
        # XLA's reduce-scatter primitive is sum-only; min/max/product
        # decompose into all_to_all + local reduce — same bytes on the
        # wire as a reduce-scatter (each device sends shard j to owner j)
        world = lax.axis_size(axes)
        if tensor.shape[0] % world != 0:
            raise ValueError(
                f"reducescatter dim 0 ({tensor.shape[0]}) must divide "
                f"evenly by the axis size ({world})")
        xr = tensor.reshape((world, tensor.shape[0] // world)
                            + tensor.shape[1:])
        got = lax.all_to_all(xr, axes, split_axis=0, concat_axis=0)
        reducer = {Min: jnp.min, Max: jnp.max, Product: jnp.prod}[red_op]
        return reducer(got, axis=0)

    st = basics._ensure_init()
    x = _to_plane(tensor)
    if not _is_worker_stacked(x):
        if _multiprocess_world(st) and _runtime_capable(st):
            # per-process data: route through the runtime lane like
            # allreduce (each rank contributes its local tensor, receives
            # its shard of the reduction)
            from horovod_tpu.runtime.runtime import get_runtime

            return synchronize(get_runtime().enqueue_reducescatter(
                _auto_name("reducescatter"), x,
                reduce_op=_OP_NAMES[red_op]))
        raise ValueError("eager reducescatter requires stacked per-worker input")
    if x.ndim < 2:
        raise ValueError(
            "reducescatter scatters along dim 0 of per-worker tensors, so "
            f"stacked input must have rank >= 2; got shape {x.shape}"
        )
    if x.shape[1] % st.size != 0:
        raise ValueError(
            f"reducescatter dim 1 ({x.shape[1]}) must divide evenly by "
            f"size ({st.size})"
        )
    return _op_event(
        "reducescatter", st, x,
        lambda: _reducescatter_stacked_fn(st.mesh, red_op, st.size)(x))


def alltoall(tensor, name: Optional[str] = None, axis_name=None):
    """Each worker splits its tensor into ``size`` chunks along axis 0 and
    sends chunk j to worker j (TPU extension; enables Ulysses-style sequence
    parallelism)."""
    if _is_tracer(tensor):
        return lax.all_to_all(
            tensor, _global_axes(axis_name), split_axis=0, concat_axis=0,
            tiled=True,
        )

    st = basics._ensure_init()
    x = _to_plane(tensor)
    if not _is_worker_stacked(x):
        if _multiprocess_world(st) and _runtime_capable(st):
            from horovod_tpu.runtime.runtime import get_runtime

            return synchronize(get_runtime().enqueue_alltoall(
                name or _auto_name("alltoall"), x))
        raise ValueError("eager alltoall requires stacked per-worker input")
    if x.ndim < 2:
        raise ValueError(
            "alltoall splits along dim 0 of per-worker tensors, so stacked "
            f"input must have rank >= 2; got shape {x.shape}"
        )
    if x.shape[1] % st.size != 0:
        raise ValueError(
            f"alltoall dim 1 ({x.shape[1]}) must divide evenly by size "
            f"({st.size})"
        )
    return _alltoall_stacked_fn(st.mesh, st.size)(x)


# ---------------------------------------------------------------------------
# Async handles
# ---------------------------------------------------------------------------

class Handle:
    """Future for an async collective.

    XLA dispatch is already asynchronous — the returned ``jax.Array`` is a
    future whose buffer materializes when the collective completes on
    device. This class carries the reference's handle API on top
    (reference: horovod/torch/handle_manager.cc, mpi_ops.py:93-124). Unlike
    the reference there is no global handle table to leak: the handle owns
    its result and is garbage-collected with it.
    """

    __slots__ = ("_result",)

    def __init__(self, result):
        self._result = result

    def poll(self) -> bool:
        # Exceptions surface here, not swallowed: an error inside
        # is_ready() (e.g. a failed async computation) must reach the
        # caller that polled, not masquerade as "complete" and then raise
        # from an unrelated wait() later. Duck-typed on is_ready so
        # non-array leaves (python scalars in a result tree) pass through.
        leaves = jax.tree_util.tree_leaves(self._result)
        return all(
            leaf.is_ready() for leaf in leaves if hasattr(leaf, "is_ready")
        )

    def wait(self):
        return jax.block_until_ready(self._result)


def allreduce_async(tensor, average=None, name=None, op=None,
                    compression=Compression.none, priority=0):
    """Async allreduce. With a ``name``, the tensor enters the dynamic
    enqueue runtime — per-tensor negotiation, response cache and tensor
    fusion, the reference's core execution model (reference:
    operations.cc:736-768 EnqueueTensorAllreduce). Unnamed tensors dispatch
    immediately (XLA's async dispatch already overlaps). ``priority``
    orders runtime tensors within a cycle, highest first (reference:
    horovod/mxnet/mpi_ops.py:52)."""
    if name is not None:
        red_op = _resolve_op(average, op)
        from horovod_tpu.runtime.runtime import get_runtime

        x, ctx = compression.compress(
            _to_plane(tensor))
        handle = get_runtime().enqueue_allreduce(
            name, x, reduce_op=_OP_NAMES[red_op], priority=priority)
        handle._decompress = (compression, ctx)  # applied in synchronize()
        return handle
    return Handle(allreduce(tensor, average=average, op=op,
                            compression=compression))


def grouped_allreduce_async(tensors, names, average=None, op=None,
                            reduce_op=None, priority=0,
                            group_callback=None):
    """Async grouped allreduce through the runtime: the whole group is
    enqueued atomically (``Runtime.enqueue_allreduce_group``) so one
    negotiation cycle sees it and the fusion planner packs it into as few
    dispatches as ``HOROVOD_FUSION_THRESHOLD`` allows. This is the wire
    primitive behind bucket-wise gradient release
    (:class:`horovod_tpu.parallel.buckets.GradReleasePlan`): each bucket
    becomes one grouped enqueue, released while backward is still
    running. Returns one handle per tensor, in order; ``group_callback``
    fires on the cycle thread per completion (see the runtime method)."""
    tensors = list(tensors)
    names = list(names)
    if len(tensors) != len(names):
        raise ValueError("tensors and names must pair up")
    if not tensors:
        return []
    if reduce_op is None:
        red_op = _resolve_op(average, op)
        reduce_op = _OP_NAMES[red_op]
    elif average is not None or op is not None:
        raise ValueError("specify reduce_op or average/op, not both")
    from horovod_tpu.runtime.runtime import get_runtime

    return get_runtime().enqueue_allreduce_group(
        names, [_to_plane(t) for t in tensors], reduce_op=reduce_op,
        priority=priority, group_callback=group_callback)


def allgather_async(tensor, name=None, priority=0):
    if name is not None:
        from horovod_tpu.runtime.runtime import get_runtime

        return get_runtime().enqueue_allgather(
            name, _to_plane(tensor), priority=priority)
    return Handle(allgather(tensor))


def broadcast_async(tensor, root_rank, name=None, priority=0):
    if name is not None:
        from horovod_tpu.runtime.runtime import get_runtime

        return get_runtime().enqueue_broadcast(
            name, _to_plane(tensor), root_rank, priority=priority)
    return Handle(broadcast(tensor, root_rank))


def poll(handle: Handle) -> bool:
    """True if the collective backing ``handle`` has completed
    (reference: horovod/torch/mpi_ops.py:93-105)."""
    return handle.poll()


def synchronize(handle):
    """Block until the collective completes and return its result
    (reference: horovod/torch/mpi_ops.py:107-124). Accepts both immediate
    handles and runtime handles."""
    out = handle.wait()
    decompress = getattr(handle, "_decompress", None)
    if decompress is not None and out is not None:
        compression, ctx = decompress
        out = compression.decompress(out, ctx)
    return out
