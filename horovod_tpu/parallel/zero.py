"""ZeRO-1/2/3 sharded training over the Horovod data plane.

Horovod's data-parallel contract replicates optimizer state on every
worker. ZeRO stage-1 (Rajbhandari et al., 2020) keeps the same contract
— allreduced gradients into a wrapped optimizer — while sharding the
optimizer state 1/N ways, by decomposing the allreduce into

    reduce-scatter  ->  update on the local shard  ->  allgather

Same bytes on the wire as an allreduce (a ring allreduce IS a
reduce-scatter followed by an allgather), but each chip touches only
1/N of the optimizer state per step and holds only 1/N of it in HBM.

Stages 2 and 3 drop the "same bytes" part:

* **Stage 2** — gradients live only as the local 1/N shard.
  :func:`scatter_gradients` (or ``GradReleasePlan(reduce_scatter=True)``
  bucket-by-bucket during backprop) produces a :class:`ShardedGrads`,
  and the update functions consume it directly, skipping their internal
  reduce-scatter. A reduce-scatter moves (N-1)/N bytes per payload byte
  where an allreduce moves 2(N-1)/N — gradient wire bytes per step are
  halved (visible as busbw on the ``zero``/``bucket_wire`` comms
  lanes), and gradient HBM drops to 1/N (``grad_shards`` in the memory
  ledger).

* **Stage 3** — parameters are sharded at rest (:class:`ShardedParams`,
  built by :func:`shard_params`) and gathered on demand bucket-by-bucket
  (:func:`iter_param_buckets` / :func:`gather_params`): group k+1's
  allgather is dispatched while group k is being consumed, with the
  in-flight window bounded by ``HOROVOD_ZERO_PREFETCH_BUCKETS``.
  ``sharded_adamw.apply`` given ``ShardedParams`` updates the shards in
  place of the full tree and returns a new ``ShardedParams`` — no
  trailing param allgather at all; the forward pass re-gathers under
  compute. Gather stalls are charged to the goodput tracker's
  ``exposed_comm`` category, and the hidden (overlapped) fraction is
  exported as ``horovod_zero_gather_hidden_fraction``.

``HOROVOD_ZERO_STAGE`` selects the stage for the stock training-step
wiring (:func:`stage_from_env`); the functional API above works at any
stage explicitly.

The gradient pytree is flattened into one flat buffer per dtype group
(reusing the PR-3 size-bucket policy: per-rank shard lengths are padded
up to ``bucket_elems`` of ``HOROVOD_FUSION_BUCKET_QUANTUM``, so shard
boundaries land on even per-rank splits AND every step reuses the same
O(#buckets) compiled programs — zero new compiles after warmup). The pad
region holds zeros, the reduction identity for sum/average, and is
sliced off before unpacking, so padded results bit-match unpadded ones.

Two entry points:

* :func:`sharded_update` — wraps any *elementwise* optax transformation
  (sgd, adam, adamw, lamb, ...) as an ``optax.GradientTransformation``
  whose state lives on shards. It keeps the optax delta contract: the
  inner update runs on gradient/param *shards* and the resulting update
  deltas are allgathered back into the original pytree, so
  ``optax.apply_updates(params, updates)`` computes ``p + delta`` with
  the exact same bits as the replicated path (elementwise inner
  transforms only; global-norm clipping must run *before* the wrapper).
  This is what ``hvd.DistributedOptimizer(...,
  shard_optimizer_states=True)`` returns.

* :func:`sharded_adamw` — step-level fused AdamW
  (``opt.apply(params, state, grads)``) keeping flat fp32 master
  weights + moments in the local shard and emitting updated params in
  the parameter dtype (bf16 master-weight training). Step-level because
  the delta contract would break fp32-master semantics: in bf16,
  ``p + (cast(master') - p) != cast(master')``. The per-shard pass runs
  as one fused Pallas kernel
  (:mod:`horovod_tpu.ops.pallas.fused_optimizer`) on TPU local shards,
  gated by ``HOROVOD_SHARDED_FUSED_KERNEL``.

Three call modes, mirroring :mod:`horovod_tpu.ops.collectives`:

* **In-jit under ``shard_map``** — ``lax.psum_scatter`` /
  ``lax.all_gather`` over the bound mesh axes; the local shard is this
  device's slice at ``lax.axis_index``.
* **Eager single-controller** — cached jitted programs over the global
  mesh: pack+reduce-scatter (stacked ``(W, shard)`` output,
  worker-sharded), update, allgather+unpack. Gradient leaves must be
  uniformly worker-stacked or uniformly replicated.
* **Eager multi-process** — host-packed flat buffers ride the enqueue
  runtime's named lanes (``sharded.grads.g<i>`` /
  ``sharded.params.g<i>``), so negotiation, the response cache and the
  timeline see stable per-phase tensor names.

``Compression`` composes on the wire: the flat gradient buffer is
compressed before the reduce-scatter and decompressed on the shard.

Elastic integration: a sharded state snapshot holds only the local
shard (1/N of the bytes per commit); on a membership reform
``elastic.ArrayState.sync`` detects sharded leaves and calls
:func:`resync` instead of broadcasting them (a broadcast would clobber
the distinct per-rank shards).
"""

from __future__ import annotations

import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import comms, flight_recorder
from horovod_tpu.compression import Compression
from horovod_tpu.core import basics, mesh as mesh_mod
from horovod_tpu.metrics import LATENCY_BUCKETS, registry as _metrics
from horovod_tpu.ops import collectives
from horovod_tpu.ops.pallas import fused_optimizer as fused_mod
from horovod_tpu.parallel import sparse as sparse_mod
from horovod_tpu.runtime.fusion_buffer import bucket_elems
from horovod_tpu.utils import env as env_mod

_UPDATES = _metrics().counter(
    "horovod_sharded_updates_total",
    "Sharded (ZeRO-1) optimizer updates applied.")
_UPDATE_SECONDS = _metrics().histogram(
    "horovod_sharded_update_seconds",
    "Wall time of one sharded optimizer update (reduce-scatter + shard "
    "update + allgather).", buckets=LATENCY_BUCKETS)
_STATE_BYTES = _metrics().gauge(
    "horovod_sharded_state_bytes",
    "Optimizer-state bytes resident per chip under sharding (~1/N of "
    "the replicated footprint).")
_RS_BYTES = _metrics().counter(
    "horovod_sharded_reducescatter_bytes_total",
    "Flat gradient bytes entering the sharded reduce-scatter phase.")
_AG_BYTES = _metrics().counter(
    "horovod_sharded_allgather_bytes_total",
    "Flat update/param bytes entering the sharded allgather phase.")
_PROGRAM_BUILDS = _metrics().counter(
    "horovod_sharded_program_builds_total",
    "Compiled sharded-step programs built (steady state goes flat: "
    "bucket-stable shapes mean zero new compiles after warmup).")
_GATHER_STALL_SECONDS = _metrics().counter(
    "horovod_zero_gather_stall_seconds_total",
    "Wall seconds the consumer was blocked waiting on a stage-3 "
    "parameter allgather (exposed communication).")
_GATHER_HIDDEN_SECONDS = _metrics().counter(
    "horovod_zero_gather_hidden_seconds_total",
    "Wall seconds of stage-3 parameter allgather transfer overlapped "
    "under consumer compute (hidden communication).")
_GATHER_HIDDEN_FRACTION = _metrics().gauge(
    "horovod_zero_gather_hidden_fraction",
    "Cumulative fraction of stage-3 gather transfer time hidden under "
    "compute: hidden / (hidden + stalled).")


# ---------------------------------------------------------------------------
# Stage selection + stage-3 prefetch window knobs
# ---------------------------------------------------------------------------

HOROVOD_ZERO_STAGE = "HOROVOD_ZERO_STAGE"
HOROVOD_ZERO_PREFETCH_BUCKETS = "HOROVOD_ZERO_PREFETCH_BUCKETS"
DEFAULT_ZERO_PREFETCH_BUCKETS = 2

_autotuned_prefetch_buckets = 0


def stage_from_env() -> int:
    """ZeRO stage for the stock wiring: 1 (optimizer state only, the
    default), 2 (+ gradient shards via reduce-scatter release), 3
    (+ params sharded at rest). Clamped to [1, 3]."""
    raw = env_mod._get_int(HOROVOD_ZERO_STAGE, 1)
    return max(1, min(3, raw))


def set_autotuned_prefetch_buckets(n: int) -> None:
    """Autotuner commit hook: override the stage-3 prefetch window
    (``parameter_manager`` sweeps ``zero_prefetch_buckets`` alongside
    bucket bytes and pipeline depth). 0 clears the override."""
    global _autotuned_prefetch_buckets
    _autotuned_prefetch_buckets = max(0, int(n))


def prefetch_buckets_from_env() -> int:
    """Stage-3 prefetch window: how many group allgathers may be in
    flight ahead of the consumer (bounds transient HBM to roughly
    window x group bytes). Autotuned value wins over the env knob."""
    if _autotuned_prefetch_buckets > 0:
        return _autotuned_prefetch_buckets
    raw = env_mod._get_int(HOROVOD_ZERO_PREFETCH_BUCKETS,
                           DEFAULT_ZERO_PREFETCH_BUCKETS)
    return max(1, raw)


# ---------------------------------------------------------------------------
# Flat layout spec
# ---------------------------------------------------------------------------

class LeafMeta(NamedTuple):
    """Shape/dtype stand-in for a pytree leaf — enough for
    :func:`build_spec` to lay out a flat buffer without holding the
    (possibly freed) array itself."""

    shape: tuple
    dtype: Any


class GroupSpec(NamedTuple):
    """Flat layout of one same-dtype group of pytree leaves."""

    dtype: str        # np.dtype(...).str
    indices: tuple    # positions in the flattened leaf list
    shapes: tuple     # per-leaf shapes
    sizes: tuple      # per-leaf element counts
    n: int            # total real elements
    shard_elems: int  # per-rank shard length (bucket-padded)
    padded: int       # shard_elems * world


class ZeroSpec(NamedTuple):
    """Static description of a sharded flat layout. Registered as a
    static pytree node: it rides inside optimizer state without
    contributing leaves, so ``tree_map``/``jit``/``device_get`` all pass
    it through untouched (and jit caches key on it)."""

    groups: tuple     # of GroupSpec
    world: int
    rank: int         # -1 in traced (shard_map) mode: slice at axis_index
    num_leaves: int


jax.tree_util.register_static(ZeroSpec)


def _quantum_bytes(st) -> int:
    cfg = getattr(st, "config", None)
    return int(getattr(cfg, "fusion_bucket_quantum",
                       env_mod.DEFAULT_FUSION_BUCKET_QUANTUM_BYTES))


def build_spec(leaves, world: int, rank: int,
               quantum_bytes: int, *, partition=None) -> ZeroSpec:
    """Group ``leaves`` by dtype and lay each group out as one flat
    buffer whose per-rank shard is a PR-3 size bucket (identity at or
    under ``quantum_bytes``, next power-of-two multiple above), so the
    padded total splits evenly into ``world`` bucket-stable shards.

    ``partition`` — optional ordered list of leaf-index cells (e.g. a
    ``GradReleasePlan``'s reverse-topological buckets). Each cell
    becomes its own group (split by dtype if mixed), preserving cell
    order, so bucket-wise reduce-scatters and the optimizer's shard
    layout line up 1:1. Omitted leaves form no group."""
    cells = []
    if partition is None:
        by_dtype: dict = {}
        for i, leaf in enumerate(leaves):
            # .name, not .str: extension dtypes (bfloat16) stringify to
            # a raw void ('<V2') under .str and would not round-trip
            by_dtype.setdefault(np.dtype(leaf.dtype).name, []).append(i)
        cells = [(dts, by_dtype[dts]) for dts in sorted(by_dtype)]
    else:
        for cell in partition:
            by_dtype = {}
            for i in cell:
                by_dtype.setdefault(
                    np.dtype(leaves[i].dtype).name, []).append(i)
            cells.extend((dts, by_dtype[dts]) for dts in sorted(by_dtype))
    groups = []
    for dts, idxs in cells:
        dt = np.dtype(dts)
        shapes = tuple(tuple(leaves[i].shape) for i in idxs)
        sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
        n = int(sum(sizes))
        per = -(-n // world)  # ceil
        shard = bucket_elems(per, dt.itemsize, quantum_bytes)
        groups.append(GroupSpec(
            dtype=dts, indices=tuple(idxs), shapes=shapes, sizes=sizes,
            n=n, shard_elems=shard, padded=shard * world))
    return ZeroSpec(groups=tuple(groups), world=int(world),
                    rank=int(rank), num_leaves=len(leaves))


def _pack_group(leaves, g: GroupSpec):
    """Flatten group leaves into one (padded,) vector; the pad holds
    zeros — the sum/average reduction identity (fusion_buffer.py)."""
    parts = [jnp.reshape(leaves[i], (-1,)) for i in g.indices]
    pad = g.padded - g.n
    if pad:
        parts.append(jnp.zeros((pad,), np.dtype(g.dtype)))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _pack_group_stacked(leaves, g: GroupSpec, world: int):
    """Per-worker pack: stacked (W, *shape) leaves -> (W, padded)."""
    parts = [jnp.reshape(leaves[i], (world, -1)) for i in g.indices]
    pad = g.padded - g.n
    if pad:
        parts.append(jnp.zeros((world, pad), np.dtype(g.dtype)))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _np_pack_group(leaves, g: GroupSpec) -> np.ndarray:
    out = np.zeros((g.padded,), np.dtype(g.dtype))
    off = 0
    for i, size in zip(g.indices, g.sizes):
        out[off:off + size] = np.asarray(leaves[i]).reshape(-1)
        off += size
    return out


def _unpack_group(flat, g: GroupSpec, out: list) -> None:
    off = 0
    for i, shape, size in zip(g.indices, g.shapes, g.sizes):
        out[i] = jnp.reshape(flat[off:off + size], shape)
        off += size


def _bound_axes(axis_name=None) -> tuple:
    """Mesh axes bound in the current trace (empty outside shard_map)."""
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


def _check_dense(leaves) -> None:
    for leaf in leaves:
        if sparse_mod.is_sparse(leaf):
            raise ValueError(
                "shard_optimizer_states does not support SparseGrad "
                "leaves; pass sparse_as_dense=True (densify before the "
                "flat pack) or keep the replicated path for sparse "
                "models")


def _densify(leaves):
    return [sparse_mod.densify_leaf(g) if sparse_mod.is_sparse(g) else g
            for g in leaves]


def _mode(leaves, st) -> str:
    """'tracer' | 'local' (multi-process) | 'stacked' | 'replicated'."""
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return "tracer"
    if collectives._multiprocess_world(st):
        return "local"
    stacked = [collectives._is_worker_stacked(collectives._to_plane(x))
               for x in leaves]
    if all(stacked):
        return "stacked"
    if not any(stacked):
        return "replicated"
    raise ValueError(
        "sharded update needs gradient leaves to be uniformly "
        "worker-stacked or uniformly replicated, got a mix")


def _emit_phase(op: str, phase: str, shard: int, nbytes: int, fn):
    """Flight-recorder bracket for one sharded data-plane phase
    (satellite: postmortems attribute stalls inside a sharded step to
    the reduce-scatter vs allgather phase, with shard index + bytes)."""
    flight_recorder.emit("op_dispatch", op=op, phase=phase,
                         shard=int(shard), bytes=int(nbytes))
    t0 = time.monotonic()
    out = fn()
    seconds = time.monotonic() - t0
    flight_recorder.emit("op_complete", op=op, phase=phase,
                         shard=int(shard), bytes=int(nbytes),
                         seconds=round(seconds, 6))
    # comms plane: the ZeRO reduce-scatter/allgather phases get their own
    # "zero" lane — end-to-end sharded-phase bandwidth, next to the wire
    # lane the bytes physically rode (docs/comms.md)
    comms.record(op, "zero", nbytes, seconds)
    return out


def _set_state_bytes(inner_state, world: int) -> None:
    total = 0
    for leaf in jax.tree_util.tree_leaves(inner_state):
        if not hasattr(leaf, "shape"):
            continue
        nbytes = int(np.prod(leaf.shape, dtype=np.int64)
                     * np.dtype(leaf.dtype).itemsize)
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == world:
            nbytes //= world  # stacked (W, shard): 1/W lives per chip
        total += nbytes
    _STATE_BYTES.set(total)
    from horovod_tpu import memory

    memory.tracker().set_bytes("optimizer_shards", total)


def _set_shard_bytes(subsystem: str, shards, world: int) -> int:
    """Memory-ledger accounting for grad/param shards (PR-13 satellite:
    ``grad_shards`` / ``param_shards`` are first-class subsystems).
    Stacked (W, shard) single-controller arrays count 1/W per chip."""
    total = 0
    for leaf in shards:
        if not hasattr(leaf, "shape"):
            continue
        nbytes = int(np.prod(leaf.shape, dtype=np.int64)
                     * np.dtype(leaf.dtype).itemsize)
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == world:
            nbytes //= world
        total += nbytes
    from horovod_tpu import memory

    memory.tracker().set_bytes(subsystem, total)
    return total


_MODULE_PROGS: dict = {}


def _module_prog(key, builder):
    """Module-level cached-program table for the stage-2/3 functional
    API (scatter_gradients / shard_params / gather) — same
    zero-steady-state-compile contract as the per-optimizer closures."""
    fn = _MODULE_PROGS.get(key)
    if fn is None:
        _PROGRAM_BUILDS.inc()
        fn = builder()
        _MODULE_PROGS[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Stage 2: gradients as shards (reduce-scatter, no full-gradient buffer)
# ---------------------------------------------------------------------------

class ShardedGrads(NamedTuple):
    """Gradients living only as the local 1/N shard (ZeRO-2): one flat
    array per dtype group — ``(shard,)`` local in multi-process/traced
    mode, ``(W, shard)`` worker-sharded single-controller. Produced by
    :func:`scatter_gradients` or a reduce-scatter
    ``GradReleasePlan``; consumed directly by ``sharded_update`` /
    ``sharded_adamw.apply`` (which then skip their internal
    reduce-scatter)."""

    spec: ZeroSpec
    shards: tuple


def _check_shard_spec(got: ZeroSpec, want: ZeroSpec, what: str) -> None:
    if got.groups == want.groups and got.world == want.world:
        return
    raise ValueError(
        f"{what} layout does not match the sharded optimizer state — "
        "build both from the same partition (e.g. sharded_adamw(..., "
        "partition=plan.zero_partition(params)) next to a "
        "reduce-scatter GradReleasePlan), and re-init/resync after an "
        "elastic reform")


def scatter_bucket_group(values: dict, spec: ZeroSpec, gi: int, st, *,
                         average: bool, stacked: bool):
    """Single-controller reduce-scatter of one group's leaves (``values``
    maps leaf index -> array) into a worker-sharded ``(W, shard)`` flat
    array. Replicated inputs take the same short-circuit (and the same
    bits) as the replicated allreduce path; worker-stacked inputs
    reduce across the stack. Cached per (mesh, spec, group)."""
    g = spec.groups[gi]

    def build():
        def f(vals):
            dt = np.dtype(g.dtype)
            if stacked:
                flat = _pack_group_stacked(vals, g, spec.world)
                r = (jnp.mean(flat, axis=0) if average
                     else jnp.sum(flat, axis=0))
            else:
                flat = _pack_group(vals, g)
                r = flat if average else flat * spec.world
            return jnp.reshape(r.astype(dt), (spec.world, g.shard_elems))

        return jax.jit(f, out_shardings=mesh_mod.worker_sharding(st.mesh))

    key = ("zb2s", st.mesh, spec, gi, stacked, average)
    return _module_prog(key, build)(values)


def scatter_gradients(grads, *, spec: ZeroSpec = None,
                      average: bool = True, compression=Compression.none,
                      axis_name=None, partition=None) -> ShardedGrads:
    """Reduce-scatter a full gradient pytree into :class:`ShardedGrads`
    — the stage-2 entry point when gradients arrive whole (for
    bucket-by-bucket release during backprop use
    ``GradReleasePlan(reduce_scatter=True)`` instead).

    ``spec`` aligns the shard layout with an existing optimizer state
    (pass ``state.spec``); otherwise a fresh spec is built (optionally
    from ``partition``). ``compression`` rides the wire exactly as in
    the stage-1 reduce-scatter phase."""
    leaves, _ = jax.tree_util.tree_flatten(grads)
    _check_dense(leaves)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        axes = _bound_axes(axis_name)
        if not axes:
            raise ValueError(
                "scatter_gradients traced without a bound mesh axis — "
                "use shard_map (or run eagerly)")
        if spec is None:
            world = int(np.prod([lax.axis_size(a) for a in axes]))
            spec = build_spec(leaves, world, -1,
                              _quantum_bytes(basics._ensure_init()),
                              partition=partition)
        shards = []
        for g in spec.groups:
            flat = _pack_group(leaves, g)
            wire, ctx = compression.compress(flat)
            s = lax.psum_scatter(wire, tuple(axes), scatter_dimension=0,
                                 tiled=True)
            if average:
                s = s / spec.world
            shards.append(compression.decompress(s, ctx)
                          .astype(np.dtype(g.dtype)))
        return ShardedGrads(spec, tuple(shards))
    st = basics._ensure_init()
    mp = collectives._multiprocess_world(st)
    if spec is None:
        spec = build_spec(leaves, st.size, st.rank if mp else 0,
                          _quantum_bytes(st), partition=partition)
    if spec.world != st.size:
        raise ValueError(
            f"scatter_gradients spec was built for world {spec.world} "
            f"but the current world is {st.size}")
    if len(leaves) != spec.num_leaves:
        raise ValueError(
            f"gradient tree has {len(leaves)} leaves but the spec was "
            f"built for {spec.num_leaves}")
    mode = _mode(leaves, st)
    if mode == "local":
        from horovod_tpu.runtime.runtime import get_runtime

        if not collectives._runtime_capable(st):
            raise NotImplementedError(
                "scatter_gradients in a multi-process world needs the "
                "enqueue runtime (tpurun / HOROVOD_RANK env contract)")
        op_name = collectives._OP_NAMES[
            collectives.Average if average else collectives.Sum]
        handles = []
        for gi, g in enumerate(spec.groups):
            flat = _np_pack_group(leaves, g)
            wire, ctx = compression.compress(jnp.asarray(flat))
            nbytes = int(wire.size * np.dtype(wire.dtype).itemsize)
            _RS_BYTES.inc(nbytes)
            flight_recorder.emit(
                "op_dispatch", op="reducescatter", phase="grad_scatter",
                shard=spec.rank, group=gi, bytes=nbytes)
            handles.append((gi, g, ctx, nbytes, time.monotonic(),
                            get_runtime().enqueue_reducescatter(
                                f"zero2.grads.g{gi}", wire,
                                reduce_op=op_name)))
        shards = [None] * len(spec.groups)
        for gi, g, ctx, nbytes, t0, h in handles:
            out = compression.decompress(collectives.synchronize(h), ctx)
            seconds = time.monotonic() - t0
            flight_recorder.emit(
                "op_complete", op="reducescatter", phase="grad_scatter",
                shard=spec.rank, group=gi, seconds=round(seconds, 6))
            comms.record("reducescatter", "zero", nbytes, seconds,
                         world=spec.world)
            shards[gi] = jnp.asarray(out).astype(np.dtype(g.dtype))
        shards = tuple(shards)
    else:
        stacked = mode == "stacked"
        rs_bytes = sum(g.padded * np.dtype(g.dtype).itemsize
                       for g in spec.groups)
        _RS_BYTES.inc(rs_bytes)

        def build():
            def f(lvs):
                outs = []
                for g in spec.groups:
                    dt = np.dtype(g.dtype)
                    if stacked:
                        flat = _pack_group_stacked(lvs, g, spec.world)
                        wire, ctx = compression.compress(flat)
                        r = (jnp.mean(wire, axis=0) if average
                             else jnp.sum(wire, axis=0))
                    else:
                        flat = _pack_group(lvs, g)
                        wire, ctx = compression.compress(flat)
                        r = wire if average else wire * spec.world
                    r = compression.decompress(r, ctx)
                    outs.append(jnp.reshape(
                        r.astype(dt), (spec.world, g.shard_elems)))
                return tuple(outs)

            return jax.jit(
                f, out_shardings=mesh_mod.worker_sharding(st.mesh))

        key = ("zg2s", st.mesh, spec, stacked, average, compression)
        shards = _emit_phase(
            "reducescatter", "grad_scatter", spec.rank, rs_bytes,
            lambda: _module_prog(key, build)(leaves))
    _set_shard_bytes("grad_shards", shards, spec.world)
    return ShardedGrads(spec, tuple(shards))


# ---------------------------------------------------------------------------
# Stage 3: params sharded at rest, gathered on demand with prefetch
# ---------------------------------------------------------------------------

class ShardedParams:
    """Parameters sharded at rest (ZeRO-3): one flat array per dtype
    group (``(shard,)`` local multi-process, ``(W, shard)``
    worker-sharded single-controller) plus the original tree structure.
    Registered as a pytree node whose children are the shards, so it
    rides through ``tree_map`` / checkpoint flattening; the elastic and
    checkpoint layers stop at it via :func:`is_sharded_state`."""

    __slots__ = ("spec", "treedef", "shards")

    def __init__(self, spec: ZeroSpec, treedef, shards: tuple):
        self.spec = spec
        self.treedef = treedef
        self.shards = tuple(shards)

    def __repr__(self):
        return (f"ShardedParams(world={self.spec.world}, "
                f"rank={self.spec.rank}, "
                f"groups={len(self.spec.groups)})")


jax.tree_util.register_pytree_node(
    ShardedParams,
    lambda sp: (sp.shards, (sp.spec, sp.treedef)),
    lambda aux, children: ShardedParams(aux[0], aux[1], tuple(children)))


def shard_params(params, *, partition=None) -> ShardedParams:
    """Shard a full parameter pytree at rest (stage-3 entry): keep only
    this rank's 1/N flat slice per dtype group and drop the full tree.
    Eager only — sharding-at-rest is a storage decision, not a traced
    op. The ``param_shards`` memory-ledger subsystem reflects the
    resident bytes."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    _check_dense(leaves)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        raise ValueError(
            "shard_params is an eager (at-rest) operation; call it "
            "outside jit/shard_map")
    st = basics._ensure_init()
    mp = collectives._multiprocess_world(st)
    spec = build_spec(leaves, st.size, st.rank if mp else 0,
                      _quantum_bytes(st), partition=partition)
    if mp:
        shards = tuple(
            jnp.asarray(_np_pack_group(leaves, g)[
                spec.rank * g.shard_elems:
                (spec.rank + 1) * g.shard_elems])
            for g in spec.groups)
    else:
        def build():
            def f(lvs):
                return tuple(
                    jnp.reshape(_pack_group(lvs, g),
                                (spec.world, g.shard_elems))
                    for g in spec.groups)

            return jax.jit(
                f, out_shardings=mesh_mod.worker_sharding(st.mesh))

        shards = _module_prog(("zp2s", st.mesh, spec), build)(leaves)
    sp = ShardedParams(spec, treedef, tuple(shards))
    _set_shard_bytes("param_shards", sp.shards, spec.world)
    flight_recorder.emit("zero_shard_params", rank=int(spec.rank),
                         world=int(spec.world),
                         groups=len(spec.groups))
    return sp


def _account_gather(stall: float, hidden: float) -> None:
    _GATHER_STALL_SECONDS.inc(stall)
    _GATHER_HIDDEN_SECONDS.inc(hidden)
    stall_total = _GATHER_STALL_SECONDS.value
    hidden_total = _GATHER_HIDDEN_SECONDS.value
    if stall_total + hidden_total > 0:
        _GATHER_HIDDEN_FRACTION.set(
            hidden_total / (stall_total + hidden_total))
    if stall > 0:
        # goodput satellite: a stage-3 gather stall is exposed
        # communication, not input idleness — the step was compute-ready
        # and waiting on the wire
        from horovod_tpu import goodput

        goodput.record_span("exposed_comm", stall)


def gather_hidden_fraction() -> float:
    """Cumulative fraction of stage-3 param-gather transfer time hidden
    under consumer compute (0.0 before any gather)."""
    total = _GATHER_STALL_SECONDS.value + _GATHER_HIDDEN_SECONDS.value
    return (_GATHER_HIDDEN_SECONDS.value / total) if total else 0.0


def _iter_group_gathers(sp: ShardedParams, prefetch=None):
    """Yield ``(group_index, full_flat_buffer)`` in group order, with up
    to ``prefetch`` group allgathers in flight ahead of the consumer —
    the PR-3 dispatch/drain split applied to parameter gathering: group
    k+1's wire time hides under group k's compute. Blocked time is
    charged to exposed_comm; overlapped time counts as hidden."""
    spec = sp.spec
    shards = sp.shards
    if any(isinstance(x, jax.core.Tracer) for x in shards):
        axes = _bound_axes(None)
        if not axes:
            raise ValueError(
                "gathering ShardedParams traced without a bound mesh "
                "axis — use shard_map (or run eagerly)")
        for gi in range(len(spec.groups)):
            yield gi, lax.all_gather(shards[gi], tuple(axes), axis=0,
                                     tiled=True)
        return
    st = basics._ensure_init()
    if spec.world != st.size:
        raise ValueError(
            f"ShardedParams were built for world {spec.world} but the "
            f"current world is {st.size}; re-form via zero.resync")
    mp = collectives._multiprocess_world(st)
    if mp and not collectives._runtime_capable(st):
        raise NotImplementedError(
            "gathering ShardedParams in a multi-process world needs "
            "the enqueue runtime (tpurun / HOROVOD_RANK env contract)")
    window = max(1, int(prefetch if prefetch is not None
                        else prefetch_buckets_from_env()))
    n = len(spec.groups)
    pending: dict = {}
    stall = hidden = 0.0

    def dispatch(gi):
        g = spec.groups[gi]
        nbytes = g.padded * np.dtype(g.dtype).itemsize
        _AG_BYTES.inc(int(nbytes))
        flight_recorder.emit(
            "op_dispatch", op="allgather", phase="param_gather",
            shard=spec.rank, group=gi, bytes=int(nbytes))
        if mp:
            from horovod_tpu.runtime.runtime import get_runtime

            h = get_runtime().enqueue_allgather(
                f"zero3.params.g{gi}", jnp.asarray(shards[gi]))
        else:
            def build():
                def f(shard):
                    return jnp.reshape(shard, (g.padded,))

                return jax.jit(
                    f,
                    out_shardings=mesh_mod.replicated_sharding(st.mesh))

            h = _module_prog(("zgather", st.mesh, spec, gi),
                             build)(shards[gi])
        pending[gi] = (h, time.monotonic(), nbytes)

    nxt = 0
    while nxt < min(window, n):
        dispatch(nxt)
        nxt += 1
    for gi in range(n):
        h, t_disp, nbytes = pending.pop(gi)
        t_wait = time.monotonic()
        if mp:
            full = jnp.asarray(collectives.synchronize(h))
        else:
            full = h
            full.block_until_ready()
        t_done = time.monotonic()
        if nxt < n:
            dispatch(nxt)
            nxt += 1
        waited = t_done - t_wait
        total = t_done - t_disp
        stall += waited
        hidden += max(0.0, total - waited)
        flight_recorder.emit(
            "op_complete", op="allgather", phase="param_gather",
            shard=spec.rank, group=gi, seconds=round(total, 6))
        comms.record("allgather", "zero", nbytes, max(total, 1e-9),
                     world=spec.world)
        yield gi, full
    _account_gather(stall, hidden)


def gather_params(sp: ShardedParams, *, prefetch=None):
    """Materialize the full parameter pytree from :class:`ShardedParams`
    (all groups gathered, prefetch-windowed). For bounded transient HBM
    consume :func:`iter_param_buckets` instead and release each bucket
    after use."""
    out = [None] * sp.spec.num_leaves
    for gi, full in _iter_group_gathers(sp, prefetch):
        _unpack_group(full, sp.spec.groups[gi], out)
    return jax.tree_util.tree_unflatten(sp.treedef, out)


def iter_param_buckets(sp: ShardedParams, *, prefetch=None):
    """Yield ``(group_index, {leaf_index: array})`` bucket-by-bucket in
    layout order, the next group's allgather already in flight under
    this group's compute. Transient HBM is bounded by roughly
    ``prefetch`` (default ``HOROVOD_ZERO_PREFETCH_BUCKETS``) group
    buffers as long as the consumer drops each dict after use."""
    for gi, full in _iter_group_gathers(sp, prefetch):
        g = sp.spec.groups[gi]
        out = {}
        off = 0
        for i, shape, size in zip(g.indices, g.shapes, g.sizes):
            out[i] = jnp.reshape(full[off:off + size], shape)
            off += size
        yield gi, out


# ---------------------------------------------------------------------------
# Generic elementwise wrapper (optax delta contract)
# ---------------------------------------------------------------------------

class ShardedOptState(NamedTuple):
    """State of :func:`sharded_update`: the static layout spec plus the
    inner optimizer's state over the shard tree (one flat array per
    dtype group). Snapshots/checkpoints of this state hold only the
    local shard — 1/N of the replicated bytes."""

    spec: ZeroSpec
    inner: Any


def sharded_update(optimizer, *, average: bool = True,
                   compression=Compression.none, axis_name=None,
                   sparse_as_dense: bool = False, partition=None):
    """Wrap an elementwise optax transformation with ZeRO sharding.

    Stage 2: ``update_fn`` also accepts a :class:`ShardedGrads` (from
    :func:`scatter_gradients` or a reduce-scatter release plan) in
    place of the gradient pytree — the internal reduce-scatter is
    skipped and the update runs straight on the shards (``params`` is
    then required for the output tree structure). ``partition`` aligns
    the shard layout with a release plan's buckets
    (``plan.zero_partition(params)``).

    Returns an ``optax.GradientTransformationExtraArgs`` whose state is
    :class:`ShardedOptState`. The update reduce-scatters the flat
    gradient buffer, runs ``optimizer.update`` on the gradient/param
    *shards*, and allgathers the update deltas back into the original
    pytree — so the returned updates compose with
    ``optax.apply_updates`` exactly like the replicated path, bit for
    bit for elementwise inner transforms (SGD, per-element Adam math).

    Non-elementwise inner transforms (``clip_by_global_norm``,
    ``scale_by_trust_ratio``...) are NOT valid inside the wrapper: they
    would see only 1/N of the elements. Apply them to the gradients
    before this wrapper instead.
    """
    import optax

    progs: dict = {}

    def _prog(key, builder):
        fn = progs.get(key)
        if fn is None:
            _PROGRAM_BUILDS.inc()
            fn = builder()
            progs[key] = fn
        return fn

    # -- eager single-controller programs (bucket-keyed; built once per
    #    (mesh, spec) and reused every step: zero steady-state compiles)

    def _grads_to_shards_prog(mesh, spec, stacked: bool):
        def build():
            def f(leaves):
                outs = []
                for g in spec.groups:
                    dt = np.dtype(g.dtype)
                    if stacked:
                        flat = _pack_group_stacked(leaves, g, spec.world)
                        wire, ctx = compression.compress(flat)
                        r = (jnp.mean(wire, axis=0) if average
                             else jnp.sum(wire, axis=0))
                    else:
                        # replicated input: every worker holds the same
                        # grads, so average == copy and sum == x * W —
                        # the same short-circuit (and the same bits) as
                        # the replicated allreduce path.
                        flat = _pack_group(leaves, g)
                        wire, ctx = compression.compress(flat)
                        r = wire if average else wire * spec.world
                    r = compression.decompress(r, ctx)
                    outs.append(jnp.reshape(
                        r.astype(dt), (spec.world, g.shard_elems)))
                return tuple(outs)

            return jax.jit(
                f, out_shardings=mesh_mod.worker_sharding(mesh))

        return _prog(("g2s", mesh, spec, stacked, average, compression),
                     build)

    def _params_to_shards_prog(mesh, spec):
        def build():
            def f(leaves):
                return tuple(
                    jnp.reshape(_pack_group(leaves, g),
                                (spec.world, g.shard_elems))
                    for g in spec.groups)

            return jax.jit(
                f, out_shardings=mesh_mod.worker_sharding(mesh))

        return _prog(("p2s", mesh, spec), build)

    def _update_prog(mesh, spec):
        def build():
            def f(gshards, inner, pshards, extra):
                return optimizer.update(gshards, inner, pshards, **extra)

            return jax.jit(f)

        return _prog(("upd", mesh, spec), build)

    def _shards_to_updates_prog(mesh, spec):
        def build():
            def f(deltas):
                out = [None] * spec.num_leaves
                for g, d in zip(spec.groups, deltas):
                    _unpack_group(jnp.reshape(d, (g.padded,)), g, out)
                return tuple(out)

            return jax.jit(
                f, out_shardings=mesh_mod.replicated_sharding(mesh))

        return _prog(("s2u", mesh, spec), build)

    # -- shard extraction per mode ----------------------------------------

    def _tracer_shards(leaves, spec, axes):
        idx = lax.axis_index(tuple(axes))
        shards = []
        for g in spec.groups:
            flat = _pack_group(leaves, g)
            shards.append(lax.dynamic_slice(
                flat, (idx * g.shard_elems,), (g.shard_elems,)))
        return tuple(shards)

    def _local_shards(leaves, spec):
        return tuple(
            jnp.asarray(_np_pack_group(leaves, g)[
                spec.rank * g.shard_elems:(spec.rank + 1) * g.shard_elems])
            for g in spec.groups)

    # -- init --------------------------------------------------------------

    def init_fn(params):
        leaves, _ = jax.tree_util.tree_flatten(params)
        _check_dense(leaves)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            axes = _bound_axes(axis_name)
            if not axes:
                raise ValueError(
                    "shard_optimizer_states under plain jit/pjit has no "
                    "mesh axis to shard over — call it under shard_map, "
                    "eagerly, or in multi-process mode")
            world = int(np.prod([lax.axis_size(a) for a in axes]))
            spec = build_spec(leaves, world, -1,
                              _quantum_bytes(basics._ensure_init()),
                              partition=partition)
            shards = _tracer_shards(leaves, spec, axes)
            return ShardedOptState(spec, optimizer.init(shards))
        st = basics._ensure_init()
        spec = build_spec(leaves, st.size,
                          st.rank if collectives._multiprocess_world(st)
                          else 0,
                          _quantum_bytes(st), partition=partition)
        if collectives._multiprocess_world(st):
            shards = _local_shards(leaves, spec)
        else:
            shards = _params_to_shards_prog(st.mesh, spec)(leaves)
        inner = optimizer.init(shards)
        _set_state_bytes(inner, spec.world)
        return ShardedOptState(spec, inner)

    # -- update ------------------------------------------------------------

    def _update_tracer(leaves, state, pleaves, extra, axes,
                       gshards=None):
        spec = state.spec
        if gshards is None:
            gshards = []
            for g in spec.groups:
                flat = _pack_group(leaves, g)
                wire, ctx = compression.compress(flat)
                s = lax.psum_scatter(wire, tuple(axes),
                                     scatter_dimension=0, tiled=True)
                if average:
                    s = s / spec.world
                gshards.append(compression.decompress(s, ctx)
                               .astype(np.dtype(g.dtype)))
        pshards = (_tracer_shards(pleaves, spec, axes)
                   if pleaves is not None else None)
        deltas, new_inner = optimizer.update(
            tuple(gshards), state.inner, pshards, **extra)
        out = [None] * spec.num_leaves
        for g, d in zip(spec.groups, deltas):
            full = lax.all_gather(d, tuple(axes), axis=0, tiled=True)
            _unpack_group(full, g, out)
        return tuple(out), ShardedOptState(spec, new_inner)

    def _update_single_controller(leaves, state, pleaves, extra, st,
                                  stacked: bool, gshards=None):
        spec = state.spec
        mesh = st.mesh
        if gshards is None:
            rs_bytes = sum(g.padded * np.dtype(g.dtype).itemsize
                           for g in spec.groups)
            _RS_BYTES.inc(rs_bytes)
            gshards = _emit_phase(
                "reducescatter", "sharded_grads", spec.rank, rs_bytes,
                lambda: _grads_to_shards_prog(mesh, spec,
                                              stacked)(leaves))
        pshards = (_params_to_shards_prog(mesh, spec)(pleaves)
                   if pleaves is not None else None)
        deltas, new_inner = _update_prog(mesh, spec)(
            gshards, state.inner, pshards, extra)
        ag_bytes = sum(g.padded * np.dtype(np.dtype(g.dtype)).itemsize
                       for g in spec.groups)
        _AG_BYTES.inc(ag_bytes)
        updates = _emit_phase(
            "allgather", "sharded_updates", spec.rank, ag_bytes,
            lambda: _shards_to_updates_prog(mesh, spec)(deltas))
        return updates, ShardedOptState(spec, new_inner)

    def _update_multiprocess(leaves, state, pleaves, extra, st,
                             gshards=None):
        from horovod_tpu.runtime.runtime import get_runtime

        spec = state.spec
        if not collectives._runtime_capable(st):
            raise NotImplementedError(
                "sharded update in a multi-process world needs the "
                "enqueue runtime (tpurun / HOROVOD_RANK env contract); "
                "for externally-initialized jax.distributed use the "
                "shard_map path")
        op_name = collectives._OP_NAMES[
            collectives.Average if average else collectives.Sum]
        if gshards is None:
            handles = []
            for gi, g in enumerate(spec.groups):
                flat = _np_pack_group(leaves, g)
                wire, ctx = compression.compress(jnp.asarray(flat))
                nbytes = (wire.size * np.dtype(wire.dtype).itemsize)
                _RS_BYTES.inc(int(nbytes))
                flight_recorder.emit(
                    "op_dispatch", op="reducescatter",
                    phase="sharded_grads", shard=spec.rank, group=gi,
                    bytes=int(nbytes))
                # stable per-group names: the negotiation response cache
                # and the timeline see the same tensor lane every step
                handles.append((gi, g, ctx, int(nbytes),
                                time.monotonic(),
                                get_runtime().enqueue_reducescatter(
                                    f"sharded.grads.g{gi}", wire,
                                    reduce_op=op_name)))
            gshards = [None] * len(spec.groups)
            for gi, g, ctx, nbytes, t0, h in handles:
                out = compression.decompress(
                    collectives.synchronize(h), ctx)
                seconds = time.monotonic() - t0
                flight_recorder.emit(
                    "op_complete", op="reducescatter",
                    phase="sharded_grads", shard=spec.rank, group=gi,
                    seconds=round(seconds, 6))
                comms.record("reducescatter", "zero", nbytes, seconds,
                             world=spec.world)
                gshards[gi] = jnp.asarray(out).astype(np.dtype(g.dtype))
        pshards = (_local_shards(pleaves, spec)
                   if pleaves is not None else None)
        deltas, new_inner = optimizer.update(
            tuple(gshards), state.inner, pshards, **extra)
        ag_handles = []
        for gi, (g, d) in enumerate(zip(spec.groups, deltas)):
            nbytes = g.shard_elems * np.dtype(g.dtype).itemsize
            _AG_BYTES.inc(int(nbytes) * spec.world)
            flight_recorder.emit(
                "op_dispatch", op="allgather", phase="sharded_updates",
                shard=spec.rank, group=gi,
                bytes=int(nbytes) * spec.world)
            ag_handles.append((gi, g, int(nbytes) * spec.world,
                               time.monotonic(),
                               get_runtime().enqueue_allgather(
                                   f"sharded.updates.g{gi}",
                                   jnp.asarray(d))))
        out = [None] * spec.num_leaves
        for gi, g, nbytes, t0, h in ag_handles:
            full = jnp.asarray(collectives.synchronize(h))
            seconds = time.monotonic() - t0
            flight_recorder.emit(
                "op_complete", op="allgather", phase="sharded_updates",
                shard=spec.rank, group=gi, seconds=round(seconds, 6))
            comms.record("allgather", "zero", nbytes, seconds,
                         world=spec.world)
            _unpack_group(full, g, out)
        return tuple(out), ShardedOptState(spec, new_inner)

    def _integrity_check_leaves(leaves, st, mode):
        """Single-controller digest over the eager gradient leaves (the
        multi-process path is covered in band by the runtime's
        reduce-scatter digest instead — a caller-thread check there
        could diverge across ranks). Worker-stacked leaves attribute
        the non-finite row to its rank."""
        from horovod_tpu.integrity import digest as integ_digest

        if collectives._multiprocess_world(st):
            return
        if not integ_digest.cadence_due("zero.update"):
            return
        total = 0
        suspect = None
        bad_leaf = None
        for i, leaf in enumerate(leaves):
            if np.dtype(leaf.dtype).kind not in ("f", "V"):
                continue
            if mode == "stacked":
                counts = np.asarray(jnp.sum(
                    ~jnp.isfinite(jnp.reshape(leaf, (leaf.shape[0], -1))),
                    axis=1, dtype=jnp.int32))
                bad = np.nonzero(counts)[0]
                if bad.size and suspect is None:
                    suspect = int(bad[0])
                n = int(counts.sum())
            else:
                n = int(jnp.sum(~jnp.isfinite(leaf)))
            if n and bad_leaf is None:
                bad_leaf = i
            total += n
        integ_digest.verify_local(
            total, bucket="zero.grads",
            tensor=None if bad_leaf is None else f"leaf[{bad_leaf}]",
            suspect_rank=suspect)

    def update_fn(grads, state, params=None, **extra):
        if not isinstance(state, ShardedOptState):
            raise TypeError(
                "sharded_update state must be ShardedOptState (was this "
                "optimizer initialized with shard_optimizer_states?)")
        spec = state.spec
        pre = None  # stage-2: gradients arrive already reduce-scattered
        if isinstance(grads, ShardedGrads):
            _check_shard_spec(grads.spec, spec,
                              "pre-scattered gradient (ShardedGrads)")
            if params is None:
                raise ValueError(
                    "sharded_update over ShardedGrads needs params= "
                    "(the update pytree structure)")
            pre = tuple(grads.shards)
            leaves = None
            treedef = jax.tree_util.tree_structure(params)
            probe = pre
        else:
            leaves, treedef = jax.tree_util.tree_flatten(
                grads, is_leaf=sparse_mod.is_sparse)
            if sparse_as_dense:
                leaves = _densify(leaves)
            _check_dense(leaves)
            if len(leaves) != spec.num_leaves:
                raise ValueError(
                    f"gradient tree has {len(leaves)} leaves but the "
                    f"sharded state was built for {spec.num_leaves}")
            probe = leaves
        pleaves = None
        if params is not None:
            pleaves = jax.tree_util.tree_flatten(params)[0]
        if any(isinstance(x, jax.core.Tracer) for x in probe):
            axes = _bound_axes(axis_name)
            if not axes:
                raise ValueError(
                    "sharded update traced without a bound mesh axis — "
                    "use shard_map (or run eagerly)")
            out, new_state = _update_tracer(leaves, state, pleaves,
                                            extra, axes, gshards=pre)
            return treedef.unflatten(out), new_state
        st = basics._ensure_init()
        if spec.world != st.size:
            raise ValueError(
                f"sharded state was built for world {spec.world} but the "
                f"current world is {st.size}; re-init (elastic re-forms "
                "go through elastic.ArrayState.sync / zero.resync)")
        if pre is None:
            mode = _mode(leaves, st)
            _integrity_check_leaves(leaves, st, mode)
        else:
            # pre-scattered shards carry their own in-band digests
            # (bucket wire / runtime reduce-scatter lanes)
            mode = ("local" if collectives._multiprocess_world(st)
                    else "stacked")
        t0 = time.monotonic()
        if mode == "local":
            out, new_state = _update_multiprocess(leaves, state, pleaves,
                                                  extra, st, gshards=pre)
        else:
            out, new_state = _update_single_controller(
                leaves, state, pleaves, extra, st, mode == "stacked",
                gshards=pre)
        _UPDATES.inc()
        _UPDATE_SECONDS.observe(time.monotonic() - t0)
        return treedef.unflatten(out), new_state

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Fused flat AdamW (fp32 master shards, step-level API)
# ---------------------------------------------------------------------------

class FlatAdamState(NamedTuple):
    """State of :func:`sharded_adamw`: per-dtype-group flat fp32 master
    weights and Adam moments, local shard only (~12 bytes/param / N per
    chip vs 12 replicated)."""

    spec: ZeroSpec
    count: Any
    master: Any  # tuple per group, f32 (shard,) / (W, shard) / traced
    mu: Any
    nu: Any


class ShardedAdamW(NamedTuple):
    """Step-level sharded fused AdamW: ``apply(params, state, grads) ->
    (new_params, new_state)`` (not optax's ``update -> deltas``: the
    delta contract would break fp32 master-weight semantics in bf16)."""

    init: callable
    apply: callable


def sharded_adamw(learning_rate: float, b1: float = 0.9,
                  b2: float = 0.999, eps: float = 1e-8,
                  weight_decay: float = 1e-4, *, average: bool = True,
                  compression=Compression.none,
                  axis_name=None, partition=None) -> ShardedAdamW:
    """ZeRO-1/2/3 fused AdamW: reduce-scatter grads, one fused Pallas
    pass over the local fp32 master/moment shards
    (:mod:`horovod_tpu.ops.pallas.fused_optimizer`, gated by
    ``HOROVOD_SHARDED_FUSED_KERNEL``), allgather the updated params
    back in the parameter dtype.

    Stage 2: ``apply`` accepts a :class:`ShardedGrads` in place of the
    gradient pytree and skips its internal reduce-scatter. Stage 3:
    ``apply`` given :class:`ShardedParams` (and ``init`` over them)
    updates the shards and returns a new ``ShardedParams`` — the
    trailing param allgather disappears entirely; the forward pass
    re-gathers on demand. ``partition`` aligns the layout with a
    reduce-scatter release plan (``plan.zero_partition(params)``)."""
    import optax

    progs: dict = {}

    def _prog(key, builder):
        fn = progs.get(key)
        if fn is None:
            _PROGRAM_BUILDS.inc()
            fn = builder()
            progs[key] = fn
        return fn

    def _scalars(count):
        t = count.astype(jnp.float32)
        return jnp.stack([
            jnp.float32(b1), jnp.float32(b2),
            1.0 / (1.0 - jnp.float32(b1) ** t),
            1.0 / (1.0 - jnp.float32(b2) ** t),
            jnp.float32(learning_rate), jnp.float32(weight_decay)])

    def _master_prog(mesh, spec):
        def build():
            def f(leaves):
                return tuple(
                    jnp.reshape(_pack_group(leaves, g),
                                (spec.world, g.shard_elems))
                    .astype(jnp.float32)
                    for g in spec.groups)

            return jax.jit(
                f, out_shardings=mesh_mod.worker_sharding(mesh))

        return _prog(("master", mesh, spec), build)

    def _apply_prog(mesh, spec):
        def build():
            def f(scalars, master, mu, nu, gshards):
                ps, ws, ms, vs = [], [], [], []
                for g, w, m, v, gr in zip(spec.groups, master, mu, nu,
                                          gshards):
                    p2, w2, m2, v2 = fused_mod.flat_adamw_shard(
                        w, m, v, gr, scalars, eps=eps,
                        out_dtype=np.dtype(g.dtype))
                    ps.append(p2)
                    ws.append(w2)
                    ms.append(m2)
                    vs.append(v2)
                return tuple(ps), tuple(ws), tuple(ms), tuple(vs)

            return jax.jit(f)

        return _prog(("apply", mesh, spec), build)

    def _gather_prog(mesh, spec):
        def build():
            def f(pshards):
                out = [None] * spec.num_leaves
                for g, p in zip(spec.groups, pshards):
                    _unpack_group(jnp.reshape(p, (g.padded,)), g, out)
                return tuple(out)

            return jax.jit(
                f, out_shardings=mesh_mod.replicated_sharding(mesh))

        return _prog(("gather", mesh, spec), build)

    def init(params):
        if isinstance(params, ShardedParams):
            # stage 3: params already live as shards — the fp32 masters
            # are a cast of the local slices, no pack/scatter needed
            spec = params.spec
            master = tuple(jnp.asarray(s).astype(jnp.float32)
                           for s in params.shards)
            zeros = tuple(jnp.zeros_like(w) for w in master)
            state = FlatAdamState(
                spec=spec, count=jnp.zeros([], jnp.int32), master=master,
                mu=zeros, nu=tuple(jnp.zeros_like(w) for w in master))
            _set_state_bytes((state.master, state.mu, state.nu),
                             spec.world)
            return state
        leaves, _ = jax.tree_util.tree_flatten(params)
        _check_dense(leaves)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            axes = _bound_axes(axis_name)
            if not axes:
                raise ValueError(
                    "sharded_adamw under plain jit/pjit has no mesh axis "
                    "to shard over — use shard_map, eager, or "
                    "multi-process mode")
            world = int(np.prod([lax.axis_size(a) for a in axes]))
            spec = build_spec(leaves, world, -1,
                              _quantum_bytes(basics._ensure_init()),
                              partition=partition)
            idx = lax.axis_index(tuple(axes))
            master = tuple(
                lax.dynamic_slice(_pack_group(leaves, g),
                                  (idx * g.shard_elems,),
                                  (g.shard_elems,)).astype(jnp.float32)
                for g in spec.groups)
        else:
            st = basics._ensure_init()
            mp = collectives._multiprocess_world(st)
            spec = build_spec(leaves, st.size, st.rank if mp else 0,
                              _quantum_bytes(st), partition=partition)
            if mp:
                master = tuple(
                    jnp.asarray(_np_pack_group(leaves, g)[
                        spec.rank * g.shard_elems:
                        (spec.rank + 1) * g.shard_elems])
                    .astype(jnp.float32)
                    for g in spec.groups)
            else:
                master = _master_prog(st.mesh, spec)(leaves)
        zeros = tuple(jnp.zeros_like(w) for w in master)
        state = FlatAdamState(spec=spec, count=jnp.zeros([], jnp.int32),
                              master=master, mu=zeros,
                              nu=tuple(jnp.zeros_like(w) for w in master))
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            _set_state_bytes((state.master, state.mu, state.nu),
                             spec.world)
        return state

    def _grad_shards_eager(leaves, spec, st, stacked):
        # one cached program: pack + reduce-scatter (see sharded_update)
        key = ("fg2s", st.mesh, spec, stacked)

        def build():
            def f(lvs):
                outs = []
                for g in spec.groups:
                    if stacked:
                        flat = _pack_group_stacked(lvs, g, spec.world)
                        wire, ctx = compression.compress(flat)
                        r = (jnp.mean(wire, axis=0) if average
                             else jnp.sum(wire, axis=0))
                    else:
                        flat = _pack_group(lvs, g)
                        wire, ctx = compression.compress(flat)
                        r = wire if average else wire * spec.world
                    r = compression.decompress(r, ctx)
                    outs.append(jnp.reshape(
                        r.astype(np.dtype(g.dtype)),
                        (spec.world, g.shard_elems)))
                return tuple(outs)

            return jax.jit(
                f, out_shardings=mesh_mod.worker_sharding(st.mesh))

        return _prog(key, build)(leaves)

    def apply(params, state, grads):
        spec = state.spec
        sharded_out = isinstance(params, ShardedParams)
        if sharded_out:
            # stage 3: the updated params stay sharded — no trailing
            # allgather; the forward re-gathers on demand
            _check_shard_spec(params.spec, spec,
                              "ShardedParams (stage-3 params)")
        pre = None
        if isinstance(grads, ShardedGrads):
            _check_shard_spec(grads.spec, spec,
                              "pre-scattered gradient (ShardedGrads)")
            pre = tuple(grads.shards)
            gleaves = None
            probe = pre
        else:
            gleaves, _gt = jax.tree_util.tree_flatten(grads)
            _check_dense(gleaves)
            if len(gleaves) != spec.num_leaves:
                raise ValueError(
                    f"gradient tree has {len(gleaves)} leaves but the "
                    f"sharded state was built for {spec.num_leaves}")
            probe = gleaves
        count = optax.safe_int32_increment(state.count)
        scalars = _scalars(count)

        def _pack_params(ps, ws, ms, vs):
            new_state = FlatAdamState(
                spec, count, tuple(ws), tuple(ms), tuple(vs))
            if sharded_out:
                new_params = ShardedParams(params.spec, params.treedef,
                                           tuple(ps))
                if not any(isinstance(x, jax.core.Tracer) for x in ps):
                    _set_shard_bytes("param_shards", new_params.shards,
                                     spec.world)
                return new_params, new_state
            return None, new_state  # caller gathers + unflattens

        if any(isinstance(x, jax.core.Tracer) for x in probe):
            axes = _bound_axes(axis_name)
            if not axes:
                raise ValueError("sharded_adamw traced without a bound "
                                 "mesh axis — use shard_map")
            ps, ws, ms, vs = [], [], [], []
            for gi, (g, w, m, v) in enumerate(zip(
                    spec.groups, state.master, state.mu, state.nu)):
                if pre is not None:
                    gr = pre[gi]
                else:
                    flat = _pack_group(gleaves, g)
                    wire, ctx = compression.compress(flat)
                    s = lax.psum_scatter(wire, tuple(axes),
                                         scatter_dimension=0, tiled=True)
                    if average:
                        s = s / spec.world
                    gr = compression.decompress(s, ctx)
                p2, w2, m2, v2 = fused_mod.flat_adamw_shard(
                    w, m, v, gr, scalars, eps=eps,
                    out_dtype=np.dtype(g.dtype))
                ps.append(p2)
                ws.append(w2)
                ms.append(m2)
                vs.append(v2)
            new_params, new_state = _pack_params(ps, ws, ms, vs)
            if new_params is not None:
                return new_params, new_state
            out = [None] * spec.num_leaves
            for g, p in zip(spec.groups, ps):
                full = lax.all_gather(p, tuple(axes), axis=0, tiled=True)
                _unpack_group(full, g, out)
            pt = jax.tree_util.tree_flatten(params)[1]
            return pt.unflatten(out), new_state
        st = basics._ensure_init()
        if spec.world != st.size:
            raise ValueError(
                f"sharded state was built for world {spec.world} but the "
                f"current world is {st.size}")
        t0 = time.monotonic()
        if pre is not None:
            mode = ("local" if collectives._multiprocess_world(st)
                    else "stacked")
        else:
            mode = _mode(gleaves, st)
        rs_bytes = sum(g.padded * np.dtype(g.dtype).itemsize
                       for g in spec.groups)
        if mode == "local":
            from horovod_tpu.runtime.runtime import get_runtime

            if not collectives._runtime_capable(st):
                raise NotImplementedError(
                    "sharded_adamw in a multi-process world needs the "
                    "enqueue runtime (tpurun / HOROVOD_RANK)")
            if pre is not None:
                gshards = list(pre)
            else:
                op_name = collectives._OP_NAMES[
                    collectives.Average if average else collectives.Sum]
                handles = []
                for gi, g in enumerate(spec.groups):
                    flat = _np_pack_group(gleaves, g)
                    wire, ctx = compression.compress(jnp.asarray(flat))
                    _RS_BYTES.inc(int(wire.size
                                      * np.dtype(wire.dtype).itemsize))
                    flight_recorder.emit(
                        "op_dispatch", op="reducescatter",
                        phase="sharded_grads", shard=spec.rank, group=gi,
                        bytes=int(wire.size
                                  * np.dtype(wire.dtype).itemsize))
                    handles.append((gi, g, ctx, time.monotonic(),
                                    get_runtime().enqueue_reducescatter(
                                        f"sharded.adamw.grads.g{gi}",
                                        wire, reduce_op=op_name)))
                gshards = [None] * len(spec.groups)
                for gi, g, ctx, ht0, h in handles:
                    gr = compression.decompress(
                        collectives.synchronize(h), ctx)
                    flight_recorder.emit(
                        "op_complete", op="reducescatter",
                        phase="sharded_grads", shard=spec.rank, group=gi,
                        seconds=round(time.monotonic() - ht0, 6))
                    gshards[gi] = jnp.asarray(gr).astype(
                        np.dtype(g.dtype))
            ps, ws, ms, vs = [], [], [], []
            for g, w, m, v, gr in zip(spec.groups, state.master,
                                      state.mu, state.nu, gshards):
                p2, w2, m2, v2 = fused_mod.flat_adamw_shard(
                    w, m, v, gr, scalars, eps=eps,
                    out_dtype=np.dtype(g.dtype))
                ps.append(p2)
                ws.append(w2)
                ms.append(m2)
                vs.append(v2)
            if not sharded_out:
                out = [None] * spec.num_leaves
                ag_handles = []
                for gi, (g, p) in enumerate(zip(spec.groups, ps)):
                    nbytes = g.padded * np.dtype(g.dtype).itemsize
                    _AG_BYTES.inc(int(nbytes))
                    flight_recorder.emit(
                        "op_dispatch", op="allgather",
                        phase="sharded_params", shard=spec.rank,
                        group=gi, bytes=int(nbytes))
                    ag_handles.append((gi, g, time.monotonic(),
                                       get_runtime().enqueue_allgather(
                                           f"sharded.adamw.params.g{gi}",
                                           jnp.asarray(p))))
                for gi, g, ht0, h in ag_handles:
                    full = jnp.asarray(collectives.synchronize(h))
                    flight_recorder.emit(
                        "op_complete", op="allgather",
                        phase="sharded_params", shard=spec.rank,
                        group=gi,
                        seconds=round(time.monotonic() - ht0, 6))
                    _unpack_group(full, g, out)
        else:
            if pre is not None:
                gshards = pre
            else:
                stacked = mode == "stacked"
                _RS_BYTES.inc(rs_bytes)
                gshards = _emit_phase(
                    "reducescatter", "sharded_grads", spec.rank,
                    rs_bytes,
                    lambda: _grad_shards_eager(gleaves, spec, st,
                                               stacked))
            ps, ws, ms, vs = _apply_prog(st.mesh, spec)(
                scalars, state.master, state.mu, state.nu, gshards)
            if not sharded_out:
                ag_bytes = sum(g.padded * np.dtype(g.dtype).itemsize
                               for g in spec.groups)
                _AG_BYTES.inc(ag_bytes)
                out = _emit_phase(
                    "allgather", "sharded_params", spec.rank, ag_bytes,
                    lambda: _gather_prog(st.mesh, spec)(ps))
        _UPDATES.inc()
        _UPDATE_SECONDS.observe(time.monotonic() - t0)
        new_params, new_state = _pack_params(ps, ws, ms, vs)
        if new_params is not None:
            return new_params, new_state
        pt = jax.tree_util.tree_flatten(params)[1]
        return pt.unflatten(list(out)), new_state

    return ShardedAdamW(init=init, apply=apply)


# ---------------------------------------------------------------------------
# Elastic integration: shard-aware sync after a membership reform
# ---------------------------------------------------------------------------

def is_sharded_state(x) -> bool:
    """True for leaves that hold per-rank shards — optimizer states,
    stage-3 parameter shards and stage-2 gradient shards.
    ``elastic.ArrayState.sync`` must NOT broadcast these (rank 0's shard
    would clobber every other rank's); it calls :func:`resync`."""
    return isinstance(x, (ShardedOptState, FlatAdamState, ShardedParams,
                          ShardedGrads))


def _kind_of(state) -> str:
    if isinstance(state, FlatAdamState):
        return "flat_adamw"
    if isinstance(state, ShardedParams):
        return "sharded_params"
    if isinstance(state, ShardedGrads):
        return "sharded_grads"
    return "generic"


def layout_of(state) -> dict:
    """JSON-serializable shard layout of a sharded state — recorded in
    checkpoint manifests so restore can re-flatten/re-scatter into a
    different world size (``from_full_buffers``)."""
    spec = state.spec
    return {
        "kind": _kind_of(state),
        "world": int(spec.world),
        "groups": [[g.dtype, int(g.n), int(g.shard_elems), int(g.padded)]
                   for g in spec.groups],
    }


def export_shard_arrays(state) -> dict:
    """Host-resident copies of a sharded state's local arrays, in a
    stable named layout — the unit the checkpoint writer serializes and
    the neighbor-replica exchange ships. Parallel to
    :func:`from_full_buffers` / the resync replica path."""
    if isinstance(state, FlatAdamState):
        return {"kind": "flat_adamw",
                "count": np.asarray(state.count),
                "master": [np.asarray(m) for m in state.master],
                "mu": [np.asarray(m) for m in state.mu],
                "nu": [np.asarray(m) for m in state.nu]}
    if isinstance(state, (ShardedParams, ShardedGrads)):
        # one local flat slice per dtype group: the writer's generic
        # "leaves" path serializes them as {key}#leaf/{gi}
        return {"kind": _kind_of(state),
                "leaves": [np.asarray(s) for s in state.shards]}
    leaves, _ = jax.tree_util.tree_flatten(state.inner)
    return {"kind": "generic",
            "leaves": [np.asarray(x) for x in leaves]}


def _slice_new_shard(full_old: np.ndarray, old_n: int, g_new: GroupSpec,
                     new_rank: int, dtype) -> jnp.ndarray:
    return _reshard(full_old, GroupSpec(
        dtype=g_new.dtype, indices=(), shapes=(), sizes=(), n=old_n,
        shard_elems=0, padded=full_old.shape[0]), g_new, new_rank, dtype)


def from_full_buffers(target, full: dict, old_groups):
    """Rebuild a sharded state from FULL old flat buffers (one per
    dtype group), slicing this rank's shard under ``target``'s (new)
    layout — the disk-restore analogue of :func:`resync`, with the
    gathers replaced by buffers read from shard files.

    ``target`` supplies the new spec (typically a freshly-initialized
    state); ``full`` is the named-array dict shape of
    :func:`export_shard_arrays` but with *full* (old_padded,) buffers;
    ``old_groups`` is the manifest's ``groups`` layout list."""
    spec = target.spec
    if len(old_groups) != len(spec.groups):
        raise ValueError(
            "checkpoint restore: parameter structure changed (dtype "
            "group count mismatch between manifest and target)")
    if isinstance(target, FlatAdamState):
        master, mu, nu = [], [], []
        for gi, g_new in enumerate(spec.groups):
            _dt, old_n, _s, _p = old_groups[gi]
            master.append(_slice_new_shard(
                np.asarray(full["master"][gi]), old_n, g_new, spec.rank,
                np.float32))
            mu.append(_slice_new_shard(
                np.asarray(full["mu"][gi]), old_n, g_new, spec.rank,
                np.float32))
            nu.append(_slice_new_shard(
                np.asarray(full["nu"][gi]), old_n, g_new, spec.rank,
                np.float32))
        count = jnp.asarray(np.asarray(full["count"]).astype(np.int32))
        new_state = FlatAdamState(spec=spec, count=count,
                                  master=tuple(master), mu=tuple(mu),
                                  nu=tuple(nu))
        _set_state_bytes((new_state.master, new_state.mu, new_state.nu),
                         spec.world)
        return new_state
    if isinstance(target, (ShardedParams, ShardedGrads)):
        shards = []
        for gi, g_new in enumerate(spec.groups):
            _dt, old_n, _s, _p = old_groups[gi]
            shards.append(_slice_new_shard(
                np.asarray(full["leaves"][gi]).reshape(-1), old_n,
                g_new, spec.rank, np.dtype(g_new.dtype)))
        if isinstance(target, ShardedParams):
            new_state = ShardedParams(spec, target.treedef,
                                      tuple(shards))
            _set_shard_bytes("param_shards", new_state.shards,
                             spec.world)
        else:
            new_state = ShardedGrads(spec, tuple(shards))
            _set_shard_bytes("grad_shards", new_state.shards,
                             spec.world)
        return new_state
    leaves, treedef = jax.tree_util.tree_flatten(target.inner)
    by_shard: dict = {}
    for gi, g in enumerate(spec.groups):
        by_shard.setdefault(int(g.shard_elems), []).append(gi)
    new_leaves = []
    for li, leaf in enumerate(leaves):
        stored = full["leaves"][li]
        if not hasattr(leaf, "shape") or np.ndim(leaf) == 0:
            val = np.asarray(stored).reshape(-1)[0]
            new_leaves.append(jnp.asarray(val).astype(
                leaf.dtype if hasattr(leaf, "dtype") else np.float64))
            continue
        cand = by_shard.get(int(np.shape(leaf)[0]), [])
        if np.ndim(leaf) != 1 or len(cand) != 1:
            raise ValueError(
                "checkpoint restore of a generic sharded inner state "
                "needs unambiguous 1-D shard leaves (one dtype group "
                f"per shard length); got leaf shape {np.shape(leaf)}")
        gi = cand[0]
        _dt, old_n, _s, _p = old_groups[gi]
        new_leaves.append(_slice_new_shard(
            np.asarray(stored), old_n, spec.groups[gi], spec.rank,
            leaf.dtype))
    new_inner = treedef.unflatten(new_leaves)
    new_state = ShardedOptState(spec=spec, inner=new_inner)
    _set_state_bytes(new_inner, spec.world)
    return new_state


def _gather_old_segments(local: np.ndarray, old_rank: int,
                         old_world: int, old_shard: int,
                         fill: np.ndarray, replica_rank: int = -1,
                         replica_local=None):
    """Rebuild the full old flat buffer from surviving shards: allgather
    (length, old_rank, shard) from every current rank, place each
    surviving old rank's segment, and leave ``fill`` in segments whose
    owner died. First claim wins — survivors occupy the lowest new
    ranks, so a fresh joiner can never shadow a survivor's segment.

    A second gather round collects neighbor REPLICAS
    (:mod:`horovod_tpu.ckpt.replica`): a survivor holding the dead
    rank's shard bytes contributes them, so the dead segment gets its
    true last-commit values instead of ``fill``. Every rank joins both
    rounds (collective uniformity) — ranks with nothing to offer send a
    one-element dummy tagged rank -1. Returns ``(full,
    replica_restored_ranks)``."""
    lens = np.asarray(collectives.allgather(
        np.array([local.shape[0]], np.int64))).reshape(-1)
    ranks = np.asarray(collectives.allgather(
        np.array([old_rank], np.int64))).reshape(-1)
    cat = np.asarray(collectives.allgather(np.ascontiguousarray(local)))
    full = np.array(fill, copy=True)
    claimed = set()
    off = 0
    for j in range(len(ranks)):
        ln = int(lens[j])
        r = int(ranks[j])
        seg = cat[off:off + ln]
        off += ln
        if 0 <= r < old_world and ln == old_shard and r not in claimed:
            full[r * old_shard:(r + 1) * old_shard] = seg
            claimed.add(r)
    rep = (np.zeros((1,), local.dtype) if replica_local is None
           else np.ascontiguousarray(
               np.asarray(replica_local).reshape(-1).astype(
                   local.dtype, copy=False)))
    rlens = np.asarray(collectives.allgather(
        np.array([rep.shape[0]], np.int64))).reshape(-1)
    rranks = np.asarray(collectives.allgather(
        np.array([replica_rank if replica_local is not None else -1],
                 np.int64))).reshape(-1)
    rcat = np.asarray(collectives.allgather(rep))
    replica_restored = set()
    off = 0
    for j in range(len(rranks)):
        ln = int(rlens[j])
        r = int(rranks[j])
        seg = rcat[off:off + ln]
        off += ln
        if 0 <= r < old_world and ln == old_shard and r not in claimed:
            full[r * old_shard:(r + 1) * old_shard] = seg
            claimed.add(r)
            replica_restored.add(r)
    return full, replica_restored


def _reshard(full_old: np.ndarray, g_old: GroupSpec, g_new: GroupSpec,
             new_rank: int, dtype) -> jnp.ndarray:
    real = full_old[:g_old.n]
    flat = np.zeros((g_new.padded,), np.dtype(dtype))
    flat[:g_new.n] = real
    return jnp.asarray(
        flat[new_rank * g_new.shard_elems:
             (new_rank + 1) * g_new.shard_elems])


def _meta_leaves_from_spec(spec: ZeroSpec):
    """Shape/dtype stand-ins for every leaf covered by ``spec`` — lets
    resync re-lay-out grad/param shards whose full tree no longer
    exists anywhere (that is the point of stages 2/3)."""
    metas = [None] * spec.num_leaves
    for g in spec.groups:
        for i, shape in zip(g.indices, g.shapes):
            metas[i] = LeafMeta(shape=tuple(shape),
                                dtype=np.dtype(g.dtype))
    return metas


def _resync_needed(spec: ZeroSpec, st) -> bool:
    """Collective-uniform decision: a rank-local layout mismatch on ANY
    rank re-shards on ALL ranks (a survivor keeping its old rank must
    still join the allgathers of a renumbered peer)."""
    local = int(spec.world != st.size or spec.rank != st.rank)
    if not collectives._multiprocess_world(st):
        return bool(local)
    total = np.asarray(collectives.allreduce(
        np.array([local], np.int32), op=collectives.Sum))
    return int(total.reshape(-1)[0]) > 0


def resync(state, params=None, root_rank: int = 0, replica=None):
    """Re-shard a sharded optimizer state after an elastic membership
    reform: allgather the surviving old shards, rebuild the full flat
    buffers (dead ranks' segments fall back to the neutral value —
    zeros for moments, the current params for fp32 masters; exact for
    stateless inners like SGD), and slice the new world's shard.

    ``replica`` — ``(src_old_rank, exported_arrays)`` from
    ``horovod_tpu.ckpt.replica.lookup`` when this rank holds a neighbor
    replica of a (possibly dead) old rank's shard. A second gather
    round offers those bytes to every rank, so a dead rank's moment
    segments restore to their true last-commit values instead of the
    neutral fill. Ranks without a replica pass None and still join the
    round (collective uniformity).

    ``params`` must already be synced (ArrayState.sync broadcasts
    params before the optimizer tree). It may be ``None`` when
    ``state`` is a :class:`ShardedParams` / :class:`ShardedGrads` —
    those carry their own leaf metadata. No-op when the layout still
    matches on every rank."""
    from horovod_tpu.elastic.state import broadcast_object_wire

    st = basics._ensure_init()
    spec = state.spec
    if not _resync_needed(spec, st):
        return state
    if not collectives._multiprocess_world(st):
        raise ValueError(
            "sharded-state resync needs a multi-process world (a "
            "single-controller mesh cannot change size under elastic); "
            f"state layout was world={spec.world} rank={spec.rank}, "
            f"current world={st.size} rank={st.rank}")
    # preserve the old grouping (default dtype cells or a release
    # plan's bucket partition) so bucket-aligned layouts survive the
    # reform with the same group structure
    part = [list(g.indices) for g in spec.groups]
    if isinstance(state, (ShardedParams, ShardedGrads)):
        # grad/param shards describe their own leaves: rebuild layout
        # metadata from the spec (the full tree exists nowhere)
        pleaves = _meta_leaves_from_spec(spec)
    elif isinstance(params, ShardedParams):
        # stage-3: the (already-resynced) param shards are the only
        # full copy — gather them to seed the master fills below
        pleaves = jax.tree_util.tree_flatten(gather_params(params))[0]
    else:
        pleaves, _ = jax.tree_util.tree_flatten(params)
    new_spec = build_spec(pleaves, st.size, st.rank, _quantum_bytes(st),
                          partition=part)
    # survivors (incl. the root) share the authoritative old layout;
    # fresh joiners adopt it so everyone parses the gathers identically
    old_world, old_groups = broadcast_object_wire(
        (spec.world,
         tuple((g.dtype, g.n, g.shard_elems, g.padded)
               for g in spec.groups)),
        root_rank)
    if len(old_groups) != len(new_spec.groups):
        raise ValueError(
            "elastic resync: parameter structure changed across the "
            "reform (dtype group count mismatch)")
    flight_recorder.emit("sharded_resync", old_world=int(old_world),
                         new_world=int(st.size), rank=int(st.rank))
    rep_rank = -1
    rep_entries = None
    want_kind = _kind_of(state)
    if replica is not None:
        rep_rank, rep_entries = replica
        if (not isinstance(rep_entries, dict)
                or rep_entries.get("kind") != want_kind):
            rep_rank, rep_entries = -1, None
    replica_restored: set = set()  # (component, old_rank) placements

    def regroup(leaf, gi, fill_np, rep_arr=None, tag=""):
        _dt, old_n, old_shard, old_padded = old_groups[gi]
        g_new = new_spec.groups[gi]
        g_old = GroupSpec(dtype=_dt, indices=(), shapes=(), sizes=(),
                          n=old_n, shard_elems=old_shard,
                          padded=old_padded)
        local = np.asarray(leaf).reshape(-1)
        full, from_replica = _gather_old_segments(
            local, spec.rank, old_world, old_shard, fill_np,
            replica_rank=(rep_rank if rep_arr is not None else -1),
            replica_local=rep_arr)
        replica_restored.update((tag, r) for r in from_replica)
        return _reshard(full, g_old, g_new, st.rank, leaf.dtype)

    def _rep(component, idx):
        if rep_entries is None:
            return None
        try:
            arr = rep_entries[component][idx]
        except (KeyError, IndexError, TypeError):
            return None
        return None if arr is None else np.asarray(arr)

    def _finish_replica_accounting():
        if replica_restored:
            try:
                from horovod_tpu.ckpt import stats as ckpt_stats
                ckpt_stats.REPLICA_RESTORES.inc(len(replica_restored))
            except Exception:  # pragma: no cover - metrics must not kill
                pass
            flight_recorder.emit(
                "sharded_resync_replica",
                restored_old_ranks=sorted(
                    {r for _t, r in replica_restored}),
                segments=len(replica_restored), rank=int(st.rank))

    if isinstance(state, (ShardedParams, ShardedGrads)):
        # dead ranks' segments fall back to zeros unless a neighbor
        # replica offers the true bytes — for params prefer a
        # checkpoint restore when no replica covered the dead rank
        tag0 = "param" if isinstance(state, ShardedParams) else "grad"
        new_shards = []
        for gi, g_new in enumerate(new_spec.groups):
            _dt, _n, _s, old_padded = old_groups[gi]
            zfill = np.zeros((old_padded,), np.dtype(g_new.dtype))
            new_shards.append(regroup(state.shards[gi], gi, zfill,
                                      _rep("leaves", gi),
                                      tag=f"{tag0}/{gi}"))
        if isinstance(state, ShardedParams):
            new_state = ShardedParams(new_spec, state.treedef,
                                      tuple(new_shards))
            _set_shard_bytes("param_shards", new_state.shards,
                             new_spec.world)
        else:
            new_state = ShardedGrads(new_spec, tuple(new_shards))
            _set_shard_bytes("grad_shards", new_state.shards,
                             new_spec.world)
        _finish_replica_accounting()
        return new_state

    if isinstance(state, FlatAdamState):
        new_master, new_mu, new_nu = [], [], []
        for gi, g_new in enumerate(new_spec.groups):
            _dt, old_n, old_shard, old_padded = old_groups[gi]
            # master fill: the just-synced params (cast to f32) — a dead
            # rank's master segment is reconstructed exactly
            pfill = _np_pack_group(pleaves, GroupSpec(
                dtype=g_new.dtype, indices=g_new.indices,
                shapes=g_new.shapes, sizes=g_new.sizes, n=old_n,
                shard_elems=old_shard, padded=old_padded)
            ).astype(np.float32)
            zfill = np.zeros((old_padded,), np.float32)
            new_master.append(regroup(state.master[gi], gi, pfill,
                                      _rep("master", gi),
                                      tag=f"master/{gi}"))
            new_mu.append(regroup(state.mu[gi], gi, zfill,
                                  _rep("mu", gi), tag=f"mu/{gi}"))
            new_nu.append(regroup(state.nu[gi], gi, zfill,
                                  _rep("nu", gi), tag=f"nu/{gi}"))
        count = jnp.asarray(np.asarray(collectives.broadcast(
            np.array([int(state.count)], np.int64),
            root_rank)).reshape(-1)[0].astype(np.int32))
        new_state = FlatAdamState(
            spec=new_spec, count=count, master=tuple(new_master),
            mu=tuple(new_mu), nu=tuple(new_nu))
        _set_state_bytes((new_state.master, new_state.mu, new_state.nu),
                         new_spec.world)
        _finish_replica_accounting()
        return new_state

    # generic ShardedOptState: re-shard every array leaf of the inner
    # state by matching its length to the (unique) old group shard;
    # scalar leaves (step counts) broadcast from the root
    leaves, treedef = jax.tree_util.tree_flatten(state.inner)
    by_shard: dict = {}
    for gi, (_dt, _n, old_shard, _p) in enumerate(old_groups):
        by_shard.setdefault(old_shard, []).append(gi)
    new_leaves = []
    for li, leaf in enumerate(leaves):
        if not hasattr(leaf, "shape") or np.ndim(leaf) == 0:
            val = np.asarray(collectives.broadcast(
                np.asarray(leaf).reshape(1).astype(np.float64),
                root_rank)).reshape(-1)[0]
            new_leaves.append(jnp.asarray(val).astype(
                leaf.dtype if hasattr(leaf, "dtype") else np.float64))
            continue
        cand = by_shard.get(int(np.shape(leaf)[0]), [])
        if np.ndim(leaf) != 1 or len(cand) != 1:
            raise ValueError(
                "elastic resync of a generic sharded inner state needs "
                "unambiguous 1-D shard leaves (one dtype group per "
                "shard length); use sharded_adamw or a stateless inner "
                f"(got leaf shape {np.shape(leaf)})")
        gi = cand[0]
        _dt, _n, _s, old_padded = old_groups[gi]
        zfill = np.zeros((old_padded,), np.dtype(leaf.dtype))
        new_leaves.append(regroup(leaf, gi, zfill, _rep("leaves", li),
                                  tag=f"leaf/{li}"))
    new_inner = treedef.unflatten(new_leaves)
    new_state = ShardedOptState(spec=new_spec, inner=new_inner)
    _set_state_bytes(new_inner, new_spec.world)
    _finish_replica_accounting()
    return new_state
