"""Data-plane execution of negotiated (fused) responses.

TPU-native analogue of the reference's op chain + ``PerformOperation``
(reference: horovod/common/operations.cc:211-279, ops/operation_manager.cc,
ops/collective_operations.cc fused memcpy helpers): a fused ALLREDUCE
response becomes ONE compiled XLA reduction over a fused buffer, so XLA
emits a single large all-reduce over ICI instead of many small ones.

The data plane is **pipelined** (the reference overlaps collective launch
with the next fusion-buffer memcpy the same way): ``dispatch`` packs the
fused payload and *launches* the jitted reduction, returning a pending
token; ``_PendingOp.complete`` later blocks on the device result and
unpacks entry outputs. The cycle body dispatches several responses before
draining, so packing bin k+1 overlaps the device reduction of bin k.
Where the pack happens depends on where the payload lives: the
single-controller path packs **on device** (eager flatten/concatenate/pad
— sharded gradients never visit the host, and outputs stay replicated
``jax.Array`` values), while the SPMD device_put and host-ring paths stage
through a persistent host fusion buffer (fusion_buffer.py, the
reference's MemcpyInFusionBuffer, collective_operations.cc:37-81).
Leases on those host slabs ride on the pending token and are released on
every exit path — success, error status, or cycle abort.

Compiled programs are cached by **size bucket** rather than exact shape:
the fused flat payload is padded with the reduction's identity up to a
bucket boundary (power-of-two above ``HOROVOD_FUSION_BUCKET_QUANTUM``),
so steady-state training compiles O(#buckets) programs total even as
bin-packing regroups the same tensors differently every cycle. The pad is
sliced off before unpack; integer sums stay exact (zero padding).
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import comms
from horovod_tpu import flight_recorder
from horovod_tpu import timeline as timeline_mod
from horovod_tpu import tracing
from horovod_tpu.analysis import witness
from horovod_tpu.exceptions import WorkerLostError, WorkerStallError
from horovod_tpu.utils import resilience
from horovod_tpu.core import mesh as mesh_mod
from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.ops import collectives
from horovod_tpu.runtime import types
from horovod_tpu.runtime.fusion_buffer import (FusionBufferManager,
                                               reduce_identity)

_OP_LATENCY = _metrics().histogram(
    "horovod_executor_op_duration_seconds",
    "Wall time executing one (possibly fused) response, per op type.",
    labelnames=("op",))
_OP_BYTES = _metrics().counter(
    "horovod_executor_op_bytes_total",
    "Per-worker payload bytes executed, per op type.", labelnames=("op",))
_OP_ERRORS = _metrics().counter(
    "horovod_executor_op_errors_total",
    "Responses that completed with an error status, per op type.",
    labelnames=("op",))
_PROGRAM_COMPILES = _metrics().counter(
    "horovod_executor_program_compiles_total",
    "Fused-collective program cache misses (new XLA compiles). Stops "
    "growing once steady-state traffic maps onto existing size buckets.")
_PROGRAM_CACHE_HITS = _metrics().counter(
    "horovod_executor_program_cache_hits_total",
    "Fused-collective dispatches served by an already-compiled program.")
_PAD_BYTES = _metrics().counter(
    "horovod_executor_pad_bytes_total",
    "Identity-padding bytes appended to fused payloads for size-bucketed "
    "program reuse.")
_COMM_EXPOSED = _metrics().counter(
    "horovod_comm_exposed_seconds_total",
    "Collective wall time NOT hidden behind other in-flight work: dispatch "
    "busy time plus drain (device sync + unpack) time, summed across ops. "
    "Compare against the horovod_executor_op_duration_seconds sum for the "
    "comm-hidden fraction.")


class _CommClock:
    """Cumulative comm-exposure accounting consumed by the step profiler
    (profiler.py diffs these at step boundaries). Per completed op the
    lifetime splits into dispatch-busy (pack + launch), an overlap window
    (token parked in the pipeline deque while later responses dispatch —
    the only part hidden from the caller), and drain-busy (device sync +
    unpack). Plain float adds under the GIL — same hot-path philosophy as
    the metrics registry."""

    __slots__ = ("total_seconds", "exposed_seconds", "total_bytes",
                 "hidden_bytes", "ops")

    def __init__(self) -> None:
        self.total_seconds = 0.0
        self.exposed_seconds = 0.0
        self.total_bytes = 0
        self.hidden_bytes = 0.0
        self.ops = 0

    def record(self, total: float, exposed: float, nbytes: int) -> None:
        self.total_seconds += total
        self.exposed_seconds += exposed
        self.total_bytes += nbytes
        self.ops += 1
        if total > 0.0:
            self.hidden_bytes += nbytes * (1.0 - exposed / total)
        _COMM_EXPOSED.inc(exposed)


_comm_clock = _CommClock()

# every live executor, so the memory tracker's "program_cache" subsystem
# can estimate compiled-program working sets without a push on the hot path
_executors_lock = witness.make_lock("executor._executors_lock")
_executors: "weakref.WeakSet" = weakref.WeakSet()  # guarded-by: _executors_lock


def program_cache_bytes() -> int:
    """Estimated bytes of the fused-program working sets across every
    live executor — the memory tracker's ``program_cache`` pull source."""
    with _executors_lock:
        executors = list(_executors)
    return sum(e.program_cache_bytes() for e in executors)


def comm_totals() -> dict:
    """Snapshot of the cumulative comm-exposure accumulators (the step
    profiler diffs two of these to attribute one step's collectives)."""
    c = _comm_clock
    return {"total_seconds": c.total_seconds,
            "exposed_seconds": c.exposed_seconds,
            "total_bytes": c.total_bytes,
            "hidden_bytes": c.hidden_bytes,
            "ops": c.ops}


# reduce_op name -> stacked-axis reducer for the XLA fused programs
_REDUCERS = {
    types.REDUCE_SUM: jnp.sum,
    types.REDUCE_AVERAGE: jnp.mean,
    types.REDUCE_MIN: jnp.min,
    types.REDUCE_MAX: jnp.max,
    types.REDUCE_PRODUCT: jnp.prod,
}

# reduce_op name -> host ring kernel op (average = sum + host divide)
_RING_OP = {
    types.REDUCE_SUM: "sum",
    types.REDUCE_AVERAGE: "sum",
    types.REDUCE_MIN: "min",
    types.REDUCE_MAX: "max",
    types.REDUCE_PRODUCT: "product",
}


def _widen_for_ring(a, copy: bool = False):
    """Map narrow dtypes onto the native ring kernels' four types
    (fp32 accumulation for 16-bit floats matches the reference's fp16
    MPI op behavior, half.cc:43-75). Results are always C-contiguous —
    the ring reduces through ``ravel()``, which must be a view, not a
    stray copy. ``copy=True`` guarantees a NEW array safe to reduce in
    place (callers that reduce the widened buffer itself)."""
    import numpy as np

    if a.dtype in (np.float32, np.float64, np.int32, np.int64):
        if copy:
            return np.array(a, order="C", copy=True)
        return np.ascontiguousarray(a)
    if a.dtype.kind in ("f", "V"):  # f16 / bfloat16(ml_dtypes)
        return a.astype(np.float32, order="C")
    if a.dtype == np.uint32:
        return a.astype(np.int64, order="C")  # exact, no wrap
    if a.dtype.kind in ("i", "b") or a.dtype in (np.uint8, np.uint16):
        return a.astype(np.int32, order="C")
    raise TypeError(f"unsupported host allreduce dtype {a.dtype} "
                    "(uint64 cannot be widened losslessly)")


class _PendingOp:
    """Completion token for one dispatched response.

    ``dispatch`` fills ``finish`` with the blocking tail (device sync +
    unpack) for async paths, or leaves it None when the work completed
    inline (host ring, eager ops, errors). ``complete`` runs the tail,
    fires entry callbacks exactly once, and closes the metrics/timeline
    span opened at dispatch. A host fusion-buffer lease backing the
    in-flight payload is attached as ``lease`` and released when the span
    closes — success OR failure — so transient faults (WorkersDownError
    mid-ring, an aborted cycle) never strand slabs. Responses must be
    completed in dispatch order (the cycle body's drain preserves it)."""

    __slots__ = ("executor", "op", "entries", "timeline", "name0", "t0",
                 "finish", "done", "lease", "nbytes", "bucket",
                 "t_disp_end", "t_drain_start", "t0_epoch", "lane")

    def __init__(self, executor: "Executor", op: str, entries, timeline):
        self.executor = executor
        self.op = op
        self.entries = entries
        self.timeline = timeline
        self.name0 = entries[0].name if entries else "?"
        self.t0 = time.perf_counter()
        # epoch twin of t0 (the tracing clock domain): the collective
        # span emitted at close must land on the same merged-trace
        # timeline as the request spans (tracing.py)
        self.t0_epoch = time.time()
        self.finish: Optional[Callable[[], None]] = None
        self.done = False
        self.lease = None
        self.nbytes = sum(types.entry_nbytes(e) for e in entries)
        # fused size bucket (elements per row), filled by allreduce
        # dispatch paths that pad to one; None for unbucketed ops
        self.bucket: Optional[int] = None
        # comm-exposure stamps: dispatch() sets t_disp_end when staging
        # returns; complete()/fail() set t_drain_start on entry. The gap
        # between them is the token's pipeline-overlap window — comm time
        # hidden behind later dispatches (profiler.py's hidden fraction).
        self.t_disp_end: Optional[float] = None
        self.t_drain_start: Optional[float] = None
        # transport lane for the comms plane ("device" / "host_ring" /
        # "spmd"), set by the dispatch branch that moved the bytes; None
        # for branches that delegate to eager collectives (those record
        # through ops.collectives._op_event instead — no double count)
        self.lane: Optional[str] = None

    def _close(self) -> None:
        self.done = True
        if self.lease is not None:
            self.executor.fusion_buffers.release(self.lease)
            self.lease = None
        t_end = time.perf_counter()
        total = t_end - self.t0
        _OP_LATENCY.labels(op=self.op).observe(total)
        disp_end = self.t_disp_end if self.t_disp_end is not None else t_end
        drain_start = (self.t_drain_start if self.t_drain_start is not None
                       else t_end)
        hidden = max(0.0, min(drain_start, t_end) - min(disp_end, t_end))
        _comm_clock.record(total, max(0.0, total - hidden), self.nbytes)
        if self.lane is not None:
            # the comms plane's algbw clock: payload bytes over the
            # token's dispatch→drain wall time (docs/comms.md)
            comms.record(self.op, self.lane, self.nbytes, total)
        if tracing.enabled():
            # per-tensor submit→dispatch→overlap→drain lineage: the
            # training-plane analogue of the request spans, so an
            # exposed-comm spike attributes to a named tensor
            tracing.record(
                "collective:" + str(self.name0), self.t0_epoch, total,
                op=self.op, bytes=self.nbytes, bucket=self.bucket,
                dispatch_ms=round((disp_end - self.t0) * 1000.0, 3),
                overlap_ms=round(hidden * 1000.0, 3),
                drain_ms=round(max(t_end - drain_start, 0.0) * 1000.0, 3))
        if self.timeline is not None:
            self.timeline.end(self.name0)

    def fail(self, status: types.Status) -> None:
        """Complete every entry with an error status and close the span
        (reference: ErrorOp, collective_operations.cc:202-205). Idempotent:
        a token already drained (or failed at dispatch) is left alone, so
        the cycle body's abort sweep can fail the whole pending deque."""
        if self.done:
            return
        if self.t_drain_start is None:
            self.t_drain_start = time.perf_counter()
        _OP_ERRORS.labels(op=self.op).inc()
        flight_recorder.emit("op_fail", op=self.op, name=self.name0,
                             bytes=self.nbytes, bucket=self.bucket,
                             error=str(status.reason)[:200])
        for e in self.entries:
            e.complete(status, None)
        self._close()

    def fail_exc(self, exc: Exception) -> None:
        from horovod_tpu import exceptions
        from horovod_tpu import memory

        # HBM exhaustion forensics: one choke point covers dispatch-time
        # and drain-time failures on all three data planes. No-op unless
        # the exception is an allocator OOM; never raises.
        memory.maybe_record_oom(exc, where="executor")
        if (isinstance(exc, exceptions.NumericalError)
                and self.executor.integrity_failure is None):
            # a typed integrity verdict must reach the waiting caller
            # WITHOUT marking the runtime as down: the runtime survives
            # the rollback-and-replay, so this never touches
            # executor.failure (which the cycle body lifts into a
            # runtime shutdown). RuntimeHandle.wait lifts and clears it.
            self.executor.integrity_failure = exc
        elif (isinstance(exc, exceptions.WorkersDownError)
                and self.executor.failure is None):
            # a data-plane transport loss is a workers-down event even
            # though this cycle completes "normally" (entries failed by
            # status): record it so the runtime raises typed errors
            self.executor.failure = exc
            # This rank has left a ring collective half way. A peer whose
            # own link to the dead worker showed no error (its sends were
            # all in the socket's buffer before the death) is still
            # blocked in that collective on a socket of OURS, with no
            # deadline, while our cycle goes on to a control round that
            # it will never answer: a deadlock, not a delay. Shut our
            # links so that it fails as we did, and the next control
            # round fails on both sides and ends the cycle for the
            # elastic re-form.
            if self.executor.net is not None:
                self.executor.net.abort()
        self.fail(types.Status.UnknownError(str(exc)))

    def complete(self) -> None:
        if self.done:
            return
        if self.t_drain_start is None:
            self.t_drain_start = time.perf_counter()
        try:
            if self.finish is not None:
                self.finish()
            ok = types.Status.OK()
            _OP_BYTES.labels(op=self.op).inc(
                sum(types.entry_nbytes(e) for e in self.entries))
            flight_recorder.emit(
                "op_complete", op=self.op, name=self.name0,
                bytes=self.nbytes, bucket=self.bucket,
                seconds=round(time.perf_counter() - self.t0, 6))
            for e in self.entries:
                e.complete(ok, e.output)
            self._close()
        except Exception as exc:  # propagate execution failures as statuses
            self.fail_exc(exc)


class Executor:
    """First-match dispatch per response type (reference:
    operation_manager.cc:32-80). Two data planes:

    * XLA programs over the device mesh (default — single-controller, or
      multi-process sharing a global mesh via jax.distributed);
    * the native host ring (``net``) for multi-process mode without a
      shared mesh — each process contributes its local tensor, the TCP
      ring reduces, the analogue of the reference's Gloo CPU ops
      (gloo_operations.cc).
    """

    def __init__(self, mesh, net=None):
        self.mesh = mesh
        self.net = net
        self._programs: Dict[tuple, Any] = {}  # guarded-by: _lock
        self._lock = witness.make_lock("Executor._lock")
        # typed workers-down verdict from a data-plane failure (see
        # _PendingOp.fail_exc); lifted by the runtime's cycle body
        self.failure = None
        # typed integrity verdict (NumericalError family) from a digest
        # check; lifted AND CLEARED by RuntimeHandle.wait so the runtime
        # itself survives the rollback-and-replay
        self.integrity_failure = None  # guarded-by: <cycle-thread>
        # eligible fused-allreduce dispatches seen, for the digest
        # cadence; deterministic across ranks (dispatch order is
        # negotiated)
        self._integrity_dispatches = 0  # guarded-by: <cycle-thread>
        # persistent host staging (reference: FusionBufferManager) + the
        # size-bucket policy keying the program caches
        quantum = None
        try:
            from horovod_tpu.core import state as state_mod

            quantum = state_mod.global_state().config.fusion_bucket_quantum
        except Exception:
            pass  # direct construction in tests / tools: use the default
        self.fusion_buffers = (FusionBufferManager(quantum)
                               if quantum is not None
                               else FusionBufferManager())
        self._ag_staging = bytearray()  # allgather wire staging (reused)
        # two-level host-collective group plan, memoized per (net,
        # world, rank, knob) — elastic re-forms swap the NetComm, which
        # invalidates the key so groups are recomputed for the new world
        self._hier_plan = None       # guarded-by: <cycle-thread>
        self._hier_plan_key = None   # guarded-by: <cycle-thread>
        with _executors_lock:
            _executors.add(self)
        # Multi-process with a global mesh (jax.distributed): the hot op
        # (allreduce) must ride XLA collectives over ICI/DCN, not the host
        # TCP ring — the ring stays as control plane + fallback. Requires
        # homogeneous device ownership (the reference likewise gates
        # hierarchical paths on homogeneity, mpi_controller.cc:25-81).
        self._spmd_world = jax.process_count() > 1
        self._proc_mesh = None
        if self._spmd_world:
            # One-device-per-process sub-mesh for the fused allreduce: each
            # process transfers its fusion buffer to device exactly once (no
            # k-fold duplication across its local devices) and the reduction
            # is exact for ints (one row per process, no dup correction).
            by_proc: Dict[int, list] = {}
            for d in mesh.devices.flatten():
                by_proc.setdefault(d.process_index, []).append(d)
            firsts = [min(ds, key=lambda d: d.id)
                      for _, ds in sorted(by_proc.items())]
            if len(firsts) == jax.process_count():
                import numpy as _np
                from jax.sharding import Mesh

                self._proc_mesh = Mesh(_np.array(firsts), ("proc",))

    def _replicated(self):
        from horovod_tpu.core import mesh as mesh_mod

        return mesh_mod.replicated_sharding(self.mesh)

    def _fused_allreduce_program(self, rows: int, n: int, dtype,
                                 reduce_op: str,
                                 hierarchical: bool = False):
        """One compiled reduction per (rows, bucket, dtype, op[, hier]):
        input is the packed fusion buffer (rows, n) — one row per worker —
        reduced over the worker axis, output replicated. Keyed by the
        size bucket, not the member shapes, so regrouped bins reuse it."""
        key = ("fused_allreduce", rows, n, str(dtype), reduce_op,
               hierarchical)
        with self._lock:
            fn = self._programs.get(key)
            if fn is not None:
                _PROGRAM_CACHE_HITS.inc()
                return fn
        _PROGRAM_COMPILES.inc()

        if hierarchical:
            # two-level reduction over the fused buffer (shared body with
            # the eager path: collectives.two_level_reduce_block) —
            # sum/average only; callers gate other ops to the flat path
            cross, local = self.mesh.devices.shape
            world = cross * local

            def inner(xblk):
                return collectives.two_level_reduce_block(
                    xblk[0], local, world,
                    reduce_op == types.REDUCE_AVERAGE)

            def reduce_buf(buf):
                return jax.shard_map(
                    inner, mesh=self.mesh,
                    in_specs=P(mesh_mod.GLOBAL_AXES),
                    out_specs=P(), check_vma=False)(buf)
        else:
            reducer = _REDUCERS[reduce_op]

            def reduce_buf(buf):
                return reducer(buf, axis=0)

        fn = jax.jit(reduce_buf, out_shardings=self._replicated())
        with self._lock:
            self._programs[key] = fn
        return fn

    def _integrity_due(self) -> bool:
        """Advance the digest cadence by one eligible dispatch; True on
        the first and every HOROVOD_INTEGRITY_INTERVAL-th. Called at the
        same negotiated dispatch on every rank, so the decision (and the
        in-band exchange it triggers) stays lockstep."""
        from horovod_tpu import integrity

        if not integrity.enabled():
            return False
        iv = integrity.interval()
        if iv <= 0:
            return False
        n = self._integrity_dispatches
        self._integrity_dispatches = n + 1
        return n % iv == 0

    def _digest_nonfinite_program(self, rows: int, capacity: int, dtype):
        """Per-row non-finite count over the packed fusion buffer, in
        band with the fused reduction. ``total`` is a traced scalar so
        one program per (rows, bucket, dtype) serves every payload size
        in the bucket; the mask keeps the reduction-identity padding
        (±inf for min/max) from counting as corruption."""
        key = ("digest_nf", rows, capacity, str(dtype))
        with self._lock:
            fn = self._programs.get(key)
            if fn is not None:
                _PROGRAM_CACHE_HITS.inc()
                return fn
        _PROGRAM_COMPILES.inc()

        def count_nonfinite(buf, total):
            mask = jnp.arange(capacity)[None, :] < total
            bad = jnp.logical_and(mask, ~jnp.isfinite(buf))
            return jnp.sum(bad, axis=1, dtype=jnp.int32)

        fn = jax.jit(count_nonfinite, out_shardings=self._replicated())
        with self._lock:
            self._programs[key] = fn
        return fn

    def program_cache_bytes(self) -> int:
        """Estimated working-set bytes of the compiled-program cache,
        derived from the size-bucketed cache keys (the fused input buffer
        each program was specialized for — the persistent device-side
        footprint the cache pins)."""
        import numpy as np

        with self._lock:
            keys = list(self._programs)
        total = 0
        for key in keys:
            try:
                kind = key[0]
                if kind in ("fused_allreduce", "digest_nf"):
                    rows, n, dtype = int(key[1]), int(key[2]), key[3]
                elif kind == "spmd_allreduce":
                    rows, n, dtype = jax.process_count(), int(key[1]), key[2]
                else:
                    continue
                total += rows * n * np.dtype(dtype).itemsize
            except Exception:
                continue  # an unparseable key must not break accounting
        return total

    def hierarchical_available(self) -> bool:
        """Two-level collectives need both topology axes populated
        (reference gates hierarchical on topology,
        nccl_operations.cc:348-355). On the multiprocess host-ring data
        plane the topology is the rank grouping, NOT the stacked device
        mesh — the old mesh-only check meant a two-host host-ring job
        never saw its hierarchical knobs join the autotune sweep. This
        is a static predicate (no wire traffic): an explicit group size
        must tile the world into >= 2 groups of >= 2; with auto (host-
        derived) grouping any world >= 4 COULD split, so the knob is
        sweepable and a flat-resolving plan simply makes it a no-op."""
        if self.net is not None and not self._spmd_world:
            w = self.net.world
            if w < 4:
                return False
            g = self._hier_group_size()
            return g == 0 or (g >= 2 and w % g == 0 and w // g >= 2)
        cross, local = self.mesh.devices.shape
        return cross > 1 and local > 1

    def _hier_group_size(self) -> int:
        """The HOROVOD_HIERARCHY_GROUP_SIZE knob (0 = host-derived),
        autotuner-writable through the synced config."""
        try:
            from horovod_tpu.core import state as state_mod

            return int(state_mod.global_state()
                       .config.hierarchy_group_size or 0)
        except Exception:
            return 0

    def _hierarchy_plan(self):
        """Memoized group plan for the host-ring data plane; None when
        hierarchy is off (knob disabled) or the plan resolves flat.
        Host-derived formation runs one roster allgatherv — safe here
        because dispatch order is negotiated, so every rank builds the
        plan at the same point in its wire-op sequence."""
        net = self.net
        if net is None:
            return None
        from horovod_tpu.core import state as state_mod

        cfg = state_mod.global_state().config
        if not cfg.hierarchical_allreduce:
            return None
        gsize = int(cfg.hierarchy_group_size or 0)
        key = (id(net), net.world, net.rank, gsize)
        if self._hier_plan_key != key:
            from horovod_tpu.runtime import hierarchy

            plan = hierarchy.build_plan(net, gsize)
            self._hier_plan = plan
            self._hier_plan_key = key
            if plan.enabled:
                flight_recorder.emit(
                    "hierarchy_plan", groups=plan.num_groups,
                    group_size=plan.group_size, source=plan.source,
                    world=plan.world)
        plan = self._hier_plan
        return plan if (plan is not None and plan.enabled) else None

    def _hier_wire_dtype(self):
        """Numpy wire dtype for the compressed cross-group hop (None =
        full precision), from HOROVOD_HIERARCHY_COMPRESSION."""
        from horovod_tpu.core import state as state_mod
        from horovod_tpu.runtime import hierarchy

        try:
            name = state_mod.global_state().config.hierarchy_compression
        except Exception:
            return None
        try:
            return hierarchy.wire_dtype_from_name(name)
        except ValueError:
            return None

    def execute(self, response, entries: List[types.TensorTableEntry],
                timeline=None) -> None:
        """Run one (fused) response synchronously: dispatch + complete.
        Kept for callers that don't pipeline (and as the un-overlapped
        baseline — semantics identical to dispatch().complete())."""
        self.dispatch(response, entries, timeline=timeline).complete()

    def dispatch(self, response, entries: List[types.TensorTableEntry],
                 timeline=None) -> _PendingOp:
        """Stage one (fused) response onto the data plane and return a
        pending token; ``token.complete()`` blocks on the result and fires
        entry callbacks (reference: PerformOperation, operations.cc:211-279
        — statuses are delivered through per-entry callbacks; an ERROR
        response maps to an error status on every entry).

        Asynchronous paths (the XLA fused allreduces) launch here and
        fetch in complete(); host-ring and eager paths run to completion
        here and complete() only fires callbacks — the drain order is the
        same either way.
        """
        pend = _PendingOp(self, response.response_type, entries, timeline)
        flight_recorder.emit("op_dispatch", op=pend.op, name=pend.name0,
                             tensors=len(entries), bytes=pend.nbytes)
        t0 = time.monotonic()
        try:
            if timeline is not None:
                timeline.start(pend.name0, response.response_type)
            if response.response_type == types.ERROR:
                pend.fail(
                    types.Status.PreconditionError(response.error_message))
                return pend

            if response.response_type == types.ALLREDUCE:
                if (self.net is not None and self._spmd_world
                        and self._proc_mesh is not None):
                    # 64-bit payloads can't ride the XLA sub-mesh under
                    # x32 (device_put would narrow them — 2**40 becomes
                    # garbage); they reduce exactly on the host ring
                    # instead. The split is deterministic across ranks
                    # (dtype is part of the negotiated response). Inspect
                    # dtype via the tensor attribute — np.asarray on a
                    # jax.Array would device_get every gradient just to
                    # look at its dtype.
                    wide, rest = [], []
                    for e in entries:
                        dt = e.tensor.dtype  # np.dtype for numpy AND jax
                        (wide if dt.itemsize == 8 and dt.kind in "iuf"
                         else rest).append(e)
                    if wide:
                        # the ring ran to completion right here — fire
                        # these callbacks now rather than when the token
                        # drains (under pipeline depth N the drain waits
                        # behind up to N-1 later device collectives)
                        wide_bytes = sum(
                            types.entry_nbytes(e) for e in wide)
                        t_ring = time.perf_counter()
                        self._execute_allreduce_host(wide, timeline)
                        comms.record("allreduce", "host_ring", wide_bytes,
                                     time.perf_counter() - t_ring)
                        ok = types.Status.OK()
                        _OP_BYTES.labels(op=pend.op).inc(wide_bytes)
                        for e in wide:
                            e.complete(ok, e.output)
                        pend.entries = rest
                        # the token's remaining bytes ride the SPMD lane
                        pend.nbytes -= wide_bytes
                    if rest:
                        pend.lane = "spmd"
                        pend.finish = self._dispatch_allreduce_spmd(
                            rest, timeline, pend)
                elif self.net is not None:
                    pend.lane = "host_ring"
                    self._execute_allreduce_host(entries, timeline)
                else:
                    pend.lane = "device"
                    pend.finish = self._dispatch_allreduce(
                        response, entries, timeline, pend)
            elif response.response_type == types.ALLGATHER:
                if self.net is not None:
                    pend.lane = "host_ring"
                    self._execute_allgather_host(response, entries)
                else:
                    for e in entries:
                        e.output = collectives.allgather(e.tensor)
            elif response.response_type == types.BROADCAST:
                if self.net is not None:
                    pend.lane = "host_ring"
                    self._execute_broadcast_host(entries)
                else:
                    for e in entries:
                        e.output = collectives.broadcast(e.tensor, e.root_rank)
            elif response.response_type == types.REDUCESCATTER:
                if self.net is not None:
                    pend.lane = "host_ring"
                    self._execute_reducescatter_host(entries)
                else:
                    for e in entries:
                        e.output = collectives.reducescatter(
                            e.tensor, op=collectives.OPS_BY_NAME[e.reduce_op])
            elif response.response_type == types.ALLTOALL:
                if self.net is not None:
                    pend.lane = "host_ring"
                    self._execute_alltoall_host(entries)
                else:
                    for e in entries:
                        e.output = collectives.alltoall(e.tensor)
            else:
                raise ValueError(
                    f"unknown response type {response.response_type}")
        except Exception as exc:
            pend.fail_exc(self._maybe_stall(exc, time.monotonic() - t0))
        if pend.t_disp_end is None:
            pend.t_disp_end = time.perf_counter()
        return pend

    def _maybe_stall(self, exc: Exception, elapsed: float) -> Exception:
        """Classify a data-plane transport loss that consumed the whole
        HOROVOD_COLLECTIVE_TIMEOUT budget as a generation-stamped
        ``WorkerStallError``: a peer that sat silent for the entire
        deadline is partitioned/stalled, not cleanly dead, and the
        elastic reform should treat the cycle abort as a stall (the
        error still flows through the same ``_PendingOp.fail`` path)."""
        ct = resilience.collective_timeout()
        if (ct > 0 and elapsed >= ct - 0.05
                and isinstance(exc, WorkerLostError)
                and not isinstance(exc, WorkerStallError)):
            gen = resilience.current_generation()
            flight_recorder.emit("collective_timeout", phase="dispatch",
                                 generation=gen, elapsed=round(elapsed, 3))
            return WorkerStallError(
                f"data-plane dispatch blocked {elapsed:.1f}s — "
                f"HOROVOD_COLLECTIVE_TIMEOUT={ct:g}s exceeded in "
                f"generation {gen}; aborting the cycle for elastic "
                f"recovery ({exc})", ranks=exc.ranks)
        return exc

    # -- fused pack/pad helpers --------------------------------------------
    def _pack_fused(self, arrays, rows: int, dtype, reduce_op: str):
        """Copy flattened entry payloads into a leased persistent fusion
        buffer of shape (rows, bucket) and pad the tail columns with the
        reduction identity. Returns (lease, total_elems_per_row)."""
        import numpy as np

        sizes = [a.size // rows for a in arrays]
        total = sum(sizes)
        lease = self.fusion_buffers.acquire(rows, total, dtype)
        try:
            buf = lease.array
            off = 0
            for a, n in zip(arrays, sizes):
                np.copyto(buf[:, off:off + n], a.reshape(rows, n))
                off += n
            if lease.capacity > total:
                buf[:, total:] = reduce_identity(dtype, reduce_op)
                _PAD_BYTES.inc(
                    (lease.capacity - total) * rows * buf.dtype.itemsize)
        except Exception:
            self.fusion_buffers.release(lease)
            raise
        return lease, total

    # -- single-controller XLA data plane ----------------------------------
    def _dispatch_allreduce(self, response, entries, timeline=None,
                            pend=None):
        """Fused allreduce over the global mesh, entirely on device: the
        worker-stacked entries are flattened, concatenated and
        identity-padded to the size bucket with eager XLA ops (the
        device-side MemcpyInFusionBuffer — sharded gradients never visit
        the host), the bucket-keyed compiled reduction is launched, and
        the returned completion tail blocks on the device result and
        unpacks replicated ``jax.Array`` slices. The host
        FusionBufferManager still owns the bucket policy but stages
        nothing here — it serves the host-ring and SPMD device_put paths.
        Replicated inputs need no collective and complete inline."""
        import numpy as np

        stacked, replicated = [], []
        for e in entries:
            (stacked if collectives._is_worker_stacked(e.tensor)
             else replicated).append(e)

        # Replicated inputs need no collective: every worker already holds
        # the same value (single-controller invariant). average/min/max of
        # identical copies is the identity; sum/product scale by world.
        size = collectives.state_mod.global_state().size
        for e in replicated:
            if e.reduce_op == types.REDUCE_SUM:
                e.output = e.tensor * size
            elif e.reduce_op == types.REDUCE_PRODUCT:
                e.output = e.tensor ** size
            else:
                e.output = e.tensor

        if not stacked:
            if pend is not None:
                pend.lane = None  # nothing crossed a wire
            return None
        reduce_op = stacked[0].reduce_op
        name0 = stacked[0].name
        rows = int(stacked[0].tensor.shape[0])  # worker-stacked == world
        dtype = np.dtype(stacked[0].tensor.dtype)
        sizes = [int(e.tensor.size) // rows for e in stacked]
        shapes = [tuple(e.tensor.shape[1:]) for e in stacked]
        total = sum(sizes)
        capacity = self.fusion_buffers.bucket_elems(total, dtype.itemsize)
        if pend is not None:
            pend.bucket = capacity
        if timeline is not None:
            timeline.activity_start(name0,
                                    timeline_mod.MEMCPY_IN_FUSION_BUFFER)
        # Device-side pack: eager reshape/concat/pad are tiny XLA ops
        # cached by shape in jax's own executable cache, and in steady
        # state the bounded set of bin groupings is fully warm. The
        # expensive program (the one holding the collective) stays keyed
        # by the size bucket below.
        parts = [jnp.reshape(e.tensor, (rows, n))
                 for e, n in zip(stacked, sizes)]
        if capacity > total:
            parts.append(jnp.full((rows, capacity - total),
                                  reduce_identity(dtype, reduce_op), dtype))
            _PAD_BYTES.inc((capacity - total) * rows * dtype.itemsize)
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        from horovod_tpu.integrity import digest as integ_digest
        from horovod_tpu.integrity import inject as integ_inject

        is_float = dtype.kind in ("f", "V")  # V: ml_dtypes bf16
        plan = integ_inject.plan_dispatch_any()
        if plan is not None and plan[0] == "nan" and is_float:
            # one process owns every worker's row here, so the clause
            # rank selects the ROW to poison (bitflip is a no-op on this
            # path: a single replicated result has no copy to diverge)
            row = min(max(plan[1], 0), rows - 1)
            buf = buf.at[row, 0].set(jnp.nan)
        nf_dev = None
        if is_float and self._integrity_due():
            digest_fn = self._digest_nonfinite_program(rows, capacity,
                                                       dtype)
            nf_dev = digest_fn(buf, np.int32(total))
        if timeline is not None:
            timeline.activity_end(name0)
            timeline.activity_start(name0, timeline_mod.XLA_COLLECTIVE)
        hier = (collectives.state_mod.global_state()
                .config.hierarchical_allreduce
                and self.hierarchical_available()
                and reduce_op in (types.REDUCE_SUM, types.REDUCE_AVERAGE))
        fn = self._fused_allreduce_program(rows, capacity, dtype,
                                           reduce_op, hier)
        out_dev = fn(buf)  # async launch; completion syncs in finish()

        def finish():
            # pipeline barrier without D2H: bound in-flight device work
            # at the drain, but keep results resident as replicated
            # jax.Arrays (callers rely on device residency/sharding)
            jax.block_until_ready(out_dev)
            if nf_dev is not None:
                counts = np.asarray(nf_dev)
                bad = np.nonzero(counts)[0]
                integ_digest.verify_local(
                    int(counts.sum()), bucket=f"fused[{capacity}]",
                    tensor=name0,
                    suspect_rank=int(bad[0]) if bad.size else None)
            if timeline is not None:
                timeline.activity_end(name0)
                timeline.activity_start(
                    name0, timeline_mod.MEMCPY_OUT_FUSION_BUFFER)
            off = 0
            for e, shape, n in zip(stacked, shapes, sizes):
                e.output = out_dev[off:off + n].reshape(shape)
                off += n
            if timeline is not None:
                timeline.activity_end(name0)

        return finish

    # -- host (multi-process) data plane -----------------------------------
    def _execute_allreduce_host(self, entries, timeline=None) -> None:
        """Fused host ring allreduce: pack all entries into one flat
        persistent buffer (the literal fusion-buffer memcpy of the
        reference, collective_operations.cc:37-81), one ring pass, unpack.
        No bucket padding on the wire — the ring isn't compiled, so extra
        bytes would cost bandwidth for nothing; the persistent slab is
        bucket-sized and sliced to the exact payload."""
        import numpy as np

        world = self.net.world
        hier_plan = self._hierarchy_plan()
        if hier_plan is None:
            # chaos seam on the DATA plane (the ctrl/kv seams cover only
            # the control plane): HOROVOD_FAULT_INJECT=netdelay:... slows
            # the ring pass itself, so the comms plane's host_ring busbw
            # visibly degrades (docs/comms.md, docs/robustness.md). A
            # flat ring's 2(w-1) exchange steps each cross the slow
            # group boundary, so a hop=cross netdelay taxes all of them.
            resilience.inject("ring", "allreduce",
                              crossings=2 * (world - 1))
        arrays = [np.asarray(e.tensor) for e in entries]
        # narrow types have no native host-ring kernels; widen for the wire
        wire = [_widen_for_ring(a) for a in arrays]
        if timeline is not None:
            timeline.activity_start(entries[0].name,
                                    timeline_mod.MEMCPY_IN_FUSION_BUFFER)
        total = sum(w.size for w in wire)
        lease = self.fusion_buffers.acquire(1, total, wire[0].dtype)
        try:  # the ring raising (WorkersDownError is routine in elastic
            # mode) must not strand the slab — release on every path
            buf = lease.array.ravel()[:total]
            off = 0
            for w in wire:
                np.copyto(buf[off:off + w.size], w.ravel())
                off += w.size
            from horovod_tpu.integrity import digest as integ_digest
            from horovod_tpu.integrity import inject as integ_inject

            plan = integ_inject.plan_dispatch()
            if plan == "nan" and buf.dtype.kind == "f":
                # poison this rank's INPUT before the ring pass — the
                # NaN spreads to every replica through the reduction
                integ_inject.corrupt_nan(buf)
            check = self._integrity_due()
            nf_in = integ_digest.nonfinite_count(buf) if check else 0
            if timeline is not None:
                timeline.activity_end(entries[0].name)
                timeline.activity_start(entries[0].name,
                                        "NET_RING_ALLREDUCE")
            reduce_op = entries[0].reduce_op
            if hier_plan is not None:
                # two-level path: intra reduce-scatter -> cross exchange
                # over 1/g of the bytes (optionally 16-bit on the wire)
                # -> intra allgather. nf_in above was computed on the
                # uncompressed input and checksum below on the
                # decompressed result, so integrity verdicts are
                # independent of the wire precision (pre-compression
                # digests, the PR 10 contract).
                from horovod_tpu.runtime import hierarchy

                hierarchy.hier_allreduce(
                    self.net, hier_plan, buf, _RING_OP[reduce_op],
                    wire_dtype=self._hier_wire_dtype())
            else:
                self.net.allreduce(buf, _RING_OP[reduce_op])
            if timeline is not None:
                timeline.activity_end(entries[0].name)
            if reduce_op == types.REDUCE_AVERAGE:
                buf = buf / world  # new array; slab is released unscaled
            if plan == "bitflip":
                # SDC on this rank's LOCAL copy of the reduced result:
                # the other ranks hold the correct bytes, so only the
                # cross-rank checksum vote can convict
                if reduce_op != types.REDUCE_AVERAGE:
                    buf = buf.copy()  # don't poison the reusable slab
                integ_inject.corrupt_bitflip(buf)
            if check:
                # in-band agreement: one 12-byte record per rank over
                # the same wire, same thread, same negotiated order as
                # the payload — raises BEFORE any output is unpacked
                records = integ_digest.exchange(
                    self.net, nf_in, integ_digest.checksum(buf))
                integ_digest.verify(records, bucket=f"ring[{total}]",
                                    tensor=entries[0].name)
            off = 0
            for e, orig, w in zip(entries, arrays, wire):
                n = w.size
                # astype(copy=True is the default) detaches the output
                # from the reusable slab even when dtypes already match
                out = buf[off:off + n].reshape(orig.shape).astype(
                    orig.dtype)
                e.output = out
                off += n
        finally:
            self.fusion_buffers.release(lease)

    def _fused_spmd_allreduce_program(self, n: int, dtype, reduce_op: str):
        """One compiled XLA program per (size bucket, dtype, op): the
        global stacked fusion buffer (P, n) — one row per process, sharded
        over the per-process sub-mesh — is reduced over the process axis,
        output replicated. Integer sums are exact (no duplication, and
        bucket padding is zeros for sum/average)."""
        key = ("spmd_allreduce", n, str(dtype), reduce_op)
        with self._lock:
            fn = self._programs.get(key)
            if fn is not None:
                _PROGRAM_CACHE_HITS.inc()
                return fn
        _PROGRAM_COMPILES.inc()

        replicated = NamedSharding(self._proc_mesh, P())
        reducer = _REDUCERS[reduce_op]

        def f(buf):
            return reducer(buf, axis=0)

        fn = jax.jit(f, out_shardings=replicated)
        with self._lock:
            self._programs[key] = fn
        return fn

    def _dispatch_allreduce_spmd(self, entries, timeline=None, pend=None):
        """Fused allreduce over a one-device-per-process sub-mesh in
        multi-process mode: pack entries into the flat persistent fusion
        buffer (padded to its size bucket — deterministic across ranks,
        the sizes are negotiated), place it on this process's row of a
        (P, bucket) global array (single host→device transfer), launch
        the compiled XLA collective (rides ICI/DCN), and return the
        completion tail that fetches + unpacks the replicated result. The
        slab lease rides on ``pend`` so the token releases it whether the
        response completes, fails, or the cycle aborts. The analogue of
        NCCLAllreduce on the reference's GPU path
        (nccl_operations.cc:55-105) with XLA in place of NCCL."""
        import numpy as np

        reduce_op = entries[0].reduce_op
        name0 = entries[0].name
        arrays = [np.asarray(e.tensor) for e in entries]
        if timeline is not None:
            timeline.activity_start(name0,
                                    timeline_mod.MEMCPY_IN_FUSION_BUFFER)
        lease, total = self._pack_fused(arrays, 1, arrays[0].dtype,
                                        reduce_op)
        if pend is not None:
            pend.lease = lease
            pend.bucket = lease.capacity
        flat = lease.array  # (1, bucket) — already the row layout
        from horovod_tpu.integrity import digest as integ_digest
        from horovod_tpu.integrity import inject as integ_inject

        plan = integ_inject.plan_dispatch()
        if plan == "nan" and flat.dtype.kind in ("f", "V"):
            integ_inject.corrupt_nan(flat)  # pre-reduce input poisoning
        check = self._integrity_due()
        # input digest over the exact payload — the [total:] tail is
        # reduction-identity padding (±inf for min/max), not corruption
        nf_in = (integ_digest.nonfinite_count(flat.ravel()[:total])
                 if check else 0)
        mesh = self._proc_mesh
        n_proc = mesh.devices.size
        row_sharding = NamedSharding(mesh, P("proc"))
        local_dev = [d for d in mesh.devices.flatten()
                     if d.process_index == jax.process_index()][0]
        local_row = jax.device_put(flat, local_dev)
        global_stack = jax.make_array_from_single_device_arrays(
            (n_proc, lease.capacity), row_sharding, [local_row])
        if timeline is not None:
            timeline.activity_end(name0)
            timeline.activity_start(name0, timeline_mod.XLA_COLLECTIVE)
        fn = self._fused_spmd_allreduce_program(
            lease.capacity, flat.dtype, reduce_op)
        out_dev = fn(global_stack)  # async launch; fetch in finish()

        def finish():
            out = np.asarray(out_dev)  # D2H, blocks on the collective
            if plan == "bitflip":
                out = out.copy()  # np.asarray of a jax.Array is read-only
                integ_inject.corrupt_bitflip(out)
            if check:
                # the drain runs on the cycle thread in dispatch order,
                # so the agreement exchange is in band with (never racing)
                # the ring's payload traffic; raises before unpack, and
                # complete() routes it to executor.integrity_failure
                records = integ_digest.exchange(
                    self.net, nf_in, integ_digest.checksum(out[:total]))
                integ_digest.verify(records,
                                    bucket=f"spmd[{lease.capacity}]",
                                    tensor=name0)
            if timeline is not None:
                timeline.activity_end(name0)
                timeline.activity_start(
                    name0, timeline_mod.MEMCPY_OUT_FUSION_BUFFER)
            off = 0
            for e, a in zip(entries, arrays):
                e.output = out[off:off + a.size].reshape(a.shape).astype(
                    a.dtype, copy=False)
                off += a.size
            if timeline is not None:
                timeline.activity_end(name0)

        return finish

    def _execute_allgather_host(self, response, entries) -> None:
        """Per-entry variable-size gather on the host wire. The wire wants
        one contiguous byte blob per entry; instead of a fresh
        ``tobytes()`` copy each time, contiguous arrays go out zero-copy
        (a ctypes view of their memory) and non-contiguous ones stage
        through one persistent bytearray reused across entries/cycles."""
        import ctypes

        import numpy as np

        resilience.inject("ring", "allgather")
        for e in entries:
            local = np.asarray(e.tensor)
            nb = local.nbytes
            if local.flags.c_contiguous and nb:
                blob = (ctypes.c_char * nb).from_address(
                    local.ctypes.data) if local.flags.writeable else \
                    ctypes.cast(local.ctypes.data,
                                ctypes.POINTER(ctypes.c_char * nb)).contents
            else:
                if len(self._ag_staging) < nb:
                    self._ag_staging = bytearray(nb)
                view = np.frombuffer(self._ag_staging, dtype=local.dtype,
                                     count=local.size)
                np.copyto(view.reshape(local.shape), local)
                blob = (ctypes.c_char * nb).from_buffer(self._ag_staging)
            blobs = self.net.allgatherv(blob)
            parts = []
            trailing = local.shape[1:]
            for r, blob_r in enumerate(blobs):
                a = np.frombuffer(blob_r, dtype=local.dtype)
                first = (response.tensor_sizes[r] if response.tensor_sizes
                         else a.size // max(int(np.prod(trailing)) or 1, 1))
                parts.append(a.reshape((first,) + trailing))
            e.output = np.concatenate(parts, axis=0)

    def _execute_reducescatter_host(self, entries) -> None:
        """Host reduce-scatter on the native half-ring kernel: w-1 ring
        steps moving one chunk each — (w-1)/w of the payload per link,
        the optimal byte count (the round-2 allreduce+slice fallback
        cost 2x; VERDICT r2 ask 6). The negotiation layer validated
        shape[0] %% world == 0, so the kernel's flat near-equal chunks
        coincide exactly with the leading-axis shards."""
        import numpy as np

        world = self.net.world
        hier_plan = self._hierarchy_plan()
        if hier_plan is None:
            # flat half-ring: (w-1) steps, each crossing the slow group
            # boundary (see _execute_allreduce_host on the seam)
            resilience.inject("ring", "reducescatter",
                              crossings=world - 1)
        from horovod_tpu.integrity import digest as integ_digest

        if self._integrity_due():
            # pre-reduce input digest (the ZeRO sharded-gradient lane):
            # each rank ends up holding a DIFFERENT shard, so there is
            # no replicated result to checksum — the agreement exchange
            # carries the non-finite counts only (constant CRC)
            nf_in = sum(integ_digest.nonfinite_count(np.asarray(e.tensor))
                        for e in entries)
            records = integ_digest.exchange(self.net, nf_in, 0)
            integ_digest.verify(records, bucket=f"rs[{len(entries)}]",
                                tensor=entries[0].name)
        for e in entries:
            a = np.asarray(e.tensor)
            wire = _widen_for_ring(a, copy=True)  # consumed as scratch
            if hier_plan is not None and wire.size % world == 0:
                # two-level reduce-scatter: j-major permutation + intra
                # RS + cross RS over 1/g of the bytes, same flat-chunk
                # output convention as the native kernel (ZeRO's shard
                # streams keep size % world == 0; ragged payloads fall
                # back to the flat ring per entry)
                from horovod_tpu.runtime import hierarchy

                chunk = hierarchy.hier_reducescatter(
                    self.net, hier_plan, wire.ravel(),
                    _RING_OP[e.reduce_op],
                    wire_dtype=self._hier_wire_dtype())
            else:
                chunk = self.net.reducescatter(wire.ravel(),
                                               _RING_OP[e.reduce_op])
            shard = a.shape[0] // world
            out = chunk.reshape((shard,) + a.shape[1:])
            if e.reduce_op == types.REDUCE_AVERAGE:
                out = out / world
            e.output = out.astype(a.dtype, copy=False)

    def _execute_alltoall_host(self, entries) -> None:
        """Host all-to-all on the native pairwise-exchange kernel: w-1
        rounds over the full mesh, every byte crossing exactly one link
        ((w-1)/w of the payload — the round-2 star-allgatherv fallback
        cost Wx; VERDICT r2 ask 6)."""
        import numpy as np

        resilience.inject("ring", "alltoall")
        for e in entries:
            a = np.ascontiguousarray(np.asarray(e.tensor))
            e.output = self.net.alltoall(a)

    def _execute_broadcast_host(self, entries) -> None:
        import numpy as np

        resilience.inject("ring", "broadcast")
        for e in entries:
            local = np.ascontiguousarray(np.asarray(e.tensor))
            blob = self.net.bcast_from(
                local.tobytes() if self.net.rank == e.root_rank else None,
                e.root_rank)
            e.output = np.frombuffer(
                blob, dtype=local.dtype).reshape(local.shape)
