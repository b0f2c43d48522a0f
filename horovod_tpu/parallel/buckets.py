"""Bucket-wise gradient release: overlap allreduce with backward.

The post-hoc exchange (``jax.value_and_grad`` then one
``allreduce_gradients`` call) serializes the whole backward pass in
front of the first wire byte — exactly the pattern the reference's
background loop was built to kill (reference: Sergeev & Del Balso 2018
§3, the framework hooks that submit each gradient as its op completes)
and that PyTorch DDP formalized as gradient buckets (Li et al., VLDB
2020 §4.2). This module is the TPU-native version of both: the
parameter tree is partitioned into fusion buckets in
**reverse-topological order** (last layer first — the order gradients
become final during backward), and each bucket's allreduce is released
as soon as its last gradient lands, so early buckets reduce on the
cycle thread while later layers are still differentiating.

Three lanes, matching the collectives module:

* **eager / multiprocess** — ``plan.tag(params)`` wraps every dense
  leaf in a ``custom_vjp`` identity whose backward hook runs as Python
  with the *concrete* cotangent, in backward order. When a bucket's
  last gradient arrives the whole bucket is enqueued atomically
  (:meth:`Runtime.enqueue_allreduce_group`) and reduces under the
  PR-3 dispatch/drain pipeline while backward continues.
  ``plan.gather(grads)`` then waits the handles in release order and
  splices the reduced values back into the tree.
* **shard_map (bound mesh axes)** — the hook is traced: it emits the
  leaf's ``lax.pmean``/``psum`` at its backward position and chains a
  scalar token through ``lax.optimization_barrier`` at every bucket
  boundary, so XLA cannot sink the collectives to the end of the
  program — the staged-interleave analogue of the eager release.
* **plain jit (no bound axes)** — identity, bit for bit: gradients of
  a global-mean loss are already the global average and the partitioner
  puts one all-reduce behind each weight-gradient product. Nothing the
  backward pass of the layers below computes depends on a weight
  gradient, so a hook on a parameter leaf has nothing to hold its
  reduction to; on this lane the exchange is released by the step
  program's own options (``training._exchange_options``: asynchronous
  collective fusions on a mesh of several TPU chips), and
  :func:`exchange_schedule` reads from a compiled step what became of
  it. Pins *inside* the differentiated program (a barrier tying each
  bucket to the activations' cotangent one bucket later) were built in
  PR 40 and showed no gain over the options alone on the chip (one
  reading at 8 of BERT-Large's 24 layers): PERF.md section 6.

``backward_passes_per_step > 1`` composes on the eager lane: the plan
owns the accumulation (``every_k``), buckets accumulate locally for
micro-batches ``1..k-1`` and only the final pass releases the
accumulated mean to the wire (reference: torch/__init__.py:82-143
semantics, moved to bucket granularity). Do not combine a plan with
``optax.MultiSteps`` — two accumulators double-count.

Correctness contract (mirrors the PR-3 fusion rules):

* bit-parity with the unbucketed path for sum/avg — the wire programs
  are the same size-bucketed fused reducers with the same
  reduction-identity padding, and elementwise reduction is oblivious
  to how leaves are packed into buckets;
* zero steady-state compiles — bucket shapes repeat every step, so
  after the first step every program comes from the PR-3 size-bucket
  cache (pinned by the ``_PROGRAM_COMPILES`` canary in tests);
* integrity digests ride unchanged — the digest cadence counts fused
  dispatches, and a bucketed step simply contributes one dispatch per
  bucket;
* a ``WorkersDownError`` mid-backward fails every in-flight bucket
  token (PR-3 ``_PendingOp.fail`` releases the fusion-buffer leases);
  :meth:`GradReleasePlan.gather` drains the remaining handles and
  resets, so the next generation starts clean.

ZeRO-2 composition: ``GradReleasePlan(reduce_scatter=True)`` releases
each bucket as a **reduce-scatter** instead of an allreduce — only the
local 1/N shard comes back ((N-1)/N bus bytes per payload byte, half an
allreduce) and ``gather()`` returns a ``zero.ShardedGrads`` that
``sharded_adamw`` / ``sharded_update`` consume directly. Build the
optimizer with ``partition=plan.zero_partition(params)`` so the shard
layouts line up. See ``parallel/zero.py``.

Knobs: ``HOROVOD_GRAD_BUCKET_BYTES`` (target bucket payload, default
4 MiB, rounded up to the fusion quantum), ``HOROVOD_GRAD_BUCKET_WIRE``
(``auto``/``off`` — whether single-controller replicated gradients are
shipped worker-stacked through the runtime so the release is a real
dispatch, or short-circuited to local math), and
``HOROVOD_GRAD_BUCKET_RELEASE`` (off by default, see
:func:`release_enabled`: ``1`` makes ``training.make_train_step`` build
a plan when it is given none). See docs/performance.md "backward
overlap".
"""

from __future__ import annotations

import os
import re
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu import comms
from horovod_tpu.analysis import witness
from horovod_tpu.utils import env as env_mod

DEFAULT_GRAD_BUCKET_BYTES = 4 * 1024 * 1024

_tls = threading.local()


def is_prereduced() -> bool:
    """True while the current thread is inside a :func:`prereduced`
    scope — gradients were already exchanged by a release plan and
    ``dp.allreduce_gradients`` must not reduce them again."""
    return getattr(_tls, "prereduced", False)


@contextmanager
def prereduced():
    """Mark gradients handed to ``DistributedOptimizer`` as already
    reduced (bucket-wise, during backward)."""
    prev = getattr(_tls, "prereduced", False)
    _tls.prereduced = True
    try:
        yield
    finally:
        _tls.prereduced = prev


def release_enabled() -> bool:
    """The ``HOROVOD_GRAD_BUCKET_RELEASE`` switch (default off: the
    unbucketed path stays the seed behavior unless opted in)."""
    return env_mod._get_bool("HOROVOD_GRAD_BUCKET_RELEASE", False)


# Autotuner override (runtime._autotune_sync): applies to release plans
# built AFTER the commit — an existing plan keeps its partition, since
# repartitioning mid-training would recompile every bucket program.
_autotuned_bucket_bytes = 0


def set_autotuned_bucket_bytes(nbytes: int) -> None:
    global _autotuned_bucket_bytes
    _autotuned_bucket_bytes = max(0, int(nbytes))


def bucket_bytes_from_env() -> int:
    """Target bucket payload: ``HOROVOD_GRAD_BUCKET_BYTES`` (or the
    autotuner's committed override, which wins while set) rounded up
    to a whole number of fusion quanta so bucket payloads land on the
    PR-3 size-bucket grid (zero steady-state compiles)."""
    raw = _autotuned_bucket_bytes or env_mod._get_int(
        "HOROVOD_GRAD_BUCKET_BYTES", DEFAULT_GRAD_BUCKET_BYTES)
    quantum = env_mod._get_int(env_mod.HOROVOD_FUSION_BUCKET_QUANTUM,
                               env_mod.DEFAULT_FUSION_BUCKET_QUANTUM_BYTES)
    quantum = max(1, quantum)
    raw = max(quantum, raw)
    return ((raw + quantum - 1) // quantum) * quantum


def _wire_mode() -> str:
    mode = (os.environ.get("HOROVOD_GRAD_BUCKET_WIRE", "auto")
            .strip().lower() or "auto")
    return mode if mode in ("auto", "off") else "auto"


def _leaf_nbytes(leaf) -> int:
    return int(np.prod(np.shape(leaf), dtype=np.int64)
               * np.dtype(leaf.dtype).itemsize)


# an array type in HLO text: ``bf16[1024,4096]``, ``pred[8]``, ``f8e4m3fn[4]``
_HLO_ARRAY = re.compile(r"\b(?:pred|[a-z]+(\d+)[a-z0-9]*)\[([0-9,]*)\]")
_HLO_ALL_REDUCE = re.compile(
    r" = (.+?) all-reduce(?:-start)?\(.*?channel_id=(\d+)")


def exchange_schedule(compiled_text: str) -> dict:
    """How a compiled step issues its gradient exchange, read from the
    program's own text (``compiled.as_text()``): ``sync_bytes`` are
    reduced by ``all-reduce`` instructions that hold the core's stream,
    ``async_bytes`` inside asynchronous collective fusions (the steps of
    one chain share a channel and carry its ``chain_id``; an
    ``all-reduce-start`` counts the same), ``reductions`` is how many
    there are of both. Bytes are those of the result, as the wire's
    dtype has them."""
    seen = {}   # channel -> (asynchronous, bytes)
    for line in compiled_text.splitlines():
        m = _HLO_ALL_REDUCE.search(line)
        if m is None:
            continue
        nbytes = sum(
            max(int(bits or 8) // 8, 1) * int(np.prod(
                [int(d) for d in dims.split(",") if d], dtype=np.int64))
            for bits, dims in _HLO_ARRAY.findall(m.group(1)))
        seen[m.group(2)] = ('chain_id="' in line
                            or " all-reduce-start(" in line, nbytes)
    return {"reductions": len(seen),
            "async_bytes": sum(n for a, n in seen.values() if a),
            "sync_bytes": sum(n for a, n in seen.values() if not a)}


class _Bucket:
    __slots__ = ("index", "leaves", "nbytes")

    def __init__(self, index: int, leaves: List[int], nbytes: int):
        self.index = index
        self.leaves = leaves  # leaf positions, reverse-topological order
        self.nbytes = nbytes


class GradReleasePlan:
    """Partition + release state for one model's gradient tree.

    Construct once per training setup and reuse across steps — the
    partition is computed lazily from the first tagged tree and the
    per-leaf hook closures are cached, so steady-state steps allocate
    nothing but the per-step bookkeeping dicts.
    """

    def __init__(self, *, bucket_bytes: Optional[int] = None,
                 every_k: int = 1, average: bool = True,
                 name: str = "grad", reduce_scatter: bool = False):
        if every_k < 1:
            raise ValueError("every_k must be >= 1")
        self.bucket_bytes = (bucket_bytes if bucket_bytes is not None
                             else bucket_bytes_from_env())
        self.every_k = every_k
        self.average = average
        self.name = name
        # ZeRO-2: release each bucket as a reduce-scatter and keep only
        # the local 1/N shard — gather() then returns a
        # zero.ShardedGrads for the sharded optimizer to consume
        # directly (half the gradient bus bytes of an allreduce)
        self.reduce_scatter = bool(reduce_scatter)
        # partition (filled by _ensure_partition on first tag)
        self._num_leaves: Optional[int] = None
        self._buckets: List[_Bucket] = []
        self._bucket_of: Dict[int, _Bucket] = {}
        self._tags: Dict[int, Any] = {}
        # per-backward-pass state (training thread only)
        self._grads: Dict[int, Any] = {}
        self._remaining: Dict[int, int] = {}   # bucket index -> leaves left
        self._accum: Dict[int, Any] = {}       # every_k partial sums
        self._pass_idx = 0
        self._step_id = 0
        # released wire state: (bucket, [(leaf, handle)]) in release order;
        # locally-reduced leaves land in _local instead of carrying handles
        self._released: List[tuple] = []
        self._local: Dict[int, Any] = {}
        # reduce-scatter mode: per-leaf shape/dtype metadata (for the
        # zero spec + zero-filling partial buckets), the bucket-aligned
        # ZeroSpec, its bucket->group map, and the per-group results
        self._leaf_meta: Dict[int, tuple] = {}
        self._zspec = None
        self._groups_of_bucket: Dict[int, List[int]] = {}
        self._rs_released: List[tuple] = []  # (bucket, [(gi, h)], t, B)
        self._shard_local: Dict[int, Any] = {}  # gi -> (W, shard)
        # traced-lane token for optimization_barrier chaining (valid only
        # within the enclosing trace; reset by tag())
        self._token = None
        # wire counters shared between the training thread (release) and
        # the runtime cycle thread (entry completion callbacks)
        self._wire_lock = witness.make_lock("GradReleasePlan._wire_lock")
        self._wire_released = 0   # guarded-by: _wire_lock
        self._wire_completed = 0  # guarded-by: _wire_lock
        self._wire_failed = 0     # guarded-by: _wire_lock

    # -- partition ----------------------------------------------------------
    def _ensure_partition(self, leaves) -> None:
        if self._num_leaves is not None:
            if len(leaves) != self._num_leaves:
                raise ValueError(
                    f"gradient tree changed shape: plan was built for "
                    f"{self._num_leaves} leaves, got {len(leaves)}")
            return
        self._num_leaves = len(leaves)
        dense = [i for i, leaf in enumerate(leaves)
                 if leaf is not None and hasattr(leaf, "dtype")]
        # reverse-topological: tree-flatten order follows model layer
        # order, so walking it backwards fronts the gradients that become
        # final first during backward
        order = list(reversed(dense))
        cur: List[int] = []
        cur_bytes = 0
        for i in order:
            cur.append(i)
            cur_bytes += _leaf_nbytes(leaves[i])
            if cur_bytes >= self.bucket_bytes:
                self._buckets.append(_Bucket(len(self._buckets), cur,
                                             cur_bytes))
                cur, cur_bytes = [], 0
        if cur:
            self._buckets.append(_Bucket(len(self._buckets), cur, cur_bytes))
        for b in self._buckets:
            for i in b.leaves:
                self._bucket_of[i] = b
                self._leaf_meta[i] = (tuple(np.shape(leaves[i])),
                                      np.dtype(leaves[i].dtype))

    def buckets(self) -> List[List[int]]:
        """The computed partition (leaf positions per bucket, release
        order) — empty before the first ``tag`` call."""
        return [list(b.leaves) for b in self._buckets]

    def zero_partition(self, params) -> List[List[int]]:
        """The bucket partition as a ``zero.build_spec`` partition —
        hand this to ``sharded_adamw(..., partition=...)`` /
        ``sharded_update(..., partition=...)`` so the optimizer's shard
        layout lines up 1:1 with the reduce-scatter release buckets."""
        leaves, _ = jax.tree_util.tree_flatten(params)
        self._ensure_partition(leaves)
        return self.buckets()

    def _ensure_zspec(self, st):
        """Bucket-aligned ZeroSpec (one dtype group per bucket cell),
        rebuilt when the world re-forms — bucket programs stay keyed on
        the spec, so a stable world means zero new compiles."""
        from horovod_tpu.ops import collectives
        from horovod_tpu.parallel import zero

        rank = (st.rank if collectives._multiprocess_world(st) else 0)
        spec = self._zspec
        if (spec is not None and spec.world == st.size
                and spec.rank == rank):
            return spec
        metas = [None] * (self._num_leaves or 0)
        for i, (shape, dtype) in self._leaf_meta.items():
            metas[i] = zero.LeafMeta(shape=shape, dtype=dtype)
        spec = zero.build_spec(metas, st.size, rank,
                               zero._quantum_bytes(st),
                               partition=self.buckets())
        self._groups_of_bucket = {}
        for gi, g in enumerate(spec.groups):
            b = self._bucket_of[g.indices[0]]
            self._groups_of_bucket.setdefault(b.index, []).append(gi)
        self._zspec = spec
        return spec

    # -- tagging ------------------------------------------------------------
    def _tag_for(self, i: int):
        tag = self._tags.get(i)
        if tag is not None:
            return tag

        @jax.custom_vjp
        def _tag(x):
            return x

        def _fwd(x):
            return x, None

        def _bwd(_res, g):
            return (self._on_grad(i, g),)

        _tag.defvjp(_fwd, _bwd)
        self._tags[i] = _tag
        return _tag

    def tag(self, params):
        """Wrap every dense leaf of ``params`` in its release hook.

        Call inside the loss closure, on the argument being
        differentiated — the hooks then see each leaf's cotangent the
        moment backward finishes it. Also resets the per-pass state, so
        one forward/backward == one pass."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        self._ensure_partition(leaves)
        self._begin_pass()
        out = [leaf if i not in self._bucket_of
               else self._tag_for(i)(leaf)
               for i, leaf in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _begin_pass(self) -> None:
        self._grads.clear()
        self._remaining = {b.index: len(b.leaves) for b in self._buckets}
        self._token = None
        if self._pass_idx == 0:
            self._released = []
            self._local = {}

    # -- backward hooks -----------------------------------------------------
    def _on_grad(self, i: int, g):
        if isinstance(g, jax.core.Tracer):
            return self._on_grad_traced(i, g)
        bucket = self._bucket_of[i]
        self._grads[i] = g
        self._remaining[bucket.index] -= 1
        if self._remaining[bucket.index] == 0:
            self._bucket_ready(bucket)
        return g

    def _on_grad_traced(self, i: int, g):
        from horovod_tpu.parallel import dp as dp_mod

        axes = dp_mod._bound_axes(None)
        bucket = self._bucket_of[i]
        self._remaining[bucket.index] -= 1
        boundary = self._remaining[bucket.index] == 0
        if not axes:
            # plain jit global-batch DP: gradients are already the global
            # average (XLA inserts the collective from the shardings) and
            # a hook on a leaf has nothing to hold them to (module
            # docstring): the step program's options do this lane's work
            return g
        from jax import lax

        r = dp_mod._reduce_traced(g, axes, self.average)
        if boundary:
            # chain a token through the barrier at every bucket boundary:
            # the data dependency serializes the boundaries, so XLA keeps
            # each bucket's collectives at their backward position instead
            # of sinking them all to the end of the program
            if self._token is None:
                self._token = jnp.zeros((), jnp.float32)
            self._token, r = lax.optimization_barrier((self._token, r))
        return r

    def _bucket_ready(self, bucket: _Bucket) -> None:
        values = {i: self._grads.pop(i) for i in bucket.leaves}
        if self._pass_idx + 1 < self.every_k:
            # intermediate micro-batch: accumulate locally, nothing on the
            # wire (constraint: only the final micro-batch releases)
            for i, v in values.items():
                prev = self._accum.get(i)
                self._accum[i] = v if prev is None else prev + v
            return
        if self.every_k > 1:
            inv_k = 1.0 / self.every_k
            for i in list(values):
                prev = self._accum.pop(i, None)
                total = values[i] if prev is None else prev + values[i]
                values[i] = total * np.asarray(inv_k, dtype=total.dtype)
        self._release(bucket, values)

    # -- wire ---------------------------------------------------------------
    def _release(self, bucket: _Bucket, values: Dict[int, Any]) -> None:
        from horovod_tpu.core import basics
        from horovod_tpu.ops import collectives

        if self.reduce_scatter:
            return self._release_reduce_scatter(bucket, values)
        st = basics._ensure_init()
        reduce_op = "average" if self.average else "sum"
        wire_idx: List[int] = []
        tensors: List[Any] = []
        names: List[str] = []
        multiproc = (collectives._multiprocess_world(st)
                     and collectives._runtime_capable(st))
        for i in bucket.leaves:
            x = values[i]
            name = (f"grad_bucket.{self.name}.{self._step_id}"
                    f".b{bucket.index}.{i}")
            if multiproc:
                wire_idx.append(i)
                tensors.append(collectives._to_plane(x))
                names.append(name)
            elif collectives._is_worker_stacked(x):
                wire_idx.append(i)
                tensors.append(x)
                names.append(name)
            elif st.size > 1 and _wire_mode() != "off":
                # single-controller replicated gradient: ship it
                # worker-stacked through the runtime so the release is a
                # real pipelined dispatch (the "simulated multi-lane"
                # measurement mode). The splice still uses the locally
                # exact value (_local wins over the wire result in
                # gather): a sequential reduction over identical rows can
                # round 1 ULP, and bucketed must stay bit-identical to
                # the unbucketed local shortcut.
                stacked = collectives.stack_per_worker(
                    jnp.broadcast_to(jnp.asarray(x),
                                     (st.size,) + tuple(np.shape(x))))
                wire_idx.append(i)
                tensors.append(stacked)
                names.append(name)
                self._local[i] = x if self.average else x * st.size
            else:
                # 1-worker world (or wire=off): same local math as the
                # unbucketed replicated path
                self._local[i] = x if self.average else x * st.size
        if not wire_idx:
            return
        handles = collectives.grouped_allreduce_async(
            tensors, names=names, reduce_op=reduce_op,
            priority=len(self._buckets) - bucket.index,
            group_callback=self._on_wire_complete)
        with self._wire_lock:
            self._wire_released += len(handles)
        wire_bytes = sum(
            int(np.prod(np.shape(t), dtype=np.int64)
                * np.dtype(t.dtype).itemsize) for t in tensors)
        self._released.append((bucket.index,
                               list(zip(wire_idx, handles)),
                               time.monotonic(), wire_bytes))

    def _release_reduce_scatter(self, bucket: _Bucket,
                                values: Dict[int, Any]) -> None:
        """ZeRO-2 release: pack the bucket's dtype groups and
        reduce-scatter each one — only the local 1/N shard comes back.
        Multi-process rides the runtime's reduce-scatter lane under
        stable per-group names; single-controller replicated takes the
        same local short-circuit (and the same bits) as the stage-1
        eager path via a cached worker-sharded program."""
        from horovod_tpu.core import basics
        from horovod_tpu.ops import collectives
        from horovod_tpu.parallel import zero

        st = basics._ensure_init()
        spec = self._ensure_zspec(st)
        multiproc = (collectives._multiprocess_world(st)
                     and collectives._runtime_capable(st))
        if collectives._multiprocess_world(st) and not multiproc:
            raise NotImplementedError(
                "reduce-scatter gradient release in a multi-process "
                "world needs the enqueue runtime (tpurun / HOROVOD_RANK "
                "env contract)")
        pairs: List[tuple] = []
        wire_bytes = 0
        for gi in self._groups_of_bucket.get(bucket.index, []):
            g = spec.groups[gi]
            vals = {}
            for li, shape, _size in zip(g.indices, g.shapes, g.sizes):
                v = values.get(li)
                if v is None:
                    # partial bucket (a leaf produced no cotangent):
                    # zeros are the reduction identity
                    v = np.zeros(shape, np.dtype(g.dtype))
                vals[li] = v
            nbytes = g.padded * np.dtype(g.dtype).itemsize
            zero._RS_BYTES.inc(int(nbytes))
            # bucket_wire convention matches the allreduce release: the
            # multi-process lane counts per-rank tensor bytes; the
            # single-controller simulated wire counts the whole (W, n)
            # plane — so the stage-2 bus ratio vs the allreduce baseline
            # reads exactly 0.5 off the ledger in either mode
            wire_bytes += int(nbytes) * (1 if multiproc else st.size)
            if multiproc:
                op_name = collectives._OP_NAMES[
                    collectives.Average if self.average
                    else collectives.Sum]
                from horovod_tpu.runtime.runtime import get_runtime

                flat = zero._np_pack_group(vals, g)
                h = get_runtime().enqueue_reducescatter(
                    f"zero2.{self.name}.b{bucket.index}.g{gi}",
                    jnp.asarray(flat), reduce_op=op_name,
                    priority=len(self._buckets) - bucket.index)
                pairs.append((gi, h))
            else:
                stacked_flags = [
                    collectives._is_worker_stacked(
                        collectives._to_plane(vals[li]))
                    for li in g.indices]
                if any(stacked_flags) and not all(stacked_flags):
                    raise ValueError(
                        "reduce-scatter release needs a bucket's leaves "
                        "uniformly worker-stacked or uniformly "
                        "replicated, got a mix")
                self._shard_local[gi] = zero.scatter_bucket_group(
                    vals, spec, gi, st, average=self.average,
                    stacked=all(stacked_flags))
        if pairs:
            with self._wire_lock:
                self._wire_released += len(pairs)
        self._rs_released.append((bucket.index, pairs, time.monotonic(),
                                  wire_bytes))

    def _on_wire_complete(self, ok: bool) -> None:
        # runs on the runtime cycle thread as each entry completes/fails
        with self._wire_lock:
            self._wire_completed += 1
            if not ok:
                self._wire_failed += 1

    def wire_stats(self) -> dict:
        with self._wire_lock:
            return {"released": self._wire_released,
                    "completed": self._wire_completed,
                    "failed": self._wire_failed}

    # -- gather -------------------------------------------------------------
    def gather(self, grads):
        """Splice the reduced buckets back into the gradient tree.

        Eager: waits each released handle in release order (the first
        buckets are usually already drained — that wait is the overlap
        win) and returns the reduced tree. With ``every_k > 1`` the
        intermediate passes return ``None`` (nothing to apply yet).
        Traced: identity — the hooks already emitted the staged
        collectives in place. On a ``WorkersDownError`` (or any wire
        failure) every remaining handle is drained and the per-step
        state reset before the error propagates, so an elastic re-form
        can retry the step on the plan unchanged."""
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if any(isinstance(g, jax.core.Tracer) for g in leaves):
            return grads
        if self._pass_idx + 1 < self.every_k:
            self._pass_idx += 1
            return None
        self._flush()
        if self.reduce_scatter:
            return self._gather_shards()
        from horovod_tpu.ops import collectives

        out = list(leaves)
        failure = None
        for _bucket_idx, pairs, t_release, wire_bytes in self._released:
            bucket_ok = bool(pairs)
            for i, h in pairs:
                try:
                    out[i] = collectives.synchronize(h)
                except Exception as exc:  # drain the rest before raising
                    bucket_ok = False
                    if failure is None:
                        failure = exc
            if bucket_ok:
                # comms plane "bucket_wire" lane: one record per released
                # bucket, release→drain wall time over the bucket's wire
                # payload (docs/comms.md) — the end-to-end view next to
                # the carrying lane's per-dispatch records
                comms.record("allreduce", "bucket_wire", wire_bytes,
                             time.monotonic() - t_release)
        for i, v in self._local.items():
            out[i] = v
        self._reset_step()
        if failure is not None:
            raise failure
        return jax.tree_util.tree_unflatten(treedef, out)

    def _gather_shards(self):
        """Drain the per-bucket reduce-scatters in release order and
        assemble the :class:`zero.ShardedGrads` the sharded optimizer
        consumes directly — the full-gradient buffer is never
        reassembled. One ``bucket_wire`` comms record per bucket
        (op=reducescatter: the ledger's busbw math charges (N-1)/N bus
        bytes per payload byte — half an allreduce's 2(N-1)/N)."""
        from horovod_tpu.ops import collectives
        from horovod_tpu.parallel import zero

        spec = self._zspec
        if spec is None:  # no bucket ever released (empty tree)
            from horovod_tpu.core import basics

            spec = self._ensure_zspec(basics._ensure_init())
        shards: List[Any] = [None] * len(spec.groups)
        failure = None
        for _bucket_idx, pairs, t_release, wire_bytes in self._rs_released:
            bucket_ok = True
            for gi, h in pairs:
                try:
                    out = collectives.synchronize(h)
                    shards[gi] = jnp.asarray(out).astype(
                        np.dtype(spec.groups[gi].dtype))
                except Exception as exc:  # drain the rest first
                    bucket_ok = False
                    if failure is None:
                        failure = exc
            if bucket_ok and wire_bytes:
                comms.record("reducescatter", "bucket_wire", wire_bytes,
                             time.monotonic() - t_release,
                             world=spec.world)
        for gi, s in self._shard_local.items():
            shards[gi] = s
        from horovod_tpu.core import basics

        mp = collectives._multiprocess_world(basics._ensure_init())
        for gi, s in enumerate(shards):
            if s is None:
                # a whole bucket produced no cotangents and was never
                # released — its shard is the reduction identity
                g = spec.groups[gi]
                shape = ((g.shard_elems,) if mp
                         else (spec.world, g.shard_elems))
                shards[gi] = jnp.zeros(shape, np.dtype(g.dtype))
        self._reset_step()
        if failure is not None:
            raise failure
        zero._set_shard_bytes("grad_shards", shards, spec.world)
        return zero.ShardedGrads(spec, tuple(shards))

    def _flush(self) -> None:
        """Release any buckets whose countdown never hit zero (a leaf
        that produced no cotangent — e.g. an unused parameter). Partial
        buckets go to the wire with the gradients that did arrive."""
        for b in self._buckets:
            if self._remaining.get(b.index, 0) > 0 and any(
                    i in self._grads for i in b.leaves):
                values = {i: self._grads.pop(i) for i in b.leaves
                          if i in self._grads}
                if self._pass_idx + 1 >= self.every_k:
                    self._release(b, values)
                else:
                    for i, v in values.items():
                        prev = self._accum.get(i)
                        self._accum[i] = v if prev is None else prev + v

    def _reset_step(self) -> None:
        self._pass_idx = 0
        self._step_id += 1
        self._grads.clear()
        self._accum.clear()
        self._released = []
        self._local = {}
        self._rs_released = []
        self._shard_local = {}
        self._token = None

    def abort(self) -> None:
        """Drain every in-flight handle (ignoring errors) and reset —
        for callers that abandon a step without gathering (elastic
        re-form paths). An elastic reform also invalidates the
        bucket-aligned zero spec (the world changed), so it is dropped
        and lazily rebuilt on the next release."""
        for _bucket_idx, pairs, _t_release, _wire_bytes in (
                list(self._released) + list(self._rs_released)):
            for _i, h in pairs:
                try:
                    h.wait()
                except Exception:
                    pass
        self._zspec = None
        self._reset_step()
