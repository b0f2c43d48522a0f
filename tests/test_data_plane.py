"""Pipelined data-plane tests: size-bucketed program cache, identity
padding, persistent fusion buffers, and cycle pipelining.

The load-bearing guarantees: (1) padding a fused payload to its size
bucket never changes the reduced bits for any (reduce op, dtype) pair;
(2) steady-state cycles over the same named tensors hit the compiled
program cache even when bin-packing regroups them (zero new XLA compiles
after warmup — the acceptance criterion for the pipelined data plane);
(3) host staging slabs are reused, not reallocated, across cycles.
"""

import contextlib

import numpy as np
import pytest

import ml_dtypes

from horovod_tpu.runtime import fusion_buffer as fb
from horovod_tpu.runtime import message as msg, types
from horovod_tpu.runtime.fusion_buffer import (FusionBufferManager,
                                               bucket_elems, reduce_identity)


class TestBucketPolicy:
    def test_identity_below_quantum(self):
        # payloads at or under the quantum keep their exact size
        assert bucket_elems(10, 4, 64 * 1024) == 10
        assert bucket_elems(16384, 4, 64 * 1024) == 16384  # exactly 64 KiB

    def test_power_of_two_above_quantum(self):
        q = 64 * 1024
        assert bucket_elems(16385, 4, q) == (2 * q) // 4
        assert bucket_elems(40000, 4, q) == (4 * q) // 4  # 160000B -> 256KiB

    def test_distinct_sizes_share_a_bucket(self):
        # the collapse that makes regrouped bins reuse one program
        assert bucket_elems(300, 4, 256) == bucket_elems(400, 4, 256) == 512

    def test_quantum_zero_disables_bucketing(self):
        assert bucket_elems(12345, 4, 0) == 12345

    def test_ceil_when_itemsize_does_not_divide(self):
        # 3 * 100 = 300B > 256 -> 512B bucket -> ceil(512/3) = 171 elems
        assert bucket_elems(100, 3, 256) == 171

    def test_reduce_identities(self):
        assert reduce_identity(np.float32, types.REDUCE_SUM) == 0.0
        assert reduce_identity(np.int32, types.REDUCE_AVERAGE) == 0
        assert reduce_identity(np.float32, types.REDUCE_PRODUCT) == 1.0
        assert reduce_identity(np.float32, types.REDUCE_MIN) == np.inf
        assert reduce_identity(np.float32, types.REDUCE_MAX) == -np.inf
        assert (reduce_identity(np.int32, types.REDUCE_MIN)
                == np.iinfo(np.int32).max)
        assert (reduce_identity(np.int32, types.REDUCE_MAX)
                == np.iinfo(np.int32).min)
        bf16 = np.dtype(ml_dtypes.bfloat16)
        assert reduce_identity(bf16, types.REDUCE_MIN) == np.inf
        assert reduce_identity(bf16, types.REDUCE_SUM) == 0
        with pytest.raises(ValueError):
            reduce_identity(np.float32, "median")

    def test_identity_keeps_dtype(self):
        for dt in (np.float32, np.int32, np.dtype(ml_dtypes.bfloat16)):
            for op in types.REDUCE_OPS:
                assert np.asarray(reduce_identity(dt, op)).dtype == dt


class TestFusionBufferManager:
    def test_reuse_after_release(self):
        mgr = FusionBufferManager(256)
        allocs0 = fb._BUF_ALLOCS.value
        lease = mgr.acquire(2, 300, np.float32)
        assert lease.array.shape == (2, 512)  # 1200B -> 2048B bucket
        assert mgr.live_bytes() == lease.array.nbytes
        assert mgr.leases_outstanding() == 1
        mgr.release(lease)
        assert mgr.live_bytes() == 0
        assert mgr.leases_outstanding() == 0
        again = mgr.acquire(2, 400, np.float32)  # same bucket, reused
        assert again.array is lease.array
        assert fb._BUF_ALLOCS.value - allocs0 == 1
        assert mgr.live_bytes() == again.array.nbytes
        mgr.release(again)
        assert mgr.live_bytes() == 0

    def test_outstanding_leases_get_distinct_slabs(self):
        mgr = FusionBufferManager(256)
        a = mgr.acquire(1, 100, np.float32)
        b = mgr.acquire(1, 100, np.float32)  # a still leased (pipelining)
        assert a.array is not b.array
        assert mgr.leases_outstanding() == 2
        assert mgr.live_bytes() == a.array.nbytes + b.array.nbytes
        mgr.release(a)
        mgr.release(b)
        assert mgr.leases_outstanding() == 0
        assert mgr.live_bytes() == 0

    def test_allocated_bytes_tracks_slabs(self):
        mgr = FusionBufferManager(0)  # identity buckets
        lease = mgr.acquire(4, 10, np.float32)
        assert mgr.allocated_bytes() == 4 * 10 * 4
        mgr.release(lease)
        reuse = mgr.acquire(4, 10, np.float32)
        assert mgr.allocated_bytes() == 4 * 10 * 4  # no second slab
        mgr.release(reuse)

    def test_release_is_idempotent(self):
        # the memory plane's live-bytes gauge must not go negative when a
        # failure path and a finally block both release the same lease
        mgr = FusionBufferManager(256)
        lease = mgr.acquire(1, 100, np.float32)
        mgr.release(lease)
        mgr.release(lease)  # no-op, not a double decrement
        assert mgr.live_bytes() == 0
        assert mgr.leases_outstanding() == 0

    def test_bytes_by_purpose_ledger(self):
        mgr = FusionBufferManager(256, purpose="fusion")
        stage = FusionBufferManager(256, purpose="ckpt_staging")
        lease = mgr.acquire(1, 100, np.float32)
        ledger = fb.bytes_by_purpose()
        assert ledger["fusion"]["live_bytes"] >= lease.array.nbytes
        assert ledger["fusion"]["leases_outstanding"] >= 1
        assert "ckpt_staging" in ledger
        assert ledger["ckpt_staging"]["live_bytes"] == 0
        mgr.release(lease)
        assert stage.live_bytes() == 0


_AB_CASES = [(op, dt)
             for op in (types.REDUCE_SUM, types.REDUCE_AVERAGE,
                        types.REDUCE_MIN, types.REDUCE_MAX,
                        types.REDUCE_PRODUCT)
             for dt in ("float32", "bfloat16", "int32")]


class TestPaddingCorrectness:
    """Padded fused allreduce must bit-match the unpadded result for every
    (reduce op, dtype) pair — the pad columns carry the reduction identity
    and are sliced off before unpack."""

    def _run_fused(self, hvd, executor, op, dtype, quantum, tag):
        rng = np.random.RandomState(7)
        dt = np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16" \
            else np.dtype(dtype)
        entries = []
        for j, n in enumerate((5, 3, 9)):  # odd sizes -> real padding
            if dt.kind == "i":
                vals = [rng.randint(-50, 50, size=(n,)).astype(dt)
                        for _ in range(hvd.size())]
            else:
                vals = [(rng.randn(n) * 3).astype(dt)
                        for _ in range(hvd.size())]
            entries.append(types.TensorTableEntry(
                name=f"pad/{tag}/{op}/{dtype}/t{j}",
                tensor=hvd.stack_per_worker(vals), reduce_op=op))
        saved = executor.fusion_buffers
        executor.fusion_buffers = FusionBufferManager(quantum)
        try:
            executor.execute(
                msg.Response(types.ALLREDUCE, [e.name for e in entries]),
                entries)
        finally:
            executor.fusion_buffers = saved
        for e in entries:
            assert e.output is not None, f"{e.name} did not complete"
        return [np.asarray(e.output) for e in entries]

    @pytest.mark.parametrize("op,dtype", _AB_CASES)
    def test_padded_bitmatches_unpadded(self, hvd, op, dtype):
        from horovod_tpu.runtime.runtime import get_runtime

        ex = get_runtime().executor
        # quantum 16B: every payload rounds up to a power of two (padded);
        # quantum 1<<30: identity bucketing (never padded)
        padded = self._run_fused(hvd, ex, op, dtype, 16, "q16")
        exact = self._run_fused(hvd, ex, op, dtype, 1 << 30, "exact")
        for a, b in zip(padded, exact):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)


class TestSteadyStateProgramCache:
    """Acceptance criterion: same named tensors every cycle, varying fused
    bins -> zero new XLA compiles after warmup, observed through
    horovod_executor_program_compiles_total."""

    def _one_cycle(self, hvd, rt, threshold_bytes, step):
        """Enqueue 4 named tensors inside one held cycle, then release the
        loop with ``fusion_threshold_bytes`` set so bin-packing groups
        them as the threshold dictates. Returns the reduced values."""
        from horovod_tpu.core import state

        st = state.global_state()
        saved_thresh = st.config.fusion_threshold_bytes
        real_cycle = rt.run_cycle
        rt.run_cycle = lambda: True  # hold: queue everything first
        try:
            st.config.fusion_threshold_bytes = threshold_bytes
            handles = [
                hvd.allreduce_async(
                    hvd.stack_per_worker(
                        [np.full((300,), float(i + j + step), "float32")
                         for i in range(hvd.size())]),
                    name=f"steady/t{j}")
                for j in range(4)]
        finally:
            rt.run_cycle = real_cycle
            rt._woken.set()
        outs = [np.asarray(hvd.synchronize(h)) for h in handles]
        st.config.fusion_threshold_bytes = saved_thresh
        for j, out in enumerate(outs):
            expected = np.mean([i + j + step for i in range(hvd.size())])
            np.testing.assert_allclose(out, np.full((300,), expected),
                                       rtol=1e-6)
        return outs

    def _steady_cycles(self, hvd, rt, around=contextlib.nullcontext,
                       after=lambda outs: None):
        """Regrouped bins {t0,t1},{t2,t3} (never seen in the warmup) plus
        the warmup grouping again; the reduced values of every cycle."""
        values = []
        for step, threshold in ((1, 20000), (2, 20000), (3, 20000),
                                (4, 30000)):
            with around():
                outs = self._one_cycle(hvd, rt, threshold_bytes=threshold,
                                       step=step)
            after(outs)
            values.extend(outs)
        return values

    @staticmethod
    def _switch_on(plane, monkeypatch):
        """Turn one telemetry plane on the way its users do. Returns what
        brackets a cycle, what follows it, and a count of what the plane
        has seen since (so a plane that never ran cannot pass)."""
        from horovod_tpu import comms, goodput, memory, profiler
        from horovod_tpu.integrity import digest as integ_digest

        around, after = contextlib.nullcontext, lambda outs: None
        if plane == "integrity":  # a digest at every dispatch
            monkeypatch.setenv("HOROVOD_INTEGRITY", "1")
            monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
            count = lambda: integ_digest._CHECKS.value
        elif plane == "memory":   # the per-step push and one sweep
            tracker = memory.tracker()
            tracker.enabled = True

            def after(outs):
                tracker.note_tree_bytes("grads", outs)
                tracker.sample()
            count = lambda: memory._SAMPLES.value  # the ring is bounded
        elif plane == "comms":
            tracker = comms.tracker()
            tracker.enabled = True
            count = lambda: sum(lane["ops_total"] for lane in
                                tracker.ledger()["lanes"].values())
        elif plane == "goodput":  # its step hook rides the profiler's
            tracker = goodput.tracker()
            tracker.enabled = profiler._profiler.enabled = True
            around = profiler.step

            def count():  # seconds accounted, productive or badput
                ledger = tracker.ledger()
                return (ledger["productive_seconds"]
                        + sum(ledger["badput_seconds"].values()))
        start = count()
        return around, after, lambda: count() - start

    @pytest.mark.parametrize(
        "plane", ["none", "integrity", "memory", "comms", "goodput"])
    def test_varying_bins_zero_compiles_after_warmup(self, hvd, monkeypatch,
                                                     plane):
        """Steady-state cycles compile nothing, with no telemetry plane
        and with each one switched on after the warmup; a plane watches
        the wire and the clock, so the reduced values stay bit-identical
        to the plane-off values."""
        from horovod_tpu import comms, goodput, memory, profiler
        from horovod_tpu.runtime import executor as ex_mod
        from horovod_tpu.runtime.runtime import get_runtime

        rt = get_runtime()
        # small quantum so the 4x(8,300) float32 tensors exercise real
        # power-of-two buckets: a 3-tensor bin (3600B/row) and a 2-tensor
        # bin (2400B/row) both land in the 4096B bucket
        monkeypatch.setattr(rt.executor, "fusion_buffers",
                            FusionBufferManager(256))
        for tracker in (memory.tracker(), comms.tracker(),
                        goodput.tracker(), profiler._profiler):
            monkeypatch.setattr(tracker, "enabled", False)
        monkeypatch.delenv("HOROVOD_INTEGRITY", raising=False)
        if plane == "integrity":
            # the per-bucket digest is a compiled program of its own: it
            # belongs to the warmup, and the plane goes off again for the
            # reference values
            self._switch_on(plane, monkeypatch)
        # warmup: one grouping {t0,t1,t2},{t3} compiles the 4096B and
        # 2048B buckets (per-tensor request is 8*300*4 = 9600B)
        self._one_cycle(hvd, rt, threshold_bytes=30000, step=0)
        monkeypatch.delenv("HOROVOD_INTEGRITY", raising=False)
        compiles_after_warmup = ex_mod._PROGRAM_COMPILES.value
        hits0 = ex_mod._PROGRAM_CACHE_HITS.value
        allocs0 = fb._BUF_ALLOCS.value

        plane_off = self._steady_cycles(hvd, rt)
        if plane == "none":
            plane_on = self._steady_cycles(hvd, rt)
        else:
            around, after, seen = self._switch_on(plane, monkeypatch)
            plane_on = self._steady_cycles(hvd, rt, around, after)
            assert seen() > 0, f"the {plane} plane saw no steady cycle"

        assert ex_mod._PROGRAM_COMPILES.value == compiles_after_warmup, \
            "steady-state cycles must not trigger new XLA compiles"
        assert ex_mod._PROGRAM_CACHE_HITS.value > hits0
        # the single-controller path packs on device: sharded gradients
        # never stage through (or allocate) host fusion-buffer slabs
        assert fb._BUF_ALLOCS.value == allocs0, \
            "device-path cycles must not allocate host staging slabs"
        for off, on in zip(plane_off, plane_on):
            np.testing.assert_array_equal(off, on)

    @pytest.mark.parametrize("depth", [1, 3])
    def test_pipeline_depth_preserves_results(self, hvd, monkeypatch, depth):
        from horovod_tpu.core import state
        from horovod_tpu.runtime import runtime as rt_mod
        from horovod_tpu.runtime.runtime import get_runtime

        rt = get_runtime()
        monkeypatch.setattr(state.global_state().config,
                            "cycle_pipeline_depth", depth)
        # multi-bin cycle (threshold fits 2 of the 9600B requests)
        self._one_cycle(hvd, rt, threshold_bytes=20000, step=10 + depth)
        assert rt_mod._PIPELINE_DEPTH.value == 0  # drained


class TestDeviceResidency:
    """The single-controller fused allreduce must stay on device end to
    end: inputs are sharded jax.Arrays and outputs come back as replicated
    jax.Arrays — never host numpy round-trips on the hot path."""

    def test_outputs_are_replicated_jax_arrays(self, hvd):
        import jax

        from horovod_tpu.runtime.runtime import get_runtime

        ex = get_runtime().executor
        entries = [types.TensorTableEntry(
            name=f"resid/t{j}",
            tensor=hvd.stack_per_worker(
                [np.full((7,), float(i + j), "float32")
                 for i in range(hvd.size())]),
            reduce_op=types.REDUCE_SUM) for j in range(3)]
        saved = ex.fusion_buffers
        ex.fusion_buffers = FusionBufferManager(16)  # force real padding
        try:
            allocs0 = fb._BUF_ALLOCS.value
            ex.execute(msg.Response(types.ALLREDUCE,
                                    [e.name for e in entries]), entries)
        finally:
            ex.fusion_buffers = saved
        assert fb._BUF_ALLOCS.value == allocs0  # no host staging slabs
        for j, e in enumerate(entries):
            assert isinstance(e.output, jax.Array), \
                "single-controller allreduce must return device arrays"
            assert e.output.sharding.is_fully_replicated
            np.testing.assert_allclose(
                np.asarray(e.output),
                np.full((7,), sum(i + j for i in range(hvd.size())),
                        "float32"))


class _FailingNet:
    """Ring stub whose allreduce always loses the transport."""

    world = 2
    rank = 0

    def allreduce(self, buf, op):
        raise RuntimeError("ring transport lost")


class TestLeaseLifecycle:
    """Fusion-buffer leases must come back on every failure path —
    transient faults (routine under elastic) must not grow host memory."""

    def _slabs_free(self, mgr):
        return sum(len(v) for v in mgr._free.values())

    def test_host_ring_failure_releases_lease(self, hvd):
        from horovod_tpu.core import state
        from horovod_tpu.runtime.executor import Executor

        ex = Executor(state.global_state().mesh, net=_FailingNet())
        ex.fusion_buffers = FusionBufferManager(256)
        entries = [types.TensorTableEntry(
            name="leak/ring", tensor=np.ones((10,), "float32"),
            reduce_op=types.REDUCE_SUM)]
        with pytest.raises(RuntimeError):
            ex._execute_allreduce_host(entries)
        assert self._slabs_free(ex.fusion_buffers) == 1, \
            "slab must return to the free list when the ring raises"
        assert ex.fusion_buffers.live_bytes() == 0, \
            "live-bytes gauge must drop back to baseline on failure"
        assert ex.fusion_buffers.leases_outstanding() == 0

    def test_token_fail_releases_lease(self, hvd):
        from horovod_tpu.core import state
        from horovod_tpu.runtime import executor as ex_mod

        ex = ex_mod.Executor(state.global_state().mesh)
        ex.fusion_buffers = FusionBufferManager(256)
        lease = ex.fusion_buffers.acquire(1, 100, np.float32)
        entry = types.TensorTableEntry(name="leak/tok",
                                       tensor=np.ones((4,), "float32"))
        tok = ex_mod._PendingOp(ex, types.ALLREDUCE, [entry], None)
        tok.lease = lease
        tok.fail(types.Status.UnknownError("cycle aborted"))
        assert tok.lease is None
        assert self._slabs_free(ex.fusion_buffers) == 1, \
            "failing a pending token must release its slab lease"
        assert ex.fusion_buffers.live_bytes() == 0
        # idempotent: a second fail must not double-release
        tok.fail(types.Status.UnknownError("again"))
        assert self._slabs_free(ex.fusion_buffers) == 1
        assert ex.fusion_buffers.live_bytes() == 0
        assert ex.fusion_buffers.leases_outstanding() == 0

    @pytest.mark.parametrize("with_net", [True, False])
    def test_a_lost_peer_aborts_the_ring_and_a_numerical_verdict_does_not(
            self, hvd, with_net):
        """A rank that records a data-plane ``WorkersDownError`` shuts its
        links (``NetComm.abort``), once, so that a peer still blocked in
        the same ring collective fails too and does not wait for ever; a
        ``NumericalError`` touches neither the links nor ``failure``: the
        runtime survives the rollback. No host ring (``net`` None, every
        cell) is tolerated."""
        from horovod_tpu import exceptions
        from horovod_tpu.core import state
        from horovod_tpu.runtime import executor as ex_mod

        class _Net:
            aborts = 0

            def abort(self):
                self.aborts += 1

        def failed_by(ex, exc):
            entry = types.TensorTableEntry(name="ring/tok",
                                           tensor=np.ones((4,), "float32"))
            tok = ex_mod._PendingOp(ex, types.ALLREDUCE, [entry], None)
            tok.fail_exc(exc)
            assert tok.done

        ex = ex_mod.Executor(state.global_state().mesh)
        ex.net = net = _Net() if with_net else None
        bad = exceptions.NumericalError("non-finite bucket")
        failed_by(ex, bad)
        assert ex.integrity_failure is bad and ex.failure is None
        assert not with_net or net.aborts == 0
        lost = exceptions.WorkerLostError("peer closed", ranks=[1])
        failed_by(ex, lost)
        assert ex.failure is lost
        assert not with_net or net.aborts == 1
        # the first loss stands, and the links are shut once
        failed_by(ex, exceptions.WorkerLostError("again", ranks=[1]))
        assert ex.failure is lost
        assert not with_net or net.aborts == 1


class TestKnobParsing:
    def test_defaults(self, monkeypatch):
        from horovod_tpu.utils import env

        monkeypatch.delenv(env.HOROVOD_CYCLE_PIPELINE_DEPTH, raising=False)
        monkeypatch.delenv(env.HOROVOD_FUSION_BUCKET_QUANTUM, raising=False)
        cfg = env.Config.from_env()
        assert cfg.cycle_pipeline_depth == 2
        assert cfg.fusion_bucket_quantum == 64 * 1024

    def test_overrides(self, monkeypatch):
        from horovod_tpu.utils import env

        monkeypatch.setenv(env.HOROVOD_CYCLE_PIPELINE_DEPTH, "4")
        monkeypatch.setenv(env.HOROVOD_FUSION_BUCKET_QUANTUM, "1024")
        cfg = env.Config.from_env()
        assert cfg.cycle_pipeline_depth == 4
        assert cfg.fusion_bucket_quantum == 1024
