"""Bucket-wise gradient release (ISSUE 12): partition order, bit-parity
with the unbucketed exchange, overlap accounting, zero steady-state
compiles, accumulation composition, and failure cleanup.

The load-bearing guarantees: (1) the bucketed wire path is bit-identical
to the unbucketed path for sum/avg across dtypes — bucketing changes
WHEN bytes move, never WHAT they reduce to; (2) released buckets ride
the PR-3 pipelined executor, so the profiler's hidden-comm accounting
rises with bucketed release and stays ~0 at pipeline depth 1; (3) a
failed bucket token keeps its dispatch/drain stamps and releases its
fusion-buffer lease — elastic re-forms start clean.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.parallel import buckets as buckets_mod
from horovod_tpu.parallel import dp
from horovod_tpu.runtime import message as msg, types


def _plan(**kw):
    kw.setdefault("bucket_bytes", 1024)
    return buckets_mod.GradReleasePlan(**kw)


class TestPartition:
    def test_reverse_topological_order(self, hvd):
        plan = _plan(bucket_bytes=1)  # one bucket per leaf
        params = {"a": jnp.zeros(4), "b": jnp.zeros(4), "c": jnp.zeros(4)}
        jax.grad(lambda p: sum(x.sum() for x in plan.tag(p).values()))(
            params)
        buckets = plan.buckets()
        # flatten order is a,b,c -> release order must be c,b,a
        flat_order = [i for b in buckets for i in b]
        assert flat_order == [2, 1, 0]

    def test_bucket_sizing(self, hvd):
        plan = _plan(bucket_bytes=64 * 4)  # 64 f32 elems per bucket
        params = {f"p{i}": jnp.zeros(32, jnp.float32) for i in range(6)}
        jax.grad(lambda p: sum(x.sum() for x in plan.tag(p).values()))(
            params)
        assert [len(b) for b in plan.buckets()] == [2, 2, 2]

    def test_tree_shape_change_rejected(self, hvd):
        plan = _plan()
        plan.tag({"a": jnp.zeros(4)})
        with pytest.raises(ValueError, match="changed shape"):
            plan.tag({"a": jnp.zeros(4), "b": jnp.zeros(4)})

    def test_quantum_rounding(self, hvd, monkeypatch):
        from horovod_tpu.utils import env as env_mod

        monkeypatch.setenv("HOROVOD_GRAD_BUCKET_BYTES", "100000")
        q = env_mod.DEFAULT_FUSION_BUCKET_QUANTUM_BYTES
        assert buckets_mod.bucket_bytes_from_env() % q == 0
        assert buckets_mod.bucket_bytes_from_env() >= 100000


class TestBitParity:
    """Bucketed == unbucketed, bitwise, for sum/avg across dtypes (the
    world is a power of two, so identical-row stacked reduction is an
    exact exponent shift)."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("average", [True, False])
    def test_grad_parity(self, hvd, dtype, average):
        plan = _plan(bucket_bytes=256, average=average)
        params = {"w": jnp.linspace(-2, 2, 256).astype(dtype),
                  "b": jnp.linspace(0.5, 1.5, 64).astype(dtype)}

        def loss(p):
            # sum of squares: non-constant per-element gradients (2x), so
            # any wire-side reduction rounding breaks the bit comparison
            t = plan.tag(p)
            return ((t["w"].astype(jnp.float32) ** 2).sum()
                    + (t["b"].astype(jnp.float32) ** 2).sum())

        grads = jax.grad(loss)(params)
        bucketed = plan.gather(grads)
        unbucketed = dp.allreduce_gradients(grads, average=average)
        for k in params:
            a = np.asarray(bucketed[k]).view(np.uint8)
            b = np.asarray(unbucketed[k]).view(np.uint8)
            assert np.array_equal(a, b), f"{k} not bit-identical"

    def test_i32_sum_parity(self, hvd):
        # int payloads can't come from jax.grad; feed the hooks directly
        # (the wire path is identical)
        plan = _plan(bucket_bytes=256, average=False)
        tree = {"n": jnp.arange(128, dtype=jnp.int32)}
        leaves, _ = jax.tree_util.tree_flatten(tree)
        plan._ensure_partition(leaves)
        plan._begin_pass()
        for b in reversed(plan.buckets()):
            for i in b:
                plan._on_grad(i, leaves[i])
        bucketed = plan.gather(tree)
        unbucketed = dp.allreduce_gradients(tree, average=False)
        assert np.array_equal(np.asarray(bucketed["n"]),
                              np.asarray(unbucketed["n"]))
        assert bucketed["n"].dtype == jnp.int32

    def test_zero_steady_state_compiles(self, hvd):
        from horovod_tpu.runtime import executor as executor_mod

        plan = _plan(bucket_bytes=512)
        params = {"w": jnp.ones(512, jnp.float32),
                  "v": jnp.ones(256, jnp.float32)}

        def one_step(s):
            g = jax.grad(lambda p: sum(
                (x * s).sum() for x in plan.tag(p).values()))(params)
            return plan.gather(g)

        for s in range(3):  # warmup: compile the size-bucketed programs
            one_step(float(s + 1))
        # the two buckets meet in one cycle only when the cycle thread is
        # late, and are then reduced as one fused payload of a third
        # size: warm that program too, or a loaded machine's first
        # fusion falls into a steady step
        from horovod_tpu.ops import collectives

        fused = collectives.stack_per_worker(
            jnp.ones((hvd.size(), 512 + 256), jnp.float32))
        hvd.synchronize(collectives.grouped_allreduce_async(
            [fused], names=["warm.fused"], reduce_op="average")[0])
        before = executor_mod._PROGRAM_COMPILES.value
        for s in range(4):
            one_step(float(s + 10))
        assert executor_mod._PROGRAM_COMPILES.value == before


class TestOverlapAccounting:
    """Satellite 3: comm_hidden_fraction ~0 at pipeline depth 1 / single
    bucket, rises with bucketed release; failure paths keep their
    dispatch/drain stamps."""

    def _bucketed_step(self, plan, params, salt):
        g = jax.grad(lambda p: sum(
            (x * salt).sum() for x in plan.tag(p).values()))(params)
        return plan.gather(g)

    def test_depth1_single_bucket_fully_exposed(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_CYCLE_PIPELINE_DEPTH", "1")
        monkeypatch.setenv("HOROVOD_PROFILE", "1")
        hvd.shutdown()
        hvd.init(mesh_shape=(2, 4))
        try:
            from horovod_tpu import profiler

            profiler.configure()
            plan = _plan(bucket_bytes=1 << 24)  # everything in one bucket
            params = {"w": jnp.ones(4096, jnp.float32)}
            self._bucketed_step(plan, params, 1.0)  # warmup/compile
            # what is hidden is the wall time between the dispatch's end
            # and the drain's start, a few statements apart at depth 1:
            # on a loaded machine the thread can lose the processor just
            # there (0.051 once), but not in each of five steps
            hidden = []
            for salt in range(2, 7):
                with profiler.step("depth1") as rec:
                    self._bucketed_step(plan, params, float(salt))
                comm = rec.breakdown["comm"]
                assert comm["total_seconds"] > 0
                assert comm["dispatches"] >= 1
                hidden.append(comm["hidden_fraction"])
            assert min(hidden) < 0.05, hidden
        finally:
            monkeypatch.delenv("HOROVOD_PROFILE", raising=False)
            from horovod_tpu import profiler

            profiler.configure()
            hvd.shutdown()

    def test_bucketed_release_hides_comm(self, monkeypatch):
        # small fusion threshold so same-cycle buckets keep their own
        # dispatches (fuse_responses joins by dtype+op, not priority)
        monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1024")
        monkeypatch.setenv("HOROVOD_PROFILE", "1")
        hvd.shutdown()
        hvd.init(mesh_shape=(2, 4))
        try:
            from horovod_tpu import profiler

            profiler.configure()
            plan = _plan(bucket_bytes=16 * 1024)
            params = {f"p{i}": jnp.ones(8192, jnp.float32)
                      for i in range(6)}
            self._bucketed_step(plan, params, 1.0)  # warmup/compile
            with profiler.step("bucketed") as rec:
                self._bucketed_step(plan, params, 2.0)
            comm = rec.breakdown["comm"]
            assert comm["total_seconds"] > 0
            assert comm["dispatches"] >= 2  # one per released bucket
            assert comm["hidden_fraction"] > 0.0
            assert comm["hidden_fraction_bytes"] > 0.0
        finally:
            monkeypatch.delenv("HOROVOD_PROFILE", raising=False)
            from horovod_tpu import profiler

            profiler.configure()
            hvd.shutdown()

    def test_stamps_survive_failure(self, hvd):
        from horovod_tpu.runtime.runtime import get_runtime

        ex = get_runtime().executor
        base = ex.fusion_buffers.allocated_bytes()
        entries = [types.TensorTableEntry(
            name="buckets/fail/t0",
            tensor=hvd.stack_per_worker(
                [np.ones((256,), "float32")] * hvd.size()),
            reduce_op=types.REDUCE_SUM)]
        pend = ex.dispatch(
            msg.Response(types.ALLREDUCE, [e.name for e in entries]),
            entries)
        pend.fail(types.Status.UnknownError("injected bucket failure"))
        assert pend.t_disp_end is not None
        assert pend.t_drain_start is not None
        assert pend.t_drain_start >= pend.t_disp_end
        # the lease went back: a failed bucket token must not strand its
        # fusion-buffer slab (elastic re-forms reuse the buffer)
        assert ex.fusion_buffers.allocated_bytes() == base


class TestAccumulation:
    def test_only_final_pass_releases(self, hvd):
        plan = _plan(bucket_bytes=256, every_k=3)
        params = {"w": jnp.ones(256, jnp.float32)}

        def grad_for(s):
            return jax.grad(lambda p: (plan.tag(p)["w"] * s).sum())(params)

        assert plan.gather(grad_for(1.0)) is None
        assert plan.gather(grad_for(2.0)) is None
        assert plan.wire_stats()["released"] == 0
        out = plan.gather(grad_for(6.0))
        assert out is not None
        np.testing.assert_allclose(np.asarray(out["w"]), 3.0, rtol=1e-6)
        assert plan.wire_stats()["released"] >= 1

    def test_state_resets_between_steps(self, hvd):
        plan = _plan(bucket_bytes=256)
        params = {"w": jnp.ones(128, jnp.float32)}
        for s in (1.0, 5.0):
            g = jax.grad(lambda p: (plan.tag(p)["w"] * s).sum())(params)
            out = plan.gather(g)
            np.testing.assert_allclose(np.asarray(out["w"]), s, rtol=1e-6)
        assert plan._grads == {} and plan._released == []


class TestFailureCleanup:
    def test_gather_drains_and_resets_on_failure(self, hvd):
        plan = _plan(bucket_bytes=512)
        params = {"a": jnp.ones(512, jnp.float32),
                  "b": jnp.ones(512, jnp.float32)}
        g = jax.grad(lambda p: sum(
            x.sum() for x in plan.tag(p).values()))(params)
        assert plan._released  # buckets in flight

        class _Boom:
            def wait(self):
                raise hvd.WorkersDownError("injected", ranks=(1,))

        # poison the FIRST released handle; gather must still drain the
        # rest, reset, and re-raise
        bucket_idx, pairs, t_release, wire_bytes = plan._released[0]
        plan._released[0] = (bucket_idx, [(pairs[0][0], _Boom())]
                             + pairs[1:], t_release, wire_bytes)
        with pytest.raises(hvd.WorkersDownError):
            plan.gather(g)
        assert plan._released == [] and plan._grads == {}
        # next step works on the same plan
        g2 = jax.grad(lambda p: sum(
            2.0 * x.sum() for x in plan.tag(p).values()))(params)
        out = plan.gather(g2)
        np.testing.assert_allclose(np.asarray(out["a"]), 2.0, rtol=1e-6)

    def test_abort_clears_in_flight(self, hvd):
        plan = _plan(bucket_bytes=512)
        params = {"a": jnp.ones(512, jnp.float32)}
        jax.grad(lambda p: plan.tag(p)["a"].sum())(params)
        assert plan._released
        plan.abort()
        assert plan._released == [] and plan._grads == {}


class TestTracedLanes:
    def test_shard_map_pmean_with_barriers(self, hvd):
        from jax.sharding import PartitionSpec as P

        plan = _plan(bucket_bytes=256)
        params = {"a": jnp.ones(128, jnp.float32),
                  "b": jnp.ones(128, jnp.float32)}

        def per_device(x, p):
            def loss(p):
                t = plan.tag(p)
                return (t["a"] * x.sum()).sum() + t["b"].sum()

            return jax.grad(loss)(p)

        f = jax.shard_map(per_device, mesh=hvd.mesh(),
                          in_specs=(P(hvd.GLOBAL_AXES), P()),
                          out_specs=P())
        x = jnp.arange(float(hvd.size()))
        g = plan.gather(f(x, params))
        np.testing.assert_allclose(np.asarray(g["a"]),
                                   float(np.mean(np.asarray(x))),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g["b"]), 1.0, rtol=1e-6)

    def test_plain_jit_identity(self, hvd):
        plan = _plan(bucket_bytes=256)

        @jax.jit
        def gradfn(p):
            return jax.grad(lambda q: (plan.tag(q)["a"] * 3.0).sum())(p)

        g = plan.gather(gradfn({"a": jnp.ones(64, jnp.float32)}))
        np.testing.assert_allclose(np.asarray(g["a"]), 3.0, rtol=1e-6)


_SCHEDULED = """HloModule jit_one_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[1024,4096]) -> bf16[1024,4096] {
  %param_0.1 = bf16[1024,4096]{1,0} parameter(0)
  ROOT %all-reduce.7 = bf16[1024,4096]{1,0} all-reduce(%param_0.1), channel_id=3, metadata={op_name="jit(one_step)/transpose(jvp(T))/layer_1/mlp/wi/dot_general"}, frontend_attributes={chain_id="4"}
}

%fused_computation.2 (param_0.2: bf16[1024,4096]) -> bf16[1024,4096] {
  %param_0.2 = bf16[1024,4096]{1,0} parameter(0)
  ROOT %all-reduce.8 = bf16[1024,4096]{1,0} all-reduce(%param_0.2), channel_id=3, metadata={op_name="jit(one_step)/transpose(jvp(T))/layer_1/mlp/wi/dot_general"}, frontend_attributes={chain_id="4"}
}

ENTRY %main.1 (p0: bf16[1024,4096]) -> bf16[1024,4096] {
  %p0 = bf16[1024,4096]{1,0} parameter(0)
  %async-collective-start = (bf16[1024,4096]{1,0}, u32[]{:S(2)}) fusion(%p0), kind=kCustom, calls=%fused_computation.1
  %async-collective-done = bf16[1024,4096]{1,0} fusion(%async-collective-start), kind=kCustom, calls=%fused_computation.2
  %all-reduce.1 = f32[1024]{0} all-reduce(%p0), channel_id=1, metadata={op_name="jit(one_step)/transpose(jvp(T))/layer_1/LayerNorm_0/reduce_sum"}
  %all-reduce-start.1 = bf16[512,1024]{1,0} all-reduce-start(%p0), channel_id=5
  %all-reduce-done.1 = bf16[512,1024]{1,0} all-reduce-done(%all-reduce-start.1)
  ROOT %all-reduce.2 = (bf16[30522,1024]{1,0}, bf16[30522,1024]{1,0}) all-reduce(%p0, %p0), channel_id=2, metadata={op_name="jit(one_step)/transpose(jvp(T))/token_embed.attend/dot_general"}
}
"""


def _tiny_transformer():
    from horovod_tpu.models.transformer import Transformer, causal_lm_loss

    model = Transformer(vocab_size=128, d_model=32, num_layers=3,
                        num_heads=2, d_ff=64, max_seq=16, causal=True,
                        dtype=jnp.float32)
    tokens = np.random.RandomState(0).randint(0, 128, (3, 16, 16))
    return (model, causal_lm_loss, (16, 16), jnp.int32,
            [(t.astype(np.int32), t.astype(np.int32)) for t in tokens])


def _tiny_conv():
    from horovod_tpu import training
    from horovod_tpu.models.mnist import MnistConvNet

    rng = np.random.RandomState(0)
    return (MnistConvNet(), training._default_loss_fn, (16, 28, 28, 1),
            jnp.float32,
            [(rng.rand(16, 28, 28, 1).astype(np.float32),
              rng.randint(0, 10, (16,)).astype(np.int32))
             for _ in range(3)])


class TestPlainJitLane:
    """``make_train_step`` on the CPU mesh: the plan's plain-``jit`` lane
    is the identity, the builder adds no TPU option, and the step leaves
    one ``train.build`` span."""

    @pytest.mark.parametrize("family", [_tiny_transformer, _tiny_conv],
                             ids=["transformer", "conv"])
    def test_step_with_plan_is_bit_identical_on_cpu_mesh(self, hvd, family):
        """Three steps on the eight virtual devices: with a plan the
        loss, parameters and optimizer state are those of
        ``grad_release=False``, bit for bit."""
        import optax

        from horovod_tpu import training

        model, loss_fn, shape, dtype, batches = family()

        def run(grad_release):
            opt = hvd.DistributedOptimizer(optax.adamw(1e-2))
            state = training.create_train_state(model, opt, shape,
                                                input_dtype=dtype)
            step, rows = training.make_train_step(
                model, opt, loss_fn=loss_fn, donate=False,
                grad_release=grad_release)
            params, stats, opt_state = (state.params, state.batch_stats,
                                        state.opt_state)
            losses = []
            for x, y in batches:
                loss, params, stats, opt_state = step(
                    params, stats, opt_state, jax.device_put(x, rows),
                    jax.device_put(y, rows))
                losses.append(np.asarray(loss))
            return losses, jax.device_get((params, opt_state))

        plan = _plan(bucket_bytes=8 * 1024)
        plain, planned = run(False), run(plan)
        assert len(plan.buckets()) > 2
        assert all(np.array_equal(a, b)
                   for a, b in zip(plain[0], planned[0]))
        for a, b in zip(jax.tree_util.tree_leaves(plain[1]),
                        jax.tree_util.tree_leaves(planned[1])):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_cpu_mesh_step_carries_no_tpu_option(self, hvd, monkeypatch):
        """The options are for TPU chips alone (XLA:CPU refuses them by
        name), so on the CPU mesh the builder passes ``jax.jit`` what it
        always did, and makes no plan of its own."""
        from horovod_tpu import training
        from horovod_tpu.models.mnist import MnistConvNet

        monkeypatch.delenv("HOROVOD_GRAD_BUCKET_RELEASE", raising=False)
        assert training._exchange_options(hvd.mesh()) is None
        _step, _rows, jit_kwargs = training._build(
            MnistConvNet(), None, None, None)
        assert sorted(jit_kwargs) == ["in_shardings", "out_shardings"]
        assert training._resolve_grad_release(None) is None

    def test_step_leaves_one_train_build_span(self, hvd, ring):
        import optax

        from horovod_tpu import training

        model, loss_fn, shape, dtype, batches = _tiny_transformer()
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        state = training.create_train_state(model, opt, shape,
                                            input_dtype=dtype)
        step, rows = training.make_train_step(
            model, opt, loss_fn=loss_fn, donate=False)
        args = (state.params, state.batch_stats, state.opt_state)
        for x, y in batches[:2]:
            _loss, *args = step(*args, jax.device_put(x, rows),
                                jax.device_put(y, rows))
        built = [s for s in ring.spans() if s["name"] == "train.build"]
        assert len(built) == 1
        assert (built[0]["devices"], built[0]["async_exchange"]) == (
            8, False)

    def test_exchange_schedule_reads_a_compiled_program(self):
        """One chain of two steps on one channel, one ``all-reduce-start``,
        and two synchronous reductions, one of them a tuple."""
        assert buckets_mod.exchange_schedule(_SCHEDULED) == {
            "reductions": 4,
            "async_bytes": 1024 * 4096 * 2 + 512 * 1024 * 2,
            "sync_bytes": 1024 * 4 + 2 * 30522 * 1024 * 2}


class TestIntegration:
    def test_prereduced_scope_skips_exchange(self, hvd):
        grads = {"w": jnp.full((32,), 2.0)}
        with buckets_mod.prereduced():
            out = dp.allreduce_gradients(grads)
        assert out is grads
        assert not buckets_mod.is_prereduced()

    def test_training_step_with_plan_matches_without(self, hvd):
        import optax

        from horovod_tpu import training
        from horovod_tpu.models.mnist import MnistConvNet

        model = MnistConvNet()
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        rng = np.random.RandomState(0)
        images = jnp.asarray(rng.rand(4, 28, 28, 1), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 10, (4,)), jnp.int32)

        def run(grad_release):
            state = training.create_train_state(model, opt, (1, 28, 28, 1),
                                                broadcast=False)
            step = training._make_one_step(
                model, opt, training._default_loss_fn,
                grad_release=grad_release)
            loss, params, _stats, _opt = step(
                state.params, state.batch_stats, state.opt_state,
                images, labels)
            return float(loss), params

        loss_plain, p_plain = run(None)
        loss_plan, p_plan = run(_plan(bucket_bytes=4096))
        assert loss_plain == pytest.approx(loss_plan, rel=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(p_plain),
                        jax.tree_util.tree_leaves(p_plan)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-5, atol=1e-7)

    def test_grouped_allreduce_async_roundtrip(self, hvd):
        xs = [hvd.stack_per_worker(
            [np.full((64,), float(i + j), "float32")
             for i in range(hvd.size())]) for j in range(3)]
        handles = hvd.grouped_allreduce_async(
            xs, names=[f"gaa/t{j}" for j in range(3)], reduce_op="sum")
        outs = [hvd.synchronize(h) for h in handles]
        world = hvd.size()
        for j, o in enumerate(outs):
            expect = sum(float(i + j) for i in range(world))
            np.testing.assert_allclose(np.asarray(o), expect, rtol=1e-6)

    def test_add_group_atomic_duplicate(self, hvd):
        from horovod_tpu.runtime.runtime import get_runtime
        from horovod_tpu.runtime.tensor_queue import DuplicateNameError

        rt = get_runtime()
        x = hvd.stack_per_worker(
            [np.ones((32,), "float32")] * hvd.size())
        h = rt.enqueue_allreduce_group(["dupe/a"], [x], reduce_op="sum")
        with pytest.raises(DuplicateNameError):
            rt.enqueue_allreduce_group(["dupe/b", "dupe/a"], [x, x],
                                       reduce_op="sum")
        # all-or-nothing: "dupe/b" must NOT be stranded in the table
        assert rt.queue.peek("dupe/b") is None
        hvd.synchronize(h[0])

    def test_dp_eager_submission_reverse_topological(self, hvd,
                                                     monkeypatch):
        from horovod_tpu.ops import collectives

        seen = []
        real = collectives.grouped_allreduce

        def spy(tensors, **kw):
            seen.append([int(t.shape[-1]) for t in tensors])
            return real(tensors, **kw)

        monkeypatch.setattr(collectives, "grouped_allreduce", spy)
        grads = {"a": jnp.ones(8), "b": jnp.ones(16), "c": jnp.ones(32)}
        dp.allreduce_gradients(grads)
        # flatten order a(8), b(16), c(32) -> submitted last layer first
        assert seen and seen[0] == [32, 16, 8]
