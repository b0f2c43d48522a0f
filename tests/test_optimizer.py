"""DistributedOptimizer / gradient API / callback tests.

Mirrors the reference's optimizer and gradient tests (reference:
test/test_tensorflow.py:684-977 gradient correctness, test_keras.py
callback coverage) plus an e2e convergence check like the reference's MNIST
examples (reference: examples/pytorch_mnist.py usage pattern).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P


def _make_data(key, n=64):
    w_true = jnp.array([[2.0], [-3.0]])
    x = jax.random.normal(key, (n, 2))
    return x, x @ w_true


class TestPallasRaggedBlocks:
    def test_flat_shard_kernel_ragged_block_matches_jnp(self):
        """The ZeRO flat-shard kernel over a shard whose rows leave a
        ragged last block (interpret mode here; tests/test_tpu_compile.py
        puts the same call before the TPU compiler)."""
        from horovod_tpu.ops.pallas import fused_optimizer

        rng = np.random.RandomState(2)
        n = 1049 * 128
        master, mu, grad = (jnp.asarray(rng.randn(n), jnp.float32)
                            for _ in range(3))
        nu = jnp.asarray(rng.rand(n), jnp.float32)
        grad = grad.astype(jnp.bfloat16)
        scalars = jnp.asarray([0.9, 0.999, 10.0, 1000.0, 1e-3, 1e-2],
                              jnp.float32)
        got = fused_optimizer.pallas_flat_adamw(
            master, mu, nu, grad, scalars, eps=1e-8,
            out_dtype=jnp.bfloat16)
        want = fused_optimizer._jnp_flat(
            master, mu, nu, grad, scalars, 1e-8, jnp.dtype(jnp.bfloat16))
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("shape,dtype", [
        ((2400, 32), jnp.bfloat16),   # 128/c channel groups per lane row
        ((300, 256), jnp.float32),    # c/128 lane rows per position
    ])
    def test_scale_bias_act_kernel_ragged_block_matches_jnp(self, shape,
                                                            dtype):
        """The fused BN+ReLU epilogue kernel at 600 lane rows: one whole
        512-row block and a ragged one, in both lane layouts."""
        from horovod_tpu.ops.pallas import conv_bn_act

        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(*shape), dtype)
        s = jnp.asarray(rng.rand(shape[-1]) + 0.5, jnp.float32)
        b = jnp.asarray(rng.randn(shape[-1]), jnp.float32)
        got = conv_bn_act._sba_pallas(x, s, b)
        assert got is not None and got.dtype == x.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(conv_bn_act._sba_jnp(x, s, b), np.float32),
            rtol=1e-5, atol=1e-6)


class TestDistributedOptimizer:
    def test_shard_map_training_converges(self, hvd):
        """e2e: per-device microbatches under shard_map, gradients averaged
        by the wrapper across all 8 workers."""
        x, y = _make_data(jax.random.PRNGKey(0))
        params = {"w": jnp.zeros((2, 1))}
        params = hvd.broadcast_parameters(params)
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        opt_state = opt.init(params)
        mesh = hvd.mesh()

        def inner(p, s, xb, yb):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((xb @ p["w"] - yb) ** 2))(p)
            updates, s2 = opt.update(g, s, p)
            return loss, optax.apply_updates(p, updates), s2

        step = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), P(hvd.GLOBAL_AXES), P(hvd.GLOBAL_AXES)),
            out_specs=(P(), P(), P()), check_vma=False))

        for _ in range(40):
            loss, params, opt_state = step(params, opt_state, x, y)
        assert float(loss) < 1e-3
        np.testing.assert_allclose(
            np.asarray(params["w"]).ravel(), [2.0, -3.0], atol=0.05)

    @pytest.mark.parametrize("check_vma", [True, False])
    @pytest.mark.parametrize("average", [True, False])
    def test_shard_map_reduction_under_either_vma_setting(self, hvd,
                                                          check_vma,
                                                          average):
        """Per-device losses, replicated parameters: the wrapper hands the
        inner optimizer the mean (or sum) of the per-device gradients
        under shard_map's default ``check_vma=True`` — where autodiff has
        already summed the cotangent of a replicated input — exactly as
        under ``check_vma=False``."""
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), average=average)
        params = {"w": jnp.ones(4)}

        def inner(p, xb):
            g = jax.grad(lambda p: (p["w"] * xb.sum()).sum())(p)
            updates, _ = opt.update(g, opt.init(p), p)
            return updates

        f = jax.shard_map(inner, mesh=hvd.mesh(),
                          in_specs=(P(), P(hvd.GLOBAL_AXES)),
                          out_specs=P(), check_vma=check_vma)
        x = jnp.arange(float(hvd.size()))
        want = float(np.mean(np.asarray(x)) if average
                     else np.sum(np.asarray(x)))
        np.testing.assert_allclose(np.asarray(f(params, x)["w"]), -want,
                                   rtol=1e-6)

    def test_plain_jit_noop_reduction(self, hvd):
        """Under plain jit (global batch), the wrapper must be a no-op:
        gradients of a global-mean loss are already the global average."""
        x, y = _make_data(jax.random.PRNGKey(1))
        params = {"w": jnp.zeros((2, 1))}
        opt_plain = optax.sgd(0.1)
        opt_dist = hvd.DistributedOptimizer(optax.sgd(0.1))
        sp, sd = opt_plain.init(params), opt_dist.init(params)

        def g(p):
            return jax.grad(lambda p: jnp.mean((x @ p["w"] - y) ** 2))(p)

        @jax.jit
        def both(p, sp, sd):
            grads = g(p)
            up, _ = opt_plain.update(grads, sp, p)
            ud, _ = opt_dist.update(grads, sd, p)
            return up, ud

        up, ud = both(params, sp, sd)
        np.testing.assert_allclose(np.asarray(up["w"]), np.asarray(ud["w"]))

    def test_gradient_accumulation(self, hvd):
        """backward_passes_per_step accumulates N micro-batches between
        updates (reference: torch/__init__.py:82-143)."""
        params = {"w": jnp.ones((2,))}
        opt = hvd.DistributedOptimizer(
            optax.sgd(1.0), backward_passes_per_step=2)
        s = opt.init(params)
        g = {"w": jnp.ones((2,))}
        u1, s = opt.update(g, s, params)
        # first micro-batch: no update applied yet
        np.testing.assert_allclose(np.asarray(u1["w"]), 0.0)
        u2, s = opt.update(g, s, params)
        # second: applies update from the mean of accumulated grads
        np.testing.assert_allclose(np.asarray(u2["w"]), -1.0)

    def test_compression_roundtrip_dtype(self, hvd):
        params = {"w": jnp.ones((4,), jnp.float32)}
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), compression=hvd.Compression.fp16)
        s = opt.init(params)
        g = {"w": jnp.full((4,), 0.25, jnp.float32)}
        u, _ = opt.update(g, s, params)
        assert u["w"].dtype == jnp.float32

    def test_bad_backward_passes(self, hvd):
        with pytest.raises(ValueError, match=">= 1"):
            hvd.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=0)


class TestDistributedGradientTape:
    def test_grad_fn_wrapping(self, hvd):
        """reference: tensorflow/__init__.py:323-376."""
        def loss(p):
            return jnp.sum(p ** 2)

        wrapped = hvd.DistributedGradientTape(jax.grad(loss))
        g = wrapped(jnp.array([1.0, 2.0]))
        np.testing.assert_allclose(np.asarray(g), [2.0, 4.0])

    def test_value_and_grad_wrapping(self, hvd):
        wrapped = hvd.DistributedGradientTape(
            jax.value_and_grad(lambda p: jnp.sum(p ** 2)),
            returns="value_and_grads")
        v, g = wrapped(jnp.array([3.0]))
        np.testing.assert_allclose(float(v), 9.0)
        np.testing.assert_allclose(np.asarray(g), [6.0])

    def test_grads_and_aux_wrapping(self, hvd):
        wrapped = hvd.DistributedGradientTape(
            jax.grad(lambda p: (jnp.sum(p ** 2), {"n": 1}), has_aux=True),
            returns="grads_and_aux")
        g, aux = wrapped(jnp.array([2.0]))
        np.testing.assert_allclose(np.asarray(g), [4.0])
        assert aux == {"n": 1}

    def test_tuple_params_grads_not_misparsed(self, hvd):
        # plain jax.grad over 2-tuple params returns a 2-tuple of grads;
        # default returns="grads" must reduce both, not treat it as
        # (value, grads)
        wrapped = hvd.DistributedGradientTape(
            jax.grad(lambda ab: jnp.sum(ab[0] ** 2) + jnp.sum(ab[1] ** 3)))
        ga, gb = wrapped((jnp.array([1.0]), jnp.array([2.0])))
        np.testing.assert_allclose(np.asarray(ga), [2.0])
        np.testing.assert_allclose(np.asarray(gb), [12.0])

    def test_bad_returns_mode(self, hvd):
        with pytest.raises(ValueError, match="returns must be"):
            hvd.DistributedGradientTape(lambda: None, returns="bogus")


class TestBroadcastState:
    def test_broadcast_parameters_replicates(self, hvd):
        params = {"a": jnp.ones((2, 2)), "b": {"c": jnp.zeros(3)}}
        out = hvd.broadcast_parameters(params)
        assert out["a"].sharding.is_fully_replicated
        np.testing.assert_allclose(np.asarray(out["b"]["c"]), 0.0)

    def test_broadcast_optimizer_state(self, hvd):
        opt = optax.adam(1e-3)
        s = opt.init({"w": jnp.ones((2,))})
        out = hvd.broadcast_optimizer_state(s)
        # non-array leaves (counters) survive; array leaves broadcast
        leaves = jax.tree_util.tree_leaves(out)
        assert len(leaves) == len(jax.tree_util.tree_leaves(s))

    def test_broadcast_object_single_process(self, hvd):
        assert hvd.broadcast_object({"epoch": 3}) == {"epoch": 3}


class TestCallbacks:
    def test_metric_average(self, hvd):
        from horovod_tpu import callbacks

        m = callbacks.average_metrics({"loss": jnp.float32(2.0)})
        np.testing.assert_allclose(float(m["loss"]), 2.0)

    def test_warmup_schedule(self, hvd):
        from horovod_tpu import callbacks

        sched = callbacks.warmup_scaled_schedule(
            base_lr=0.1, warmup_epochs=2, steps_per_epoch=10, size=8)
        np.testing.assert_allclose(float(sched(0)), 0.1)
        np.testing.assert_allclose(float(sched(20)), 0.8, rtol=1e-5)
        np.testing.assert_allclose(float(sched(10)), 0.45, rtol=1e-5)
        np.testing.assert_allclose(float(sched(100)), 0.8, rtol=1e-5)

    def test_warmup_with_after_schedule(self, hvd):
        from horovod_tpu import callbacks

        sched = callbacks.warmup_scaled_schedule(
            base_lr=0.1, warmup_epochs=1, steps_per_epoch=10, size=8,
            after=lambda e: 0.1 ** (e // 30))
        np.testing.assert_allclose(float(sched(10)), 0.8, rtol=1e-5)
        np.testing.assert_allclose(float(sched(10 + 300)), 0.08, rtol=1e-5)

    def test_broadcast_callback(self, hvd):
        from horovod_tpu import callbacks

        cb = callbacks.BroadcastGlobalVariablesCallback(root_rank=0)
        state = {"w": jnp.ones((2,))}
        out = cb.on_train_begin(state)
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0)

    def test_lr_schedule_callback(self, hvd):
        from horovod_tpu import callbacks

        cb = callbacks.LearningRateScheduleCallback(
            base_lr=1.0, multiplier=lambda e: 0.1 ** (e // 2))
        cb.on_epoch_begin(0, None)
        assert cb.lr == pytest.approx(1.0)
        cb.on_epoch_begin(2, None)
        assert cb.lr == pytest.approx(0.1)
