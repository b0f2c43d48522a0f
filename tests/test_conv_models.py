"""VGG-16, Inception-V3 and the conv-net kernels (space-to-depth stem,
fused BN + ReLU). Beside ``tests/test_models.py`` and not in it: one
Inception train step compiles for a minute and a half, and a file is
what tier-1's ``--dist loadfile`` schedules."""

import jax
import jax.numpy as jnp
import numpy as np


class TestVggInception:
    def test_vgg16_param_count(self, hvd_flat):
        from horovod_tpu.models.vgg import VGG16

        model = VGG16(num_classes=1000)
        tokens = jnp.zeros((1, 224, 224, 3))
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens, train=False))
        n = sum(int(np.prod(x.shape)) for x in
                jax.tree_util.tree_leaves(variables["params"]))
        # canonical VGG-16 ImageNet size: ~138.4M params
        assert 137_000_000 < n < 140_000_000

    def test_vgg16_forward(self, hvd_flat):
        from horovod_tpu.models.vgg import VGG16

        model = VGG16(num_classes=10, dtype=jnp.float32)
        x = jnp.zeros((2, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        out = model.apply(variables, x, train=False)
        assert out.shape == (2, 10) and out.dtype == jnp.float32

    def test_inception_v3_param_count(self, hvd_flat):
        from horovod_tpu.models.inception import InceptionV3

        model = InceptionV3(num_classes=1000)
        x = jnp.zeros((1, 299, 299, 3))
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, train=False))
        n = sum(int(np.prod(x.shape)) for x in
                jax.tree_util.tree_leaves(variables["params"]))
        # canonical Inception-V3 (no aux head): ~23.8M params
        assert 22_000_000 < n < 25_000_000

    def test_inception_v3_trains(self, hvd):
        import optax
        from horovod_tpu import training
        from horovod_tpu.models.inception import InceptionV3

        model = InceptionV3(num_classes=10, dtype=jnp.float32)
        opt = hvd.DistributedOptimizer(optax.sgd(0.01))
        state = training.create_train_state(model, opt, (1, 128, 128, 3))
        step, sh = training.make_train_step(model, opt)
        rng = np.random.RandomState(0)
        images = jax.device_put(rng.rand(8, 128, 128, 3).astype(np.float32), sh)
        labels = jax.device_put(rng.randint(0, 10, (8,)).astype(np.int32), sh)
        loss, p, st, os_ = step(state.params, state.batch_stats,
                                state.opt_state, images, labels)
        loss2, *_ = step(p, st, os_, images, labels)
        assert float(loss2) < float(loss)


class TestFusedConvKernels:
    """Parity pins for the conv-net MFU campaign (ISSUE 12): the
    space-to-depth Inception stem and the fused BN+ReLU epilogue must
    compute the same function as the direct formulations they replace."""

    def test_space_to_depth_stem_matches_direct_conv(self, hvd_flat):
        from horovod_tpu.models.inception import SpaceToDepthStem

        x = jnp.asarray(np.random.RandomState(0).uniform(
            -1, 1, (2, 75, 75, 3)), jnp.float32)  # odd size, like 299
        stem = SpaceToDepthStem(32, jnp.float32)
        variables = stem.init(jax.random.PRNGKey(0), x)
        folded = stem.apply(variables, x)
        direct = jax.lax.conv_general_dilated(
            x, variables["params"]["kernel"], (2, 2), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        assert folded.shape == direct.shape == (2, 37, 37, 32)
        np.testing.assert_allclose(np.asarray(folded), np.asarray(direct),
                                   rtol=1e-5, atol=1e-5)

    def test_fused_bn_act_matches_unfused(self, hvd_flat):
        import flax.linen as nn
        from horovod_tpu.ops.pallas.conv_bn_act import FusedBatchNormAct

        x = jnp.asarray(np.random.RandomState(1).uniform(
            -2, 2, (4, 9, 9, 16)), jnp.float32)
        fused = FusedBatchNormAct(momentum=0.9, epsilon=1e-3,
                                  dtype=jnp.float32)
        ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-3, dtype=jnp.float32,
                           param_dtype=jnp.float32)
        # identical variable names by construction: one init serves both
        variables = fused.init(jax.random.PRNGKey(0), x)
        out_f, mut_f = fused.apply(variables, x,
                                   mutable=["batch_stats"])
        out_r, mut_r = ref.apply(variables, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(out_f),
                                   np.asarray(nn.relu(out_r)),
                                   rtol=1e-5, atol=1e-5)
        for k in ("mean", "var"):
            np.testing.assert_allclose(
                np.asarray(mut_f["batch_stats"][k]),
                np.asarray(mut_r["batch_stats"][k]), rtol=1e-5, atol=1e-6)

    def test_fused_bn_act_gradients_match(self, hvd_flat):
        import flax.linen as nn
        from horovod_tpu.ops.pallas.conv_bn_act import FusedBatchNormAct

        x = jnp.asarray(np.random.RandomState(2).uniform(
            -2, 2, (2, 7, 7, 8)), jnp.float32)
        fused = FusedBatchNormAct(momentum=0.9, epsilon=1e-3,
                                  dtype=jnp.float32)
        ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-3, dtype=jnp.float32,
                           param_dtype=jnp.float32)
        variables = fused.init(jax.random.PRNGKey(0), x)

        def loss_fused(params, x):
            out, _ = fused.apply(
                {"params": params,
                 "batch_stats": variables["batch_stats"]},
                x, mutable=["batch_stats"])
            return jnp.sum(out ** 2)

        def loss_ref(params, x):
            out, _ = ref.apply(
                {"params": params,
                 "batch_stats": variables["batch_stats"]},
                x, mutable=["batch_stats"])
            return jnp.sum(nn.relu(out) ** 2)

        gf = jax.grad(loss_fused, argnums=(0, 1))(variables["params"], x)
        gr = jax.grad(loss_ref, argnums=(0, 1))(variables["params"], x)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            gf, gr)
