"""Model + training-step tests (the graft entry contract, which compiles
ResNet-50 twice, is ``tests/test_graft_entry.py``; VGG, Inception and the
fused conv kernels ``tests/test_conv_models.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


class TestResNet:
    def test_resnet18_forward_shape(self, hvd_flat):
        from horovod_tpu.models.resnet import ResNet18

        # a shape and a dtype: traced, not run
        model = ResNet18(num_classes=10, dtype=jnp.float32)
        out = jax.eval_shape(
            lambda: model.apply(
                model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False),
                jnp.zeros((2, 32, 32, 3)), train=False))
        assert out.shape == (2, 10)
        assert out.dtype == jnp.float32

    def test_resnet50_param_count(self, hvd_flat):
        from horovod_tpu.models.resnet import ResNet50

        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 64, 64, 3)), train=False))
        n_params = sum(int(np.prod(x.shape)) for x in
                       jax.tree_util.tree_leaves(variables["params"]))
        # canonical ResNet-50 ImageNet size: ~25.5M params
        assert 25_000_000 < n_params < 26_000_000

    def test_space_to_depth_conv_init_is_exact(self, hvd_flat):
        """The MXU-friendly input-conv reparametrization must compute
        the SAME function as the direct 7x7/2 conv on the same
        (7,7,3,64) parameter — checkpoint-interchangeable by
        construction (docs/perf_experiments.md has the 1.43x layer
        speedup read on an earlier machine)."""
        from horovod_tpu.models.resnet import ResNet50

        x = jnp.asarray(np.random.RandomState(0).uniform(
            -1, 1, (2, 64, 64, 3)), jnp.float32)
        s2d = ResNet50(num_classes=10, dtype=jnp.float32)
        direct = ResNet50(num_classes=10, dtype=jnp.float32,
                          space_to_depth=False)
        variables = s2d.init(jax.random.PRNGKey(0), x[:1], train=False)
        # identical param trees (same names/shapes) serve both models
        out_a = s2d.apply(variables, x, train=False)
        out_b = direct.apply(variables, x, train=False)
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                                   rtol=1e-5, atol=1e-5)


class TestTrainStep:
    def test_mnist_train_step_runs_and_learns(self, hvd):
        from horovod_tpu.models.mnist import MnistConvNet
        from horovod_tpu import training

        model = MnistConvNet()
        opt = hvd.DistributedOptimizer(optax.adam(1e-3))
        state = training.create_train_state(model, opt, (1, 28, 28, 1))
        step, batch_sharding = training.make_train_step(model, opt)

        rng = np.random.RandomState(0)
        images = jax.device_put(
            rng.rand(16, 28, 28, 1).astype(np.float32), batch_sharding)
        labels = jax.device_put(
            rng.randint(0, 10, (16,)).astype(np.int32), batch_sharding)

        params, stats, opt_state = (state.params, state.batch_stats,
                                    state.opt_state)
        losses = []
        for _ in range(10):
            loss, params, stats, opt_state = step(params, stats, opt_state,
                                                  images, labels)
            losses.append(float(loss))
        assert losses[-1] < losses[0]  # memorizing a fixed batch


class TestTransformer:
    def _tiny(self, causal, **kw):
        from horovod_tpu.models.transformer import Transformer

        return Transformer(vocab_size=64, d_model=32, num_layers=2,
                           num_heads=2, d_ff=64, max_seq=64, causal=causal,
                           dtype=jnp.float32, **kw)

    def test_bert_forward_shape(self, hvd_flat):
        model = self._tiny(causal=False)
        tokens = jnp.zeros((2, 16), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), tokens, train=False)
        out = model.apply(variables, tokens, train=False)
        assert out.shape == (2, 16, 64)
        assert out.dtype == jnp.float32

    def test_causal_masking_matters(self, hvd_flat):
        """A causal decoder's logits at position t must not depend on
        tokens after t; a bidirectional encoder's do."""
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 64, (1, 16)), jnp.int32)
        tokens2 = tokens.at[0, -1].set((int(tokens[0, -1]) + 1) % 64)

        gpt = self._tiny(causal=True)
        variables = gpt.init(jax.random.PRNGKey(1), tokens, train=False)
        a = gpt.apply(variables, tokens, train=False)
        b = gpt.apply(variables, tokens2, train=False)
        np.testing.assert_allclose(a[0, :-1], b[0, :-1], atol=1e-5)

        bert = self._tiny(causal=False)
        variables = bert.init(jax.random.PRNGKey(1), tokens, train=False)
        a = bert.apply(variables, tokens, train=False)
        b = bert.apply(variables, tokens2, train=False)
        assert np.abs(np.asarray(a[0, :-1]) - np.asarray(b[0, :-1])).max() > 1e-6

    def test_gpt_memorizes_batch(self, hvd):
        import optax
        from horovod_tpu import training
        from horovod_tpu.models.transformer import causal_lm_loss

        model = self._tiny(causal=True)
        opt = hvd.DistributedOptimizer(optax.adam(5e-3))
        state = training.create_train_state(
            model, opt, (1, 16), input_dtype=jnp.int32)
        step, batch_sharding = training.make_train_step(
            model, opt, loss_fn=lambda logits, labels: causal_lm_loss(
                logits, labels))

        rng = np.random.RandomState(0)
        tokens = jax.device_put(
            rng.randint(0, 64, (8, 16)).astype(np.int32), batch_sharding)

        params, stats, opt_state = (state.params, state.batch_stats,
                                    state.opt_state)
        losses = []
        for _ in range(15):
            loss, params, stats, opt_state = step(params, stats, opt_state,
                                                  tokens, tokens)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_gathered_mlm_loss_matches_full_logits(self, hvd_flat):
        """The gather-before-projection MLM path (output='hidden' +
        masked_lm_loss_gathered) must equal the full-logits
        masked_lm_loss exactly when the gathered positions are the mask
        — it is an algebraic rearrangement, not an approximation."""
        from horovod_tpu.models.transformer import (
            masked_lm_loss, masked_lm_loss_gathered)

        model = self._tiny(causal=False)
        rng = np.random.RandomState(3)
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), tokens, train=False)

        m = 4
        positions = jnp.asarray(
            np.stack([np.sort(rng.choice(16, m, replace=False))
                      for _ in range(2)]).astype(np.int32))
        mask = np.zeros((2, 16), np.int32)
        for b in range(2):
            mask[b, np.asarray(positions)[b]] = 1

        logits = model.apply(variables, tokens, train=False)
        full = masked_lm_loss(logits, tokens, jnp.asarray(mask))

        hidden = model.apply(variables, tokens, train=False,
                             output="hidden")
        assert hidden.shape == (2, 16, 32)
        emb = variables["params"]["token_embed"]["embedding"]
        labels = jnp.take_along_axis(tokens, positions, axis=1)
        gathered = masked_lm_loss_gathered(hidden, emb, positions, labels)
        np.testing.assert_allclose(float(gathered), float(full),
                                   rtol=1e-6)

    def test_bert_large_param_count(self, hvd_flat):
        from horovod_tpu.models.transformer import BertLarge

        model = BertLarge(vocab_size=30522, max_seq=128)
        tokens = jnp.zeros((1, 8), jnp.int32)
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens, train=False))
        n_params = sum(int(np.prod(x.shape)) for x in
                       jax.tree_util.tree_leaves(variables["params"]))
        # BERT-Large: ~334M params (here without the pooler/NSP head and
        # with a short learned-position table)
        assert 330_000_000 < n_params < 345_000_000

    def test_masked_lm_loss(self, hvd_flat):
        from horovod_tpu.models.transformer import masked_lm_loss

        logits = jnp.zeros((2, 4, 8))
        labels = jnp.zeros((2, 4), jnp.int32)
        mask = jnp.array([[1, 1, 0, 0], [0, 0, 0, 0]], jnp.int32)
        loss = masked_lm_loss(logits, labels, mask)
        np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)


class _TinyBnNet:
    """Conv+BatchNorm model so the scan carries non-empty batch_stats
    (the path ``make_train_round`` takes for ResNet-50)."""

    def __new__(cls):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = True):
                x = nn.Conv(8, (3, 3))(x)
                x = nn.BatchNorm(use_running_average=not train)(x)
                x = nn.relu(x).mean(axis=(1, 2))
                return nn.Dense(10)(x)

        return Net()


class TestTrainRound:
    def test_scanned_round_matches_sequential_steps(self, hvd):
        """make_train_round(steps=3) == three make_train_step calls,
        including the BatchNorm running-stats carry."""
        import optax
        from horovod_tpu import training

        model = _TinyBnNet()
        opt = hvd.DistributedOptimizer(optax.sgd(0.05))
        state = training.create_train_state(model, opt, (1, 28, 28, 1))
        assert state.batch_stats  # non-empty stats actually carried
        step, sh = training.make_train_step(model, opt, donate=False)
        round_fn, _ = training.make_train_round(model, opt, steps=3,
                                                donate=False)

        rng = np.random.RandomState(0)
        images = jax.device_put(rng.rand(16, 28, 28, 1).astype(np.float32), sh)
        labels = jax.device_put(rng.randint(0, 10, (16,)).astype(np.int32), sh)

        p, st, os_ = state.params, state.batch_stats, state.opt_state
        for _ in range(3):
            loss_seq, p, st, os_ = step(p, st, os_, images, labels)

        loss_rnd, p2, st2, os2 = round_fn(state.params, state.batch_stats,
                                          state.opt_state, images, labels)
        np.testing.assert_allclose(float(loss_rnd), float(loss_seq), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves((p, st)),
                        jax.tree_util.tree_leaves((p2, st2))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
