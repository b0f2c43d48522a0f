"""Worker script for multi-process runtime tests (launched by
test_multiprocess.py with the launcher env contract set).

Plays the role of one rank in the reference's mpirun-launched op tests
(reference: test/test_tensorflow.py run under ``mpirun -np 2``): computes
collectives through the public named-async API and asserts against locally
computed expectations.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    scenario = sys.argv[1]
    rank = int(os.environ["HOROVOD_RANK"])
    world = int(os.environ["HOROVOD_SIZE"])
    if scenario == "pod_soak":
        # per-rank timeline paths must exist BEFORE init (the launcher
        # hands every rank the same env; the rank-suffixed path is the
        # worker's to derive)
        os.environ["HOROVOD_TIMELINE"] = os.path.join(
            os.environ["SOAK_DIR"], f"timeline.{rank}.json")
    hvd.init()

    if scenario == "collectives":
        # named allreduce: mean over ranks
        for step in range(3):  # steady state -> cache fast path
            h = hvd.allreduce_async(
                np.full((5,), float(rank), np.float32), name="grad/w")
            out = hvd.synchronize(h)
            np.testing.assert_allclose(
                np.asarray(out), np.mean(np.arange(world, dtype=np.float32)))
        # sum + int dtype
        h = hvd.allreduce_async(np.full((3,), rank + 1, np.int32),
                                name="grad/int", average=False)
        np.testing.assert_array_equal(
            np.asarray(hvd.synchronize(h)), sum(range(1, world + 1)))
        # ragged allgather: rank r contributes (r+1, 2)
        h = hvd.allgather_async(
            np.full((rank + 1, 2), rank, np.float32), name="ag/x")
        out = np.asarray(hvd.synchronize(h))
        expected = np.concatenate(
            [np.full((r + 1, 2), r, np.float32) for r in range(world)])
        np.testing.assert_allclose(out, expected)
        # broadcast root=1
        h = hvd.broadcast_async(
            np.full((4,), float(rank), np.float32), root_rank=1, name="bc/x")
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)), 1.0)
        # min/max/product ride the same wire (op-generalized ring kernels;
        # reference: op-type dispatch of torch/mpi_ops_v2.cc:52-76) —
        # bit-exact expectations
        h = hvd.allreduce_async(np.full((3,), float(rank + 1), np.float32),
                                name="red/min", op=hvd.Min)
        np.testing.assert_array_equal(np.asarray(hvd.synchronize(h)), 1.0)
        h = hvd.allreduce_async(np.full((3,), float(rank + 1), np.float32),
                                name="red/max", op=hvd.Max)
        np.testing.assert_array_equal(
            np.asarray(hvd.synchronize(h)), float(world))
        h = hvd.allreduce_async(np.full((3,), rank + 2, np.int32),
                                name="red/prod", op=hvd.Product)
        expect = int(np.prod(np.arange(2, world + 2, dtype=np.int64)))
        out = np.asarray(hvd.synchronize(h))
        assert out.dtype == np.int32
        np.testing.assert_array_equal(out, expect)
        # reducescatter: rank r contributes data[r] (world*2, 3); rank r
        # receives shard r of the element-wise sum
        data = np.stack([np.arange(world * 2 * 3, dtype=np.float32)
                         .reshape(world * 2, 3) + 10 * r
                         for r in range(world)])
        out = np.asarray(hvd.reducescatter(data[rank], op=hvd.Sum))
        full = data.sum(axis=0)
        np.testing.assert_allclose(out, full[rank * 2:(rank + 1) * 2])
        out = np.asarray(hvd.reducescatter(data[rank], op=hvd.Min))
        np.testing.assert_allclose(out, data.min(axis=0)[rank * 2:(rank + 1) * 2])
        # non-C-contiguous input must still reduce correctly (regression:
        # the in-place ring must not write into a stray ravel() copy)
        out = np.asarray(hvd.reducescatter(
            np.asfortranarray(data[rank]), op=hvd.Sum))
        np.testing.assert_allclose(out, full[rank * 2:(rank + 1) * 2])
        # alltoall: rank r sends chunk j of its tensor to rank j
        out = np.asarray(hvd.alltoall(data[rank]))
        expect_a2a = np.concatenate(
            [data[j, rank * 2:(rank + 1) * 2] for j in range(world)])
        np.testing.assert_allclose(out, expect_a2a)

        # byte-count optimality of the native kernels (VERDICT r2 ask 6):
        # one big reducescatter and one big alltoall must each send
        # exactly (w-1)/w of the payload from this rank — not the old
        # fallbacks' 2x (allreduce+slice) / Wx (star allgatherv)
        from horovod_tpu.core import state as _state
        net = _state.global_state().runtime.controller.net
        big = np.ones((world * 1024, 16), np.float32)
        before = net.data_bytes_sent()
        hvd.reducescatter(big, op=hvd.Sum)
        sent_rs = net.data_bytes_sent() - before
        optimal = big.nbytes * (world - 1) // world
        assert sent_rs == optimal, (sent_rs, optimal)
        before = net.data_bytes_sent()
        hvd.alltoall(big, name="bytes/a2a")
        sent_a2a = net.data_bytes_sent() - before
        assert sent_a2a == optimal, (sent_a2a, optimal)
        # no rank leaves before every rank has read its counters: the
        # first to finish shuts the world down, the others' loops close
        # their communicators, and a closed one counts 0 (beside five
        # busy workers a late rank read -131412 here, one run in twelve)
        hvd.allreduce(np.zeros((1,), np.float32), name="bytes/read")
        # cache populated
        from horovod_tpu.core import state
        rt = state.global_state().runtime
        assert len(rt.controller.cache) >= 3, len(rt.controller.cache)

    elif scenario == "skewed_arrival":
        # The negotiation protocol's reason to exist: workers announce the
        # same named tensor in DIFFERENT cycles. Rank r delays by r*0.4s —
        # far more than the 5ms cycle — so early announcers must wait
        # (uncached path), then repeat with the tensor cached (deferred-hit
        # path), then repeat with a changed shape (synchronized
        # invalidation path).
        import time

        for round_no, shape in [(0, (4,)), (1, (4,)), (2, (4,)), (3, (8,))]:
            time.sleep(0.4 * rank)
            h = hvd.allreduce_async(
                np.full(shape, float(rank), np.float32), name="skew/x")
            out = hvd.synchronize(h)
            np.testing.assert_allclose(
                np.asarray(out), np.mean(np.arange(world, dtype=np.float32)))
        # caches must still be bit-aligned: a fresh steady-state round on a
        # second tensor plus the first must take the fast path correctly
        for _ in range(2):
            h1 = hvd.allreduce_async(np.full((8,), float(rank), np.float32),
                                     name="skew/x")
            h2 = hvd.allreduce_async(np.full((2,), float(rank) * 2, np.float32),
                                     name="skew/y")
            np.testing.assert_allclose(
                np.asarray(hvd.synchronize(h1)),
                np.mean(np.arange(world, dtype=np.float32)))
            np.testing.assert_allclose(
                np.asarray(hvd.synchronize(h2)),
                2 * np.mean(np.arange(world, dtype=np.float32)))

    elif scenario == "autotune":
        # coordinator tunes, workers apply via the per-cycle param
        # broadcast; collectives stay correct while knobs change
        from horovod_tpu.runtime.runtime import get_runtime
        rt = get_runtime()
        if rank == 0:
            assert rt.param_manager is not None
        else:
            assert rt.param_manager is None
        # fixed iteration count on every rank — breaking early when this
        # rank observes convergence would shut down while peers still have
        # collectives in flight
        for i in range(250):
            h = hvd.allreduce_async(
                np.full((8,), float(rank), np.float32), name=f"at/{i % 3}")
            out = np.asarray(hvd.synchronize(h))
            np.testing.assert_allclose(
                out, np.mean(np.arange(world, dtype=np.float32)))
        assert not rt._autotune_active, "autotune did not converge"
        # every worker holds the frozen tuned config
        assert rt._st.config.cycle_time_ms > 0

    elif scenario == "large_allreduce":
        # chunks far larger than kernel socket buffers: the ring must run
        # full-duplex or it deadlocks (every rank blocked in send)
        n = 8 * 1024 * 1024  # 32 MB fp32
        h = hvd.allreduce_async(
            np.full((n,), float(rank), np.float32), name="big/x")
        out = np.asarray(hvd.synchronize(h))
        np.testing.assert_allclose(
            out[::65537], np.mean(np.arange(world, dtype=np.float32)))

    elif scenario == "spmd_allreduce":
        # launcher default mode: jax.distributed forms a global mesh and the
        # hot op rides XLA collectives, not the host ring (net is control
        # plane only). Verifies routing + numerics.
        import jax as _jax

        assert _jax.process_count() == world, (
            _jax.process_count(), world)
        from horovod_tpu.runtime.runtime import get_runtime
        rt = get_runtime()
        assert rt.executor._spmd_world
        assert rt.executor._proc_mesh is not None
        for step in range(3):
            h = hvd.allreduce_async(
                np.full((6,), float(hvd.rank()), np.float32), name="spmd/g")
            out = np.asarray(hvd.synchronize(h))
            np.testing.assert_allclose(
                out, np.mean(np.arange(world, dtype=np.float32)))
        h = hvd.allreduce_async(np.full((3,), 2.0, np.float32),
                                name="spmd/sum", average=False)
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   2.0 * world)
        # integer sum must be exact through the SPMD path
        h = hvd.allreduce_async(
            np.full((2,), 1 << 24, np.int32), name="spmd/int",
            average=False)
        out = np.asarray(hvd.synchronize(h))
        assert out.dtype == np.int32
        np.testing.assert_array_equal(out, (1 << 24) * world)
        # min/max/product through the XLA sub-mesh path
        h = hvd.allreduce_async(
            np.full((2,), float(hvd.rank() + 1), np.float32),
            name="spmd/min", op=hvd.Min)
        np.testing.assert_array_equal(np.asarray(hvd.synchronize(h)), 1.0)
        h = hvd.allreduce_async(
            np.full((2,), hvd.rank() + 2, np.int32),
            name="spmd/prod", op=hvd.Product)
        np.testing.assert_array_equal(
            np.asarray(hvd.synchronize(h)),
            int(np.prod(np.arange(2, world + 2, dtype=np.int64))))
        # 64-bit payloads can't ride the x32 XLA sub-mesh; the executor
        # must route them to the host ring EXACTLY (r5 — found live by
        # the verify drive: 2**40 came back as garbage pre-fix)
        h = hvd.allreduce_async(
            np.array([(1 << 40) + hvd.rank()], np.int64),
            name="spmd/i64", average=False)
        out = np.asarray(hvd.synchronize(h))
        assert out.dtype == np.int64
        assert out[0] == (1 << 40) * world + world * (world - 1) // 2, out
        h = hvd.allreduce_async(np.array([1e300], np.float64),
                                name="spmd/f64", average=False)
        out = np.asarray(hvd.synchronize(h))
        assert out.dtype == np.float64 and np.isfinite(out[0]), out
        np.testing.assert_allclose(out[0], 1e300 * world)

    elif scenario == "jit_train":
        # The canonical jax-surface-under-tpurun flow: jax.distributed has
        # formed one global mesh across processes; the jitted train step
        # is compiled over it with the batch sharded per process, and
        # gradient averaging falls out of the shardings as real
        # cross-process collectives.
        import jax as _jax
        import jax.numpy as jnp
        import optax

        from horovod_tpu import training
        from horovod_tpu.models.mnist import MnistConvNet

        assert _jax.process_count() == world

        model = MnistConvNet()
        opt = hvd.DistributedOptimizer(optax.sgd(0.05))
        state = training.create_train_state(model, opt, (1, 28, 28, 1))
        step, batch_sharding = training.make_train_step(model, opt)

        rng = np.random.RandomState(rank)  # DIFFERENT data per process
        p, s, o = state.params, state.batch_stats, state.opt_state
        for _ in range(3):
            local_x = rng.rand(4, 28, 28, 1).astype(np.float32)
            local_y = rng.randint(0, 10, 4).astype(np.int32)
            xb = _jax.make_array_from_process_local_data(
                batch_sharding, local_x)
            yb = _jax.make_array_from_process_local_data(
                batch_sharding, local_y)
            loss, p, s, o = step(p, s, o, xb, yb)
        assert np.isfinite(float(loss))
        # parameters must be identical on every process — broadcast from
        # rank 0 and compare (catches any silently-local gradient math)
        flat = np.concatenate([np.asarray(x).ravel()
                               for x in _jax.tree_util.tree_leaves(p)])
        h = hvd.broadcast_async(flat.astype(np.float32), 0, name="jt/check")
        root_flat = np.asarray(hvd.synchronize(h))
        np.testing.assert_allclose(root_flat, flat, rtol=1e-6, atol=1e-7)

    elif scenario == "kitchen_sink":
        # Everything at once: named grads in rank-skewed order, unnamed
        # eager ops, broadcast + ragged allgather in the same cycles, and
        # periodic shape changes — in BOTH launcher modes. Caught the
        # multi-controller eager-dispatch ordering bug (unnamed eager ops
        # must ride the runtime's single ordered lane, not dispatch global
        # programs from the caller thread).
        rngk = np.random.RandomState(1000 + rank)
        for step in range(20):
            order = rngk.permutation(6)
            hs = {}
            for i in order:
                hs[int(i)] = hvd.allreduce_async(
                    np.full((8 + i,), float(rank + i), np.float32),
                    name=f"ks/g{i}")
            u = hvd.allreduce(np.full((4,), float(rank), np.float32))
            np.testing.assert_allclose(
                np.asarray(u), np.mean(np.arange(world, dtype=np.float32)))
            b = hvd.broadcast_async(
                np.full((3,), float(rank), np.float32),
                root_rank=step % world, name="ks/b")
            g = hvd.allgather_async(
                np.full((rank + 1, 2), float(rank), np.float32),
                name="ks/ag")
            for i, h in hs.items():
                expect = np.mean([r + i for r in range(world)])
                np.testing.assert_allclose(
                    np.asarray(hvd.synchronize(h)), expect,
                    err_msg=f"step {step} grad {i}")
            np.testing.assert_allclose(np.asarray(hvd.synchronize(b)),
                                       float(step % world))
            ag = np.asarray(hvd.synchronize(g))
            expect = np.concatenate(
                [np.full((r + 1, 2), float(r), np.float32)
                 for r in range(world)])
            np.testing.assert_allclose(ag, expect)
            if step % 8 == 7:  # shape change -> synchronized invalidation
                h = hvd.allreduce_async(
                    np.ones((step,), np.float32), name="ks/shapeshift")
                np.testing.assert_allclose(
                    np.asarray(hvd.synchronize(h)), 1.0)

    elif scenario == "keras":
        # The keras-style Trainer under the launcher: fit/evaluate over
        # the jax.distributed global mesh, metric averaging across ranks.
        import jax as _jax
        import optax

        import horovod_tpu.keras as hvd_keras
        from horovod_tpu import callbacks
        from horovod_tpu.models.mnist import MnistConvNet

        assert _jax.process_count() == world
        rng = np.random.RandomState(0)  # same data everywhere
        x = rng.rand(64, 28, 28, 1).astype(np.float32)
        y = rng.randint(0, 10, 64).astype(np.int32)
        trainer = hvd_keras.Trainer(
            MnistConvNet(), optax.sgd(0.05 * hvd.size()), (1, 28, 28, 1))
        history = trainer.fit(
            x, y, epochs=2, batch_size=32,
            callbacks=[callbacks.MetricAverageCallback()])
        assert len(history["loss"]) == 2
        assert np.isfinite(history["loss"]).all()
        metrics = trainer.evaluate(x, y)
        assert np.isfinite(metrics["loss"])

    elif scenario == "shape_mismatch":
        # reference: error paths (test_tensorflow.py:314-384) — mismatched
        # shapes across ranks must error on every rank
        shape = (4,) if rank == 0 else (5,)
        h = hvd.allreduce_async(np.ones(shape, np.float32), name="bad/x")
        try:
            hvd.synchronize(h)
        except RuntimeError as e:
            assert "shape" in str(e).lower(), str(e)
        else:
            raise AssertionError("expected shape mismatch error")
        # the world must still be usable afterwards
        h = hvd.allreduce_async(np.ones((2,), np.float32), name="good/x",
                                average=False)
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   float(world))

    elif scenario == "stall_shutdown":
        # reference: test/test_stall.py — one rank never submits; stall
        # inspector triggers global shutdown
        if rank == 0:
            h = hvd.allreduce_async(np.ones((2,), np.float32), name="stall/x")
            try:
                hvd.synchronize(h)
                raise AssertionError("expected shutdown error")
            except RuntimeError as e:
                assert "shut down" in str(e).lower() or "fail" in str(e).lower(), str(e)
        else:
            # never submit; wait for the coordinator-triggered shutdown to
            # propagate through the status bits
            import time

            deadline = time.time() + 30
            from horovod_tpu.core import state
            rt = state.global_state().runtime
            # rank!=0 needs the runtime started to participate in cycles
            from horovod_tpu.runtime.runtime import get_runtime
            rt = get_runtime()
            while time.time() < deadline and rt._thread.is_alive():
                time.sleep(0.1)
            assert not rt._thread.is_alive(), "shutdown did not propagate"
    elif scenario == "peer_death":
        # A rank dying mid-training must fail the survivors' pending work
        # loudly, never hang (reference: any rank failure aborts the job —
        # gloo_run.py:256-262 at the launcher, SHUT_DOWN_ERROR to pending
        # callbacks at the runtime, operations.cc:480-486).
        h = hvd.allreduce_async(np.ones((4,), np.float32), name="pd/warm")
        hvd.synchronize(h)  # world is healthy once
        if rank == 1:
            os._exit(17)  # abrupt death: no shutdown handshake, no atexit
        import time

        deadline = time.time() + 60
        got_error = None
        while time.time() < deadline and got_error is None:
            try:
                h = hvd.allreduce_async(
                    np.ones((4,), np.float32), name=f"pd/{time.time_ns()}")
                hvd.synchronize(h)
                time.sleep(0.2)  # peer may not have died yet; retry
            except (RuntimeError, TimeoutError) as e:
                got_error = e
        assert got_error is not None, \
            "survivor never observed the peer's death"

    elif scenario == "unnamed_eager":
        # Unnamed eager collectives must really communicate in a
        # multi-process world (auto call-order names through the runtime,
        # like the reference's unnamed torch ops) — NOT return local-only
        # "replicated" math.
        out = hvd.allreduce(np.full((4,), float(rank), np.float32))
        np.testing.assert_allclose(
            np.asarray(out), np.mean(np.arange(world, dtype=np.float32)))
        out = hvd.allreduce(np.full((4,), float(rank), np.float32),
                            op=hvd.Sum)
        np.testing.assert_allclose(
            np.asarray(out), np.sum(np.arange(world, dtype=np.float32)))
        g = hvd.allgather(np.array([float(rank)], np.float32))
        np.testing.assert_allclose(
            np.asarray(g), np.arange(world, dtype=np.float32))
        b = hvd.broadcast(np.full((3,), float(rank), np.float32),
                          root_rank=1)
        np.testing.assert_allclose(np.asarray(b), 1.0)
        # eager min/max/product: same execution modes as sum/average now
        # (the r1 API-surface inconsistency is gone)
        out = hvd.allreduce(np.full((4,), float(rank + 1), np.float32),
                            op=hvd.Min)
        np.testing.assert_array_equal(np.asarray(out), 1.0)
        out = hvd.allreduce(np.full((4,), float(rank + 1), np.float32),
                            op=hvd.Max)
        np.testing.assert_array_equal(np.asarray(out), float(world))
        out = hvd.allreduce(np.full((4,), rank + 2, np.int32),
                            op=hvd.Product)
        np.testing.assert_array_equal(
            np.asarray(out),
            int(np.prod(np.arange(2, world + 2, dtype=np.int64))))
        # grouped: all tensors enqueue before any synchronize, so the
        # runtime fuses them within one cycle
        group = hvd.grouped_allreduce(
            [np.full((k + 1,), float(rank), np.float32) for k in range(4)],
            op=hvd.Sum)
        for k, g in enumerate(group):
            assert g.shape == (k + 1,)
            np.testing.assert_allclose(
                np.asarray(g), np.sum(np.arange(world, dtype=np.float32)))
    elif scenario == "soak":
        # Combined stress (VERDICT r1 #8): autotune param sync + cache
        # churn/invalidation + skewed arrival + torch hooks + eager
        # interleave, all SIMULTANEOUSLY for SOAK_SECONDS, then a
        # bit-alignment audit. Each ingredient has a dedicated test; this
        # proves they compose (the reference's tests run the whole runtime
        # under mpirun the same way, SURVEY.md §4).
        import time

        import torch
        import horovod_tpu.torch as thvd

        soak_seconds = float(os.environ.get("SOAK_SECONDS", "45"))
        rng = np.random.RandomState(1000 + rank)

        model = torch.nn.Linear(6, 3)
        for p in model.parameters():  # identical start on every rank
            torch.nn.init.constant_(p, 0.5)
        opt = thvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.01),
            named_parameters=model.named_parameters())

        n_churn = 6  # 2x the cache capacity set by the test
        shapes = [(4,), (8,)]
        deadline = time.monotonic() + soak_seconds
        it = 0
        world_mean = np.mean(np.arange(world, dtype=np.float32))
        # time-bounded, but with an iteration floor so a heavily loaded
        # box still does real combined work (and a ceiling so a fast box
        # is bounded by the deadline, not the floor)
        min_iters = int(os.environ.get("SOAK_MIN_ITERS", "5"))
        debug = os.environ.get("SOAK_DEBUG")
        while True:
            # Collective termination: per-rank clocks diverge, and a rank
            # that exits one iteration before its peers strands their last
            # enqueues forever. The continue flag is itself a Min
            # allreduce over the new wire op — every rank stops at the
            # SAME iteration, the first one where any rank's deadline
            # passed.
            my_continue = 1.0 if (time.monotonic() < deadline
                                  or it < min_iters) else 0.0
            cont = hvd.synchronize(hvd.allreduce_async(
                np.full((1,), my_continue, np.float32),
                name="soak/continue", op=hvd.Min))
            if float(np.asarray(cont)[0]) < 1.0:
                break
            it += 1
            if debug:
                print(f"[r{rank}] iter {it} "
                      f"t={time.monotonic() - deadline + soak_seconds:.1f}",
                      file=sys.stderr, flush=True)
            # skewed arrival: per-rank jitter far beyond the cycle time
            time.sleep(float(rng.uniform(0, 0.02)))
            # cache churn: rotating names, period-flipping shapes
            # (invalidation), random submission order per rank
            order = rng.permutation(n_churn)  # per-rank order
            shape = shapes[(it // 7) % 2]
            handles = [
                hvd.allreduce_async(
                    np.full(shape, float(rank), np.float32),
                    name=f"soak/churn_{k}")
                for k in order
            ]
            # torch hook-driven step on per-rank data (its own named ops)
            x = torch.full((5, 6), float(rank + it % 3))
            opt.zero_grad()
            model(x).sum().backward()
            opt.step()
            # eager interleave: unnamed op through the same ordered lane
            out = hvd.allreduce(np.full((3,), float(rank), np.float32))
            np.testing.assert_allclose(np.asarray(out), world_mean,
                                       rtol=1e-5)
            for h in handles:
                np.testing.assert_allclose(
                    np.asarray(hvd.synchronize(h)), world_mean, rtol=1e-5)

        # parameters must not have diverged across ranks (hooks averaged
        # every gradient)
        digest = thvd.allgather(
            torch.cat([p.detach().reshape(-1)
                       for p in model.parameters()]).reshape(1, -1),
            name="soak/weights")
        for r in range(1, world):
            assert torch.equal(digest[0], digest[r]), \
                f"rank weights diverged after {it} iterations"
        # bit-alignment audit: every rank's cache must map the same names
        # to the same bits (the invariant cache churn attacks)
        from horovod_tpu.core import state as state_mod

        cache = state_mod.global_state().runtime.controller.cache
        bits = ";".join(
            f"{k}={cache.bit_for_name(f'soak/churn_{k}')}"
            for k in range(n_churn))
        assert it >= min_iters
        blobs = hvd.synchronize(hvd.allgather_async(
            np.frombuffer(bits.ljust(256).encode(), dtype=np.uint8)
            .reshape(1, -1).copy(), name="soak/bits"))
        rows = np.asarray(blobs)
        for r in range(1, world):
            assert np.array_equal(rows[0], rows[r]), (
                "cache bit maps diverged:\n"
                + rows[0].tobytes().decode()
                + "\nvs\n" + rows[r].tobytes().decode())
        print(f"soak: {it} iterations, bit map {bits!r}", flush=True)

    elif scenario == "lane_misuse":
        # SPMD mode only: a caller-thread global-mesh program while named
        # async ops are in flight is the documented cross-rank
        # program-order hazard (docs/troubleshooting.md) — it must RAISE
        # now, not hang. Legal path first: nothing in flight, eager
        # stacked dispatch is fine.
        import jax as _jax

        assert _jax.process_count() == world
        s = hvd.stack_per_worker(
            [np.full((2,), float(r), np.float32) for r in range(world)])
        out = hvd.allreduce(s, op=hvd.Sum)
        np.testing.assert_allclose(
            np.asarray(out), np.sum(np.arange(world, dtype=np.float32)))
        # a name only this rank announces can never complete -> stays in
        # flight deterministically
        h = hvd.allreduce_async(np.full((4,), 1.0, np.float32),
                                name=f"lane/only_rank_{rank}")
        try:
            hvd.allreduce(s, op=hvd.Sum)
        except hvd.OrderedLaneError:
            pass
        else:
            raise AssertionError("expected OrderedLaneError")
        # the public guard for user-owned pjit programs sees it too
        try:
            hvd.assert_collective_lane_clear()
        except hvd.OrderedLaneError:
            pass
        else:
            raise AssertionError("expected OrderedLaneError from guard")
        del h  # completed with SHUT_DOWN_ERROR at shutdown

    elif scenario == "cache_churn":
        # Tiny cache capacity + periodically changing shapes: constant
        # evictions (LRU bit recycling) and synchronized invalidations
        # while ranks submit in different orders. Any cross-worker
        # cache-bit misalignment — the invariant the native cache
        # (cpp/cycle.cc) must uphold — corrupts results immediately
        # (reference: response_cache.cc:232+ bit redistribution).
        rng_order = np.random.RandomState(100 + rank)  # per-rank order
        n_tensors = 12  # 3x the cache capacity set by the test
        for rounds in range(12):
            order = rng_order.permutation(n_tensors)
            handles = {}
            for t in order:
                # every 4th round, tensor shapes shift -> INVALID ->
                # synchronized invalidation + renegotiation
                size = 3 + int(t) + (rounds // 4)
                handles[int(t)] = hvd.allreduce_async(
                    np.full((size,), float(rank + t), np.float32),
                    name=f"cc/{t}", average=False)
            for t, h in handles.items():
                out = np.asarray(hvd.synchronize(h))
                expect = np.full(
                    (3 + t + (rounds // 4),),
                    sum(r + t for r in range(world)), np.float32)
                np.testing.assert_allclose(out, expect,
                                           err_msg=f"round {rounds} t {t}")
        from horovod_tpu.core import state
        cache = state.global_state().runtime.controller.cache
        assert len(cache) <= 4, len(cache)  # capacity respected

    elif scenario == "fusion_stress":
        # Many named tensors of mixed sizes/dtypes in flight per cycle —
        # the fusion bin-packer and response cache under load (reference:
        # test_tensorflow.py:152 fused many-small-tensors coverage). Ranks
        # submit in different orders; the negotiation must still converge
        # and every result must unfuse to the right buffer.
        # x64 on, so the float64 specs genuinely exercise a distinct
        # element size in the bin-packer rather than downcasting to f32.
        jax.config.update("jax_enable_x64", True)
        rng = np.random.RandomState(7)  # same on all ranks
        specs = []
        for t in range(60):
            dt = [np.float32, np.float64, np.int32][t % 3]
            shape = (int(rng.randint(1, 2000)),)
            specs.append((f"fs/{t}", dt, shape))
        for rounds in range(3):
            order = list(range(len(specs)))
            # rank-dependent submission order (reference: grads arrive in
            # different orders per rank)
            if rank % 2:
                order = order[::-1]
            handles = {}
            for t in order:
                name, dt, shape = specs[t]
                handles[t] = hvd.allreduce_async(
                    np.full(shape, float(rank + t), dt), name=name,
                    op=hvd.Sum)
            for t, h in handles.items():
                name, dt, shape = specs[t]
                out = np.asarray(hvd.synchronize(h))
                assert out.dtype == dt, (name, out.dtype, dt)
                expect = sum(float(r + t) for r in range(world))
                np.testing.assert_allclose(out, np.full(shape, expect),
                                           rtol=1e-6)
    elif scenario == "ring_sp":
        # Long-context path across REAL process boundaries: ring attention
        # ppermutes K/V around a process-spanning mesh; every shard must
        # match the dense reference.
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        import jax as _jax
        from horovod_tpu.ops.pallas import attention_reference

        assert _jax.process_count() == world
        mesh = hvd.mesh()
        B, H, S, D = 1, 2, 32, 16
        rngr = np.random.RandomState(0)  # same inputs on all ranks
        q = jnp.asarray(rngr.randn(B, H, S, D).astype(np.float32))
        k = jnp.asarray(rngr.randn(B, H, S, D).astype(np.float32))
        v = jnp.asarray(rngr.randn(B, H, S, D).astype(np.float32))

        def ring(q, k, v):
            return hvd.ring_attention(q, k, v, hvd.GLOBAL_AXES, True,
                                      None, 8, 8, 8, 8)

        spec = P(None, None, hvd.GLOBAL_AXES, None)
        out = _jax.jit(_jax.shard_map(
            ring, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))(q, k, v)
        ref = attention_reference(q, k, v, causal=True)
        shard = out.addressable_shards[0]
        got = np.asarray(_jax.device_get(shard.data))
        start = shard.index[2].start or 0
        np.testing.assert_allclose(
            got, np.asarray(ref)[:, :, start:start + got.shape[2]],
            rtol=2e-4, atol=2e-4)

    elif scenario == "pp_ep_xproc":
        # Pipeline (ppermute) and expert (all_to_all) parallelism across
        # REAL process boundaries, checked against local single-device
        # math computed from the same seeds.
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        import jax as _jax

        assert _jax.process_count() == world
        mesh = hvd.mesh()
        n_stage = mesh.shape[hvd.LOCAL_AXIS] * mesh.shape[hvd.CROSS_AXIS]
        # pipeline/moe take ONE mesh axis; pick the one spanning the world
        axis = (hvd.LOCAL_AXIS if mesh.shape[hvd.LOCAL_AXIS] == n_stage
                else hvd.CROSS_AXIS)
        rngp = np.random.RandomState(0)
        stage_ws = [rngp.randn(6, 6).astype(np.float32) * 0.3
                    for _ in range(n_stage)]
        stages = hvd.stack_stage_params([{"w": jnp.asarray(w)}
                                         for w in stage_ws])
        x = jnp.asarray(rngp.randn(4, 2, 6).astype(np.float32))

        def pp(stages, x):
            out = hvd.pipeline_apply(
                lambda p, h: jnp.tanh(h @ p["w"]), stages, x, axis)
            return hvd.last_stage_value(jnp.mean(out ** 2), axis)

        loss = _jax.jit(_jax.shard_map(
            pp, mesh=mesh, in_specs=(P(hvd.GLOBAL_AXES), P()),
            out_specs=P(), check_vma=False))(stages, x)
        # local reference: run the microbatches through all stages
        h = np.asarray(x)
        for w in stage_ws:
            h = np.tanh(h @ w)
        np.testing.assert_allclose(float(loss), float(np.mean(h ** 2)),
                                   rtol=1e-5)

        # expert parallelism: one expert per worker, all_to_all routing
        experts = hvd.stack_stage_params([
            {"w": jnp.asarray(rngp.randn(6, 6).astype(np.float32) * 0.3)}
            for _ in range(n_stage)])
        gate_w = jnp.asarray(rngp.randn(6, n_stage).astype(np.float32))
        xe = jnp.asarray(rngp.randn(n_stage * 4, 6).astype(np.float32))

        def ep(experts, gate_w, xe):
            y, probs = hvd.switch_moe(
                xe, xe @ gate_w, lambda p, h: jnp.tanh(h @ p["w"]),
                experts, axis, capacity=8)
            return jax.lax.pmean(jnp.mean(y ** 2), axis)

        mse = _jax.jit(_jax.shard_map(
            ep, mesh=mesh,
            in_specs=(P(hvd.GLOBAL_AXES), P(), P(hvd.GLOBAL_AXES)),
            out_specs=P(), check_vma=False))(experts, gate_w, xe)
        assert np.isfinite(float(mse))

    elif scenario == "dtype_matrix":
        # Reference-breadth dtype x op sweep over the REAL wire (r5;
        # reference: test/test_torch.py dtype sweeps ~1,382 LoC,
        # test_tensorflow.py:152-649 fused many-small + variable-size
        # allgather per dtype). Values deliberately include payloads
        # that corrupt if anything narrows to 32-bit (2**40 int64,
        # 1e300 float64) — the widening shim (runtime/executor.py
        # _widen_for_ring) and the enqueue conversion (_to_plane) are
        # exactly where such corruption would hide.
        import ml_dtypes

        dtypes = [np.uint8, np.int8, np.int16, np.uint16, np.int32,
                  np.uint32, np.int64, np.float16, ml_dtypes.bfloat16,
                  np.float32, np.float64, np.bool_]

        def per_rank_value(dti, r):
            if dti == np.bool_:
                return bool(r % 2)
            if dti.kind in "iu":
                big = (1 << 40) if dti.itemsize == 8 else 0
                return dti.type(big + 3 * (r + 1))
            big = 1e300 if dti == np.float64 else 0.0
            return dti.type(big + 1.5 * (r + 1))

        for dt in dtypes:
            dti = np.dtype(dt)
            tag = dti.name
            x = np.full((6,), per_rank_value(dti, rank), dti)
            # -- allreduce sum (exact, computed wide then cast like the
            #    ring kernels)
            out = np.asarray(hvd.synchronize(hvd.allreduce_async(
                x, name=f"dm/{tag}/ar", average=False)))
            assert out.dtype == dti, (tag, out.dtype)
            wide = np.int64 if dti.kind in "iu" else np.float64
            expect = np.sum([np.asarray(per_rank_value(dti, r),
                                        dtype=wide)
                             for r in range(world)]).astype(dti)
            np.testing.assert_array_equal(out, np.full((6,), expect),
                                          err_msg=f"allreduce {tag}")
            # -- allreduce min (op-generalized ring) for ordered dtypes
            if dti != np.bool_:
                out = np.asarray(hvd.synchronize(hvd.allreduce_async(
                    x, name=f"dm/{tag}/min", op=hvd.Min)))
                np.testing.assert_array_equal(
                    out, np.full((6,), per_rank_value(dti, 0), dti),
                    err_msg=f"min {tag}")
            # -- broadcast root 1
            out = np.asarray(hvd.synchronize(hvd.broadcast_async(
                x, root_rank=1, name=f"dm/{tag}/bc")))
            assert out.dtype == dti, (tag, out.dtype)
            np.testing.assert_array_equal(
                out, np.full((6,), per_rank_value(dti, 1), dti),
                err_msg=f"broadcast {tag}")
            # -- variable-size allgather: rank r contributes (r+1, 2)
            out = np.asarray(hvd.synchronize(hvd.allgather_async(
                np.full((rank + 1, 2), per_rank_value(dti, rank), dti),
                name=f"dm/{tag}/agv")))
            expect = np.concatenate(
                [np.full((r + 1, 2), per_rank_value(dti, r), dti)
                 for r in range(world)])
            assert out.dtype == dti, (tag, out.dtype)
            np.testing.assert_array_equal(out, expect,
                                          err_msg=f"allgather {tag}")
            if dti == np.bool_:
                continue  # rs/a2a arithmetic on bool is not a contract
            # -- reducescatter sum: dim 0 = world*2
            data = np.stack([
                (np.arange(world * 2 * 3) % 5 + 1).reshape(world * 2, 3)
                .astype(wide) * np.asarray(per_rank_value(dti, r), wide)
                for r in range(world)])
            mine = data[rank].astype(dti)
            out = np.asarray(hvd.reducescatter(mine, op=hvd.Sum))
            assert out.dtype == dti, (tag, out.dtype)
            full = np.sum([data[r].astype(wide) for r in range(world)],
                          axis=0).astype(dti)
            np.testing.assert_array_equal(
                out, full[rank * 2:(rank + 1) * 2],
                err_msg=f"reducescatter {tag}")
            # -- alltoall
            out = np.asarray(hvd.alltoall(mine, name=f"dm/{tag}/a2a"))
            assert out.dtype == dti, (tag, out.dtype)
            expect = np.concatenate(
                [data[j].astype(dti)[rank * 2:(rank + 1) * 2]
                 for j in range(world)])
            np.testing.assert_array_equal(out, expect,
                                          err_msg=f"alltoall {tag}")

        # -- fused many-small ACROSS dtypes: every tensor enqueued before
        #    any synchronize, so one cycle negotiates and bin-packs the
        #    whole burst in per-dtype fusion groups (reference:
        #    test_tensorflow.py fused many-small sweeps)
        handles = []
        for dt in dtypes:
            dti = np.dtype(dt)
            for i in range(6):
                arr = np.full((4,), per_rank_value(dti, rank), dti)
                handles.append((dti, i, hvd.allreduce_async(
                    arr, name=f"dmf/{dti.name}/{i}", average=False)))
        for dti, i, h in handles:
            out = np.asarray(hvd.synchronize(h))
            wide = np.int64 if dti.kind in "iu" else np.float64
            expect = np.sum([np.asarray(per_rank_value(dti, r),
                                        dtype=wide)
                             for r in range(world)]).astype(dti)
            np.testing.assert_array_equal(
                out, np.full((4,), expect),
                err_msg=f"fused burst {dti.name}/{i}")

    elif scenario == "torch_sink":
        # Torch hook-driven optimizer with gradient accumulation, eager
        # ops interleaved while async allreduces are in flight, and a
        # final cross-rank parameter-identity check.
        import torch
        import torch.nn.functional as F

        import horovod_tpu.torch as thvd

        torch.manual_seed(42)
        model = torch.nn.Sequential(
            torch.nn.Linear(16, 32), torch.nn.ReLU(),
            torch.nn.Linear(32, 4))
        opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
        opt = thvd.DistributedOptimizer(
            opt, named_parameters=model.named_parameters(),
            backward_passes_per_step=2)
        thvd.broadcast_parameters(model.state_dict(), root_rank=0)
        thvd.broadcast_optimizer_state(opt, root_rank=0)
        rng = np.random.RandomState(rank)
        for step in range(10):
            for _ in range(2):
                x = torch.tensor(rng.rand(8, 16), dtype=torch.float32)
                y = torch.tensor(rng.randint(0, 4, (8,)), dtype=torch.long)
                F.cross_entropy(model(x), y).backward()
            m = thvd.allreduce(torch.tensor([float(rank)]),
                               name=f"ts/metric{step}")
            assert abs(float(m) - np.mean(range(world))) < 1e-6
            opt.step()
            opt.zero_grad()
        flat = torch.cat([p.data.flatten() for p in model.parameters()])
        root = thvd.broadcast(flat.clone(), root_rank=0, name="ts/final")
        assert torch.allclose(root, flat, rtol=1e-5, atol=1e-7)

    elif scenario == "torch":
        # The torch binding end-to-end under a real multi-process world
        # (reference: test/test_torch.py run under mpirun): hook-driven
        # DistributedOptimizer training convergence across ranks, plus
        # parameter/optimizer-state/object broadcast from rank 0.
        import torch

        import horovod_tpu.torch as thvd

        # distinct per-rank values average correctly
        x = torch.full((5,), float(rank))
        out = thvd.allreduce(x, name="t/ar")
        expected = float(np.mean(np.arange(world)))
        assert torch.allclose(out, torch.full((5,), expected)), out

        # ragged allgather
        g = thvd.synchronize(
            thvd.allgather_async(torch.full((rank + 1, 2), float(rank)),
                                 name="t/ag"))
        want = torch.cat(
            [torch.full((r + 1, 2), float(r)) for r in range(world)])
        assert torch.equal(g, want), g

        # model + optimizer: ranks start with different weights, broadcast
        # aligns them, hooks average gradients of per-rank data so all
        # ranks stay in lockstep
        torch.manual_seed(rank)  # deliberately different init per rank
        model = torch.nn.Linear(4, 2)
        opt = thvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9),
            named_parameters=model.named_parameters())
        thvd.broadcast_parameters(model.state_dict(), root_rank=0)
        thvd.broadcast_optimizer_state(opt, root_rank=0)
        torch.manual_seed(100 + rank)  # different data per rank
        for _ in range(3):
            data = torch.randn(8, 4)
            target = torch.randn(8, 2)
            loss = (model(data) - target).pow(2).mean()
            loss.backward()
            opt.step()
            opt.zero_grad()
        # weights must be bitwise-identical across ranks after sync steps
        digest = thvd.allgather(
            torch.cat([p.detach().reshape(-1) for p in model.parameters()])
            .reshape(1, -1), name="t/weights")
        for r in range(1, world):
            assert torch.equal(digest[0], digest[r]), "ranks diverged"

        # object broadcast (resume-epoch convention)
        obj = {"epoch": 7, "rank_was": 0} if rank == 0 else None
        got = thvd.broadcast_object(obj, root_rank=0, name="t/obj")
        assert got == {"epoch": 7, "rank_was": 0}, got

        # sparse embedding exchange (BASELINE config #5): each rank
        # touches different rows; the allgather-based sparse allreduce
        # must equal the dense average
        emb = torch.nn.Embedding(10, 4, sparse=True)
        thvd.broadcast_parameters(emb.state_dict(), root_rank=0)
        ids = torch.tensor([rank, rank + 1, 5])  # overlap on 5
        opt2 = thvd.DistributedOptimizer(
            torch.optim.SGD(emb.parameters(), lr=1.0),
            named_parameters=emb.named_parameters())
        w_before = emb.weight.detach().clone()
        emb(ids).sum().backward()
        opt2.synchronize()
        g = emb.weight.grad.coalesce().to_dense()
        dense = torch.zeros(10, 4)
        for r in range(world):
            for row in (r, r + 1, 5):
                dense[row] += 1.0
        np.testing.assert_allclose(np.asarray(g), np.asarray(dense / world),
                                   rtol=1e-6)
        with opt2.skip_synchronize():
            opt2.step()
        # sparse SGD applies the averaged rows; all ranks identical
        dig = thvd.allgather(emb.weight.detach().reshape(1, -1),
                             name="t/emb")
        for r in range(1, world):
            assert torch.equal(dig[0], dig[r]), "embedding diverged"
        np.testing.assert_allclose(
            np.asarray(emb.weight.detach()),
            np.asarray(w_before - dense / world), rtol=1e-5)

    elif scenario == "lane_hazard":
        # The user-owned-global-program interleaving hazard (VERDICT r2
        # ask 8): rank 0 has a named op in flight while "its caller
        # thread runs its own global program" (simulated by sleeping —
        # the runtime only sees silence); rank 1 never announces the
        # tensor. The lane watchdog must print its diagnostic within
        # one stall-check period (the test asserts on our output).
        import time as _time

        # both ranks bring the runtime up (the comm is created lazily on
        # first use) and agree on a warmup tensor first
        hvd.allreduce(np.ones(2, np.float32), name="hazard/warm")
        if rank == 0:
            h = hvd.allreduce_async(np.ones(4, np.float32),
                                    name="hazard/x")
            _time.sleep(2.5)  # > 2 stall periods of 0.5s
            try:
                hvd.synchronize(h)
            except Exception:
                pass  # peers shut down; the hang became an error — fine
        else:
            _time.sleep(2.5)

    elif scenario == "tensorflow":
        # The TF binding end-to-end under a real multi-process world
        # (reference: test/test_tensorflow.py run under mpirun): eager
        # collectives, custom gradients, DistributedGradientTape +
        # DistributedOptimizer lockstep training, broadcast_variables,
        # IndexedSlices gather path, object broadcast.
        import tensorflow as tf

        import horovod_tpu.tensorflow as tfhvd

        # distinct per-rank values: average and sum
        x = tf.fill([5], float(rank))
        out = tfhvd.allreduce(x, average=True)
        expected = float(np.mean(np.arange(world)))
        np.testing.assert_allclose(out.numpy(), np.full(5, expected),
                                   rtol=1e-6)
        out = tfhvd.allreduce(x, average=False)
        np.testing.assert_allclose(out.numpy(),
                                   np.full(5, float(sum(range(world)))),
                                   rtol=1e-6)

        # ragged allgather
        g = tfhvd.allgather(tf.fill([rank + 1, 2], float(rank)))
        want = np.concatenate(
            [np.full((r + 1, 2), float(r)) for r in range(world)])
        np.testing.assert_allclose(g.numpy(), want)

        # broadcast from a non-zero root
        b = tfhvd.broadcast(tf.fill([3], float(rank)), root_rank=1)
        np.testing.assert_allclose(b.numpy(), np.full(3, 1.0))

        # gradient THROUGH a collective (custom_gradient):
        # y = sum(allreduce_sum(x)) -> dy/dx = allreduce_sum(ones) = world
        xv = tf.Variable([1.0, 2.0])
        with tf.GradientTape() as tape:
            y = tf.reduce_sum(tfhvd._allreduce(xv))
        gx = tape.gradient(y, xv)
        np.testing.assert_allclose(gx.numpy(), [world, world], rtol=1e-6)

        # DistributedGradientTape: per-rank loss scale (rank+1) ->
        # averaged gradient = mean over ranks of 2*(rank+1)*v
        v = tf.Variable([1.0, 3.0])
        with tf.GradientTape() as tape:
            loss = (rank + 1) * tf.reduce_sum(v * v)
        dtape = tfhvd.DistributedGradientTape(tape)
        grads = dtape.gradient(loss, [v])
        scale = np.mean([r + 1 for r in range(world)])
        np.testing.assert_allclose(grads[0].numpy(), 2 * scale * v.numpy(),
                                   rtol=1e-6)

        # broadcast_variables aligns different inits; DistributedOptimizer
        # keeps ranks in lockstep over different per-rank data
        tf.random.set_seed(rank)
        w = tf.Variable(tf.random.normal([4, 2]))
        bias = tf.Variable(tf.random.normal([2]))
        tfhvd.broadcast_variables([w, bias], root_rank=0)
        opt = tfhvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.05))
        tf.random.set_seed(100 + rank)  # different data per rank
        for _ in range(3):
            data = tf.random.normal([8, 4])
            target = tf.random.normal([8, 2])
            with tf.GradientTape() as tape:
                loss = tf.reduce_mean(
                    tf.square(tf.matmul(data, w) + bias - target))
            grads = tape.gradient(loss, [w, bias])
            opt.apply_gradients(zip(grads, [w, bias]))
        digest = tfhvd.allgather(tf.reshape(
            tf.concat([tf.reshape(w, [-1]), tf.reshape(bias, [-1])], 0),
            [1, -1]))
        for r in range(1, world):
            np.testing.assert_array_equal(digest[0].numpy(),
                                          digest[r].numpy(),
                                          err_msg="ranks diverged")

        # IndexedSlices -> gather path (embedding-style sparse grads)
        s = tf.IndexedSlices(tf.fill([2, 3], float(rank + 1)),
                             tf.constant([rank, rank + 1]),
                             tf.constant([world + 1, 3]))
        r = tfhvd.allreduce(s, average=False)
        assert r.values.shape[0] == 2 * world, r.values.shape
        got_idx = np.sort(r.indices.numpy())
        want_idx = np.sort(np.concatenate(
            [[rr, rr + 1] for rr in range(world)]))
        np.testing.assert_array_equal(got_idx, want_idx)

        # object broadcast (resume-epoch convention)
        obj = {"epoch": 7, "rank_was": 0} if rank == 0 else None
        got = tfhvd.broadcast_object(obj, root_rank=0, name="tf/obj")
        assert got == {"epoch": 7, "rank_was": 0}, got

        # dtype sweep with DISTINCT per-rank values through the TF layer
        # (reference: test_tensorflow.py:314-460 sweeps dtypes x dims
        # across ranks; the single-controller tests can only assert
        # replicated-world identities)
        for tf_dt, avg in [(tf.float32, True), (tf.float64, True),
                           (tf.bfloat16, True), (tf.int32, False),
                           (tf.int64, False)]:
            for dim in (1, 2):
                shape = (3,) * dim
                x = tf.cast(tf.fill(shape, rank + 1), tf_dt)
                out = tfhvd.allreduce(x, average=avg, name=None)
                assert out.dtype == tf_dt, (tf_dt, out.dtype)
                vals = [r + 1 for r in range(world)]
                want = np.mean(vals) if avg else np.sum(vals)
                np.testing.assert_allclose(
                    np.asarray(tf.cast(out, tf.float64).numpy()),
                    np.full(shape, want), rtol=1e-2)
                # allgather the same dtype: distinct rank rows
                ga = tfhvd.allgather(tf.cast(
                    tf.fill((1,) + shape, rank), tf_dt))
                assert ga.shape[0] == world
                np.testing.assert_allclose(
                    np.asarray(tf.cast(ga, tf.float64).numpy())[..., 0]
                    .reshape(world, -1)[:, 0], np.arange(world))

        # fused many-small-tensors burst THROUGH the TF tape (VERDICT r3
        # ask 5/6): 48 small grads in one DistributedGradientTape.gradient
        # call must ride few fused cycles, not 48 rings — asserted on the
        # deterministic exchange-calls counter, not wall clock
        from horovod_tpu.core import state as _state

        net = _state.global_state().runtime.controller.net
        n_small = 48
        # identical weights everywhere, per-rank LOSS scale: the averaged
        # gradient is then 2 * mean(rank+1) * w — cross-rank averaging is
        # observable while the expectation stays closed-form
        weights = [tf.Variable(tf.fill([7 + (i % 5)], float(i + 1)))
                   for i in range(n_small)]
        with tf.GradientTape() as tape:
            loss = tf.add_n([tf.reduce_sum(w * w) * (rank + 1)
                             for w in weights])
        dtape = tfhvd.DistributedGradientTape(tape)
        ex0 = net.exchange_calls()
        grads = dtape.gradient(loss, weights)
        ex1 = net.exchange_calls()
        mean_scale = np.mean([r + 1 for r in range(world)])
        for i, (w, g) in enumerate(zip(weights, grads)):
            np.testing.assert_allclose(
                g.numpy(), 2 * mean_scale * w.numpy(), rtol=1e-5)
        # unfused would cost 2*(world-1) ring exchanges PER gradient =
        # 2*(w-1)*48; fused bin-packing collapses the burst into a
        # handful of buffers. Generous bound: a quarter of unfused.
        unfused = 2 * (world - 1) * n_small
        burst = ex1 - ex0
        assert burst <= unfused // 4, \
            f"TF tape burst not fused: {burst} exchanges (unfused={unfused})"
        print(f"tf-tape-burst exchanges={burst} unfused={unfused}",
              flush=True)

    elif scenario == "tensorflow_graph":
        # TF1 graph-mode path across a real multi-process world
        # (reference: horovod/tensorflow/__init__.py:125-192 —
        # broadcast_global_variables + BroadcastGlobalVariablesHook under
        # MonitoredTrainingSession): per-rank divergent initializers must
        # converge to rank 0's values through the session-run broadcast.
        import tensorflow as tf

        import horovod_tpu.tensorflow as tfhvd

        g = tf.Graph()
        with g.as_default():
            assert not tf.executing_eagerly()
            v1 = tf.compat.v1.get_variable(
                "v1", initializer=np.full((3, 2), float(rank + 1),
                                          np.float32))
            v2 = tf.compat.v1.get_variable(
                "v2", initializer=np.asarray([10.0 * (rank + 1)],
                                             np.float32))
            # int64 variable: exercises the 64-bit bit-pair path through
            # the graph bridge
            step = tf.compat.v1.get_variable(
                "global_step", initializer=np.int64(1000 + rank),
                dtype=tf.int64)
            hook = tfhvd.BroadcastGlobalVariablesHook(root_rank=0)
            with tf.compat.v1.train.MonitoredTrainingSession(
                    hooks=[hook]) as sess:
                got1, got2, gots = sess.run([v1, v2, step])
            np.testing.assert_allclose(got1, np.full((3, 2), 1.0))
            np.testing.assert_allclose(got2, [10.0])
            assert gots == 1000, gots

        # direct graph op (no hook): explicit broadcast_variables from a
        # NON-zero root inside a plain compat.v1 Session
        g2 = tf.Graph()
        with g2.as_default():
            w = tf.compat.v1.get_variable(
                "w", initializer=np.arange(4, dtype=np.float32) + rank)
            op = tfhvd.broadcast_variables([w], root_rank=1)
            with tf.compat.v1.Session() as sess:
                sess.run(tf.compat.v1.global_variables_initializer())
                sess.run(op)
                got = sess.run(w)
            np.testing.assert_allclose(got,
                                       np.arange(4, dtype=np.float32) + 1)

    elif scenario == "tensorflow_errors":
        # Error paths THROUGH the TF binding (reference:
        # test_tensorflow.py:314-460 test_horovod_allreduce_error /
        # _type_error / _grad_cpu): a shape or dtype mismatched across
        # ranks must raise on EVERY rank, and the world must stay usable.
        import tensorflow as tf

        import horovod_tpu.tensorflow as tfhvd

        # shape mismatch across ranks
        x = tf.ones([4] if rank == 0 else [5])
        try:
            tfhvd.allreduce(x, average=False, name="bad/shape")
        except Exception as e:  # noqa: BLE001 — py_function wraps it
            assert "shape" in str(e).lower() or "mismatch" in str(e).lower(), \
                str(e)
        else:
            raise AssertionError("expected cross-rank shape error")

        # dtype mismatch across ranks under one wire name
        y = (tf.ones([3], tf.float32) if rank == 0
             else tf.ones([3], tf.int32))
        try:
            tfhvd.allreduce(y, average=False, name="bad/dtype")
        except Exception as e:  # noqa: BLE001
            msg = str(e).lower()
            assert "dtype" in msg or "type" in msg or "mismatch" in msg, \
                str(e)
        else:
            raise AssertionError("expected cross-rank dtype error")

        # the world must still be usable after both failures
        out = tfhvd.allreduce(tf.fill([2], float(rank)), average=False,
                              name="good/after")
        np.testing.assert_allclose(out.numpy(),
                                   np.full(2, float(sum(range(world)))))

    elif scenario == "pod_soak":
        # Pod dress rehearsal (VERDICT r3 ask 3): the whole stack in ONE
        # job the way a real pod run would see it — native wire, autotune
        # on (env from the test), per-rank timelines, torch + TF + JAX
        # collectives interleaved, a mid-run rank-0 checkpoint, a HARD
        # death (os._exit, no shutdown, simulating preemption), and a
        # resume run that restores, continues, and asserts lockstep.
        # Integration bugs live in the seams between these — each is
        # tested separately elsewhere.
        #
        # env: SOAK_DIR (artifact directory), SOAK_RESUME ("1" on the
        # second run). NOTE: HOROVOD_TIMELINE is set per-rank by the
        # TEST's wrapper env before hvd.init() ran above (mp_worker's
        # module init), so timelines are already recording here.
        import jax.numpy as jnp
        import torch

        import horovod_tpu.torch as thvd
        import horovod_tpu.tensorflow as tfhvd
        import tensorflow as tf
        from horovod_tpu import checkpoint as ckpt

        soak_dir = os.environ["SOAK_DIR"]
        resume = os.environ.get("SOAK_RESUME") == "1"
        ckpt_dir = os.path.join(soak_dir, "ckpt")

        # identical model state everywhere (broadcast aligns below)
        torch.manual_seed(1234 + rank)  # deliberately divergent init
        tmodel = torch.nn.Linear(6, 3)
        topt = thvd.DistributedOptimizer(
            torch.optim.SGD(tmodel.parameters(), lr=0.02),
            named_parameters=tmodel.named_parameters())
        thvd.broadcast_parameters(tmodel.state_dict(), root_rank=0)

        tf_w = tf.Variable(tf.fill([5], float(rank + 1)))
        tfhvd.broadcast_variables([tf_w], root_rank=0)

        jnp_w = np.full((4,), 1.0, np.float32)

        start_step = 0
        if resume:
            state0 = {"step": 0, "jnp_w": np.zeros((4,), np.float32)}
            restored, ckpt_step = ckpt.restore_latest(ckpt_dir, state0)
            assert ckpt_step == 5, f"resumed wrong checkpoint {ckpt_step}"
            start_step = int(restored["step"])
            jnp_w = np.asarray(restored["jnp_w"])
            assert start_step == 5, f"resumed wrong step {start_step}"

        def one_step(step):
            # JAX named collective (the runtime/wire path)
            h = hvd.allreduce_async(jnp_w * (rank + 1),
                                    name="soak/jnp_w")
            # torch hook path
            topt.zero_grad()
            loss = (tmodel(torch.ones(2, 6)).sum()) * (rank + 1)
            loss.backward()
            topt.step()
            # TF tape path
            with tf.GradientTape() as tape:
                tloss = tf.reduce_sum(tf_w * tf_w) * (rank + 1)
            dtape = tfhvd.DistributedGradientTape(tape)
            (g,) = dtape.gradient(tloss, [tf_w])
            tf_w.assign_sub(0.01 * g)
            return np.asarray(hvd.synchronize(h))

        stop_at = 5 if not resume else 10
        for step in range(start_step, stop_at):
            out = one_step(step)

        if not resume:
            ckpt.save(ckpt_dir, {"step": 5, "jnp_w": jnp_w}, step=5)
            # everyone waits until the save is published before dying —
            # an allreduce doubles as the barrier
            h = hvd.allreduce_async(np.ones(1, np.float32),
                                    name="soak/barrier")
            hvd.synchronize(h)
            print(f"CKPT_SAVED rank={rank}", flush=True)
            sys.stdout.flush()
            os._exit(137)  # hard preemption: no shutdown, no atexit

        # resume run: final lockstep assertions across every surface
        tdigest = np.concatenate(
            [p.detach().numpy().ravel() for p in tmodel.parameters()])
        full = np.concatenate([tdigest, tf_w.numpy(), out])
        h = hvd.allgather_async(full[None, :], name="soak/digest")
        dig = np.asarray(hvd.synchronize(h))
        for r in range(1, world):
            np.testing.assert_array_equal(dig[0], dig[r],
                                          err_msg="soak ranks diverged")
        print(f"SOAK_DONE rank={rank} steps={stop_at}", flush=True)

    elif scenario == "zero_parity":
        # ZeRO-1 sharded optimizer over the REAL wire: reduce-scatter +
        # update-on-shard + allgather must match the replicated update
        # computed locally from the same per-rank gradients. Integer-
        # valued f32 grads, so the ring sums (and /world for power-of-two
        # worlds) are exact and the SGD comparison is BIT-exact.
        import optax

        rng = np.random.RandomState(0)  # same tree on every rank
        params = {
            "a": np.asarray(rng.randint(-8, 8, (7,)), np.float32),
            "b": np.asarray(rng.randint(-8, 8, (5, 6)), np.float32),
        }
        # rank-DEPENDENT integer grads (known closed form across ranks)
        def grad_for(r, step):
            gr = np.random.RandomState(100 + step)
            base = {k: np.asarray(gr.randint(-4, 4, v.shape), np.float32)
                    for k, v in params.items()}
            return {k: v + np.float32(r) for k, v in base.items()}

        def mean_grad(step):
            acc = {k: np.zeros(v.shape, np.float64)
                   for k, v in params.items()}
            for r in range(world):
                g = grad_for(r, step)
                for k in acc:
                    acc[k] += g[k]
            return {k: (v / world).astype(np.float32) for k, v in
                    acc.items()}

        import jax.numpy as jnp

        sh = hvd.sharded_update(optax.sgd(0.25))
        jparams = {k: jnp.asarray(v) for k, v in params.items()}
        state = sh.init(jparams)
        p_sh = jparams
        expect = {k: v.copy() for k, v in params.items()}
        for step in range(3):
            g = {k: jnp.asarray(v)
                 for k, v in grad_for(rank, step).items()}
            upd, state = sh.update(g, state, p_sh)
            p_sh = optax.apply_updates(p_sh, upd)
            mg = mean_grad(step)
            for k in expect:
                expect[k] = expect[k] - np.float32(0.25) * mg[k]
        for k in expect:
            np.testing.assert_array_equal(
                np.asarray(p_sh[k]), expect[k],
                err_msg=f"sharded SGD diverged from replicated math "
                        f"on leaf {k} (rank {rank})")

        # fused flat AdamW over the wire vs replicated optax.adamw on
        # the mean grad (f32 round-off tolerance)
        ref = optax.adamw(1e-2, weight_decay=1e-3)
        ref_state = ref.init(jparams)
        sa = hvd.sharded_adamw(1e-2, weight_decay=1e-3)
        sa_state = sa.init(jparams)
        p_ref, p_sa = jparams, jparams
        for step in range(2):
            mg = {k: jnp.asarray(v) for k, v in mean_grad(step).items()}
            upd, ref_state = ref.update(mg, ref_state, p_ref)
            p_ref = optax.apply_updates(p_ref, upd)
            g = {k: jnp.asarray(v)
                 for k, v in grad_for(rank, step).items()}
            p_sa, sa_state = sa.apply(p_sa, sa_state, g)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_sa[k]), np.asarray(p_ref[k]),
                rtol=2e-5, atol=2e-6,
                err_msg=f"sharded adamw diverged on leaf {k}")
        # the state gauge must report the SHARD footprint, not the
        # replicated one (master+mu+nu f32 ~= 3 x params / world,
        # padding-inflated on these toy shapes)
        m = hvd.metrics().get("horovod_sharded_state_bytes")
        assert m and m["values"][0]["value"] > 0

    elif scenario == "debug_locks":
        # short training loop under the deadlock witness
        # (HOROVOD_DEBUG_LOCKS=1 set by the launcher): the runtime's own
        # locks are DebugLocks; assert the run is violation-free, the
        # observed acquisition order is consistent with the static
        # lock-order graph, and lock events reached the flight recorder.
        assert os.environ.get("HOROVOD_DEBUG_LOCKS") == "1"
        from horovod_tpu import flight_recorder
        from horovod_tpu.analysis import lockgraph, witness

        for step in range(4):
            hs = [hvd.allreduce_async(
                      np.full((64,), float(rank + step), np.float32),
                      name=f"grad/w{i}") for i in range(3)]
            hs.append(hvd.allgather_async(
                np.full((rank + 1, 2), rank, np.float32), name="ag/x"))
            for h in hs:
                hvd.synchronize(h)
        state = hvd.dump_debug_state()
        viols = witness.violations()
        assert not viols, f"witness violations on rank {rank}: {viols}"
        edges = witness.order_edges()
        assert edges, "expected at least one observed lock-order edge"
        pkg = os.path.dirname(os.path.dirname(
            os.path.abspath(hvd.__file__)))
        static = lockgraph.analyze_paths(
            [os.path.join(pkg, "horovod_tpu")], root=pkg)
        conflicts = witness.check_static_consistency(static.edges)
        assert not conflicts, f"static/runtime order conflict: {conflicts}"
        lock_events = [e for e in flight_recorder.recorder().events()
                       if str(e.get("kind", "")).startswith("lock_")]
        assert lock_events, "no lock_* events in the flight recorder"
        # the dump's state providers include the witness's view
        assert state["state"].get("locks", {}).get("enabled") is True

    elif scenario == "comms_degraded":
        # ISSUE 16 acceptance: a netdelay window on the host-ring data
        # plane must trip the comms-plane degradation detector exactly
        # once, naming the host_ring lane; the shutdown dump then
        # carries the ledger for the postmortem comms report.
        import time

        from horovod_tpu import comms, flight_recorder

        t = comms.tracker()
        # chaos t0 armed at the first inject seam during init, so it is
        # strictly before this scenario's entry stamp: the delay window
        # (never-closing, seconds=inf) is guaranteed open by
        # t_scn + after, and the fast phase below — seconds from t_scn —
        # is guaranteed clean as long as after= grants real headroom
        # over a loaded box's init tail
        t_scn = time.monotonic()
        # fast phase: enough host-ring ops to pass detector warmup and
        # set the lane's peak-observed roofline, all before the fault's
        # after= window opens
        for step in range(12):
            h = hvd.allreduce_async(
                np.full((4096,), float(rank), np.float32), name="cd/fast")
            hvd.synchronize(h)
        led = t.ledger()["lanes"].get("host_ring")
        assert led and led["ops_total"] >= 8, led
        assert not led["alerting"], led
        # wait out the fault-free window (anchored to the scenario
        # stamp, an upper bound on chaos t0), then run a FIXED number of
        # now-delayed ops — both ranks must issue the same collective
        # sequence in lockstep (a break-on-alert loop lets the first
        # alerting rank shut down while its peer still has an op in
        # flight). The EWMA (alpha 0.25) falls to 0.75^k of the fast
        # peak after k ~100x-slower records, crossing the 0.5 threshold
        # by k=3; 10 ops is deep margin
        wake = t_scn + float(os.environ.get("COMMS_DELAY_AFTER", "8.5"))
        time.sleep(max(0.0, wake - time.monotonic()))
        for step in range(10):
            h = hvd.allreduce_async(
                np.full((4096,), float(rank), np.float32), name="cd/slow")
            hvd.synchronize(h)
        evs = [e for e in flight_recorder.recorder().events()
               if e.get("kind") == "comms_degraded"
               and e.get("lane") == "host_ring"]
        assert len(evs) == 1, evs  # latched: ONE event per crossing
        assert evs[0]["op"] == "allreduce", evs
        assert evs[0]["utilization"] < evs[0]["threshold"], evs
        led = t.ledger()["lanes"]["host_ring"]
        assert led["alerting"] and led["degraded_count"] == 1, led
        assert led["last_degraded"]["op"] == "allreduce", led
        # leave a dump for the launcher's postmortem comms-report check
        hvd.dump_debug_state(reason="comms_degraded_test")
        print("COMMS_DEGRADED_OK", flush=True)

    else:
        raise SystemExit(f"unknown scenario {scenario}")

    hvd.shutdown()
    print(f"OK rank={rank}", flush=True)


if __name__ == "__main__":
    main()
