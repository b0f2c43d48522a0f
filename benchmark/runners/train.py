"""The training runner: one cell's model trained through the program's
own entry points, ``hvd.DistributedOptimizer`` inside
``training.make_train_step``, one dispatch per step, a fresh seeded
batch every step fed through ``horovod_tpu.data.prefetch_to_device``.

Set-up builds one object - the compiled step with its state - drives it
from the seed through its first ``check_steps`` steps with the window's
own call and feed, and hands that same object to the window. Once the
window has closed and the program's state is freed, the plain reference
follows the same first steps from the same seed, and each step's loss,
the first gradient's norm (from the optimizer's first moment after one
step) and the norm of the parameters' change are compared by the worst
leaf. The reference's time is printed and is not part of ``setup_s``.
"""

from __future__ import annotations

import itertools
import statistics

import numpy as np

from benchmark import flops, harness, reference, traffic, weights
from benchmark import trace as trace_mod


def _objectives():
    from horovod_tpu.models import transformer

    return {
        "causal_lm": transformer.causal_lm_loss,
        "masked_lm": lambda logits, labels: transformer.masked_lm_loss(
            logits, labels[0], labels[1]),
    }


def _first_moment(opt_state):
    """The ``mu`` tree of the Adam state inside ``opt_state``."""
    import jax

    has_mu = lambda node: hasattr(node, "mu")
    for node in jax.tree.leaves(opt_state, is_leaf=has_mu):
        if has_mu(node):
            return node.mu
    raise ValueError("no Adam first moment in the optimizer state")


def leaf_gaps(program, ref, skip=()):
    """Per leaf, the gap between the program's norm and the reference's
    (not the norm of their difference), over the reference's norm of
    that leaf or of the median leaf, whichever is larger: some gradients
    are all but zero."""
    floor = statistics.median(ref.values())
    return {k: abs(program[k] - ref[k]) / max(ref[k], floor)
            for k in ref if k not in skip}


def noise_driven(ref_grad_norms):
    """Leaves whose gradient is zero by the mathematics (a key bias: the
    softmax does not see it) and only rounding noise in any arithmetic.
    Adam divides the noise by its own size and takes a full-size step in
    the noise's direction, so the parameters' change of such a leaf says
    nothing about the program; it is left out of that one comparison.
    The test is the reference's own: a gradient norm under a thousandth
    of the median leaf's."""
    floor = 1e-3 * statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < floor}


def _replica_digests(params, mesh):
    """Per device, two 32-bit digests (sum and xor of the bit patterns)
    of its own copy of the replicated parameters."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names

    def digest(tree):
        words = [jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
                 for x in jax.tree.leaves(tree)]
        total = sum(jnp.sum(w, dtype=jnp.uint32) for w in words)
        mixed = jnp.uint32(0)
        for w in words:
            mixed = mixed ^ jnp.bitwise_xor.reduce(w)
        return jnp.stack([total, mixed])[None]

    fn = jax.jit(jax.shard_map(digest, mesh=mesh, in_specs=P(),
                               out_specs=P(axes), check_vma=False))
    return jax.device_get(fn(params))


class Program:
    """The system under test: the model of one cell behind the program's
    own entry points, its compiled step, and (after :meth:`start`) its
    state and feed for one seed."""

    def __init__(self, cfg, published, mix, devices):
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvd
        from horovod_tpu import training

        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.rows = mix["batch_per_chip"] * len(devices)
        # token ids below the published vocabulary (the table may be padded)
        self.vocab = min(published["vocab_size"], cfg["vocab_size"])
        hvd.init(devices=devices)
        self.mesh = hvd.mesh()
        self.replicated = NamedSharding(self.mesh, P())
        model = harness.transformer(cfg)
        self.opt = hvd.DistributedOptimizer(
            optax.adamw(mix["learning_rate"]))
        self.step, self.batch_sharding = training.make_train_step(
            model, self.opt, loss_fn=_objectives()[mix["objective"]])
        self.compiled = self.feed = self.state = None

    def batches(self, seed):
        return traffic.train_batches(self.mix, self.vocab, self.rows, seed)

    def start(self, seed):
        """State from ``seed`` (weights made on the device, AdamW state
        zero), the feed, and - the first time - the compiled step.
        Returns the first batch."""
        import jax

        from horovod_tpu.data import prefetch_to_device

        params = weights.make_params(self.cfg, seed, self.replicated)
        opt_state = jax.jit(self.opt.init,
                            out_shardings=self.replicated)(params)
        self.state = (params, {}, opt_state)
        self.feed = prefetch_to_device(
            self.batches(seed), size=self.mix["prefetch"],
            sharding=self.batch_sharding)
        batch = next(self.feed)
        if self.compiled is None:
            jitted = self.step   # profiler/integrity hooks wrap only when on
            while not hasattr(jitted, "lower"):
                jitted = jitted.__wrapped__
            self.compiled = jitted.lower(*self.state, *batch).compile()
        return batch

    def one_step(self, batch):
        """One dispatch of the compiled step; the loss, not waited for."""
        loss, *self.state = self.compiled(*self.state, *batch)
        return loss

    def first_steps(self, seed, batch):
        """Drive the first ``check_steps`` steps and read what the
        reference will be compared with. Returns the readings and the
        next batch."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        norms = jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            tree))
        change = jax.jit(lambda a, b: norms(jax.tree.map(jnp.subtract, a, b)))
        losses, grad_norms = [], None
        for i in range(self.mix["check_steps"]):
            losses.append(self.one_step(batch))
            if i == 0:   # read before the next step donates the state
                moment = _first_moment(self.state[2])
                grad_norms = norms(moment)
                grad_sums = reference.chunk_sums_on_device(moment)
            batch = next(self.feed)
        change_norms = change(self.state[0], weights.make_params(
            self.cfg, seed, self.replicated))

        def named(tree, scale=1.0):
            return {jax.tree_util.keystr(k): float(v) * scale
                    for k, v in jax.tree_util.tree_leaves_with_path(
                        jax.device_get(tree))}

        return {"losses": [float(x) for x in losses],
                # mu after one step is (1 - b1) x the gradient
                "grad_norms": named(grad_norms,
                                    1.0 / (1.0 - reference.ADAM_B1)),
                "grad_chunk_sums": {
                    jax.tree_util.keystr(k):
                        np.asarray(v) / (1.0 - reference.ADAM_B1)
                    for k, v in jax.tree_util.tree_leaves_with_path(
                        jax.device_get(grad_sums))},
                "change_norms": named(change_norms)}, batch

    def stop(self):
        if self.feed is not None:
            self.feed.close()
        self.feed = self.state = None

    def reference(self, seed, precision="f32"):
        """The plain reference over the same first steps (call it only
        once the program's state is freed)."""
        return reference.follow_steps(
            weights.make_params(self.cfg, seed),
            list(itertools.islice(self.batches(seed),
                                  self.mix["check_steps"])),
            self.cfg, self.mix["objective"], self.mix["learning_rate"],
            precision=precision,
            block_rows=self.mix["reference_block_rows"])


def run(ctx):
    import jax
    import numpy as np

    import horovod_tpu as hvd

    cfg, mix = ctx.config, ctx.mix
    chips = len(ctx.devices)
    seq = mix["seq"]
    on_tpu = ctx.devices[0].platform == "tpu"
    checks = []
    program = Program(cfg, ctx.published, mix, ctx.devices)
    try:
        t0 = harness.now()
        batch = program.start(ctx.seed)
        text = program.compiled.as_text()
        analysis = program.compiled.memory_analysis()
        harness.say(
            f"train: state made and step compiled in "
            f"{harness.now() - t0:.2f} s; memory_analysis arguments "
            f"{analysis.argument_size_in_bytes:,} B, temporaries "
            f"{analysis.temp_size_in_bytes:,} B, outputs "
            f"{analysis.output_size_in_bytes:,} B, aliased "
            f"{analysis.alias_size_in_bytes:,} B")
        kernels = text.count("tpu_custom_call")
        if on_tpu:
            checks.append(harness.at_least(
                "flash_kernels_in_step", kernels, 3 * cfg["num_layers"]))
        if chips > 1:
            checks.append(harness.at_least(
                "all_reduce_in_step", text.count("all-reduce"), 1))
            everywhere = all(
                {s.device for s in leaf.addressable_shards}
                == set(ctx.devices)
                for leaf in jax.tree.leaves((program.state[0], batch)))
            checks.append(harness.at_least(
                "state_and_batch_on_every_chip", int(everywhere), 1))
        del text

        # ---- the first steps, through the window's own call and feed
        readings, batch = program.first_steps(ctx.seed, batch)
        jax.block_until_ready(program.state)

        # ---- the window: one step kept in flight
        compiles_before = ctx.compiles.compiles
        losses, done = [], []
        pending = None
        opened = harness.now()
        setup_s = opened - ctx.started
        deadline = opened + ctx.seconds
        while True:
            loss = program.one_step(batch)
            if pending is not None:
                losses.append(float(pending))
                done.append(harness.now())
            pending = loss
            if harness.now() >= deadline:
                break
            batch = next(program.feed)
        losses.append(float(pending))
        done.append(harness.now())
        window_s = done[-1] - opened
        compiles_in_window = ctx.compiles.compiles - compiles_before
        memory_peak = harness.memory_peak_bytes(ctx.devices)
        harness.say(f"train: memory_stats after the window: "
                    f"{ctx.devices[0].memory_stats()}")

        # ---- a short traced slice, after the window
        trace = {}
        if ctx.trace:
            batches = [next(program.feed)
                       for _ in range(mix["trace_steps"])]
            jax.block_until_ready(program.state)
            with harness.traced(trace):
                for b in batches:
                    loss = program.one_step(b)
                jax.block_until_ready((loss, program.state))
            del batches

        if chips > 1:
            digests = _replica_digests(program.state[0], program.mesh)
            harness.say(f"train: per-chip parameter digests "
                        f"{digests.tolist()}")
            checks.append(harness.at_least(
                "replicas_bit_identical",
                int((digests == digests[0]).all()), 1))
    finally:
        program.stop()
    del batch, pending, loss
    hvd.shutdown()

    # ---- the plain reference follows the same first steps
    t0 = harness.now()
    ref = program.reference(ctx.seed)
    harness.say(f"train: reference followed {mix['check_steps']} steps in "
                f"{harness.now() - t0:.2f} s (not part of setup_s)")
    checks += compare(readings, ref, ctx.limits)
    checks.append(harness.at_most("compiles_in_window",
                                  compiles_in_window, 0))

    finite = np.isfinite(losses)
    step_s = np.diff([opened] + done)
    harness.say(
        f"train: {len(losses)} steps in {window_s:.3f} s; set-up "
        f"{setup_s:.2f} s; first losses {readings['losses']}; cache "
        f"{dict(ctx.compiles.counts)}")
    n_params = weights.count(cfg, vocab_size=program.vocab)
    return {
        "attempted": len(losses), "failed": int((~finite).sum()),
        "checks": checks, "memory_peak_bytes": memory_peak,
        "setup_s": setup_s, "window_s": window_s, "chips": chips,
        "tokens": len(losses) * program.rows * seq,
        "step_s": step_s.tolist(), "rows": program.rows, "seq": seq,
        "config": cfg,
        "device_kind": ctx.devices[0].device_kind,
        "platform": ctx.devices[0].platform,
        "flops_per_token": flops.train_flops_per_token(
            n_params, cfg["num_layers"], cfg["d_model"], seq, cfg["causal"]),
        "trace": trace,
        "trace_steps": mix["trace_steps"],
        "breakdown": trace_mod.breakdown(trace) if trace else None,
    }


def chunk_errors(program, ref):
    """Per leaf, the estimated norm of the element-wise error of the
    program's first gradient (``reference.chunk_sums``) over the
    reference's norm of that leaf or of the median leaf."""
    floor = statistics.median(ref["grad_norms"].values())
    return {k: float(np.sqrt(np.sum(np.square(
                program["grad_chunk_sums"][k] - sums))))
            / max(ref["grad_norms"][k], floor)
            for k, sums in ref["grad_chunk_sums"].items()}


def compare(program, ref, limits):
    """Each number compared, beside its limit: every step's loss; the
    first gradient's norm by the worst leaf; the first gradient's
    element-wise error, estimated from chunk sums, by the median leaf
    (norms and losses average rounding noise away and so hardly tell
    bfloat16 from float8; this number is the noise itself, is steady
    from seed to seed, and is the one the float8 control fails); the norm
    of the parameters' change by the worst leaf."""
    checks = [
        harness.at_most(f"loss_rel.step{i + 1}", abs(p - r) / abs(r),
                        limits["loss_rel"])
        for i, (p, r) in enumerate(zip(program["losses"], ref["losses"]))]
    grad = leaf_gaps(program["grad_norms"], ref["grad_norms"])
    change = leaf_gaps(program["change_norms"], ref["change_norms"],
                       skip=noise_driven(ref["grad_norms"]))
    checks.append(harness.at_most(
        "grad_norm_gap.worst_leaf", max(grad.values()),
        limits["grad_norm_gap.worst_leaf"]))
    checks.append(harness.at_most(
        "grad_error.median_leaf",
        statistics.median(chunk_errors(program, ref).values()),
        limits["grad_error.median_leaf"]))
    checks.append(harness.at_most(
        "change_norm_gap.worst_leaf", max(change.values()),
        limits["change_norm_gap.worst_leaf"]))
    return checks


def calibrate(config, published, mix, devices, seeds, control_seeds):
    """For ``benchmark/tools/calibrate.py``: per seed the numbers a run
    compares, for the program and for the float8 control, each against
    the float32 reference."""
    import horovod_tpu as hvd

    zero = {"loss_rel": 0, "grad_norm_gap.worst_leaf": 0,
            "grad_error.median_leaf": 0, "change_norm_gap.worst_leaf": 0}
    numbers = lambda a, b: {c.name: c.value for c in compare(a, b, zero)}
    raw = {}
    program = Program(config, published, mix, devices)
    readings = {}
    for seed in sorted(set(seeds + control_seeds)):
        readings[seed], _ = program.first_steps(seed, program.start(seed))
        program.stop()
        harness.say(f"seed {seed}: program losses "
                    f"{readings[seed]['losses']}")
    program.compiled = None
    hvd.shutdown()
    sound, control = [], []
    for seed in sorted(readings):
        ref = program.reference(seed)
        slim = lambda r: {k: v for k, v in r.items()
                          if k != "grad_chunk_sums"}
        raw[seed] = {"program": slim(readings[seed]),
                     "reference": slim(ref)}
        if seed in seeds:
            sound.append(numbers(readings[seed], ref))
            harness.say(f"seed {seed} program-vs-reference: {sound[-1]}")
        if seed in control_seeds:
            low = program.reference(seed, precision="fp8")
            raw[seed]["control"] = slim(low)
            control.append(numbers(low, ref))
            harness.say(f"seed {seed} fp8-control-vs-reference: "
                        f"{control[-1]}")
    return sound, control, raw
