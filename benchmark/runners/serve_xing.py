"""The serving runner for Xing4.0-29B-A4B: ``runners/serve.py``'s closed
loop, window and sampling and ``runners/serve_sala.py``'s ramp and fixed
order over ``hvd.serve()`` with the hybrid decoder's latent attention,
routed experts and four residual streams
(``horovod_tpu/models/hybrid.py``), its weights
(``benchmark/weights_xing.py``) and its plain reference
(``benchmark/reference_xing.py``).

What differs from ``runners/serve_brumby.py`` is the program (every
mixer ``latent``, the MLP of every layer after the leading dense one a
router over experts, a hyper-connection round every sublayer; a cache of
latents and rotary keys, and the experts' running counts beside them),
the trace, which keeps the device seconds by this model's scopes, all
programs together and the decode program's apart
(``benchmark/scopes_xing.py``), and the counters: the engine's
``expert_counts`` are read before and after the window and round the
traced slice, and their differences go into the summary. ``correct`` is
decided as there, by the gaps by which the served tokens' logits lie
below the reference's best, over a sample of what the window served with
the longest request in it - but by two numbers of them, the widest and
the 99th percentile (``reference_gaps`` says why).
"""

from __future__ import annotations

import numpy as np

from benchmark import (controls_xing, harness, reference_xing, scopes_xing,
                       traffic, weights_xing)
from benchmark import trace as trace_mod
from benchmark.runners import serve, serve_sala


def build_model(cfg):
    """The program's model at the configuration's sizes as run."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid

    if not hasattr(hybrid, "LATENT"):
        raise SystemExit("benchmark: this program's models/hybrid.py has "
                         "no latent mixer: it cannot run the "
                         "configuration")
    return hybrid.HybridDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        d_ff=cfg["d_ff"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_heads"], head_dim=cfg["v_dim"],
        mixers=(hybrid.LATENT,) * cfg["num_layers"],
        latent=dict(q_rank=cfg["q_rank"], kv_rank=cfg["kv_rank"],
                    nope_dim=cfg["nope_dim"], rope_dim=cfg["rope_dim"],
                    v_dim=cfg["v_dim"], yarn=cfg["yarn"]),
        mlps=tuple(hybrid.DENSE_MLP if weights_xing.is_dense(cfg, i)
                   else hybrid.EXPERTS_MLP
                   for i in range(cfg["num_layers"])),
        experts=dict(num_experts=cfg["num_experts"], top_k=cfg["top_k"],
                     d_ff=cfg["expert_d_ff"], shared=cfg["shared_experts"],
                     scaling=cfg["routed_scaling"],
                     first=cfg["experts_first"],
                     count=cfg["experts_count"]),
        streams=cfg["streams"],
        hyper=dict(sinkhorn_iters=cfg["sinkhorn_iters"], eps=cfg["hc_eps"],
                   clamp=tuple(cfg["hc_clamp"])),
        layer_indices=tuple(cfg["layer_indices"]),
        published_depth=cfg["published_depth"], scale_depth=None,
        dim_model_base=cfg["dim_model_base"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_seq"], dtype=jnp.dtype(cfg["dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


class Program:
    """The system under test: ``hvd.serve()`` over the cell's model."""

    def __init__(self, cfg, mix, devices):
        import horovod_tpu as hvd

        self.cfg, self.mix = cfg, mix
        self.vocab = cfg["vocab_size"]
        self.hvd = hvd
        self.model = build_model(cfg)
        hvd.init(devices=devices)
        self.handle = None

    def start(self, seed):
        """Weights from ``seed`` on the device, the replica set, and one
        request through every prompt bucket the mix can reach."""
        from horovod_tpu.serve.kv_cache import prompt_bucket

        mix = self.mix
        params = weights_xing.make_params(self.cfg, seed)
        self.handle = self.hvd.serve(
            self.model, params, replicas=mix["replicas"],
            slots=mix["slots"], paged=mix["paged"],
            max_new_tokens=mix["new_tokens"]["max"], **mix["policy"])
        del params
        buckets = sorted({prompt_bucket(p, self.cfg["max_seq"])
                          for p, _ in traffic.request_sizes(mix)})
        rng = np.random.default_rng([seed, 2])
        for bucket in buckets:   # one at a time: each compiles its program
            uid = self.handle.submit(
                rng.integers(1, self.vocab,
                             min(bucket, mix["prompt_len"]["max"])).tolist(),
                max_new_tokens=mix["warm_new_tokens"])
            self.handle.result(uid, timeout=900.0)
        return buckets

    def stop(self):
        if self.handle is not None:
            self.handle.close()
        self.handle = None


def expert_counts(handle_or_stats):
    """The engine's running expert counts, (layers, 3, experts) uint32:
    pairs routed, decode steps that hit the expert, decode steps. They
    run modulo 2**32, so the difference of two readings is taken in
    uint32 (:func:`counted_between`)."""
    stats = handle_or_stats if isinstance(handle_or_stats, dict) \
        else handle_or_stats.stats()["replicas"][0]
    return np.asarray(stats["engine"]["expert_counts"], np.uint32)


def counted_between(before, after):
    """What the counters counted between two readings, int64: right
    across a wrap of the running count."""
    return (expert_counts(after) - expert_counts(before)).astype(np.int64)


def positions_attended_a_step(before, after):
    """Positions a decode step's latent kernel attended, all rows
    together, between two readings of the replica's stats; ``None``
    where the decode program holds no such kernel."""
    read = [s["engine"].get("decode_positions_read") for s in (before, after)]
    steps = after["decode_steps"] - before["decode_steps"]
    if None in read or steps <= 0:
        return None
    return (read[1] - read[0]) / steps


def reference_len(mix):
    """The one shape the reference runs: the longest prompt plus the
    longest answer of the mix."""
    return mix["prompt_len"]["max"] + mix["new_tokens"]["max"]


def served_gaps(logits, tokens):
    """At every served position, how far the served token's logit lies
    below the reference's best (``logits``: one row a served position)."""
    rows = logits[:len(tokens)]
    return rows.max(axis=-1) - rows[np.arange(len(tokens)),
                                    np.asarray(tokens)]


def summed_up(gaps):
    """The widest of the served-token gaps and their 99th percentile."""
    gaps = np.concatenate(gaps)
    return float(gaps.max()), float(np.percentile(gaps, 99))


def reference_gaps(cfg, mix, seed, sample, precision=None, faults=False):
    """The served-token gaps over ``sample`` (``(prompt, tokens)`` pairs)
    under the float32 reference, their widest and their 99th percentile;
    with ``precision`` the same for the tokens that precision's own
    forward puts first at the same positions (the control: it need not
    decode); with ``faults`` the same for each of
    ``benchmark/controls_xing.py``'s: the reference with a piece of its
    mathematics left out, a slot that read another request's latents,
    and, position by position, one served token altered.

    Two numbers, because a router chooses: where the fourth and the fifth
    score lie closer than bfloat16 resolves, the program takes another
    expert than the float32 reference, at a few served tokens in a
    hundred, and such a token's logits move by what one expert's output
    is worth. The widest gap is that of the unluckiest such token and is
    held to a limit that only a token gone wrong outright passes; the
    99th percentile does not see the few, is held to a limit a tenth of
    that, and is what a lower precision (which moves every token) fails."""
    import jax
    import jax.numpy as jnp

    frozen = reference_xing.frozen(cfg)
    forward = jax.jit(reference_xing.forward, static_argnums=(2, 3))
    params = weights_xing.make_params(cfg, seed)
    most = max(len(tokens) for _, tokens in sample)
    length = reference_len(mix)
    sound, control, flips = [], [], 0
    broken = {name: controls_xing.forward(name, cfg)
              for name in (controls_xing.FAULTS if faults else ())}
    planted = {name: [] for name in broken}
    kept = []
    for prompt, tokens in sample:
        # one shape for every request: the model is causal, so zeros
        # after the sequence change nothing before them
        ids = np.zeros((length,), np.int32)
        full = (list(prompt) + list(tokens))[:length]
        ids[:len(full)] = full
        rows = np.minimum(len(prompt) - 1 + np.arange(most),
                          length - 1).astype(np.int32)
        logits = np.asarray(forward(params, jnp.asarray(ids), frozen,
                                    "f32", jnp.asarray(rows)))
        sound.append(served_gaps(logits, tokens))
        flips += int((sound[-1] > 0).sum())
        if precision:
            low = np.asarray(forward(params, jnp.asarray(ids), frozen,
                                     precision, jnp.asarray(rows)))
            control.append(served_gaps(
                logits, low[:len(tokens)].argmax(-1)))
        for name, fn in broken.items():
            low = np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(rows)))
            planted[name].append(served_gaps(
                logits, low[:len(tokens)].argmax(-1)))
        if faults:
            kept.append(logits[:len(tokens)])
    widest, p99 = summed_up(sound)
    low_widest, low_p99 = summed_up(control) if control else (0.0, 0.0)
    out = {"widest_gap": widest, "p99_gap": p99,
           "control_widest_gap": low_widest, "control_p99_gap": low_p99,
           "flips": flips, "tokens": sum(len(g) for g in sound)}
    if faults:
        firsts = [rows.argmax(-1) for rows in kept]
        planted["another_slots_latents"] = [
            served_gaps(rows, np.resize(
                controls_xing.another_slots_tokens(firsts, i), len(rows)))
            for i, rows in enumerate(kept)]
        out["faults"] = {name: dict(zip(("widest_gap", "p99_gap"),
                                        summed_up(gaps)))
                         for name, gaps in planted.items()}
        altered = np.concatenate([controls_xing.altered_token_gaps(rows, seed)
                                  for rows in kept])
        out["faults"]["one_altered_token"] = {
            "smallest_gap": float(altered.min()),
            "p1_gap": float(np.percentile(altered, 1)),
            "median_gap": float(np.median(altered)),
            "smallest_five": np.sort(altered)[:5].tolist(),
            "positions": int(altered.size)}
    return out


def run(ctx):
    import gc

    import jax

    import horovod_tpu as hvd

    cfg, mix = ctx.config, ctx.mix
    program = Program(cfg, mix, ctx.devices)
    checks = []
    try:
        t0 = harness.now()
        buckets = program.start(ctx.seed)
        handle = program.handle
        harness.say(f"serve: replica set up and prompt buckets {buckets} + "
                    f"decode warmed in {harness.now() - t0:.2f} s; "
                    f"{handle.compiles_total()} programs")
        loop = serve.ClosedLoop(
            handle, serve_sala.requests(mix, program.vocab, ctx.seed),
            mix["callers"], mix["request_timeout_s"])
        serve_sala.ramp(loop, mix)

        # ---- the window
        ramp_done, ramp_failed = len(loop.finished), loop.failed
        ramp_timeouts = loop.timeouts
        compiles_before = (ctx.compiles.compiles, handle.compiles_total())
        before = handle.stats()["replicas"][0]
        opened = harness.now()
        setup_s = opened - ctx.started
        loop.run_until(opened + ctx.seconds)
        closed = harness.now()
        after = handle.stats()["replicas"][0]
        compiles_in_window = (
            ctx.compiles.compiles - compiles_before[0]
            + handle.compiles_total() - compiles_before[1])
        in_window = loop.finished[ramp_done:]
        failed = loop.failed - ramp_failed
        attempted = len(in_window) + loop.timeouts - ramp_timeouts
        memory_peak = harness.memory_peak_bytes(ctx.devices)
        harness.say(f"serve: memory_stats after the window: "
                    f"{ctx.devices[0].memory_stats()}")

        # ---- a short traced slice with the loop still running
        trace, traced_counts, positions_a_step = {}, None, None
        if ctx.trace:
            # the counters are read inside the trace: starting, stopping
            # and reducing it take seconds in which this thread submits
            # nothing, slots empty, and a step counts fewer positions and
            # experts than the traced steps did
            with scopes_xing.traced(trace):
                t0 = handle.stats()["replicas"][0]
                loop.run_until(harness.now() + mix["trace_seconds"])
                t1 = handle.stats()["replicas"][0]
            traced_counts = counted_between(t0, t1)
            positions_a_step = positions_attended_a_step(t0, t1)
            harness.say(f"serve: device seconds by scope in the traced "
                        f"slice: {trace.get('scope_s')}; in its decode "
                        f"program: {trace.get('decode_scope_s')}")
        loop.run_until(harness.now() + mix["request_timeout_s"],
                       refill=False)          # drain what is open
        replica = handle.stats()["replicas"][0]
        harness.say(f"serve: engine {replica['engine']}")
    finally:
        program.stop()
    hvd.shutdown()
    # the reference needs the chip's memory: 13 GB of weights and latents
    # live as long as anything holds the replica set
    finished = loop.finished
    del handle, loop, program
    gc.collect()
    jax.clear_caches()
    harness.say(f"serve: bytes in use after the replica set was freed: "
                f"{(ctx.devices[0].memory_stats() or {}).get('bytes_in_use')}")

    # ---- the plain reference over a sample of what the window served
    t0 = harness.now()
    sample = serve.draw_sample(in_window, ctx.seed, mix["check_requests"])
    gaps = reference_gaps(cfg, mix, ctx.seed, sample)
    harness.say(
        f"serve: reference ran {len(sample)} requests (prompts "
        f"{[len(p) for p, _ in sample]}), {gaps['tokens']} served tokens "
        f"({gaps['flips']} not the reference's first) in "
        f"{harness.now() - t0:.2f} s (not part of setup_s)")
    checks.append(harness.at_most("served_logit_gap", gaps["widest_gap"],
                                  ctx.limits["served_logit_gap"]))
    checks.append(harness.at_most("served_logit_gap_p99", gaps["p99_gap"],
                                  ctx.limits["served_logit_gap_p99"]))
    checks.append(harness.at_most("compiles_in_window",
                                  compiles_in_window, 0))
    checks.append(harness.at_most("replica_quarantined",
                                  int(replica["quarantined"]), 0))
    checks.append(harness.at_least(
        "cache_donated", int(replica["engine"]["cache_donated"]), 1))

    window_s = closed - opened
    steps = after["decode_steps"] - before["decode_steps"]
    occupied = (after["avg_occupancy"] * after["decode_steps"]
                - before["avg_occupancy"] * before["decode_steps"])
    done = [c for _, _, _, c in in_window]
    harness.say(
        f"serve: {len(in_window)} requests finished in {window_s:.3f} s "
        f"({failed} failed); {steps} decode steps; set-up {setup_s:.2f} s; "
        f"cache {dict(ctx.compiles.counts)}")
    longest = sorted(((t1 - t0, len(c.tokens)) for t0, t1, _, c in in_window),
                     reverse=True)[:10]
    harness.say("serve: the ten longest latencies (s, served tokens): "
                + ", ".join(f"{s:.2f} {n}" for s, n in longest))
    window_pairs = counted_between(before, after)[:, 0]
    harness.say(f"serve: (token, expert) pairs routed in the window, by "
                f"layer: {window_pairs.sum(axis=1).tolist()}; busiest "
                f"expert of each layer {window_pairs.max(axis=1).tolist()}")
    return {
        "attempted": attempted, "failed": failed,
        "checks": checks, "memory_peak_bytes": memory_peak,
        "setup_s": setup_s, "window_s": window_s, "chips": len(ctx.devices),
        "served_tokens": serve.tokens_in_window(finished, opened, closed),
        "latency_s": [t1 - t0 for t0, t1, _, _ in in_window],
        "ttft_s": [c.ttft_s for c in done],
        "tpot_s": [(c.latency_s - c.ttft_s) / (len(c.tokens) - 1)
                   for c in done if len(c.tokens) > 1],
        "decode_steps": steps, "occupied_slot_steps": occupied,
        "slots": mix["slots"], "config": cfg,
        "device_kind": ctx.devices[0].device_kind,
        "platform": ctx.devices[0].platform, "trace": trace,
        "expert_pairs": window_pairs.tolist(),
        "traced_expert_counts": (None if traced_counts is None
                                 else traced_counts.tolist()),
        "traced_positions_a_step": positions_a_step,
        "breakdown": trace_mod.breakdown(trace) if trace else None,
    }


def calibrate(config, published, mix, devices, seeds, control_seeds):
    """For ``benchmark/tools/calibrate.py``: per seed a short window at
    the cell's own load, then the widest served-token gap of a run's
    sample under the float32 reference and the gaps' 99th percentile, and
    for the control seeds the same two of the float8 reference's own
    first tokens and of every fault of ``benchmark/controls_xing.py``
    (``raw[seed]["faults"]``)."""
    import gc

    import jax

    import horovod_tpu as hvd

    del published
    sound, control, raw = [], [], {}
    for seed in sorted(set(seeds + control_seeds)):
        program = Program(config, mix, devices)
        program.start(seed)
        loop = serve.ClosedLoop(
            program.handle, serve_sala.requests(mix, program.vocab, seed),
            mix["callers"], mix["request_timeout_s"])
        serve_sala.ramp(loop, mix)
        ramp_done = len(loop.finished)
        loop.run_until(harness.now() + mix["calibrate_seconds"])
        loop.run_until(harness.now() + mix["request_timeout_s"],
                       refill=False)
        program.stop()
        hvd.shutdown()
        finished, failed = loop.finished, loop.failed
        del loop, program        # they hold the replica set's 13 GB
        gc.collect()
        jax.clear_caches()
        sample = serve.draw_sample(finished[ramp_done:], seed,
                                   mix["check_requests"])
        gaps = reference_gaps(config, mix, seed, sample,
                              "fp8" if seed in control_seeds else None,
                              faults=seed in control_seeds)
        harness.say(f"seed {seed}: {len(finished)} finished, "
                    f"{failed} failed; {gaps}")
        raw[seed] = gaps
        if seed in seeds:
            sound.append({"served_logit_gap": gaps["widest_gap"],
                          "served_logit_gap_p99": gaps["p99_gap"]})
        if seed in control_seeds:
            control.append({"served_logit_gap":
                            gaps["control_widest_gap"],
                            "served_logit_gap_p99":
                            gaps["control_p99_gap"]})
        jax.clear_caches()
    return sound, control, raw
