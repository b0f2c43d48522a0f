"""The serving runner for Brumby-14B-Base: ``runners/serve.py``'s closed
loop, window and sampling and ``runners/serve_sala.py``'s ramp and fixed
order over ``hvd.serve()`` with the hybrid decoder's power-retention
layers (``horovod_tpu/models/hybrid.py``), its weights
(``benchmark/weights_brumby.py``) and its plain reference
(``benchmark/reference_brumby.py``).

What differs from ``runners/serve_sala.py`` is the program (every layer
a ``power_retention`` mixer, a trunk without muP scalings, a cache of
states alone), the reference call (one shape of the longest prompt plus
the longest answer, not of ``max_seq``: no cache is sized by it) and the
trace, which keeps the device seconds by this model's scopes
(``benchmark/scopes_brumby.py``). ``correct`` is decided as there: the
widest gap by which a served token's logit lies below the reference's
best, over a sample of what the window served with the longest request
in it.
"""

from __future__ import annotations

import numpy as np

from benchmark import (harness, reference_brumby, scopes_brumby, traffic,
                       weights_brumby)
from benchmark import trace as trace_mod
from benchmark.runners import serve, serve_sala


def build_model(cfg):
    """The program's model at the configuration's sizes as run."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid

    if not hasattr(hybrid, "POWER_RETENTION"):
        raise SystemExit("benchmark: this program's models/hybrid.py has "
                         "no power_retention mixer: it cannot run the "
                         "configuration")
    return hybrid.HybridDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        d_ff=cfg["d_ff"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_kv_heads"], head_dim=cfg["head_dim"],
        mixers=(hybrid.POWER_RETENTION,) * cfg["num_layers"],
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
        params = weights_brumby.make_params(self.cfg, seed)
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


def reference_len(mix):
    """The one shape the reference runs: the longest prompt plus the
    longest answer of the mix."""
    return mix["prompt_len"]["max"] + mix["new_tokens"]["max"]


def reference_gaps(cfg, mix, seed, sample, precision=None):
    """The widest served-token gap over ``sample`` (``(prompt, tokens)``
    pairs) under the float32 reference; with ``precision`` the same for
    the tokens that precision's own forward puts first at the same
    positions (the control: it need not decode)."""
    import jax
    import jax.numpy as jnp

    frozen = reference_brumby.frozen(cfg)
    forward = jax.jit(reference_brumby.forward, static_argnums=(2, 3))
    params = weights_brumby.make_params(cfg, seed)
    most = max(len(tokens) for _, tokens in sample)
    length = reference_len(mix)
    widest, low_widest, flips, count = 0.0, 0.0, 0, 0
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
        widest = max(widest, serve.served_gap(logits, 1, tokens))
        flips += int((logits[:len(tokens)].argmax(-1)
                      != np.asarray(tokens)).sum())
        count += len(tokens)
        if precision:
            low = np.asarray(forward(params, jnp.asarray(ids), frozen,
                                     precision, jnp.asarray(rows)))
            low_widest = max(low_widest, serve.served_gap(
                logits, 1, low[:len(tokens)].argmax(-1)))
    return {"widest_gap": widest, "control_widest_gap": low_widest,
            "flips": flips, "tokens": count}


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
        trace = {}
        if ctx.trace:
            with scopes_brumby.traced(trace):
                loop.run_until(harness.now() + mix["trace_seconds"])
            harness.say(f"serve: device seconds by scope in the traced "
                        f"slice: {trace.get('scope_s')}")
        loop.run_until(harness.now() + mix["request_timeout_s"],
                       refill=False)          # drain what is open
        replica = handle.stats()["replicas"][0]
        harness.say(f"serve: engine {replica['engine']}")
    finally:
        program.stop()
    hvd.shutdown()
    # the reference needs the chip's memory: 10 GB of weights and state
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
        "breakdown": trace_mod.breakdown(trace) if trace else None,
    }


def calibrate(config, published, mix, devices, seeds, control_seeds):
    """For ``benchmark/tools/calibrate.py``: per seed a short window at
    the cell's own load, then the widest served-token gap of a run's
    sample under the float32 reference, and for the control seeds the
    widest gap of the float8 reference's own first tokens."""
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
        del loop, program        # they hold the replica set's 10 GB
        gc.collect()
        jax.clear_caches()
        sample = serve.draw_sample(finished[ramp_done:], seed,
                                   mix["check_requests"])
        gaps = reference_gaps(config, mix, seed, sample,
                              "fp8" if seed in control_seeds else None)
        harness.say(f"seed {seed}: {len(finished)} finished, "
                    f"{failed} failed; {gaps}")
        raw[seed] = gaps
        if seed in seeds:
            sound.append({"served_logit_gap": gaps["widest_gap"]})
        if seed in control_seeds:
            control.append({"served_logit_gap":
                            gaps["control_widest_gap"]})
        jax.clear_caches()
    return sound, control, raw
