"""The serving runner for K-EXAONE-236B-A23B: ``runners/serve_xing.py``'s
closed loop, ramp, window, counters and two-number comparison over
``hvd.serve()`` with the hybrid decoder's window and full grouped-query
layers, norms on the sublayers' outputs and this chip's share of the
routed experts (``horovod_tpu/models/hybrid.py``), its weights
(``benchmark/weights_kexaone.py``) and its plain reference
(``benchmark/reference_kexaone.py``).

What differs from ``runners/serve_xing.py`` is the program (three window
layers to a full one, a ring of window-many positions a window layer
beside the full layers' ``max_seq`` rows, 8 of 128 experts held), the
trace's scopes (``benchmark/scopes_kexaone.py``), one more counter - the
positions the decode steps attended by the kind of leaf they were read
from, read before and after the window and round the traced slice - and
the planted faults, which are this model's own: a window layer read as
a full one, and a ring read one position too far. ``correct`` is decided
as there: at every served position, how far the served token's logit
lies below the float32 reference's best, by the widest of those gaps and
by their 99th percentile (``serve_xing.reference_gaps`` says why two).
"""

from __future__ import annotations

import numpy as np

from benchmark import (controls_xing, harness, reference_kexaone,
                       scopes_kexaone, traffic, weights_kexaone)
from benchmark import trace as trace_mod
from benchmark.runners import serve, serve_sala, serve_xing


def build_model(cfg):
    """The program's model at the configuration's sizes as run."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid

    if not hasattr(hybrid, "WINDOW"):
        raise SystemExit("benchmark: this program's models/hybrid.py has "
                         "no window mixer: it cannot run the "
                         "configuration")
    return hybrid.HybridDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        d_ff=cfg["d_ff"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_kv_heads"], head_dim=cfg["head_dim"],
        mixers=tuple(cfg["mixers"]), window=cfg["window"],
        norms=cfg["norms"], layer_barriers=True,
        mlps=tuple(hybrid.DENSE_MLP if weights_kexaone.is_dense(cfg, i)
                   else hybrid.EXPERTS_MLP
                   for i in range(cfg["num_layers"])),
        experts=dict(num_experts=cfg["num_experts"], top_k=cfg["top_k"],
                     d_ff=cfg["expert_d_ff"], shared=cfg["shared_experts"],
                     scaling=cfg["routed_scaling"],
                     first=cfg["experts_first"],
                     count=cfg["experts_count"]),
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
        params = weights_kexaone.make_params(self.cfg, seed)
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


def positions_between(before, after):
    """{kind: positions a decode step attended}, all rows and all layers
    of the kind together, between two readings of the replica's stats;
    ``None`` where the engine counts no such thing."""
    read = [s["engine"].get("decode_positions_by_kind") for s in
            (before, after)]
    steps = after["decode_steps"] - before["decode_steps"]
    if None in read or steps <= 0:
        return None
    return {kind: (read[1][kind] - read[0].get(kind, 0)) / steps
            for kind in read[1]}


def reference_gaps(cfg, mix, seed, sample, precision=None, faults=False):
    """``serve_xing.reference_gaps`` under this model's reference: the
    served-token gaps over ``sample`` (``(prompt, tokens)`` pairs), their
    widest and their 99th percentile; with ``precision`` the same for the
    tokens that precision's own forward puts first at the same positions
    (the control: it need not decode); with ``faults`` the same for each
    of ``reference_kexaone.FAULTS`` (the reference with a window layer
    read as a full one, and with a ring read one position too far), for
    a slot that served another request's tokens and, position by
    position, for one served token altered."""
    import jax
    import jax.numpy as jnp

    frozen = reference_kexaone.frozen(cfg)
    forward = jax.jit(reference_kexaone.forward, static_argnums=(2, 3, 5))
    params = weights_kexaone.make_params(cfg, seed)
    most = max(len(tokens) for _, tokens in sample)
    length = serve_xing.reference_len(mix)
    sound, control, flips, kept = [], [], 0, []
    names = reference_kexaone.FAULTS if faults else ()
    planted = {name: [] for name in names}
    for prompt, tokens in sample:
        # one shape for every request: the model is causal, so zeros
        # after the sequence change nothing before them
        ids = np.zeros((length,), np.int32)
        full = (list(prompt) + list(tokens))[:length]
        ids[:len(full)] = full
        rows = jnp.asarray(np.minimum(len(prompt) - 1 + np.arange(most),
                                      length - 1).astype(np.int32))
        ids = jnp.asarray(ids)
        logits = np.asarray(forward(params, ids, frozen, "f32", rows, None))
        sound.append(serve_xing.served_gaps(logits, tokens))
        flips += int((sound[-1] > 0).sum())
        others = [(control, (precision, None))] if precision else []
        others += [(planted[name], ("f32", name)) for name in names]
        for into, (how, fault) in others:
            low = np.asarray(forward(params, ids, frozen, how, rows, fault))
            into.append(serve_xing.served_gaps(
                logits, low[:len(tokens)].argmax(-1)))
        if faults:
            kept.append(logits[:len(tokens)])
    widest, p99 = serve_xing.summed_up(sound)
    low_widest, low_p99 = serve_xing.summed_up(control) if control \
        else (0.0, 0.0)
    out = {"widest_gap": widest, "p99_gap": p99,
           "control_widest_gap": low_widest, "control_p99_gap": low_p99,
           "flips": flips, "tokens": sum(len(g) for g in sound)}
    if faults:
        firsts = [rows.argmax(-1) for rows in kept]
        planted["another_slots_cache"] = [
            serve_xing.served_gaps(rows, np.resize(
                controls_xing.another_slots_tokens(firsts, i), len(rows)))
            for i, rows in enumerate(kept)]
        out["faults"] = {name: dict(zip(("widest_gap", "p99_gap"),
                                        serve_xing.summed_up(gaps)))
                         for name, gaps in planted.items()}
        altered = np.concatenate([controls_xing.altered_token_gaps(rows, seed)
                                  for rows in kept])
        out["faults"]["one_altered_token"] = {
            "smallest_gap": float(altered.min()),
            "p1_gap": float(np.percentile(altered, 1)),
            "median_gap": float(np.median(altered)),
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
        trace, traced_counts, traced_positions = {}, None, None
        if ctx.trace:
            # the counters are read inside the trace, as serve_xing.py
            # reads them: stopping and reducing it empties slots
            with scopes_kexaone.traced(trace):
                t0 = handle.stats()["replicas"][0]
                loop.run_until(harness.now() + mix["trace_seconds"])
                t1 = handle.stats()["replicas"][0]
            traced_counts = serve_xing.counted_between(t0, t1)
            traced_positions = positions_between(t0, t1)
            harness.say(f"serve: device seconds by scope in the traced "
                        f"slice: {trace.get('scope_s')}; in its decode "
                        f"program: {trace.get('decode_scope_s')}; positions "
                        f"attended a step by kind: {traced_positions}")
        loop.run_until(harness.now() + mix["request_timeout_s"],
                       refill=False)          # drain what is open
        replica = handle.stats()["replicas"][0]
        harness.say(f"serve: engine {replica['engine']}")
    finally:
        program.stop()
    hvd.shutdown()
    # the reference needs the chip's memory: 12 GB of weights and cache
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
    window_pairs = serve_xing.counted_between(before, after)[:, 0]
    window_positions = positions_between(before, after)
    harness.say(f"serve: (token, expert) pairs routed in the window, by "
                f"layer: {window_pairs.sum(axis=1).tolist()}; busiest "
                f"expert of each layer {window_pairs.max(axis=1).tolist()}; "
                f"positions a decode step attended by kind: "
                f"{window_positions}")
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
        "window_positions_by_kind": window_positions,
        "traced_positions_by_kind": traced_positions,
        "breakdown": trace_mod.breakdown(trace) if trace else None,
    }


def calibrate(config, published, mix, devices, seeds, control_seeds):
    """For ``benchmark/tools/calibrate.py``: per seed a short window at
    the cell's own load, then the widest served-token gap of a run's
    sample under the float32 reference and the gaps' 99th percentile, and
    for the control seeds the same two of the float8 reference's own
    first tokens and of every planted fault
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
        del loop, program        # they hold the replica set's 12 GB
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
