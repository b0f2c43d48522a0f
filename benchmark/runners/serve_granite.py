"""The serving runner for granite-4.0-h-small: ``runners/serve_kexaone.py``'s
closed loop, ramp, window, counters and two-number comparison over
``hvd.serve()`` with the hybrid decoder's Mamba-2 state-space layers
beside one position-free grouped-query layer, this chip's share of the
routed experts on every layer and a tied head
(``horovod_tpu/models/hybrid.py``), its weights
(``benchmark/weights_granite.py``) and its plain reference
(``benchmark/reference_granite.py``).

What differs from ``runners/serve_kexaone.py`` is the program (nine
state-space layers to a full one: a slot holds a float32 state and a
convolution tail a state-space layer beside the full layer's ``max_seq``
rows; a softmax router; 36 of 72 experts held), the trace's scopes
(``benchmark/scopes_granite.py``) and the planted faults, which are this
model's own (``benchmark/controls_granite.py``): a slot that keeps its
earlier occupant's state, a convolution tail dropped between prefill and
decode, and a prompt's padding run through the recurrence. ``correct``
is decided as there: at every served position, how far the served
token's logit lies below the float32 reference's best, by the widest of
those gaps and by their 99th percentile (``serve_xing.reference_gaps``
says why two).
"""

from __future__ import annotations

import numpy as np

from benchmark import (controls_granite, controls_xing, harness,
                       reference_granite, scopes_granite, traffic,
                       weights_granite)
from benchmark import trace as trace_mod
from benchmark.runners import serve, serve_sala, serve_xing
from benchmark.runners.serve_kexaone import positions_between


def build_model(cfg):
    """The program's model at the configuration's sizes as run."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid

    if not hasattr(hybrid, "MAMBA2"):
        raise SystemExit("benchmark: this program's models/hybrid.py has "
                         "no state-space mixer: it cannot run the "
                         "configuration")
    return hybrid.HybridDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        d_ff=cfg["d_ff"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_kv_heads"], head_dim=cfg["head_dim"],
        mixers=tuple(cfg["mixers"]), ssm=cfg["ssm"], qk_norm=False,
        attention_scale=cfg["attention_multiplier"], layer_barriers=True,
        mlps=(hybrid.EXPERTS_MLP,) * cfg["num_layers"],
        experts=dict(num_experts=cfg["num_experts"], top_k=cfg["top_k"],
                     d_ff=cfg["expert_d_ff"],
                     shared_d_ff=cfg["shared_d_ff"],
                     scoring=hybrid.SOFTMAX_ROUTER,
                     first=cfg["experts_first"],
                     count=cfg["experts_count"]),
        layer_indices=tuple(cfg["layer_indices"]),
        published_depth=cfg["published_depth"],
        scale_emb=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_divisor=cfg["logits_scaling"], tied_head=True,
        eps=cfg["rms_norm_eps"], max_seq=cfg["max_seq"],
        dtype=jnp.dtype(cfg["dtype"]),
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
        params = weights_granite.make_params(self.cfg, seed)
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


def reference_gaps(cfg, mix, seed, sample, precision=None, faults=False):
    """``serve_xing.reference_gaps`` under this model's reference: the
    served-token gaps over ``sample`` (``(prompt, tokens)`` pairs), their
    widest and their 99th percentile; with ``precision`` the same for the
    tokens that precision's own forward puts first at the same positions
    (the control: it need not decode); with ``faults`` the same for each
    of ``controls_granite.FAULTS`` (the reference with the state of the
    sample's request before it left in the slot, with the convolution's
    tail dropped at the prompt's end, and with the prompt's padding run
    through the recurrence), for a slot that served another request's
    tokens and, position by position, for one served token altered."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serve.kv_cache import prompt_bucket

    frozen = reference_granite.frozen(cfg)
    forward = jax.jit(reference_granite.forward, static_argnums=(2, 3))
    params = weights_granite.make_params(cfg, seed)
    most = max(len(tokens) for _, tokens in sample)
    length = serve_xing.reference_len(mix)
    sound, control, flips, kept, ids_of = [], [], 0, [], []
    spread, echoes = [], []
    for prompt, tokens in sample:
        # one shape for every request: the model is causal, so zeros
        # after the sequence change nothing before them
        ids = np.zeros((length,), np.int32)
        full = (list(prompt) + list(tokens))[:length]
        ids[:len(full)] = full
        rows = jnp.asarray(np.minimum(len(prompt) - 1 + np.arange(most),
                                      length - 1).astype(np.int32))
        ids = jnp.asarray(ids)
        logits = np.asarray(forward(params, ids, frozen, "f32", rows))
        sound.append(serve_xing.served_gaps(logits, tokens))
        flips += int((sound[-1] > 0).sum())
        served = logits[:len(tokens)]
        spread.append(served.std(axis=-1).mean())
        echoes.append(served.argmax(-1)[1:] == np.asarray(tokens)[:-1])
        if precision:
            low = np.asarray(forward(params, ids, frozen, precision, rows))
            control.append(serve_xing.served_gaps(
                logits, low[:len(tokens)].argmax(-1)))
        if faults:
            kept.append(served)
            ids_of.append((ids, rows, len(full)))
    widest, p99 = serve_xing.summed_up(sound)
    low_widest, low_p99 = serve_xing.summed_up(control) if control \
        else (0.0, 0.0)
    out = {"widest_gap": widest, "p99_gap": p99,
           "control_widest_gap": low_widest, "control_p99_gap": low_p99,
           "flips": flips, "tokens": sum(len(g) for g in sound),
           # what the weights' spread has to give (weights_granite.py): the
           # logits' standard deviation, and how often the reference's
           # first token is the input token itself (the tied head's echo)
           "logits_std": float(np.mean(spread)),
           "echo_share": float(np.mean(np.concatenate(echoes)))}
    if faults:
        planted = {name: [] for name in controls_granite.FAULTS}
        broken = {name: controls_granite.forward(name, cfg)
                  for name in controls_granite.FAULTS}
        states = controls_granite.final_states(cfg)
        wide = cfg["max_seq"] + mix["new_tokens"]["max"]
        for i, (prompt, tokens) in enumerate(sample):
            ids, rows, _ = ids_of[i]
            before = ids_of[i - 1]      # the slot's occupant before it
            left = states(params, before[0], before[2])
            at = jnp.asarray(len(prompt), jnp.int32)
            first = {
                "stale_state": broken["stale_state"](
                    params, ids, rows, at, left),
                "tail_dropped": broken["tail_dropped"](
                    params, ids, rows, at, jnp.asarray(0, jnp.int32))}
            padded_ids, pad, padded_rows = controls_granite.padded(
                prompt, tokens, prompt_bucket(len(prompt), cfg["max_seq"]),
                wide)
            first["padding_in_recurrence"] = broken["padding_in_recurrence"](
                params, jnp.asarray(padded_ids), jnp.asarray(padded_rows),
                at, jnp.asarray(pad, jnp.int32))
            for name, low in first.items():
                planted[name].append(serve_xing.served_gaps(
                    kept[i], np.asarray(low)[:len(tokens)].argmax(-1)))
        firsts = [rows.argmax(-1) for rows in kept]
        planted["another_slots_cache"] = [
            serve_xing.served_gaps(rows, np.resize(
                controls_xing.another_slots_tokens(firsts, i), len(rows)))
            for i, rows in enumerate(kept)]
        out["faults"] = {name: dict(zip(("widest_gap", "p99_gap"),
                                        serve_xing.summed_up(g)))
                         for name, g in planted.items()}
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
            with scopes_granite.traced(trace):
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
    # the reference needs the chip's memory: 13 GB of weights and cache
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
        f"({gaps['flips']} not the reference's first; logits' standard "
        f"deviation {gaps['logits_std']:.3f}, the input token first at "
        f"{100 * gaps['echo_share']:.2f}% of them) in "
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
