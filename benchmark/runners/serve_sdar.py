"""The serving runner for SDAR-30B-A3B-Chat: ``runners/serve_granite.py``'s
closed loop, ramp, window and counters over ``hvd.serve()`` with the
hybrid decoder's full grouped-query layers (rotary, QK-norm), all the
routed experts on every layer (softmax router, no shared expert), an
untied head and ``block_len`` 4 (``horovod_tpu/models/hybrid.py``), its
weights (``benchmark/weights_sdar.py``) and its plain reference
(``benchmark/reference_sdar.py``).

What differs from ``runners/serve_granite.py`` is the program's
generation loop, and so the comparison. The model generates by diffusion
over blocks: a step of the engine is a pass that unmasks from no token to
several a row, chosen by the logits, and a completion carries, beside
the answer, what was cut of its last block and the pass that unmasked
each position (``Completion.cut`` / ``.passes``). ``correct`` is decided
on that served trajectory, teacher forced through the float32 reference
in one call (``benchmark/controls_sdar.py``), by four numbers: at every
position, in the pass that unmasked it, how far the served token's logit
lies under the reference's best (the widest and the 99th percentile, as
``serve_xing.reference_gaps`` says why two), and at every pass that left
something masked, how far the reference's log-confidence at the program's
choice lies under the reference's own (the widest and the 99th
percentile). The trace's scopes are ``benchmark/scopes_sdar.py``'s, and
the engine's pass counters (``row_passes``, ``commit_row_passes``,
``tokens_unmasked``) are read round the window and round the traced
slice.
"""

from __future__ import annotations

import numpy as np

from benchmark import controls_sdar, harness, scopes_sdar, traffic, \
    weights_sdar
from benchmark import trace as trace_mod
from benchmark.runners import serve, serve_sala, serve_xing
from benchmark.runners.serve_kexaone import positions_between

PASS_COUNTERS = ("row_passes", "commit_row_passes", "tokens_unmasked",
                 "blocks_committed")


def build_model(cfg):
    """The program's model at the configuration's sizes as run."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid

    if "block_len" not in getattr(hybrid.HybridDecoder,
                                  "__dataclass_fields__", {}):
        raise SystemExit("benchmark: this program's models/hybrid.py "
                         "generates one token a step alone: it cannot run "
                         "the configuration")
    return hybrid.HybridDecoder(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        d_ff=cfg["d_ff"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_kv_heads"], head_dim=cfg["head_dim"],
        mixers=tuple(cfg["mixers"]), rotary=(hybrid.FULL,), qk_norm=True,
        mlps=(hybrid.EXPERTS_MLP,) * cfg["num_layers"],
        experts=dict(num_experts=cfg["num_experts"], top_k=cfg["top_k"],
                     d_ff=cfg["expert_d_ff"], shared=cfg["shared_experts"],
                     scoring=hybrid.SOFTMAX_ROUTER,
                     first=cfg["experts_first"],
                     count=cfg["experts_count"]),
        layer_indices=tuple(cfg["layer_indices"]),
        published_depth=cfg["published_depth"], scale_depth=None,
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_seq"], block_len=cfg["block_len"],
        mask_id=cfg["mask_id"], denoising_steps=cfg["denoising_steps"],
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
        params = weights_sdar.make_params(self.cfg, seed)
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


def draw_sample(finished, seed, size):
    """``serve.draw_sample``'s choice (``size`` finished requests drawn
    from the seed, the longest among them), as ``(prompt, completion)``:
    the comparison needs the trajectory a completion carries."""
    rng = np.random.default_rng([seed, 3])
    longest = max(range(len(finished)), key=lambda i: len(finished[i][2])
                  + len(finished[i][3].tokens))
    rest = [i for i in rng.permutation(len(finished)) if i != longest]
    return [(finished[i][2], finished[i][3])
            for i in [longest] + rest[:size - 1]]


def passes_between(before, after):
    """What the engine's pass counters counted between two readings of
    the replica's stats; ``None`` where the engine has none."""
    read = [s["engine"] for s in (before, after)]
    if any(name not in r for r in read for name in PASS_COUNTERS):
        return None
    return {name: read[1][name] - read[0][name] for name in PASS_COUNTERS}


def summed_up(gaps):
    """``serve_xing.summed_up`` (the widest of some gaps and their 99th
    percentile), 0 and 0 where there are none (no pass that chose)."""
    return serve_xing.summed_up(gaps) if sum(g.size for g in gaps) \
        else (0.0, 0.0)


def reference_gaps(cfg, mix, seed, sample, precision=None, faults=False):
    """The two kinds of gap (``benchmark/controls_sdar.py``) over
    ``sample`` (``(prompt, completion)`` pairs) under the float32
    reference, each by its widest and its 99th percentile; with
    ``precision`` the same for what that precision's own forward would
    serve and choose on the served trajectory (the control: it need not
    decode); with ``faults`` the same for each of
    ``controls_sdar.FAULTS``."""
    import jax.numpy as jnp

    params = weights_sdar.make_params(cfg, seed)
    length = controls_sdar.rows_needed(mix["prompt_len"]["max"],
                                       mix["new_tokens"]["max"], cfg)
    block = cfg["block_len"]
    most = (mix["new_tokens"]["max"] + 2 * block) * cfg["denoising_steps"]
    apart = ([("fp8", None)] if precision else []) + [
        ("f32", name) for name in (controls_sdar.RUN_APART if faults
                                   else ())]
    sound_fn = controls_sdar.forward(cfg)
    apart_fns = {key: controls_sdar.forward(cfg, *key) for key in apart}
    names = {("fp8", None): "float8", **{("f32", n): n for n in
                                         controls_sdar.RUN_APART}}
    found = {name: ([], []) for name in ["sound"] + [names[k] for k in apart]
             + (["confidence_from_logit"] if faults else [])}
    spread, positions, flips = [], 0, 0
    for prompt, done in sample:
        generated = list(done.tokens) + list(done.cut)

        def laid_out(fault=None):
            *arrays, reads = controls_sdar.trajectory(
                prompt, generated, done.passes, cfg, length, fault)
            rows = np.concatenate([r[0] for r in reads])
            rows = np.pad(rows, (0, most - len(rows)))
            return [jnp.asarray(a) for a in arrays] + [jnp.asarray(rows)], \
                reads

        arrays, reads = laid_out()
        count = block * len(reads)
        served = np.pad(np.concatenate([r[3] for r in reads]),
                        (0, most - count))
        picks, theirs = [served], {}
        for key in apart:
            inputs = laid_out(key[1])[0] if key[1] in (
                "commit_skipped", "mask_as_token_0") else arrays
            _, first, sure, _ = apart_fns[key](
                params, *inputs, jnp.asarray(served[None]))
            theirs[names[key]] = np.asarray(sure)[:count]
            picks.append(np.asarray(first))
        best, _, sure, picked = (np.asarray(x) for x in sound_fn(
            params, *arrays, jnp.asarray(np.stack(picks))))
        best, sure, picked = best[:count], sure[:count], picked[:, :count]
        token, choice = controls_sdar.gaps(reads, best, sure, picked[0])
        found["sound"][0].append(token)
        found["sound"][1].append(choice)
        flips += int((token > 0).sum())
        positions += token.size
        spread.append(float(np.mean(sure)))
        for i, key in enumerate(apart):
            token, choice = controls_sdar.gaps(
                reads, best, sure, picked[i + 1], theirs[names[key]])
            found[names[key]][0].append(token)
            found[names[key]][1].append(choice)
        if faults:
            token, choice = controls_sdar.gaps(reads, best, sure, picked[0],
                                               chosen_by=best)
            found["confidence_from_logit"][0].append(token)
            found["confidence_from_logit"][1].append(choice)
    out = {}
    for name, (token, choice) in found.items():
        widest, p99 = summed_up(token)
        wide_choice, p99_choice = summed_up(choice)
        out[name] = {"widest_gap": widest, "p99_gap": p99,
                     "widest_choice_gap": wide_choice,
                     "p99_choice_gap": p99_choice,
                     "passes_that_chose": int(sum(c.size for c in choice))}
    return dict(out.pop("sound"), flips=flips, tokens=positions,
                mean_confidence=float(np.mean(spread)),
                control=out.pop("float8", None), faults=out)


LIMITS = (("served_logit_gap", "widest_gap"),
          ("served_logit_gap_p99", "p99_gap"),
          ("unmask_choice_gap", "widest_choice_gap"),
          ("unmask_choice_gap_p99", "p99_choice_gap"))


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
                    f"the pass program warmed in {harness.now() - t0:.2f} s; "
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
        trace, traced_counts, traced_positions, traced_passes = \
            {}, None, None, None
        if ctx.trace:
            # the counters are read inside the trace, as serve_xing.py
            # reads them: stopping and reducing it empties slots
            with scopes_sdar.traced(trace):
                t0 = handle.stats()["replicas"][0]
                loop.run_until(harness.now() + mix["trace_seconds"])
                t1 = handle.stats()["replicas"][0]
            traced_counts = serve_xing.counted_between(t0, t1)
            traced_positions = positions_between(t0, t1)
            traced_passes = passes_between(t0, t1)
            harness.say(f"serve: device seconds by scope in the traced "
                        f"slice: {trace.get('scope_s')}; in its pass "
                        f"program: {trace.get('decode_scope_s')}; positions "
                        f"attended a pass by kind: {traced_positions}; "
                        f"passes {traced_passes}")
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
    sample = draw_sample(in_window, ctx.seed, mix["check_requests"])
    gaps = reference_gaps(cfg, mix, ctx.seed, sample)
    harness.say(
        f"serve: reference ran {len(sample)} requests (prompts "
        f"{[len(p) for p, _ in sample]}), {gaps['tokens']} served positions "
        f"({gaps['flips']} not the reference's first; mean confidence "
        f"{gaps['mean_confidence']:.2e}), {gaps['passes_that_chose']} "
        f"passes that chose, in {harness.now() - t0:.2f} s (not part of "
        f"setup_s)")
    checks += [harness.at_most(name, gaps[key], ctx.limits[name])
               for name, key in LIMITS]
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
    window_passes = passes_between(before, after)
    harness.say(
        f"serve: {len(in_window)} requests finished in {window_s:.3f} s "
        f"({failed} failed); {steps} passes ({window_passes}); set-up "
        f"{setup_s:.2f} s; cache {dict(ctx.compiles.counts)}")
    longest = sorted(((t1 - t0, len(c.tokens)) for t0, t1, _, c in in_window),
                     reverse=True)[:10]
    harness.say("serve: the ten longest latencies (s, served tokens): "
                + ", ".join(f"{s:.2f} {n}" for s, n in longest))
    window_pairs = serve_xing.counted_between(before, after)[:, 0]
    window_positions = positions_between(before, after)
    harness.say(f"serve: (token, expert) pairs routed in the window, by "
                f"layer: {window_pairs.sum(axis=1).tolist()}; busiest "
                f"expert of each layer {window_pairs.max(axis=1).tolist()}; "
                f"positions a pass attended by kind: {window_positions}")
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
        "window_passes": window_passes, "traced_passes": traced_passes,
        "breakdown": trace_mod.breakdown(trace) if trace else None,
    }


def calibrate(config, published, mix, devices, seeds, control_seeds):
    """For ``benchmark/tools/calibrate.py``: per seed a short window at
    the cell's own load, then the four numbers of a run's sample under
    the float32 reference, and for the control seeds the same of the
    float8 reference and of every planted fault
    (``raw[seed]["control"]``, ``raw[seed]["faults"]``)."""
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
        sample = draw_sample(finished[ramp_done:], seed,
                             mix["check_requests"])
        gaps = reference_gaps(config, mix, seed, sample,
                              "fp8" if seed in control_seeds else None,
                              faults=seed in control_seeds)
        harness.say(f"seed {seed}: {len(finished)} finished, "
                    f"{failed} failed; {gaps}")
        raw[seed] = gaps
        if seed in seeds:
            sound.append({name: gaps[key] for name, key in LIMITS})
        if seed in control_seeds:
            control.append({name: gaps["control"][key]
                            for name, key in LIMITS})
        jax.clear_caches()
    return sound, control, raw
