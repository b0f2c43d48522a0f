"""The serving runner: one cell's model behind ``hvd.serve()``, driven by
a closed loop of a fixed number of callers.

Each caller submits, waits for its reply and submits again; one driver
thread plays all of them (it polls ``handle.result`` for each open
request), so that the load comes from one process with few threads. The
callers run for ``ramp_seconds`` before the window opens, which keeps the
first synchronized burst of prefills out of it, and every prompt bucket
and the decode program are warmed before that: all of it is set-up.

``correct``: once the window has closed and the program's state is
freed, a sample of the finished requests, drawn from the seed and with
the longest in it, is run through the plain reference - one full forward
over each prompt with its served tokens - and the widest gap by which a
served token's logit lies below the reference's best is compared with
the cell's limit. Decoding is greedy, so a sound program serves the
reference's best token wherever the reference's lead is wider than the
program's rounding.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness, reference, traffic, weights
from benchmark import trace as trace_mod


class Program:
    """The system under test: ``hvd.serve()`` over the cell's model."""

    def __init__(self, cfg, published, mix, devices):
        import horovod_tpu as hvd

        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.vocab = min(published["vocab_size"], cfg["vocab_size"])
        self.hvd = hvd
        hvd.init(devices=devices)
        self.model = harness.transformer(cfg)
        self.handle = None

    def start(self, seed):
        """Weights from ``seed`` on the device, the replica set, and one
        request through every prompt bucket the mix can reach."""
        from horovod_tpu.serve.kv_cache import prompt_bucket

        mix = self.mix
        params = weights.make_params(self.cfg, seed)
        self.handle = self.hvd.serve(
            self.model, params, replicas=mix["replicas"],
            slots=mix["slots"], paged=mix["paged"],
            max_new_tokens=mix["new_tokens"]["max"], **mix["policy"])
        del params
        sizes = traffic.request_sizes(mix)
        buckets = sorted({prompt_bucket(p, self.cfg["max_seq"])
                          for p, _ in sizes})
        rng = np.random.default_rng([seed, 2])
        warm = [self.handle.submit(
            rng.integers(1, self.vocab, min(b, mix["prompt_len"]["max"])
                         ).tolist(), max_new_tokens=mix["warm_new_tokens"])
            for b in buckets]
        for uid in warm:
            self.handle.result(uid, timeout=900.0)
        return buckets

    def stop(self):
        if self.handle is not None:
            self.handle.close()
        self.handle = None


# how often the driver thread looks at the open requests: coarse enough
# to leave the interpreter to the replica's own thread, fine against
# latencies of seconds
POLL_S = 0.01


def tokens_in_window(finished, opened, closed):
    """Generated tokens attributed to the window: each request's tokens
    times the share of its submit-to-reply time that lies inside the
    window. Requests that straddle an edge count in part, so the number
    does not jump with which side of the edge a 256-token reply lands."""
    total = 0.0
    for submitted, completed, _, done in finished:
        inside = min(completed, closed) - max(submitted, opened)
        if inside > 0:
            total += len(done.tokens) * inside / (completed - submitted)
    return total


class ClosedLoop:
    """``callers`` callers, each with one request open at a time."""

    def __init__(self, handle, source, callers, timeout_s):
        self.handle, self.source = handle, source
        self.callers, self.timeout_s = callers, timeout_s
        self.open = {}        # uid -> (submitted at, prompt, new_tokens)
        self.finished = []    # (submitted, completed, prompt, Completion)
        self.failed = 0       # timed out, refused, cut short
        self.timeouts = 0

    def _submit(self):
        prompt, new_tokens = next(self.source)
        uid = self.handle.submit(prompt, max_new_tokens=new_tokens)
        self.open[uid] = (harness.now(), prompt, new_tokens)

    def run_until(self, deadline, refill=True):
        """Poll the open requests until ``deadline``; a finished caller
        submits its next request at once (unless ``refill`` is off)."""
        while refill and len(self.open) < self.callers:
            self._submit()
        while harness.now() < deadline and self.open:
            for uid in list(self.open):
                try:
                    done = self.handle.result(uid, timeout=0)
                except TimeoutError:
                    if harness.now() - self.open[uid][0] > self.timeout_s:
                        del self.open[uid]
                        self.failed += 1
                        self.timeouts += 1
                        if refill:
                            self._submit()
                    continue
                submitted, prompt, new_tokens = self.open.pop(uid)
                ok = (done.finish == "length"
                      and len(done.tokens) == new_tokens)
                self.failed += 0 if ok else 1
                self.finished.append((submitted, harness.now(), prompt, done))
                if refill:
                    self._submit()
            time.sleep(POLL_S)


def served_gap(logits, prompt_len, tokens):
    """At every served position, how far the served token's logit lies
    below the reference's best; the widest."""
    rows = logits[prompt_len - 1:prompt_len - 1 + len(tokens)]
    best = rows.max(axis=-1)
    served = rows[np.arange(len(tokens)), np.asarray(tokens)]
    return float((best - served).max())


def reference_gaps(cfg, seed, sample, precision=None):
    """The widest served-token gap over ``sample`` (``(prompt, tokens)``
    pairs) under the float32 reference; with ``precision`` the same for
    the tokens that precision's own forward puts first at the same
    positions (the control: it need not decode)."""
    import jax
    import jax.numpy as jnp

    frozen = reference._Frozen(cfg)
    forward = jax.jit(reference.forward, static_argnums=(2, 3))
    params = weights.make_params(cfg, seed)
    widest, low_widest, flips, count = 0.0, 0.0, 0, 0
    for prompt, tokens in sample:
        ids = np.zeros((1, cfg["max_seq"]), np.int32)   # one shape: the
        full = (list(prompt) + list(tokens))[:cfg["max_seq"]]  # model is
        ids[0, :len(full)] = full     # causal, so padding changes nothing
        logits = np.asarray(forward(params, jnp.asarray(ids), frozen,
                                    "f32"))[0]
        widest = max(widest, served_gap(logits, len(prompt), tokens))
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        flips += int((rows.argmax(-1) != np.asarray(tokens)).sum())
        count += len(tokens)
        if precision:
            low = np.asarray(forward(params, jnp.asarray(ids), frozen,
                                     precision))[0]
            first = low[len(prompt) - 1:len(prompt) - 1 + len(tokens)
                        ].argmax(-1)
            low_widest = max(low_widest,
                             served_gap(logits, len(prompt), first))
    return {"widest_gap": widest, "control_widest_gap": low_widest,
            "flips": flips, "tokens": count}


def draw_sample(finished, seed, size):
    """``size`` finished requests drawn from the seed, the longest (by
    prompt plus served tokens) among them."""
    rng = np.random.default_rng([seed, 3])
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][2]) + len(finished[i][3].tokens))
    rest = [i for i in rng.permutation(len(finished)) if i != longest]
    picked = [longest] + rest[:size - 1]
    return [(finished[i][2], list(finished[i][3].tokens)) for i in picked]


def run(ctx):
    import jax

    import horovod_tpu as hvd

    cfg, mix = ctx.config, ctx.mix
    program = Program(cfg, ctx.published, mix, ctx.devices)
    checks = []
    try:
        t0 = harness.now()
        buckets = program.start(ctx.seed)
        handle = program.handle
        harness.say(f"serve: replica set up and prompt buckets {buckets} + "
                    f"decode warmed in {harness.now() - t0:.2f} s; "
                    f"{handle.compiles_total()} programs")
        loop = ClosedLoop(handle, traffic.requests(mix, program.vocab,
                                                   ctx.seed),
                          mix["callers"], mix["request_timeout_s"])
        loop.run_until(harness.now() + mix["ramp_seconds"])

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
            with harness.traced(trace):
                loop.run_until(harness.now() + mix["trace_seconds"])
        loop.run_until(harness.now() + mix["request_timeout_s"],
                       refill=False)          # drain what is open
        quarantined = handle.stats()["replicas"][0]["quarantined"]
    finally:
        program.stop()
    hvd.shutdown()
    jax.clear_caches()

    # ---- the plain reference over a sample of what the window served
    t0 = harness.now()
    sample = draw_sample(in_window, ctx.seed, mix["check_requests"])
    gaps = reference_gaps(cfg, ctx.seed, sample)
    harness.say(
        f"serve: reference ran {len(sample)} requests, {gaps['tokens']} "
        f"served tokens ({gaps['flips']} not the reference's first) in "
        f"{harness.now() - t0:.2f} s (not part of setup_s)")
    checks.append(harness.at_most("served_logit_gap", gaps["widest_gap"],
                                  ctx.limits["served_logit_gap"]))
    checks.append(harness.at_most("compiles_in_window",
                                  compiles_in_window, 0))
    checks.append(harness.at_most("replica_quarantined",
                                  int(quarantined), 0))

    window_s = closed - opened
    steps = after["decode_steps"] - before["decode_steps"]
    occupied = (after["avg_occupancy"] * after["decode_steps"]
                - before["avg_occupancy"] * before["decode_steps"])
    done = [c for _, _, _, c in in_window]
    harness.say(
        f"serve: {len(in_window)} requests finished in {window_s:.3f} s "
        f"({failed} failed); {steps} decode steps; set-up {setup_s:.2f} s; "
        f"cache {dict(ctx.compiles.counts)}")
    return {
        "attempted": attempted, "failed": failed,
        "checks": checks, "memory_peak_bytes": memory_peak,
        "setup_s": setup_s, "window_s": window_s, "chips": len(ctx.devices),
        "served_tokens": tokens_in_window(loop.finished, opened, closed),
        "latency_s": [t1 - t0 for t0, t1, _, _ in in_window],
        "ttft_s": [c.ttft_s for c in done],
        "tpot_s": [(c.latency_s - c.ttft_s) / (len(c.tokens) - 1)
                   for c in done if len(c.tokens) > 1],
        "decode_steps": steps, "occupied_slot_steps": occupied,
        "slots": mix["slots"],
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

    import horovod_tpu as hvd

    program = Program(config, published, mix, devices)
    sound, control, raw = [], [], {}
    for seed in sorted(set(seeds + control_seeds)):
        program.start(seed)
        loop = ClosedLoop(program.handle,
                          traffic.requests(mix, program.vocab, seed),
                          mix["callers"], mix["request_timeout_s"])
        loop.run_until(harness.now() + mix["ramp_seconds"])
        ramp_done = len(loop.finished)
        loop.run_until(harness.now() + mix["calibrate_seconds"])
        loop.run_until(harness.now() + mix["request_timeout_s"],
                       refill=False)
        program.stop()
        gc.collect()
        sample = draw_sample(loop.finished[ramp_done:], seed,
                             mix["check_requests"])
        gaps = reference_gaps(config, seed, sample,
                              "fp8" if seed in control_seeds else None)
        harness.say(f"seed {seed}: {len(loop.finished)} finished, "
                    f"{loop.failed} failed; {gaps}")
        raw[seed] = gaps
        if seed in seeds:
            sound.append({"served_logit_gap": gaps["widest_gap"]})
        if seed in control_seeds:
            control.append({"served_logit_gap":
                            gaps["control_widest_gap"]})
    hvd.shutdown()
    return sound, control, raw
