"""The program's own spans (``horovod_tpu.tracing``), as the per-layer
readers use them.

The program records a span at each layer boundary of the serving loop
(``serve.step`` with ``serve.pull`` / ``serve.admit`` / ``serve.retire``),
the KV-cache engine (``engine.prefill`` and ``engine.decode`` with their
``dispatch`` / ``wait`` parts), the input feed (``input.wait``,
``input.put``) and each request (``request.*``) into one bounded ring,
each with its ``parent``: the span of its thread it was written in, as
``(name, sid)``. The ring outlives ``hvd.shutdown()``, so a reader finds
it after the run. A program without these spans (the ring off, or a
commit from before them) gives ``None`` everywhere here.
"""

from __future__ import annotations

from benchmark import harness

# a serving step counts as steady when it decoded rows for this share of
# the slots and at least half of them were there before it (it did not
# admit them itself): the drain runs below the first, and the opening
# burst, which fills every slot in one step and prefills them one after
# the other inside it, fails the second - both by the program's own count
STEADY_OCCUPANCY = 0.9


def ring():
    """Every span the program's ring holds, oldest first."""
    try:
        from horovod_tpu import tracing
    except ImportError:
        return []
    return tracing.spans()


def key(span):
    """A span's ``(name, sid)``, as its children's ``parent`` has it."""
    return (span["name"], span.get("sid"))


def enclosing(span, index, name):
    """The span called ``name`` that ``span`` was written in, however
    many spans lie between; ``None`` if there is none in the ring."""
    parent = span.get("parent")
    while parent:
        parent = tuple(parent)
        found = index.get(parent)
        if parent[0] == name:
            return found
        parent = found.get("parent") if found else None
    return None


def steady_by_name(summary, spans):
    """{name: spans} of a serving run's steady part: the ``serve.step``
    spans that decoded rows at 0.9 x ``summary["slots"]`` or more, at
    most half of them admitted in that step, and every span written
    inside one of them."""
    floor = STEADY_OCCUPANCY * summary["slots"]
    index = {key(s): s for s in spans if "sid" in s}
    steps = {k for k, s in index.items()
             if k[0] == "serve.step" and s.get("decoded", 0) > 0
             and s.get("occupancy", 0) >= floor
             and 2 * s.get("admitted", 0) <= s["occupancy"]}
    out = {}
    for s in spans:
        step = s if s["name"] == "serve.step" else \
            enclosing(s, index, "serve.step")
        if step is not None and key(step) in steps:
            out.setdefault(s["name"], []).append(s)
    return out


def steady(summary, name, spans=None):
    """The ring's spans called ``name``; in a serving run only those of
    its steady part (:func:`steady_by_name`), which drops the opening
    burst and the drain.
    ``None`` when the ring is off or holds none."""
    spans = ring() if spans is None else spans
    if "slots" in summary:
        return steady_by_name(summary, spans).get(name)
    return [s for s in spans if s["name"] == name] or None


def total(spans):
    return sum(s["dur"] for s in spans or ())


def serving_split(summary, spans=None):
    """The mean steady ``serve.step`` in milliseconds and its parts:
    the engine's calls, the time blocked on the device inside them, and
    the loop's own three blocks. ``None`` without steady steps."""
    named = steady_by_name(summary, ring() if spans is None else spans)
    steps = named.get("serve.step")
    if not steps:
        return None
    per_step = lambda name: total(named.get(name)) * 1e3 / len(steps)
    out = {"steps": len(steps), "step_ms": per_step("serve.step")}
    for name in ("engine.prefill", "engine.prefill.dispatch",
                 "engine.prefill.wait", "engine.decode",
                 "engine.decode.prep", "engine.decode.dispatch",
                 "engine.decode.wait", "serve.pull", "serve.admit",
                 "serve.retire"):
        out[name] = per_step(name)
    out["wait_ms"] = out["engine.prefill.wait"] + out["engine.decode.wait"]
    out["loop_host_ms"] = out["step_ms"] - out["wait_ms"]
    # host time inside the engine's calls, as its own spans have it
    out["engine_host_ms"] = (out["engine.prefill.dispatch"]
                             + out["engine.decode.prep"]
                             + out["engine.decode.dispatch"])
    return out


def first_token_parts(spans):
    """Per request (by ``trace_id``) the three parts of its time to the
    first token as the replica's spans have them, in seconds: the
    admission wait (``request.queue_wait``), the wait for its turn among
    the prefills of the requests admitted with it (the end of that span
    to the start of its ``request.prefill``), and the prefill."""
    waits = {s["trace_id"]: s for s in spans
             if s["name"] == "request.queue_wait"}
    return [(waits[s["trace_id"]]["dur"],
             max(s["t"] - waits[s["trace_id"]]["t"]
                 - waits[s["trace_id"]]["dur"], 0.0), s["dur"])
            for s in spans
            if s["name"] == "request.prefill" and s["trace_id"] in waits]


def say_serving_split(summary, say=print):
    """Print the split, the sum of the three step metrics beside the mean
    step, the parts of the time to the first token, and what the ring
    holds."""
    spans = ring()
    split = serving_split(summary, spans)
    if split is None:
        return
    parts = (split["engine.prefill"] + split["engine.decode"]
             + split["loop_host_ms"] - split["engine_host_ms"])
    say("spans: mean steady serve.step %.3f ms over %d steps = "
        "engine.prefill %.3f + engine.decode %.3f + loop host %.3f - "
        "the engine's prep and dispatch parts %.3f = %.3f ms (%.3f ms over "
        "the step: time inside the engine's calls that none of their "
        "parts covers)"
        % (split["step_ms"], split["steps"], split["engine.prefill"],
           split["engine.decode"], split["loop_host_ms"],
           split["engine_host_ms"], parts, parts - split["step_ms"]))
    say("spans: per step, ms: " + ", ".join(
        f"{name} {split[name]:.3f}" for name in (
            "serve.pull", "serve.admit", "serve.retire",
            "engine.prefill.dispatch", "engine.prefill.wait",
            "engine.decode.prep", "engine.decode.dispatch",
            "engine.decode.wait")))
    named = steady_by_name(summary, spans)
    for label, some in (("steady", [s for name in ("request.queue_wait",
                                                   "request.prefill")
                                    for s in named.get(name, ())]),
                        ("all in the ring", spans)):
        rows = first_token_parts(some)
        if rows:
            say("spans: to the first token, %s requests (%d): p95 ms of "
                "admission wait %.1f, turn among the admitted %.1f (max "
                "%.1f), prefill %.1f"
                % ((label, len(rows))
                   + tuple(harness.percentile([r[i] for r in rows], 95) * 1e3
                           for i in (0, 1))
                   + (max(r[1] for r in rows) * 1e3,
                      harness.percentile([r[2] for r in rows], 95) * 1e3)))
    ends = [s["t"] + s["dur"] for s in (spans[0], spans[-1])]
    held = ends[1] - ends[0]
    counts = {}
    for s in spans:
        counts[s["name"]] = counts.get(s["name"], 0) + 1
    say("spans: the ring holds %d spans recorded over %.1f s (%.0f a "
        "second): %s" % (len(spans), held,
                         len(spans) / held if held > 0 else 0.0,
                         ", ".join(f"{n} {c}" for n, c in sorted(
                             counts.items(), key=lambda kv: -kv[1]))))
