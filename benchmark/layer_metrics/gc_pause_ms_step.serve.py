"""Milliseconds a steady step of the serving loop that the garbage
collector held the interpreter: the seconds of ``host.gc`` spans, on any
thread, that lie inside a steady ``serve.step``, over the steady steps.
Only a pass of half a millisecond or more is a span; an earlier line
gives every pass of the process by generation (``tracing.gc_totals()``:
count, seconds, longest)."""

from benchmark import harness, stalls


def read(summary):
    if "served_tokens" not in summary:
        return None
    pauses = stalls.gc_pauses(summary)
    if pauses is None:
        return None
    stalls.say_once(summary, harness.say)
    harness.say(
        "gc_pause_ms_step.serve: %d host.gc spans in the ring (seconds by "
        "thread %s), %d of them inside the %d steady steps; the longest as "
        "(ms, generation, thread, the span of that thread it lay in, "
        "seconds before the traced slice, ms inside a steady step): %s; "
        "every pass of the program by generation: %s"
        % (pauses["count"],
           {k: round(v, 6) for k, v in pauses["by_thread"].items()},
           pauses["in_steps"], pauses["steps"],
           [(round(ms, 3), gen, thread, parent,
             before if before is None else round(before, 2),
             round(inside, 3))
            for ms, gen, thread, parent, before, inside
            in pauses["longest"]],
           stalls.gc_totals()))
    return pauses["ms_step"]
