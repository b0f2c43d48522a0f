"""What kept the serving loop's host from the chip, read from the
program's own spans (``horovod_tpu.tracing``) after the run.

Since PR 37 every span carries its ``thread``; a ``serve.step`` says
whether the device had run dry when the pass came to enqueue
(``starved``) and whether a profiler session was recording
(``profiled``); an ``engine.decode.wait`` says whether the result was
there already when the wait began (``ready``); the dispatch spans and
``engine.stats`` say how long they waited for the cache's lock
(``lock_ms``); and a garbage-collector pass of half a millisecond or more
is a ``host.gc`` span on the thread it ran on. The three readers here
(``starved_step_share.serve``, ``host_late_share.serve``,
``gc_pause_ms_step.serve``) reduce them over the steady steps as
``benchmark/spans.py`` defines those. A program without the attributes
(a commit from before them, or the ring off) gives ``None`` everywhere.
"""

from __future__ import annotations

import bisect

from benchmark import spans

_said = False   # say_once has printed (a process runs one cell once)
# a dispatch this long is a stall (one takes 0.2-1.5 ms): the runtime's
# allocator has been seen to hold one for 112 ms (PERF.md section 6, PR 37)
SLOW_DISPATCH_S = 0.02


def steady_steps(summary, ring):
    """The steady ``serve.step`` spans, oldest first, and every steady
    span by name."""
    named = spans.steady_by_name(summary, ring)
    return sorted(named.get("serve.step", ()), key=lambda s: s["t"]), named


def starved_shares(summary, ring=None):
    """``{"window": (starved, steps, dry), "profiled": (...)}`` over the
    steady steps that carry ``starved``: those of the window (no profiler
    session at their entry) and those of the traced slice; ``dry`` counts
    the steps one of whose enqueues returned to a dry device
    (``dry_enqueues``). ``None`` where no steady step carries it."""
    steps, _ = steady_steps(summary, spans.ring() if ring is None else ring)
    out = {"window": [0, 0, 0], "profiled": [0, 0, 0]}
    for step in steps:
        if "starved" in step:
            part = out["profiled" if step.get("profiled") else "window"]
            part[0] += step["starved"]
            part[1] += 1
            part[2] += bool(step.get("dry_enqueues"))
    if not out["window"][1] and not out["profiled"][1]:
        return None
    return {k: tuple(v) for k, v in out.items()}


def host_late(summary, ring=None):
    """``(ready, waits)`` over the steady ``engine.decode.wait`` spans
    that carry ``ready``; ``None`` where none does."""
    _, named = steady_steps(summary, spans.ring() if ring is None else ring)
    waits = [w for w in named.get("engine.decode.wait", ())
             if "ready" in w]
    if not waits:
        return None
    return sum(w["ready"] for w in waits), len(waits)


def overlap(span, intervals):
    """Seconds of ``span`` inside the sorted, disjoint ``intervals``."""
    start, end = span["t"], span["t"] + span["dur"]
    return sum(max(0.0, min(end, b) - max(start, a))
               for a, b in intervals if a < end and b > start)


def gc_totals():
    """The program's running totals of collector passes by generation;
    ``None`` for a program without the hook."""
    try:
        from horovod_tpu import tracing
    except ImportError:
        return None
    totals = getattr(tracing, "gc_totals", None)
    return totals() if totals else None


def gc_pauses(summary, ring=None):
    """The ``host.gc`` spans against the steady steps: ``ms_step``
    (their seconds inside a steady step's interval over the steady
    steps), ``in_steps`` (how many touch one), and of all in the ring
    ``count``, ``by_thread`` and the five ``longest``. ``None`` for a
    program
    without the collector's hook, or without steady steps."""
    if gc_totals() is None:
        return None
    ring = spans.ring() if ring is None else ring
    steps, _ = steady_steps(summary, ring)
    if not steps:
        return None
    intervals = [(s["t"], s["t"] + s["dur"]) for s in steps]
    passes = [s for s in ring if s["name"] == "host.gc"]
    inside = [overlap(s, intervals) for s in passes]
    by_thread = {}
    for s in passes:
        by_thread[s.get("thread", "?")] = \
            by_thread.get(s.get("thread", "?"), 0.0) + s["dur"]
    longest = sorted(zip(passes, inside), key=lambda p: -p[0]["dur"])[:5]
    marked = [s["t"] for s in ring
              if s["name"] == "serve.step" and s.get("profiled")]
    slice_at = min(marked) if marked else None
    return {"ms_step": sum(inside) * 1e3 / len(steps),
            "in_steps": sum(1 for x in inside if x > 0),
            "steps": len(steps), "count": len(passes),
            "by_thread": by_thread,
            # (ms, generation, thread, the span of that thread it lay in,
            # seconds from its start to the traced slice's first step,
            # ms of it inside a steady step)
            "longest": [(s["dur"] * 1e3, s.get("generation"),
                         s.get("thread"), (s.get("parent") or [None])[0],
                         None if slice_at is None else slice_at - s["t"],
                         x * 1e3) for s, x in longest]}


def by_thread(steps, ring):
    """Per steady step (``steps``, oldest first), the milliseconds of the
    outermost spans (those with no ``parent``) of every thread, as
    ``{(thread, name): ms}``: the loop's thread is its ``serve.step``,
    another thread's span counts where its midpoint lies inside a steady
    step. ``None`` where the spans carry no ``thread``."""
    if not steps or "thread" not in steps[0]:
        return None
    starts = [s["t"] for s in steps]          # one thread's: disjoint
    out = {}
    for s in ring:
        if "parent" in s or "thread" not in s:
            continue
        middle = s["t"] + 0.5 * s["dur"]
        at = bisect.bisect_right(starts, middle) - 1
        if at >= 0 and middle <= starts[at] + steps[at]["dur"]:
            key = (s["thread"], s["name"])
            out[key] = out.get(key, 0.0) + s["dur"] * 1e3 / len(steps)
    return out


def window_held(summary, steps, ring):
    """Seconds of the window the ring still holds: from its oldest span
    to the first ``serve.step`` of the traced slice (``profiled``), or to
    its newest steady step where no step is marked; never more than the
    window itself."""
    if not ring or not steps:
        return None
    marked = [s["t"] for s in ring
              if s["name"] == "serve.step" and s.get("profiled")]
    end = min(marked) if marked else steps[-1]["t"] + steps[-1]["dur"]
    return max(0.0, min(end - min(s["t"] for s in ring),
                        summary.get("window_s", float("inf"))))


def say_once(summary, say=print):
    """Once a run, what the three readers share: the spans by thread a
    steady step, the waits for the cache's lock, and how much of the
    window the ring still holds."""
    global _said
    if _said:
        return
    _said = True
    ring = spans.ring()
    steps, named = steady_steps(summary, ring)
    threads = by_thread(steps, ring)
    if threads is None:
        return
    say("stalls: per steady step (%d steps), ms of outermost spans by "
        "thread: %s" % (len(steps), ", ".join(
            f"{thread} {name} {ms:.4f}"
            for (thread, name), ms in sorted(threads.items()))))
    dispatches = [s for name in ("engine.prefill.dispatch",
                                 "engine.decode.dispatch")
                  for s in named.get(name, ()) if "lock_ms" in s]
    stats = [s for s in ring if s["name"] == "engine.stats"]
    slow = [s["dur"] * 1e3 for s in dispatches if s["dur"] >= SLOW_DISPATCH_S]
    say("stalls: waits for the cache's lock: %.4f ms over %d steady "
        "dispatches (longest %.4f), %d of which took %.0f ms or more "
        "(%s); engine.stats in the ring: %d spans, %.3f ms in all, %.4f "
        "ms of it waiting for the lock"
        % (sum(s["lock_ms"] for s in dispatches), len(dispatches),
           max((s["lock_ms"] for s in dispatches), default=0.0),
           len(slow), SLOW_DISPATCH_S * 1e3,
           ", ".join(f"{ms:.1f}" for ms in sorted(slow, reverse=True)[:8]),
           len(stats), sum(s["dur"] for s in stats) * 1e3,
           sum(s.get("lock_ms", 0.0) for s in stats)))
    held = window_held(summary, steps, ring)
    profiled = sum(1 for s in steps if s.get("profiled"))
    say("stalls: the ring's %d spans hold %.2f s of the window's %.2f s "
        "before the traced slice; %d of its %d steady steps ran under the "
        "profiler" % (len(ring), held, summary.get("window_s", 0.0),
                      profiled, len(steps)))
