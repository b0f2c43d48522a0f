#!/usr/bin/env python3
"""Name what the host was doing in each idle gap of a kept trace, on
every thread.

    BENCHMARK_KEEP_TRACE=<dir> python3 benchmark/run.py ... --trace 1
    python3 benchmark/tools/stall_causes.py <file.xplane.pb | directory>

``idle_causes.py`` puts a gap down to one span of the loop's thread at
the gap's midpoint. A stall is a span that could not *end*: whatever held
the interpreter ran on another thread, or was the collector, and the
midpoint's span is the victim and not the cause. For every gap over
100 us of the first device this prints

(a) the loop thread's innermost span when the gap opened and when it
    closed (the loop's thread is the host line with the ``serve.step``
    events);
(b) every program span open on another host line during the gap, and on
    any line the ``host.gc`` spans (a collector pass of 0.5 ms or more)
    and ``engine.stats``;
(c) whether the ``engine.*.dispatch`` that enqueued the next program
    began before the gap opened (``runtime late``: the call was under way
    or done and the program still did not start) or inside it (``host
    late``: the loop had not come to it yet);
(d) the profiler's own host events of 1 ms or more that lie in the gap
    for more than 100 us (compiles, transfers; with
    ``python_tracer_level`` raised, which only a builder's own run may do,
    the Python frames), by name, of each host line the three innermost;

then the ten longest gaps and the idle seconds by class. ROADMAP S10(a)'s
``benchmark`` PR moves this into ``trace.breakdown``.
"""

from __future__ import annotations

import collections
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402
from benchmark.tools import idle_causes  # noqa: E402

PREFIXES = idle_causes.PREFIXES + ("host.",)
MIN_GAP_S = idle_causes.MIN_GAP_S
LONG_HOST_EVENT_NS = 1_000_000
INNERMOST = 3
# program spans that name a cause on whatever line they lie
ANY_LINE = ("host.gc", "engine.stats")


def host_lines(profile):
    """The host planes' lines as ``{line: (spans, others)}``: the
    program's spans and every other event of ``LONG_HOST_EVENT_NS`` or
    more, each ``(name, start_ns, end_ns)`` sorted by start. The
    profiler names a line by the thread's native name, which Python does
    not set: a line is called by that name, a running number and the
    program span it holds most of (``python3#2 request.submit``)."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans, others = [], []
            for e in line.events:
                item = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith(PREFIXES):
                    spans.append(item)
                elif e.duration_ns >= LONG_HOST_EVENT_NS:
                    others.append(item)
            if spans or others:
                counts = collections.Counter(s[0] for s in spans)
                name = " ".join([f"{line.name or '?'}#{len(out)}"]
                                + [n for n, _ in counts.most_common(1)])
                out[name] = (sorted(spans, key=lambda s: s[1]),
                             sorted(others, key=lambda s: s[1]))
    return out


def loop_line(lines):
    """The line that holds most ``serve.step`` events (else most
    ``engine.*``): the serving loop's thread. ``None`` without any."""
    def count(prefix):
        return {name: sum(1 for s in spans if s[0].startswith(prefix))
                for name, (spans, _) in lines.items()}
    for prefix in ("serve.step", "engine."):
        counts = count(prefix)
        if counts and max(counts.values()):
            return max(counts, key=counts.get)
    return None


def overlapping(events, start, end, least=0.0):
    """``[(name, nanoseconds inside start .. end)]`` of the events that
    overlap the interval by more than ``least`` nanoseconds, in the
    events' order."""
    return [(name, min(b, end) - max(a, start)) for name, a, b in events
            if min(b, end) - max(a, start) > least]


def dispatch_class(loop_spans, start, end):
    """(c): ``host late``, ``runtime late`` or ``no dispatch`` for the gap
    ``start .. end``, and the dispatch span it was judged by. The device
    runs programs in the order they were enqueued and was busy until
    ``start``, so the program that ended the gap was enqueued by the
    first dispatch that was still open when the gap opened or began
    inside it (``no dispatch``: the loop's line holds none before the
    gap's end, so something else enqueued the program)."""
    before = [s for s in loop_spans
              if s[0].endswith(".dispatch") and s[1] < end]
    if not before:
        return "no dispatch", None
    for span in before:
        if span[2] > start:
            return ("runtime late" if span[1] <= start else "host late",
                    span)
    # the newest had returned before the gap opened
    return "runtime late", before[-1]


def gaps(profile):
    """Every gap over 100 us of the first device, longest first, each a
    dict: ``seconds``, ``start_ns``, ``at_start`` / ``at_end`` (a), the
    ``others`` of (b) as ``[(line, name, ns)]``, ``class`` and
    ``dispatch`` (c), ``host_events`` (d) as ``[(line, name, ns)]``."""
    ops = trace.device_ops(profile)
    if not ops:
        return [], None
    first = ops[sorted(ops)[0]]
    lines = host_lines(profile)
    loop = loop_line(lines)
    loop_spans = lines[loop][0] if loop else []
    out = []
    for start, seconds in trace.gaps(first):
        if seconds <= MIN_GAP_S:
            continue
        end = start + seconds * 1e9
        others, events = [], []
        for name, (spans, rest) in lines.items():
            wanted = [s for s in spans
                      if name != loop or s[0] in ANY_LINE]
            others += [(name, n, ns)
                       for n, ns in overlapping(wanted, start, end)]
            # of a line's nested events (the Python tracer's frames) the
            # three that began last: the innermost
            events += [(name, n, ns) for n, ns in overlapping(
                rest, start, end, MIN_GAP_S * 1e9)[-INNERMOST:]]
        kind, dispatch = dispatch_class(loop_spans, start, end)
        out.append({
            "seconds": seconds, "start_ns": start,
            "at_start": idle_causes.cause(loop_spans, start),
            "at_end": idle_causes.cause(loop_spans, end),
            "others": sorted(others, key=lambda o: -o[2]),
            "class": kind, "dispatch": dispatch and dispatch[0],
            "host_events": sorted(events, key=lambda o: -o[2])})
    return out, loop


def label(gap):
    """A gap's class for the totals: (c), and what of (b) lay over it."""
    names = sorted({name for _, name, _ in gap["others"]})
    return gap["class"] + (" + " + ", ".join(names) if names else "")


def report(profile, say=print):
    found, loop = gaps(profile)
    if not found:
        say("no device operations, or no gap over %.0f us, in this trace"
            % (MIN_GAP_S * 1e6))
        return None
    ops = trace.device_ops(profile)
    modules = sorted(trace.device_ops(profile, trace.MODULES_LINE).get(
        sorted(ops)[0], []), key=lambda m: m[1])
    idle = sum(g["seconds"] for g in found)
    say(f"first device: {len(found)} gaps over {MIN_GAP_S * 1e6:.0f} us "
        f"hold {idle:.6f} s; the loop's thread is the host line {loop!r}")
    by_class = collections.Counter()
    for gap in found:
        by_class[label(gap)] += gap["seconds"]
    say("idle seconds by class (the dispatch of the next program began "
        "before the gap: runtime late; inside it: host late; + what ran "
        "on another line or the collector meanwhile):")
    for name, seconds in by_class.most_common():
        say(f"  {seconds:10.6f} s {100 * seconds / idle:6.2f}%  {name}")
    say("ten longest gaps:")
    for gap in found[:10]:
        end = gap["start_ns"] + gap["seconds"] * 1e9
        after = next((m[0] for m in modules if m[1] >= end - 1000), "?")
        say(f"  {gap['seconds'] * 1e3:9.3f} ms at {gap['start_ns']:.0f} ns"
            f"  loop: {gap['at_start']} -> {gap['at_end']}  "
            f"{gap['class']} ({gap['dispatch'] or 'no dispatch span'})"
            f"  next: {after.split('(')[0]}")
        for what, rows in (("other lines", gap["others"]),
                           ("host events >= 1 ms", gap["host_events"])):
            if rows:
                say(f"      {what}: " + "; ".join(
                    f"{line}: {trace.short(name, 60)} {ns * 1e-6:.3f} ms"
                    for line, name, ns in rows[:6]))
    return {"idle_s": idle, "by_class": dict(by_class), "gaps": found,
            "loop": loop}


def main(path):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print("trace:", path)
    report(ProfileData.from_file(path))


if __name__ == "__main__":
    main(sys.argv[1])
