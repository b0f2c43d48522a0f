"""The gradient exchange as the first device's trace shows it, whether
its reductions run as operations of their own or inside asynchronous
collective fusions.

A synchronous collective is one event, named by its instruction
(``%all-reduce.7 = ...``). An asynchronous collective fusion is a chain
of events: ``%async-collective-start.N``, then fusions that each carry a
step of the ring beside their own work (nothing in their names tells
them from any other fusion), then ``%async-collective-done.N``, in which
the core waits for what those did not cover. Between a start and its
done the reduction is open. An event is told by the name of its own
instruction, the part before `` = ``: the rest lists its operands, and a
copy of a reduced gradient is no collective. A program with no
asynchronous fusion reads as ``benchmark.trace.collective_seconds``
reads it.
"""

from benchmark import trace

STARTS = ("async-collective-start",) + tuple(
    c + "-start" for c in trace.COLLECTIVES)
DONES = ("async-collective-done",) + tuple(
    c + "-done" for c in trace.COLLECTIVES)


def kind(name):
    """``"start"``, ``"done"`` or ``"sync"`` for an event of the
    exchange, ``None`` for any other."""
    own = name.split(" = ", 1)[0].lstrip("%")
    if own.startswith(STARTS):
        return "start"
    if own.startswith(DONES):
        return "done"
    if own.startswith(trace.COLLECTIVES):
        return "sync"
    return None


def exposed_seconds(events):
    """Seconds in which the device ran an event of the exchange and
    nothing else: synchronous collectives, and the starts and dones of
    asynchronous ones."""
    return trace.collective_seconds(
        events, match=lambda name: kind(name) is not None)[1]


def open_seconds(events):
    """Seconds in which a reduction was open: a synchronous collective
    ran, or an asynchronous one had started and was not yet done."""
    spans, opened, depth = [], None, 0
    for name, start, duration in sorted(events, key=lambda e: e[1]):
        what = kind(name)
        if what == "sync":
            spans.append((start, start + duration))
        elif what == "start":
            if depth == 0:
                opened = start
            depth += 1
        elif what == "done" and depth:
            depth -= 1
            if depth == 0:
                spans.append((opened, start + duration))
    if depth:   # the slice ended inside a chain
        spans.append((opened, max(s + d for _, s, d in events)))
    return sum(e - s for s, e in trace.merged(spans)) * 1e-9
