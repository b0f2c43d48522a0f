"""Where a start goes: run a Python script under JAX's own duration events
and print, for each event, how often it fired and the seconds it held
(nested events counted once, under the outermost) - up to the moment the
script first prints a line holding ``--until`` and over the whole run.

    python3 tools/setup_account.py [--until "warmed in"] benchmark/run.py \\
        --workload granite-serve-chat-c1 --seed 1 --seconds 30 --trace 0

A program's start is ``/jax/core/compile/jaxpr_trace_duration`` (the
Python of the model, once a program: flax traces every layer),
``.../jaxpr_to_mlir_module_duration`` (lowering, a Pallas kernel's Mosaic
module among it), ``.../backend_compile_duration`` (the compile, or on a
warm start the fetch from the persistent cache, which
``/jax/compilation_cache/cache_retrieval_time_sec`` counts by itself).
The benchmark's serving runners print "... warmed in X s; N programs" when
every program has run once: that line is the default mark. PERF.md section
6, PR 49, is the first account made with it.
"""

import json
import runpy
import sys
import time


class _Watch:
    """``sys.stdout`` that marks the time its ``needle`` first goes by."""

    def __init__(self, stream, needle):
        self.stream, self.needle, self.seen = stream, needle, None

    def write(self, text):
        if self.seen is None and self.needle in text:
            self.seen = time.perf_counter()
        return self.stream.write(text)

    def __getattr__(self, name):
        return getattr(self.stream, name)


def account(events, until=None):
    """``{event: [count, seconds, outermost seconds]}`` of the events that
    ended by ``until``; an event that lies inside another of its name is
    left out of the third number."""
    out = {}
    for name in sorted({e[0] for e in events}):
        spans = sorted((end - took, end) for n, took, end in events
                       if n == name and (until is None or end <= until))
        outer, edge = 0.0, float("-inf")
        for start, end in spans:
            if start >= edge:
                outer, edge = outer + end - start, end
        out[name] = [len(spans), round(sum(e - s for s, e in spans), 3),
                     round(outer, 3)]
    return out


def main(argv):
    until = "warmed in"
    if argv and argv[0] == "--until":
        until, argv = argv[1], argv[2:]
    if not argv:
        raise SystemExit(__doc__)
    import jax

    events, began = [], time.perf_counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_: events.append(
            (event, seconds, time.perf_counter())))
    sys.stdout = watch = _Watch(sys.stdout, until)
    sys.argv = argv
    try:
        runpy.run_path(argv[0], run_name="__main__")
    finally:
        sys.stdout = watch.stream
        if watch.seen is not None:
            print("setup_account until %r (%.2f s after the start): %s" % (
                until, watch.seen - began,
                json.dumps(account(events, watch.seen))), flush=True)
        print("setup_account whole run (%.2f s): %s" % (
            time.perf_counter() - began, json.dumps(account(events))),
            flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
