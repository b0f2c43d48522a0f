#!/usr/bin/env python3
"""Put a kept trace's idle time down to what the host was doing.

    BENCHMARK_KEEP_TRACE=<dir> python3 benchmark/run.py ... --trace 1
    python3 benchmark/tools/idle_causes.py <file.xplane.pb | directory>

The program writes each of its spans (``serve.*``, ``engine.*``,
``input.*``, ``request.*``) into the profiler's trace as a host event of
the same name, on the clock the device events are on. This takes the
first device's idle gaps over 100 us, as ``benchmark/trace.py gaps``
has them, and puts each down to the innermost such event open at the
gap's midpoint (the one that started last), else ``unattributed``. It
prints idle seconds by span name, the ten longest gaps with their cause
and the program that ran next, the same idle time split over every span
a gap overlaps (a ``wait`` span counts as ``readback`` where the host
was in it before the gap opened, as ``launch`` where it entered it with
the chip still idle), and the device's seconds by scope: the program's
``jax.named_scope``s (``loss``, ``optimizer``, ``grad_exchange``) and
kernel names, read from whichever statistic carries the ``op_name``
path. On a TPU that statistic hangs on the event's metadata, which
``ProfileData`` does not hand out; it is then read from the file itself
through the ``xplane_pb2`` that ships inside the tensorflow package
(loaded by path, tensorflow is not imported), where that is installed.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402

PREFIXES = ("serve.", "engine.", "input.", "request.")
MIN_GAP_S = 100e-6
SCOPES = ("loss", "optimizer", "grad_exchange", "flash_fwd", "flash_dq",
          "flash_dkv", "flash_bwd")
_SCOPE = re.compile(r"(?<![A-Za-z0-9])(%s)(?![A-Za-z0-9])" % "|".join(SCOPES))


def annotations(profile):
    """The program's spans on the host planes, as
    ``(name, start_ns, end_ns)`` sorted by start."""
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in profile.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda a: a[1])


def cause(spans, at_ns):
    """The name of the span open at ``at_ns`` that started last."""
    name = "unattributed"
    for span, start, end in spans:     # sorted by start: the last wins
        if start > at_ns:
            break
        if end >= at_ns:
            name = span
    return name


def split(spans, start, end):
    """{label: nanoseconds} of the gap ``start .. end`` over the spans
    it overlaps, each instant going to the span open at it that started
    last. A ``wait`` span is ``:readback`` if it was open when the gap
    began and ``:launch`` if it was entered inside the gap."""
    inside = [s for s in spans if s[1] < end and s[2] > start]
    cuts = sorted({start, end} | {t for _, a, b in inside for t in (a, b)
                                  if start < t < end})
    out = collections.Counter()
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in inside if s[1] <= a and s[2] >= b]
        if not open_:
            out["unattributed"] += b - a
            continue
        name, began, _ = max(open_, key=lambda s: s[1])
        if name.endswith(".wait"):
            name += ":readback" if began <= start else ":launch"
        out[name] += b - a
    return out


def idle_by_cause(events, spans):
    """The first device's gaps over 100 us as ``(seconds, cause,
    start_ns)``, longest first."""
    return [(seconds, cause(spans, start + seconds * 0.5e9), start)
            for start, seconds in trace.gaps(events) if seconds > MIN_GAP_S]


def scope_of(name, stats):
    """The innermost of the program's scopes in an event's ``op_name``
    path (or its name); else forward / backward by the path's autodiff
    wrappers; else ``other``."""
    texts = [name] + [v for _, v in stats if isinstance(v, str)]
    found = [m for text in texts for m in _SCOPE.finditer(text)]
    if found:
        return max(found, key=lambda m: m.start()).group(1)
    if any("transpose(" in t for t in texts[1:]):
        return "backward"
    return "forward" if any("jvp(" in t for t in texts[1:]) else "other"


def _first_device(planes):
    return sorted((p for p in planes
                   if p.name.startswith(trace.DEVICE_PREFIX)),
                  key=lambda p: p.name)[:1]


def _by_scope(operations):
    """{scope: seconds} and {statistic that held a path: count} over
    ``(name, [(statistic, value)], seconds)`` triples."""
    seconds, carriers = collections.Counter(), collections.Counter()
    scopes = {}   # an operation's name is its whole instruction: one
    for name, stats, took in operations:   # scope per name
        if name not in scopes:
            scopes[name] = scope_of(name, stats)
        seconds[scopes[name]] += took
        carriers.update(k for k, v in stats
                        if isinstance(v, str) and "/" in v)
    return dict(seconds), dict(carriers)


def device_seconds_by_scope(profile):
    """{scope: seconds} over the first device's operations, and the names
    of the statistics that held a path, from the events' own statistics
    (all that ``ProfileData`` hands out)."""
    return _by_scope(
        (e.name, list(e.stats), e.duration_ns * 1e-9)
        for plane in _first_device(profile.planes) for line in plane.lines
        if line.name == trace.OPS_LINE for e in line.events)


def xplane_pb2():
    """The ``xplane_pb2`` inside the tensorflow package, loaded by its
    path (importing tensorflow takes a quarter of a minute); ``None``
    where there is none."""
    import importlib.util

    try:
        spec = importlib.util.find_spec("tensorflow")
        path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                            "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    except (ImportError, AttributeError, OSError, TypeError, ValueError):
        return None


def device_seconds_by_scope_from_file(path):
    """As :func:`device_seconds_by_scope`, with the statistics of each
    event's metadata (where a TPU trace keeps ``op_name``) read from the
    file itself; ``None`` where ``xplane_pb2`` is not installed."""
    pb2 = xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())

    def operations():
        for plane in _first_device(space.planes):
            names = {k: v.name for k, v in plane.stat_metadata.items()}
            texts = {}   # event metadata id -> (name, its string statistics)
            for key, meta in plane.event_metadata.items():
                stats = []
                for stat in meta.stats:
                    kind = stat.WhichOneof("value")
                    if kind == "str_value":
                        stats.append((names.get(stat.metadata_id, "?"),
                                      stat.str_value))
                    elif kind == "ref_value":
                        stats.append((names.get(stat.metadata_id, "?"),
                                      names.get(stat.ref_value, "")))
                texts[key] = (meta.name, stats)
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    for e in line.events:
                        yield (*texts.get(e.metadata_id, ("", [])),
                               e.duration_ps * 1e-12)

    return _by_scope(operations())


def report(profile, say=print, path=None):
    ops = trace.device_ops(profile)
    if not ops:
        say("no device operations in this trace")
        return None
    first = ops[sorted(ops)[0]]
    spans = annotations(profile)
    busy, window = trace.busy_and_window(first)
    gaps = idle_by_cause(first, spans)
    idle = sum(g[0] for g in gaps)
    by_cause = collections.Counter()
    for seconds, name, _ in gaps:
        by_cause[name] += seconds
    say(f"first device: window {window:.6f} s, busy {busy:.6f} s, idle "
        f"{window - busy:.6f} s; {len(gaps)} gaps over "
        f"{MIN_GAP_S * 1e6:.0f} us hold {idle:.6f} s; {len(spans)} program "
        f"spans on the host planes")
    say("idle seconds by cause (innermost program span at the gap's "
        "midpoint):")
    for name, seconds in by_cause.most_common():
        say(f"  {seconds:10.6f} s {100 * seconds / idle:6.2f}%  {name}")
    named = idle - by_cause.get("unattributed", 0.0)
    say(f"named: {100 * named / idle if idle else 0.0:.2f}% of the idle "
        f"time in gaps over {MIN_GAP_S * 1e6:.0f} us")
    modules = sorted(trace.device_ops(profile, trace.MODULES_LINE).get(
        sorted(ops)[0], []), key=lambda m: m[1])
    say("ten longest gaps (cause at the midpoint; the program that ran "
        "next):")
    for seconds, name, start in gaps[:10]:
        after = next((m[0] for m in modules
                      if m[1] >= start + seconds * 1e9 - 1000), "?")
        say(f"  {seconds * 1e3:9.3f} ms at {start:.0f} ns  {name}  -> "
            f"{after.split('(')[0]}")
    shares = collections.Counter()
    for seconds, _, start in gaps:
        shares.update(split(spans, start, start + seconds * 1e9))
    say("the same idle time split over every span a gap overlaps:")
    for name, ns in shares.most_common():
        say(f"  {ns * 1e-9:10.6f} s {100 * ns * 1e-9 / idle:6.2f}%  {name}")
    scopes, carriers = device_seconds_by_scope(profile)
    if not carriers and path is not None:
        scopes, carriers = (device_seconds_by_scope_from_file(path)
                            or (scopes, carriers))
    say(f"device seconds by scope (op_name read from {carriers or 'no'} "
        f"statistic):")
    for name, seconds in sorted(scopes.items(), key=lambda kv: -kv[1]):
        say(f"  {seconds:10.6f} s  {name}")
    return {"idle_s": idle, "by_cause": dict(by_cause), "gaps": gaps,
            "shares": {k: v * 1e-9 for k, v in shares.items()},
            "scopes": scopes}


def main(path):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print("trace:", path)
    report(ProfileData.from_file(path), path=path)


if __name__ == "__main__":
    main(sys.argv[1])
