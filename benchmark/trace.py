"""Reduction of a JAX profiler trace (``*.xplane.pb``) to numbers.

A trace is read with ``jax.profiler.ProfileData`` alone. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO operation (start and duration in nanoseconds);
on the CPU backend (rehearsals only) the operations are the events of
``/host:CPU`` that carry an ``hlo_op`` statistic. Everything below the
loader works on plain ``(name, start_ns, duration_ns)`` tuples, and is
checked in ``benchmark/tests/test_trace.py`` on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that move data between chips
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def find(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def device_ops(profile, line_name=OPS_LINE):
    """{device plane name: [(name, start_ns, duration_ns), ...]} of the
    operations that ran on each device (or, with ``MODULES_LINE``, of the
    programs)."""
    out = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == line_name:
                    out.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
    if out or line_name != OPS_LINE:
        return out
    for plane in profile.planes:   # the CPU backend: rehearsals only
        if plane.name == "/host:CPU":
            events = [(e.name, e.start_ns, e.duration_ns)
                      for line in plane.lines for e in line.events
                      if any(k == "hlo_op" for k, _ in e.stats)]
            if events:
                out[plane.name] = events
    return out


def load(path):
    """The operations and the programs of every device in the trace."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    return device_ops(profile), device_ops(profile, MODULES_LINE)


def merged(intervals):
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def spans(events):
    return [(s, s + d) for _, s, d in events]


def busy_and_window(events):
    """Seconds in which at least one operation ran, and the seconds from
    the first operation's start to the last one's end."""
    if not events:
        return 0.0, 0.0
    cover = merged(spans(events))
    return (sum(e - s for s, e in cover) * 1e-9,
            (cover[-1][1] - cover[0][0]) * 1e-9)


def per_name(events):
    """{operation name: seconds}, summed over its events."""
    out = {}
    for name, _, dur in events:
        out[name] = out.get(name, 0.0) + dur * 1e-9
    return out


def gaps(events):
    """The idle gaps between operations, longest first, as
    ``(start_ns, seconds)``."""
    cover = merged(spans(events))
    out = [(a_end, (b_start - a_end) * 1e-9)
           for (_, a_end), (b_start, _) in zip(cover, cover[1:])]
    return sorted(out, key=lambda g: -g[1])


def is_collective(name):
    return any(name.startswith(c) or ("%" + c) in name for c in COLLECTIVES)


def collective_seconds(events, match=is_collective):
    """Seconds during which a collective ran, and the part of them
    during which no other operation ran on that device (the exposed
    part)."""
    coll = merged(spans([e for e in events if match(e[0])]))
    rest = merged(spans([e for e in events if not match(e[0])]))
    total = sum(e - s for s, e in coll)
    hidden = 0
    j = 0
    for s, e in coll:
        while j < len(rest) and rest[j][1] <= s:
            j += 1
        k = j
        while k < len(rest) and rest[k][0] < e:
            hidden += min(e, rest[k][1]) - max(s, rest[k][0])
            k += 1
    return total * 1e-9, (total - hidden) * 1e-9


MOSAIC = 'custom_call_target="tpu_custom_call"'


def is_mosaic(name):
    """A Pallas (Mosaic) kernel's event: the trace names an event by the
    whole HLO instruction, custom-call target included."""
    return " custom-call(" in name and MOSAIC in name


def flash_kind(name):
    """Which flash-attention kernel an event is, read from the results
    of its HLO instruction (the program gives its kernels no ``name=``
    yet): the forward returns the output and the float32 row statistics,
    the dk/dv kernel two bfloat16 tensors, the dq kernel one. ``None``
    for any other event."""
    if not is_mosaic(name) or " = " not in name:
        return None
    results = name.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    wide, narrow = results.count("f32["), results.count("bf16[")
    return {(1, 1): "fwd", (0, 2): "dkv", (0, 1): "dq"}.get((wide, narrow))


_NUMBERED = re.compile(r"(%[A-Za-z_][A-Za-z0-9_\-]*(?:\.[A-Za-z_][A-Za-z0-9_\-]*)*)(?:\.\d+)+")


def signature(name):
    """An event's name with the running numbers of its instruction names
    taken off (``%attention.71`` -> ``%attention``), so that the same
    operation in every layer is one row of a breakdown."""
    return _NUMBERED.sub(r"\1", name)


def breakdown(reduced):
    """The ``breakdown`` of a traced run's last line: the ten kinds of
    device operation (see :func:`signature`) that took most time, each
    with its number of events, and the ten longest idle gaps. The program
    has no host spans yet, so every gap is the host's."""
    seconds, count = {}, {}
    for name, total in reduced["per_name_s"].items():
        key = signature(name)
        seconds[key] = seconds.get(key, 0.0) + total
    for name, _, _ in reduced["events"]:
        key = signature(name)
        count[key] = count.get(key, 0) + 1
    top = sorted(seconds.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[f"{count[key]}x {short(key, 80)}", total]
                           for key, total in top],
            "idle_gaps": [["host", gap] for _, gap in reduced["gaps"][:10]]}


def short(name, limit=96):
    """An event's name cut to what a ledger line can carry."""
    return name if len(name) <= limit else name[:limit - 3] + "..."


def reduce(ops_by_device, modules_by_device=None):
    """The summary the harness and the per-layer readers use: busy and
    window seconds averaged over the devices, the per-name seconds of
    the first device, its longest gaps, and its programs' executions."""
    devices = sorted(ops_by_device)
    pairs = [busy_and_window(ops_by_device[d]) for d in devices]
    first = ops_by_device[devices[0]] if devices else []
    return {
        "devices": devices,
        "busy_s": sum(p[0] for p in pairs) / max(len(pairs), 1),
        "window_s": sum(p[1] for p in pairs) / max(len(pairs), 1),
        "per_name_s": per_name(first),
        "gaps": gaps(first)[:10],
        "events": first,
        "modules": sorted((modules_by_device or {}).get(
            devices[0] if devices else "", []), key=lambda e: e[1]),
    }
