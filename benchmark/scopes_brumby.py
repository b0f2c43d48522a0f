"""Device seconds by the ``jax.named_scope``s of the power-retention
decoder, from a traced run's ``.xplane.pb``: ``benchmark/scopes.py``'s
reading for another set of names (its ``SCOPES`` is a fixed tuple, and
the file an accepted part of the yardstick, so the names of
``horovod_tpu/models/hybrid.py``'s ``power_retention`` mixer live here).

The reading is the same: the scope an operation ran in is the ``tf_op``
statistic on the event's metadata (the ``op_name`` path, as in
``jit(_decode_impl)/.../retention_step/dot_general``); a fusion carries
one member's path; an operation that only holds others is left out.
Without the ``xplane_pb2`` module, in a trace with no device plane (a CPU
rehearsal) or over a program that has no such scope, there is nothing
to read and every reader leaves its metric out.
"""

from __future__ import annotations

import contextlib
import os
import re
import shutil
import tempfile

from benchmark import scopes, trace
from benchmark.tools import idle_causes

# the recurrence of a decode step (update and read), the prefill's
# chunked form, and the trunk's two scopes as scopes.py has them
SCOPES = ("retention_step", "retention_chunk", "mlp", "head")
_SCOPE = re.compile(r"(?<=/)(%s)(?=/)" % "|".join(SCOPES))


def scope_of(texts):
    """The innermost of :data:`SCOPES` in any of an operation's paths;
    ``other`` where none names one."""
    found = [m for text in texts for m in _SCOPE.finditer(text)]
    return max(found, key=lambda m: m.start()).group(1) if found else "other"


def seconds_by_scope(path):
    """{scope: seconds} over the first device's operations in the trace
    file ``path``; ``{}`` where the paths cannot be read."""
    pb2 = idle_causes.xplane_pb2()
    if pb2 is None:
        return {}
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = sorted((p for p in space.planes
                     if p.name.startswith(trace.DEVICE_PREFIX)),
                    key=lambda p: p.name)[:1]
    out = {}
    for plane in planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        scope = {}       # event metadata id -> scope
        for key, meta in plane.event_metadata.items():
            texts = [s.str_value if s.WhichOneof("value") == "str_value"
                     else names.get(s.ref_value, "")
                     for s in meta.stats
                     if s.WhichOneof("value") in ("str_value", "ref_value")]
            scope[key] = None if scopes._HOLDER.search(meta.name) \
                else scope_of(texts)
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                for e in line.events:
                    kind = scope.get(e.metadata_id, "other")
                    if kind is not None:
                        out[kind] = out.get(kind, 0.0) \
                            + e.duration_ps * 1e-12
    return out


@contextlib.contextmanager
def traced(into):
    """``harness.traced`` with the seconds by scope kept too: the body
    runs under the JAX profiler and ``into`` gets ``trace.reduce``'s
    summary plus ``scope_s`` (:func:`seconds_by_scope`)."""
    import jax

    directory = tempfile.mkdtemp(prefix="benchmark-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        path = trace.find(directory)
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, keep)
        into.update(trace.reduce(*trace.load(path)))
        into["scope_s"] = seconds_by_scope(path)
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def seconds(summary, name):
    """Device seconds under scope ``name`` in the traced slice; ``None``
    where the trace holds no such reading."""
    scope_s = (summary.get("trace") or {}).get("scope_s") or {}
    return scope_s.get(name)
