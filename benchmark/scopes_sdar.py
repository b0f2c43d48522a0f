"""Device seconds by the ``jax.named_scope``s of the block-diffusion
decoder (full grouped-query layers, routed experts on every layer) and of
the engine's in-graph sampler, from a traced run's ``.xplane.pb``:
``benchmark/scopes_granite.py``'s reading for another set of names (its
``SCOPES`` is a fixed tuple, and the file an accepted part of the
yardstick), kept twice as there: over every program, and over the pass
program alone (``jit(_decode_impl)``: a block model's pass keeps the
decode program's name).

``unmask`` is ``serve/kv_cache.py``'s scope round a pass's sampler: the
float32 argmax and softmax probability over the (slots x block, vocab)
logits, the choice of the surest masked positions and the new feed.
``full_attention`` (a prompt's block-causal flash kernel and its rows'
write into the cache; a pass's write of the block's columns and its read
of the live tiles), ``moe`` and ``head`` are as ``scopes_kexaone.py`` has
them. The rules of the reading are ``scopes_xing.py``'s: the scope an
operation ran in is the ``tf_op`` statistic on the event's metadata, a
fusion carries one member's path, an operation that only holds others is
left out, a grouped product (which carries no path) stands for ``moe``,
and where the paths cannot be read (no ``xplane_pb2`` module, no device
plane: a CPU rehearsal) every reader leaves its metric out.
"""


from __future__ import annotations

import contextlib
import os
import re
import shutil
import tempfile

from benchmark import scopes, scopes_xing, trace
from benchmark.tools import idle_causes

SCOPES = ("unmask", "full_attention", "moe", "head")
_SCOPE = re.compile(r"(?<=/)(%s)(?=/)" % "|".join(SCOPES))


def scope_of(texts, name=""):
    """The innermost of :data:`SCOPES` in any of an operation's paths;
    ``moe`` for a grouped product, which has no path (``name``: the
    event's name); ``other`` where none names one."""
    found = [m for text in texts for m in _SCOPE.finditer(text)]
    if found:
        return max(found, key=lambda m: m.start()).group(1)
    return "moe" if scopes_xing._RAGGED_DOT.match(name) else "other"


def seconds_by_scope(path):
    """({scope: seconds}, {scope: seconds in the decode program}) over the
    first device's operations in the trace file ``path``; ``({}, {})``
    where the paths cannot be read."""
    pb2 = idle_causes.xplane_pb2()
    if pb2 is None:
        return {}, {}
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = sorted((p for p in space.planes
                     if p.name.startswith(trace.DEVICE_PREFIX)),
                    key=lambda p: p.name)[:1]
    out, decode = {}, {}
    for plane in planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        scope = {}       # event metadata id -> (scope, in the decode program)
        for key, meta in plane.event_metadata.items():
            texts = [s.str_value if s.WhichOneof("value") == "str_value"
                     else names.get(s.ref_value, "")
                     for s in meta.stats
                     if s.WhichOneof("value") in ("str_value", "ref_value")]
            scope[key] = None if scopes._HOLDER.search(meta.name) else (
                scope_of(texts, meta.name),
                any(t.startswith(scopes_xing.DECODE) for t in texts))
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                for e in line.events:
                    kind = scope.get(e.metadata_id, ("other", False))
                    if kind is None:
                        continue
                    took = e.duration_ps * 1e-12
                    out[kind[0]] = out.get(kind[0], 0.0) + took
                    if kind[1]:
                        decode[kind[0]] = decode.get(kind[0], 0.0) + took
    return out, decode


@contextlib.contextmanager
def traced(into):
    """``harness.traced`` with the seconds by scope kept too: the body
    runs under the JAX profiler and ``into`` gets ``trace.reduce``'s
    summary plus ``scope_s`` and ``decode_scope_s``
    (:func:`seconds_by_scope`)."""
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
        into["scope_s"], into["decode_scope_s"] = seconds_by_scope(path)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
