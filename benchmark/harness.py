"""What every runner needs from the harness: the device gate, the
compile cache and its counters, the profiler slice, the run's context.

Nothing here knows a configuration, a cell or a metric by name.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CACHE_BYTES = 2 << 30


def say(msg):
    print(msg, flush=True)


def configure_cache():
    """JAX's persistent compilation cache: the operator's
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``
    (a fixed path: the path is part of the cache's key). Every program
    is kept, however quick its compile, and none is evicted: a cap on the
    directory (the chip machines set ``JAX_COMPILATION_CACHE_MAX_SIZE`` to
    192 MiB) is lifted to ``CACHE_BYTES``, because a cell's programs
    together pass it and then evict one another, so that every run
    compiles (PERF.md section 6, PR 24)."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    if 0 <= jax.config.jax_compilation_cache_max_size < CACHE_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Programs built or fetched (``compiles``), and the persistent
    cache's hits and misses, from JAX's own monitoring events."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    CACHE = "/jax/compilation_cache/cache_"

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event.startswith(self.CACHE):
            self.counts[event[len(self.CACHE):]] += 1

    def _duration(self, event, duration, **_):
        if event == self.BUILD:
            self.counts["compiles"] += 1

    @property
    def compiles(self):
        return self.counts["compiles"]


def require_devices(chips, rehearse):
    """The first ``chips`` devices as JAX reports them. No TPU (unless
    rehearsing), or fewer devices than the cell asks for, ends the run
    with no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        raise SystemExit(
            f"benchmark: JAX found no accelerator (jax.devices() = "
            f"{devices}); a cell is measured on the chip or not at all")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"JAX reports {len(devices)}: {devices}")
    return devices[:chips]


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest device: ``peak_bytes_in_use`` (live
    buffers: state, batches) plus ``peak_bytes_reserved`` (what the
    runtime sets aside for the running programs' temporaries; on the v5e
    runtime ``peak_bytes_in_use`` leaves those out - PERF.md section 7).
    0 where the backend reports nothing (the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def percentile(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


@contextlib.contextmanager
def traced(into):
    """Run the body under the JAX profiler and leave the reduced trace
    in ``into`` (a dict): see ``trace.reduce``. The trace files go to a
    directory under ``TMPDIR`` and are removed."""
    import jax

    from benchmark import trace

    directory = tempfile.mkdtemp(prefix="benchmark-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        path = trace.find(directory)
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, keep)
        into.update(trace.reduce(*trace.load(path)))
    finally:
        shutil.rmtree(directory, ignore_errors=True)


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit."""

    name: str
    value: float
    limit: float
    ok: bool

    def line(self):
        return (f"check {self.name}: {self.value!r} limit {self.limit!r} "
                f"{'ok' if self.ok else 'FAILED'}")


def at_most(name, value, limit):
    return Check(name, float(value), float(limit),
                 bool(value == value and value <= limit))


def at_least(name, value, limit):
    return Check(name, float(value), float(limit),
                 bool(value == value and value >= limit))


@dataclasses.dataclass
class Context:
    """One run of one cell, as the runner sees it."""

    config: dict          # the configuration as run (``as_run``)
    published: dict       # the whole configuration file
    mix: dict             # the traffic mix's parameters
    limits: dict          # the cell's limits for ``correct``
    seed: int
    seconds: float
    trace: bool
    devices: list
    started: float        # perf_counter at process start
    compiles: CompileCounter


def load_cell(name, rehearse):
    """One cell of BENCHMARK.json: its entry, its configuration file and
    the sizes as run (the file's ``as_run`` group, under its ``rehearse``
    group's toy sizes when rehearsing), its traffic mix and its limits."""
    from benchmark import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        published = json.load(f)
    config = dict(published["as_run"])
    if rehearse:
        config.update(published.get("rehearse", {}))
    with open(os.path.join(HERE, "limits", name + ".json")) as f:
        limits = json.load(f)
    return spec, cell, published, config, \
        traffic.load(cell["traffic"], rehearse), limits


def transformer(cfg):
    """The program's model at the configuration's sizes as run."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer

    return Transformer(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        d_ff=cfg["d_ff"], max_seq=cfg["max_seq"], causal=cfg["causal"],
        dtype=jnp.dtype(cfg["dtype"]))


def now():
    return time.perf_counter()
