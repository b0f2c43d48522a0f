"""Hardware bandwidth probes seeding the autotuner.

The north star for the TPU rebuild keeps the reference's response-cache /
fusion-buffer / autotuner design "backed by TPU HBM and ICI bandwidth
probes" (BASELINE.json; the reference itself starts from a fixed 64 MB
threshold, reference: operations.cc:379). These probes measure the actual
machine once at startup and turn the measurement into a principled initial
fusion threshold: fuse at most what the interconnect can reduce within a
set fraction of one cycle, so the first autotune samples start near the
right region instead of at a hardware-blind constant.

Timing protocol: K iterations chained inside ONE jitted program (data
dependency between iterations), wall-clocked against a scalar readback
(converting the result to a Python float waits for the device), at two
chain lengths so that the constant per-launch cost cancels.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.core import mesh as mesh_mod

log = logging.getLogger("horovod_tpu")

HOROVOD_PROBE_CACHE = "HOROVOD_PROBE_CACHE"

# persisted roofline artifact schema (bumped on incompatible change;
# a mismatched schema simply re-probes). v2: the hierarchy's two socket
# hops are probed separately (``hier_intra_busbw_gbps`` /
# ``hier_cross_busbw_gbps``) — a v1 artifact knows nothing about the
# split, so reloading it would leave the new lanes unseeded while
# claiming a cache hit.
_CACHE_SCHEMA = 2


def _timed_scalar(fn, *args) -> float:
    """Wall-clock one compiled call ending in a scalar readback."""
    t0 = time.perf_counter()
    float(fn(*args))
    return time.perf_counter() - t0


def _per_iter_time(make_chain, x, lo: int, hi: int,
                   repeats: int = 3) -> float:
    """Difference-quotient timing: build chain(lo) and chain(hi), take
    min over repeats of each, return (t_hi - t_lo) / (hi - lo). Cancels
    the constant dispatch/readback overhead of one launch, which would
    otherwise dominate a short kernel.
    ``x`` stays a traced argument so XLA cannot constant-fold the chain.
    """
    hi = max(hi, lo + 1)
    c_lo, c_hi = make_chain(lo), make_chain(hi)
    _timed_scalar(c_lo, x)  # compile + warm
    _timed_scalar(c_hi, x)
    t_lo = min(_timed_scalar(c_lo, x) for _ in range(repeats))
    t_hi = min(_timed_scalar(c_hi, x) for _ in range(repeats))
    return max((t_hi - t_lo) / (hi - lo), 1e-9)


def probe_hbm_bandwidth(size_mb: int = 64, iters: int = 16) -> float:
    """Sustained single-device HBM copy bandwidth in GB/s (read + write).

    A chained scale-by-~one copy: each iteration reads and writes the
    buffer once, so bytes moved per iteration = 2 * size.
    """
    n = size_mb * (1 << 20) // 4
    x = jnp.ones((n,), jnp.float32)
    k = jnp.float32(1.0000001)

    def make_chain(length):
        @jax.jit
        def chain(v):
            def body(c, _):
                return c * k, None

            out, _ = jax.lax.scan(body, v, None, length=length)
            return out[0]

        return chain

    dt = _per_iter_time(make_chain, x, max(1, iters // 4), iters)
    return 2.0 * x.nbytes / dt / 1e9


def probe_allreduce_bandwidth(mesh=None, size_mb: int = 32,
                              iters: int = 8,
                              detail: bool = False) -> Union[float, dict]:
    """Algorithm bandwidth (input bytes / time) of a full-mesh all-reduce
    in GB/s — the ICI number that bounds fused-collective latency. On a
    1-device mesh this degenerates to an HBM-bound pass, which is the
    right bound there too.

    ``detail=True`` returns ``{"algbw_gbps", "busbw_gbps", "world"}`` —
    bus bandwidth (algbw x 2(N-1)/N, the comms-plane convention,
    docs/comms.md) plus the mesh size it was probed on, so a persisted
    roofline from a different world size can be invalidated."""
    from horovod_tpu.core import basics

    if mesh is None:
        mesh = basics._ensure_init().mesh
    n = size_mb * (1 << 20) // 4
    repl = NamedSharding(mesh, P())
    x = jax.device_put(jnp.ones((n,), jnp.float32), repl)
    inv = jnp.float32(1.0 / mesh.size)

    def make_chain(length):
        @jax.jit
        def chain(w):
            def inner(v):
                def step(c, _):
                    s = jax.lax.psum(c, mesh_mod.GLOBAL_AXES)
                    return s * inv, None

                out, _ = jax.lax.scan(step, v, None, length=length)
                return out

            y = jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False)(w)
            return y[0]

        return chain

    dt = _per_iter_time(make_chain, x, max(1, iters // 4), iters)
    algbw = x.nbytes / dt / 1e9
    if not detail:
        return algbw
    from horovod_tpu import comms

    world = int(mesh.size)
    return {"algbw_gbps": algbw,
            "busbw_gbps": algbw * comms.bus_factor("allreduce", world),
            "world": world}


def recommended_fusion_threshold(allreduce_gbps: float,
                                 cycle_time_ms: float,
                                 cycle_fraction: float = 0.5,
                                 floor_bytes: int = 1 << 20,
                                 ceil_bytes: int = 256 << 20,
                                 hbm_gbps: Optional[float] = None) -> int:
    """Fusion threshold such that reducing one full fused buffer takes at
    most ``cycle_fraction`` of a cycle at the probed bandwidth — big
    enough to amortize launch overhead, small enough that fused
    collectives don't starve the cycle cadence (the trade the reference's
    autotuner searches for blindly, reference: parameter_manager.h:225).

    The effective rate is capped by HBM when given: a fused collective
    also packs and unpacks the buffer through HBM (one read + one write
    each way), so the wire can never be fed faster than ``hbm/2``.
    """
    rate = allreduce_gbps
    if hbm_gbps is not None:
        rate = min(rate, hbm_gbps / 2.0)
    budget_s = cycle_time_ms * 1e-3 * cycle_fraction
    threshold = int(rate * 1e9 * budget_s)
    return max(floor_bytes, min(ceil_bytes, threshold))


def _cache_path() -> Optional[str]:
    path = os.environ.get(HOROVOD_PROBE_CACHE, "").strip()
    return path or None


def load_cached_roofline(path: Optional[str] = None,
                         world: Optional[int] = None) -> Optional[dict]:
    """Read the persisted probe artifact (``HOROVOD_PROBE_CACHE``).
    Returns None when the knob is unset, the file is missing/corrupt,
    the schema moved on, or — the invalidation this artifact exists to
    get right — it was probed on a different world size (busbw's ring
    factor is a function of N; a 4-chip roofline says nothing about a
    32-chip pod)."""
    path = path or _cache_path()
    if not path:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("schema") != _CACHE_SCHEMA:
        return None
    if world is not None and int(doc.get("world", -1)) != int(world):
        log.info("probe cache %s ignored: probed on world=%s, running "
                 "world=%d", path, doc.get("world"), world)
        return None
    return doc


def _persist_roofline(path: str, doc: dict) -> None:
    """fsync'd write of the roofline artifact (tmp + rename, directory
    fsync'd too — a crashed init must not leave a torn JSON that every
    later restart trips over)."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    dir_fd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
    try:
        os.fsync(dir_fd)
    finally:
        os.close(dir_fd)


def probe_and_seed(config, mesh=None) -> dict:
    """Run the probes and seed ``config.fusion_threshold_bytes``; returns
    the measurements. Called at runtime startup when
    ``HOROVOD_AUTOTUNE_PROBE`` is on. Must run on EVERY process in a
    multi-controller (jax.distributed) world — the probe programs execute
    over the global mesh, which all processes must enter together; the
    coordinator's seeded value then wins via the per-cycle parameter
    broadcast, so probe noise cannot diverge the workers.

    With ``HOROVOD_PROBE_CACHE=<path>`` the measurements are persisted as
    a JSON roofline artifact (fsync'd) and reloaded on restart instead of
    re-probing every ``hvd.init()`` — a cached artifact from a different
    world size is invalidated (the busbw ring factor depends on N). The
    same artifact seeds the comms plane's lane rooflines
    (comms.configure / docs/comms.md)."""
    from horovod_tpu import comms

    if mesh is None:
        from horovod_tpu.core import basics

        mesh = basics._ensure_init().mesh
    world = int(mesh.size)
    cached = load_cached_roofline(world=world)
    if cached is not None and "hbm_gbps" not in cached:
        # a hier-hop-only artifact (host-ring probe wrote this path):
        # says nothing about the mesh lanes — probe them live
        cached = None
    if cached is not None:
        measured = {
            "hbm_gbps": float(cached["hbm_gbps"]),
            "allreduce_gbps": float(cached["allreduce_gbps"]),
            "allreduce_busbw_gbps": float(
                cached.get("allreduce_busbw_gbps", 0.0)),
            "world": world,
            "cached": True,
        }
        log.info("probe cache hit (%s): HBM %.1f GB/s, allreduce %.1f "
                 "GB/s algbw / %.1f GB/s busbw (world=%d) — probes "
                 "skipped", _cache_path(), measured["hbm_gbps"],
                 measured["allreduce_gbps"],
                 measured["allreduce_busbw_gbps"], world)
    else:
        hbm = probe_hbm_bandwidth()
        ar = probe_allreduce_bandwidth(mesh, detail=True)
        if not isinstance(ar, dict):  # a monkeypatched/legacy float
            ar = {"algbw_gbps": float(ar),
                  "busbw_gbps": float(ar)
                  * comms.bus_factor("allreduce", world),
                  "world": world}
        measured = {
            "hbm_gbps": hbm,
            "allreduce_gbps": ar["algbw_gbps"],
            "allreduce_busbw_gbps": ar["busbw_gbps"],
            "world": world,
            "cached": False,
        }
    threshold = recommended_fusion_threshold(
        measured["allreduce_gbps"], config.cycle_time_ms,
        hbm_gbps=measured["hbm_gbps"])
    config.fusion_threshold_bytes = threshold
    measured["fusion_threshold_bytes"] = threshold
    path = _cache_path()
    if path and not measured["cached"]:
        try:
            _persist_roofline(path, {
                "schema": _CACHE_SCHEMA,
                "hbm_gbps": measured["hbm_gbps"],
                "allreduce_gbps": measured["allreduce_gbps"],
                "allreduce_busbw_gbps": measured["allreduce_busbw_gbps"],
                "world": world,
                "fusion_threshold_bytes": threshold,
                "wall_time": time.time(),
            })
        except OSError as exc:
            log.warning("probe cache not persisted to %s: %s", path, exc)
    # seed the comms plane's XLA-lane rooflines from the live (or cached)
    # measurement — the probe runs after comms.configure, so a first-boot
    # probe (no artifact yet) still pins the roofline this run
    if measured["allreduce_busbw_gbps"] > 0:
        source = "probe_cache" if measured["cached"] else "probe"
        for lane in ("device", "spmd"):
            comms.tracker().seed_roofline(
                lane, measured["allreduce_busbw_gbps"], source=source)
    return measured


# -- host-hierarchy hop probes (socket data plane) ----------------------------

def probe_hier_hops(net, plan, size_mb: int = 4,
                    iters: int = 6) -> dict:
    """Probe the two hops of the socket hierarchy SEPARATELY: a timed
    subgroup ring allreduce inside each group (``hier_intra``) and one
    over each cross-group slot ring (``hier_cross``). The two lanes can
    differ by an order of magnitude (intra-host loopback vs a throttled
    DCN hop), so one blended number would mis-bound both.

    Collective: every rank of the plan must call this at the same
    execution point. The intra rings (one per group) and the cross rings
    (one per slot) are each disjoint over ranks, so all ranks probe both
    hops concurrently. Returns busbw GB/s per hop.
    """
    from horovod_tpu import comms
    from horovod_tpu.runtime import hierarchy

    n = max(1, size_mb * (1 << 20) // 4)
    buf = np.ones((n,), np.float32)

    def timed(ring, pos) -> float:
        # "max" keeps values fixed across iterations (an iterated "sum"
        # would overflow); 2 warmup rounds double as a ring barrier so
        # the timed window starts aligned
        for _ in range(2):
            hierarchy._ring_allreduce(net, ring, pos, buf, "max")
        t0 = time.perf_counter()
        for _ in range(iters):
            hierarchy._ring_allreduce(net, ring, pos, buf, "max")
        dt = (time.perf_counter() - t0) / iters
        algbw = buf.nbytes / dt / 1e9
        return algbw * comms.bus_factor("allreduce", len(ring))

    intra = timed(plan.members, plan.local_index)
    cross = timed(plan.cross_members, plan.group_index)
    return {"hier_intra_busbw_gbps": intra,
            "hier_cross_busbw_gbps": cross}


def probe_host_hier_and_seed(net, config) -> Optional[dict]:
    """Host-ring analogue of :func:`probe_and_seed` for the hierarchy
    lanes: reuse a matching schema-2 artifact when present, otherwise
    probe both hops over the live sockets, persist (rank 0 only — the
    write is atomic but there is no reason for N ranks to race on one
    path), and seed the ``hier_intra``/``hier_cross`` comms rooflines.
    Returns None when the world cannot form a hierarchy (the flat ring
    keeps its self-calibrating peak-observed roofline). Collective:
    every rank must call this at the same execution point."""
    from horovod_tpu import comms
    from horovod_tpu.runtime import hierarchy

    plan = hierarchy.build_plan(
        net, getattr(config, "hierarchy_group_size", 0))
    if not plan.enabled:
        return None
    cached = load_cached_roofline(world=net.world)
    if cached is not None and cached.get("hier_intra_busbw_gbps") \
            and cached.get("hier_cross_busbw_gbps"):
        measured = {
            "hier_intra_busbw_gbps": float(
                cached["hier_intra_busbw_gbps"]),
            "hier_cross_busbw_gbps": float(
                cached["hier_cross_busbw_gbps"]),
            "cached": True,
        }
    else:
        measured = probe_hier_hops(net, plan)
        measured["cached"] = False
        path = _cache_path()
        if path and net.rank == 0:
            try:
                _persist_roofline(path, {
                    "schema": _CACHE_SCHEMA,
                    "hier_intra_busbw_gbps":
                        measured["hier_intra_busbw_gbps"],
                    "hier_cross_busbw_gbps":
                        measured["hier_cross_busbw_gbps"],
                    "world": net.world,
                    "wall_time": time.time(),
                })
            except OSError as exc:
                log.warning("probe cache not persisted to %s: %s",
                            path, exc)
    source = "probe_cache" if measured["cached"] else "probe"
    comms.tracker().seed_roofline(
        "hier_intra", measured["hier_intra_busbw_gbps"], source=source)
    comms.tracker().seed_roofline(
        "hier_cross", measured["hier_cross_busbw_gbps"], source=source)
    return measured
