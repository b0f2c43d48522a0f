"""Lifecycle + topology API: init / shutdown / rank / size / ...

TPU-native analogue of the reference's C lifecycle API and ctypes wrapper
(reference: horovod/common/operations.cc:611-732, horovod/common/basics.py).

Worker model
------------
The reference runs one process per accelerator; ``rank``/``size`` are MPI
ranks. JAX is a single-controller SPMD system: one process typically drives
many devices, and on a pod each host runs one process. We therefore define
**worker == device (TPU chip)**:

* ``size()``       — total number of devices in the global mesh.
* ``local_size()`` — extent of the ``local`` (ICI) mesh axis.
* ``cross_size()`` — extent of the ``cross`` (DCN) mesh axis.
* ``rank()``       — flat index of the first device owned by this process
                     (0 in single-process mode). With one process per chip —
                     the reference's launch topology — this is exactly the
                     MPI rank.
* ``local_rank()`` / ``cross_rank()`` — ``rank`` split along the mesh axes.

User conventions from the reference carry over unchanged: scale the learning
rate by ``size()``, checkpoint when ``rank() == 0``.
"""

from __future__ import annotations

import atexit
import os
from typing import Optional, Sequence

import jax

from horovod_tpu import flight_recorder
from horovod_tpu.core import mesh as mesh_mod
from horovod_tpu.core import state as state_mod
from horovod_tpu.utils import logging as log
from horovod_tpu.utils.env import Config


class NotInitializedError(RuntimeError):
    def __init__(self) -> None:
        # reference error text: horovod/common/operations.cc NOT_INITIALIZED
        super().__init__(
            "horovod_tpu has not been initialized; use hvd.init()."
        )


def _ensure_init() -> state_mod.GlobalState:
    st = state_mod.global_state()
    if not st.initialized:
        raise NotInitializedError()
    return st


def init(
    comm=None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[tuple[int, int]] = None,
) -> None:
    """Initialize the framework: build the device mesh, parse config knobs,
    and start background subsystems.

    Mirrors ``horovod_init`` → ``InitializeHorovodOnce`` (reference:
    horovod/common/operations.cc:554-600). ``comm`` is accepted for API
    compatibility and ignored (there is no MPI communicator on TPU; process
    membership comes from ``jax.distributed``).

    Multi-process (multi-host) initialization: if ``HOROVOD_COORDINATOR_ADDR``
    is set (by the ``tpurun`` launcher), ``jax.distributed.initialize`` is
    called first so all processes join one global device mesh.
    """
    st = state_mod.global_state()
    with st.lock:
        if st.initialized:
            return

        # NOTE: must not touch any jax API that initializes the local
        # backend (jax.devices / jax.process_count) before
        # jax.distributed.initialize — the guard reads env vars only.
        coordinator = os.environ.get("HOROVOD_COORDINATOR_ADDR")
        num_processes = int(os.environ.get("HOROVOD_NUM_PROCESSES", "1"))
        if coordinator and num_processes > 1 and not _jax_dist_initialized():
            process_id = int(os.environ.get("HOROVOD_PROCESS_ID", "0"))
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )

        st.config = Config.from_env()
        if devices is None and "HOROVOD_RANK" in os.environ \
                and jax.process_count() > 1:
            devices = _devices_in_launcher_order()
        st.mesh = mesh_mod.build_mesh(devices=devices, mesh_shape=mesh_shape)

        cross, local = st.mesh.devices.shape
        st.size = cross * local
        st.local_size = local
        st.cross_size = cross

        # rank = flat index of the first device this process owns.
        flat = list(st.mesh.devices.flatten())
        proc = jax.process_index()
        st.rank = next(
            (i for i, d in enumerate(flat) if d.process_index == proc), 0
        )
        st.local_rank = st.rank % local
        st.cross_rank = st.rank // local

        # Socket (host data plane) mode: the launcher's env contract defines
        # the world — worker == process, exactly the reference's MPI-rank
        # semantics (reference: gloo_context.cc:128-133 reads
        # HOROVOD_RANK/SIZE/... set by gloo_run). Without this, rank()/size()
        # would report only the process-local mesh.
        env_size = int(os.environ.get("HOROVOD_SIZE", "1"))
        if env_size > 1 and jax.process_count() == 1:
            st.size = env_size
            st.rank = int(os.environ.get("HOROVOD_RANK", "0"))
            st.local_size = int(
                os.environ.get("HOROVOD_LOCAL_SIZE", str(env_size)))
            st.local_rank = int(
                os.environ.get("HOROVOD_LOCAL_RANK", str(st.rank)))
            st.cross_size = int(os.environ.get(
                "HOROVOD_CROSS_SIZE",
                str(max(1, env_size // max(st.local_size, 1)))))
            st.cross_rank = int(os.environ.get(
                "HOROVOD_CROSS_RANK", str(st.rank // max(st.local_size, 1))))

        st.initialized = True
        st.shut_down = False
        log.debug(
            "initialized: size=%d local=%d cross=%d rank=%d",
            st.size, st.local_size, st.cross_size, st.rank,
        )

        # flight recorder: adopt the (possibly re-formed) rank, hook fatal
        # signals so a SIGTERM/SIGSEGV leaves a postmortem dump
        flight_recorder.configure(rank=st.rank)
        flight_recorder.install_signal_handlers()
        flight_recorder.emit("init", rank=st.rank, size=st.size)

        # step profiler: adopt the rank and register its flight-recorder
        # state provider (HOROVOD_PROFILE / HOROVOD_PROFILE_DIR)
        from horovod_tpu import profiler

        profiler.configure(rank=st.rank)

        # memory plane: adopt the rank, register the flight-recorder
        # "memory" state provider, start the reconciliation sampler
        # (HOROVOD_MEMORY / HOROVOD_MEMORY_SAMPLE_SECONDS)
        from horovod_tpu import memory

        memory.configure(rank=st.rank)

        # tracing + SLO plane: adopt the rank, register the "slo" state
        # provider, flip the /healthz readiness gate (HOROVOD_TRACE /
        # HOROVOD_SLO_*)
        from horovod_tpu import tracing

        tracing.configure(rank=st.rank)

        # collective transport observatory: adopt rank/world, seed lane
        # rooflines from the persisted probe artifact, register the
        # "comms" state provider (HOROVOD_COMMS_* / HOROVOD_PROBE_CACHE)
        from horovod_tpu import comms

        comms.configure(rank=st.rank, world=st.size)

        # goodput ledger: adopt rank/world, pin the wall-clock epoch
        # (first init only — elastic re-inits keep the original clock),
        # register the "goodput" state provider (HOROVOD_GOODPUT_*)
        from horovod_tpu import goodput

        goodput.configure(rank=st.rank, world=st.size)

        if st.config.timeline_file:
            from horovod_tpu.timeline import Timeline

            st.timeline = Timeline(st.config.timeline_file,
                                   mark_cycles=st.config.timeline_mark_cycles)

        # Prometheus exposition endpoint (HOROVOD_METRICS_PORT): when the
        # knob is unset, no thread or socket exists — the metrics hot path
        # stays a plain dict/int update per event.
        if st.config.metrics_port is not None:
            from horovod_tpu.metrics import registry as metrics_registry

            port = metrics_registry().serve(st.config.metrics_port)
            log.debug("metrics endpoint serving on port %d", port)


def _devices_in_launcher_order():
    """The global devices, ordered by the launcher rank of the process
    that owns them.

    ``jax.devices()`` orders by where the backend finds each process: on
    TPU the runtime numbers processes by the position of their chips in
    the topology, whatever ``process_id`` the launcher asked for (on a
    v5e 2x2 host launcher slot 3 came up as process 0). The host data
    plane numbers ranks by ``HOROVOD_RANK``. Collectives that name a rank
    (a broadcast root, the order of an allgather) need the two planes to
    agree, and the launcher's numbering is the contract — so every
    process publishes its ``HOROVOD_RANK`` in the coordination service
    and the mesh is laid out in that order."""
    from horovod_tpu.runtime.coordination import _kv_client

    client = _kv_client()
    key = "horovod_tpu/launcher_rank/{}".format
    # allow_overwrite: an elastic re-init publishes the same key again
    client.key_value_set(key(jax.process_index()),
                         os.environ["HOROVOD_RANK"], allow_overwrite=True)
    launcher_rank = {
        p: int(client.blocking_key_value_get(key(p), 60_000))
        for p in range(jax.process_count())}
    return sorted(jax.devices(),
                  key=lambda d: (launcher_rank[d.process_index], d.id))


def _jax_dist_initialized() -> bool:
    try:
        from jax._src import distributed

        return distributed.global_state.client is not None
    except Exception:
        return False


def shutdown() -> None:
    """Tear down background subsystems and reset state.

    Mirrors ``horovod_shutdown`` (reference: horovod/common/operations.cc):
    in-flight enqueued tensors receive a shut-down error through their
    callbacks before the state is reset.
    """
    st = state_mod.global_state()
    with st.lock:
        if not st.initialized:
            return
        st.shut_down = True
        if st.runtime is not None:
            st.runtime.stop()
        if st.timeline is not None:
            st.timeline.close()
        from horovod_tpu.metrics import registry as metrics_registry

        reg = metrics_registry()
        reg.stop_server()
        if st.config.metrics_dump:
            try:
                reg.dump(st.config.metrics_dump, rank=st.rank)
            except OSError as exc:
                log.warning("could not write metrics dump: %s", exc)
        from horovod_tpu.ops import collectives

        collectives.clear_compiled_cache()
        # step profiler: close any implicit step, dump + ship the profile
        # (no-op unless HOROVOD_PROFILE / HOROVOD_PROFILE_DIR enabled it)
        from horovod_tpu import profiler

        profiler.finalize()
        # memory plane: stop the sampler so it doesn't outlive the state
        # it reconciles (re-init restarts it with the new rank)
        from horovod_tpu import memory

        memory.tracker().stop()
        # /healthz must stop reporting ready the moment the runtime is
        # gone — a load balancer probing a shut-down worker gets 503
        from horovod_tpu import tracing

        tracing.mark_initialized(False)
        flight_recorder.emit("shutdown", rank=st.rank)
        # leave a final dump behind (and ship it to the launcher) so the
        # postmortem covers clean exits too — only when a destination is
        # configured; a bare single-process run writes nothing
        if flight_recorder.recorder().enabled and (
                flight_recorder.recorder().dir
                or flight_recorder._rendezvous_addr() is not None):
            flight_recorder.recorder().dump("shutdown")
    state_mod.reset()


def reinit(
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[tuple[int, int]] = None,
) -> None:
    """Tear down and re-initialize against the CURRENT environment.

    The elastic runner calls this after re-forming membership: by then
    ``HOROVOD_RANK``/``HOROVOD_SIZE``/rendezvous knobs describe the new
    generation, and ``init()`` rebuilds the mesh, config, and topology from
    them. A plain ``init()`` call would be a no-op (``st.initialized``
    short-circuits), hence the explicit shutdown-first entry point.
    """
    shutdown()
    init(devices=devices, mesh_shape=mesh_shape)


atexit.register(shutdown)  # reference: horovod/common/basics.py:40


def is_initialized() -> bool:
    return state_mod.global_state().initialized


def rank() -> int:
    return _ensure_init().rank


def size() -> int:
    return _ensure_init().size


def local_rank() -> int:
    return _ensure_init().local_rank


def local_size() -> int:
    return _ensure_init().local_size


def cross_rank() -> int:
    return _ensure_init().cross_rank


def cross_size() -> int:
    return _ensure_init().cross_size


def mesh():
    """The global (cross, local) device mesh."""
    return _ensure_init().mesh


def metrics() -> dict:
    """Snapshot of the process-wide runtime metrics registry as a nested
    JSON-serializable dict: cycle timing, queue depth, cache hit/miss
    counts, fusion bytes/utilization, per-op collective latency and bytes,
    stall and timeline health counters (see docs/metrics.md).

    Works before ``init()`` too — the registry is process-global — but
    counters only move once the runtime is running."""
    from horovod_tpu.metrics import registry as metrics_registry

    return metrics_registry().snapshot()


def is_homogeneous() -> bool:
    """True when every process owns the same number of devices
    (reference: mpi_controller.cc:25-81 homogeneity check)."""
    st = _ensure_init()
    counts: dict[int, int] = {}
    for d in st.mesh.devices.flatten():
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1


# Capability probes, mirroring horovod_*_built/enabled
# (reference: horovod/common/operations.cc:640-732). The TPU build has no
# MPI/NCCL/Gloo; its transports are XLA collectives over ICI/DCN.
def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def mlsl_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False
