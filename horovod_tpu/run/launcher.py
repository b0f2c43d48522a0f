"""Process fan-out: run one worker per slot, locally or over ssh.

TPU-native port of the reference's gloo launcher (reference:
horovod/run/gloo_run.py:211-301): for every allocated slot, build the
worker env (slot contract + rendezvous + knobs), spawn the command —
``exec`` locally, ``ssh`` for remote hosts — stream tag-prefixed output
(optionally also captured to ``<output_dir>/rank.N/``), and terminate the
whole job when any worker exits non-zero (gloo_run.py:256-262) or the
launcher receives SIGINT/SIGTERM.

On top of the reference contract the launcher also wires up
``jax.distributed`` (HOROVOD_COORDINATOR_ADDR / NUM_PROCESSES /
PROCESS_ID) so every process joins one global TPU mesh — the TPU-native
equivalent of NCCL communicator bootstrap.
"""

from __future__ import annotations

import os
import signal
import socket
import sys
import threading
from typing import Dict, List, Optional

from horovod_tpu import flight_recorder
from horovod_tpu.run import util
from horovod_tpu.run.hosts import SlotInfo
from horovod_tpu.run.rendezvous import RendezvousServer

LOCAL_HOSTNAMES = {"localhost", "127.0.0.1", "::1"}


def is_local_host(hostname: str) -> bool:
    if hostname in LOCAL_HOSTNAMES:
        return True
    try:
        return hostname in (socket.gethostname(), socket.getfqdn())
    except OSError:
        return False


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("0.0.0.0", 0))
        return s.getsockname()[1]


def _announce_net_chaos() -> None:
    """Log the armed network-fault clauses (if any) once at launch — a
    chaos run whose faults silently fail to parse tests nothing."""
    from horovod_tpu.utils import resilience

    spec = os.environ.get("HOROVOD_FAULT_INJECT", "")
    if not spec:
        return
    try:
        faults = resilience.parse_net_faults(spec)
    except ValueError as exc:
        print(f"tpurun: ignoring malformed HOROVOD_FAULT_INJECT net "
              f"clause: {exc}", file=sys.stderr)
        return
    if faults:
        print("tpurun: network chaos armed: "
              + "; ".join(str(f) for f in faults), file=sys.stderr)


def get_driver_ip(slots: List[SlotInfo]) -> str:
    """Address remote workers use to reach the launcher host."""
    if all(is_local_host(s.hostname) for s in slots):
        return "127.0.0.1"
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
    except OSError:
        return socket.gethostname()


class SlotLayoutError(ValueError):
    """The requested slots cannot each be given a TPU chip of their own."""


# TPU_PROCESS_BOUNDS for N one-chip processes sharing one host: the table
# of JAX's own multi-process launcher (jax/_src/test_multiprocess.py).
# 4 is the v5e 2x2 host that ``chip_smoke.py --chips 4`` runs; 8 has not
# been run from here.
_ONE_CHIP_PROCESS_BOUNDS = {4: "2,2,1", 8: "4,2,1"}


def local_tpu_chips(env: Dict[str, str]) -> int:
    """TPU chips attached to this host, counted from sysfs as JAX itself
    counts them — no backend is initialised, so the launcher never holds
    a chip. 0 when ``JAX_PLATFORMS`` keeps the workers off the TPU."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def tpu_chip_envs(slots: List[SlotInfo], chips: int,
                  use_jax_distributed: bool) -> List[Dict[str, str]]:
    """Per-slot ``TPU_*`` environment that gives every local slot a chip
    of its own: a chip belongs to one process, and without this every
    worker on a multi-chip host opens every chip and all but one hang.

    One slot per host needs nothing (that process drives all its host's
    chips). Several slots on this host get chip ``local_rank`` each:
    joined into one global mesh under ``jax.distributed``, or as
    isolated one-chip worlds for the socket controller. Raises
    :class:`SlotLayoutError` for a layout it cannot serve: refusing here
    is loud, where every worker opening every chip would hang."""
    n = max(s.local_size for s in slots)
    if chips == 0 or n == 1:
        return [{} for _ in slots]
    if not all(is_local_host(s.hostname) for s in slots):
        raise SlotLayoutError(
            f"{n} slots per host across several hosts is not supported "
            f"on TPU: give each host one slot (one process drives all of "
            f"a host's chips)")
    if n > chips:
        raise SlotLayoutError(
            f"{n} local slots but this host has {chips} TPU chip(s): a "
            f"chip belongs to one process")
    common = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
              "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    if not use_jax_distributed:
        return [dict(common, TPU_PROCESS_BOUNDS="1,1,1",
                     TPU_VISIBLE_CHIPS=str(s.local_rank)) for s in slots]
    if n not in _ONE_CHIP_PROCESS_BOUNDS:
        raise SlotLayoutError(
            f"{n} one-chip processes on one TPU host: supported counts "
            f"are {sorted(_ONE_CHIP_PROCESS_BOUNDS)} (or one slot, "
            f"driving every chip)")
    ports = [_free_port() for _ in slots]
    addresses = ",".join(f"localhost:{port}" for port in ports)
    return [dict(common,
                 TPU_PROCESS_BOUNDS=_ONE_CHIP_PROCESS_BOUNDS[n],
                 TPU_PROCESS_ADDRESSES=addresses,
                 TPU_PROCESS_PORT=str(ports[s.local_rank]),
                 TPU_VISIBLE_CHIPS=str(s.local_rank),
                 CLOUD_TPU_TASK_ID=str(s.local_rank)) for s in slots]


def build_worker_env(slot: SlotInfo, base_env: Dict[str, str],
                     driver_ip: str, socket_port: int, http_port: int,
                     coordinator_port: int, num_processes: int,
                     use_jax_distributed: bool = True) -> Dict[str, str]:
    """Full worker environment: launcher contract (reference:
    gloo_run.py:211-240) + jax.distributed bootstrap.

    Two rendezvous channels: the native socket controller's coordinator
    (rank 0 binds ``socket_port``; others dial it — the analogue of the
    gloo TCP context) and the launcher's HTTP KV store on ``http_port``
    (the analogue of the reference's rendezvous server)."""
    env = dict(base_env)
    env.update(slot.to_env())
    # per-rank file templating (the metrics dump supports the same
    # placeholder): one launcher-side setting fans out to rank-unique
    # paths — used by --profile-dir for timeline-rank-N.json
    if "{rank}" in env.get("HOROVOD_TIMELINE", ""):
        env["HOROVOD_TIMELINE"] = env["HOROVOD_TIMELINE"].format(
            rank=slot.rank)
    env.update({
        "HOROVOD_CONTROLLER": env.get("HOROVOD_CONTROLLER", "socket"),
        "HOROVOD_CPU_OPERATIONS": env.get("HOROVOD_CPU_OPERATIONS", "socket"),
        "HOROVOD_GLOO_RENDEZVOUS_ADDR": driver_ip,
        "HOROVOD_GLOO_RENDEZVOUS_PORT": str(socket_port),
        "HOROVOD_RENDEZVOUS_HTTP_ADDR": driver_ip,
        "HOROVOD_RENDEZVOUS_HTTP_PORT": str(http_port),
    })
    if use_jax_distributed:
        env.update({
            "HOROVOD_COORDINATOR_ADDR": f"{driver_ip}:{coordinator_port}",
            "HOROVOD_NUM_PROCESSES": str(num_processes),
            "HOROVOD_PROCESS_ID": str(slot.rank),
        })
    return env


def launch_job(command: str, slots: List[SlotInfo],
               env: Optional[Dict[str, str]] = None,
               ssh_port: Optional[int] = None,
               output_dir: Optional[str] = None,
               use_jax_distributed: bool = True,
               prefix_output: bool = True,
               start_timeout: float = 300.0,
               backend=None,
               elastic: bool = False,
               min_workers: int = 1,
               max_workers: Optional[int] = None,
               discovery_script: Optional[str] = None,
               flight_recorder_dir: Optional[str] = None,
               profile_dir: Optional[str] = None) -> int:
    """Run ``command`` on every slot; returns the job exit code (first
    non-zero worker code, else 0). Starts the rendezvous KV server for the
    job's lifetime. ``backend`` is a :class:`run.backends.LaunchBackend`
    (default: ssh/local — the seam the reference's gloo-vs-mpirun choice
    occupies, run/run.py:715-732).

    ``elastic`` flips the failure policy (reference: elastic gloo_run vs
    plain gloo_run): a non-zero worker exit no longer tears the job down;
    survivors re-form on their own and the job fails only when fewer than
    ``min_workers`` workers remain. With a ``discovery_script`` an
    :class:`~horovod_tpu.elastic.driver.ElasticDriver` polls it and
    publishes host-change notices + heartbeat evictions through the
    rendezvous store.

    ``flight_recorder_dir`` closes the observability loop: workers write
    (and ship, via the rendezvous store) per-rank flight-recorder dumps;
    the launcher collects the shipped copies for workers whose local
    filesystem died with them and, when the job fails, prints a merged
    cross-rank postmortem naming the suspected culprit rank.

    ``profile_dir`` turns on the step profiler on every worker
    (``HOROVOD_PROFILE_DIR`` — per-rank timelines land in the same
    directory); after the job the launcher harvests shipped profile
    dumps, merges every rank's runtime timeline + step markers (+ any
    jax.profiler device traces) onto one clock-corrected Chrome trace,
    and prints the cross-rank step-time report naming the slowest phase
    and rank."""
    from horovod_tpu.run.backends import make_backend

    base_env = dict(os.environ if env is None else env)
    if flight_recorder_dir:
        base_env["HOROVOD_FLIGHT_RECORDER_DIR"] = flight_recorder_dir
    if profile_dir:
        base_env["HOROVOD_PROFILE_DIR"] = profile_dir
        # each rank's runtime Chrome trace feeds the merged view; an
        # explicit HOROVOD_TIMELINE (single shared path — wrong for
        # multi-rank anyway) is overridden by the per-rank template
        base_env["HOROVOD_TIMELINE"] = os.path.join(
            profile_dir, "timeline-rank-{rank}.json")
        try:
            os.makedirs(profile_dir, exist_ok=True)
        except OSError as exc:
            print(f"tpurun: cannot create profile dir {profile_dir!r}: "
                  f"{exc}", file=sys.stderr)
    if backend is None:
        # resolve from the CALLER's env mapping (like the NIC-discovery
        # knob below), so programmatic callers control the backend the
        # same way tpurun's CLI does
        backend = make_backend(ssh_port=ssh_port, env=base_env)
    driver_ip = get_driver_ip(slots)

    # NIC discovery (reference: run/run.py:195-265): on multi-NIC hosts
    # the heuristic driver_ip may not be the address workers can route
    # to — run the ring probe and use the proven address. Default: on
    # whenever a remote host is involved; HOROVOD_NIC_DISCOVERY=1 forces
    # it for all-local runs (tests), =0 disables. ssh backend only (the
    # agents are ssh-spawned); a non-ssh backend announces the skip so a
    # forced =1 never disappears silently.
    knob = base_env.get("HOROVOD_NIC_DISCOVERY", "").lower()
    any_remote = not all(is_local_host(s.hostname) for s in slots)
    discovery_wanted = knob not in ("0", "false", "off") and (
        any_remote or knob in ("1", "true", "on"))
    if discovery_wanted and getattr(backend, "name", "ssh") != "ssh":
        print(f"tpurun: NIC discovery skipped for launch backend "
              f"{backend.name!r} (agents are ssh-spawned); using "
              f"{driver_ip}", file=sys.stderr)
    elif discovery_wanted:
        from horovod_tpu.run import discovery as discovery_mod

        hostnames = list(dict.fromkeys(s.hostname for s in slots))
        try:
            found = discovery_mod.discover(
                hostnames, util.make_secret_key(), ssh_port=ssh_port)
            driver_ip = found.driver_addr
        except Exception as exc:  # fall back to the heuristic address
            print(f"tpurun: NIC discovery failed ({exc}); using "
                  f"{driver_ip}", file=sys.stderr)

    chip_envs = tpu_chip_envs(slots, local_tpu_chips(base_env),
                              use_jax_distributed)

    rendezvous = RendezvousServer()
    http_port = rendezvous.start()
    _announce_net_chaos()
    socket_port = _free_port()
    coordinator_port = _free_port()

    elastic_driver = None
    if elastic and discovery_script:
        from horovod_tpu.elastic.driver import (ElasticDriver,
                                                HostDiscoveryScript)

        elastic_driver = ElasticDriver(
            rendezvous, HostDiscoveryScript(discovery_script),
            min_workers=min_workers, max_workers=max_workers)
        elastic_driver.start()

    exit_codes: List[Optional[int]] = [None] * len(slots)
    failure = threading.Event()
    first_failure: List[Optional[int]] = [None]
    failure_lock = threading.Lock()

    def run_slot(i: int, slot: SlotInfo) -> None:
        worker_env = build_worker_env(
            slot, base_env, driver_ip, socket_port, http_port,
            coordinator_port,
            num_processes=len(slots),
            use_jax_distributed=use_jax_distributed)
        worker_env.update(chip_envs[i])
        if elastic:
            worker_env["HOROVOD_ELASTIC"] = "1"
            worker_env["HOROVOD_ELASTIC_MIN_WORKERS"] = str(min_workers)
        cmd = backend.command_for_slot(slot, command, worker_env)

        stdout = stderr = None
        files = []
        try:
            if output_dir:
                rank_dir = os.path.join(output_dir, f"rank.{slot.rank}")
                os.makedirs(rank_dir, exist_ok=True)
                stdout = open(os.path.join(rank_dir, "stdout"), "w")
                stderr = open(os.path.join(rank_dir, "stderr"), "w")
                files = [stdout, stderr]
            code = util.execute(
                cmd, env=worker_env,
                stdout=stdout or sys.stdout, stderr=stderr or sys.stderr,
                index=slot.rank, events=[failure],
                prefix_output=prefix_output)
            exit_codes[i] = code
            if code not in (0, None):
                with failure_lock:
                    if elastic:
                        # survivors re-form on their own; only kill the
                        # job once fewer than min_workers could remain
                        failed = sum(1 for c in exit_codes
                                     if c not in (0, None))
                        if len(slots) - failed < min_workers:
                            if not failure.is_set():
                                first_failure[0] = code
                            failure.set()
                    else:
                        # report the code of the worker that failed first,
                        # not of workers we subsequently tore down
                        # (gloo_run.py:256-262)
                        if not failure.is_set():
                            first_failure[0] = code
                        failure.set()
        finally:
            for f in files:
                f.close()

    threads = [threading.Thread(target=run_slot, args=(i, s), daemon=True)
               for i, s in enumerate(slots)]

    prev_handlers = {}

    def on_signal(signum, frame):
        failure.set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev_handlers[sig] = signal.signal(sig, on_signal)
        except ValueError:  # not main thread (tests)
            pass

    shipped: Dict[str, bytes] = {}
    shipped_profile: Dict[str, bytes] = {}
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        for sig, handler in prev_handlers.items():
            signal.signal(sig, handler)
        if elastic_driver is not None:
            elastic_driver.stop()
        if flight_recorder_dir:
            # harvest dumps workers shipped into the rendezvous store
            # BEFORE stopping it — the in-memory store dies with it
            try:
                scope = flight_recorder.RENDEZVOUS_SCOPE
                for key in rendezvous.live_keys(scope):
                    value = rendezvous.get(scope, key)
                    if value:
                        shipped[key] = value
            except Exception as exc:
                print(f"tpurun: could not collect shipped flight-recorder "
                      f"dumps: {exc}", file=sys.stderr)
        if profile_dir:
            # same store, the profiler's scope: per-rank step profiles
            try:
                from horovod_tpu import profiler

                for key in rendezvous.live_keys(profiler.RENDEZVOUS_SCOPE):
                    value = rendezvous.get(profiler.RENDEZVOUS_SCOPE, key)
                    if value:
                        shipped_profile[key] = value
            except Exception as exc:
                print(f"tpurun: could not collect shipped profiles: {exc}",
                      file=sys.stderr)
        rendezvous.stop()

    def job_exit_code() -> int:
        if elastic:
            # success = enough workers finished cleanly; lost ranks
            # (non-zero exits) were absorbed by the survivors' re-form
            clean = sum(1 for c in exit_codes if c == 0)
            if clean >= min_workers:
                return 0
            if first_failure[0] is not None:
                return first_failure[0]
            for code in exit_codes:
                if code not in (0, None):
                    return code
            return 1
        if first_failure[0] is not None:
            return first_failure[0]
        for code in exit_codes:
            if code not in (0, None):
                return code
        if any(code is None for code in exit_codes):
            return 1
        return 0

    code = job_exit_code()
    if flight_recorder_dir:
        _finalize_flight_dumps(flight_recorder_dir, shipped, code)
    if profile_dir:
        _finalize_profile(profile_dir, shipped_profile)
    return code


RESTART_LINEAGE_FILE = "restart-lineage.json"


def launch_supervised(command: str, slots: List[SlotInfo],
                      restart_budget: int = 3,
                      env: Optional[Dict[str, str]] = None,
                      **kwargs) -> int:
    """``launch_job`` under supervision: a failed job (any non-zero exit
    the elastic layer could not absorb) is relaunched up to
    ``restart_budget`` times — the crash-consistent checkpoint
    (``HOROVOD_CKPT_DIR``) is what makes the relaunch resume instead of
    retrain.

    Every attempt runs with ``HOROVOD_RESTART_ATTEMPT=<n>`` in the
    worker env, and the restart lineage — per attempt: exit code, wall
    times, budget — is appended to ``restart-lineage.json`` in the
    flight-recorder dir, where ``tpurun --postmortem`` folds it into the
    merged report (which restart a dump belongs to is otherwise
    guesswork)."""
    import json
    import time

    base_env = dict(os.environ if env is None else env)
    flight_dir = kwargs.get("flight_recorder_dir")
    lineage: List[dict] = []
    attempt = 0
    while True:
        base_env["HOROVOD_RESTART_ATTEMPT"] = str(attempt)
        t0 = time.time()
        code = launch_job(command, slots, env=dict(base_env), **kwargs)
        lineage.append({"attempt": attempt, "exit_code": code,
                        "started": t0, "ended": time.time(),
                        "restart_budget": restart_budget})
        if flight_dir:
            try:
                os.makedirs(flight_dir, exist_ok=True)
                from horovod_tpu.ckpt import io as ckpt_io

                ckpt_io.atomic_write(
                    os.path.join(flight_dir, RESTART_LINEAGE_FILE),
                    json.dumps({"attempts": lineage}, indent=1).encode(),
                    base="lineage")
            except Exception as exc:
                print(f"tpurun: could not record restart lineage: {exc}",
                      file=sys.stderr)
        if code == 0:
            if attempt:
                print(f"tpurun: job succeeded on supervised restart "
                      f"{attempt}/{restart_budget}", file=sys.stderr)
            return 0
        if attempt >= restart_budget:
            print(f"tpurun: restart budget exhausted "
                  f"({restart_budget} restarts); giving up with exit "
                  f"code {code}", file=sys.stderr)
            return code
        attempt += 1
        print(f"tpurun: job failed (exit {code}); supervised restart "
              f"{attempt}/{restart_budget}", file=sys.stderr)


def _finalize_flight_dumps(directory: str, shipped: Dict[str, bytes],
                           exit_code: int) -> None:
    """Persist rendezvous-shipped dumps (only for ranks that left no local
    file — a worker-written file is at least as fresh) and, when the job
    failed, print the merged cross-rank postmortem."""
    try:
        os.makedirs(directory, exist_ok=True)
    except OSError as exc:
        print(f"tpurun: cannot write flight-recorder dumps to "
              f"{directory!r}: {exc}", file=sys.stderr)
        return
    for key, value in shipped.items():
        if not key.startswith("rank."):
            continue
        path = os.path.join(
            directory, f"{flight_recorder.DUMP_PREFIX}"
            f"{key[len('rank.'):]}.json")
        if os.path.exists(path):
            continue
        try:
            with open(path, "wb") as f:
                f.write(value)
        except OSError as exc:
            print(f"tpurun: could not write {path}: {exc}", file=sys.stderr)
    if exit_code == 0:
        return
    dumps = flight_recorder.load_dumps(directory)
    if dumps:
        print(flight_recorder.format_postmortem(dumps), file=sys.stderr)
    else:
        print(f"tpurun: job failed but no flight-recorder dumps were found "
              f"in {directory!r}", file=sys.stderr)


def _finalize_profile(directory: str, shipped: Dict[str, bytes]) -> None:
    """Persist rendezvous-shipped per-rank profiles (worker-written local
    files win — they are at least as fresh), merge every rank's timeline /
    device trace / step markers onto one corrected clock, and print the
    cross-rank step-time report."""
    from horovod_tpu import profiler

    try:
        os.makedirs(directory, exist_ok=True)
    except OSError as exc:
        print(f"tpurun: cannot write profiles to {directory!r}: {exc}",
              file=sys.stderr)
        return
    for key, value in shipped.items():
        if not key.startswith("rank."):
            continue
        path = os.path.join(
            directory,
            f"{profiler.DUMP_PREFIX}{key[len('rank.'):]}.json")
        if os.path.exists(path):
            continue
        try:
            with open(path, "wb") as f:
                f.write(value)
        except OSError as exc:
            print(f"tpurun: could not write {path}: {exc}", file=sys.stderr)
    try:
        merged_path, n_events = profiler.merge_profile_dir(directory)
    except Exception as exc:
        print(f"tpurun: could not merge profile traces: {exc}",
              file=sys.stderr)
        merged_path, n_events = None, 0
    dumps = profiler.load_dumps(directory)
    if dumps:
        print(profiler.format_step_report(dumps))
    if merged_path and n_events:
        print(f"tpurun: merged trace ({n_events} events) written to "
              f"{merged_path} — load it in Perfetto / chrome://tracing")
