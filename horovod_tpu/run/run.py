"""``tpurun`` — the launcher CLI (≡ ``horovodrun``).

TPU-native port of the reference CLI (reference: horovod/run/run.py:374-732
and bin/horovodrun): parse flags / YAML config into the HOROVOD_* env
contract, check host reachability, allocate slots, and fan the training
command out across hosts.

    tpurun -np 4 -H host1:2,host2:2 python train.py
    tpurun -np 8 python train.py           # 8 local workers
    tpurun --check-build
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import textwrap
import time
from typing import List, Optional

from horovod_tpu.run import config_parser, hosts as hosts_mod, launcher
from horovod_tpu.run import util
from horovod_tpu.version import __version__

SSH_CHECK_TIMEOUT_S = 30
# reference caches ssh reachability results for 60 minutes in ~/.horovod
# (run/run.py:49-60)
CACHE_TTL_S = 60 * 60
CACHE_DIR = os.path.expanduser("~/.horovod_tpu")


class _RecordAction(argparse.Action):
    """Records explicitly-passed flags so config-file precedence can be
    applied (reference: run.py:422-425 _add_arg tracking)."""

    def __init__(self, option_strings, dest, nargs=None, const=None, **kw):
        self._const = const
        self._nargs = nargs
        super().__init__(option_strings, dest, nargs=nargs, const=const, **kw)

    def __call__(self, parser, namespace, values, option_string=None):
        if self._const is not None and values in (None, []):
            values = self._const
        setattr(namespace, self.dest, values)
        if not hasattr(namespace, "seen_args"):
            namespace.seen_args = set()
        namespace.seen_args.add(self.dest)


def _add(parser, *flags, **kw):
    if kw.get("action") == "store_true":
        kw.pop("action")
        kw.update(action=_RecordAction, nargs=0, const=True, default=kw.get(
            "default", None))
    else:
        kw.setdefault("action", _RecordAction)
    parser.add_argument(*flags, **kw)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="tpurun",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        description="Launch a distributed horovod_tpu job.",
        epilog=textwrap.dedent("""\
            Example:
                tpurun -np 4 -H host1:2,host2:2 python train.py
            """))
    parser.add_argument("-v", "--version", action="version",
                        version=__version__)
    _add(parser, "-np", "--num-proc", dest="np", type=int,
         help="Total number of worker processes (one per TPU chip).")
    _add(parser, "-H", "--hosts", dest="hosts",
         help="Comma-separated host:slots list, e.g. host1:4,host2:4.")
    _add(parser, "--hostfile", dest="hostfile",
         help="mpirun-style hostfile ('hostname slots=N' per line).")
    _add(parser, "-p", "--ssh-port", dest="ssh_port", type=int,
         help="SSH port on all hosts.")
    _add(parser, "--start-timeout", dest="start_timeout", type=int,
         default=600, help="Seconds to wait for all processes to start.")
    _add(parser, "--output-filename", dest="output_dir",
         help="Capture each rank's output under <dir>/rank.N/std{out,err}.")
    _add(parser, "--verbose", dest="verbose", action="store_true",
         help="Verbose launcher logging.")
    _add(parser, "--disable-cache", dest="disable_cache",
         action="store_true",
         help="Do not cache ssh reachability checks.")
    parser.add_argument("--check-build", action="store_true",
                        help="Print capability report and exit "
                             "(reference: run/run.py:268-303).")
    _add(parser, "--config-file", dest="config_file",
         help="YAML config file; flags given after it take precedence.")
    _add(parser, "--no-jax-distributed", dest="no_jax_distributed",
         action="store_true",
         help="Do not bootstrap jax.distributed (host data plane only).")
    _add(parser, "--launch-backend", dest="launch_backend",
         choices=["ssh", "gcloud-tpu-vm"],
         help="Fan-out mechanism: ssh (default; local exec for local "
              "hosts) or gcloud-tpu-vm (GCE `gcloud compute tpus tpu-vm "
              "ssh --worker=N`; hosts name TPU VMs). Also "
              "HOROVOD_LAUNCH_BACKEND. The seam the reference's "
              "gloo-vs-mpirun choice occupies (run/run.py:715-732).")
    _add(parser, "--gcloud-zone", dest="gcloud_zone",
         help="GCE zone for --launch-backend gcloud-tpu-vm.")
    _add(parser, "--gcloud-project", dest="gcloud_project",
         help="GCP project for --launch-backend gcloud-tpu-vm.")
    _add(parser, "--mesh-shape", dest="mesh_shape",
         help="Global mesh as 'cross,local' (default: hosts x slots).")

    params = parser.add_argument_group("tunable parameters")
    _add(params, "--fusion-threshold-mb", dest="fusion_threshold_mb",
         type=float, help="Tensor fusion buffer threshold in MB.")
    _add(params, "--cycle-time-ms", dest="cycle_time_ms", type=float,
         help="Background cycle time in ms.")
    _add(params, "--cache-capacity", dest="cache_capacity", type=int,
         help="Response cache capacity.")
    _add(params, "--hierarchical-allreduce", dest="hierarchical_allreduce",
         action="store_true",
         help="Force two-level (ICI then DCN) allreduce.")
    _add(params, "--hierarchical-allgather", dest="hierarchical_allgather",
         action="store_true",
         help="Force two-level (ICI then DCN) allgather.")

    timeline = parser.add_argument_group("timeline")
    _add(timeline, "--timeline-filename", dest="timeline_filename",
         help="Chrome-trace timeline output (rank 0).")
    _add(timeline, "--timeline-mark-cycles", dest="timeline_mark_cycles",
         action="store_true", help="Mark cycles in the timeline.")
    _add(timeline, "--merge-trace", dest="merge_trace", metavar="OUT",
         help="Merge Chrome trace files (per-rank timelines, device "
              "traces exported as Chrome JSON / .json.gz) into OUT and "
              "exit; inputs follow as positional arguments.")
    _add(timeline, "--merge-trace-align", dest="merge_trace_align",
         action="store_true",
         help="With --merge-trace: rebase each input's earliest event to "
              "a common origin (for traces not in the epoch clock "
              "domain).")

    metrics_group = parser.add_argument_group("metrics")
    _add(metrics_group, "--metrics-summary", dest="metrics_summary",
         action="store_true",
         help="Aggregate per-rank metrics dumps (written at shutdown when "
              "HOROVOD_METRICS_DUMP is set) into a cross-rank min/median/"
              "max table and exit; dump files (or directories containing "
              "metrics-rank-*.json) follow as positional arguments. Exits "
              "non-zero when no dump files are found.")

    flight = parser.add_argument_group("flight recorder")
    _add(flight, "--flight-recorder-dir", dest="flight_recorder_dir",
         help="Directory for per-rank flight-recorder dumps "
              "(flight-rank-N.json): workers write them on failure/exit, "
              "the launcher collects rendezvous-shipped copies for dead "
              "workers, and on a failed job a merged cross-rank "
              "postmortem is printed. Sets HOROVOD_FLIGHT_RECORDER_DIR.")
    _add(flight, "--postmortem", dest="postmortem", metavar="DIR",
         help="Print the merged cross-rank postmortem from the "
              "flight-recorder dumps in DIR and exit (non-zero when DIR "
              "holds no dumps).")

    profile = parser.add_argument_group("profiler")
    _add(profile, "--profile-dir", dest="profile_dir",
         help="Directory for per-rank step profiles. Sets "
              "HOROVOD_PROFILE_DIR (enabling the step profiler) and a "
              "per-rank HOROVOD_TIMELINE; after the job the launcher "
              "collects every rank's profile + timeline + device trace, "
              "merges them onto one clock-corrected Chrome trace "
              "(merged-trace.json), and prints a cross-rank step-time "
              "report naming the slowest rank and its dominant phase.")
    _add(profile, "--profile-report", dest="profile_report", metavar="DIR",
         help="Print the cross-rank step-time report from the profile "
              "dumps in DIR (re-merging the trace) and exit; non-zero "
              "when DIR holds no dumps.")

    autotune = parser.add_argument_group("autotune")
    _add(autotune, "--autotune", dest="autotune", action="store_true",
         help="Enable Bayesian autotuning of fusion/cycle parameters.")
    _add(autotune, "--autotune-log-file", dest="autotune_log_file",
         help="CSV log of autotune trials.")
    _add(autotune, "--autotune-warmup-samples", dest="autotune_warmup_samples",
         type=int, help="Discarded warmup samples per trial.")
    _add(autotune, "--autotune-steps-per-sample",
         dest="autotune_steps_per_sample", type=int,
         help="Steps per timing sample.")
    _add(autotune, "--autotune-bayes-opt-max-samples",
         dest="autotune_bayes_opt_max_samples", type=int,
         help="Max Bayesian-optimization samples.")
    _add(autotune, "--autotune-gaussian-process-noise",
         dest="autotune_gaussian_process_noise", type=float,
         help="GP noise regularization in [0, 1].")

    elastic_group = parser.add_argument_group("elastic (fault-tolerant)")
    _add(elastic_group, "--elastic", dest="elastic", action="store_true",
         help="Elastic mode: worker failures no longer kill the job; "
              "survivors re-form membership and resume from the last "
              "committed state (requires the training script to use "
              "hvd.elastic). Sets HOROVOD_ELASTIC=1 for workers.")
    _add(elastic_group, "--min-workers", dest="min_workers", type=int,
         help="Minimum workers an elastic job may shrink to (default 1); "
              "below this the job fails. Sets HOROVOD_ELASTIC_MIN_WORKERS.")
    _add(elastic_group, "--max-workers", dest="max_workers", type=int,
         help="Maximum workers an elastic job may grow to (discovered "
              "hosts beyond this are held in reserve).")
    _add(elastic_group, "--host-discovery-script",
         dest="host_discovery_script",
         help="Executable printing the current 'hostname[:slots]' set, one "
              "per line; polled by the elastic driver to add/remove "
              "hosts at runtime.")
    _add(elastic_group, "--supervise", dest="supervise",
         action="store_true",
         help="Supervised restarts: when the whole job fails (beyond "
              "what elastic re-forms absorb), relaunch it — resuming "
              "from the crash-consistent checkpoint directory "
              "(HOROVOD_CKPT_DIR) when the training script uses "
              "hvd.elastic state. Each attempt gets "
              "HOROVOD_RESTART_ATTEMPT=<n>; the restart lineage is "
              "recorded in the flight-recorder dir for --postmortem.")
    _add(elastic_group, "--restart-budget", dest="restart_budget",
         type=int,
         help="Maximum supervised relaunches before giving up "
              "(default 3; only with --supervise).")

    serving = parser.add_argument_group("online serving")
    _add(serving, "--serve", dest="serve", action="store_true",
         help="Launch each slot as a continuous-batching inference "
              "replica instead of a training worker (docs/inference.md). "
              "With no command, runs the built-in demo worker "
              "(python -m horovod_tpu.serve); with a command, the "
              "command is expected to call hvd.serve()/run_kv_replica. "
              "Replicas pull from the rendezvous-KV request queue and "
              "register heartbeats the dispatcher uses to redistribute "
              "work from dead replicas. HOROVOD_SERVE_* env knobs set "
              "the batching policy.")

    stall = parser.add_argument_group("stall check")
    _add(stall, "--no-stall-check", dest="no_stall_check",
         action="store_true", help="Disable the stall inspector.")
    _add(stall, "--stall-check-warning-time-seconds",
         dest="stall_check_warning_time_seconds", type=float,
         help="Seconds before a stall warning is logged.")
    _add(stall, "--stall-check-shutdown-time-seconds",
         dest="stall_check_shutdown_time_seconds", type=float,
         help="Seconds before a stall aborts the job (0 = never).")

    logging_group = parser.add_argument_group("logging")
    _add(logging_group, "--log-level", dest="log_level",
         choices=["trace", "debug", "info", "warning", "error", "fatal"],
         help="Runtime log level.")
    _add(logging_group, "--log-hide-timestamp", dest="log_hide_timestamp",
         action="store_true", help="Hide timestamps in log output.")

    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="Training command to run on every slot.")

    args = parser.parse_args(argv)
    if not hasattr(args, "seen_args"):
        args.seen_args = set()

    if args.config_file:
        config = config_parser.parse_config_file(args.config_file)
        config_parser.set_args_from_config_file(args, config)
    config_parser.validate_config_args(args)
    return args


def check_build(out=sys.stdout) -> None:
    """Capability report (reference: run/run.py:268-303 --check-build)."""
    import horovod_tpu as hvd
    from horovod_tpu.runtime.native import native_built

    def mark(flag: bool) -> str:
        return "[X]" if flag else "[ ]"

    out.write(textwrap.dedent(f"""\
        horovod_tpu v{__version__}:

        Available frameworks:
            {mark(True)} JAX
            {mark(_flax_available())} Flax

        Available controllers:
            {mark(True)} XLA (in-jit SPMD)
            {mark(native_built())} Socket (native TCP)

        Available tensor operations:
            {mark(hvd.xla_built())} XLA collectives (ICI/DCN)
            {mark(native_built())} Native host ring
            {mark(hvd.mpi_built())} MPI
            {mark(hvd.nccl_built())} NCCL
            {mark(hvd.gloo_built())} Gloo
        """))


def _flax_available() -> bool:
    try:
        import flax  # noqa: F401
        return True
    except ImportError:
        return False


# ---------------------------------------------------------------------------
# ssh reachability (reference: run/run.py:60-112, cached per run/run.py:49-60)
# ---------------------------------------------------------------------------

def _cache_path() -> str:
    return os.path.join(CACHE_DIR, "ssh_checks.json")


def check_all_hosts_ssh_successful(hostnames: List[str],
                                   ssh_port: Optional[int] = None,
                                   use_cache: bool = True) -> None:
    import json

    remote = [h for h in hostnames if not launcher.is_local_host(h)]
    if not remote:
        return

    cache = {}
    if use_cache and os.path.exists(_cache_path()):
        try:
            with open(_cache_path()) as f:
                cache = json.load(f)
        except (ValueError, OSError):
            cache = {}

    now = time.time()
    failed = []
    for host in remote:
        entry = cache.get(host)
        if entry and now - entry < CACHE_TTL_S:
            continue
        port_arg = f"-p {ssh_port}" if ssh_port else ""
        result = subprocess.run(
            f"ssh -o PasswordAuthentication=no -o StrictHostKeyChecking=no "
            f"{port_arg} {host} true",
            shell=True, capture_output=True,
            timeout=SSH_CHECK_TIMEOUT_S)
        if result.returncode == 0:
            cache[host] = now
        else:
            failed.append(host)

    if use_cache:
        os.makedirs(CACHE_DIR, exist_ok=True)
        with open(_cache_path(), "w") as f:
            json.dump(cache, f)

    if failed:
        raise RuntimeError(
            "passwordless ssh checked failed for hosts: "
            + ", ".join(failed)
            + ". Set up passwordless ssh or run single-host.")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)

    if args.check_build:
        check_build()
        return 0

    command = list(args.command or [])
    if command and command[0] == "--":
        command = command[1:]
    if args.merge_trace:
        from horovod_tpu.timeline import merge_traces

        if not command:
            sys.stderr.write("tpurun --merge-trace: no input traces\n")
            return 2
        n = merge_traces(args.merge_trace, command,
                         align=args.merge_trace_align)
        print(f"merged {n} events from {len(command)} trace(s) into "
              f"{args.merge_trace}")
        return 0
    if args.metrics_summary:
        import glob as _glob

        from horovod_tpu.metrics import format_summary, summarize_dumps

        if not command:
            sys.stderr.write("tpurun --metrics-summary: no dump files\n")
            return 2
        # a directory argument stands for its metrics-rank-*.json dumps
        paths: List[str] = []
        for arg in command:
            if os.path.isdir(arg):
                paths.extend(sorted(_glob.glob(
                    os.path.join(arg, "metrics-rank-*.json"))))
            else:
                paths.append(arg)
        if not paths:
            sys.stderr.write("tpurun --metrics-summary: no metrics dump "
                             "files found\n")
            return 1
        try:
            rows = summarize_dumps(paths)
        except (OSError, ValueError, KeyError) as exc:
            sys.stderr.write(f"tpurun --metrics-summary: {exc}\n")
            return 2
        print(format_summary(rows, n_ranks=len(paths)))
        return 0
    if args.profile_report:
        from horovod_tpu import profiler

        dumps = profiler.load_dumps(args.profile_report)
        if not dumps:
            sys.stderr.write(f"tpurun --profile-report: no profile dumps "
                             f"found in {args.profile_report!r}\n")
            return 1
        try:
            merged_path, n_events = profiler.merge_profile_dir(
                args.profile_report)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"tpurun --profile-report: merge failed: "
                             f"{exc}\n")
            merged_path, n_events = None, 0
        print(profiler.format_step_report(dumps))
        spans = sum(len(d.get("request_spans", ())) for d in dumps)
        if spans:
            traces = {s.get("trace_id") for d in dumps
                      for s in d.get("request_spans", ())
                      if isinstance(s, dict) and s.get("trace_id")}
            print(f"tpurun: {spans} request/collective spans across "
                  f"{len(traces)} trace(s) merged into per-rank request "
                  f"lanes (docs/tracing.md)")
        if merged_path and n_events:
            print(f"tpurun: merged trace ({n_events} events) written to "
                  f"{merged_path}")
        return 0
    if args.postmortem:
        from horovod_tpu import flight_recorder

        dumps = flight_recorder.load_dumps(args.postmortem)
        if not dumps:
            sys.stderr.write(f"tpurun --postmortem: no flight-recorder "
                             f"dumps found in {args.postmortem!r}\n")
            return 1
        lineage = flight_recorder.load_restart_lineage(args.postmortem)
        print(flight_recorder.format_postmortem(dumps, lineage=lineage))
        return 0
    if getattr(args, "serve", False) and not command:
        # the serving plane's default worker: one KV-queue replica per
        # slot, identical random-weight demo model on every rank
        command = [sys.executable, "-m", "horovod_tpu.serve"]
    if not command:
        sys.stderr.write("tpurun: no command given\n")
        return 2

    if args.hostfile:
        host_infos = hosts_mod.parse_hostfile(args.hostfile)
    elif args.hosts:
        host_infos = hosts_mod.parse_hosts(args.hosts)
    else:
        nproc = args.np or 1
        host_infos = [hosts_mod.HostInfo("localhost", nproc)]
    np = args.np or sum(h.slots for h in host_infos)

    from horovod_tpu.run.backends import make_backend

    try:
        backend = make_backend(args.launch_backend, ssh_port=args.ssh_port,
                               gcloud_zone=args.gcloud_zone,
                               gcloud_project=args.gcloud_project)
    except ValueError as exc:  # bad HOROVOD_LAUNCH_BACKEND env value
        sys.stderr.write(f"tpurun: {exc}\n")
        return 2
    if backend.name == "ssh":
        # plain-ssh reachability only makes sense for the ssh backend —
        # gcloud-tpu-vm hosts are TPU VM names reached through gcloud
        check_all_hosts_ssh_successful(
            [h.hostname for h in host_infos], args.ssh_port,
            use_cache=not args.disable_cache)

    slots = hosts_mod.allocate(host_infos, np)
    if args.verbose:
        for s in slots:
            sys.stderr.write(f"tpurun: rank {s.rank} -> {s.hostname} "
                             f"(local {s.local_rank}/{s.local_size}, "
                             f"cross {s.cross_rank}/{s.cross_size})\n")

    env = dict(os.environ)
    env.update(config_parser.env_from_args(args))
    env["HOROVOD_NP"] = str(np)

    import shlex as _shlex

    elastic = bool(args.elastic)
    min_workers = args.min_workers or 1
    if elastic and args.min_workers and args.min_workers > np:
        sys.stderr.write(f"tpurun: --min-workers {args.min_workers} "
                         f"exceeds the launch size {np}\n")
        return 2

    command_str = " ".join(_shlex.quote(c) for c in command)
    launch_kwargs = dict(
        env=env, ssh_port=args.ssh_port,
        output_dir=args.output_dir,
        use_jax_distributed=not args.no_jax_distributed,
        start_timeout=args.start_timeout, backend=backend,
        elastic=elastic, min_workers=min_workers,
        max_workers=args.max_workers,
        discovery_script=args.host_discovery_script,
        flight_recorder_dir=args.flight_recorder_dir,
        profile_dir=args.profile_dir)
    try:
        if args.supervise:
            budget = (args.restart_budget
                      if args.restart_budget is not None else 3)
            launch_kwargs.pop("env")
            return launcher.launch_supervised(
                command_str, slots, restart_budget=budget, env=env,
                **launch_kwargs)
        return launcher.launch_job(command_str, slots, **launch_kwargs)
    except launcher.SlotLayoutError as exc:
        # raised before anything is spawned
        sys.stderr.write(f"tpurun: {exc}\n")
        return 2


def main() -> None:
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
