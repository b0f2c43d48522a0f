"""Environment-variable knob catalog and parsing.

The reference converges three config layers onto environment variables read
at init (reference: horovod/common/common.h:61-85, operations.cc:363-454,
utils/env_parser.cc). We keep the same knob names so launcher flags, config
files and user envs translate 1:1.
"""

from __future__ import annotations

import dataclasses
import os

# Knob names (reference: horovod/common/common.h:61-85 plus gloo/logging).
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_PROBE = "HOROVOD_AUTOTUNE_PROBE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_AUTOTUNE_WARMUP_SAMPLES = "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"
HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE = "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"
HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
HOROVOD_METRICS_PORT = "HOROVOD_METRICS_PORT"
HOROVOD_METRICS_DUMP = "HOROVOD_METRICS_DUMP"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_STALL_CHECK_TIME_SECONDS = "HOROVOD_STALL_CHECK_TIME_SECONDS"
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS = "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_HIERARCHICAL_ALLGATHER = "HOROVOD_HIERARCHICAL_ALLGATHER"
# two-level host collectives (runtime/hierarchy.py; docs/performance.md):
# ranks per slice (0 = derive groups from the rendezvous roster's
# hostnames) and the wire dtype of the slow cross-group hop
# (none | fp16 (bf16 on TPU) | ieee_fp16)
HOROVOD_HIERARCHY_GROUP_SIZE = "HOROVOD_HIERARCHY_GROUP_SIZE"
HOROVOD_HIERARCHY_COMPRESSION = "HOROVOD_HIERARCHY_COMPRESSION"
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
HOROVOD_LOG_HIDE_TIME = "HOROVOD_LOG_HIDE_TIME"
HOROVOD_MESH_SHAPE = "HOROVOD_MESH_SHAPE"
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_CONTROLLER = "HOROVOD_CONTROLLER"
HOROVOD_CPU_OPERATIONS = "HOROVOD_CPU_OPERATIONS"
HOROVOD_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
HOROVOD_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
HOROVOD_ELASTIC = "HOROVOD_ELASTIC"
HOROVOD_CYCLE_PIPELINE_DEPTH = "HOROVOD_CYCLE_PIPELINE_DEPTH"
HOROVOD_FUSION_BUCKET_QUANTUM = "HOROVOD_FUSION_BUCKET_QUANTUM"
HOROVOD_FLIGHT_RECORDER = "HOROVOD_FLIGHT_RECORDER"
HOROVOD_FLIGHT_RECORDER_DIR = "HOROVOD_FLIGHT_RECORDER_DIR"
HOROVOD_STRAGGLER_REPORT_SECONDS = "HOROVOD_STRAGGLER_REPORT_SECONDS"
HOROVOD_SHARDED_FUSED_KERNEL = "HOROVOD_SHARDED_FUSED_KERNEL"
HOROVOD_PROFILE = "HOROVOD_PROFILE"
HOROVOD_PROFILE_DIR = "HOROVOD_PROFILE_DIR"
HOROVOD_PROFILE_HISTORY = "HOROVOD_PROFILE_HISTORY"
HOROVOD_PROFILE_JAX = "HOROVOD_PROFILE_JAX"
# deadlock witness (analysis/witness.py): instrument runtime locks,
# record acquisition order, flag inversions / live deadlocks / long holds
HOROVOD_DEBUG_LOCKS = "HOROVOD_DEBUG_LOCKS"
HOROVOD_LOCK_HOLD_WARN_SECONDS = "HOROVOD_LOCK_HOLD_WARN_SECONDS"
# request-level tracing + SLO plane (tracing.py; docs/tracing.md)
HOROVOD_TRACE = "HOROVOD_TRACE"
HOROVOD_SLO_TTFT_MS = "HOROVOD_SLO_TTFT_MS"
HOROVOD_SLO_LATENCY_MS = "HOROVOD_SLO_LATENCY_MS"
HOROVOD_SLO_AVAILABILITY = "HOROVOD_SLO_AVAILABILITY"
HOROVOD_SLO_WINDOW = "HOROVOD_SLO_WINDOW"
HOROVOD_SLO_BURN_ALERT = "HOROVOD_SLO_BURN_ALERT"

# Knobs read at their point of use rather than parsed into Config —
# launcher/rendezvous wiring that exists before hvd.init() runs, elastic
# re-form parameters rewritten between generations, and test/debug
# switches. Registered here so tools/check_env_knobs.py can verify the
# complete catalog lives in this module: a knob missing from both Config
# and this tuple fails CI as UNREGISTERED.
ENV_DIRECT_KNOBS = (
    # identity / wiring injected by the launcher before init
    "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
    "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK", "HOROVOD_CROSS_SIZE",
    "HOROVOD_CONTROLLER", "HOROVOD_COORDINATOR_ADDR", "HOROVOD_HOSTNAME",
    "HOROVOD_PROCESS_ID", "HOROVOD_SECRET_KEY", "HOROVOD_TASK_KEY",
    "HOROVOD_NP", "HOROVOD_NUM_PROCESSES",
    # rendezvous / gloo-compatible store
    "HOROVOD_GLOO_RENDEZVOUS_ADDR", "HOROVOD_GLOO_RENDEZVOUS_PORT",
    "HOROVOD_GLOO_TIMEOUT_SECONDS", "HOROVOD_RENDEZVOUS_HTTP_ADDR",
    "HOROVOD_RENDEZVOUS_HTTP_PORT", "HOROVOD_RENDEZVOUS_HEARTBEAT_TTL",
    "HOROVOD_RENDEZVOUS_LONG_POLL_SECONDS", "HOROVOD_PROBE_TIMEOUT",
    # launcher backends / host discovery
    "HOROVOD_LAUNCH_BACKEND", "HOROVOD_NIC_DISCOVERY",
    "HOROVOD_GCLOUD_PROJECT", "HOROVOD_GCLOUD_ZONE",
    # elastic re-form parameters (rewritten per generation)
    "HOROVOD_ELASTIC_MIN_WORKERS", "HOROVOD_ELASTIC_MAX_RETRIES",
    "HOROVOD_ELASTIC_BACKOFF_BASE_SECONDS",
    "HOROVOD_ELASTIC_BACKOFF_MAX_SECONDS",
    "HOROVOD_ELASTIC_DISCOVERY_INTERVAL_SECONDS",
    "HOROVOD_ELASTIC_HEARTBEAT_SECONDS",
    "HOROVOD_ELASTIC_REJOIN_TIMEOUT_SECONDS",
    "HOROVOD_ELASTIC_SETTLE_SECONDS",
    "HOROVOD_ELASTIC_SPILL_DIR", "HOROVOD_ELASTIC_SPILL_SYNC",
    # crash-consistent sharded checkpointing (ckpt/; docs/checkpointing.md)
    "HOROVOD_CKPT_DIR", "HOROVOD_CKPT_ASYNC", "HOROVOD_CKPT_KEEP",
    "HOROVOD_CKPT_REPLICATION", "HOROVOD_CKPT_VERIFY",
    "HOROVOD_CKPT_BARRIER_TIMEOUT_SECONDS", "HOROVOD_CKPT_FAULT",
    "HOROVOD_RESTART_ATTEMPT",
    # control-plane resilience (utils/resilience.py; docs/robustness.md)
    "HOROVOD_COLLECTIVE_TIMEOUT", "HOROVOD_NET_MAX_RETRIES",
    "HOROVOD_NET_BACKOFF_BASE_SECONDS", "HOROVOD_NET_BACKOFF_MAX_SECONDS",
    "HOROVOD_NET_DEADLINE_SECONDS", "HOROVOD_NET_ATTEMPT_TIMEOUT_SECONDS",
    # native/build/test switches
    "HOROVOD_NATIVE_CYCLE", "HOROVOD_TPU_WITHOUT_NATIVE",
    "HOROVOD_PALLAS_INTERPRET", "HOROVOD_FAULT_INJECT",
    # numerical integrity plane (integrity/; docs/integrity.md)
    "HOROVOD_INTEGRITY", "HOROVOD_INTEGRITY_INTERVAL",
    "HOROVOD_INTEGRITY_SPIKE_SIGMA", "HOROVOD_INTEGRITY_SKIP_STEPS",
    "HOROVOD_INTEGRITY_QUARANTINE", "HOROVOD_ROLLBACK_BUDGET",
    # online serving plane (serve/; docs/inference.md)
    "HOROVOD_SERVE_MAX_BATCH_TOKENS", "HOROVOD_SERVE_ADMISSION_MS",
    "HOROVOD_SERVE_QUEUE_CAPACITY", "HOROVOD_SERVE_DECODE_BLOCK",
    "HOROVOD_SERVE_SLOTS", "HOROVOD_SERVE_MAX_NEW_TOKENS",
    "HOROVOD_SERVE_QUARANTINE", "HOROVOD_SERVE_RESULT_TTL_S",
    # paged KV cache + prefix reuse (serve/paging.py; docs/inference.md)
    "HOROVOD_SERVE_PAGED", "HOROVOD_SERVE_PAGE_TOKENS",
    "HOROVOD_SERVE_PAGE_POOL", "HOROVOD_SERVE_PREFIX_CACHE",
    # bucket-wise gradient release (parallel/buckets.py;
    # docs/performance.md "backward overlap")
    "HOROVOD_GRAD_BUCKET_RELEASE", "HOROVOD_GRAD_BUCKET_BYTES",
    "HOROVOD_GRAD_BUCKET_WIRE",
    # fused BN+activation epilogue (ops/pallas/conv_bn_act.py)
    "HOROVOD_FUSED_BN_ACT",
    # memory telemetry plane (memory.py; docs/memory.md)
    "HOROVOD_MEMORY", "HOROVOD_MEMORY_SAMPLE_SECONDS",
    "HOROVOD_MEMORY_TOPK",
    # collective transport observatory (comms.py; docs/comms.md) + the
    # persisted probe roofline artifact (autotune/probe.py)
    "HOROVOD_COMMS", "HOROVOD_COMMS_WINDOW",
    "HOROVOD_COMMS_EWMA_ALPHA", "HOROVOD_COMMS_DEGRADED_FRACTION",
    "HOROVOD_PROBE_CACHE",
    # goodput ledger (goodput.py; docs/goodput.md)
    "HOROVOD_GOODPUT", "HOROVOD_GOODPUT_INCIDENTS",
    "HOROVOD_GOODPUT_REPORT_SECONDS",
    # ZeRO stage selection + stage-3 prefetch window (parallel/zero.py;
    # docs/performance.md "sharded training")
    "HOROVOD_ZERO_STAGE", "HOROVOD_ZERO_PREFETCH_BUCKETS",
)

DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024  # reference: operations.cc:379
DEFAULT_CYCLE_TIME_MS = 5.0  # reference: operations.cc:386
DEFAULT_CACHE_CAPACITY = 1024  # reference: global_state.h:88
DEFAULT_CYCLE_PIPELINE_DEPTH = 2
DEFAULT_FUSION_BUCKET_QUANTUM_BYTES = 64 * 1024
DEFAULT_FLIGHT_RECORDER_CAPACITY = 2048
DEFAULT_STRAGGLER_REPORT_SECONDS = 60.0
DEFAULT_PROFILE_HISTORY = 64
DEFAULT_LOCK_HOLD_WARN_SECONDS = 5.0
DEFAULT_TRACE_CAPACITY = 16384
DEFAULT_SLO_WINDOW = 512


def _get_int(name: str, default: int) -> int:
    value = os.environ.get(name)
    if value is None or value == "":
        return default
    try:
        return int(value)
    except ValueError:
        return default


def _get_float(name: str, default: float) -> float:
    value = os.environ.get(name)
    if value is None or value == "":
        return default
    try:
        return float(value)
    except ValueError:
        return default


def _get_bool(name: str, default: bool = False) -> bool:
    value = os.environ.get(name)
    if value is None or value == "":
        return default
    return value.strip().lower() not in ("0", "false", "no", "off", "")


def parse_trace(value: "str | None") -> "tuple[bool, int]":
    """``HOROVOD_TRACE`` -> (enabled, span ring capacity). Same grammar
    as ``HOROVOD_FLIGHT_RECORDER``: unset or truthy = on at the default
    capacity; an integer > 1 is the capacity; 0/false/no/off disables."""
    if value is None or value.strip() == "":
        return True, DEFAULT_TRACE_CAPACITY
    v = value.strip().lower()
    if v in ("0", "false", "no", "off"):
        return False, DEFAULT_TRACE_CAPACITY
    try:
        n = int(v)
    except ValueError:
        return True, DEFAULT_TRACE_CAPACITY
    return True, (n if n > 1 else DEFAULT_TRACE_CAPACITY)


def parse_flight_recorder(value: "str | None") -> "tuple[bool, int]":
    """``HOROVOD_FLIGHT_RECORDER`` -> (enabled, ring capacity). Unset or
    truthy = on at the default capacity; an integer > 1 is the capacity;
    0/false/no/off disables."""
    if value is None or value.strip() == "":
        return True, DEFAULT_FLIGHT_RECORDER_CAPACITY
    v = value.strip().lower()
    if v in ("0", "false", "no", "off"):
        return False, DEFAULT_FLIGHT_RECORDER_CAPACITY
    try:
        n = int(v)
    except ValueError:
        return True, DEFAULT_FLIGHT_RECORDER_CAPACITY
    return True, (n if n > 1 else DEFAULT_FLIGHT_RECORDER_CAPACITY)


@dataclasses.dataclass
class Config:
    """Runtime knobs parsed once at ``hvd.init()``.

    Mirrors the env parsing block in the reference background thread init
    (reference: horovod/common/operations.cc:363-454).
    """

    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    timeline_file: str = ""
    timeline_mark_cycles: bool = False
    # None = endpoint disabled (no thread, no socket); 0 = ephemeral port
    metrics_port: "int | None" = None
    metrics_dump: str = ""
    autotune: bool = False
    autotune_probe: bool = False
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8
    stall_check_disable: bool = False
    stall_check_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # two-level host collectives: ranks per slice (0 = host-derived
    # grouping from the rendezvous roster) and the slow-hop wire dtype
    # (none | fp16 | ieee_fp16); autotuner-writable via the synced blob
    hierarchy_group_size: int = 0
    hierarchy_compression: str = "none"
    # elastic mode: stall shutdown and peer loss raise catchable
    # WorkersDownError instead of tearing the process down
    elastic: bool = False
    # data-plane pipelining: responses in flight per cycle (1 = serial)
    cycle_pipeline_depth: int = DEFAULT_CYCLE_PIPELINE_DEPTH
    # size-bucket quantum for the fused program cache; payloads at or
    # under it keep exact sizes, larger ones pad to a power of two
    fusion_bucket_quantum: int = DEFAULT_FUSION_BUCKET_QUANTUM_BYTES
    # flight recorder: always-on bounded event ring + crash dumps
    flight_recorder: bool = True
    flight_recorder_capacity: int = DEFAULT_FLIGHT_RECORDER_CAPACITY
    flight_recorder_dir: str = ""
    # coordinator straggler report interval (0 disables the log line;
    # the lag gauge/skew histogram stay on either way)
    straggler_report_seconds: float = DEFAULT_STRAGGLER_REPORT_SECONDS
    # step profiler (profiler.py): per-step phase attribution, comm-hidden
    # fraction and MFU; a profile dir also turns profiling on
    profile: bool = False
    profile_dir: str = ""
    profile_history: int = DEFAULT_PROFILE_HISTORY
    # additionally capture a jax.profiler device trace into the profile dir
    profile_jax: bool = False
    # deadlock witness: runtime locks become order/hold-tracking DebugLocks
    # (analysis/witness.py; lock creation also reads the env directly, as
    # locks can be constructed before init parses this Config)
    debug_locks: bool = False
    lock_hold_warn_seconds: float = DEFAULT_LOCK_HOLD_WARN_SECONDS

    @classmethod
    def from_env(cls) -> "Config":
        return cls(
            fusion_threshold_bytes=_get_int(
                HOROVOD_FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES
            ),
            cycle_time_ms=_get_float(HOROVOD_CYCLE_TIME, DEFAULT_CYCLE_TIME_MS),
            cache_capacity=_get_int(HOROVOD_CACHE_CAPACITY, DEFAULT_CACHE_CAPACITY),
            timeline_file=os.environ.get(HOROVOD_TIMELINE, ""),
            timeline_mark_cycles=_get_bool(HOROVOD_TIMELINE_MARK_CYCLES),
            metrics_port=(
                _get_int(HOROVOD_METRICS_PORT, 0)
                if os.environ.get(HOROVOD_METRICS_PORT, "") != "" else None),
            metrics_dump=os.environ.get(HOROVOD_METRICS_DUMP, ""),
            autotune=_get_bool(HOROVOD_AUTOTUNE),
            autotune_probe=_get_bool(HOROVOD_AUTOTUNE_PROBE),
            autotune_log=os.environ.get(HOROVOD_AUTOTUNE_LOG, ""),
            autotune_warmup_samples=_get_int(HOROVOD_AUTOTUNE_WARMUP_SAMPLES, 3),
            autotune_steps_per_sample=_get_int(HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE, 10),
            autotune_bayes_opt_max_samples=_get_int(
                HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES, 20
            ),
            autotune_gaussian_process_noise=_get_float(
                HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE, 0.8
            ),
            stall_check_disable=_get_bool(HOROVOD_STALL_CHECK_DISABLE),
            stall_check_time_seconds=_get_float(HOROVOD_STALL_CHECK_TIME_SECONDS, 60.0),
            stall_shutdown_time_seconds=_get_float(
                HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, 0.0
            ),
            hierarchical_allreduce=_get_bool(HOROVOD_HIERARCHICAL_ALLREDUCE),
            hierarchical_allgather=_get_bool(HOROVOD_HIERARCHICAL_ALLGATHER),
            hierarchy_group_size=_get_int(HOROVOD_HIERARCHY_GROUP_SIZE, 0),
            hierarchy_compression=os.environ.get(
                HOROVOD_HIERARCHY_COMPRESSION, "none"),
            elastic=_get_bool(HOROVOD_ELASTIC),
            cycle_pipeline_depth=_get_int(
                HOROVOD_CYCLE_PIPELINE_DEPTH, DEFAULT_CYCLE_PIPELINE_DEPTH
            ),
            fusion_bucket_quantum=_get_int(
                HOROVOD_FUSION_BUCKET_QUANTUM,
                DEFAULT_FUSION_BUCKET_QUANTUM_BYTES,
            ),
            flight_recorder=parse_flight_recorder(
                os.environ.get(HOROVOD_FLIGHT_RECORDER))[0],
            flight_recorder_capacity=parse_flight_recorder(
                os.environ.get(HOROVOD_FLIGHT_RECORDER))[1],
            flight_recorder_dir=os.environ.get(
                HOROVOD_FLIGHT_RECORDER_DIR, ""),
            straggler_report_seconds=_get_float(
                HOROVOD_STRAGGLER_REPORT_SECONDS,
                DEFAULT_STRAGGLER_REPORT_SECONDS,
            ),
            profile=(_get_bool(HOROVOD_PROFILE)
                     or os.environ.get(HOROVOD_PROFILE_DIR, "") != ""),
            profile_dir=os.environ.get(HOROVOD_PROFILE_DIR, ""),
            profile_history=_get_int(HOROVOD_PROFILE_HISTORY,
                                     DEFAULT_PROFILE_HISTORY),
            profile_jax=_get_bool(HOROVOD_PROFILE_JAX),
            debug_locks=_get_bool(HOROVOD_DEBUG_LOCKS),
            lock_hold_warn_seconds=_get_float(
                HOROVOD_LOCK_HOLD_WARN_SECONDS,
                DEFAULT_LOCK_HOLD_WARN_SECONDS),
        )


def parse_mesh_shape(value: str | None) -> tuple[int, int] | None:
    """Parse ``HOROVOD_MESH_SHAPE`` of the form "cross,local"."""
    if not value:
        return None
    parts = value.split(",")
    if len(parts) != 2:
        raise ValueError(
            f"{HOROVOD_MESH_SHAPE} must be 'cross,local', got {value!r}"
        )
    return int(parts[0]), int(parts[1])
