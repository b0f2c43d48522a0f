"""horovod_tpu — TPU-native distributed training framework.

A ground-up, TPU-first implementation of the capability surface of the
reference data-parallel framework (Horovod v0.18.1, surveyed in SURVEY.md):
wrap your optimizer, and named gradient tensors are averaged across workers
with bandwidth-optimal collectives — here XLA collectives
(``psum``/``all_gather``/``ppermute``) over ICI/DCN on a
``jax.sharding.Mesh``, instead of NCCL/MPI rings over GPUs.

Canonical usage (mirrors reference: examples/*.py):

    import horovod_tpu as hvd

    hvd.init()
    # scale learning rate by number of workers
    opt = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.size()))
    params = hvd.broadcast_parameters(params, root_rank=0)
    ...
    if hvd.rank() == 0:
        save_checkpoint(...)
"""

from horovod_tpu.version import __version__

# Load the metrics submodule BEFORE binding the hvd.metrics() API below:
# the first import of a submodule sets it as a package attribute, which
# would clobber the function whenever internal code lazily imported the
# module later. Loaded up front, the module sits in sys.modules (where
# `from horovod_tpu.metrics import ...` resolves it) and the function
# binding below stays the package attribute.
import horovod_tpu.metrics  # noqa: F401

from horovod_tpu.core.basics import (
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    mesh,
    metrics,
    is_homogeneous,
    mpi_built,
    gloo_built,
    nccl_built,
    ddl_built,
    mlsl_built,
    xla_built,
    mpi_enabled,
    mpi_threads_supported,
)
from horovod_tpu.core.mesh import CROSS_AXIS, GLOBAL_AXES, LOCAL_AXIS
from horovod_tpu.ops.collectives import (
    Average,
    Sum,
    Min,
    Max,
    Product,
    Handle,
    OrderedLaneError,
    allreduce,
    allreduce_async,
    assert_collective_lane_clear,
    allgather,
    allgather_async,
    alltoall,
    broadcast,
    broadcast_async,
    grouped_allreduce,
    grouped_allreduce_async,
    poll,
    reducescatter,
    stack_per_worker,
    synchronize,
)
from horovod_tpu.compression import Compression
from horovod_tpu.parallel.dp import (
    DistributedOptimizer,
    DistributedGradientTape,
    allreduce_gradients,
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_object,
)
from horovod_tpu.parallel.buckets import GradReleasePlan
from horovod_tpu.parallel.zero import (
    FlatAdamState,
    ShardedGrads,
    ShardedOptState,
    ShardedParams,
    gather_params,
    iter_param_buckets,
    scatter_gradients,
    shard_params,
    sharded_adamw,
    sharded_update,
)
from horovod_tpu.parallel.sparse import (
    SparseGrad,
    sparse_allgather,
    with_sparse_embedding_grad,
)
from horovod_tpu.parallel.ring import ring_attention
from horovod_tpu.parallel.ulysses import ulysses_attention
from horovod_tpu.parallel.tp import (
    params_shardings,
    tp_train_step,
    transformer_tp_rules,
    xla_attention,
)
from horovod_tpu.parallel.pp import (
    last_stage_value,
    pipeline_apply,
    stack_stage_params,
)
from horovod_tpu.parallel.ep import (
    default_capacity,
    load_balance_loss,
    switch_moe,
)
from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.flight_recorder import dump_debug_state
from horovod_tpu import profiler
from horovod_tpu import tracing
from horovod_tpu import checkpoint
from horovod_tpu import ckpt
from horovod_tpu import data
from horovod_tpu import elastic
from horovod_tpu import integrity
# `hvd.serve(model, params, ...)` is the API; the module stays reachable
# as `horovod_tpu.serve` via sys.modules for internal imports.
from horovod_tpu.serve import ServePolicy, serve
from horovod_tpu.exceptions import (
    CheckpointCorruptError,
    CollectiveIntegrityError,
    HorovodInternalError,
    HostsUpdatedInterrupt,
    NumericalError,
    WorkersDownError,
    WorkerLostError,
    WorkerStallError,
)

__all__ = [
    "__version__",
    # lifecycle / topology
    "init", "shutdown", "is_initialized",
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "mesh", "metrics", "is_homogeneous", "dump_debug_state", "profiler",
    "tracing",
    "CROSS_AXIS", "LOCAL_AXIS", "GLOBAL_AXES",
    # capability probes
    "mpi_built", "gloo_built", "nccl_built", "ddl_built", "mlsl_built",
    "xla_built", "mpi_enabled", "mpi_threads_supported",
    # collectives
    "Average", "Sum", "Min", "Max", "Product",
    "allreduce", "allreduce_async", "grouped_allreduce",
    "grouped_allreduce_async",
    "allgather", "allgather_async", "broadcast", "broadcast_async",
    "reducescatter", "alltoall", "stack_per_worker",
    "Handle", "poll", "synchronize",
    "OrderedLaneError", "assert_collective_lane_clear",
    # data-parallel API
    "DistributedOptimizer", "DistributedGradientTape", "allreduce_gradients",
    "broadcast_parameters", "broadcast_optimizer_state", "broadcast_object",
    "Compression",
    # bucket-wise gradient release (overlap allreduce with backward)
    "GradReleasePlan",
    # ZeRO-1/2/3 sharded training (TPU-first extension)
    "sharded_update", "sharded_adamw", "ShardedOptState", "FlatAdamState",
    "ShardedGrads", "ShardedParams", "scatter_gradients", "shard_params",
    "gather_params", "iter_param_buckets",
    # sparse/embedding gradients
    "SparseGrad", "sparse_allgather", "with_sparse_embedding_grad",
    # long-context / sequence parallelism (TPU-first extensions)
    "flash_attention", "ring_attention", "ulysses_attention",
    # tensor parallelism (TPU-first extension)
    "transformer_tp_rules", "params_shardings", "tp_train_step",
    "xla_attention",
    # pipeline parallelism (TPU-first extension)
    "pipeline_apply", "last_stage_value", "stack_stage_params",
    # expert parallelism / MoE (TPU-first extension)
    "switch_moe", "load_balance_loss", "default_capacity",
    # checkpoint / resume (rank-0 save + broadcast restore)
    "checkpoint",
    # crash-consistent sharded checkpointing (two-phase commit + replicas)
    "ckpt", "CheckpointCorruptError",
    "data",
    # elastic fault tolerance (reference: horovod.elastic)
    "elastic",
    "HorovodInternalError", "HostsUpdatedInterrupt",
    "WorkersDownError", "WorkerLostError", "WorkerStallError",
    # numerical integrity plane (digests / guards / rollback-and-replay)
    "integrity", "NumericalError", "CollectiveIntegrityError",
    # online serving plane (continuous batching; docs/inference.md)
    "serve", "ServePolicy",
]
