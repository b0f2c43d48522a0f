"""Pallas TPU kernels for the framework's hot ops."""

from horovod_tpu.ops.pallas.flash_attention import (
    attention_reference,
    flash_attention,
    flash_attention_partial,
    merge_partials,
)
from horovod_tpu.ops.pallas.fused_optimizer import flat_adamw_shard
from horovod_tpu.ops.pallas.conv_bn_act import (
    FusedBatchNormAct,
    bn_stats,
    scale_bias_act,
)

__all__ = [
    "flash_attention",
    "flash_attention_partial",
    "merge_partials",
    "attention_reference",
    "flat_adamw_shard",
    "FusedBatchNormAct",
    "bn_stats",
    "scale_bias_act",
]
