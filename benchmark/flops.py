"""Operations and bytes computed from shapes, and the table of peaks.

The arithmetic of the model's training FLOPs is ``bench.py``'s
(``transformer_main``), copied so that no later PR can change the
yardstick: 6 x parameters per token for the forward and backward
matrix multiplications, plus the attention term 12 x layers x seq x
d_model per token (forward and backward of QK^T and PV), of which a
causal model needs half. Recomputed operations do not count.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of ``device_kind``; an unknown kind raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    for prefix in sorted(table, key=len, reverse=True):
        if device_kind.startswith(prefix):
            return table[prefix]
    raise KeyError(f"no published peak for device kind {device_kind!r}: "
                   f"add it to benchmark/peaks.json with its source")


def train_flops_per_token(n_params, num_layers, d_model, seq, causal):
    attention = 12 * num_layers * seq * d_model
    return 6 * n_params + (attention // 2 if causal else attention)


# matrix multiplications per flash kernel, each 2*seq*seq*head_dim
# operations per (row, head): forward S=QK^T, O=PV; dq kernel S, dP=dO V^T,
# dQ=dS K; dkv kernel S, dP, dV=P^T dO, dK=dS^T Q
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_flops(kind, batch, heads, seq, head_dim, causal):
    """Operations one call of a flash kernel needs (the causal half of
    the score matrix where ``causal``: the masked half is not needed)."""
    full = FLASH_MATMULS[kind] * 2 * batch * heads * seq * seq * head_dim
    return full // 2 if causal else full


def flash_bytes(kind, batch, heads, seq, head_dim, itemsize=2):
    """Bytes one call has to move at the least: each operand read once
    and each result written once (bfloat16 tensors of ``batch x heads x
    seq x head_dim``; the float32 row statistics are left out)."""
    tensors = {"fwd": 4,   # q k v -> o
               "dq": 5,    # q k v do -> dq
               "dkv": 6}[kind]  # q k v do -> dk dv
    return tensors * batch * heads * seq * head_dim * itemsize
