"""Bytes that a pass of a block-diffusion model's serving step requires,
whatever implements it (beside ``benchmark/flops.py``, which an accepted
benchmark may not have edited).

A pass runs ``block_len`` positions a row against the row's cache. At
the least it reads every layer's attention matrices, the three matrices
of every expert that some position of the pass chose and the router,
the head (a pass's sampler needs every position's logits), the final
norm aside, each once in the weights' dtype; and the key and the value
of every position its rows attend, once each (``benchmark/
flops_kexaone.py`` ``grouped_decode_bytes``: a block's ``block_len``
queries share them, which is what a block buys over one token a step).
It writes ``block_len`` columns a row a leaf, thousandths of that. With
``slots x block_len x top_k`` pairs a pass over ``num_experts`` experts
(2,048 over 128 in the cell) every expert is hit by nearly every pass,
at 16 pairs an expert: far under the 240 operations a byte at which the
chip's matrix unit would bound them, so a pass is bound by these bytes.
The experts hit and the positions attended come from the program's
counters, never from the shapes.
"""

from __future__ import annotations

from benchmark import flops_kexaone, flops_xing


def attention_weight_bytes(d_model, heads, kv_heads, head_dim, itemsize=2):
    """Bytes of one layer's query, key, value and output matrices."""
    return (2 * heads + 2 * kv_heads) * head_dim * d_model * itemsize


def head_bytes(d_model, vocab_size, itemsize=2):
    """Bytes of the untied head."""
    return d_model * vocab_size * itemsize


def pass_bytes(cfg, experts_hit, positions_attended, itemsize=2):
    """Bytes one pass reads at the least: ``experts_hit`` (expert, layer)
    pairs some position chose, ``positions_attended`` positions summed
    over the rows and the layers' leaves of one kind."""
    layers = cfg["num_layers"]
    return (layers * attention_weight_bytes(
        cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
        cfg["head_dim"], itemsize)
        + flops_xing.moe_decode_bytes(
            1, experts_hit, layers, cfg["d_model"], cfg["expert_d_ff"],
            cfg["shared_experts"], cfg["num_experts"], itemsize)
        + head_bytes(cfg["d_model"], cfg["vocab_size"], itemsize)
        + flops_kexaone.grouped_decode_bytes(
            positions_attended, cfg["num_kv_heads"], cfg["head_dim"],
            itemsize))


def block_write_bytes(rows, block_len, kv_heads, head_dim, itemsize=2):
    """Bytes one layer's pass writes into the cache: a key and a value
    column a position of every row's block."""
    return rows * block_len * 2 * kv_heads * head_dim * itemsize
