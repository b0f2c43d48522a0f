"""Weights made from ``--seed`` on the device in one jitted call.

The benchmark makes the parameter tree itself, in the layout
``horovod_tpu.models.transformer.Transformer`` reads (the names below are
flax's for that module), so that the program and the plain reference are
both *given* the same weights and neither takes anything the other made.
Parameters are float32, as the configurations state (bfloat16 is the
compute type; the program casts at use).

Every matrix and embedding is normal(0, 0.02) (GPT-2's and BERT's
published initializer range); biases are normal(0, 0.02) and LayerNorm
scales 1 + normal(0, 0.02) rather than the customary 0 and 1, so that a
path that dropped a bias or a scale would show in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


def shapes(cfg):
    """{path tuple: shape} of the trunk's parameters."""
    d, h, ff = cfg["d_model"], cfg["num_heads"], cfg["d_ff"]
    hd = d // h
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("pos_embed",): (cfg["max_seq"], d)}
    for norm in ("final_norm",):
        out[(norm, "scale")] = out[(norm, "bias")] = (d,)
    for i in range(cfg["num_layers"]):
        layer = f"layer_{i}"
        for norm in ("LayerNorm_0", "LayerNorm_1"):
            out[(layer, norm, "scale")] = out[(layer, norm, "bias")] = (d,)
        for name in ("query", "key", "value"):
            out[(layer, "attention", name, "kernel")] = (d, h, hd)
            out[(layer, "attention", name, "bias")] = (h, hd)
        out[(layer, "attention", "out", "kernel")] = (h, hd, d)
        out[(layer, "attention", "out", "bias")] = (d,)
        out[(layer, "mlp", "wi", "kernel")] = (d, ff)
        out[(layer, "mlp", "wi", "bias")] = (ff,)
        out[(layer, "mlp", "wo", "kernel")] = (ff, d)
        out[(layer, "mlp", "wo", "bias")] = (d,)
    return out


def count(cfg, vocab_size=None):
    """Number of parameters, with the embedding at ``vocab_size`` rows
    (the published vocabulary, where the table as run is padded)."""
    total = sum(int(np.prod(s)) for s in shapes(cfg).values())
    if vocab_size is not None:
        total -= (cfg["vocab_size"] - vocab_size) * cfg["d_model"]
    return total


def _make(words, table):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    tree = {}
    for index, (path, shape) in enumerate(table):
        value = STD * jax.random.normal(jax.random.fold_in(key, index),
                                        shape, jnp.float32)
        if path[-1] == "scale":
            value = 1.0 + value
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return tree


@functools.lru_cache(maxsize=None)
def _maker(table, sharding):
    return jax.jit(functools.partial(_make, table=table),
                   out_shardings=sharding)


def make_params(cfg, seed, sharding=None):
    """The parameter tree for ``cfg`` from ``seed`` (any whole number: it
    is folded into the key as two 31-bit words), placed by ``sharding``."""
    table = tuple(sorted(shapes(cfg).items()))
    words = np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       np.uint32)
    return _maker(table, sharding)(words)
