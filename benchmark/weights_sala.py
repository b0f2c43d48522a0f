"""MiniCPM-SALA's weights made from ``--seed`` on the device in one
jitted call, in the layout ``horovod_tpu.models.hybrid.HybridDecoder``
reads (the names below are flax's for that module).

Every matrix and the embedding is normal(0, 0.02) and every norm scale
1 + normal(0, 0.02) (so that a path that dropped a scale would show),
drawn in float32 and rounded to bfloat16 once: the program and the plain
reference are given the same rounded values and neither takes anything
the other made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
LIGHTNING = "lightning-attn"


def shapes(cfg):
    """{path tuple: shape} of the decoder's parameters."""
    d, ff, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    heads, groups = cfg["num_heads"], cfg["num_kv_heads"]
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("head",): (d, cfg["vocab_size"]),
           ("final_norm", "scale"): (d,)}
    for i, kind in enumerate(cfg["mixer_types"]):
        layer, kv = f"layer_{i}", heads if kind == LIGHTNING else groups
        out[(layer, "input_norm", "scale")] = (d,)
        out[(layer, "post_norm", "scale")] = (d,)
        mixer = (layer, "mixer")
        out[mixer + ("query", "kernel")] = (d, heads * hd)
        out[mixer + ("key", "kernel")] = (d, kv * hd)
        out[mixer + ("value", "kernel")] = (d, kv * hd)
        out[mixer + ("gate", "kernel")] = (d, heads * hd)
        out[mixer + ("out", "kernel")] = (heads * hd, d)
        for norm in ("q_norm", "k_norm") + (
                ("o_norm",) if kind == LIGHTNING else ()):
            out[mixer + (norm, "scale")] = (hd,)
        for name in ("gate", "up"):
            out[(layer, "mlp", name, "kernel")] = (d, ff)
        out[(layer, "mlp", "down", "kernel")] = (ff, d)
    return out


def count(cfg):
    """Number of parameters."""
    return sum(int(np.prod(s)) for s in shapes(cfg).values())


def _make(words, table, dtype):
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
        node[path[-1]] = value.astype(dtype)
    return tree


@functools.lru_cache(maxsize=None)
def _maker(table, dtype):
    return jax.jit(functools.partial(_make, table=table, dtype=dtype))


def make_params(cfg, seed):
    """The parameter tree for ``cfg`` from ``seed`` (any whole number: it
    is folded into the key as two 31-bit words), in ``param_dtype``."""
    table = tuple(sorted(shapes(cfg).items()))
    words = np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       np.uint32)
    return _maker(table, jnp.dtype(cfg["param_dtype"]))(words)
