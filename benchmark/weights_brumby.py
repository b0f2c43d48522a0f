"""Brumby-14B-Base's weights made from ``--seed`` on the device in one
jitted call, in the layout ``horovod_tpu.models.hybrid.HybridDecoder``
reads for ``power_retention`` layers (the names below are flax's for
that module).

Every matrix and the embedding is normal(0, 0.02) and every norm scale
1 + normal(0, 0.02) (so that a path that dropped a scale would show),
drawn in float32 and rounded to bfloat16 once: the program and the plain
reference are given the same rounded values and neither takes anything
the other made.

The gate's bias is the one parameter that is not centred on 0: key/value
head ``j`` of ``n`` has ``GATE_BIAS[0] + (GATE_BIAS[1] - GATE_BIAS[0])
j / (n - 1)`` plus normal(0, 0.02). With a bias of 0 every gate would
sit near 1/2 and a state would forget all but its last few tokens, so
that neither a lost state nor a state taken after the padding could be
told from a sound one a few tokens later. With these the heads' gates
lie about sigmoid(1) = 0.73 to sigmoid(7) = 0.9991 (the gate's matrix
adds about +-1.4 to the logit from token to token): memories of a few
tokens up to a thousand and more, as a trained model's heads spread
theirs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
GATE_BIAS = (1.0, 7.0)


def shapes(cfg):
    """{path tuple: shape} of the decoder's parameters."""
    d, ff, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    heads, groups = cfg["num_heads"], cfg["num_kv_heads"]
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("head",): (d, cfg["vocab_size"]),
           ("final_norm", "scale"): (d,)}
    for i in range(cfg["num_layers"]):
        layer = f"layer_{i}"
        out[(layer, "input_norm", "scale")] = (d,)
        out[(layer, "post_norm", "scale")] = (d,)
        mixer = (layer, "mixer")
        out[mixer + ("query", "kernel")] = (d, heads * hd)
        out[mixer + ("key", "kernel")] = (d, groups * hd)
        out[mixer + ("value", "kernel")] = (d, groups * hd)
        out[mixer + ("gate", "kernel")] = (d, groups)
        out[mixer + ("gate", "bias")] = (groups,)
        out[mixer + ("out", "kernel")] = (heads * hd, d)
        out[mixer + ("q_norm", "scale")] = (hd,)
        out[mixer + ("k_norm", "scale")] = (hd,)
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
        elif path[-1] == "bias":
            value = value + jnp.linspace(*GATE_BIAS, shape[0])
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
