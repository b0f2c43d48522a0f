"""K-EXAONE-236B-A23B's weights made from ``--seed`` on the device in one
jitted call, in the layout ``horovod_tpu.models.hybrid.HybridDecoder``
reads for ``window`` and ``full`` mixers, ``experts`` MLPs and norms on
the sublayers' outputs (the names below are flax's for those modules).

Every matrix is normal(0, 0.02) and every norm scale 1 + normal(0, 0.02)
(so that a path that dropped a scale would show), drawn in float32 and
rounded to ``param_dtype`` once: the program and the plain reference are
given the same rounded values and neither takes anything the other made.
Of the scalings ``benchmark/weights_xing.py`` found necessary, so that
one expert chosen otherwise than in the float32 reference moves a logit
by a few percent and not by its whole size, this model keeps two and
needs no third (``benchmark/configs/k-exaone-236b-a23b.json`` ``assumed``
says why):

* the embedding is normal(0, ``embed_std``), as run 1,
  ``torch.nn.Embedding``'s default: every sublayer's output passes an
  RMSNorm before it is added, so each adds a vector of size 1 whatever
  its matrices' scale, and a stream that started at 0.02 would be
  nothing beside the first of them;
* the router's matrix is normal(0, 1 / sqrt(hidden)): router logits of a
  standard deviation near 1, a trained router's spread; its correction
  bias ``b`` is 0;
* not kept: the scaled residual projections (``residual_std``). The norm
  on a sublayer's output divides any such scale out again, so attention's
  ``out`` and every ``down`` are normal(0, 0.02) like the rest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


def is_dense(cfg, i):
    """Whether layer ``i`` as run is one of the published leading dense
    layers."""
    return cfg["layer_indices"][i] < cfg["first_dense"]


def shapes(cfg):
    """{path tuple: shape} of the decoder's parameters."""
    d, heads, groups = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    width, held, f = cfg["head_dim"], cfg["experts_count"], cfg["expert_d_ff"]
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("head",): (d, cfg["vocab_size"]),
           ("final_norm", "scale"): (d,)}
    for i in range(cfg["num_layers"]):
        layer = f"layer_{i}"
        out[(layer, "mixer_norm", "scale")] = (d,)
        out[(layer, "mlp_norm", "scale")] = (d,)
        mixer = (layer, "mixer")
        out[mixer + ("query", "kernel")] = (d, heads * width)
        out[mixer + ("key", "kernel")] = (d, groups * width)
        out[mixer + ("value", "kernel")] = (d, groups * width)
        out[mixer + ("q_norm", "scale")] = (width,)
        out[mixer + ("k_norm", "scale")] = (width,)
        out[mixer + ("out", "kernel")] = (heads * width, d)
        if is_dense(cfg, i):
            mlp, inner = (layer, "mlp"), cfg["d_ff"]
        else:
            moe = (layer, "moe")
            out[moe + ("router",)] = (d, cfg["num_experts"])
            out[moe + ("router_bias",)] = (cfg["num_experts"],)
            out[moe + ("experts_gate",)] = (held, d, f)
            out[moe + ("experts_up",)] = (held, d, f)
            out[moe + ("experts_down",)] = (held, f, d)
            mlp, inner = moe + ("shared",), cfg["shared_experts"] * f
        for name in ("gate", "up"):
            out[mlp + (name, "kernel")] = (d, inner)
        out[mlp + ("down", "kernel")] = (inner, d)
    return out


def count(cfg):
    """Number of parameters."""
    return sum(int(np.prod(s)) for s in shapes(cfg).values())


def _make(words, table, dtype, embed_std):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    tree = {}
    for index, (path, shape) in enumerate(table):
        noise = jax.random.normal(jax.random.fold_in(key, index), shape,
                                  jnp.float32)
        last = path[-1]
        if last == "scale":
            value = 1.0 + STD * noise
        elif last == "embedding":
            value = embed_std * noise
        elif last == "router":
            value = noise * shape[0] ** -0.5
        elif last == "router_bias":
            value = jnp.zeros(shape)
        else:
            value = STD * noise
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[last] = value.astype(dtype)
    return tree


@functools.lru_cache(maxsize=None)
def _maker(table, dtype, embed_std):
    return jax.jit(functools.partial(_make, table=table, dtype=dtype,
                                     embed_std=embed_std))


def make_params(cfg, seed):
    """The parameter tree for ``cfg`` from ``seed`` (any whole number: it
    is folded into the key as two 31-bit words), in ``param_dtype``."""
    table = tuple(sorted(shapes(cfg).items()))
    words = np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       np.uint32)
    return _maker(table, jnp.dtype(cfg["param_dtype"]),
                  float(cfg["embed_std"]))(words)
