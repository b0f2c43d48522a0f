"""granite-4.0-h-small's weights made from ``--seed`` on the device in one
jitted call, in the layout ``horovod_tpu.models.hybrid.HybridDecoder``
reads for ``mamba2`` and ``full`` mixers, ``experts`` MLPs on every layer
and a tied head (the names below are flax's for those modules).

Drawn in float32 and rounded to ``param_dtype`` once: the program and the
plain reference are given the same rounded values and neither takes
anything the other made. Every matrix is normal(0, ``matrix_std``) - 0.02
as run; the toy sizes take 0.113 so that a projection of a normed row
has the spread it has at full width, 0.02 x sqrt(4096) = 1.28 = 0.113 x
sqrt(128), without which the toy's ``x``, ``B`` and ``C`` are a fifth of
the cell's and its recurrence a thirtieth - and every norm
scale 1 + normal(0, 0.02) (so that a path that dropped a scale would
show), but for what has to have a trained model's spread for the
mechanisms to matter (``benchmark/configs/granite-4.0-h-small.json``
``assumed`` has the arithmetic):

* the state-space layers' own parameters are the Mamba-2 reference
  initialisation: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse
  softplus of ``exp(U(log 1e-3, log 1e-1))``, ``D = 1``; the
  convolution's taps and bias normal(0, ``conv_std``), the variance of
  ``torch.nn.Conv1d``'s default at four taps (at 0.02 ``x``, ``B`` and
  ``C`` would be hundredths, the recurrence nothing beside the ``D x``
  skip, and no fault in a state could show);
* the router's matrix is normal(0, 1 / sqrt(hidden)): logits of a
  standard deviation near 1, a trained router's spread;
* the head is the embedding (``tie_word_embeddings``), so the stream's
  embedded part scores its own token sqrt(hidden) = 64 times what a
  random direction scores: with every matrix at 0.02 the ten layers add
  1.3 to a stream that starts at 12 x ``embed_std``, and the largest
  logit of every position is the input token's own, whatever the layers
  compute. A trained model's stream grows by orders of magnitude over
  its depth; here the matrices that write to the stream (``out_proj``,
  ``out``, every ``down``) are normal(0, ``residual_std``) so that the
  layers' sum is the stream at the head, and the final norm's scale has
  the mean ``final_norm_mean`` so that the logits spread as a trained
  model's do (a standard deviation near 1.6: ``embed_std`` x 64 x
  ``final_norm_mean`` / ``logits_scaling``). RMSNorm on every
  sublayer's input makes each sublayer indifferent to the stream's
  size, so none of this moves what a precision or a fault changes
  relative to what is right.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
# the matrices that write to the residual stream
RESIDUAL = {("out_proj", "kernel"), ("out", "kernel"), ("down", "kernel"),
            ("experts_down",)}


def shapes(cfg):
    """{path tuple: shape} of the decoder's parameters."""
    d, heads, groups = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    width, held, f = cfg["head_dim"], cfg["experts_count"], cfg["expert_d_ff"]
    ssm = cfg["ssm"]
    inner = ssm["num_heads"] * ssm["head_dim"]
    channels = inner + 2 * ssm["n_groups"] * ssm["d_state"]
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("final_norm", "scale"): (d,)}
    for i, kind in enumerate(cfg["mixers"]):
        layer = f"layer_{i}"
        out[(layer, "input_norm", "scale")] = (d,)
        out[(layer, "post_norm", "scale")] = (d,)
        mixer = (layer, "mixer")
        if kind == "mamba2":
            out[mixer + ("in_proj", "kernel")] = (
                d, inner + channels + ssm["num_heads"])
            out[mixer + ("conv_kernel",)] = (ssm["d_conv"], channels)
            out[mixer + ("conv_bias",)] = (channels,)
            for name in ("dt_bias", "A_log", "D"):
                out[mixer + (name,)] = (ssm["num_heads"],)
            out[mixer + ("norm", "scale")] = (inner,)
            out[mixer + ("out_proj", "kernel")] = (inner, d)
        else:
            out[mixer + ("query", "kernel")] = (d, heads * width)
            out[mixer + ("key", "kernel")] = (d, groups * width)
            out[mixer + ("value", "kernel")] = (d, groups * width)
            out[mixer + ("out", "kernel")] = (heads * width, d)
        moe = (layer, "moe")
        out[moe + ("router",)] = (d, cfg["num_experts"])
        out[moe + ("experts_gate",)] = (held, d, f)
        out[moe + ("experts_up",)] = (held, d, f)
        out[moe + ("experts_down",)] = (held, f, d)
        for name in ("gate", "up"):
            out[moe + ("shared", name, "kernel")] = (d, cfg["shared_d_ff"])
        out[moe + ("shared", "down", "kernel")] = (cfg["shared_d_ff"], d)
    return out


def count(cfg):
    """Number of parameters."""
    return sum(int(np.prod(s)) for s in shapes(cfg).values())


def _make(words, table, dtype, spread):
    embed_std, residual_std, final_norm_mean, conv_std, matrix_std = spread
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    tree = {}
    for index, (path, shape) in enumerate(table):
        k = jax.random.fold_in(key, index)
        noise = jax.random.normal(k, shape, jnp.float32)
        last = path[-1]
        if path[:2] == ("final_norm", "scale"):
            value = final_norm_mean * (1.0 + STD * noise)
        elif last == "scale":
            value = 1.0 + STD * noise
        elif last == "embedding":
            value = embed_std * noise
        elif last == "router":
            value = noise * shape[0] ** -0.5
        elif last in ("conv_kernel", "conv_bias"):
            value = conv_std * noise
        elif last == "A_log":
            value = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            np.log(1e-3), np.log(1e-1)))
            value = dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1(dt)
        elif last == "D":
            value = jnp.ones(shape)
        elif path[-2:] in RESIDUAL or path[-1:] in RESIDUAL:
            value = residual_std * noise
        else:
            value = matrix_std * noise
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[last] = value.astype(dtype)
    return tree


@functools.lru_cache(maxsize=None)
def _maker(table, dtype, spread):
    return jax.jit(functools.partial(_make, table=table, dtype=dtype,
                                     spread=spread))


def make_params(cfg, seed):
    """The parameter tree for ``cfg`` from ``seed`` (any whole number: it
    is folded into the key as two 31-bit words), in ``param_dtype``."""
    table = tuple(sorted(shapes(cfg).items()))
    words = np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       np.uint32)
    return _maker(table, jnp.dtype(cfg["param_dtype"]), (
        float(cfg["embed_std"]), float(cfg["residual_std"]),
        float(cfg["final_norm_mean"]), float(cfg["conv_std"]),
        float(cfg["matrix_std"])))(words)
