"""SDAR-30B-A3B-Chat's weights made from ``--seed`` on the device in one
jitted call, in the layout ``horovod_tpu.models.hybrid.HybridDecoder``
reads for ``full`` mixers with QK-norm, ``experts`` MLPs with a softmax
router and no shared expert, norms on the sublayers' inputs and an
untied head (the names below are flax's for those modules).

Every matrix is normal(0, 0.02) and every norm scale 1 + normal(0, 0.02)
(so that a path that dropped a scale would show), drawn in float32 and
rounded to ``param_dtype`` once: the program and the plain reference are
given the same rounded values and neither takes anything the other made.
Five kinds of parameter are not that (``benchmark/configs/
sdar-30b-a3b.json`` ``assumed`` says why):

* the embedding is normal(0, ``embed_std``), as run 1
  (``benchmark/weights_xing.py``'s scaling, for its reason: a router
  chooses, and where the eighth and the ninth score lie closer than
  bfloat16 resolves the program and the float32 reference take different
  experts; with a stream that starts at 1 one such choice moves a
  token's logits by a percent and not by their whole size), and the
  projections that write into the residual stream (attention's ``out``,
  the experts' ``down``) normal(0, ``residual_std``), as run the plain
  0.02: a block's masked positions all carry ONE embedding, [MASK]'s,
  so everything that tells them apart reaches the logits through the
  layers, which at 0.02 add about what the embedding put there;
* the ``q_norm`` / ``k_norm`` scales are ``qk_gain`` + normal(0, 0.02),
  as run 1.5: scores spread enough that a row attends tens of keys and
  not a thousand evenly, not so much that six layers of near-hard
  choices amplify bfloat16's rounding (the configuration's ``assumed``
  has both readings);
* the router's matrix is normal(0, 1 / sqrt(hidden)): router logits of a
  standard deviation near 1, a trained router's spread;
* the head's columns for the last ``tail_share`` of the vocabulary (the
  "tail") are normal(0, ``tail_std``) + ``tail_shift`` x u, u one unit
  direction of the hidden space: a position whose final hidden state
  leans along u lifts half the vocabulary together (by ``tail_shift``
  times a standard normal) without lifting its best token, which a tail
  token, three times narrower, almost never is. So positions differ in
  how much probability their tail holds, as a trained model's contexts
  differ in entropy, and the confidence ``softmax(logits)[argmax]`` is
  not the largest logit in another guise: with a plain normal head a
  position's log-sum-exp over 151,936 logits is the same to a hundredth
  everywhere, a sampler that ranked positions by their largest logit
  would choose as one that ranks by probability, and that planted fault
  (``benchmark/controls_sdar.py``) could not be told from a sound
  program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights_xing import is_residual_projection

STD = 0.02


def shapes(cfg):
    """{path tuple: shape} of the decoder's parameters."""
    d, heads, groups = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    width, experts, f = cfg["head_dim"], cfg["num_experts"], cfg["expert_d_ff"]
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("head",): (d, cfg["vocab_size"]),
           ("final_norm", "scale"): (d,)}
    for i in range(cfg["num_layers"]):
        layer = f"layer_{i}"
        out[(layer, "input_norm", "scale")] = (d,)
        out[(layer, "post_norm", "scale")] = (d,)
        mixer = (layer, "mixer")
        out[mixer + ("query", "kernel")] = (d, heads * width)
        out[mixer + ("key", "kernel")] = (d, groups * width)
        out[mixer + ("value", "kernel")] = (d, groups * width)
        out[mixer + ("q_norm", "scale")] = (width,)
        out[mixer + ("k_norm", "scale")] = (width,)
        out[mixer + ("out", "kernel")] = (heads * width, d)
        moe = (layer, "moe")
        out[moe + ("router",)] = (d, experts)
        out[moe + ("experts_gate",)] = (experts, d, f)
        out[moe + ("experts_up",)] = (experts, d, f)
        out[moe + ("experts_down",)] = (experts, f, d)
    return out


def count(cfg):
    """Number of parameters."""
    return sum(int(np.prod(s)) for s in shapes(cfg).values())


def _make(words, table, dtype, embed_std, residual_std, qk_gain, tail):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    tree = {}
    for index, (path, shape) in enumerate(table):
        noise = jax.random.normal(jax.random.fold_in(key, index), shape,
                                  jnp.float32)
        last = path[-1]
        if last == "scale":
            value = (qk_gain if path[-2] in ("q_norm", "k_norm") else 1.0) \
                + STD * noise
        elif last == "embedding":
            value = embed_std * noise
        elif is_residual_projection(path):
            value = residual_std * noise
        elif last == "router":
            value = noise * shape[0] ** -0.5
        elif last == "head":
            head_std, share, std, shift = tail
            d, vocab = shape
            u = jax.random.normal(jax.random.fold_in(key, len(table)), (d,),
                                  jnp.float32)
            u = u / jnp.linalg.norm(u)
            in_tail = jnp.arange(vocab) >= vocab - int(share * vocab)
            value = jnp.where(in_tail, std * noise + shift * u[:, None],
                              head_std * noise)
        else:
            value = STD * noise
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[last] = value.astype(dtype)
    return tree


@functools.lru_cache(maxsize=None)
def _maker(table, dtype, embed_std, residual_std, qk_gain, tail):
    return jax.jit(functools.partial(
        _make, table=table, dtype=dtype, embed_std=embed_std,
        residual_std=residual_std, qk_gain=qk_gain, tail=tail))


def make_params(cfg, seed):
    """The parameter tree for ``cfg`` from ``seed`` (any whole number: it
    is folded into the key as two 31-bit words), in ``param_dtype``."""
    table = tuple(sorted(shapes(cfg).items()))
    words = np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       np.uint32)
    return _maker(table, jnp.dtype(cfg["param_dtype"]),
                  float(cfg["embed_std"]), float(cfg["residual_std"]),
                  float(cfg["qk_gain"]),
                  (float(cfg["head_std"]), float(cfg["tail_share"]),
                   float(cfg["tail_std"]), float(cfg["tail_shift"])))(words)
