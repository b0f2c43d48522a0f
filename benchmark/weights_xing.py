"""Xing4.0-29B-A4B's weights made from ``--seed`` on the device in one
jitted call, in the layout ``horovod_tpu.models.hybrid.HybridDecoder``
reads for ``latent`` mixers, ``experts`` MLPs and four residual streams
(the names below are flax's for those modules).

Every matrix is normal(0, 0.02) and every norm scale 1 + normal(0, 0.02)
(so that a path that dropped a scale would show), drawn in float32 and
rounded to ``param_dtype`` once: the program and the plain reference are
given the same rounded values and neither takes anything the other made.
Six kinds of parameter are not that (``benchmark/configs/
xing4-29b-a4b.json`` ``assumed`` says why):

* the embedding is normal(0, ``embed_std``), as run 1,
  ``torch.nn.Embedding``'s default, where this repository's other
  generators use 0.02. A router
  chooses, and a choice is not continuous: where the fourth and the
  fifth score lie closer than bfloat16 resolves, the program and the
  float32 reference take different experts. With streams that start at
  0.02 a sublayer's output (0.3) is fifteen times its input, one flipped
  expert changes a token's streams by a fifth, every later router then
  flips too, and the bfloat16 program lies as far from the reference as
  the float8 control does (on the chip: ``served_logit_gap`` 2.26 with
  12% of the served tokens not the reference's first; PERF.md, PR 34).
  With streams that start at 1, as large as the sublayers' outputs
  together, a flip moves a token's streams by a few percent and the
  later routers mostly hold, as in a trained model, whose residual
  stream dwarfs any one expert's output. The toy sizes of the CPU tests
  keep 0.02 (8 experts and a few hundred tokens meet few near ties):
  there the layers are most of the logits, and each piece of their
  mathematics left out shows;
* the projections that write into the residual streams (attention's
  ``out``, every ``down``, the experts' among them) are normal(0,
  ``residual_std``), as run 0.02 / sqrt(2 x 40 published layers) =
  0.00224: the scaled initialisation of residual projections (GPT-2,
  Megatron), for the same reason - a sublayer's output is then a tenth
  of the streams it is added to, one flipped expert moves them by a few
  percent, and the twelve sublayers together are still two fifths of
  what the head sees. The toy sizes keep 0.02;
* the router's matrix is normal(0, 1 / sqrt(hidden)): router logits of a
  standard deviation near 1, a trained router's spread (scores 0.1-0.9;
  much smaller and every score is 0.5 and the choice of four is
  rounding); its correction bias ``b`` is 0;
* a hyper-connection's ``alpha`` (``a_pre``, ``a_post``, ``a_res``) is
  0.01, as the paper initialises;
* its ``phi`` is normal(0, ``DYNAMIC`` / (0.01 sqrt(streams hidden))), so
  that ``alpha`` times the projection of the normalised streams has a
  standard deviation of ``DYNAMIC``: the dynamic part then moves the
  three maps visibly from token to token (at the paper's zero
  initialisation they would be constants, and a program that skipped the
  projection could not be told from a sound one);
* its ``bias``: 0 for ``H_pre`` and ``H_post`` (a sigmoid at 1/2), and for
  ``H_res`` ``RES_DIAGONAL`` on the diagonal and 0 off it (a dominant
  diagonal: each stream keeps most of itself).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
ALPHA = 0.01
DYNAMIC = 1.0
RES_DIAGONAL = 2.0


def is_dense(cfg, i):
    """Whether layer ``i`` as run is one of the published leading dense
    layers."""
    return cfg["layer_indices"][i] < cfg["first_dense"]


def shapes(cfg):
    """{path tuple: shape} of the decoder's parameters."""
    d, heads, n = cfg["d_model"], cfg["num_heads"], cfg["streams"]
    qk = cfg["nope_dim"] + cfg["rope_dim"]
    held, f = cfg["experts_count"], cfg["expert_d_ff"]
    out = {("token_embed", "embedding"): (cfg["vocab_size"], d),
           ("head",): (d, cfg["vocab_size"]),
           ("final_norm", "scale"): (d,)}
    for i in range(cfg["num_layers"]):
        layer = f"layer_{i}"
        out[(layer, "input_norm", "scale")] = (d,)
        out[(layer, "post_norm", "scale")] = (d,)
        mixer = (layer, "mixer")
        out[mixer + ("q_a", "kernel")] = (d, cfg["q_rank"])
        out[mixer + ("q_norm", "scale")] = (cfg["q_rank"],)
        out[mixer + ("q_b", "kernel")] = (cfg["q_rank"], heads * qk)
        out[mixer + ("kv_a", "kernel")] = (d, cfg["kv_rank"]
                                           + cfg["rope_dim"])
        out[mixer + ("kv_norm", "scale")] = (cfg["kv_rank"],)
        out[mixer + ("kv_b",)] = (cfg["kv_rank"], heads,
                                  cfg["nope_dim"] + cfg["v_dim"])
        out[mixer + ("out", "kernel")] = (heads * cfg["v_dim"], d)
        if is_dense(cfg, i):
            mlps = [((layer, "mlp"), cfg["d_ff"])]
        else:
            moe = (layer, "moe")
            out[moe + ("router",)] = (d, cfg["num_experts"])
            out[moe + ("router_bias",)] = (cfg["num_experts"],)
            out[moe + ("experts_gate",)] = (held, d, f)
            out[moe + ("experts_up",)] = (held, d, f)
            out[moe + ("experts_down",)] = (held, f, d)
            mlps = [(moe + ("shared",), cfg["shared_experts"] * f)]
        for path, width in mlps:
            for name in ("gate", "up"):
                out[path + (name, "kernel")] = (d, width)
            out[path + ("down", "kernel")] = (width, d)
        for name in ("hyper_mixer", "hyper_mlp"):
            out[(layer, name, "phi")] = (n * d, n * n + 2 * n)
            out[(layer, name, "alpha")] = (3,)
            out[(layer, name, "bias")] = (n * n + 2 * n,)
    return out


def count(cfg):
    """Number of parameters."""
    return sum(int(np.prod(s)) for s in shapes(cfg).values())


def is_residual_projection(path):
    """Attention's ``out`` and every ``down`` (the experts' among them)."""
    return path[-1] == "experts_down" or (
        path[-1] == "kernel" and path[-2] in ("out", "down"))


def _make(words, table, dtype, embed_std, residual_std):
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
        elif is_residual_projection(path):
            value = residual_std * noise
        elif last == "router":
            value = noise * shape[0] ** -0.5
        elif last == "router_bias":
            value = jnp.zeros(shape)
        elif last == "alpha":
            value = jnp.full(shape, ALPHA)
        elif last == "phi":
            value = noise * (DYNAMIC / (ALPHA * shape[0] ** 0.5))
        elif last == "bias":          # a hyper-connection's static part
            n = int(round((1 + shape[0]) ** 0.5)) - 1   # n^2 + 2n entries
            value = jnp.concatenate([
                jnp.zeros((2 * n,)), RES_DIAGONAL * jnp.eye(n).reshape(-1)])
        else:
            value = STD * noise
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[last] = value.astype(dtype)
    return tree


@functools.lru_cache(maxsize=None)
def _maker(table, dtype, embed_std, residual_std):
    return jax.jit(functools.partial(
        _make, table=table, dtype=dtype, embed_std=embed_std,
        residual_std=residual_std))


def make_params(cfg, seed):
    """The parameter tree for ``cfg`` from ``seed`` (any whole number: it
    is folded into the key as two 31-bit words), in ``param_dtype``."""
    table = tuple(sorted(shapes(cfg).items()))
    words = np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       np.uint32)
    return _maker(table, jnp.dtype(cfg["param_dtype"]),
                  float(cfg["embed_std"]), float(cfg["residual_std"]))(words)
