"""The plain reference: the transformer trunk, its two losses, their
gradients and AdamW, in straightforward ``jax.numpy`` float32.

It imports nothing of ``horovod_tpu`` and takes nothing the program has
made: the weights come from ``benchmark/weights.py`` (made from the
seed), the batches from ``benchmark/traffic.py``. It follows the trunk
as the program runs it (``trunk`` in the configuration file: pre-LayerNorm
blocks, tanh GELU, LayerNorm epsilon 1e-6, learned positions, tied head,
float32 logits) - the departures from the published GPT-2 / BERT
descriptions are listed under ``assumed`` in each configuration file.

``precision`` selects how every matrix multiplication of the dense
layers and of the tied head is computed:

* ``f32``  - float32 operands, ``Precision.HIGHEST`` (the reference).
* ``fp8``  - the customary float8 recipe: operands scaled per tensor into
  float8_e4m3fn's range and rounded to it, the cotangent that enters the
  two backward multiplications scaled and rounded to float8_e5m2, float32
  accumulation. This is the *control*: the nearest precision below the
  bfloat16 the configurations state.
  ``benchmark/tools/calibrate.py`` reads it at the cells' own sizes and
  ``benchmark/tests/test_correct.py`` keeps it at a small one; a cell's
  limits (``benchmark/limits/<cell>.json``) lie between what sound runs
  of the program read and what this control reads, and PERF.md section 2
  gives the readings each limit was set from.

The attention scores and the softmax, the LayerNorms and the loss stay
float32 in every mode (an fp8 recipe keeps them wide too).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def _round_trip(x, dtype, top):
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _quantize_fp8(x):
    """Per-tensor scaled round trip through float8_e4m3fn; the gradient
    passes straight through."""
    return x + jax.lax.stop_gradient(
        _round_trip(x, jnp.float8_e4m3fn, E4M3_MAX) - x)


@jax.custom_vjp
def _quantize_cotangent(x):
    """Identity whose cotangent is rounded through float8_e5m2."""
    return x


_quantize_cotangent.defvjp(
    lambda x: (x, None),
    lambda _, g: (_round_trip(g, jnp.float8_e5m2, E5M2_MAX),))


def _einsum(precision):
    """The matrix multiplication of the dense layers in ``precision``."""
    if precision == "f32":
        return functools.partial(jnp.einsum, precision=HIGHEST)
    if precision == "fp8":
        def mm(eq, a, b):
            return _quantize_cotangent(jnp.einsum(
                eq, _quantize_fp8(a), _quantize_fp8(b), precision=HIGHEST))
        return mm
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, causal):
    """softmax(q k^T / sqrt(d)) v over (batch, seq, heads, head_dim)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * scale
    if causal:
        n = q.shape[1]
        keep = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(keep[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def _block(x, p, cfg, mm):
    """One pre-LayerNorm block: x + Attn(LN(x)); x + MLP(LN(x))."""
    eps = cfg["trunk"]["layer_norm_eps"]
    a = p["attention"]
    h = _layer_norm(x, p["LayerNorm_0"], eps)
    q, k, v = (mm("bsd,dhe->bshe", h, a[n]["kernel"]) + a[n]["bias"]
               for n in ("query", "key", "value"))
    o = _attention(q, k, v, cfg["causal"])
    x = x + mm("bshe,hed->bsd", o, a["out"]["kernel"]) + a["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"], eps)
    m = p["mlp"]
    h = _gelu_tanh(mm("bsd,df->bsf", h, m["wi"]["kernel"]) + m["wi"]["bias"])
    return x + mm("bsf,fd->bsd", h, m["wo"]["kernel"]) + m["wo"]["bias"]


def forward(params, tokens, cfg, precision="f32"):
    """Logits (batch, seq, vocab) float32 of the trunk over ``tokens``.

    The layers are alike, so they run as one ``lax.scan`` over their
    stacked parameters: the same arithmetic as a Python loop, compiled
    once instead of ``num_layers`` times (the compile is paid by every
    first run of every cell). Under a gradient each block is
    rematerialised (``jax.checkpoint``), so that the float32 residuals of
    24 layers of BERT-Large do not have to fit beside nothing: the
    backward pass recomputes a block's forward, which changes no value."""
    mm = _einsum(precision)
    seq = tokens.shape[1]
    table = params["token_embed"]["embedding"]
    x = table[tokens] + params["pos_embed"][:seq][None]
    layers = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"layer_{i}"] for i in range(cfg["num_layers"])])
    block = jax.checkpoint(lambda x, p: _block(x, p, cfg, mm))
    x, _ = jax.lax.scan(lambda x, p: (block(x, p), None), x, layers)
    x = _layer_norm(x, params["final_norm"], cfg["trunk"]["layer_norm_eps"])
    return mm("bsd,vd->bsv", x, table)


def _cross_entropy(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked


def loss_sum(params, inputs, labels, cfg, objective, precision):
    """Sum (not mean) of the objective's per-token losses over these
    rows, so that blocks of rows add up; the caller divides by
    :func:`loss_count` of the whole batch."""
    logits = forward(params, inputs, cfg, precision)
    if objective == "causal_lm":
        return jnp.sum(_cross_entropy(logits[:, :-1], labels[:, 1:]))
    if objective == "masked_lm":
        ids, mask = labels
        return jnp.sum(_cross_entropy(logits, ids) * mask.astype(jnp.float32))
    raise ValueError(f"unknown objective {objective!r}")


def loss_count(labels, objective):
    if objective == "causal_lm":
        return float(labels.shape[0] * (labels.shape[1] - 1))
    return max(float(np.sum(labels[1])), 1.0)


# optax.adamw's defaults written out
ADAM_B1, ADAM_B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


def adamw_update(params, mu, nu, grads, lr, c1, c2):
    """One AdamW step with decoupled weight decay; ``c1``/``c2`` are the
    bias corrections ``1 - b**t`` of step ``t``."""
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                      mu, grads)
    nu = jax.tree.map(lambda n, g: ADAM_B2 * n + (1 - ADAM_B2) * g * g,
                      nu, grads)
    params = jax.tree.map(
        lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + ADAM_EPS)
                                  + WEIGHT_DECAY * p),
        params, mu, nu)
    return params, mu, nu


class _Frozen(dict):
    """A configuration that can be a static argument of ``jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


_grad = jax.jit(jax.value_and_grad(loss_sum), static_argnums=(3, 4, 5))
_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
_sub = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
_scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t))
_norms = jax.jit(lambda t: jax.tree.map(
    lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
_update = jax.jit(adamw_update)


CHUNKS = 64


def _chunk_sums(tree):
    def sums(x):
        flat = x.astype(jnp.float32).ravel()
        flat = jnp.pad(flat, (0, -flat.size % CHUNKS))
        return flat.reshape(CHUNKS, -1).sum(axis=1)
    return jax.tree.map(sums, tree)


chunk_sums_on_device = jax.jit(_chunk_sums)


def chunk_sums(tree):
    """{path: the sums of the leaf's ``CHUNKS`` contiguous chunks}, as
    host arrays. Two gradients' chunk sums differ by the sums of their
    element-wise error, so the root of the summed squares of those
    differences estimates the norm of the error (64 degrees of freedom a
    leaf) without either side holding the other's tree."""
    flat = jax.tree_util.tree_leaves_with_path(
        jax.device_get(chunk_sums_on_device(tree)))
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def leaf_norms(tree):
    """{path: l2 norm} of every leaf, as host floats."""
    flat = jax.tree_util.tree_leaves_with_path(jax.device_get(_norms(tree)))
    return {jax.tree_util.keystr(k): float(n) for k, n in flat}


def follow_steps(params, batches, cfg, objective, lr, precision="f32",
                 block_rows=4):
    """Drive the reference through ``batches`` (a list of ``(inputs,
    labels)`` host arrays), ``block_rows`` rows at a time so that it fits
    on one chip beside nothing else.

    Returns each step's loss, the per-leaf norms and chunk sums of the
    first step's gradient, and the per-leaf norms of the parameters'
    change after the last step."""
    cfg = _Frozen(cfg)
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for step, (inputs, labels) in enumerate(batches, start=1):
        total, grads = 0.0, None
        for lo in range(0, inputs.shape[0], block_rows):
            cut = lambda a: jnp.asarray(a[lo:lo + block_rows])
            value, g = _grad(params, cut(inputs), jax.tree.map(cut, labels),
                             cfg, objective, precision)
            total += float(value)
            grads = g if grads is None else _add(grads, g)
        n = loss_count(labels, objective)
        grads = _scale(grads, jnp.float32(1.0 / n))
        losses.append(total / n)
        if first_grad is None:
            first_grad = leaf_norms(grads)
            first_sums = chunk_sums(grads)
        params, mu, nu = _update(params, mu, nu, grads, lr,
                                 1.0 - ADAM_B1 ** step, 1.0 - ADAM_B2 ** step)
    return {"losses": losses, "grad_norms": first_grad,
            "grad_chunk_sums": first_sums,
            "change_norms": leaf_norms(_sub(params, start))}
