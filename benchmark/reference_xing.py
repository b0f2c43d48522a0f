"""The plain reference for Xing4.0-29B-A4B: its forward pass over one
sequence in straightforward ``jax.numpy`` float32 at
``Precision.HIGHEST``. It imports nothing of ``horovod_tpu``, keeps no
cache and absorbs nothing: every position's keys and values are expanded
from its latent, the experts are a plain loop over all of them with a
0/1 weight, Sinkhorn is a loop, one call computes every position from
the tokens, and a served request is compared with it on logits.

The weights are ``benchmark/weights_xing.py``'s (made from the seed,
rounded to bfloat16 once and given to the program and to this file
alike; they are widened where they are used, which changes no value).
Work that is alike for every position (projections, experts,
hyper-connections) runs ``ROW_BLOCK`` rows at a time and attention
``QUERY_BLOCK`` queries at a time against every key, so that the cell's
longest request fits on the chip beside the weights; a block computes
what the whole would.

The equations (``C`` the hidden size, ``n`` the residual streams, no
biases; what ``config.json`` does not say is listed in the configuration
file's ``assumed`` with its origin):

* streams: ``X_0 = [e, e, e, e]`` (the embedding ``n`` times); after
  the last layer ``h = sum_i X[i]``, ``logits = head(rms_w(h))``, the
  head untied.
* a hyper-connection round a sublayer ``F`` (attention or MLP, each
  with its own maps): ``u = flat(X) / sqrt(mean(flat(X)^2) + eps)``;
  ``m = u Phi`` (``n^2 + 2n`` numbers); ``H_pre = sigmoid(a_pre m[:n] +
  b_pre)``, ``H_post = 2 sigmoid(a_post m[n:2n] + b_post)``, ``Z =
  clip(a_res mat(m[2n:]) + b_res, lo, hi)`` (row-major), ``M = exp(Z)``,
  then ``iters`` times each row divided by (its sum + ``hc_eps``) and
  each column by (its sum + ``hc_eps``) -> ``H_res``;
  ``X' = H_res X + H_post^T F(rms_w(H_pre X))``.
* latent attention: ``c_q = rms_w(x W_qa)``; per head ``[q_nope, q_rope]
  = c_q W_qb``; ``[c, k_r] = x W_kva``, ``c = rms_w(c)``; rotary positions
  (YaRN frequencies, halves paired) on ``q_rope`` and on ``k_r``, one
  for all heads; ``[k_nope_h, v_h] = c W_kvb``; score ``(q_nope_h .
  k_nope_h + q_rope_h . k_r) s``, ``s = (nope + rope)^-0.5 (0.1
  mscale_all_dim ln factor + 1)^2``; causal softmax; ``o_h = sum p
  v_h``; out ``= concat(o_h) W_o``.
* routed experts: ``g = sigmoid(x W_r)``; the ``top_k`` largest of ``g +
  b`` chosen; ``w_i = scaling g_i / (sum of the chosen g + 1e-20)``;
  ``y = sum_i w_i E_i(x) + E_shared(x)``, every ``E`` ``down(silu(gate
  x) * up x)``. Only the experts ``experts_first .. + experts_count``
  are held (all of them as run): the others' part is left out.
* the leading dense layers: one such ``E`` of width ``d_ff``.

``precision="fp8"`` is the control: the operands of every dense matrix
multiplication (projections, experts, MLP, head) scaled per tensor into
float8_e4m3fn's range and rounded to it, as ``benchmark/reference.py``
has it; norms, the router, the hyper-connections' maps, rotary positions
and the softmax stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
ROW_BLOCK = 512
QUERY_BLOCK = 128


def _fp8(x):
    scale = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def _matmul(precision):
    if precision == "f32":
        return lambda a, w: jnp.dot(a, w.astype(F32), precision=HIGHEST)
    if precision == "fp8":
        return lambda a, w: jnp.dot(_fp8(a), _fp8(w.astype(F32)),
                                    precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def _blocks(fn, arrays, size):
    """``fn`` over blocks of ``size`` leading rows of every array in
    ``arrays`` (whose rows are a multiple of it), results joined."""
    n = arrays[0].shape[0] // size
    cut = [a.reshape((n, size) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), cut)
    return jax.tree.map(lambda o: o.reshape((n * size,) + o.shape[2:]), out)


def yarn_frequencies(cfg):
    """The rotary frequencies of the ``rope_dim`` dims: plain ``theta^(-2i
    / d)`` where a pair turns more than ``beta_fast`` times over the
    original context, divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp between the two correction dims
    (DeepSeek-V2's published YaRN); plain where ``yarn`` is absent."""
    d, theta = cfg["rope_dim"], cfg["rope_theta"]
    plain = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    yarn = cfg["yarn"]
    if not yarn:
        return jnp.asarray(plain, F32)
    yarn = dict(yarn)

    def correction(turns):
        return d * math.log(yarn["original_max_position_embeddings"]
                            / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), d - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low if high > low else 1e-3),
                       0.0), 1.0)
        out.append(f / yarn["factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, F32)


def softmax_scale(cfg):
    scale = (cfg["nope_dim"] + cfg["rope_dim"]) ** -0.5
    yarn = dict(cfg["yarn"]) if cfg["yarn"] else None
    if yarn and yarn["factor"] > 1:
        scale *= (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"])
                  + 1.0) ** 2
    return scale


def _rope(x, positions, freq):
    """``x``: (rows, heads, d); halves paired."""
    half = x.shape[-1] // 2
    angle = positions.astype(F32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def attention(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal softmax attention with every key and value expanded.
    ``q_nope``/``k_nope``: (seq, heads, nope); ``q_rope``: (seq, heads,
    r); ``k_rope``: (seq, r), one for all heads; ``v``: (seq, heads, v);
    ``seq`` a multiple of ``QUERY_BLOCK`` or under it."""
    seq = q_nope.shape[0]
    keys = jnp.arange(seq)

    def one(qn, qr, at):
        s = (jnp.einsum("thn,shn->hts", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("thr,sr->hts", qr, k_rope, precision=HIGHEST)) \
            * scale
        s = jnp.where(keys[None, None, :] <= at[None, :, None], s, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    return _blocks(one, [q_nope, q_rope, keys], min(QUERY_BLOCK, seq))


def hyper_maps(X, p, cfg):
    """``H_pre`` (rows, n), ``H_post`` (rows, n) and ``H_res`` (rows, n,
    n) for the streams ``X`` (rows, n, C)."""
    rows, n, _ = X.shape
    u = X.reshape(rows, -1)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    m = jnp.dot(u, p["phi"].astype(F32), precision=HIGHEST)
    a, b = p["alpha"].astype(F32), p["bias"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    lo, hi = cfg["hc_clamp"]
    z = jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:], lo, hi)
    M = jnp.exp(z).reshape(rows, n, n)
    for _ in range(cfg["sinkhorn_iters"]):
        M = M / (M.sum(axis=2, keepdims=True) + cfg["hc_eps"])
        M = M / (M.sum(axis=1, keepdims=True) + cfg["hc_eps"])
    return pre, post, M


def gated(mm, x, p):
    return mm(jax.nn.silu(mm(x, p["gate"]["kernel"]))
              * mm(x, p["up"]["kernel"]), p["down"]["kernel"])


def routed(mm, x, p, cfg):
    """``sum_i w_i E_i(x)`` over the held experts, each computed for every
    row and weighted by the row's weight for it (0 where not chosen),
    plus the shared expert."""
    k = cfg["top_k"]
    g = jax.nn.sigmoid(jnp.dot(x, p["router"].astype(F32),
                               precision=HIGHEST))
    _, chosen = jax.lax.top_k(g + p["router_bias"].astype(F32), k)
    took = (chosen[..., None] == jnp.arange(g.shape[-1])).any(axis=1)
    weight = cfg["routed_scaling"] * g * took \
        / ((g * took).sum(axis=-1, keepdims=True) + 1e-20)
    first, held = cfg["experts_first"], cfg["experts_count"]

    def one(y, xs):
        w_gate, w_up, w_down, w = xs
        e = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return y + w[:, None] * e, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        weight[:, first:first + held].T))
    if cfg["shared_experts"]:
        y = y + gated(mm, x, p["shared"])
    return y


def forward(params, tokens, cfg, precision="f32", rows=None):
    """Float32 logits of one sequence ``tokens`` (seq,), at every
    position or, with ``rows`` (an int array), at those positions only
    (the head is the one part that does not have to see every row)."""
    mm = _matmul(precision)
    eps, heads, n = cfg["rms_norm_eps"], cfg["num_heads"], cfg["streams"]
    rank, nope, v_dim = cfg["kv_rank"], cfg["nope_dim"], cfg["v_dim"]
    seq = tokens.shape[0]
    unit = ROW_BLOCK if seq >= ROW_BLOCK else QUERY_BLOCK
    pad = -seq % unit        # zeros after the sequence: causal, so unseen
    tokens = jnp.pad(tokens, (0, pad))
    row_block = min(ROW_BLOCK, seq + pad)
    at = jnp.arange(seq + pad)
    freq, scale = yarn_frequencies(cfg), softmax_scale(cfg)
    e = params["token_embed"]["embedding"][tokens].astype(F32)
    X = jnp.repeat(e[:, None], n, axis=1)                   # (rows, n, C)
    for i in range(cfg["num_layers"]):
        p = params[f"layer_{i}"]
        m = p["mixer"]

        def project(X, pos, m=m, p=p):
            pre, post, res = hyper_maps(X, p["hyper_mixer"], cfg)
            x = _rms(jnp.einsum("tn,tnc->tc", pre, X, precision=HIGHEST),
                     p["input_norm"]["scale"], eps)
            c_q = _rms(mm(x, m["q_a"]["kernel"]), m["q_norm"]["scale"], eps)
            q = mm(c_q, m["q_b"]["kernel"]).reshape(-1, heads,
                                                    nope + cfg["rope_dim"])
            kv = mm(x, m["kv_a"]["kernel"])
            c = _rms(kv[:, :rank], m["kv_norm"]["scale"], eps)
            expanded = mm(c, m["kv_b"].reshape(rank, -1)).reshape(
                -1, heads, nope + v_dim)
            return (q[..., :nope], _rope(q[..., nope:], pos, freq),
                    expanded[..., :nope],
                    _rope(kv[:, None, rank:], pos, freq)[:, 0],
                    expanded[..., nope:], post, res)

        q_nope, q_rope, k_nope, k_rope, v, post, res = _blocks(
            project, [X, at], row_block)
        o = attention(q_nope, q_rope, k_nope, k_rope, v, scale)

        def finish(X, o, post, res, m=m, p=p, dense=i):
            y = mm(o.reshape(-1, heads * v_dim), m["out"]["kernel"])
            X = jnp.einsum("tij,tjc->tic", res, X, precision=HIGHEST) \
                + post[..., None] * y[:, None]
            pre, post, res = hyper_maps(X, p["hyper_mlp"], cfg)
            x = _rms(jnp.einsum("tn,tnc->tc", pre, X, precision=HIGHEST),
                     p["post_norm"]["scale"], eps)
            y = gated(mm, x, p["mlp"]) if "mlp" in p \
                else routed(mm, x, p["moe"], cfg)
            return jnp.einsum("tij,tjc->tic", res, X, precision=HIGHEST) \
                + post[..., None] * y[:, None]

        X = _blocks(finish, [X, o, post, res], row_block)
    h = X.sum(axis=1)
    h = h[:seq] if rows is None else h[rows]
    h = _rms(h, params["final_norm"]["scale"], eps)
    if cfg["dim_model_base"]:     # toy sizes only: see the file's note
        h = h / (cfg["d_model"] / cfg["dim_model_base"])
    return mm(h, params["head"])


class Frozen(dict):
    """A configuration that can be a static argument of ``jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def frozen(cfg):
    """``cfg`` (a configuration's ``as_run``) as a :class:`Frozen`, its
    lists tuples and its groups :class:`Frozen` too."""
    def freeze(v):
        if isinstance(v, list):
            return tuple(v)
        return Frozen((k, freeze(x)) for k, x in v.items()) \
            if isinstance(v, dict) else v
    return Frozen((k, freeze(v)) for k, v in cfg.items())
