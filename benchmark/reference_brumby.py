"""The plain reference for Brumby-14B-Base: its forward pass over one
sequence in straightforward ``jax.numpy`` float32 at
``Precision.HIGHEST``. It imports nothing of ``horovod_tpu``, keeps no
cache, no chunks and no state: power retention is computed in its
*attention form* (it squares ``q . k``; the feature map ``phi``, the
state ``S`` and the normaliser ``z`` of the recurrent form are never
built), one call computes every position from the tokens, and a served
request is compared with it on logits.

The weights are ``benchmark/weights_brumby.py``'s (made from the seed,
rounded to bfloat16 once and given to the program and to this file
alike; they are widened where they are used, which changes no value).
Work that is alike for every position (projections, MLP) runs
``ROW_BLOCK`` rows at a time and retention ``QUERY_BLOCK`` queries at a
time against every key, so that 16,896 tokens fit on the chip beside
the weights; a block computes what the whole would.

The equations, ``x`` a layer's input, ``d`` the head width (128), no
biases but the gate's:

* trunk (Qwen3-14B's): ``h = E[tokens]``; each layer ``h += Mixer(rms(h))``
  then ``h += down(silu(gate u) * up u)``, ``u = rms(h)``; logits
  ``= head(rms(h))``, the head untied.
* power retention: ``q`` as ``heads`` heads, ``k, v`` as ``kv_heads``;
  per-head RMSNorm on ``q`` and ``k``, then rotary positions (theta,
  whole head width, halves paired) on both; one log-gate a token a
  key/value head, ``g_t = log_sigmoid(W_g x_t + b_g) <= 0``; for a query
  head ``i`` of the group that shares key/value head ``j`` and
  ``s = 1 / sqrt(d)``,
  ``a_ts = (s q_t . k_s)^2 exp(g_(s+1) + ... + g_t)`` for ``s <= t`` and
  ``y_t = sum_s a_ts v_s / (sum_s a_ts + eps)``; then ``out``.
  It is the closed form of ``S_t = exp(g_t) S_(t-1) + phi(k_t) v_t^T``,
  ``z_t = exp(g_t) z_(t-1) + phi(k_t)``, ``y_t = phi(q_t)^T S_t /
  (phi(q_t)^T z_t + eps)`` with ``phi(x) . phi(y) = (s x . y)^2``.

Assumed, because ``config.json`` has no key for it (each also under
``assumed`` in ``benchmark/configs/brumby-14b.json``):

* the degree of the power is 2;
* the gate is ``log_sigmoid(W_g x + b_g)`` with ``W_g``: hidden ->
  kv_heads (one gate a key/value head, shared by its five query heads),
  computed in float32;
* the output is divided by the sum of its weights plus ``eps = 1e-6``
  (with an even degree every weight is >= 0; ``s`` cancels in the
  division and is there for range only);
* QK-norm first, then rotary positions, on ``q`` and ``k`` before they
  are multiplied;
* float32 gates, running sums, squares and normaliser;
* no output gate and no output norm beyond the division.

The running sums of log-gates are kept small where they matter: for a
block of queries starting at ``t0`` the exponent of a pair is ``c_t +
r_s``, ``c_t = g_t0 + ... + g_t`` (at most ``QUERY_BLOCK`` terms) and
``r_s`` the sum of the gates after ``s`` up to ``t0`` (``-c_s`` inside
the block). One cumulative sum over 16,896 positions would reach
thousands, where float32 keeps four digits of a difference; ``r_s`` is
large only where ``exp`` of it is nothing.

``precision="fp8"`` is the control: the operands of every dense matrix
multiplication (projections, MLP, head) scaled per tensor into
float8_e4m3fn's range and rounded to it, as ``benchmark/reference.py``
has it; norms, gates, squares and sums stay float32.
"""

from __future__ import annotations

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


def _rope(x, positions, theta):
    """``x``: (rows, heads, d); halves paired."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def retention(q, k, v, log_gate, eps):
    """``y_t = sum_(s<=t) a_ts v_s / (sum_(s<=t) a_ts + eps)`` with
    ``a_ts = (q_t . k_s)^2 / d * exp(g_(s+1) + ... + g_t)``.

    ``q``: (seq, heads, d); ``k``/``v``: (seq, kv_heads, d), query head
    ``h`` with key/value head ``h // (heads / kv_heads)``; ``log_gate``:
    (seq, kv_heads), at most 0; ``seq`` a multiple of ``QUERY_BLOCK`` or
    under it."""
    seq, heads, d = q.shape
    groups = k.shape[1]
    block = min(QUERY_BLOCK, seq)
    keys = jnp.arange(seq)
    q = q.reshape(seq, groups, heads // groups, d)

    def one(q_b, g_b, at):
        start = at[0]
        c = jnp.cumsum(g_b, axis=0)                      # (t, groups)
        # the gates after key s and before the block: summed from the
        # block backwards, so that the sum is small where it matters
        before = jnp.where((keys < start)[:, None], log_gate, 0.0)
        r = jnp.cumsum(before[::-1], axis=0)[::-1] - before
        own = jax.lax.dynamic_update_slice(jnp.zeros_like(r), c, (start, 0))
        r = jnp.where((keys < start)[:, None], r, -own)   # (s, groups)
        exponent = c[:, None, :] + r[None, :, :]          # (t, s, groups)
        seen = keys[None, :] <= at[:, None]               # (t, s)
        weight = jnp.where(seen[..., None],
                           jnp.exp(jnp.minimum(exponent, 0.0)), 0.0)
        s = jnp.einsum("tgrd,sgd->grts", q_b, k, precision=HIGHEST)
        a = s * s / d * weight.transpose(2, 0, 1)[:, None]   # (g, r, t, s)
        num = jnp.einsum("grts,sge->tgre", a, v, precision=HIGHEST)
        den = a.sum(axis=-1).transpose(2, 0, 1)              # (t, g, r)
        return (num / (den[..., None] + eps)).reshape(-1, heads, d)

    return _blocks(one, [q, log_gate, keys], block)


def forward(params, tokens, cfg, precision="f32", rows=None):
    """Float32 logits of one sequence ``tokens`` (seq,), at every
    position or, with ``rows`` (an int array), at those positions only
    (the head is the one part that does not have to see every row)."""
    mm = _matmul(precision)
    eps, heads, groups, d = (cfg["rms_norm_eps"], cfg["num_heads"],
                             cfg["num_kv_heads"], cfg["head_dim"])
    seq = tokens.shape[0]
    unit = ROW_BLOCK if seq >= ROW_BLOCK else QUERY_BLOCK
    pad = -seq % unit        # zeros after the sequence: causal, so unseen
    tokens = jnp.pad(tokens, (0, pad))
    row_block = min(ROW_BLOCK, seq + pad)
    at = jnp.arange(seq + pad)
    h = params["token_embed"]["embedding"][tokens].astype(F32)
    for i in range(cfg["num_layers"]):
        p = params[f"layer_{i}"]
        m = p["mixer"]

        def project(x, pos, m=m, p=p):
            u = _rms(x, p["input_norm"]["scale"], eps)
            q = _rms(mm(u, m["query"]["kernel"]).reshape(-1, heads, d),
                     m["q_norm"]["scale"], eps)
            k = _rms(mm(u, m["key"]["kernel"]).reshape(-1, groups, d),
                     m["k_norm"]["scale"], eps)
            v = mm(u, m["value"]["kernel"]).reshape(-1, groups, d)
            # the gate is float32 in the program too: never float8
            gate = jax.nn.log_sigmoid(
                jnp.dot(u, m["gate"]["kernel"].astype(F32),
                        precision=HIGHEST) + m["gate"]["bias"].astype(F32))
            return (_rope(q, pos, cfg["rope_theta"]),
                    _rope(k, pos, cfg["rope_theta"]), v, gate)

        q, k, v, gate = _blocks(project, [h, at], row_block)
        o = retention(q, k, v, gate, cfg["retention_eps"])

        def finish(x, o, m=m, p=p):
            x = x + mm(o.reshape(-1, heads * d), m["out"]["kernel"])
            u = _rms(x, p["post_norm"]["scale"], eps)
            f = p["mlp"]
            return x + mm(jax.nn.silu(mm(u, f["gate"]["kernel"]))
                          * mm(u, f["up"]["kernel"]), f["down"]["kernel"])

        h = _blocks(finish, [h, o], row_block)
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
    lists tuples."""
    return Frozen((k, tuple(v) if isinstance(v, list) else v)
                  for k, v in cfg.items())
