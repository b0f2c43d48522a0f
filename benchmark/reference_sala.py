"""The plain reference for MiniCPM-SALA: its forward pass over one
sequence in straightforward ``jax.numpy`` float32 at
``Precision.HIGHEST``. It imports nothing of ``horovod_tpu``, keeps no
cache and no chunks: one call computes every position from the tokens,
and a served request is compared with it on logits.

The weights are ``benchmark/weights_sala.py``'s (made from the seed,
rounded to bfloat16 once and given to the program and to this file
alike; they are widened where they are used, which changes no value).
Work that is alike for every position (projections, MLP) runs
``ROW_BLOCK`` rows at a time and attention ``QUERY_BLOCK`` queries at a
time, so that 32,768 tokens fit on the chip beside the weights; a block
computes what the whole would.

The equations, ``x`` a layer's input, ``d`` the head width, no biases:

* trunk: ``h = scale_emb E[tokens]``; each layer ``h += a Mixer(rms(h))``
  then ``h += a down(silu(gate u) * up u)``, ``u = rms(h)``,
  ``a = scale_depth / sqrt(published depth)``; logits
  ``= head(rms(h) / (hidden / dim_model_base))``.
* ``lightning-attn``: ``q, k, v`` as ``heads`` heads; per-head RMSNorm on
  ``q`` and ``k``; rotary positions (theta, whole head width, halves
  paired) on both; ``o_t = sum_{s <= t} lambda_h^(t-s) (q_t . k_s) /
  sqrt(d) v_s`` - the closed form of the recurrence
  ``S_t = lambda_h S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``;
  per-head RMSNorm on ``o``, times ``sigmoid(gate x)``, then ``out``.
  ``lambda_h = exp(-s_h)``, ``s_h = 2^(-8h/heads) (1 - l/(depth-1) +
  1e-5)`` for head ``h`` = 1.. and published layer index ``l``.
* ``minicpm4`` (InfLLM-v2): ``q`` as ``heads`` heads, ``k, v`` as
  ``kv_heads``; per-head RMSNorm on ``q`` and ``k``; no positions. A
  query ``t`` whose context ``t + 1`` is at most ``dense_len`` attends
  every key up to itself. Past it: ``Kc_j = mean(k[stride j : stride j +
  kernel])``; ``p = softmax_j(q_t . Kc_j / sqrt(d))`` over the windows
  that end at or before ``t``, summed over the heads that share the
  key-value head; block ``b``'s score is the largest ``p_j`` among the
  windows that overlap it; the query takes the first ``init_blocks``
  blocks, the blocks covering its last ``window_size`` tokens, and the
  highest-scoring others until ``topk`` in all (ties to the lower
  index), and attends the keys of those blocks up to itself. Then
  ``o * sigmoid(gate x)``, then ``out``.

Departures from the published description, each also under ``assumed``
in ``benchmark/configs/minicpm-sala.json``: the sparse layer's sizes and
the decay's slopes are the family's conventions (``config.json`` has no
key for them); dense or sparse is decided for each query by its own
context ``t + 1``, not once for a sequence by its length, so that a
prefill followed by decode steps computes what one forward pass does.

``precision="fp8"`` is the control: the operands of every dense matrix
multiplication (projections, MLP, head) scaled per tensor into
float8_e4m3fn's range and rounded to it, as ``benchmark/reference.py``
has it; norms, scores, softmax and the recurrence stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
ROW_BLOCK = 2048
QUERY_BLOCK = 128
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


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


def slopes(cfg, layer):
    heads, depth = cfg["num_heads"], cfg["published_depth"]
    base = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)
    return base * (1.0 - cfg["layer_indices"][layer] / (depth - 1) + 1e-5)


def lightning(q, k, v, rates):
    """``o_t = sum_{s<=t} exp(-rate (t-s)) (q_t . k_s) / sqrt(d) v_s``.
    ``q``/``k``/``v``: (seq, heads, d); ``rates``: (heads,)."""
    seq, _, d = q.shape
    keys = jnp.arange(seq)

    def block(q_b, at):
        s = jnp.einsum("thd,shd->hts", q_b, k, precision=HIGHEST)
        apart = at[:, None] - keys[None, :]
        decay = jnp.where(
            apart >= 0,
            jnp.exp(-rates[:, None, None] * jnp.maximum(apart, 0)), 0.0)
        return jnp.einsum("hts,shd->thd", s * decay / math.sqrt(d), v,
                          precision=HIGHEST)

    return _blocks(block, [q, jnp.arange(seq)], QUERY_BLOCK)


def block_choice(q, k, sparse):
    """Which key blocks each query attends: bool (kv_heads, seq, blocks).
    ``q``: (seq, heads, d); ``k``: (seq, kv_heads, d)."""
    seq, heads, d = q.shape
    groups = k.shape[1]
    kernel, stride, size = (sparse["kernel"], sparse["stride"],
                            sparse["block_size"])
    n_blocks = -(-seq // size)
    windows = (seq - kernel) // stride + 1 if seq >= kernel else 0
    block = jnp.arange(n_blocks)
    if windows:
        starts = jnp.arange(windows) * stride
        compressed = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
            k, s, kernel, axis=0).mean(axis=0))(starts)  # (windows, g, d)
        overlap = ((starts[:, None] < (block[None, :] + 1) * size)
                   & (starts[:, None] + kernel > block[None, :] * size))
    q = q.reshape(seq, groups, heads // groups, d)

    def choose(q_b, at):
        causal = block[None, :] <= at[:, None] // size        # (t, blocks)
        near = block[None, :] >= jnp.maximum(
            at[:, None] - sparse["window_size"] + 1, 0) // size
        forced = (block[None, :] < sparse["init_blocks"]) | near
        if windows:
            s = jnp.einsum("tgrd,jgd->gtrj", q_b, compressed,
                           precision=HIGHEST) / math.sqrt(d)
            seen = (starts + kernel - 1)[None, :] <= at[:, None]   # (t, j)
            s = jnp.where(seen[None, :, None, :], s, -jnp.inf)
            p = jnp.exp(s - jnp.max(jnp.where(seen[None, :, None, :], s,
                                              -1e30), -1, keepdims=True))
            p = jnp.where(seen[None, :, None, :], p, 0.0)
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            p = p.sum(axis=2)                                  # (g, t, j)
            score = jnp.where(overlap[None, None], p[..., None],
                              -jnp.inf).max(axis=2)       # (g, t, blocks)
        else:
            score = jnp.zeros((groups,) + causal.shape, F32)
        score = jnp.where(forced[None], jnp.inf, score)
        score = jnp.where(causal[None], score, -jnp.inf)
        order = jnp.argsort(-score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        chosen = (rank < sparse["topk"]) & causal[None]
        dense = (at + 1 <= sparse["dense_len"])[None, :, None]
        return jnp.where(dense, causal[None], chosen).transpose(1, 0, 2)

    return _blocks(choose, [q, jnp.arange(seq)], QUERY_BLOCK
                   ).transpose(1, 0, 2)


def sparse_attention(q, k, v, sparse):
    """Causal softmax attention of each query over the keys of its chosen
    blocks. ``q``: (seq, heads, d); ``k``/``v``: (seq, kv_heads, d)."""
    seq, heads, d = q.shape
    groups, size = k.shape[1], sparse["block_size"]
    chosen = block_choice(q, k, sparse).transpose(1, 0, 2)  # (seq, g, blocks)
    keys = jnp.arange(seq)

    def block(q_b, chosen_b, at):
        q_b = q_b.reshape(-1, groups, heads // groups, d)
        mask = (jnp.repeat(chosen_b, size, axis=-1)[..., :seq]
                & (keys[None, None, :] <= at[:, None, None]))  # (t, g, s)
        s = jnp.einsum("tgrd,sgd->tgrs", q_b, k,
                       precision=HIGHEST) / math.sqrt(d)
        s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("tgrs,sgd->tgrd", p, v,
                          precision=HIGHEST).reshape(-1, heads, d)

    return _blocks(block, [q, chosen, jnp.arange(seq)], QUERY_BLOCK)


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
    a = cfg["scale_depth"] / math.sqrt(cfg["published_depth"])
    h = params["token_embed"]["embedding"][tokens].astype(F32) \
        * cfg["scale_emb"]
    for i, kind in enumerate(cfg["mixer_types"]):
        p = params[f"layer_{i}"]
        m = p["mixer"]
        kv = heads if kind == LIGHTNING else groups

        def project(x, pos, m=m, p=p, kind=kind, kv=kv):
            u = _rms(x, p["input_norm"]["scale"], eps)
            q = _rms(mm(u, m["query"]["kernel"]).reshape(-1, heads, d),
                     m["q_norm"]["scale"], eps)
            k = _rms(mm(u, m["key"]["kernel"]).reshape(-1, kv, d),
                     m["k_norm"]["scale"], eps)
            v = mm(u, m["value"]["kernel"]).reshape(-1, kv, d)
            if kind == LIGHTNING:
                q = _rope(q, pos, cfg["rope_theta"])
                k = _rope(k, pos, cfg["rope_theta"])
            return q, k, v, jax.nn.sigmoid(mm(u, m["gate"]["kernel"]))

        q, k, v, gate = _blocks(project, [h, at], row_block)
        if kind == LIGHTNING:
            o = _rms(lightning(q, k, v, slopes(cfg, i)),
                     m["o_norm"]["scale"], eps)
        elif kind == SPARSE:
            o = sparse_attention(q, k, v, cfg["sparse"])
        else:
            raise ValueError(f"unknown mixer {kind!r}")

        def finish(x, o, gate, m=m, p=p):
            x = x + a * mm(o.reshape(-1, heads * d) * gate,
                           m["out"]["kernel"])
            u = _rms(x, p["post_norm"]["scale"], eps)
            f = p["mlp"]
            return x + a * mm(
                jax.nn.silu(mm(u, f["gate"]["kernel"]))
                * mm(u, f["up"]["kernel"]), f["down"]["kernel"])

        h = _blocks(finish, [h, o, gate], row_block)
    h = h[:seq] if rows is None else h[rows]
    h = _rms(h, params["final_norm"]["scale"], eps) \
        / (cfg["d_model"] / cfg["dim_model_base"])
    return mm(h, params["head"])


class Frozen(dict):
    """A configuration that can be a static argument of ``jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def frozen(cfg):
    """``cfg`` (a configuration's ``as_run``) as a :class:`Frozen`, its
    nested groups frozen and its lists tuples."""
    return Frozen((k, Frozen(v) if isinstance(v, dict) else
                   tuple(v) if isinstance(v, list) else v)
                  for k, v in cfg.items())
