"""The plain reference for granite-4.0-h-small: its forward pass over one
sequence in straightforward ``jax.numpy`` float32 at
``Precision.HIGHEST``. It imports nothing of ``horovod_tpu`` (its
helpers that are not this model's own are ``benchmark/
reference_xing.py``'s), keeps no cache, no state between calls and no
chunks, batches nothing: the state-space recurrence runs **token by
token** (``lax.scan`` over the positions), the convolution is an
explicit sum over its four shifted copies, the experts are a plain loop
over the held ones with a 0/1 weight, one call computes every position
from the tokens, and a served request is compared with it on logits.

The weights are ``benchmark/weights_granite.py``'s (made from the seed,
rounded to bfloat16 once and given to the program and to this file
alike; they are widened where they are used, which changes no value).
Work that is alike for every position (projections, norms, experts)
runs ``ROW_BLOCK`` rows at a time and attention ``QUERY_BLOCK`` queries
at a time against every key, so that the cell's longest request fits on
the chip beside the weights; a block computes what the whole would.

The equations (``C`` the hidden size, no biases but the convolution's;
what ``config.json`` does not say is listed in the configuration file's
``assumed`` with its origin):

* trunk: ``h_0 = embedding_multiplier E[ids]``; layer ``i``: ``h +=
  residual_multiplier Mixer_i(rms(h))``, then ``u = rms(h)``, ``h +=
  residual_multiplier (Moe(u) + Shared(u))``; ``logits = rms(h) E^T /
  logits_scaling``, the head tied to the embedding.
* state-space mixer (``mamba2``; ``H`` heads of ``P``, ``N`` the state
  size, ``G`` groups): ``[z | xBC | dt] = x W_in`` (``HP``, ``HP + 2GN``
  and ``H`` wide); ``xBC_t = silu(sum_{j<k} w_j * xBC_{t-(k-1)+j} + b)``
  a channel, zeros before the sequence; ``x`` (``H x P``), ``B``, ``C``
  (``G x N`` each, head ``h`` reading pair ``h // (H / G)``) split from
  it; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from ``S = 0``; ``y_t =
  S_t C_t + D x_t``; ``out = rms(y * silu(z)) W_out``, the norm over all
  ``HP`` with a learned scale.
* attention (``full``): ``q = x W_q`` (``heads x d``), ``k = x W_k``,
  ``v = x W_v`` (``kv_heads x d``), no bias, no norm, no positions;
  scale ``attention_multiplier``; query ``t`` sees keys ``0 .. t``;
  query head ``j`` reads key/value head ``j // (heads / kv_heads)``.
* the MLP of every layer: ``l = u W_r``; the ``top_k`` largest chosen,
  ``w = softmax`` over those; ``Moe(u) = sum_i w_i E_i(u)``, ``E_i`` a
  SiLU-gated MLP of ``expert_d_ff``; ``Shared`` the same of
  ``shared_d_ff``. Only the experts ``experts_first .. +
  experts_count`` are held: the others' part is left out, as the chip
  that holds them would add it.

``precision="fp8"`` is the control: the operands of every dense matrix
multiplication (projections, experts, shared MLP, head) scaled per
tensor into float8_e4m3fn's range and rounded to it, as
``benchmark/reference.py`` has it; norms, the router, the convolution,
the recurrence and the softmax stay float32. Faults are planted from
outside (``benchmark/controls_granite.py``), never an option here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what is the same plain mathematics in the references is written once:
# the float8 control's rounding and the two matrix products, the RMSNorm,
# a function over blocks of rows, the SiLU-gated MLP, a configuration
# that can be a static argument
from benchmark.reference_xing import (Frozen, _blocks, _matmul,  # noqa: F401
                                      _rms, frozen, gated)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512
QUERY_BLOCK = 128


def conv(xbc, kernel, bias):
    """``silu(sum_j kernel[j] * xbc[t - (k - 1) + j] + bias)``: the sum
    over the ``k`` shifted copies, zeros before the sequence. ``xbc``:
    (seq, channels); ``kernel``: (k, channels)."""
    seq, taps = xbc.shape[0], kernel.shape[0]
    out = jnp.broadcast_to(bias.astype(F32), xbc.shape)
    for j in range(taps):
        back = taps - 1 - j            # row t takes xbc[t - back]
        out = out + kernel[j].astype(F32) \
            * jnp.pad(xbc, ((back, 0), (0, 0)))[:seq]
    return jax.nn.silu(out)


def recurrence(x, dt, a, b, c, d_skip):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
    D x_t`` from a zero state, one position at a time. ``x``: (seq, H,
    P); ``dt``: (seq, H); ``a``/``d_skip``: (H,); ``b``/``c``: (seq, H,
    N), already a head. Returns ``y`` (seq, H, P)."""
    def one(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)
        return state, y + d_skip[:, None] * x_t

    state = jnp.zeros(x.shape[1:] + b.shape[-1:], F32)
    return jax.lax.scan(one, state, (x, dt, b, c))[1]


def attention(q, k, v, scale):
    """Causal softmax attention over full scores, query ``t`` seeing
    keys ``0 .. t``. ``q``: (seq, heads, d); ``k``/``v``: (seq, kv_heads,
    d); ``seq`` a multiple of ``QUERY_BLOCK`` or under it."""
    seq, heads, d = q.shape
    per = heads // k.shape[1]
    keys = jnp.arange(seq)

    def one(q_b, at):
        q_b = q_b.reshape(-1, heads // per, per, d)
        s = jnp.einsum("tgrd,sgd->grts", q_b, k, precision=HIGHEST) * scale
        s = jnp.where((keys[None, :] <= at[:, None])[None, None], s,
                      -jnp.inf)
        o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        return o.reshape(-1, heads, d)

    return _blocks(one, [q, keys], min(QUERY_BLOCK, seq))


def routed(mm, x, p, cfg):
    """``sum_i w_i E_i(x)`` over the held experts, each computed for every
    row and weighted by the row's weight for it (0 where not chosen: the
    softmax is over the chosen logits alone), plus the shared MLP."""
    logits = jnp.dot(x, p["router"].astype(F32), precision=HIGHEST)
    _, chosen = jax.lax.top_k(logits, cfg["top_k"])
    took = (chosen[..., None] == jnp.arange(logits.shape[-1])).any(axis=1)
    weight = jax.nn.softmax(jnp.where(took, logits, -jnp.inf), axis=-1)
    first, held = cfg["experts_first"], cfg["experts_count"]

    def one(y, xs):
        w_gate, w_up, w_down, w = xs
        e = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return y + w[:, None] * e, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        weight[:, first:first + held].T))
    # every chip that shares the layer computes the shared MLP: a share
    # handed no ``shared`` is the routed part alone (the shares' sum)
    return y + gated(mm, x, p["shared"]) if "shared" in p else y


def forward(params, tokens, cfg, precision="f32", rows=None):
    """Float32 logits of one sequence ``tokens`` (seq,), at every
    position or, with ``rows`` (an int array), at those positions only
    (the head is the one part that does not have to see every row)."""
    mm = _matmul(precision)
    eps, a_res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    heads, groups, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    ssm = cfg["ssm"]
    ssm_heads, p_dim, n = ssm["num_heads"], ssm["head_dim"], ssm["d_state"]
    inner, pairs = ssm_heads * p_dim, ssm["n_groups"]
    channels = inner + 2 * pairs * n
    seq = tokens.shape[0]
    unit = ROW_BLOCK if seq >= ROW_BLOCK else QUERY_BLOCK
    pad = -seq % unit        # zeros after the sequence: causal, so unseen
    tokens = jnp.pad(tokens, (0, pad))
    row_block = min(ROW_BLOCK, seq + pad)
    table = params["token_embed"]["embedding"]
    h = cfg["embedding_multiplier"] * table[tokens].astype(F32)
    for i, kind in enumerate(cfg["mixers"]):
        p = params[f"layer_{i}"]
        m = p["mixer"]
        if kind == "mamba2":
            proj = _blocks(lambda x, m=m, p=p: mm(
                _rms(x, p["input_norm"]["scale"], eps),
                m["in_proj"]["kernel"]), [h], row_block)
            z, xbc = proj[:, :inner], proj[:, inner:inner + channels]
            dt = jax.nn.softplus(proj[:, inner + channels:]
                                 + m["dt_bias"].astype(F32))
            xbc = conv(xbc, m["conv_kernel"], m["conv_bias"])
            a_head = lambda t: jnp.repeat(            # a pair a head
                t.reshape(-1, pairs, n), ssm_heads // pairs, axis=1)
            y = recurrence(
                xbc[:, :inner].reshape(-1, ssm_heads, p_dim), dt,
                -jnp.exp(m["A_log"].astype(F32)),
                a_head(xbc[:, inner:inner + pairs * n]),
                a_head(xbc[:, inner + pairs * n:]), m["D"].astype(F32))
            mixed = _blocks(lambda y, z, m=m: mm(
                _rms(y.reshape(-1, inner) * jax.nn.silu(z),
                     m["norm"]["scale"], eps), m["out_proj"]["kernel"]),
                            [y, z], row_block)
        else:
            def project(x, m=m, p=p):
                x = _rms(x, p["input_norm"]["scale"], eps)
                return (mm(x, m["query"]["kernel"]).reshape(-1, heads, d),
                        mm(x, m["key"]["kernel"]).reshape(-1, groups, d),
                        mm(x, m["value"]["kernel"]).reshape(-1, groups, d))

            q, k, v = _blocks(project, [h], row_block)
            o = attention(q, k, v, cfg["attention_multiplier"])
            mixed = _blocks(lambda o, m=m: mm(o.reshape(-1, heads * d),
                                              m["out"]["kernel"]),
                            [o], row_block)

        def finish(x, mixed, p=p):
            x = x + a_res * mixed
            return x + a_res * routed(
                mm, _rms(x, p["post_norm"]["scale"], eps), p["moe"], cfg)

        h = _blocks(finish, [h, mixed], row_block)
    h = h[:seq] if rows is None else h[rows]
    h = _rms(h, params["final_norm"]["scale"], eps)
    return mm(h, table.T) / cfg["logits_scaling"]
