"""The plain reference for K-EXAONE-236B-A23B: its forward pass over one
sequence in straightforward ``jax.numpy`` float32 at
``Precision.HIGHEST``. It imports nothing of ``horovod_tpu`` (its
helpers that are not this model's own are ``benchmark/
reference_xing.py``'s), keeps no cache and no ring, batches nothing: a window layer is a mask over the
full scores, the experts are a plain loop over the held ones with a 0/1
weight, one call computes every position from the tokens, and a served
request is compared with it on logits.

The weights are ``benchmark/weights_kexaone.py``'s (made from the seed,
rounded to bfloat16 once and given to the program and to this file
alike; they are widened where they are used, which changes no value).
Work that is alike for every position (projections, MLPs, experts) runs
``ROW_BLOCK`` rows at a time and attention ``QUERY_BLOCK`` queries at a
time against every key, so that the cell's longest request fits on the
chip beside the weights; a block computes what the whole would.

The equations (``C`` the hidden size, no biases; what ``config.json``
does not say is listed in the configuration file's ``assumed`` with its
origin):

* trunk: ``h_0 = E[ids]``; layer ``i``: ``h += rms_a(Attn_i(h))``, then
  ``h += rms_m(Mlp_i(h))``: the norms sit on the sublayers' outputs and
  none on their inputs; ``logits = rms_f(h) W_head``, the head untied.
* attention: ``q = x W_q`` (``heads x d``), ``k = x W_k``, ``v = x W_v``
  (``kv_heads x d``); ``q`` and ``k`` each normed over their ``d`` with a
  learned scale; on a ``window`` layer ``q`` and ``k`` rotated over all
  ``d`` dims (base ``rope_theta``, halves paired) and query ``t`` sees
  keys ``max(0, t - window + 1) .. t``; on a ``full`` layer no rotation
  and query ``t`` sees ``0 .. t``; scale ``d^-0.5``; query head ``j``
  reads key/value head ``j // (heads / kv_heads)``; out ``= concat(o_j)
  W_o``.
* the leading dense layer: ``(silu(x W_g) * x W_u) W_d`` of width
  ``d_ff``.
* routed experts: ``g = sigmoid(x W_r)``; the ``top_k`` largest of ``g +
  b`` chosen; ``w_i = scaling g_i / (sum of the chosen g + 1e-20)``;
  ``y = sum_i w_i E_i(x) + E_shared(x)``, every ``E`` such an MLP of
  width ``expert_d_ff``. Only the experts ``experts_first .. +
  experts_count`` are held: the others' part is left out, as the chip
  that holds them would add it.

``precision="fp8"`` is the control: the operands of every dense matrix
multiplication (projections, experts, MLP, head) scaled per tensor into
float8_e4m3fn's range and rounded to it, as ``benchmark/reference.py``
has it; norms, the router, rotary positions and the softmax stay
float32.

``fault`` plants one of this model's own faults, for the limits'
readings and the tests: ``"window_as_full"`` (a window layer sees every
causal key, as a full layer does) and ``"ring_one_too_far"`` (a window
layer also sees the key at ``t - window``, the column a ring read one
position too far would hold); ``"qk_norm_dropped"`` (queries and keys
not normed) is for the toy tests alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what is the same plain mathematics in both references is written once:
# the float8 control's rounding and the two matrix products, the RMSNorm,
# a function over blocks of rows, the SiLU-gated MLP, the noaux_tc router
# over the held experts beside the shared one, a configuration that can
# be a static argument
from benchmark.reference_xing import (Frozen, _blocks, _matmul,  # noqa: F401
                                      _rms, frozen, gated, routed)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512
QUERY_BLOCK = 128
FAULTS = ("window_as_full", "ring_one_too_far")
# told from a sound program at toy size only (tests/test_window_attention.py)
TOY_FAULTS = ("qk_norm_dropped",)


def _rope(x, positions, theta):
    """``x``: (rows, heads, d); halves paired, every dim rotated."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def attention(q, k, v, reach):
    """Causal softmax attention over full scores, query ``t`` seeing keys
    ``t - reach + 1 .. t`` (``reach`` ``None``: all of them). ``q``:
    (seq, heads, d); ``k``/``v``: (seq, kv_heads, d); ``seq`` a multiple
    of ``QUERY_BLOCK`` or under it."""
    seq, heads, d = q.shape
    per = heads // k.shape[1]
    keys = jnp.arange(seq)

    def one(q_b, at):
        q_b = q_b.reshape(-1, heads // per, per, d)
        s = jnp.einsum("tgrd,sgd->grts", q_b, k, precision=HIGHEST) \
            * d ** -0.5
        seen = keys[None, :] <= at[:, None]
        if reach is not None:
            seen = seen & (at[:, None] - keys[None, :] < reach)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        return o.reshape(-1, heads, d)

    return _blocks(one, [q, keys], min(QUERY_BLOCK, seq))


def reach_of(kind, cfg, fault=None):
    """The keys a layer of ``kind`` sees back from a query, itself among
    them (``None``: all)."""
    if kind == "full" or fault == "window_as_full":
        return None
    return cfg["window"] + (fault == "ring_one_too_far")


def forward(params, tokens, cfg, precision="f32", rows=None, fault=None):
    """Float32 logits of one sequence ``tokens`` (seq,), at every
    position or, with ``rows`` (an int array), at those positions only
    (the head is the one part that does not have to see every row)."""
    if fault is not None and fault not in FAULTS + TOY_FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    mm = _matmul(precision)
    eps, heads, groups = (cfg["rms_norm_eps"], cfg["num_heads"],
                          cfg["num_kv_heads"])
    d = cfg["head_dim"]
    seq = tokens.shape[0]
    unit = ROW_BLOCK if seq >= ROW_BLOCK else QUERY_BLOCK
    pad = -seq % unit        # zeros after the sequence: causal, so unseen
    tokens = jnp.pad(tokens, (0, pad))
    row_block = min(ROW_BLOCK, seq + pad)
    at = jnp.arange(seq + pad)
    h = params["token_embed"]["embedding"][tokens].astype(F32)
    for i, kind in enumerate(cfg["mixers"]):
        p = params[f"layer_{i}"]
        m = p["mixer"]

        def project(x, pos, m=m, kind=kind):
            q = mm(x, m["query"]["kernel"]).reshape(-1, heads, d)
            k = mm(x, m["key"]["kernel"]).reshape(-1, groups, d)
            v = mm(x, m["value"]["kernel"]).reshape(-1, groups, d)
            if fault != "qk_norm_dropped":
                q = _rms(q, m["q_norm"]["scale"], eps)
                k = _rms(k, m["k_norm"]["scale"], eps)
            if kind == "window":
                q, k = (_rope(t, pos, cfg["rope_theta"]) for t in (q, k))
            return q, k, v

        q, k, v = _blocks(project, [h, at], row_block)
        o = attention(q, k, v, reach_of(kind, cfg, fault))

        def finish(x, o, m=m, p=p):
            x = x + _rms(mm(o.reshape(-1, heads * d), m["out"]["kernel"]),
                         p["mixer_norm"]["scale"], eps)
            y = gated(mm, x, p["mlp"]) if "mlp" in p \
                else routed(mm, x, p["moe"], cfg)
            return x + _rms(y, p["mlp_norm"]["scale"], eps)

        h = _blocks(finish, [h, o], row_block)
    h = h[:seq] if rows is None else h[rows]
    h = _rms(h, params["final_norm"]["scale"], eps)
    if cfg["dim_model_base"]:     # toy sizes only: see the file's note
        h = h / (cfg["d_model"] / cfg["dim_model_base"])
    return mm(h, params["head"])
