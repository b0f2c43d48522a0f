"""The plain reference for SDAR-30B-A3B-Chat (arXiv:2510.06303): its
forward pass over one sequence under the block-causal mask, and the
published generation loop on top of it, in straightforward ``jax.numpy``
float32 at ``Precision.HIGHEST``. It imports nothing of ``horovod_tpu``
(its helpers that are not this model's own are ``benchmark/
reference_xing.py``'s), keeps no cache, batches nothing: the mask is a
mask over the full scores, the experts are a plain loop over all of them
with the row's weight for each (0 where not chosen), one call computes
every row from the tokens, every pass of :func:`generate` recomputes the
whole sequence, and a served request is compared with it on logits.

The weights are ``benchmark/weights_sdar.py``'s (made from the seed,
rounded to bfloat16 once and given to the program and to this file
alike; they are widened where they are used, which changes no value).
Work that is alike for every row (projections, experts) runs
``ROW_BLOCK`` rows at a time and attention ``QUERY_BLOCK`` queries at a
time against every key, so that the cell's longest request fits on the
chip beside the weights; a block of rows computes what the whole would.

The equations (``C`` the hidden size, no biases; what ``config.json``
does not say is listed in the configuration file's ``assumed`` with its
origin):

* trunk: ``h_0 = E[ids]``; layer ``i``: ``h += Attn_i(rms(h))``, then
  ``h += Moe_i(rms(h))``; ``logits = rms(h) W_head``, the head untied.
  Row ``t``'s logits are of position ``t``'s OWN token (no shift by one).
* attention: ``q = x W_q`` (``heads x d``), ``k = x W_k``, ``v = x W_v``
  (``kv_heads x d``); ``q`` and ``k`` each normed over their ``d`` with a
  learned scale, then rotated over all ``d`` dims (base ``rope_theta``,
  halves paired) on every layer; scale ``d^-0.5``; query head ``j`` reads
  key/value head ``j // (heads / kv_heads)``; a row sees the rows of the
  blocks before its own and every row of its own block, the later ones
  too: block ``b`` is positions ``b B .. b B + B - 1``.
* experts: ``g = x W_r`` over all ``num_experts`` in float32; ``p =
  softmax(g)``; the ``top_k`` largest kept and renormalised to sum 1
  (``norm_topk_prob``: the same numbers as a softmax over the chosen
  logits); ``y = sum_i w_i E_i(x)``, each ``E`` ``down(silu(gate x) * up
  x)`` of width ``expert_d_ff``; no shared expert.
* generation (``generate.py`` of the SDAR repository, ``low_confidence_
  static``): the prompt's whole blocks are the context; the next block
  holds the prompt's last ``P mod B`` tokens and ``[MASK]`` elsewhere.
  A pass runs the sequence up to the block's end; at every masked
  position ``x0 = argmax(logits)`` and ``c = softmax(logits)[x0]``; the
  ``B / denoising_steps`` masked positions of highest ``c`` take their
  ``x0``. When no mask is left the next block starts, all masked.

Departures from the published code, each for a stated reason:

* a row of the mask, not two cached stages: the published loop caches a
  finished block's keys and values in a pass of its own ("commit") and
  runs the block alone against the cache. Recomputing the sequence with
  the finished blocks' final tokens in place gives those very keys and
  values, so :func:`generate` has no commit pass and no cache.
* what is masked is kept beside the ids, not read off them
  (``cur_x == mask_id``): with random weights an argmax over 151,936
  tokens is the mask's own id once in a run, and the published loop
  would unmask that position twice.
* a pass unmasks ``min(B / denoising_steps, masked positions left)``:
  the published ``topk`` of a fixed count over a block that a prompt's
  tail has partly filled reaches into the known positions (confidence
  ``-inf``) and overwrites prompt tokens with their ``x0``.
* ties in confidence go to the lower position (``torch.topk`` leaves
  them open).
* greedy ``x0`` (the published default samples at temperature 1 and
  reads the confidence of the sample); the dynamic threshold
  (``low_confidence_dynamic``) is not here: the configuration's
  ``not_served`` says why.

:func:`forward` takes the rows' ``positions``, ``blocks`` and ``copies``
beside their tokens, because the comparison that decides ``correct``
teacher-forces a served request's whole trajectory in one call: the
committed sequence is copy 0, and the state of every generated block
before each of its passes rides behind it as a copy of its own (1, 2,
...) at the block's own positions. A row sees the copy-0 rows of the
blocks before its own and the rows of its own block IN ITS OWN COPY:
for copy 0 alone that is the block-causal mask above, and a row of copy
``c`` computes what pass ``c - 1`` of its block computed against the
cache. ``generate`` and the tests of the mask pass none of the three.

``precision="fp8"`` is the control: the operands of every dense matrix
multiplication (projections, experts, head) scaled per tensor into
float8_e4m3fn's range and rounded to it, as ``benchmark/reference.py``
has it; norms, the router, rotary positions and the softmax stay
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# what is the same plain mathematics in the other references is written
# once: the float8 control's rounding and the two matrix products, the
# RMSNorm, a function over blocks of rows, a configuration that can be a
# static argument; and K-EXAONE's rotation of every dim, halves paired
from benchmark.reference_kexaone import _rope
from benchmark.reference_xing import (Frozen, _blocks, _matmul,  # noqa: F401
                                      _rms, frozen)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512
QUERY_BLOCK = 128


def attention(q, k, v, blocks, copies):
    """Softmax attention over full scores under the mask of the module's
    note: row ``t`` sees row ``s`` where ``s`` is a copy-0 row of an
    earlier block, or a row of ``t``'s own block in ``t``'s own copy.
    ``q``: (rows, heads, d); ``k``/``v``: (rows, kv_heads, d); ``blocks``
    / ``copies``: (rows,) int; rows a multiple of ``QUERY_BLOCK`` or
    under it."""
    rows, heads, d = q.shape
    per = heads // k.shape[1]

    def one(q_b, block_b, copy_b):
        q_b = q_b.reshape(-1, heads // per, per, d)
        s = jnp.einsum("tgrd,sgd->grts", q_b, k, precision=HIGHEST) \
            * d ** -0.5
        seen = ((copies[None, :] == 0)
                & (blocks[None, :] < block_b[:, None])) \
            | ((copies[None, :] == copy_b[:, None])
               & (blocks[None, :] == block_b[:, None]))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        return o.reshape(-1, heads, d)

    return _blocks(one, [q, blocks, copies], min(QUERY_BLOCK, rows))


def routed(mm, x, p, cfg):
    """``sum_i w_i E_i(x)`` over all the experts, each computed for every
    row and weighted by the row's weight for it: the softmax over all the
    router's logits, the ``top_k`` largest kept and renormalised."""
    g = jnp.dot(x, p["router"].astype(F32), precision=HIGHEST)
    prob = jax.nn.softmax(g, axis=-1)
    _, chosen = jax.lax.top_k(prob, cfg["top_k"])
    took = (chosen[..., None] == jnp.arange(g.shape[-1])).any(axis=1)
    weight = prob * took / (prob * took).sum(axis=-1, keepdims=True)

    def one(y, xs):
        w_gate, w_up, w_down, w = xs
        e = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return y + w[:, None] * e, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"], weight.T))
    return y


def forward(params, tokens, cfg, precision="f32", rows=None, positions=None,
            blocks=None, copies=None):
    """Float32 logits of one sequence ``tokens`` (rows,), at every row
    or, with ``rows`` (an int array), at those rows only (the head is the
    one part that does not have to see every row). ``positions``,
    ``blocks``, ``copies``: (rows,) int, by default ``0, 1, 2, ...``,
    ``positions // block_len`` and 0 (the module's note)."""
    mm = _matmul(precision)
    eps, heads, groups = (cfg["rms_norm_eps"], cfg["num_heads"],
                          cfg["num_kv_heads"])
    d = cfg["head_dim"]
    seq = tokens.shape[0]
    if positions is None:
        positions = jnp.arange(seq)
    if blocks is None:
        blocks = positions // cfg["block_len"]
    if copies is None:
        copies = jnp.zeros((seq,), jnp.int32)
    unit = ROW_BLOCK if seq >= ROW_BLOCK else QUERY_BLOCK
    pad = -seq % unit
    # rows after the sequence: a block of their own that nothing sees
    tokens = jnp.pad(tokens, (0, pad))
    positions = jnp.pad(positions, (0, pad))
    blocks = jnp.pad(blocks, (0, pad), constant_values=-1)
    copies = jnp.pad(copies, (0, pad), constant_values=-1)
    row_block = min(ROW_BLOCK, seq + pad)
    h = params["token_embed"]["embedding"][tokens].astype(F32)
    for i in range(cfg["num_layers"]):
        p = params[f"layer_{i}"]
        m = p["mixer"]

        def project(h, pos, m=m, p=p):
            x = _rms(h, p["input_norm"]["scale"], eps)
            q = _rms(mm(x, m["query"]["kernel"]).reshape(-1, heads, d),
                     m["q_norm"]["scale"], eps)
            k = _rms(mm(x, m["key"]["kernel"]).reshape(-1, groups, d),
                     m["k_norm"]["scale"], eps)
            v = mm(x, m["value"]["kernel"]).reshape(-1, groups, d)
            return (_rope(q, pos, cfg["rope_theta"]),
                    _rope(k, pos, cfg["rope_theta"]), v)

        q, k, v = _blocks(project, [h, positions], row_block)
        o = attention(q, k, v, blocks, copies)

        def finish(h, o, m=m, p=p):
            h = h + mm(o.reshape(-1, heads * d), m["out"]["kernel"])
            return h + routed(mm, _rms(h, p["post_norm"]["scale"], eps),
                              p["moe"], cfg)

        h = _blocks(finish, [h, o], row_block)
    h = h[:seq] if rows is None else h[rows]
    h = _rms(h, params["final_norm"]["scale"], eps)
    return mm(h, params["head"])


def confidence(logits):
    """``softmax(logits)[argmax]`` a row, float32."""
    logits = logits.astype(F32)
    return 1.0 / jnp.sum(jnp.exp(logits - logits.max(-1, keepdims=True)),
                         axis=-1)


def choose(sure, masked, count):
    """Which of a block's positions a pass unmasks: the ``count`` masked
    ones of highest confidence ``sure``, ties to the lower position.
    numpy, (block,) each; returns a bool (block,)."""
    order = sorted(np.flatnonzero(masked), key=lambda j: (-sure[j], j))
    take = np.zeros(len(sure), bool)
    take[order[:count]] = True
    return take


def generate(params, prompt, cfg, max_new, run=None):
    """The published loop (module docstring): whole blocks are generated
    until ``max_new`` tokens after ``prompt`` are, and every pass
    recomputes the sequence. Returns the generated ids (all of the last
    block: the caller cuts the answer), for each the pass of its block
    that unmasked it, and the float32 logits of every pass as ``[(block
    start, (block_len, vocab) logits)]``. ``run(ids) -> logits`` of one
    sequence (default: :func:`forward`, jitted one program a length)."""
    block, mask_id = cfg["block_len"], cfg["mask_id"]
    unmask = block // cfg["denoising_steps"]
    if run is None:
        jitted = jax.jit(forward, static_argnums=(2,))
        run = lambda ids: jitted(params, jnp.asarray(ids, jnp.int32), cfg)
    prompt = list(prompt)
    whole = len(prompt) - len(prompt) % block
    total = -(-(len(prompt) + max_new) // block) * block
    ids = np.full((total,), mask_id, np.int64)
    ids[:len(prompt)] = prompt
    masked = np.arange(total) >= len(prompt)
    unmasked_at = np.full((total,), -1)
    passes = []
    for start in range(whole, total, block):
        here = slice(start, start + block)
        number = 0
        while masked[here].any():
            seen = np.where(masked, mask_id, ids)[:start + block]
            logits = np.asarray(run(seen))[here]
            passes.append((start, logits))
            take = choose(np.asarray(confidence(logits)), masked[here],
                          min(unmask, int(masked[here].sum())))
            ids[here][take] = logits.argmax(-1)[take]
            unmasked_at[here][take] = number
            masked[here] &= ~take
            number += 1
    return (ids[len(prompt):].tolist(), unmasked_at[len(prompt):].tolist(),
            passes)
