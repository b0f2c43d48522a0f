"""Decoders whose layers differ in kind (flax): block-sparse softmax
attention, linear ("lightning") attention with a fixed decay, power
retention (gated, normalised linear attention of degree 2), latent
attention (MLA: one low-rank latent and one rotary key a token, shared by
the heads), grouped-query softmax attention over every causal key
("full") or over the last ``window`` keys ("window") and Mamba-2
state-space layers ("mamba2": a selective scan, the decay a token a
head from the input), on a modern trunk -
RMSNorm on a sublayer's input or on its output, a SiLU-gated MLP or a
top-k router (sigmoid or softmax) over such MLPs ("experts", all of
them or this chip's share) beside a shared one or none, rotary positions
(plain or YaRN) or none, a causal mask or one that is causal over blocks
and open inside them (``block_len``: generation by diffusion over
blocks), grouped key/value heads, per-head QK-norm or none,
sigmoid output gates, one residual stream or several hyper-connected
ones, a head of its own or the embedding's, and (optional) muP scalings
or fixed multipliers.

:class:`HybridDecoder` reads its layer kinds from ``mixers`` and is what
``hvd.serve()`` runs for MiniCPM-SALA (``benchmark/configs/
minicpm-sala.json``; the plain reference is
``benchmark/reference_sala.py``), for Brumby-14B-Base
(``benchmark/configs/brumby-14b.json``, ``benchmark/
reference_brumby.py``), for Xing4.0-29B-A4B (``benchmark/configs/
xing4-29b-a4b.json``, ``benchmark/reference_xing.py``), for
K-EXAONE-236B-A23B (``benchmark/configs/k-exaone-236b-a23b.json``,
``benchmark/reference_kexaone.py``), for granite-4.0-h-small
(``benchmark/configs/granite-4.0-h-small.json``,
``benchmark/reference_granite.py``) and for SDAR-30B-A3B-Chat
(``benchmark/configs/sdar-30b-a3b.json``,
``benchmark/reference_sdar.py``). The blocks (:class:`RMSNorm`,
:class:`GatedMlp`, :func:`rope`, :class:`BlockSparseAttention`,
:class:`LightningAttention`, :class:`PowerRetention`,
:class:`LatentAttention`, :class:`GroupedQueryAttention`,
:class:`StateSpace`, :class:`RoutedExperts`, :class:`HyperConnection`)
are not tied to those models.

Serving (``decode=True``) keeps a ``cache`` collection whose leaves all
have the slot as axis 0; which variables a kind keeps, and their kinds, is
``MIXERS[kind].cache`` (a model of power-retention layers alone has no
leaf with a position axis):

* ``cached_key`` / ``cached_value`` ``(slots, kv_heads, head_dim,
  max_seq)`` - a sparse or a full layer's keys and values, positions
  last, the layout ``ops/pallas/kv_cache_write`` writes one token into;
* ``ring_key`` / ``ring_value`` ``(slots, kv_heads, head_dim, ring)`` - a
  window layer's, position ``p`` in column ``p mod ring``
  (:class:`GroupedQueryAttention`);
* ``compressed_key`` ``(slots, kv_heads, head_dim, windows)`` - the
  means of the key windows that block selection scores;
* ``state`` ``(slots, heads, head_dim, head_dim)`` float32 - a lightning
  layer's recurrent state, which does not grow with the context;
* ``state`` ``(slots, kv_heads, head_dim / 2 + 1, head_dim, head_dim)``
  and ``state_norm`` ``(slots, kv_heads, head_dim / 2 + 1, head_dim)``
  float32 - a power-retention layer's state, ``D = head_dim (head_dim +
  1) / 2`` rows of ``head_dim`` values, and its normaliser, both padded to
  whole rows of distances (``power_features``; 8,256 to 8,320 at width
  128) in the layout ``ops/pallas/power_retention`` reads;
* ``latent`` ``(slots, kv_rank, max_seq)`` and ``rope_key`` ``(slots,
  rope_dim, max_seq)`` - a latent-attention layer's normed latent and
  rotated key, positions last, nothing a head;
* ``ssm_state`` float32 and ``conv_state`` - a state-space layer's
  recurrent state and the rows before its convolution
  (:class:`StateSpace`); neither grows with the context;
* ``expert_counts`` ``(3, experts)`` uint32 - an expert layer's running
  count, the one leaf that is no slot's row (:class:`RoutedExperts`).

A call with one token a row is a decode step (``block_len`` tokens a
row where the model generates by blocks: one pass over every row's
block, :class:`GroupedQueryAttention`); a call with more is a
prefill from position 0, which computes the prompt without the cache and
then fills it; a model of power-retention layers alone, handed a slot's
cache and the ``positions`` where the pieces before ended, continues the
prompt from there (``HybridDecoder.resumable_prefill``), so that the
engine may run a prompt in pieces. A recurrence is not indifferent to
padding as masked softmax is, so a prefill takes the true ``lengths``:
the state it leaves is the state after ``lengths`` tokens (a
state-space layer's convolution tail the last true rows), and with
``lengths`` given the head runs on row ``lengths - 1`` alone.

Each kind's prompt form and step form are documented where they are
written: :func:`sparse_prompt_attention` and
:func:`sparse_step_attention`, :func:`lightning_chunked`,
:func:`retention_chunked` (and ``ops/pallas/power_retention``),
:func:`latent_prompt_attention` and :func:`latent_step_attention`,
:func:`window_prompt_attention`, :func:`full_prompt_attention` and
:func:`ring_step_attention`, :func:`ssm_chunked` and :func:`ssm_step`.
"""

from __future__ import annotations

import collections
import math
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.serving import ServingContract
from horovod_tpu.models.transformer import write_cache_rows
from horovod_tpu.ops.pallas import (grouped_decode_attention,
                                    latent_attention, power_retention,
                                    sparse_attention)
from horovod_tpu.ops.pallas.expert_combine import expert_combine
from horovod_tpu.ops.pallas.flash_attention import flash_attention
from horovod_tpu.ops.pallas.grouped_product import gated_products
from horovod_tpu.ops.pallas.kv_cache_write import (LANES, write_block,
                                                   write_token)

Dtype = Any
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_SPARSE, LIGHTNING = "block_sparse", "lightning"
POWER_RETENTION = "power_retention"
LATENT = "latent"
# grouped-query softmax attention over every causal key, and the same over
# the last ``window`` keys
FULL, WINDOW = "full", "window"
# a Mamba-2 state-space layer
MAMBA2 = "mamba2"
# where a sublayer's RMSNorm sits: ``h += F(norm(h))`` or ``h += norm(F(h))``
NORM_INPUT, NORM_OUTPUT = "input", "output"
# a layer's MLP: one gated MLP, or a router over experts beside a shared one
DENSE_MLP, EXPERTS_MLP = "dense", "experts"
# a router's scores: sigmoids normalised over the chosen (``noaux_tc``), or
# a softmax over the chosen logits
SIGMOID_ROUTER, SOFTMAX_ROUTER = "sigmoid", "softmax"
# a masked score: finite, so that a row with nothing to see stays a number
NEG_INF = -1e30

# queries a block of the sparse layer's prompt selection: the float32
# scores of one block are (heads, QUERY_BLOCK, key windows)
QUERY_BLOCK = 128
# the prompt's query blocks run in at most this many groups, each against
# the key windows up to its own end (static), so that about half of the
# windows after a query are never scored
KEY_EXTENTS = 8
# query blocks of a window layer's prompt that are scored at once: the
# float32 scores of one turn are (heads, BAND_BLOCKS, block, 2 block)
BAND_BLOCKS = 16


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, in float32."""

    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x = x.astype(F32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps)
        return (y * scale.astype(F32)).astype(self.dtype)


class GatedMlp(nn.Module):
    """``down(silu(gate x) * up x)``, no biases."""

    d_ff: int
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        h = nn.silu(dense(self.d_ff, name="gate")(x)) \
            * dense(self.d_ff, name="up")(x)
        return dense(x.shape[-1], name="down")(h)


def yarn_frequencies(dim, theta, factor, original, beta_fast, beta_slow):
    """The ``dim / 2`` rotary frequencies of YaRN (arXiv:2309.00071, as
    DeepSeek-V2 publishes it): a pair that turns more than ``beta_fast``
    times over the ``original`` context keeps its frequency
    ``theta^(-2i/dim)``, one that turns fewer than ``beta_slow`` times
    has it divided by ``factor``, and between the two correction
    dimensions (``dim ln(original / (2 pi turns)) / (2 ln theta)``,
    floor of the fast one, ceiling of the slow one) a linear ramp mixes
    them."""
    half = dim // 2
    plain = theta ** (-jnp.arange(half, dtype=F32) / half)

    def turning(turns):
        return dim * math.log(original / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(turning(beta_fast)), 0)
    high = min(math.ceil(turning(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low)
                    / (high - low if high > low else 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope(x, positions, theta, frequencies=None):
    """Rotary positions over the whole head width, halves paired
    (``x[..., i]`` with ``x[..., i + d/2]``). ``x``: (batch, seq, heads,
    d); ``positions``: (batch, seq) absolute; ``frequencies``: (d / 2,)
    in place of ``theta^(-2i/d)`` (:func:`yarn_frequencies`). Float32 in
    and out."""
    half = x.shape[-1] // 2
    freq = (theta ** (-jnp.arange(half, dtype=F32) / half)
            if frequencies is None else frequencies)
    angle = positions.astype(F32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(F32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ---------------------------------------------------------------- lightning

def lightning_slopes(heads, layer_index, published_depth):
    """The per-head decay rates ``s_h`` (``lambda_h = exp(-s_h)``):
    ``2^(-8h/heads)``, h = 1..heads, times ``1 - l/(depth-1) + 1e-5`` for
    the layer's published index ``l``."""
    base = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)
    return base * (1.0 - layer_index / max(published_depth - 1, 1) + 1e-5)


def lightning_step(state, q, k, v, slopes):
    """One token of the recurrence: ``S = lambda S + k^T v``,
    ``o = q S / sqrt(d)``. ``state``: (batch, heads, d, d) float32;
    ``q``/``k``/``v``: (batch, heads, d)."""
    q, k, v = (t.astype(F32) for t in (q, k, v))
    state = (jnp.exp(-slopes)[None, :, None, None] * state
             + k[..., :, None] * v[..., None, :])
    o = jnp.einsum("bhd,bhde->bhe", q, state, precision=HIGHEST)
    return state, o / math.sqrt(q.shape[-1])


def lightning_chunked(q, k, v, slopes, lengths=None, chunk=256):
    """The same recurrence over a whole sequence from a zero state, by
    chunks: inside a chunk the masked products with the decay, between
    chunks the state. ``q``/``k``/``v``: (batch, seq, heads, d).

    Returns the outputs (batch, seq, heads, d) float32 and the state
    after ``lengths`` tokens of each row (default: all of them); rows of
    the output at or past ``lengths`` mean nothing. Every exponent is at
    most 0, so nothing overflows however long the sequence."""
    batch, seq, heads, d = q.shape
    chunk = min(chunk, seq)
    pad = -seq % chunk
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    if lengths is None:
        lengths = jnp.full((batch,), seq, jnp.int32)
    n = (seq + pad) // chunk
    # (chunks, batch, chunk, heads, d)
    qs, ks, vs = (t.reshape(batch, n, chunk, heads, d).transpose(
        1, 0, 2, 3, 4) for t in (q, k, v))
    at = jnp.arange(chunk, dtype=F32)
    apart = at[:, None] - at[None, :]
    rate = slopes[:, None, None]
    within = jnp.where(apart >= 0, jnp.exp(-rate * jnp.maximum(apart, 0.0)),
                       0.0)                               # (heads, i, j)
    since_start = jnp.exp(-slopes[None, :] * (at[:, None] + 1.0))  # (i, h)
    scale = 1.0 / math.sqrt(d)

    def one(state, xs):
        q_c, k_c, v_c, start = xs
        s = jnp.einsum("bihd,bjhd->bhij", q_c, k_c,
                       preferred_element_type=F32) * (within * scale)
        o = jnp.einsum("bhij,bjhd->bihd", s.astype(v_c.dtype), v_c,
                       preferred_element_type=F32)
        o = o + jnp.einsum(
            "bihd,bhde->bihe",
            q_c.astype(F32) * (since_start * scale)[None, :, :, None],
            state, precision=HIGHEST)
        # the state after this chunk's first ``valid`` tokens
        valid = jnp.clip(lengths - start, 0, chunk).astype(F32)   # (batch,)
        left = valid[:, None, None] - 1.0 - at[None, :, None]     # (b, j, 1)
        weight = jnp.where(left >= 0,
                           jnp.exp(-slopes[None, None, :]
                                   * jnp.maximum(left, 0.0)), 0.0)  # (b, j, h)
        state = (jnp.exp(-slopes[None, :] * valid[:, None])[..., None, None]
                 * state
                 + jnp.einsum("bjhd,bjhe->bhde",
                              k_c.astype(F32) * weight[..., None],
                              v_c.astype(F32), precision=HIGHEST))
        return state, o

    state = jnp.zeros((batch, heads, d, d), F32)
    starts = jnp.arange(n, dtype=jnp.int32) * chunk
    state, out = jax.lax.scan(one, state, (qs, ks, vs, starts))
    out = out.transpose(1, 0, 2, 3, 4).reshape(batch, n * chunk, heads, d)
    return out[:, :seq], state


class LightningAttention(nn.Module):
    """Linear attention with a per-head decay: QK-norm, rotary positions,
    the recurrence, a per-head output norm and a sigmoid output gate."""

    num_heads: int
    head_dim: int
    layer_index: int = 0
    published_depth: int = 1
    rope_theta: float = 10000.0
    eps: float = 1e-6
    chunk: int = 256
    decode: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        batch, seq, d_model = x.shape
        heads, d = self.num_heads, self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        norm = partial(RMSNorm, eps=self.eps, dtype=self.dtype,
                       param_dtype=self.param_dtype)
        split = lambda t: t.reshape(batch, seq, heads, d)
        q = norm(name="q_norm")(split(dense(heads * d, name="query")(x)))
        k = norm(name="k_norm")(split(dense(heads * d, name="key")(x)))
        v = split(dense(heads * d, name="value")(x))
        gate = dense(heads * d, name="gate")(x)
        at = positions[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]
        q = rope(q, at, self.rope_theta).astype(self.dtype)
        k = rope(k, at, self.rope_theta).astype(self.dtype)
        slopes = lightning_slopes(heads, self.layer_index,
                                  self.published_depth)
        with jax.named_scope("lightning"):
            if self.decode and seq == 1:
                state = self.variable("cache", "state", jnp.zeros,
                                      (batch, heads, d, d), F32)
                state.value, o = lightning_step(
                    state.value, q[:, 0], k[:, 0], v[:, 0], slopes)
                o = o[:, None]
            else:
                o, last = lightning_chunked(q, k, v, slopes, lengths,
                                            self.chunk)
                if self.decode:
                    self.variable("cache", "state", jnp.zeros,
                                  (batch, heads, d, d), F32).value = last
        o = norm(name="o_norm")(o).reshape(batch, seq, heads * d)
        o = o * jax.nn.sigmoid(gate.astype(F32)).astype(self.dtype)
        return dense(d_model, name="out")(o)


# --------------------------------------------------------- power retention

def power_features(x):
    """``phi(x)``, the symmetric half of ``x (x) x``, scaled so that
    ``phi(x) . phi(y) = (x . y)^2 / d`` exactly: ``d (d + 1) / 2`` entries
    (``x_a^2`` once, ``sqrt(2) x_a x_b`` once for each pair ``a != b``),
    in ``(d/2 + 1) d`` places, whole lane tiles.

    The pairs are laid out by their distance round the head: entry
    ``o d + a`` is ``x_a x_((a + o) mod d)`` for ``o = 0 .. d/2``; the
    last distance pairs each ``a < d/2`` with ``a + d/2``, and the other
    half of its row is zeros. A row of distances is then ``x`` times a
    rotation of ``x``, and all the rotations at once are one product of
    ``x`` with a matrix of zeros and ones (exact: in one pass where ``x``
    is bfloat16, at the highest precision otherwise), so nothing is
    gathered and nothing is copied lane by lane. ``x``: (..., d) with
    ``d`` even; float32 out."""
    d = x.shape[-1]
    turns = power_retention.turns(d)
    source = jnp.arange(d)[:, None, None]
    turn = jnp.arange(turns)[None, :, None]
    at = jnp.arange(d)[None, None, :]
    turned = jnp.dot(
        x, (source == (at + turn) % d).astype(x.dtype).reshape(d, turns * d),
        precision=None if x.dtype == jnp.bfloat16 else HIGHEST
    ).reshape(x.shape[:-1] + (turns, d))      # [..., o, a] = x[(a + o) % d]
    once = (turn < turns - 1) | (at < d // 2)
    out = (x.astype(F32)[..., None, :] * turned.astype(F32)
           * jnp.where(once[0], power_retention.turn_weights(d), 0.0))
    return out.reshape(x.shape[:-1] + (turns * d,))


def retention_step(state, norm, q, k, v, log_gate, eps=1e-6):
    """One token of power retention: ``S = e^g S + phi(k) v^T``,
    ``z = e^g z + phi(k)``, ``y = phi(q)^T S / (phi(q)^T z + eps)``, in
    one pass over the state (``ops/pallas/power_retention.step_state``).

    ``state``: (batch, kv_heads, turns, d, d) and ``norm``: (batch,
    kv_heads, turns, d), float32, the cache's layout (:func:`cache_state`);
    ``q``: (batch, heads, d), query head ``h`` reading the state of
    key/value head ``h // (heads / kv_heads)``; ``k``/``v``: (batch,
    kv_heads, d); ``log_gate``: (batch, kv_heads) float32, at most 0.
    Everything is float32. Returns the new state and normaliser and ``y``
    (batch, heads, d)."""
    batch, heads, d = q.shape
    groups = k.shape[1]
    state, norm, num, den = power_retention.step_state(
        state, norm,
        q.astype(F32).reshape(batch, groups, heads // groups, d),
        k.astype(F32), v.astype(F32), jnp.exp(log_gate.astype(F32)))
    return state, norm, (num / (den[..., None] + eps)).reshape(
        batch, heads, d)


def cache_state(state, norm):
    """A prefill's state (batch, kv_heads, turns d, d) and normaliser
    (batch, kv_heads, turns d), both padded to whole rows of distances,
    as the cache keeps them: (batch, kv_heads, turns, d, d) with the
    values before the pairs (``[o, e, a]``) and (batch, kv_heads, turns,
    d)."""
    d = state.shape[-1]
    return (jnp.swapaxes(state.reshape(state.shape[:2] + (-1, d, d)), -1, -2),
            norm.reshape(norm.shape[:2] + (-1, d)))


def retention_chunked(q, k, v, log_gate, lengths=None, chunk=256, eps=1e-6,
                      dtype=jnp.bfloat16, initial=None):
    """The same recurrence over a whole sequence, by chunks: inside a
    chunk the masked squares ``(q_i . k_j)^2 / d`` with ``exp(b_i -
    b_j)``, ``b`` the running sum of the chunk's log-gates; between
    chunks the state and its normaliser. ``q``: (batch, seq, heads, d);
    ``k``/``v``: (batch, seq, kv_heads, d); ``log_gate``: (batch, seq,
    kv_heads) float32. The sequence starts from a zero state, or from
    ``initial``: the state and the normaliser an earlier call returned,
    so that a sequence cut into pieces at multiples of ``chunk`` makes
    the products of the whole one in the same order.

    A position at or past ``lengths`` neither decays the state nor
    enters it (its gate is taken as 1 and its key as absent), so the
    state returned is the state after ``lengths`` tokens of each row;
    rows of the output past ``lengths`` mean nothing. Matrix operands
    are ``dtype`` (the features and the state of the between-chunk
    products among them) and every sum float32; the normaliser sums the
    features as the state's product rounded them, so that an output is
    the same weighted mean above and below the line. Every exponent is at
    most 0. Returns the outputs (batch, seq, heads, d) in ``dtype`` and
    the state and the normaliser as the cache keeps them
    (:func:`cache_state`), both float32."""
    batch, seq, heads, d = q.shape
    groups = k.shape[2]
    per = heads // groups
    # features, state and normaliser keep whole lane tiles throughout
    # (``power_features``): the places past D are zeros
    turns = power_retention.turns(d)
    turn_weights = power_retention.turn_weights(d)
    chunk = min(chunk, seq)
    pad = -seq % chunk
    if pad:
        q, k, v, log_gate = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, log_gate))
    if lengths is None:
        lengths = jnp.full((batch,), seq, jnp.int32)
    n = (seq + pad) // chunk
    present = (jnp.arange(n * chunk, dtype=jnp.int32)[None, :]
               < lengths[:, None])                        # (batch, seq)
    log_gate = jnp.where(present[..., None], log_gate.astype(F32), 0.0)

    def cut(t, inner):
        """(batch, seq, kv_heads, ...) as (chunks, batch, kv_heads, chunk,
        ...): key/value heads before positions, as every product below
        batches them, so that a chunk's features are made in the order
        their product reads them and are never transposed."""
        t = t.reshape((batch, n, chunk, groups) + inner)
        return jnp.moveaxis(jnp.moveaxis(t, 3, 1), 2, 0)

    at = jnp.arange(chunk)
    causal = at[:, None] >= at[None, :]                   # (i, j)
    scale = 1.0 / math.sqrt(d)

    def one(carry, xs):
        state, norm = carry
        q_c, k_c, v_c, g_c, here = xs     # (b, g, i, r, d) (b, g, j, d) ...
        since = jnp.cumsum(g_c, axis=-1)                  # b: (batch, g, i)
        whole = since[..., -1]                            # (batch, g)
        # inside the chunk
        s = jnp.einsum("bgird,bgjd->bgrij", q_c, k_c,
                       preferred_element_type=F32) * scale
        apart = since[..., :, None] - since[..., None, :]     # (b, g, i, j)
        seen = causal & here[:, None, None, :]
        weight = jnp.where(seen, jnp.exp(jnp.minimum(apart, 0.0)), 0.0)
        a = s * s * weight[:, :, None]                    # (b, g, r, i, j)
        num = jnp.einsum("bgrij,bgje->bgire", a.astype(dtype), v_c,
                         preferred_element_type=F32)
        den = jnp.moveaxis(a.sum(axis=-1), 2, 3)          # (b, g, i, r)
        # what came before the chunk: phi(q)^T S and phi(q)^T z, the
        # features made inside the kernel and their weights put on the
        # state's side
        before, total = power_retention.read_state(
            q_c.reshape(batch * groups, chunk * per, d),
            (state.reshape(batch * groups, turns, d, d)
             * turn_weights[..., None]).astype(dtype),
            norm.reshape(batch * groups, turns, d) * turn_weights)
        grown = jnp.exp(since)[..., None]                 # (b, g, i, 1)
        num = num + grown[..., None] * before.reshape(num.shape)
        den = den + grown * total.reshape(den.shape)
        out = (num / (den[..., None] + eps)).astype(dtype)
        # the state after the chunk's present tokens
        left = jnp.where(here[:, None, :],
                         jnp.exp(whole[..., None] - since), 0.0)  # (b, g, j)
        f_k = (power_features(k_c) * left[..., None]).astype(dtype)
        #                                                   (b, g, j, n)
        kept = jnp.exp(whole)
        state = kept[..., None, None] * state + jnp.einsum(
            "bgjn,bgje->bgne", f_k, v_c, preferred_element_type=F32)
        norm = kept[..., None] * norm + f_k.astype(F32).sum(axis=2)
        return (state, norm), out

    if initial is None:
        carry = (jnp.zeros((batch, groups, turns * d, d), F32),
                 jnp.zeros((batch, groups, turns * d), F32))
    else:       # out of the cache's layout: the inverse of cache_state
        state, norm = initial
        carry = (
            jnp.swapaxes(state, -1, -2).reshape(batch, groups, turns * d, d),
            norm.reshape(batch, groups, turns * d))
    (state, norm), out = jax.lax.scan(one, carry, (
        cut(q, (per, d)), cut(k, (d,)), cut(v, (d,)), cut(log_gate, ()),
        jnp.moveaxis(present.reshape(batch, n, chunk), 1, 0)))
    # (chunks, batch, g, chunk, r, d) -> (batch, positions, heads, d)
    out = out.transpose(1, 0, 3, 2, 4, 5).reshape(batch, n * chunk, heads, d)
    return (out[:, :seq],) + cache_state(state, norm)


class PowerRetention(nn.Module):
    """Power retention of degree 2 over grouped heads: QK-norm, rotary
    positions, one log-sigmoid gate a token a key/value head (float32,
    with a bias), the recurrence and its normaliser; no output gate and
    no output norm beyond the division."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    den_eps: float = 1e-6
    chunk: int = 256
    decode: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        batch, seq, d_model = x.shape
        heads, groups, d = self.num_heads, self.num_kv_heads, self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        norm = partial(RMSNorm, eps=self.eps, dtype=self.dtype,
                       param_dtype=self.param_dtype)
        q = norm(name="q_norm")(
            dense(heads * d, name="query")(x).reshape(batch, seq, heads, d))
        k = norm(name="k_norm")(
            dense(groups * d, name="key")(x).reshape(batch, seq, groups, d))
        v = dense(groups * d, name="value")(x).reshape(batch, seq, groups, d)
        log_gate = jax.nn.log_sigmoid(nn.Dense(
            groups, dtype=F32, param_dtype=self.param_dtype,
            name="gate")(x))
        at = positions[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]
        q = rope(q, at, self.rope_theta).astype(self.dtype)
        k = rope(k, at, self.rope_theta).astype(self.dtype)
        turns = power_retention.turns(d)
        # a prompt's piece that was handed a cache continues from it; one
        # that was handed none starts from zeros and leaves a fresh cache
        resumed = self.decode and self.has_variable("cache", "state")
        if self.decode:
            state = self.variable("cache", "state", jnp.zeros,
                                  (batch, groups, turns, d, d), F32)
            total = self.variable("cache", "state_norm", jnp.zeros,
                                  (batch, groups, turns, d), F32)
        if self.decode and seq == 1:
            with jax.named_scope("retention_step"):
                state.value, total.value, o = retention_step(
                    state.value, total.value, q[:, 0], k[:, 0], v[:, 0],
                    log_gate[:, 0], self.den_eps)
            o = o.astype(self.dtype)[:, None]
        else:
            with jax.named_scope("retention_chunk"):
                o, last, last_norm = retention_chunked(
                    q, k, v, log_gate, lengths, self.chunk, self.den_eps,
                    self.dtype,
                    (state.value, total.value) if resumed else None)
            if self.decode:
                state.value, total.value = last, last_norm
        return dense(d_model, name="out")(o.reshape(batch, seq, heads * d))


# ------------------------------------------------------------- block sparse

def compress_keys(k, kernel, stride):
    """``Kc_j = mean(k[stride j : stride j + kernel])`` for every window
    that lies inside the sequence. ``k``: (batch, seq, kv_heads, d);
    returns (batch, windows, kv_heads, d) float32 (no windows: 0)."""
    batch, seq, groups, d = k.shape
    parts, steps = kernel // stride, seq // stride
    if steps < parts:
        return jnp.zeros((batch, 0, groups, d), F32)
    sums = k[:, :steps * stride].astype(F32).reshape(
        batch, steps, stride, groups, d).sum(axis=2)
    windows = steps - parts + 1
    return sum(sums[:, i:i + windows] for i in range(parts)) / kernel


def count_windows(positions, sparse):
    """Whole key windows inside the first ``positions`` positions."""
    return max((positions - sparse["kernel"]) // sparse["stride"] + 1, 0)


def block_scores(scores, q_pos, n_blocks, sparse):
    """From a query's scores against the compressed keys to a score for
    each key block. ``scores``: (..., heads of the group, windows)
    float32, already scaled; ``q_pos``: (...,) the query's position.

    Softmax over the windows that end at or before the query, summed over
    the group's heads; a block's score is the largest among the windows
    that overlap it. Returns (..., n_blocks)."""
    kernel, stride, size = (sparse["kernel"], sparse["stride"],
                            sparse["block_size"])
    windows = scores.shape[-1]
    ends = jnp.arange(windows, dtype=jnp.int32) * stride + kernel - 1
    seen = ends <= q_pos[..., None, None]
    s = jnp.where(seen, scores, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    p = p.sum(axis=-2)                                       # (..., windows)
    # windows r b - (m - 1) .. r b + r - 1 overlap block b
    r, m = size // stride, kernel // stride
    p = jnp.pad(p[..., :r * n_blocks],
                [(0, 0)] * (p.ndim - 1)
                + [(m - 1, max(r * n_blocks - windows, 0))])
    lead = (1,) * (p.ndim - 1)
    return jax.lax.reduce_window(p, -jnp.inf, jax.lax.max,
                                 lead + (r + m - 1,), lead + (r,), "VALID")


def select_blocks(scores, q_pos, sparse):
    """Which key blocks each query attends: (..., n_blocks) bool from the
    blocks' ``scores`` (..., n_blocks) and the queries' positions (...,).

    A query whose context (``q_pos + 1`` tokens) is at most ``dense_len``
    takes every causal block. Past it: the first ``init_blocks`` blocks,
    the blocks covering its last ``window_size`` tokens, and the
    highest-scoring others until ``topk`` in all, ties to the lower index."""
    size, topk = sparse["block_size"], sparse["topk"]
    n_blocks = scores.shape[-1]
    block = jnp.arange(n_blocks, dtype=jnp.int32)
    at = q_pos[..., None]
    causal = block <= at // size
    if n_blocks <= topk:
        return causal
    near = block >= jnp.maximum(at - sparse["window_size"] + 1, 0) // size
    forced = (block < sparse["init_blocks"]) | near
    s = jnp.where(forced, jnp.inf, scores)
    s = jnp.where(causal, s, -1.0)
    # the topk highest, ties to the lower index (neighbouring blocks tie
    # exactly whenever the window they share is the best of both)
    kth = jax.lax.top_k(s, topk)[0][..., -1:]
    above, tied = s > kth, s == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    return jnp.where(at + 1 <= sparse["dense_len"], causal, causal & chosen)


def _token_mask(selected, q_pos, size, keys):
    """(..., keys) bool: the key's block is selected and the key is not
    after the query."""
    mask = jnp.repeat(selected, size, axis=-1)[..., :keys]
    return mask & (jnp.arange(keys, dtype=jnp.int32) <= q_pos[..., None])


def prompt_block_choice(q, compressed, sparse, dtype):
    """The key blocks every query of a prompt attends, as the table
    ``ops/pallas/sparse_attention`` reads: (batch, kv_heads, seq, blocks)
    int8, 1 where :func:`select_blocks` set the block (every causal
    block at or under ``dense_len``).

    ``q``: (batch, seq, kv_heads, heads of the group, d), ``seq`` a
    multiple of ``QUERY_BLOCK`` or under it; ``compressed``:
    :func:`compress_keys` of the same ``seq`` keys. A block of
    ``QUERY_BLOCK`` queries at a time, each group of blocks against the
    windows up to its own end."""
    batch, seq, groups, per, d = q.shape
    size, scale = sparse["block_size"], 1.0 / math.sqrt(d)
    q_block = min(QUERY_BLOCK, seq)
    blocks = seq // q_block
    q = q.reshape(batch, blocks, q_block, groups, per, d)
    extents = max(e for e in range(1, KEY_EXTENTS + 1) if blocks % e == 0)
    each = blocks // extents
    rows = []
    for e in range(extents):
        first = e * each * q_block
        end = first + each * q_block          # keys this group can see
        n_blocks = -(-end // size)
        if end <= sparse["dense_len"] or n_blocks <= sparse["topk"]:
            # every causal block, no scores
            at = jnp.arange(first, end, dtype=jnp.int32)[:, None]
            chosen = jnp.broadcast_to(
                jnp.arange(n_blocks, dtype=jnp.int32) <= at // size,
                (batch, groups, end - first, n_blocks)).astype(jnp.int8)
        else:
            kc_e = compressed[:, :count_windows(end, sparse)].astype(dtype)

            def one(xs):  # mapped below, inside this turn of the loop
                q_b, start = xs               # (batch, q_block, g, per, d)
                q_pos = start + jnp.arange(q_block, dtype=jnp.int32)
                q_pos = jnp.broadcast_to(q_pos, (batch, 1, q_block))
                s = jnp.einsum("btgrd,bjgd->bgtrj", q_b, kc_e,
                               preferred_element_type=F32) * scale
                return select_blocks(
                    block_scores(s, q_pos, n_blocks, sparse), q_pos,
                    sparse).astype(jnp.int8)   # (batch, g, t, n_blocks)

            chosen = jax.lax.map(one, (
                q[:, e * each:(e + 1) * each].transpose(1, 0, 2, 3, 4, 5),
                first + jnp.arange(each, dtype=jnp.int32) * q_block))
            # (each, batch, g, q_block, blocks) -> (batch, g, positions,
            # blocks)
            chosen = chosen.transpose(1, 2, 0, 3, 4).reshape(
                batch, groups, end - first, n_blocks)
        rows.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, 0),
                                     (0, -(-seq // size) - n_blocks))))
    return jnp.concatenate(rows, axis=2)


def sparse_prompt_attention(q, k, v, sparse, dtype):
    """Block-sparse causal attention of a whole prompt from position 0.

    ``q``: (batch, seq, heads, d); ``k``/``v``: (batch, seq, kv_heads, d).
    Returns (batch, seq, heads, d) in ``dtype``, the compressed keys
    (batch, windows, kv_heads, d) float32 and the kernel's live share of
    key blocks (a float32 scalar).

    Each query's key blocks (:func:`select_blocks`, in XLA) become a
    table of bits, and one kernel (``ops/pallas/sparse_attention``)
    computes the attention under it: any sequence length, padded here to
    a multiple of ``QUERY_BLOCK``."""
    batch, seq, heads, d = q.shape
    groups = k.shape[2]
    pad = -seq % min(QUERY_BLOCK, seq)
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    compressed = compress_keys(k, sparse["kernel"], sparse["stride"])
    with jax.named_scope("sparse_select"):
        chosen = prompt_block_choice(
            q.reshape(batch, seq + pad, groups, heads // groups, d),
            compressed, sparse, dtype)
    with jax.named_scope("sparse_attn"):
        out, share = sparse_attention.sparse_prompt_attention(
            q, k, v, chosen, block_size=sparse["block_size"])
    return (out[:, :seq].astype(dtype),
            compressed[:, :count_windows(seq, sparse)], share)


def sparse_step_attention(q, keys, values, compressed, positions, sparse,
                          dtype):
    """One decode step of the same attention against the cache.

    ``q``: (batch, heads, d); ``keys``/``values``: (batch, kv_heads, d,
    max_seq); ``compressed``: (batch, kv_heads, d, windows, padded);
    ``positions``: (batch,) the new token's position, already written.
    Rows at or under ``dense_len`` attend every key up to their own."""
    batch, heads, d = q.shape
    groups, max_seq = keys.shape[1], keys.shape[-1]
    size = sparse["block_size"]
    scale = 1.0 / math.sqrt(d)
    q = q.reshape(batch, groups, heads // groups, d)
    q_pos = positions[:, None]                              # (batch, 1)
    n_blocks = -(-max_seq // size)
    with jax.named_scope("sparse_select"):
        windows = count_windows(max_seq, sparse)
        s = jnp.einsum("bgrd,bgdj->bgrj", q, compressed[..., :windows],
                       preferred_element_type=F32) * scale
        chosen = select_blocks(block_scores(s, q_pos, n_blocks, sparse),
                               q_pos, sparse)               # (b, g, blocks)
    with jax.named_scope("sparse_attn"):
        mask = _token_mask(chosen, q_pos, size, max_seq)    # (b, g, keys)
        s = jnp.einsum("bgrd,bgds->bgrs", q, keys,
                       preferred_element_type=F32) * scale
        s = jnp.where(mask[:, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bgrs,bgds->bgrd", p, values)
    return o.reshape(batch, heads, d)


class BlockSparseAttention(nn.Module):
    """Grouped-query softmax attention over the key blocks each query
    selects by its scores against compressed keys (InfLLM-v2's scheme),
    with QK-norm, no positional encoding and a sigmoid output gate.

    ``sparse`` is a mapping with ``kernel``, ``stride``, ``block_size``,
    ``topk``, ``init_blocks``, ``window_size`` and ``dense_len``
    (``block_size`` and ``kernel`` multiples of ``stride``)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    sparse: Any = None
    eps: float = 1e-6
    decode: bool = False
    max_cache_len: int = 0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        del lengths       # masked softmax: padded keys are never attended
        batch, seq, d_model = x.shape
        heads, groups, d = self.num_heads, self.num_kv_heads, self.head_dim
        sparse = dict(self.sparse)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        norm = partial(RMSNorm, eps=self.eps, dtype=self.dtype,
                       param_dtype=self.param_dtype)
        q = norm(name="q_norm")(
            dense(heads * d, name="query")(x).reshape(batch, seq, heads, d))
        k = norm(name="k_norm")(
            dense(groups * d, name="key")(x).reshape(batch, seq, groups, d))
        v = dense(groups * d, name="value")(x).reshape(batch, seq, groups, d)
        gate = dense(heads * d, name="gate")(x)

        if self.decode:
            windows = count_windows(self.max_cache_len, sparse)
            kv_shape = (batch, groups, d, self.max_cache_len)
            keys = self.variable("cache", "cached_key", jnp.zeros,
                                 kv_shape, self.dtype)
            values = self.variable("cache", "cached_value", jnp.zeros,
                                   kv_shape, self.dtype)
            compressed = self.variable(
                "cache", "compressed_key", jnp.zeros,
                (batch, groups, d, -(-windows // LANES) * LANES), self.dtype)
            keys.value = write_cache_rows(keys.value, k, positions)
            values.value = write_cache_rows(values.value, v, positions)

        if self.decode and seq == 1:
            # the newest window the context has completed; before the
            # first one is whole this writes a partial mean into window
            # 0, which no query sees until a later step has rewritten it
            window = jnp.maximum(positions + 1 - sparse["kernel"], 0) \
                // sparse["stride"]
            # a slice a row, in a loop: vmapped it is a gather, for which
            # XLA:TPU copies the whole key cache into another layout
            inside = jax.lax.map(
                lambda xs: jax.lax.dynamic_slice(
                    keys.value, (xs[0], 0, 0, xs[1]),
                    (1, groups, d, sparse["kernel"]))[0],
                (jnp.arange(batch, dtype=jnp.int32),
                 window * sparse["stride"]))
            compressed.value = write_token(
                compressed.value,
                inside.astype(F32).mean(axis=-1).astype(self.dtype), window)
            o = sparse_step_attention(
                q[:, 0], keys.value, values.value, compressed.value,
                positions, sparse, self.dtype)[:, None]
        else:
            o, kc, share = sparse_prompt_attention(q, k, v, sparse,
                                                   self.dtype)
            # what the prompt kernel ran of the blocks under the diagonal:
            # the serving engine reads it with the prefill's first token
            self.sow("kernel_stats", "live_block_share", share)
            if self.decode and kc.shape[1]:
                # windows that reach past the true length hold padding:
                # the decode step that completes one rewrites it
                compressed.value = jax.lax.dynamic_update_slice(
                    compressed.value,
                    kc.astype(self.dtype).transpose(0, 2, 3, 1),
                    (0, 0, 0, 0))
        o = o.reshape(batch, seq, heads * d)
        o = o * jax.nn.sigmoid(gate.astype(F32)).astype(self.dtype)
        return dense(d_model, name="out")(o)


# --------------------------------------------------------- latent attention

def latent_scale(qk_dim, yarn=None):
    """The softmax scale ``qk_dim^-0.5``, times YaRN's ``mscale^2``
    (``mscale = 0.1 mscale_all_dim ln(factor) + 1``) where the positions
    are stretched."""
    scale = qk_dim ** -0.5
    if yarn and yarn["factor"] > 1:
        mscale = 0.1 * yarn.get("mscale_all_dim", 0) \
            * math.log(yarn["factor"]) + 1.0
        scale *= mscale * mscale
    return scale


def latent_step_attention(q_nope, q_rope, kv_b, latent, rope_key, positions,
                          scale, dtype):
    """One decode step of latent attention in its absorbed form: the
    queries go into the latent's space (``qt_h = q_nope_h W_K,h^T``), the
    scores and the weighted sum are taken against the cached latents
    themselves, and the sum comes out through ``W_V,h``. No cached
    position is ever expanded to keys and values.

    ``q_nope``: (batch, heads, nope); ``q_rope``: (batch, heads, rope_dim),
    rotated; ``kv_b``: (rank, heads, nope + v); ``latent``: (batch, rank,
    max_seq) and ``rope_key``: (batch, rope_dim, max_seq), positions last;
    ``positions``: (batch,) the new token's, already written. Returns
    (batch, heads, v) in ``dtype``.

    The middle part is one kernel (``ops/pallas/latent_attention``) that
    reads each row's live position tiles once."""
    nope = q_nope.shape[-1]
    w_k, w_v = kv_b[..., :nope], kv_b[..., nope:]
    qt = jnp.einsum("bhn,khn->bhk", q_nope, w_k,
                    preferred_element_type=F32).astype(dtype)
    ot = latent_attention.latent_decode_attention(
        qt, q_rope, latent, rope_key, positions, scale).astype(dtype)
    return jnp.einsum("bhk,khv->bhv", ot, w_v,
                      preferred_element_type=F32).astype(dtype)


def latent_prompt_attention(q_nope, q_rope, kv_b, c, k_rope, scale, dtype):
    """Causal attention of a whole prompt from position 0 with keys and
    values expanded from the prompt's own latents (no cache is read):
    ``[k_nope_h, v_h] = c W_kvb``, a key is ``[k_nope_h, k_rope]`` with
    the one rotary key shared by the heads. Runs through the flash kernel,
    which has one width for queries, keys and values: all three are
    padded with zeros to a whole number of lane tiles.

    ``q_nope``/``q_rope``: (batch, seq, heads, .); ``c``: (batch, seq,
    rank); ``k_rope``: (batch, seq, 1, rope_dim). Returns (batch, seq,
    heads, v) in ``dtype``."""
    batch, seq, heads, nope = q_nope.shape
    kv = jnp.einsum("bsk,khn->bshn", c, kv_b,
                    preferred_element_type=F32).astype(dtype)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1]
                                  + k_rope.shape[-1:])], axis=-1)
    width = -(-max(q.shape[-1], v.shape[-1]) // LANES) * LANES

    def fit(t):     # (batch, seq, heads, d) -> (batch, heads, seq, width)
        return jnp.pad(t.transpose(0, 2, 1, 3),
                       [(0, 0)] * 3 + [(0, width - t.shape[-1])])

    o = flash_attention(fit(q), fit(k), fit(v), causal=True, sm_scale=scale)
    return o[..., :v.shape[-1]].transpose(0, 2, 1, 3)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2's MLA): queries through a
    low-rank pair ``q_a``, ``q_b`` with a norm between; keys and values
    through one ``rank``-wide latent a token (normed) that ``kv_b``
    expands a head, plus one rotary key a token shared by the heads.
    The cache holds the latent and the rotated key and nothing a head.

    ``yarn`` is the published ``rope_scaling`` group (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale_all_dim``) or ``None`` for plain rotary positions."""

    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    yarn: Any = None
    eps: float = 1e-6
    decode: bool = False
    max_cache_len: int = 0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        del lengths       # masked softmax: padded keys are never attended
        batch, seq, d_model = x.shape
        heads, rank, nope, turned = (self.num_heads, self.kv_rank,
                                     self.nope_dim, self.rope_dim)
        yarn = dict(self.yarn) if self.yarn else None
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        norm = partial(RMSNorm, eps=self.eps, dtype=self.dtype,
                       param_dtype=self.param_dtype)
        q = dense(heads * (nope + turned), name="q_b")(
            norm(name="q_norm")(dense(self.q_rank, name="q_a")(x)))
        q = q.reshape(batch, seq, heads, nope + turned)
        kv = dense(rank + turned, name="kv_a")(x)
        c = norm(name="kv_norm")(kv[..., :rank])
        kv_b = self.param("kv_b", nn.initializers.normal(0.02),
                          (rank, heads, nope + self.v_dim),
                          self.param_dtype).astype(self.dtype)
        freq = yarn and yarn_frequencies(
            turned, self.rope_theta, yarn["factor"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"])
        at = positions[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]
        q_nope = q[..., :nope]
        q_rope = rope(q[..., nope:], at, self.rope_theta,
                      freq).astype(self.dtype)
        k_rope = rope(kv[..., None, rank:], at, self.rope_theta,
                      freq).astype(self.dtype)            # (b, s, 1, turned)
        scale = latent_scale(nope + turned, yarn)

        if self.decode:
            latent = self.variable(
                "cache", "latent", jnp.zeros,
                (batch, rank, self.max_cache_len), self.dtype)
            rope_key = self.variable(
                "cache", "rope_key", jnp.zeros,
                (batch, turned, self.max_cache_len), self.dtype)
            latent.value = write_cache_rows(
                latent.value[:, None], c[:, :, None], positions)[:, 0]
            rope_key.value = write_cache_rows(
                rope_key.value[:, None], k_rope, positions)[:, 0]
        if self.decode and seq == 1:
            with jax.named_scope("latent_step"):
                o = latent_step_attention(
                    q_nope[:, 0], q_rope[:, 0], kv_b, latent.value,
                    rope_key.value, positions, scale, self.dtype)[:, None]
        else:
            with jax.named_scope("latent_prompt"):
                o = latent_prompt_attention(q_nope, q_rope, kv_b, c, k_rope,
                                            scale, self.dtype)
        return dense(d_model, name="out")(
            o.reshape(batch, seq, heads * self.v_dim))


# ------------------------------------------------- grouped-query attention

def ring_len(window):
    """Positions a window layer's ring holds: the smallest whole number
    of lane tiles that holds the window."""
    return -(-window // LANES) * LANES


def ring_positions(last, ring):
    """The position each column of a ring holds once position ``last``
    is written: column ``j`` holds the newest ``p <= last`` with ``p mod
    ring == j`` (negative: nothing yet). ``last``: (batch,); returns
    (batch, ring) int32."""
    column = jnp.arange(ring, dtype=jnp.int32)
    return last[:, None] - (last[:, None] - column) % ring


def window_prompt_attention(q, k, v, window, scale, dtype):
    """Causal attention of a whole prompt from position 0 in which query
    ``t`` sees keys ``max(0, t - window + 1) .. t``, as banded blocks:
    the queries in blocks of ``window``, each block against its own keys
    and the block's before it, so the work is ``seq x 2 window`` whatever
    the length (masked full attention does ``seq^2 / 2``). ``q``: (batch,
    seq, heads, d); ``k``/``v``: (batch, seq, kv_heads, d). Returns
    (batch, seq, heads, d) in ``dtype``."""
    batch, seq, heads, d = q.shape
    groups = k.shape[2]
    per = heads // groups
    block = window
    turn = block * (BAND_BLOCKS if seq > block * BAND_BLOCKS else 1)
    pad = -seq % turn
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    n = (seq + pad) // block

    def banded(t):      # each block's keys after the block's before it
        t = t.reshape(batch, n, block, groups, d)
        before = jnp.pad(t[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)
        return jnp.concatenate([before, t], axis=2)

    at = jnp.arange(block, dtype=jnp.int32)
    apart = at[:, None] + block - jnp.arange(2 * block, dtype=jnp.int32)
    near = (apart >= 0) & (apart < window)                  # (t, s)
    own = jnp.arange(2 * block) >= block     # the block's own keys

    def one(xs):        # BAND_BLOCKS blocks (or all of a short prompt)
        q_b, k_b, v_b, number = xs
        # block 0's "block before" is padding, not keys
        seen = near & (own | (number > 0)[:, None, None])   # (n, t, s)
        s = jnp.einsum("bntgrd,bnsgd->bngrts", q_b, k_b,
                       preferred_element_type=F32) * scale
        s = jnp.where(seen[None, :, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("bngrts,bnsgd->bntgrd", p, v_b)

    turns = n * block // turn
    each = n // turns
    cut = lambda t: jnp.moveaxis(
        t.reshape((batch, turns, each) + t.shape[2:]), 1, 0)
    out = jax.lax.map(one, (
        cut(q.reshape(batch, n, block, groups, per, d)), cut(banded(k)),
        cut(banded(v)), jnp.arange(n, dtype=jnp.int32).reshape(turns, each)))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq + pad, heads, d)
    return out[:, :seq]


def full_prompt_attention(q, k, v, scale, block_len=1):
    """Causal attention of a whole prompt from position 0 through the
    flash kernel, which has one key/value head a query head: each group's
    keys and values are repeated to its queries. ``q``: (batch, seq,
    heads, d); ``k``/``v``: (batch, seq, kv_heads, d). ``block_len`` > 1:
    causal over blocks of that many positions and open inside one.
    Returns (batch, seq, heads, d)."""
    per = q.shape[2] // k.shape[2]
    heads_first = lambda t: t.transpose(0, 2, 1, 3)
    wide = lambda t: jnp.repeat(heads_first(t), per, axis=1)
    o = flash_attention(heads_first(q), wide(k), wide(v), causal=True,
                        sm_scale=scale, block_len=block_len)
    return o.transpose(0, 2, 1, 3)


def ring_step_attention(q, keys, values, positions, window, scale, dtype):
    """One decode step of window attention against the ring, the new
    token's columns already in it: column ``j`` holds position
    :func:`ring_positions` and is seen where that is a position of this
    request (not negative: an earlier occupant's column is never read)
    inside the window. ``q``: (batch, heads, d); ``keys``/``values``:
    (batch, kv_heads, d, ring); ``positions``: (batch,). The whole ring
    is one lane tile a head at a window of 128: XLA's masked products
    read what a kernel would."""
    batch, heads, d = q.shape
    groups, ring = keys.shape[1], keys.shape[-1]
    held = ring_positions(positions, ring)
    seen = (held >= 0) & (positions[:, None] - held < window)
    s = jnp.einsum("bgrd,bgdj->bgrj",
                   q.reshape(batch, groups, heads // groups, d), keys,
                   preferred_element_type=F32) * scale
    s = jnp.where(seen[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("bgrj,bgdj->bgrd", p, values).reshape(batch, heads, d)


class GroupedQueryAttention(nn.Module):
    """Grouped-query softmax attention with per-head QK-norm (none
    where ``qk_norm`` is false) and no gate: over every causal key
    (``window`` ``None``; no positional encoding unless ``rotary``) or
    over the last ``window`` keys, the query's own among them (rotary
    positions over the whole head width where ``rotary``). The softmax
    scale is ``head_dim^-0.5``, or ``scale`` where the model fixes one.

    With ``decode=True`` a full layer keeps ``cached_key`` /
    ``cached_value`` ``(batch, kv_heads, head_dim, max_cache_len)`` and a
    decode step reads the live tiles of its rows
    (``ops/pallas/grouped_decode_attention``); a window layer keeps
    ``ring_key`` / ``ring_value`` ``(batch, kv_heads, head_dim, ring)``,
    position ``p`` in column ``p mod ring`` (:func:`ring_len`): a prefill
    leaves the prompt's last ``ring`` positions there (``lengths``: the
    true length) and a decode step writes over the oldest column.

    With ``block_len`` > 1 (a full layer) the mask is causal over blocks
    of that many positions and open inside one: a prompt goes through the
    flash kernel's block form, and a call of ``block_len`` tokens a row is
    one pass over a block that starts at ``positions``. It writes the
    block's keys and values over the block's columns of the cache and
    every query attends positions ``0 .. positions + block_len - 1``
    (``grouped_decode_attention.grouped_block_attention``). The columns
    are provisional: the next pass over the same block writes them again,
    and what the block's last pass wrote is what later blocks read."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int] = None
    rotary: bool = False
    block_len: int = 1
    rope_theta: float = 10000.0
    qk_norm: bool = True
    scale: Optional[float] = None
    eps: float = 1e-6
    decode: bool = False
    max_cache_len: int = 0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        batch, seq, d_model = x.shape
        heads, groups, d = self.num_heads, self.num_kv_heads, self.head_dim
        window = self.window
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        norm = partial(RMSNorm, eps=self.eps, dtype=self.dtype,
                       param_dtype=self.param_dtype)
        q = dense(heads * d, name="query")(x).reshape(batch, seq, heads, d)
        k = dense(groups * d, name="key")(x).reshape(batch, seq, groups, d)
        v = dense(groups * d, name="value")(x).reshape(batch, seq, groups, d)
        if self.qk_norm:
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        if self.rotary:
            at = positions[:, None] \
                + jnp.arange(seq, dtype=jnp.int32)[None, :]
            q = rope(q, at, self.rope_theta).astype(self.dtype)
            k = rope(k, at, self.rope_theta).astype(self.dtype)
        scale = d ** -0.5 if self.scale is None else self.scale
        if self.decode:
            keys, values = (self.variable(
                "cache", name, jnp.zeros,
                (batch, groups, d,
                 ring_len(window) if window else self.max_cache_len),
                self.dtype) for name in (
                    ("ring_key", "ring_value") if window
                    else ("cached_key", "cached_value")))
        if self.block_len > 1 and window:
            raise ValueError("a window layer has no block form")
        with jax.named_scope("window_attention" if window
                             else "full_attention"):
            if self.decode and seq == self.block_len > 1:
                keys.value = write_block(keys.value, k, positions)
                values.value = write_block(values.value, v, positions)
                o = grouped_decode_attention.grouped_block_attention(
                    q.reshape(batch, seq, groups, heads // groups, d),
                    keys.value, values.value, positions, scale)
            elif self.decode and seq == 1:
                column = positions % keys.value.shape[-1] if window \
                    else positions
                keys.value = write_token(keys.value, k[:, 0], column)
                values.value = write_token(values.value, v[:, 0], column)
                if window:
                    o = ring_step_attention(
                        q[:, 0], keys.value, values.value, positions, window,
                        scale, self.dtype)
                else:
                    o = grouped_decode_attention.grouped_decode_attention(
                        q[:, 0].reshape(batch, groups, heads // groups, d),
                        keys.value, values.value, positions, scale)
            elif window:
                o = window_prompt_attention(q, k, v, window, scale,
                                            self.dtype)
                if self.decode:
                    # column j takes the newest position of the prompt
                    # that falls on it; a column no position has reached
                    # keeps zeros, and the step's mask never reads it
                    last = (jnp.full((batch,), seq, jnp.int32)
                            if lengths is None else lengths) - 1
                    held = ring_positions(last, keys.value.shape[-1])
                    pick = lambda t: jnp.where(
                        (held >= 0)[:, None, None], jnp.take_along_axis(
                            t.transpose(0, 2, 3, 1),
                            jnp.clip(held, 0, seq - 1)[:, None, None],
                            axis=-1), 0).astype(self.dtype)
                    keys.value, values.value = pick(k), pick(v)
            else:
                o = full_prompt_attention(q, k, v, scale, self.block_len)
                if self.decode:
                    keys.value = write_cache_rows(keys.value, k, positions)
                    values.value = write_cache_rows(values.value, v,
                                                    positions)
        return dense(d_model, name="out")(o.reshape(batch, seq, heads * d))


# -------------------------------------------------------------- state space

def ssm_conv(rows, kernel, bias):
    """The causal depthwise convolution before the recurrence, then SiLU:
    ``silu(sum_j kernel[j] * rows[j] + bias)``, ``rows[j]`` the input
    ``k - 1 - j`` positions back (the last one the position's own).
    ``rows``: ``k`` arrays (..., channels); ``kernel``: (k, channels);
    float32 out."""
    return nn.silu(sum(kernel[j].astype(F32) * row.astype(F32)
                       for j, row in enumerate(rows)) + bias.astype(F32))


def ssm_step(state, x, dt, a, b, c, d_skip):
    """One token of the Mamba-2 recurrence: ``S = exp(dt A) S + dt x
    B^T``, ``y = S C + D x``, the state read once and written once.
    ``state``: (batch, heads, p, n) float32; ``x``: (batch, heads, p);
    ``dt``: (batch, heads) float32, after its softplus; ``a``/``d_skip``:
    (heads,) float32, ``a`` negative; ``b``/``c``: (batch, groups, n),
    head ``h`` reading pair ``h // (heads / groups)``. Everything is
    float32: the sums are elementwise (a product would read the new state
    a second time). Returns the state and ``y`` (batch, heads, p)."""
    batch, heads, p, n = state.shape
    groups = b.shape[1]
    x, b, c = (t.astype(F32) for t in (x, b, c))
    wide = lambda t: t[:, :, None, None, :]                # (b, g, 1, 1, n)
    s = state.reshape(batch, groups, heads // groups, p, n)
    keep = jnp.exp(dt * a).reshape(batch, groups, -1, 1, 1)
    put = (dt[..., None] * x).reshape(batch, groups, -1, p, 1)
    s = keep * s + put * wide(b)
    y = jnp.sum(s * wide(c), axis=-1).reshape(batch, heads, p)
    return s.reshape(state.shape), y + d_skip[:, None] * x


def ssm_chunked(x, dt, a, b, c, d_skip, chunk=256, dtype=jnp.bfloat16):
    """The same recurrence over a whole sequence from a zero state, by
    chunks (the Mamba-2 paper's SSD form, arXiv:2405.21060): inside a
    chunk the masked products ``(C_i . B_j) exp(s_i - s_j) dt_j`` with
    ``s`` the running sum of ``dt A``, one ``C B^T`` a group for all its
    heads; between chunks the state. ``x``: (batch, seq, heads, p);
    ``dt``: (batch, seq, heads) float32, after its softplus, **0 at a
    position that is padding**: such a position neither decays the state
    nor enters it, so the state returned is the state after the true
    tokens; ``b``/``c``: (batch, seq, groups, n).

    Matrix operands are ``dtype`` (the state of the between-chunk product
    among them) and every sum, decay and the carried state float32; every
    exponent is at most 0. Returns the outputs (batch, seq, heads, p) in
    ``dtype`` and the state (batch, heads, p, n) float32."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    chunk = min(chunk, seq)
    pad = -seq % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    count = (seq + pad) // chunk
    cut = lambda t, inner: jnp.moveaxis(
        t.reshape((batch, count, chunk) + inner), 1, 0)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    a = a.reshape(groups, per)
    d_skip = d_skip.reshape(groups, per)

    def one(state, xs):
        x_c, dt_c, b_c, c_c = xs     # (b, j, g, r, p) (b, j, g, r) (b, j, g, n)
        since = jnp.cumsum(dt_c * a, axis=1)               # s: (b, i, g, r)
        whole = since[:, -1]                               # (b, g, r)
        heads_first = lambda t: jnp.moveaxis(t, 1, -1)     # (b, g, r, i)
        s_i = heads_first(since)
        # inside the chunk
        cb = jnp.einsum("bign,bjgn->bgij", c_c, b_c,
                        preferred_element_type=F32)
        apart = s_i[..., :, None] - s_i[..., None, :]      # (b, g, r, i, j)
        weight = jnp.where(causal, jnp.exp(jnp.minimum(apart, 0.0)), 0.0) \
            * heads_first(dt_c)[..., None, :]
        y = jnp.einsum("bgrij,bjgrp->bigrp",
                       (cb[:, :, None] * weight).astype(dtype), x_c,
                       preferred_element_type=F32)
        # what came before the chunk
        y = y + jnp.exp(since)[..., None] * jnp.einsum(
            "bign,bgrpn->bigrp", c_c, state.astype(dtype),
            preferred_element_type=F32)
        y = y + d_skip[..., None] * x_c.astype(F32)
        # the state after the chunk
        left = jnp.exp(whole[:, None] - since) * dt_c      # (b, j, g, r)
        state = jnp.exp(whole)[..., None, None] * state + jnp.einsum(
            "bjgrp,bjgn->bgrpn",
            (x_c.astype(F32) * left[..., None]).astype(dtype), b_c,
            preferred_element_type=F32)
        return state, y.astype(dtype)

    state, out = jax.lax.scan(
        one, jnp.zeros((batch, groups, per, p, n), F32),
        (cut(x, (groups, per, p)), cut(dt, (groups, per)),
         cut(b, (groups, n)), cut(c, (groups, n))))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, count * chunk, heads, p)
    return out[:, :seq], state.reshape(batch, heads, p, n)


class StateSpace(nn.Module):
    """A Mamba-2 mixer (arXiv:2405.21060, as Hugging Face's ``bamba`` /
    ``granitemoehybrid`` code has it): ``[z | xBC | dt] = x W_in``; a
    causal depthwise convolution of ``d_conv`` taps and SiLU over ``xBC``;
    ``x`` (heads x head_dim), ``B`` and ``C`` (``d_state`` each a group,
    one pair for all the heads of a group) split from it; ``dt =
    softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head; the
    recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
    S_t C_t + D x_t``; ``out = RMSNorm(y * silu(z)) W_out``, the norm
    over all heads at once with a learned scale. No positions at all.

    A prompt is scanned by chunks (:func:`ssm_chunked`) and takes the
    true ``lengths``; a decode step is :func:`ssm_step`. With
    ``decode=True`` the ``cache`` collection holds ``ssm_state`` (batch,
    heads, head_dim, d_state) float32 and ``conv_state`` (batch,
    (d_conv - 1) x channels): the last ``d_conv - 1`` rows of ``xBC``
    before the convolution, oldest first, side by side along the lanes
    (zeros where the prompt had fewer tokens). ``dt``, the decays, the
    state and the gated norm are float32, matrix operands ``dtype``."""

    num_heads: int
    head_dim: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    eps: float = 1e-6
    decode: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        del positions          # a recurrence: the order is the position
        batch, seq, d_model = x.shape
        heads, p, n, groups = (self.num_heads, self.head_dim, self.d_state,
                               self.n_groups)
        inner, taps = heads * p, self.d_conv
        channels = inner + 2 * groups * n
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        per_head = lambda name, init: self.param(
            name, init, (heads,), self.param_dtype).astype(F32)
        with jax.named_scope("ssm"):
            proj = dense(inner + channels + heads, name="in_proj")(x)
            z, xbc = proj[..., :inner], proj[..., inner:inner + channels]
            kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                                (taps, channels), self.param_dtype)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (channels,), self.param_dtype)
            dt = jax.nn.softplus(
                proj[..., inner + channels:].astype(F32)
                + per_head("dt_bias", nn.initializers.zeros))
            a = -jnp.exp(per_head("A_log", nn.initializers.zeros))
            d_skip = per_head("D", nn.initializers.ones)
            split = lambda t: (
                t[..., :inner].reshape(t.shape[:-1] + (heads, p)),
                t[..., inner:inner + groups * n].reshape(
                    t.shape[:-1] + (groups, n)),
                t[..., inner + groups * n:].reshape(
                    t.shape[:-1] + (groups, n)))
            if self.decode:
                state = self.variable("cache", "ssm_state", jnp.zeros,
                                      (batch, heads, p, n), F32)
                tail = self.variable("cache", "conv_state", jnp.zeros,
                                     (batch, (taps - 1) * channels),
                                     self.dtype)
            if self.decode and seq == 1:
                with jax.named_scope("ssm_step"):
                    rows = [tail.value[:, j * channels:(j + 1) * channels]
                            for j in range(taps - 1)] + [xbc[:, 0]]
                    tail.value = jnp.concatenate(rows[1:], axis=-1)
                    xs, b, c = split(ssm_conv(rows, kernel, bias).astype(
                        self.dtype))
                    state.value, y = ssm_step(state.value, xs, dt[:, 0], a,
                                              b, c, d_skip)
                y = y[:, None]
            else:
                true = jnp.full((batch,), seq, jnp.int32) \
                    if lengths is None else lengths
                at = jnp.arange(seq, dtype=jnp.int32)
                dt = jnp.where((at[None, :] < true[:, None])[..., None],
                               dt, 0.0)
                # zeros before the sequence
                padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
                xs, b, c = split(ssm_conv(
                    [padded[:, j:j + seq] for j in range(taps)], kernel,
                    bias).astype(self.dtype))
                with jax.named_scope("ssm_scan"):
                    y, last = ssm_chunked(xs, dt, a, b, c, d_skip,
                                          self.chunk, self.dtype)
                if self.decode:
                    state.value = last
                    # the last true rows, zeros before the prompt's start
                    rows = true[:, None] - (taps - 1) \
                        + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
                    kept = jnp.take_along_axis(
                        xbc, jnp.clip(rows, 0, seq - 1)[..., None], axis=1)
                    tail.value = jnp.where(
                        (rows >= 0)[..., None], kept, 0).reshape(
                            batch, (taps - 1) * channels).astype(self.dtype)
            gated = y.reshape(batch, seq, inner).astype(F32) \
                * nn.silu(z.astype(F32))
            gated = RMSNorm(eps=self.eps, dtype=self.dtype,
                            param_dtype=self.param_dtype, name="norm")(gated)
            return dense(d_model, name="out_proj")(gated)


# ----------------------------------------------------------- routed experts

# at or under this many (token, expert) pairs a held expert (of the pairs
# expected here: a layer that holds a share of the experts gets that share
# of a step's pairs), every held expert multiplies every row under a 0/1
# weight (experts_masked); above it the pairs are sorted and grouped
# (experts_grouped, experts_grouped_held). Four points are measured on
# the chip, each a decode step, and the masked product won at each: 64 rows x top-4 over 64 experts, 4 pairs an expert, within
# 8-16% of the time of reading the experts (PERF.md, PR 34); 32 rows x
# top-8 over 8 of 128 experts, 2 pairs an expert of the 16 expected
# here, at the time of reading them (0.84 ms a layer for 681 MB; PERF.md,
# PR 39); 64 rows x top-10 over 36 of 72 experts of width 768, 8.9 pairs
# an expert, 21.4 ms a step masked against 28.7 grouped (ten layers,
# 6.8 GB of experts; PERF.md, PR 41): sorting, gathering and scattering
# 640 rows cost more than multiplying 64 rows by every held expert; 256
# rows (64 rows' blocks of 4) x top-8 over all 128 experts of width 768,
# 16 pairs an expert, 15.8 ms a pass masked against 32.0 grouped (six
# layers, 7.25 GB of experts; the three grouped products 1.49 ms each a
# layer against 1.2 ms of reading them, and the masked product within
# 17% of that read: PERF.md, PR 46). The masked product's arithmetic
# grows with rows x held experts (309 GFLOP a layer at 256 x 128: 1.6
# ms at the chip's peak, about the time of the read) and passes it
# beyond; nothing above 256 rows has been measured masked, and 16 keeps
# every accepted cell's prompt buckets (17.8 pairs an expert at the
# least) grouped. Every grouped time above is of the way back as it was
# until PR 47: one gather of the tokens' rows and one pass over the whole
# sum for each of a token's top_k pairs, and a scatter for the inverse of
# the sort. Since PR 47 the products are added to their tokens' sums in
# one pass in the order they lie in (ops/pallas/expert_combine.py) and
# nobody needs the inverse, so the grouped side of each comparison costs
# less than it read here; the crossover is to be measured again (ROADMAP
# S17 (b)), and 16 stays until then: changing it moves every decode
# program.
MASKED_PAIRS = 16
# tokens of a prompt that a layer holding a share of the experts groups at
# once (experts_grouped_held)
HELD_TOKENS = 4096

def route(x, router, bias, top_k, scaling, scoring=SIGMOID_ROUTER):
    """A top-k router. ``scoring="sigmoid"`` is DeepSeek-V3's ``noaux_tc``
    without group limits: scores ``g = sigmoid(x W_r)``, the ``top_k``
    largest of ``g + bias`` chosen, their weights ``scaling g_i / (sum of
    the chosen g + 1e-20)``. ``scoring="softmax"`` chooses the ``top_k``
    largest logits ``g = x W_r`` themselves (``+ bias``, if there is one)
    and weighs them ``scaling softmax(the chosen g)``. The product and the
    scores are float32 at the highest precision: the last score chosen
    and the first left out can lie closer than bfloat16 resolves.

    ``x``: (..., d); ``router``: (d, experts); ``bias``: (experts,) or
    ``None``. Returns the chosen experts (..., top_k) int32 and their
    weights (..., top_k) float32."""
    g = jnp.dot(x.astype(F32), router.astype(F32), precision=HIGHEST)
    if scoring == SIGMOID_ROUTER:
        g = jax.nn.sigmoid(g)
    elif scoring != SOFTMAX_ROUTER:
        raise ValueError(f"unknown router scoring {scoring!r}")
    _, chosen = jax.lax.top_k(g if bias is None else g + bias.astype(F32),
                              top_k)
    picked = jnp.take_along_axis(g, chosen, axis=-1)
    if scoring == SIGMOID_ROUTER:
        weights = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    else:
        weights = jax.nn.softmax(picked, axis=-1)
    return chosen.astype(jnp.int32), scaling * weights


def experts_masked(x, weights, gate, up, down):
    """``sum_e weights[t, e] E_e(x_t)`` as products over every held
    expert, the weight of an expert a token did not choose being 0: few
    rows against many experts (a decode step) are bound by reading the
    experts, which this does once. ``x``: (tokens, d); ``weights``:
    (tokens, experts) float32; ``gate``/``up``: (experts, d, f);
    ``down``: (experts, f, d). Returns (tokens, d) float32."""
    h = nn.silu(jnp.einsum("td,edf->tef", x, gate)) \
        * jnp.einsum("td,edf->tef", x, up)
    h = (h.astype(F32) * weights[..., None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", h, down, preferred_element_type=F32)


def sorted_pairs(chosen, weights, experts):
    """The permutation from token order to expert order: the (token,
    expert) pairs of ``chosen`` (tokens, top_k) sorted by expert, stably,
    so that an expert's pairs lie together in the order of their tokens
    and the pairs that are not here (``chosen == experts``) last. Returns
    ``token`` (pairs,), the token of the pair at each sorted place;
    ``weight`` (pairs,), that pair's router weight; and ``counts``
    (experts,), the pairs of each held expert. The one sort carries the
    pairs' numbers and weights along: nothing is gathered one element at
    a time, and nobody needs the way back from a pair to its sorted
    place (:func:`~horovod_tpu.ops.pallas.expert_combine.expert_combine`
    walks the products in sorted order)."""
    pair_expert = chosen.reshape(-1)
    _, pair, weight = jax.lax.sort(
        (pair_expert, jnp.arange(pair_expert.shape[0], dtype=jnp.int32),
         weights.reshape(-1)), num_keys=1, is_stable=True)
    counts = jnp.sum(pair_expert[:, None] == jnp.arange(experts)[None, :],
                     axis=0, dtype=jnp.int32)
    return pair // chosen.shape[1], weight, counts


def pair_rows(x, token, here):
    """``x[token]``, the rows of the sorted pairs' tokens, gathered from a
    copy of ``x`` made for the gather alone (``here``, a traced number
    that is never negative, keeps the copy from being folded away). A
    stopgap until the way in has a gather kernel of its own (ROADMAP S20
    (b)): XLA holds a buffer in VMEM only while no Mosaic kernel runs,
    and ``x`` lives on past :func:`expert_combine` (the shared MLP and a
    later turn read it), so gathered from ``x`` itself the rows come
    from HBM: 2.50 ms for 40,960 rows of 4,096 where from VMEM they take
    0.51, and ``granite-serve-chat-c1`` serves +4.4% over the parent
    without the copy and +7.2% with it (my chip runs, PR 47; PERF.md
    section 6). The copy dies at the gather, and
    ``tests/test_tpu_compile.py`` reads the compiled prefill for the
    gather's operand in VMEM."""
    return jnp.where(here >= 0, x, jnp.zeros_like(x))[token]


def experts_grouped(x, chosen, weights, gate, up, down):
    """The same sum as a grouped product: the (token, expert) pairs
    sorted by expert (:func:`sorted_pairs`), each expert's rows
    multiplied by its own matrices (``ops/pallas/grouped_product.py``:
    gate and up in one kernel, down in another, both walking one list
    of (row tile, expert) visits; an expert's matrices stay in VMEM
    across its row tiles, an expert with no pair is not read, the hidden
    rows are rounded to bfloat16 once), and the float32
    products added to their tokens' sums, each times its weight, in one
    pass over them in the order they lie in
    (``ops/pallas/expert_combine.py``). No pair is dropped and none is
    padded to a capacity. ``chosen``: (tokens, top_k) int32, an entry
    equal to the number of held experts meaning "not here" (another
    chip's expert, or a padded token): such pairs sort last, lie past the
    last group, cost no product and are not read on the way back.
    Returns (tokens, d) float32."""
    token, weight, sizes = sorted_pairs(chosen, weights, gate.shape[0])
    y = gated_products(x[token], gate, up, down, sizes)
    return expert_combine(y, token, weight, jnp.sum(sizes), x.shape[0])


def experts_grouped_held(x, chosen, weights, gate, up, down, share):
    """:func:`experts_grouped` for a layer that holds a ``share`` (under
    1) of the experts, where most pairs are not here: only the pairs that
    are here are gathered and multiplied. The sorted pairs are taken
    ``room`` at a time - twice the pairs an even router would send here,
    in whole lane tiles - as often as the pairs here need: one turn,
    and after it a loop of more where the router is very uneven. No pair
    is dropped;
    the rows gathered, the hidden rows and the float32 products are
    ``room`` long and not ``tokens x top_k``. A turn's products are
    added to their tokens' sums as :func:`experts_grouped`'s are, those
    of them that belong to a pair that is here, and a second turn adds
    to the first's sums in place. A long prompt goes ``HELD_TOKENS``
    tokens at a time, so that none of that grows with its length (each
    turn reads the held experts again: a millisecond), and a chunk of it
    with no pair here (the padding at the end of its bucket) runs
    nothing. Returns (tokens, d) float32."""
    tokens, top_k = chosen.shape
    if tokens > HELD_TOKENS and tokens % HELD_TOKENS == 0:
        cut = lambda t: t.reshape((-1, HELD_TOKENS) + t.shape[1:])

        def chunk(xs):
            # a chunk with no pair here (all padding: the tail of a long
            # prompt's bucket) runs nothing, as on the loop of old
            return jax.lax.cond(
                jnp.any(xs[1] < gate.shape[0]),
                lambda: experts_grouped_held(*xs, gate, up, down, share),
                lambda: jnp.zeros(xs[0].shape, F32))
        return jax.lax.map(
            chunk, (cut(x), cut(chosen), cut(weights))).reshape(tokens, -1)
    pairs = tokens * top_k
    room = min(pairs, -(-math.ceil(2 * pairs * share) // LANES) * LANES)
    token, weight, counts = sorted_pairs(chosen, weights, gate.shape[0])
    token, weight = (jnp.pad(a, (0, -pairs % room)) for a in (token, weight))
    ends = jnp.cumsum(counts)
    here = ends[-1]

    def turn(start, out):
        taken = jax.lax.dynamic_slice(token, (start,), (room,))
        # what each expert's run of sorted places has inside this turn
        sizes = jnp.clip(ends - start, 0, room) \
            - jnp.clip(ends - counts - start, 0, room)
        y = gated_products(pair_rows(x, taken, here), gate, up, down,
                           sizes)
        return expert_combine(
            y, taken, jax.lax.dynamic_slice(weight, (start,), (room,)),
            here - start, tokens, out)

    # the first turn has no sum to add to, and where ``room`` holds every
    # pair there is no other
    first = turn(0, None)
    if room == pairs:
        return first
    return jax.lax.fori_loop(1, -(-here // room),
                             lambda i, out: turn(i * room, out), first)


class RoutedExperts(nn.Module):
    """A router over ``num_experts`` SiLU-gated MLPs of width ``d_ff``,
    ``top_k`` of them a token (:func:`route`, ``scoring`` its form; the
    softmax form has no correction bias), beside ``shared`` experts that
    every token takes (one :class:`GatedMlp` of ``shared * d_ff``, or of
    ``shared_d_ff`` where the shared MLP has a width of its own). No
    token is dropped and there is no capacity factor.

    The layer holds experts ``first .. first + count - 1`` (``count``
    ``None``: all of them): it routes over all ``num_experts`` router
    outputs and computes the part of the sum its own experts give, which
    is the whole sum where it holds them all. Across chips the parts are
    added; here nothing stands in for that exchange.

    With ``decode=True`` the ``cache`` collection holds ``expert_counts``
    (3, count) uint32, a running count and no slot's row: ``[0]`` the
    (token, expert) pairs routed to each held expert by both programs,
    ``[1]`` the decode steps in which some counted row chose it, ``[2]``
    the decode steps (a call of ``block_len`` tokens a row, every one of
    which is routed and counted). A prompt's padding (``lengths``) and a
    decode step's rows outside ``active`` are not counted. The counts are never
    reset and run modulo 2**32 (weeks of serving at a thousand pairs a
    second an expert): a reader takes the difference of two readings in
    uint32, which is right across a wrap."""

    num_experts: int
    top_k: int
    d_ff: int
    shared: int = 1
    shared_d_ff: Optional[int] = None
    scaling: float = 1.0
    scoring: str = SIGMOID_ROUTER
    first: int = 0
    count: Optional[int] = None
    decode: bool = False
    block_len: int = 1     # tokens a row of a decode step
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x, lengths=None, active=None):
        batch, seq, d = x.shape
        count = self.num_experts if self.count is None else self.count
        init = nn.initializers.normal(0.02)
        # logits of a standard deviation near 1 whatever the width
        router = self.param("router", nn.initializers.normal(d ** -0.5),
                            (d, self.num_experts), self.param_dtype)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (self.num_experts,), self.param_dtype) \
            if self.scoring == SIGMOID_ROUTER else None
        gate, up = (self.param(name, init, (count, d, self.d_ff),
                               self.param_dtype).astype(self.dtype)
                    for name in ("experts_gate", "experts_up"))
        down = self.param("experts_down", init, (count, self.d_ff, d),
                          self.param_dtype).astype(self.dtype)
        # the sigmoid form is called with the five arguments it always
        # had: the benchmark's own tests swap ``route`` for a function of
        # those five (benchmark/tests/test_serve_xing.py)
        chosen, weights = route(
            x, router, bias, self.top_k, self.scaling,
            *(() if self.scoring == SIGMOID_ROUTER else (self.scoring,)))
        counted = jnp.ones((batch, seq), bool)
        if lengths is not None:
            counted = jnp.arange(seq, dtype=jnp.int32)[None, :] \
                < lengths[:, None]
        if active is not None:
            counted = counted & active[:, None]
        here = chosen - self.first
        here = jnp.where((here >= 0) & (here < count) & counted[..., None],
                         here, count)                 # count: not here
        hit = here[..., None] == jnp.arange(count, dtype=jnp.int32)
        if self.decode:
            counts = self.variable("cache", "expert_counts", jnp.zeros,
                                   (3, count), jnp.uint32)
            pairs = jnp.sum(hit, axis=(0, 1, 2), dtype=jnp.uint32)
            step = jnp.full((count,), int(seq == self.block_len),
                            jnp.uint32)
            counts.value = counts.value + jnp.stack(
                [pairs, jnp.minimum(pairs, 1) * step, step])
        flat = x.reshape(batch * seq, d)
        # the pairs expected here are ``count / num_experts`` of the
        # step's: MASKED_PAIRS an expert of them, over all the experts
        if batch * seq * self.top_k <= MASKED_PAIRS * self.num_experts:
            y = experts_masked(
                flat, jnp.sum(hit * weights[..., None], axis=2).reshape(
                    batch * seq, count), gate, up, down)
        elif count == self.num_experts:
            y = experts_grouped(flat, here.reshape(-1, self.top_k),
                                weights.reshape(-1, self.top_k), gate, up,
                                down)
        else:
            y = experts_grouped_held(
                flat, here.reshape(-1, self.top_k),
                weights.reshape(-1, self.top_k), gate, up, down,
                count / self.num_experts)
        y = y.reshape(batch, seq, d)
        if self.shared:
            y = y + GatedMlp(self.shared_d_ff or self.shared * self.d_ff,
                             dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="shared")(x).astype(F32)
        return y.astype(self.dtype)


# -------------------------------------------------------- hyper-connections

def sinkhorn(z, iters, eps):
    """``exp(z)`` made (nearly) doubly stochastic: ``iters`` times each
    row divided by (its sum + ``eps``), then each column. ``z``: (n, n,
    ...), rows first; float32. The passes are unrolled: they are a chain
    of small elementwise operations that fuse, where a loop would launch
    each pass on its own."""
    m = jnp.exp(z)

    def one(_, m):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        return m / (m.sum(axis=0, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, one, m, unroll=True)


class HyperConnection(nn.Module):
    """The manifold-constrained hyper-connection round one sublayer
    (mHC, arXiv:2512.24880, on hyper-connections, arXiv:2409.19606):
    from the ``n`` residual streams ``X`` of a token (``n x C``) three
    maps, each a static part plus ``alpha`` times a projection ``phi`` of
    the normalised streams: ``H_pre`` (n, a sigmoid) mixes the streams
    into the sublayer's input, ``H_post`` (n, twice a sigmoid) spreads
    its output over them, ``H_res`` (n x n, Sinkhorn of the clamped
    exponent) mixes the streams themselves. All of it float32; ``X`` is
    kept in ``dtype``, the stream before the position: (batch, n, seq,
    C), so that each stream is an activation of the usual shape and
    nothing is laid out anew to mix them.

    The module gives the three maps for ``X``; :func:`hyper_mix` and
    :func:`hyper_update` apply them."""

    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, streams):
        """``streams``: (batch, n, seq, C). Returns ``H_pre`` (n, batch,
        seq), ``H_post`` (n, batch, seq) and ``H_res`` (n, n, batch,
        seq). The projection is a sum of one product a stream, and the
        normalisation (one number a token) is applied to its 24 results,
        not to the streams; the maps are made with the tokens as the
        last axis (all of them: a decode step has one a row), so that the
        Sinkhorn passes run along the lanes."""
        batch, n, seq, width = streams.shape
        phi = self.param("phi", nn.initializers.normal(1.0),
                         (n * width, n * n + 2 * n), self.param_dtype)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           self.param_dtype).astype(F32)
        bias = self.param("bias", nn.initializers.zeros, (n * n + 2 * n,),
                          self.param_dtype).astype(F32)
        x = streams.astype(F32)
        phi = phi.astype(F32).reshape(n, width, -1)
        m = sum(jnp.einsum("bsc,ck->bsk", x[:, i], phi[i], precision=HIGHEST)
                for i in range(n))
        m = m * jax.lax.rsqrt(jnp.mean(x * x, axis=(1, 3))
                              + self.norm_eps)[..., None]
        m = m.reshape(batch * seq, -1).T                   # (n^2 + 2n, T)
        at = lambda lo, hi: bias[lo:hi, None]
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + at(0, n))
        post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + at(n, 2 * n))
        z = jnp.clip(alpha[2] * m[2 * n:] + at(2 * n, None), *self.clamp)
        res = sinkhorn(z.reshape(n, n, batch * seq), self.sinkhorn_iters,
                       self.eps)
        return (pre.reshape(n, batch, seq), post.reshape(n, batch, seq),
                res.reshape(n, n, batch, seq))


def hyper_mix(pre, streams):
    """``H_pre @ X``: (batch, seq, C) float32."""
    x = streams.astype(F32)
    return sum(pre[i][..., None] * x[:, i] for i in range(x.shape[1]))


def hyper_update(post, res, streams, y):
    """``X' = H_res @ X + H_post^T y`` in float32, kept in ``X``'s dtype."""
    x, y = streams.astype(F32), y.astype(F32)
    n = x.shape[1]
    out = [sum(res[i, j][..., None] * x[:, j] for j in range(n))
           + post[i][..., None] * y for i in range(n)]
    return jnp.stack(out, axis=1).astype(streams.dtype)


# ---------------------------------------------------------- the layer kinds

# One kind of layer, declared once: every decision about a kind is a column
# here, and nothing outside this table and the kind's own module compares a
# kind with a constant. ``module``: its flax class; ``fields(d, i)``: that
# module's fields for layer ``i`` of the :class:`HybridDecoder` ``d``;
# ``cache``: the ``cache`` variables the module declares under
# ``decode=True``, each with its kind of leaf (``ServingContract.
# cache_kinds``); ``resumes``: a prefill that is handed the slot's cache
# continues from it; ``reads``: (kind of leaf, ``(d, seen) -> positions``)
# one layer's decode step attends of that leaf, ``seen`` the positions each
# row has once the step's own are written (its position + the step's
# tokens); ``dense_len(d)``: prompts longer than this select key blocks;
# ``blocks``: the module has a form for a step of ``block_len`` > 1 tokens
# a row (:attr:`HybridDecoder.block_len`).
Mixer = collections.namedtuple(
    "Mixer", "module fields cache resumes reads dense_len blocks",
    defaults=(False, None, None, False))


def _grouped_query(kind, cache, reads):
    return Mixer(GroupedQueryAttention, lambda d, i: dict(
        num_heads=d.num_heads, num_kv_heads=d.num_kv_heads,
        head_dim=d.head_dim, window=d.window if kind == WINDOW else None,
        rotary=kind in d.rotary, rope_theta=d.rope_theta,
        qk_norm=d.qk_norm, scale=d.attention_scale,
        max_cache_len=d.max_seq, decode=d.decode, block_len=d.block_len),
        cache, reads=reads, blocks=kind == FULL)


# a plain mapping, no registration API: a new kind is one module above and
# one entry here (tests/test_engine_contract.py serves one of its own)
MIXERS = {
    LIGHTNING: Mixer(LightningAttention, lambda d, i: dict(
        num_heads=d.num_heads, head_dim=d.head_dim,
        layer_index=d.layer_indices[i] if d.layer_indices else i,
        published_depth=d.published_depth or len(d.mixers),
        rope_theta=d.rope_theta, decode=d.decode), {"state": "state"}),
    BLOCK_SPARSE: Mixer(BlockSparseAttention, lambda d, i: dict(
        num_heads=d.num_heads, num_kv_heads=d.num_kv_heads,
        head_dim=d.head_dim, sparse=d.sparse, max_cache_len=d.max_seq,
        decode=d.decode),
        {"cached_key": "kv", "cached_value": "kv",
         "compressed_key": "compressed"},
        dense_len=lambda d: dict(d.sparse)["dense_len"]),
    # carries its state and its normaliser from piece to piece; the other
    # kinds start every prefill from an empty cache
    POWER_RETENTION: Mixer(PowerRetention, lambda d, i: dict(
        num_heads=d.num_heads, num_kv_heads=d.num_kv_heads,
        head_dim=d.head_dim, rope_theta=d.rope_theta, decode=d.decode),
        {"state": "state", "state_norm": "state"}, resumes=True),
    LATENT: Mixer(LatentAttention, lambda d, i: dict(
        num_heads=d.num_heads, rope_theta=d.rope_theta,
        max_cache_len=d.max_seq, decode=d.decode, **dict(d.latent)),
        {"latent": "latent", "rope_key": "latent"}),
    # every position up to the row's own
    FULL: _grouped_query(FULL, {"cached_key": "kv", "cached_value": "kv"},
                         ("kv", lambda d, seen: seen.sum())),
    # the window's at most
    WINDOW: _grouped_query(
        WINDOW, {"ring_key": "ring", "ring_value": "ring"},
        ("ring", lambda d, seen: np.minimum(seen, d.window or 0).sum())),
    MAMBA2: Mixer(StateSpace,
                  lambda d, i: dict(decode=d.decode, **dict(d.ssm)),
                  {"ssm_state": "state", "conv_state": "conv"}),
}


def mixer_of(kind) -> Mixer:
    if kind not in MIXERS:
        raise ValueError(f"unknown mixer {kind!r}")
    return MIXERS[kind]


# a layer's MLP, the same way: the ``cache`` variables it declares and
# whether its decode step wants ``active`` (:class:`RoutedExperts` counts
# the pairs it routes, and only rows that hold a request)
Mlp = collections.namedtuple("Mlp", "cache wants_active")
MLPS = {DENSE_MLP: Mlp({}, False),
        EXPERTS_MLP: Mlp({"expert_counts": "counter"}, True)}


# ------------------------------------------------------------------- trunk

class HybridLayer(nn.Module):
    """``h += a Mixer(norm(h)); h += a Mlp(norm(h))`` with the residual
    scale ``a`` (muP's ``scale_depth / sqrt(depth)``, or 1); ``kind``
    says which mixer, ``mixer_args`` are its fields; ``mlp`` says which
    MLP (``"dense"``: a :class:`GatedMlp` of ``d_ff``; ``"experts"``:
    :class:`RoutedExperts` with the fields ``mlp_args``).

    With ``norms="output"`` the norm sits on each sublayer's output and
    none on its input: ``h += a norm(Mixer(h)); h += a norm(Mlp(h))``
    (EXAONE 4.0's placement; one residual stream only).

    With ``streams`` > 1 the residual is ``streams`` streams a token
    (``h``: (batch, streams, seq, C)) and each sublayer sits inside a
    :class:`HyperConnection` (fields ``hyper_args``): ``X' = H_res X +
    H_post^T F(norm(H_pre X))``; there is no ``a`` then."""

    kind: str
    mixer_args: Any
    d_ff: int
    residual_scale: float = 1.0
    norms: str = NORM_INPUT
    mlp: str = DENSE_MLP
    mlp_args: Any = None
    streams: int = 1
    hyper_args: Any = None
    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, h, positions, lengths=None, active=None):
        common = dict(eps=self.eps, dtype=self.dtype,
                      param_dtype=self.param_dtype)
        mixer = mixer_of(self.kind).module(
            name="mixer", **dict(self.mixer_args), **common)
        norm = partial(RMSNorm, **common)
        if self.mlp == EXPERTS_MLP:
            scope = "moe"
            mlp = lambda u: RoutedExperts(
                dtype=self.dtype, param_dtype=self.param_dtype, name="moe",
                **dict(self.mlp_args))(u, lengths, active)
        else:
            scope = "mlp"
            mlp = lambda u: GatedMlp(
                self.d_ff, dtype=self.dtype, param_dtype=self.param_dtype,
                name="mlp")(u)
        if self.norms == NORM_OUTPUT:
            if self.streams != 1:
                raise ValueError("norms on the sublayers' outputs go with "
                                 "one residual stream")
            a = jnp.asarray(self.residual_scale, self.dtype)
            h = h + a * norm(name="mixer_norm")(mixer(h, positions, lengths))
            with jax.named_scope(scope):
                return h + a * norm(name="mlp_norm")(mlp(h))
        if self.streams == 1:
            a = jnp.asarray(self.residual_scale, self.dtype)
            h = h + a * mixer(norm(name="input_norm")(h), positions, lengths)
            with jax.named_scope(scope):
                return h + a * mlp(norm(name="post_norm")(h))

        def scoped_mlp(u):
            with jax.named_scope(scope):
                return mlp(u)

        for name, before, sublayer in (
                ("hyper_mixer", "input_norm",
                 lambda u: mixer(u, positions, lengths)),
                ("hyper_mlp", "post_norm", scoped_mlp)):
            with jax.named_scope("hyper"):
                pre, post, res = HyperConnection(
                    self.streams, norm_eps=self.eps,
                    param_dtype=self.param_dtype, name=name,
                    **dict(self.hyper_args or {}))(h)
                u = hyper_mix(pre, h)
            y = sublayer(norm(name=before)(u))
            with jax.named_scope("hyper"):
                h = hyper_update(post, res, h, y)
        return h


class HybridDecoder(nn.Module):
    """Embedding, ``len(mixers)`` layers of the kinds ``mixers`` names
    (``"block_sparse"`` / ``"lightning"`` / ``"power_retention"`` /
    ``"latent"`` / ``"full"`` / ``"window"`` / ``"mamba2"``), final
    RMSNorm, a head of its own or (``tied_head``) the embedding's matrix.

    ``mlps`` names each layer's MLP (``"dense"``, the default, or
    ``"experts"``: :class:`RoutedExperts` with the fields ``experts``);
    ``latent`` holds :class:`LatentAttention`'s ranks and widths;
    ``ssm`` holds :class:`StateSpace`'s heads and sizes;
    ``window`` is the keys a ``"window"`` layer sees
    (:class:`GroupedQueryAttention`; ``rotary`` names the grouped-query
    kinds whose layers rotate queries and keys, by default the window
    layers alone; ``qk_norm`` and ``attention_scale`` are its
    ``qk_norm`` and ``scale``); ``norms`` says where a sublayer's norm sits
    (:class:`HybridLayer`); ``layer_barriers`` puts an optimisation
    barrier after every layer of a prompt, so that XLA does not run one
    layer's work under another's: without it the temporaries of four
    layers of a 16,384-token prompt at a width of 6,144 live at once
    (4.2 GB where the barriers leave 2.5; a decode step has none);
    ``streams`` > 1 repeats the embedding into that many residual
    streams, puts a :class:`HyperConnection` (fields ``hyper``) round
    every sublayer and sums the streams before the final norm.

    ``layer_indices`` gives each layer's index in the published model
    (a lightning layer's decay depends on it) and ``published_depth`` the
    published number of layers, which the residual scale and the decay
    keep when the depth is cut. The muP scalings are neutral at their
    defaults, ``scale_depth=None`` meaning a residual scale of 1;
    ``residual_multiplier``, where given, is the residual scale itself,
    and ``logits_divisor`` divides the logits. ``causal``, ``max_seq``,
    ``vocab_size`` and :meth:`serving` are what
    ``serve.kv_cache.DecodeEngine`` asks of a model.

    ``block_len`` > 1 is generation by diffusion over blocks (SDAR,
    arXiv:2510.06303): attention is causal over blocks of that many
    positions and open inside one, a position predicts its OWN token, a
    decode step is one pass over a block of ``block_len`` tokens a row
    (``[MASK]``, ``mask_id``, where the block is still masked) and
    unmasks ``block_len / denoising_steps`` of its positions, those the
    model is surest of. The model only declares this (:meth:`serving`);
    the passes, the choice and the schedule are the engine's
    (``serve/kv_cache.py``). Every mixer must have a block form
    (``MIXERS[kind].blocks``: the full grouped-query layers)."""

    vocab_size: int
    d_model: int
    d_ff: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mixers: Tuple[str, ...]
    sparse: Any = None                  # a mapping; see BlockSparseAttention
    latent: Any = None                  # a mapping; see LatentAttention
    ssm: Any = None                     # a mapping; see StateSpace
    window: Optional[int] = None        # keys a "window" layer sees
    rotary: Tuple[str, ...] = (WINDOW,)  # grouped-query kinds that rotate
    qk_norm: bool = True
    attention_scale: Optional[float] = None
    norms: str = NORM_INPUT
    layer_barriers: bool = False
    mlps: Optional[Tuple[str, ...]] = None
    experts: Any = None                 # a mapping; see RoutedExperts
    streams: int = 1
    hyper: Any = None                   # a mapping; see HyperConnection
    layer_indices: Optional[Tuple[int, ...]] = None
    published_depth: Optional[int] = None
    scale_emb: float = 1.0
    scale_depth: Optional[float] = 1.0
    residual_multiplier: Optional[float] = None
    dim_model_base: Optional[int] = None
    logits_divisor: float = 1.0
    tied_head: bool = False
    rope_theta: float = 10000.0
    eps: float = 1e-6
    max_seq: int = 2048
    causal: bool = True
    block_len: int = 1                  # > 1: diffusion over blocks
    mask_id: Optional[int] = None       # the [MASK] token's id
    denoising_steps: int = 1            # passes that unmask a whole block
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32
    decode: bool = False

    def _mixers(self):
        """(the table's entry, layers of it) for each kind in ``mixers``."""
        return [(mixer_of(kind), layers) for kind, layers
                in collections.Counter(self.mixers).items()]

    @property
    def dense_len(self):
        """Prompts longer than this select key blocks (or ``None``)."""
        return next((mixer.dense_len(self) for mixer, _ in self._mixers()
                     if mixer.dense_len), None)

    @property
    def resumable_prefill(self):
        """Whether a prefill that is handed a slot's cache continues from
        it (``positions`` the piece's offset): when every mixer can."""
        return all(mixer.resumes for mixer, _ in self._mixers())

    @property
    def counts_active_rows(self):
        """Whether a decode step wants ``active``, the rows in use."""
        return any(MLPS[mlp].wants_active for mlp in self.mlps or ())

    def decode_positions_by_kind(self, positions):
        """Positions a decode step at ``positions`` (numpy, (rows,), a row
        that is not active at 0) attends, all rows and all layers of a
        kind together, by the kind of cache leaf they are read from (every
        kind a ``reads`` names); ``None`` where no layer has a ``reads``."""
        readers = [(*mixer.reads, layers) for mixer, layers
                   in self._mixers() if mixer.reads]
        if not readers:
            return None
        seen = np.asarray(positions, np.int64) + self.block_len
        out = {mixer.reads[0]: 0 for mixer in MIXERS.values() if mixer.reads}
        for leaf, attended, layers in readers:
            out[leaf] += layers * int(attended(self, seen))
        return out

    def serving(self) -> ServingContract:
        """What ``serve.kv_cache.DecodeEngine`` asks of a model, folded
        from :data:`MIXERS` and :data:`MLPS` over this model's layers."""
        layers = [mixer for mixer, _ in self._mixers()] \
            + [MLPS[mlp] for mlp in self.mlps or ()]
        blocks = {}
        if self.block_len > 1:
            if not all(mixer.blocks for mixer, _ in self._mixers()):
                raise ValueError("block_len > 1 needs mixers with a block "
                                 f"form; got {self.mixers}")
            if self.mask_id is None or self.block_len % self.denoising_steps:
                raise ValueError("block_len > 1 needs a mask_id and "
                                 "denoising_steps that divide block_len")
            blocks = dict(block_len=self.block_len, mask_id=self.mask_id,
                          unmask=self.block_len // self.denoising_steps)
        return ServingContract(
            model=self.clone(decode=True),
            cache_kinds={name: kind for layer in layers
                         for name, kind in layer.cache.items()},
            dense_len=self.dense_len, resumable=self.resumable_prefill,
            wants_active=self.counts_active_rows,
            step_reads=(self.decode_positions_by_kind
                        if any(mixer.reads for mixer, _ in self._mixers())
                        else None), **blocks)

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 lengths=None, output: str = "logits", active=None):
        """``positions``: (batch,) the absolute position of each row's
        first token (decode steps; a prefill starts at 0, or where the
        pieces before it ended: :attr:`resumable_prefill`). ``lengths``:
        (batch,) the true length of each padded row; with it the result
        has one row a sequence, row ``lengths - 1``. ``active``: (batch,)
        bool, the rows that hold a request (only what is counted looks at
        it: :attr:`counts_active_rows`)."""
        del train
        if token_ids.ndim != 2:
            raise ValueError("expected (batch, seq) int token ids")
        batch, seq = token_ids.shape
        if seq > self.max_seq:
            raise ValueError(
                f"sequence length {seq} exceeds max_seq={self.max_seq}")
        if self.decode and positions is None:
            raise ValueError("decode=True requires per-row positions")
        positions = (jnp.zeros((batch,), jnp.int32) if positions is None
                     else jnp.asarray(positions, jnp.int32))
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)
        depth = self.published_depth or len(self.mixers)
        if self.residual_multiplier is not None:
            residual_scale = self.residual_multiplier
        elif self.scale_depth is None:
            residual_scale = 1.0
        else:
            residual_scale = self.scale_depth / math.sqrt(depth)
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         param_dtype=self.param_dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="token_embed")
        h = embed(token_ids) * jnp.asarray(self.scale_emb, self.dtype)
        if self.streams > 1:
            h = jnp.repeat(h[:, None], self.streams, axis=1)
        mlps = self.mlps or (DENSE_MLP,) * len(self.mixers)
        for i, kind in enumerate(self.mixers):
            h = HybridLayer(
                kind=kind, mixer_args=mixer_of(kind).fields(self, i),
                d_ff=self.d_ff, residual_scale=residual_scale,
                norms=self.norms, mlp=mlps[i],
                mlp_args=(dict(self.experts, decode=self.decode,
                               block_len=self.block_len)
                          if mlps[i] == EXPERTS_MLP else None),
                streams=self.streams, hyper_args=self.hyper,
                eps=self.eps, dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"layer_{i}")(h, positions, lengths, active)
            if self.layer_barriers and seq > self.block_len:
                h = jax.lax.optimization_barrier(h)
        if self.streams > 1:
            h = h.astype(F32).sum(axis=1).astype(self.dtype)
        if lengths is not None:
            h = jnp.take_along_axis(
                h, jnp.clip(lengths - 1, 0, seq - 1)[:, None, None], axis=1)
        with jax.named_scope("head"):
            h = RMSNorm(eps=self.eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="final_norm")(h)
            if output == "hidden":
                return h
            width = self.d_model / (self.dim_model_base or self.d_model)
            h = h / jnp.asarray(width, self.dtype)
            if self.tied_head:
                logits = jnp.einsum(
                    "bsd,vd->bsv", h, embed.embedding.astype(self.dtype),
                    preferred_element_type=F32)
            else:
                kernel = self.param(
                    "head", nn.initializers.normal(0.02),
                    (self.d_model, self.vocab_size), self.param_dtype)
                logits = jnp.einsum(
                    "bsd,dv->bsv", h, kernel.astype(self.dtype),
                    preferred_element_type=F32)
            if self.logits_divisor != 1.0:
                logits = logits / self.logits_divisor
            return logits
