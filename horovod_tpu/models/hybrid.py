"""Decoders whose layers differ in kind (flax): block-sparse softmax
attention, linear ("lightning") attention with a fixed decay and power
retention (gated, normalised linear attention of degree 2), on a modern
trunk - RMSNorm, SiLU-gated MLP, rotary positions, grouped key/value
heads, per-head QK-norm, sigmoid output gates, an untied head and
(optional) muP scalings.

:class:`HybridDecoder` reads its layer kinds from ``mixers`` and is what
``hvd.serve()`` runs for MiniCPM-SALA (``benchmark/configs/
minicpm-sala.json``; the plain reference is
``benchmark/reference_sala.py``) and for Brumby-14B-Base
(``benchmark/configs/brumby-14b.json``, ``benchmark/
reference_brumby.py``). The blocks (:class:`RMSNorm`, :class:`GatedMlp`,
:func:`rope`, :class:`BlockSparseAttention`, :class:`LightningAttention`,
:class:`PowerRetention`) are not tied to those models.

Serving (``decode=True``) keeps a ``cache`` collection whose leaves all
have the slot as axis 0; which kinds it holds depends on the mixers (a
model of power-retention layers alone has no leaf with a position axis):

* ``cached_key`` / ``cached_value`` ``(slots, kv_heads, head_dim,
  max_seq)`` - a sparse layer's keys and values, positions last, the
  layout ``ops/pallas/kv_cache_write`` writes one token into;
* ``compressed_key`` ``(slots, kv_heads, head_dim, windows)`` - the
  means of the key windows that block selection scores;
* ``state`` ``(slots, heads, head_dim, head_dim)`` float32 - a lightning
  layer's recurrent state, which does not grow with the context;
* ``state`` ``(slots, kv_heads, head_dim / 2 + 1, head_dim, head_dim)``
  and ``state_norm`` ``(slots, kv_heads, head_dim / 2 + 1, head_dim)``
  float32 - a power-retention layer's state, ``D = head_dim (head_dim +
  1) / 2`` rows of ``head_dim`` values, and its normaliser, both padded to
  whole rows of distances (``power_features``; 8,256 to 8,320 at width
  128) in the layout ``ops/pallas/power_retention`` reads.

A call with one token a row is a decode step; a call with more is a
prefill from position 0, which computes the prompt without the cache and
then fills it. A recurrence is not indifferent to padding as masked
softmax is, so a prefill takes the true ``lengths``: the state it leaves
is the state after ``lengths`` tokens, and with ``lengths`` given the
head runs on row ``lengths - 1`` alone.

The sparse layer computes masked dense attention in blocks of queries
(each query's selected key blocks are a mask over all causal keys) and
the lightning layer scans chunks, both in XLA; PERF.md says what that
costs and what a kernel would save. The power-retention layer scans
chunks too, and reads its state through two kernels
(``ops/pallas/power_retention``): XLA would write every query's 8,256
features to memory first.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import write_cache_rows
from horovod_tpu.ops.pallas import power_retention
from horovod_tpu.ops.pallas.kv_cache_write import LANES, write_token

Dtype = Any
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_SPARSE, LIGHTNING = "block_sparse", "lightning"
POWER_RETENTION = "power_retention"
# a masked score: finite, so that a row with nothing to see stays a number
NEG_INF = -1e30

# queries a block of the sparse layer's prompt attention: the float32
# scores of one block are (heads, QUERY_BLOCK, keys)
QUERY_BLOCK = 128
# the prompt's query blocks run in at most this many groups, each against
# the keys up to its own end (static), so that about half of the causal
# triangle's upper part is never computed
KEY_EXTENTS = 8


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


def rope(x, positions, theta):
    """Rotary positions over the whole head width, halves paired
    (``x[..., i]`` with ``x[..., i + d/2]``). ``x``: (batch, seq, heads,
    d); ``positions``: (batch, seq) absolute. Float32 in and out."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
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
                      dtype=jnp.bfloat16):
    """The same recurrence over a whole sequence from a zero state, by
    chunks: inside a chunk the masked squares ``(q_i . k_j)^2 / d`` with
    ``exp(b_i - b_j)``, ``b`` the running sum of the chunk's log-gates;
    between chunks the state and its normaliser. ``q``: (batch, seq,
    heads, d); ``k``/``v``: (batch, seq, kv_heads, d); ``log_gate``:
    (batch, seq, kv_heads) float32.

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

    carry = (jnp.zeros((batch, groups, turns * d, d), F32),
             jnp.zeros((batch, groups, turns * d), F32))
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
                    self.dtype)
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


def sparse_prompt_attention(q, k, v, sparse, dtype):
    """Block-sparse causal attention of a whole prompt from position 0.

    ``q``: (batch, seq, heads, d); ``k``/``v``: (batch, seq, kv_heads, d).
    Returns (batch, seq, heads, d) in ``dtype`` and the compressed keys
    (batch, windows, kv_heads, d) float32.

    Masked dense attention a block of ``QUERY_BLOCK`` queries at a time:
    each query's key blocks (:func:`select_blocks`) become a mask over
    the keys up to the end of its group of blocks."""
    batch, seq, heads, d = q.shape
    groups, size = k.shape[2], sparse["block_size"]
    per = heads // groups
    scale = 1.0 / math.sqrt(d)
    q_block = min(QUERY_BLOCK, seq)
    unit = q_block * size // math.gcd(q_block, size)
    pad = -seq % unit
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    total = seq + pad
    compressed = compress_keys(k, sparse["kernel"], sparse["stride"])
    q = q.reshape(batch, total // q_block, q_block, groups, per, d)
    blocks = total // q_block
    extents = max(e for e in range(1, KEY_EXTENTS + 1) if blocks % e == 0)
    each = blocks // extents
    outs = []
    for e in range(extents):
        end = (e + 1) * each * q_block        # keys this group can see
        n_blocks = -(-end // size)
        k_e, v_e = k[:, :end], v[:, :end]
        kc_e = compressed[:, :count_windows(end, sparse)].astype(dtype)
        select = end > sparse["dense_len"] and n_blocks > sparse["topk"]

        def one(xs):      # mapped below, inside this turn of the loop
            q_b, start = xs                   # (batch, q_block, g, per, d)
            q_pos = start + jnp.arange(q_block, dtype=jnp.int32)
            q_pos = jnp.broadcast_to(q_pos, (batch, 1, q_block))
            if select:
                with jax.named_scope("sparse_select"):
                    s = jnp.einsum("btgrd,bjgd->bgtrj", q_b, kc_e,
                                   preferred_element_type=F32) * scale
                    chosen = select_blocks(
                        block_scores(s, q_pos, n_blocks, sparse), q_pos,
                        sparse)                # (batch, g, t, n_blocks)
            else:
                chosen = jnp.ones((batch, 1, q_block, n_blocks), bool)
            with jax.named_scope("sparse_attn"):
                mask = _token_mask(chosen, q_pos, size, end)
                s = jnp.einsum("btgrd,bsgd->bgrts", q_b, k_e,
                               preferred_element_type=F32) * scale
                s = jnp.where(mask[:, :, None], s, NEG_INF)
                p = jax.nn.softmax(s, axis=-1).astype(dtype)
                # heads before queries, as the product leaves them: the
                # loop then stacks its blocks without a strided write
                return jnp.einsum("bgrts,bsgd->bgrtd", p, v_e)

        starts = (e * each + jnp.arange(each, dtype=jnp.int32)) * q_block
        outs.append(jax.lax.map(
            one, (q[:, e * each:(e + 1) * each].transpose(
                1, 0, 2, 3, 4, 5), starts)))
    # (blocks, batch, g, per, q_block, d) -> (batch, positions, heads, d)
    out = jnp.concatenate(outs, axis=0).transpose(1, 0, 4, 2, 3, 5)
    out = out.reshape(batch, total, heads, d)[:, :seq]
    return out, compressed[:, :count_windows(seq, sparse)]


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
            o, kc = sparse_prompt_attention(q, k, v, sparse, self.dtype)
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


# ------------------------------------------------------------------- trunk

class HybridLayer(nn.Module):
    """``h += a Mixer(norm(h)); h += a Mlp(norm(h))`` with the residual
    scale ``a`` (muP's ``scale_depth / sqrt(depth)``, or 1); ``kind``
    says which mixer, ``mixer_args`` are its fields."""

    kind: str
    mixer_args: Any
    d_ff: int
    residual_scale: float = 1.0
    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, h, positions, lengths=None):
        common = dict(eps=self.eps, dtype=self.dtype,
                      param_dtype=self.param_dtype)
        mixer = {LIGHTNING: LightningAttention,
                 BLOCK_SPARSE: BlockSparseAttention,
                 POWER_RETENTION: PowerRetention}[self.kind](
                     name="mixer", **dict(self.mixer_args), **common)
        norm = partial(RMSNorm, **common)
        a = jnp.asarray(self.residual_scale, self.dtype)
        h = h + a * mixer(norm(name="input_norm")(h), positions, lengths)
        with jax.named_scope("mlp"):
            return h + a * GatedMlp(
                self.d_ff, dtype=self.dtype, param_dtype=self.param_dtype,
                name="mlp")(norm(name="post_norm")(h))


class HybridDecoder(nn.Module):
    """Embedding, ``len(mixers)`` layers of the kinds ``mixers`` names
    (``"block_sparse"`` / ``"lightning"`` / ``"power_retention"``), final
    RMSNorm, untied head.

    ``layer_indices`` gives each layer's index in the published model
    (a lightning layer's decay depends on it) and ``published_depth`` the
    published number of layers, which the residual scale and the decay
    keep when the depth is cut. The muP scalings are neutral at their
    defaults, ``scale_depth=None`` meaning a residual scale of 1. ``causal``, ``max_seq``, ``vocab_size``
    and ``clone(decode=..., remat=..., attention_fn=...)`` are what
    ``serve.kv_cache.DecodeEngine`` asks of a model (``remat`` and
    ``attention_fn`` are accepted for that and not used)."""

    vocab_size: int
    d_model: int
    d_ff: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mixers: Tuple[str, ...]
    sparse: Any = None                  # a mapping; see BlockSparseAttention
    layer_indices: Optional[Tuple[int, ...]] = None
    published_depth: Optional[int] = None
    scale_emb: float = 1.0
    scale_depth: Optional[float] = 1.0
    dim_model_base: Optional[int] = None
    rope_theta: float = 10000.0
    eps: float = 1e-6
    max_seq: int = 2048
    causal: bool = True
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = F32
    decode: bool = False
    remat: bool = False
    attention_fn: Optional[Callable] = None

    @property
    def dense_len(self):
        """Prompts longer than this select key blocks (``None`` when no
        layer is block sparse)."""
        if BLOCK_SPARSE not in self.mixers:
            return None
        return dict(self.sparse)["dense_len"]

    def _mixer_args(self, i, kind):
        depth = self.published_depth or len(self.mixers)
        if kind == LIGHTNING:
            index = self.layer_indices[i] if self.layer_indices else i
            return dict(num_heads=self.num_heads, head_dim=self.head_dim,
                        layer_index=index, published_depth=depth,
                        rope_theta=self.rope_theta, decode=self.decode)
        if kind == BLOCK_SPARSE:
            return dict(num_heads=self.num_heads,
                        num_kv_heads=self.num_kv_heads,
                        head_dim=self.head_dim, sparse=self.sparse,
                        max_cache_len=self.max_seq, decode=self.decode)
        if kind == POWER_RETENTION:
            return dict(num_heads=self.num_heads,
                        num_kv_heads=self.num_kv_heads,
                        head_dim=self.head_dim, rope_theta=self.rope_theta,
                        decode=self.decode)
        raise ValueError(f"unknown mixer {kind!r}")

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 lengths=None, output: str = "logits"):
        """``positions``: (batch,) the absolute position of each row's
        first token (decode steps; a prefill starts at 0). ``lengths``:
        (batch,) the true length of each padded row; with it the result
        has one row a sequence, row ``lengths - 1``."""
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
        h = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     embedding_init=nn.initializers.normal(0.02),
                     name="token_embed")(token_ids)
        h = h * jnp.asarray(self.scale_emb, self.dtype)
        for i, kind in enumerate(self.mixers):
            h = HybridLayer(
                kind=kind, mixer_args=self._mixer_args(i, kind),
                d_ff=self.d_ff,
                residual_scale=(1.0 if self.scale_depth is None
                                else self.scale_depth / math.sqrt(depth)),
                eps=self.eps, dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"layer_{i}")(h, positions, lengths)
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
            kernel = self.param(
                "head", nn.initializers.normal(0.02),
                (self.d_model, self.vocab_size), self.param_dtype)
            return jnp.einsum("bsd,dv->bsv", h, kernel.astype(self.dtype),
                              preferred_element_type=F32)
