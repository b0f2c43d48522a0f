"""Transformer model family (flax), TPU-first: BERT-style encoders and
GPT-style causal decoders.

The reference's BERT acceptance workload is a data-parallel fine-tune whose
distinguishing traffic is large embedding-table gradients on the allgather/
sparse path (BASELINE.md config #5; reference sparse handling:
horovod/tensorflow/__init__.py:64-75 IndexedSlices → allgather). This module
is a fresh TPU-native implementation, not a port of any reference model
code (the reference ships no transformer code at all):

* Attention runs through the Pallas flash kernel (ops/pallas/
  flash_attention.py) — the (seq, seq) score matrix never hits HBM.
* bfloat16 compute / float32 parameters; matmuls sized for the MXU
  (head_dim 64-128, hidden multiples of 128).
* Static shapes; per-layer ``jax.checkpoint`` (remat) optional for long
  sequences.
* Sequence parallelism drops in by swapping the attention function for
  ``ring_attention``/``ulysses_attention`` (parallel/) under ``shard_map``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models.serving import ServingContract
from horovod_tpu.ops.pallas._backend import shard_over_batch
from horovod_tpu.ops.pallas.decode_attention import (decode_attention,
                                                     takes_kernel)
from horovod_tpu.ops.pallas.flash_attention import NEG_INF, flash_attention
from horovod_tpu.ops.pallas.kv_cache_write import write_token

Dtype = Any


def cached_attention(q, k, v, q_positions):
    """Masked attention against an absolute-position KV cache.

    ``q``: (batch, heads, new, head_dim) — the new tokens' queries;
    ``k``/``v``: (batch, heads, cache_len, head_dim) — the FULL per-slot
    cache, freshly-written rows and stale/zero rows alike;
    ``q_positions``: (batch, new) int32 absolute position of each query.

    The mask ``key_pos <= q_pos`` is what makes the cache safe to reuse
    without per-slot length bookkeeping: a key row is attendable only
    once some query's absolute position has reached it, and by then it
    was written either by this request's prefill or by an earlier decode
    step of this request — stale rows from a previous slot occupant sit
    at positions the current request has not reached, padded prefill
    rows are overwritten by decode before a query passes them.

    Plain XLA einsum + f32 softmax over the WHOLE cache, whatever the
    rows' lengths: right for a prefill (bucket-many queries against a
    fresh cache) and for the paged engine's gathered view. The dense
    decode step, one query a row, goes through
    :func:`write_and_attend`, which reads only the live part of each row.
    """
    head_dim = q.shape[-1]
    scale = 1.0 / float(np.sqrt(head_dim))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    key_ids = jnp.arange(k.shape[2], dtype=jnp.int32)
    mask = key_ids[None, None, None, :] <= q_positions[:, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def write_cache_rows(cache, new, positions):
    """``cache`` with ``new`` written in: row ``b`` takes ``new[b]`` at
    positions ``positions[b] .. positions[b] + n - 1``.

    ``cache``: (batch, heads, head_dim, cache_len), positions last: the
    layout both contractions of :func:`cached_attention` read;
    ``new``: (batch, n, heads, head_dim) of the cache's dtype;
    ``positions``: (batch,) int32.

    One new token a row goes through the in-place kernel: XLA:TPU
    expands a batched scatter into one serial trip per row, and a select
    over the cache writes all of it back. (The dense decode step on a
    cache of whole lane tiles does not come here: :func:`write_and_attend`
    writes from its attention kernel. ``models/hybrid.py`` and a cache
    length off the tile do.) Several tokens a row (prefill) go in as one
    slice per row.
    """
    if new.shape[1] == 1:
        return write_token(cache, new[:, 0], positions)
    return jax.vmap(
        lambda row, rows, start: jax.lax.dynamic_update_slice(
            row, rows, (0, 0, start)))(
                cache, new.transpose(0, 2, 3, 1), positions)


def write_and_attend(q, k, v, k_cache, v_cache, positions):
    """A serving step's new tokens written into the positions-last cache
    and attended against it: ``(o, k_cache, v_cache)``.

    ``q``/``k``/``v``: (batch, new, heads, head_dim), the keys and values
    of the cache's dtype; ``k_cache``/``v_cache``: (batch, heads,
    head_dim, cache_len); ``positions``: (batch,) int32, the first new
    token's. ``o`` is (batch, new, heads, head_dim).

    One algorithm (write the columns, attend up to them), in the form
    the step's shape allows: one new token a row against a cache of
    whole lane tiles (the dense decode step) is one kernel,
    ``ops/pallas/decode_attention``, which fetches the lane tiles
    ``0 .. position // 128`` of each row and none past them, and puts
    the new column into the last of them while it is there. Anything
    else is :func:`write_cache_rows` a leaf, then
    :func:`cached_attention` over the whole cache.
    """
    new_tokens = q.shape[1]
    if takes_kernel(new_tokens, k_cache.shape[-1]):
        o, k_cache, v_cache = decode_attention(
            q[:, 0], k[:, 0], v[:, 0], k_cache, v_cache, positions)
        return o[:, None], k_cache, v_cache
    k_cache = write_cache_rows(k_cache, k, positions)
    v_cache = write_cache_rows(v_cache, v, positions)
    q_pos = positions[:, None] + jnp.arange(new_tokens, dtype=jnp.int32)
    o = cached_attention(
        q.transpose(0, 2, 1, 3), k_cache.transpose(0, 1, 3, 2),
        v_cache.transpose(0, 1, 3, 2), q_pos)
    return o.transpose(0, 2, 1, 3), k_cache, v_cache


class SelfAttention(nn.Module):
    """Multi-head self-attention on the flash kernel.

    ``attention_fn`` takes ``(q, k, v, causal=...)`` over
    ``(batch, heads, seq, head_dim)`` and defaults to the single-device
    Pallas kernel; sequence-parallel callers inject a ring/Ulysses closure.

    ``decode=True`` switches to the serving path: a ``cache`` variable
    collection holds per-row key/value tensors of length
    ``max_cache_len`` (positions last), new tokens are written in at
    their absolute ``positions`` and attention runs against the cache,
    masked past each query's position (:func:`write_and_attend`: for a
    one-token step one kernel that reads the live part of each row and
    writes the new column into it, else a write a leaf and attention
    over the whole cache).
    Parameters are identical to the training module — only runtime
    behavior and the (non-param) cache change.

    ``paged=True`` (with ``decode=True``) swaps the per-row cache for a
    POOLED one: ``(num_pages, page_tokens, heads, head_dim)`` per layer,
    indexed through a per-row int32 ``page_table`` mapping logical block
    ``pos // page_tokens`` to a physical page (serve/paging.py owns the
    allocator). Writes scatter at ``cache.at[page, offset]`` with traced
    indices; reads gather ``cache[page_table]`` and flatten back to a
    per-row view whose flattened key index IS the absolute position, so
    the same ``key_pos <= q_pos`` mask applies unchanged. Table entries
    past a request's last block point at the reserved scratch page 0 —
    scatter clamps overflowing (padded-garbage) positions onto it and
    the mask keeps it unattendable. Both shapes and the program are
    fixed; growing a request only changes table VALUES.
    """

    num_heads: int
    causal: bool = False
    dtype: Dtype = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    decode: bool = False
    max_cache_len: int = 0
    paged: bool = False
    num_pages: int = 0
    page_tokens: int = 0

    @nn.compact
    def __call__(self, x, positions=None, page_table=None):
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must divide d_model "
                f"({d_model})")
        head_dim = d_model // self.num_heads
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=jnp.float32)

        qkv_shape = (self.num_heads, head_dim)
        q = dense(features=qkv_shape, name="query")(x)
        k = dense(features=qkv_shape, name="key")(x)
        v = dense(features=qkv_shape, name="value")(x)

        if self.decode and self.paged:
            if positions is None:
                raise ValueError("decode=True requires per-row positions")
            if page_table is None:
                raise ValueError("paged=True requires a page_table")
            if self.num_pages <= 0 or self.page_tokens <= 0:
                raise ValueError(
                    "paged=True requires num_pages and page_tokens > 0")
            batch, new_tokens = x.shape[0], x.shape[1]
            T = self.page_tokens
            cache_shape = (self.num_pages, T, self.num_heads, head_dim)
            cached_key = self.variable("cache", "cached_key", jnp.zeros,
                                       cache_shape, self.dtype)
            cached_value = self.variable("cache", "cached_value", jnp.zeros,
                                         cache_shape, self.dtype)
            table = jnp.asarray(page_table, jnp.int32)
            width = table.shape[1]
            pos = jnp.asarray(positions, jnp.int32)
            abs_pos = pos[:, None] + jnp.arange(new_tokens, dtype=jnp.int32)
            # logical block per new token; positions past the mapped
            # table clamp onto the trailing scratch entry (padded
            # prefill garbage lands there, masked + never gathered as a
            # reachable key position)
            blk = jnp.minimum(abs_pos // T, width - 1)
            page = jnp.take_along_axis(table, blk, axis=1)
            off = abs_pos % T
            cached_key.value = cached_key.value.at[page, off].set(
                k.astype(self.dtype))
            cached_value.value = cached_value.value.at[page, off].set(
                v.astype(self.dtype))
            # gather the row's mapped pages and flatten: key index i is
            # absolute position i for every mapped block, so the dense
            # path's mask semantics carry over verbatim
            k_all = cached_key.value[table].reshape(
                batch, width * T, self.num_heads, head_dim)
            v_all = cached_value.value[table].reshape(
                batch, width * T, self.num_heads, head_dim)
            o = cached_attention(
                q.transpose(0, 2, 1, 3), k_all.transpose(0, 2, 1, 3),
                v_all.transpose(0, 2, 1, 3), abs_pos)
            o = o.transpose(0, 2, 1, 3)
            return dense(features=d_model, axis=(-2, -1), name="out")(o)

        if self.decode:
            if positions is None:
                raise ValueError("decode=True requires per-row positions")
            if self.max_cache_len <= 0:
                raise ValueError("decode=True requires max_cache_len > 0")
            cache_shape = (x.shape[0], self.num_heads, head_dim,
                           self.max_cache_len)
            cached_key = self.variable("cache", "cached_key", jnp.zeros,
                                       cache_shape, self.dtype)
            cached_value = self.variable("cache", "cached_value", jnp.zeros,
                                         cache_shape, self.dtype)
            pos = jnp.asarray(positions, jnp.int32)
            o, cached_key.value, cached_value.value = write_and_attend(
                q, k.astype(self.dtype), v.astype(self.dtype),
                cached_key.value, cached_value.value, pos)
            return dense(features=d_model, axis=(-2, -1), name="out")(o)

        # (batch, seq, heads, head_dim) -> (batch, heads, seq, head_dim)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        if self.attention_fn is not None:
            attn = self.attention_fn
        elif self.is_initializing():
            # the init trace only shapes the params (their values do not
            # depend on the attention output), so it takes the plain XLA
            # attention: off the TPU the kernel would run in interpret
            # mode at python speed, and on it ``model.init`` runs op by
            # op, where a kernel launch per layer buys nothing
            from horovod_tpu.ops.pallas.flash_attention import (
                attention_reference)

            attn = (lambda q, k, v, causal: attention_reference(
                q, k, v, causal=causal))
        else:
            attn = (lambda q, k, v, causal: shard_over_batch(
                partial(flash_attention, causal=causal), (q, k, v)))
        o = attn(q, k, v, causal=self.causal)
        o = o.transpose(0, 2, 1, 3)  # back to (batch, seq, heads, head_dim)
        return dense(features=d_model, axis=(-2, -1), name="out")(o)


class Mlp(nn.Module):
    d_ff: int
    dtype: Dtype = jnp.bfloat16
    act: Callable = nn.gelu

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        h = nn.Dense(self.d_ff, dtype=self.dtype,
                     param_dtype=jnp.float32, name="wi")(x)
        h = self.act(h)
        return nn.Dense(d_model, dtype=self.dtype,
                        param_dtype=jnp.float32, name="wo")(h)


class TransformerLayer(nn.Module):
    """Pre-LayerNorm block: x + Attn(LN(x)); x + MLP(LN(x))."""

    num_heads: int
    d_ff: int
    causal: bool = False
    dtype: Dtype = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    decode: bool = False
    max_cache_len: int = 0
    paged: bool = False
    num_pages: int = 0
    page_tokens: int = 0

    @nn.compact
    def __call__(self, x, positions=None, page_table=None):
        ln = partial(nn.LayerNorm, dtype=self.dtype, param_dtype=jnp.float32)
        x = x + SelfAttention(
            num_heads=self.num_heads, causal=self.causal, dtype=self.dtype,
            attention_fn=self.attention_fn, decode=self.decode,
            max_cache_len=self.max_cache_len,
            paged=self.paged, num_pages=self.num_pages,
            page_tokens=self.page_tokens,
            name="attention")(ln()(x), positions=positions,
                              page_table=page_table)
        x = x + Mlp(d_ff=self.d_ff, dtype=self.dtype, name="mlp")(ln()(x))
        return x


class Transformer(nn.Module):
    """Shared trunk: embeddings → N layers → final LayerNorm → logits.

    ``causal=True`` makes a GPT-style decoder; ``causal=False`` a BERT-style
    bidirectional encoder. The output projection ties the token-embedding
    matrix (standard for both families). Vocab logits are returned in
    float32 for a numerically stable softmax-cross-entropy.
    """

    vocab_size: int
    d_model: int = 768
    num_layers: int = 12
    num_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 512
    causal: bool = False
    dtype: Dtype = jnp.bfloat16
    remat: bool = False
    attention_fn: Optional[Callable] = None
    decode: bool = False
    paged: bool = False
    num_pages: int = 0
    page_tokens: int = 0

    def serving(self) -> ServingContract:
        """For ``serve.kv_cache.DecodeEngine``: keys and values alone."""
        return ServingContract(
            model=self.clone(decode=True, remat=False, attention_fn=None),
            cache_kinds={"cached_key": "kv", "cached_value": "kv"})

    @nn.compact
    def __call__(self, token_ids, train: bool = True, pos_offset=0,
                 output: str = "logits", positions=None, page_table=None,
                 lengths=None):
        """``lengths`` (serving prefill only): (batch,) the true length of
        each padded row; the result then has one row a sequence, row
        ``lengths - 1``, so that a prefill never builds the (bucket, vocab)
        logits. Masked softmax needs the lengths for nothing else.

        ``pos_offset`` is the global position of the first token — under
        sequence parallelism each device passes its shard's offset (e.g.
        ``lax.axis_index(axis) * seq_local``) so position embeddings stay
        global; it may be a traced scalar. ``max_seq`` must cover the
        GLOBAL sequence (``pos_offset + seq``); with a traced offset this
        cannot be checked at trace time, so size ``max_seq`` accordingly.

        ``output="hidden"`` returns the final-norm hidden states
        (batch, seq, d_model) WITHOUT the tied vocab projection — the
        MLM training path projects only the masked positions
        (:func:`masked_lm_loss_gathered`), so the (batch, seq, vocab)
        float32 logits tensor (0.5 GB at BERT-Large bench shapes) never
        exists. Measured on the BERT-Large bench shape: the full-logits
        head costs ~2.9 ms of a 79.2 ms step — the gathered path is
        +3.8% tokens/s end to end (docs/perf_experiments.md round 4)."""
        if token_ids.ndim != 2:
            raise ValueError("expected (batch, seq) int token ids")
        seq = token_ids.shape[1]
        if seq > self.max_seq:
            raise ValueError(
                f"sequence length {seq} exceeds max_seq={self.max_seq}")
        embed = nn.Embed(self.vocab_size, self.d_model,
                         dtype=self.dtype, param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(0.02),
                         name="token_embed")
        pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_seq, self.d_model), jnp.float32)

        if self.decode:
            # serving decode: each batch row sits at its own absolute
            # position (continuous batching mixes requests of different
            # lengths in one step). Gather per-row position embeddings
            # and thread ``positions`` to every layer's KV cache.
            if positions is None:
                raise ValueError("decode=True requires per-row positions")
            pos_idx = (jnp.asarray(positions, jnp.int32)[:, None]
                       + jnp.arange(seq, dtype=jnp.int32)[None, :])
            pos_idx = jnp.minimum(pos_idx, self.max_seq - 1)
            pos_rows = jnp.take(pos_embed, pos_idx, axis=0)
            x = embed(token_ids) + pos_rows.astype(self.dtype)
            for i in range(self.num_layers):
                x = TransformerLayer(
                    num_heads=self.num_heads, d_ff=self.d_ff,
                    causal=self.causal, dtype=self.dtype,
                    attention_fn=self.attention_fn, decode=True,
                    max_cache_len=self.max_seq, paged=self.paged,
                    num_pages=self.num_pages,
                    page_tokens=self.page_tokens,
                    name=f"layer_{i}")(x, positions=positions,
                                       page_table=page_table)
            if lengths is not None:
                x = jnp.take_along_axis(
                    x, jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0,
                                seq - 1)[:, None, None], axis=1)
            x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                             name="final_norm")(x)
            if output == "hidden":
                return x
            return embed.attend(x).astype(jnp.float32)

        if isinstance(pos_offset, int):
            # static offset: check bounds eagerly — dynamic_slice would
            # silently clamp and reuse wrong position embeddings.
            if pos_offset + seq > self.max_seq:
                raise ValueError(
                    f"pos_offset {pos_offset} + seq {seq} exceeds "
                    f"max_seq={self.max_seq}; under sequence parallelism "
                    f"max_seq must cover the GLOBAL sequence length")
            pos = jax.lax.dynamic_slice_in_dim(pos_embed, pos_offset, seq,
                                               axis=0) if pos_offset else \
                pos_embed[:seq, :]
        else:
            pos = jax.lax.dynamic_slice_in_dim(
                pos_embed, jnp.asarray(pos_offset, jnp.int32), seq, axis=0)
        x = embed(token_ids) + pos[None, :, :].astype(self.dtype)

        layer = TransformerLayer
        if self.remat:
            layer = nn.remat(layer)
        for i in range(self.num_layers):
            x = layer(num_heads=self.num_heads, d_ff=self.d_ff,
                      causal=self.causal, dtype=self.dtype,
                      attention_fn=self.attention_fn,
                      name=f"layer_{i}")(x)

        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="final_norm")(x)
        if output == "hidden":
            return x
        logits = embed.attend(x)  # tied output projection
        return logits.astype(jnp.float32)


# BERT family (bidirectional encoders; BERT-Large is BASELINE config #5's
# shape: 24 layers, hidden 1024, 16 heads).
BertBase = partial(Transformer, d_model=768, num_layers=12, num_heads=12,
                   d_ff=3072, causal=False)
BertLarge = partial(Transformer, d_model=1024, num_layers=24, num_heads=16,
                    d_ff=4096, causal=False)

# GPT family (causal decoders).
GPT2Small = partial(Transformer, d_model=768, num_layers=12, num_heads=12,
                    d_ff=3072, max_seq=1024, causal=True)
GPT2Medium = partial(Transformer, d_model=1024, num_layers=24, num_heads=16,
                     d_ff=4096, max_seq=1024, causal=True)


def masked_lm_loss(logits, labels, mask):
    """BERT MLM objective: mean cross-entropy over masked positions only."""
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    mask = mask.astype(loss.dtype)
    return (loss * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def masked_lm_loss_gathered(hidden, embed_matrix, positions, labels,
                            weights=None):
    """BERT MLM objective over a FIXED set of masked positions, vocab
    projection applied AFTER gathering — the standard BERT data layout
    (``max_predictions_per_seq``: positions/labels/weights per row).

    ``hidden``: (batch, seq, d) from ``model(..., output="hidden")``;
    ``embed_matrix``: the tied (vocab, d) token embedding
    (``params["params"]["token_embed"]["embedding"]``);
    ``positions``: (batch, M) int32; ``labels``: (batch, M) int32;
    ``weights``: (batch, M) 0/1 mask for rows with fewer than M real
    predictions (None = all real).

    Projecting only the M≈0.15*seq masked positions instead of all seq
    keeps the (batch, seq, vocab) f32 logits tensor from ever existing:
    at BERT-Large bench shapes that is 0.5 GB of HBM written + re-read
    in softmax fwd AND bwd — measured ~2.9 ms of the 79.2 ms step,
    +3.8% tokens/s end to end (docs/perf_experiments.md round 4). FLOPs
    of the projection drop the same way; MFU accounting must use the
    gathered count."""
    gathered = jnp.take_along_axis(hidden, positions[..., None], axis=1)
    logits = (gathered @ embed_matrix.astype(gathered.dtype).T
              ).astype(jnp.float32)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    if weights is None:
        return loss.mean()
    w = weights.astype(loss.dtype)
    return (loss * w).sum() / jnp.maximum(w.sum(), 1.0)


def sample_masked_positions(rng: np.random.Generator, batch: int,
                            seq: int, num_predictions: int):
    """Fixed-count masked-position sampling (BERT's
    ``max_predictions_per_seq`` layout): per row, ``num_predictions``
    distinct positions, sorted. Returns an int32 (batch, M) array of
    positions (labels are the input tokens at those positions; gather
    them with ``np.take_along_axis``)."""
    pos = np.stack([rng.choice(seq, size=num_predictions, replace=False)
                    for _ in range(batch)])
    return np.sort(pos, axis=1).astype(np.int32)


def causal_lm_loss(logits, token_ids):
    """Next-token prediction: shift-by-one cross-entropy."""
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], token_ids[:, 1:])
    return loss.mean()


def random_tokens(rng: np.random.Generator, batch: int, seq: int,
                  vocab_size: int) -> np.ndarray:
    """Synthetic token batch for benchmarks (uniform vocab draw)."""
    return rng.integers(0, vocab_size, size=(batch, seq), dtype=np.int32)
