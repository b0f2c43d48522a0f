"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second long-context strategy (complement of ring attention): the
sequence axis is sharded across the mesh for every layer *except* attention;
at the attention boundary an ``all_to_all`` re-shards from
``(batch, heads, seq/N, dim)`` to ``(batch, heads/N, seq, dim)`` so each
device runs ordinary full-sequence flash attention on a subset of heads,
then a second ``all_to_all`` restores sequence sharding. Communication is
2 all-to-alls per attention call (O(activations/N) bytes over ICI) versus
ring attention's N ppermute steps — cheaper when heads ≥ N and the
interconnect favours all-to-all; ring wins when seq is huge or heads < N.

Like ring attention this is a TPU-first extension (the reference framework
has no sequence parallelism — SURVEY.md §5.7); both compose with data
parallelism over the remaining mesh axes, and both are exact.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
from jax import lax

from horovod_tpu.ops.pallas.flash_attention import flash_attention


def ulysses_attention(q, k, v, axis_name, *, causal: bool = False,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      block_q: int = 512, block_k: int = 1024,
                      bwd_block_q: int = 1024, bwd_block_k: int = 1024):
    """Attention over a sequence sharded on ``axis_name`` via all-to-all.

    Must run inside ``shard_map``; ``q``/``k``/``v`` are local sequence
    shards ``(batch, heads, seq/N, dim)`` with ``heads`` divisible by the
    axis size. Returns the local output shard, same shape as ``q``.

    ``attn_fn(q, k, v, causal=..., sm_scale=...)`` defaults to the Pallas
    flash kernel; it sees full-sequence inputs with ``heads/N`` heads.
    """
    n = lax.axis_size(axis_name)
    heads = q.shape[1]
    if heads % n:
        raise ValueError(
            f"ulysses_attention needs heads ({heads}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring attention otherwise")

    def to_seq(x):  # (b, h, s/N, d) -> (b, h/N, s, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_heads(x):  # (b, h/N, s, d) -> (b, h, s/N, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qs, ks, vs = to_seq(q), to_seq(k), to_seq(v)
    if attn_fn is None:
        o = flash_attention(qs, ks, vs, causal=causal, sm_scale=sm_scale,
                            block_q=block_q, block_k=block_k,
                            bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k)
    else:
        o = attn_fn(qs, ks, vs, causal=causal, sm_scale=sm_scale)
    return to_heads(o)
