"""Bytes and operations that a decode step's grouped-query attention over
a positions-last key/value cache requires, whatever implements it
(beside ``benchmark/flops.py``, which an accepted benchmark may not have
edited).

A decode step's full layer reads, at the least, the key and the value
of every position its rows attend, once each, ``kv_heads x head_dim``
wide, in the cache's dtype; the queries and outputs of one token a row
are thousandths of that. Every key meets ``per`` queries (the query
heads a key/value head), so it does ``2 per`` operations a byte of
bfloat16: 16 at 8 queries a head against the chip's 240, bound by
memory. The positions attended come from the engine's counter
(``decode_positions_by_kind``), never from the shapes: a row's context
is what it is, and the cache is ``max_seq`` long whatever it holds.
"""

from __future__ import annotations


def grouped_decode_bytes(positions, kv_heads, head_dim, itemsize=2):
    """Bytes one full layer's decode attention reads at the least for
    ``positions`` attended positions (summed over rows and steps): a key
    and a value each."""
    return positions * 2 * kv_heads * head_dim * itemsize


def grouped_decode_flops(positions, heads, head_dim):
    """Operations of the same: a score and a weighted value a query head
    a position."""
    return positions * 2 * 2 * heads * head_dim
