"""What a model hands the serving engine (``serve/kv_cache.DecodeEngine``),
beside ``causal``, ``max_seq`` and ``vocab_size``: one object from one
method, ``serving()``. The engine names no layer kind, no cache variable
and no kernel; the arrow points from ``serve/`` to ``models/``, never back.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional


@dataclasses.dataclass(frozen=True)
class ServingContract:
    """``model``: the model cloned for decoding, every leaf of its ``cache``
    collection with the slot as axis 0. ``cache_kinds``: cache variable
    name -> kind of leaf, for every variable its layers declare (the engine
    treats ``state`` and ``counter`` itself and reports the others as
    named). ``dense_len``: prompts longer than this select key blocks.
    ``resumable``: a prefill that is handed a slot's cache continues from
    it (``positions`` the piece's offset), so a prompt runs in pieces of
    one shape. ``wants_active``: a decode step passes the model ``active=``
    (the rows that hold a request). ``step_reads``: ``None``, or
    ``positions -> {kind of leaf: positions attended}`` for one decode
    step at ``positions`` (numpy, (rows,), a row that is not active at 0),
    all layers of a kind together.

    ``block_len`` > 1: the model generates by diffusion over blocks. A
    decode step is then one pass over ``block_len`` tokens a row from the
    block's first position, the logits at a position are of that
    position's own token, ``mask_id`` stands where a position is still
    masked, and a pass that unmasks takes ``unmask`` positions (fewer
    where fewer are left). That is all the engine is told; how it keeps
    the block, chooses and commits is ``serve/kv_cache.py``'s."""

    model: Any
    cache_kinds: Mapping[str, str]
    dense_len: Optional[int] = None
    resumable: bool = False
    wants_active: bool = False
    step_reads: Optional[Callable] = None
    block_len: int = 1
    mask_id: Optional[int] = None
    unmask: int = 1

    def leaf_kind(self, path) -> str:
        """The declared kind of the cache leaf at a tree ``path``
        (``other`` for a variable no layer declared)."""
        name = getattr(path[-1], "key", getattr(path[-1], "name", ""))
        return self.cache_kinds.get(str(name), "other")
