"""Per-slot KV-cache management + the serving program caches.

:class:`DecodeEngine` owns everything jax about one replica:

* the decode clone of the user's model (``model.clone(decode=True)`` —
  same params, plus a ``cache`` variable collection of
  ``(slots, heads, head_dim, max_seq)`` key/value tensors per layer,
  positions last: the layout attention reads);
* ONE jitted decode program over ALL slots every step — the shape never
  changes (inactive rows run masked garbage at position 0, overwritten
  by the next prefill), so steady-state decode never recompiles;
* one jitted prefill program PER PROMPT-LENGTH BUCKET, batch 1, which
  writes the prompt's KV into a fresh single-row cache and writes that
  row into the requested slot at a traced index. Bucketing reuses the
  runtime's size-bucket policy (``fusion_buffer.bucket_elems``: identity
  up to the quantum, then power-of-two multiples), floored at the
  quantum so short prompts share one program — the bucket set is
  O(log(max_seq)) and after one request per bucket the program cache is
  warm: zero steady-state compiles.

The cache is updated IN PLACE: every program donates its cache argument
(the result aliases it, one cache lives on the device and no program
copies it), the decode step writes one position per row through
``ops/pallas/kv_cache_write`` and a prefill writes its row as one slice.
``self._cache`` is rebound from every call's result; the arrays it held
before are deleted, so a caller must not hold ``engine._cache`` across a
call. ``stats()["cache_donated"]`` says whether the runtime took the
donations (it may decline one and copy instead).

Prefill padding is safe without length bookkeeping for keys and values:
padded positions' garbage KV sits at positions ``>= prompt_len``, which
``models.transformer.cached_attention`` masks for every query that has
not reached them - and decode overwrites each one before its query
arrives. Slot reuse is safe the same way (stale rows of the previous
occupant are never attendable); tests/test_serve.py pins both down
against the uncached ``apply``.

The cache is whatever pytree the model's ``cache`` collection declares,
every leaf with the slot as axis 0: ``models/hybrid.py`` keeps keys and
values, compressed keys and a float32 recurrent state side by side
(:data:`CACHE_KINDS`, ``cache_bytes_by_kind``). A recurrence is not
indifferent to padding, so a prefill hands the model the true
``lengths``: the state it leaves is the state after the prompt, and a
prefill overwrites every leaf's row of its slot, the state included.
With ``lengths`` the model applies its head to the last prompt row
alone - the (bucket, vocab) logits never exist.

Sampling is greedy (argmax in-graph; only the winning token ids leave
the device each step, plus one max-|logit| scalar per slot for the
integrity guard).
"""

from __future__ import annotations

import time
import weakref
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu import tracing
from horovod_tpu.analysis import witness
from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.runtime.fusion_buffer import bucket_elems

# prompt-length bucket quantum (tokens). Not a knob: the policy is the
# runtime's, only the unit differs (tokens, not bytes).
PREFILL_BUCKET_QUANTUM = 16

_COMPILES = _metrics().counter(
    "horovod_serve_compiles_total",
    "Serving programs compiled, by kind (steady state adds none).",
    labelnames=("program",))
_KV_BYTES = _metrics().gauge(
    "horovod_serve_kv_cache_bytes",
    "KV-cache bytes resident per decode engine (replica).",
    labelnames=("replica",))

# every live engine, so the memory tracker's "serve_kv" subsystem can sum
# resident cache bytes without the serve plane pushing on its hot path
_engines_lock = witness.make_lock("kv_cache._engines_lock")
_engines: "weakref.WeakSet" = weakref.WeakSet()  # guarded-by: _engines_lock


def total_cache_bytes() -> int:
    """Resident KV-cache bytes across every live engine on this process —
    the memory tracker's pull source for the ``serve_kv`` subsystem."""
    with _engines_lock:
        engines = list(_engines)
    return sum(e.cache_bytes() for e in engines)


def prompt_bucket(prompt_len: int, max_seq: int,
                  quantum: int = PREFILL_BUCKET_QUANTUM) -> int:
    """Padded prompt length: the fusion-buffer size-bucket policy in
    token units, floored at the quantum (identity below the quantum
    would mean one compile per distinct short-prompt length — right for
    fusion cache keys, wrong for programs)."""
    return min(max_seq, bucket_elems(max(prompt_len, quantum), 1, quantum))


# what a cache leaf holds, by the name its model gave the variable: keys
# and values that grow with the context, compressed keys that a sparse
# layer selects blocks by, a recurrent state that does not grow
CACHE_KINDS = {"cached_key": "kv", "cached_value": "kv",
               "compressed_key": "compressed", "state": "state"}


def leaf_kind(path) -> str:
    """``kv``, ``compressed`` or ``state`` for a cache leaf's tree path
    (``other`` for a name :data:`CACHE_KINDS` does not know)."""
    name = getattr(path[-1], "key", getattr(path[-1], "name", ""))
    return CACHE_KINDS.get(str(name), "other")


class DecodeEngine:
    """Model programs + the slot cache for one replica."""

    def __init__(self, model, params, num_slots: int, name: str = "r0"):
        if not getattr(model, "causal", True):
            raise ValueError("hvd.serve() needs a causal (decoder) model")
        self.name = name
        self.num_slots = int(num_slots)
        self.max_seq = int(model.max_seq)
        self.vocab_size = int(model.vocab_size)
        self._params = params
        self._model = model.clone(decode=True, remat=False,
                                  attention_fn=None)
        # a model with block-sparse layers selects key blocks for prompts
        # past this length (the ``sparse`` attribute of ``engine.prefill``)
        self._dense_len = getattr(model, "dense_len", None)
        self._cache = self._allocate_cache()
        self._prefill_fns: Dict[int, object] = {}  # guarded-by: <replica-thread>
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._decode_compiled = False
        # program kind -> did its first call consume the cache it was
        # handed (a runtime may decline a donation and copy instead);
        # written once per kind by the replica thread, read by stats()
        self._donated: Dict[str, bool] = {}
        self._lock = witness.make_lock("DecodeEngine._lock")
        self._compiles: Dict[str, int] = {}      # guarded-by: _lock
        self.decode_steps = 0
        self.step_ms_ewma = 0.0
        with _engines_lock:
            _engines.add(self)
        _KV_BYTES.labels(replica=self.name).set(self.cache_bytes())

    # -- cache -------------------------------------------------------------
    def _cache_shapes(self):
        """The decode program's cache pytree as shapes (``eval_shape``:
        nothing compiles, nothing is allocated)."""
        tokens = jax.ShapeDtypeStruct((self.num_slots, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((self.num_slots,), jnp.int32)
        _, shapes = jax.eval_shape(
            lambda p, t, q: self._model.apply(
                {"params": p}, t, positions=q, train=False,
                mutable=["cache"]),
            self._params, tokens, pos)
        return shapes["cache"]

    def _allocate_cache(self):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_shapes())

    def cache_bytes(self) -> int:
        return sum(self.cache_bytes_by_kind().values())

    def cache_bytes_by_kind(self) -> Dict[str, int]:
        """Resident cache bytes by kind of leaf (:func:`leaf_kind`)."""
        out = {kind: 0 for kind in CACHE_KINDS.values()}
        for path, x in jax.tree_util.tree_leaves_with_path(self._cache):
            kind = leaf_kind(path)
            out[kind] = out.get(kind, 0) \
                + int(np.prod(x.shape)) * x.dtype.itemsize
        return out

    # -- programs ----------------------------------------------------------
    def _note_compile(self, program: str) -> None:
        _COMPILES.labels(program=program).inc()
        with self._lock:
            self._compiles[program] = self._compiles.get(program, 0) + 1

    def compiles_total(self) -> int:
        with self._lock:
            return sum(self._compiles.values())

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = jax.jit(self._prefill_impl, donate_argnums=(1,))
            self._prefill_fns[bucket] = fn
            self._note_compile(f"prefill_{bucket}")
        return fn

    def _run_donating(self, kind: str, fn, *args):
        """Call a program whose second argument is the (donated) cache
        and rebind ``self._cache`` to its first result; the first call
        of each kind records whether the old leaves were consumed."""
        old = None if kind in self._donated else jax.tree.leaves(self._cache)
        self._cache, *rest = fn(self._params, self._cache, *args)
        if old is not None:
            self._donated[kind] = all(x.is_deleted() for x in old)
        return rest

    def _prefill_impl(self, params, cache, tokens, prompt_len, slot):
        # batch-1 run over the padded prompt builds a fresh (1, max_seq)
        # cache (flax creates the zero cache inside the traced apply); the
        # model is told the true length, so that a recurrent state is the
        # state after the prompt and not after the padding, and applies
        # its head to the last prompt row alone: logits is (1, 1, vocab)...
        logits, mutated = self._model.apply(
            {"params": params}, tokens,
            positions=jnp.zeros((1,), jnp.int32), lengths=prompt_len[None],
            train=False, mutable=["cache"])
        # ...written into the slot row at a traced index (in place: the
        # big cache is donated), so every prompt of this bucket reuses
        # one program regardless of slot
        cache = jax.tree.map(
            lambda big, one: jax.lax.dynamic_update_index_in_dim(
                big, one[0], slot, axis=0), cache, mutated["cache"])
        last = logits[0, 0]
        return cache, jnp.argmax(last).astype(jnp.int32), \
            jnp.max(jnp.abs(last))

    def _decode_impl(self, params, cache, tokens, positions):
        logits, mutated = self._model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions, train=False, mutable=["cache"])
        step_logits = logits[:, 0, :]
        return (mutated["cache"],
                jnp.argmax(step_logits, axis=-1).astype(jnp.int32),
                jnp.max(jnp.abs(step_logits), axis=-1))

    # -- serving ops -------------------------------------------------------
    def prefill(self, slot: int, prompt: List[int]) -> Tuple[int, float]:
        """Run the prompt through the bucketed prefill program, filling
        ``slot``'s cache rows. Returns (first generated token id,
        max |logit|) — the first token comes from prefill itself."""
        if not 0 < len(prompt) <= self.max_seq:
            # callers (ServeHandle.submit, Replica._reject) screen this
            # out; fail loudly rather than let the padded copy below
            # raise an opaque broadcast error inside a replica thread
            raise ValueError(
                f"prefill: prompt length {len(prompt)} outside "
                f"(0, max_seq={self.max_seq}]")
        bucket = prompt_bucket(len(prompt), self.max_seq)
        sparse = self._dense_len is not None \
            and len(prompt) > self._dense_len
        with tracing.span("engine.prefill", bucket=bucket,
                          prompt_len=len(prompt), slot=slot, sparse=sparse):
            with tracing.span("engine.prefill.dispatch"):
                fn = self._prefill_fn(bucket)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :len(prompt)] = prompt
                token, max_abs = self._run_donating(
                    "prefill", fn, jnp.asarray(padded),
                    jnp.int32(len(prompt)), jnp.int32(slot))
            with tracing.span("engine.prefill.wait"):   # blocked on the device
                return int(token), float(max_abs)

    def decode(self, slots: List[int], tokens: List[int],
               positions: List[int]) -> Tuple[List[int], List[float]]:
        """One decode step over ALL cache rows (fixed shape — the one
        compiled decode program). Active rows get their real token and
        position; inactive rows run token 0 at position 0, whose cache
        write lands where the next prefill overwrites it."""
        if not self._decode_compiled:
            self._decode_compiled = True
            self._note_compile("decode")
        with tracing.span("engine.decode", rows=len(slots)):
            return self._decode(slots, tokens, positions)

    def _decode(self, slots, tokens, positions):
        with tracing.span("engine.decode.prep"):
            step_tokens = np.zeros((self.num_slots, 1), np.int32)
            step_pos = np.zeros((self.num_slots,), np.int32)
            for s, t, p in zip(slots, tokens, positions):
                if p >= self.max_seq:
                    # admission caps max_tokens so no write lands past the
                    # cache (batcher.ActiveRequest); overrunning silently
                    # would overwrite the last KV row and serve garbage
                    raise ValueError(
                        f"decode: slot {s} position {p} >= max_seq "
                        f"{self.max_seq} (admission cap violated)")
                step_tokens[s, 0] = t
                step_pos[s] = p
        start = time.monotonic()
        with tracing.span("engine.decode.dispatch"):
            ids, max_abs = self._run_donating(
                "decode", self._decode_fn, jnp.asarray(step_tokens),
                jnp.asarray(step_pos))
        with tracing.span("engine.decode.wait"):   # blocked on the device
            ids = np.asarray(ids)
            max_abs = np.asarray(max_abs)
        ms = (time.monotonic() - start) * 1000.0
        self.decode_steps += 1
        self.step_ms_ewma = (ms if self.decode_steps == 1
                             else 0.9 * self.step_ms_ewma + 0.1 * ms)
        return ([int(ids[s]) for s in slots],
                [float(max_abs[s]) for s in slots])

    def stats(self) -> dict:
        with self._lock:
            compiles = dict(self._compiles)
        return {"compiles": compiles,
                "compiles_total": sum(compiles.values()),
                "decode_steps": self.decode_steps,
                "decode_step_ms_ewma": round(self.step_ms_ewma, 3),
                "cache_bytes": self.cache_bytes(),
                "cache_bytes_by_kind": self.cache_bytes_by_kind(),
                "cache_donated": (self._donated.get("prefill", False)
                                  and self._donated.get("decode", False)),
                "slots": self.num_slots}
