"""Per-slot KV-cache management + the serving program caches.

:class:`DecodeEngine` owns everything jax about one replica:

* the decode clone of the user's model (``model.clone(decode=True)`` —
  same params, plus a ``cache`` variable collection: for a softmax layer
  ``(slots, heads, head_dim, max_seq)`` key/value tensors, positions
  last, the layout attention reads; for a recurrent layer its state,
  which has no position axis);
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
  warm: zero steady-state compiles;
* for a model whose prefill can resume from its cache
  (``resumable_prefill``: layers that carry a state and nothing with a
  position axis), two programs of one shape in place of those:
  a prompt is cut into pieces of :data:`PREFILL_CHUNK` tokens, enqueued
  back to back. ``prefill_chunk`` (every piece but the last) reads the
  slot's row of every leaf - zeros for the first piece - continues the
  model from it at the piece's offset and writes the row back, with no
  head; ``prefill_last`` does the same with the true length of the last
  piece and the head on its last true row. A prompt pads to the next
  piece, not to the next power of two, and a call stays one call: one
  pending result, one ``engine.prefill`` span (``chunks``).

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
values, compressed keys and float32 recurrent states side by side, or
states alone - a model of power-retention layers has no leaf with a
position axis at all, and its decode step rewrites its whole cache
(:data:`CACHE_KINDS`, ``cache_bytes_by_kind``). A latent-attention
layer keeps one latent and one rotary key a position and nothing a head
(kind ``latent``). A window layer keeps its keys and values in a ring of
window-many positions (whole lane tiles of them), position ``p`` in
column ``p mod ring``, and not in ``max_seq`` (kind ``ring``): a prefill
leaves the prompt's last positions there and a decode step writes over
the oldest column, both inside the model, so that a slot's row of a ring
leaf is written and replaced like any other row. A state-space layer
keeps a float32 state (``ssm_state``, kind ``state``) and the last rows
before its convolution (``conv_state``, kind ``conv``: a tail of three
rows, a state of its own that neither grows nor is float32), beside a
full layer's rows in one slot: a prefill overwrites both with what the
prompt's true tokens leave. Nothing here asks a
leaf for more than the slot axis,
with one exception: a leaf of kind ``counter`` (an expert layer's
``expert_counts``) is a running count and no slot's row, so a prefill
adds its fresh counts to it where it overwrites a row of every other
leaf; it rides in the donated cache, no step reads it back, and
``stats()`` alone copies it to the host. A model that counts
(``counts_active_rows``) is told a decode step's ``active`` rows, so
that rows without a request are not counted. A recurrence is not indifferent to padding,
so a prefill hands the model the true ``lengths``: the state it leaves
is the state after the prompt, and a prefill overwrites every leaf's row
of its slot, the state included. A row that is not active still runs
(token 0 at position 0, every step): its keys land where the next
prefill overwrites them, and its state is a gated running sum of one
token's features, which stays finite, until the next prefill replaces
it.
With ``lengths`` the model applies its head to the last prompt row
alone - the (bucket, vocab) logits never exist.

Sampling is greedy (argmax in-graph; only the winning token ids leave
the device each step, plus one max-|logit| scalar per slot for the
integrity guard).

The token FEED stays on the device: a ``(slots,)`` int32 array beside
the cache, donated with it. A prefill writes its first token into its
slot's entry, the decode step reads every active row's token from the
feed and writes its argmax back, so no step waits for the ids of the one
before it to cross to the host and back. What the host sends a decode
step is one int32 a row: the position, or -1 for a row that is not
active (known by count, without reading an id).

Every call therefore has two halves. :meth:`DecodeEngine.prefill` and
:meth:`DecodeEngine.decode` launch the program and return a
:class:`Pending` result whose arrays are still on the device (their copy
to the host is started at once); its ``collect()`` blocks until they are
here, and its ``ready()`` says without blocking whether the device has
finished them (the ``starved`` flag of the loop's ``serve.step`` and
the ``ready`` of the ``wait`` spans). The serving loop enqueues step k+1
before it collects step k (serve/replica.py). A pending result also unpacks like the tuple it
stands for, collecting first, so ``token, max_abs = engine.prefill(...)``
and ``ids, max_abs = engine.decode(...)`` are the blocking calls they
always were, for tests and tools.
"""

from __future__ import annotations

import re
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu import tracing
from horovod_tpu.analysis import witness
from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.ops.pallas import (decode_attention,
                                    grouped_decode_attention,
                                    latent_attention, sparse_attention)
from horovod_tpu.ops.pallas._backend import kernels_in
from horovod_tpu.runtime.fusion_buffer import bucket_elems

# prompt-length bucket quantum (tokens). Not a knob: the policy is the
# runtime's, only the unit differs (tokens, not bytes).
PREFILL_BUCKET_QUANTUM = 16
# tokens a piece of a prompt, where the model's prefill can resume from
# the slot's cache (``resumable_prefill``): a prompt pads to the next
# piece, not to the next bucket. A multiple of a recurrent mixer's own
# chunk (256), and rows enough that a piece's matrix products stay bound
# by compute beside the layers' weights it reads again (PERF.md section 6
# has the readings at 512, 1,024 and 2,048).
PREFILL_CHUNK = 1024

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
# layer selects blocks by, a recurrent state (and a normalised one's
# running sum of features) that does not grow. A model need not have
# every kind: one of recurrent layers alone holds states and nothing else.
# A latent-attention layer's two leaves (one latent and one rotary key a
# position, nothing a head) are ``latent``; a window layer's keys and
# values, a ring of window-many positions, are ``ring``; a state-space
# layer's state is ``state`` like the other recurrent states and the tail
# of its convolution ``conv``; an expert layer's
# running counts are ``counter``: no slot's row (module docstring)
CACHE_KINDS = {"cached_key": "kv", "cached_value": "kv",
               "compressed_key": "compressed", "state": "state",
               "state_norm": "state", "ssm_state": "state",
               "conv_state": "conv", "latent": "latent",
               "rope_key": "latent", "ring_key": "ring",
               "ring_value": "ring", "expert_counts": "counter"}


def leaf_kind(path) -> str:
    """``kv``, ``compressed``, ``state``, ``conv``, ``latent``, ``ring``
    or ``counter`` for a cache leaf's tree path (``other`` for a name :data:`CACHE_KINDS` does
    not know)."""
    name = getattr(path[-1], "key", getattr(path[-1], "name", ""))
    return CACHE_KINDS.get(str(name), "other")


class Pending:
    """The results of an enqueued program: on the device until
    :meth:`collect` has them. ``on_host`` is what the serving loop looks
    at: false means it may enqueue more before it collects. Unpacking a
    pending result collects it (the blocking form of the call)."""

    on_host = False
    _result = None

    def ready(self) -> bool:
        """Has the program finished on the device (nothing blocks)? What
        ``serve.step``'s ``starved`` and the ``wait`` spans' ``ready``
        are read from."""
        return self._result is not None or self._max_abs.is_ready()

    def collect(self) -> tuple:
        if self._result is None:
            self._result = self._read()
        return self._result

    def __iter__(self):
        return iter(self.collect())


class PendingPrefill(Pending):
    """``collect()`` -> (first generated token id, max |logit|).
    ``max_abs`` is the program's last result: the scalar, or (max |logit|,
    live share of key blocks) where its sparse layers ran their prompt
    kernel, which then goes to ``sparse.live_block_share`` and the span."""

    def __init__(self, token, max_abs, t0: float, attrs: dict):
        self._token, self._max_abs = token, max_abs
        self._t0, self._attrs = t0, attrs

    def _read(self) -> Tuple[int, float]:
        # ``ready``: the value was there when the wait began, so the host
        # was the later of the two
        with tracing.span("engine.prefill.wait",   # blocked on the device
                          ready=int(self.ready())):
            token, readings = int(self._token), np.asarray(self._max_abs)
        if readings.ndim:
            share = float(readings[1])
            sparse_attention.note_live_block_share(share)
            self._attrs["live_block_share"] = round(share, 4)
        out = token, float(readings.flat[0])
        # the start of the dispatch to the first token on the host
        tracing.record("engine.prefill", self._t0, time.time() - self._t0,
                       **self._attrs)
        return out


class PendingDecode(Pending):
    """``collect()`` -> (ids, max |logit|s) of the step's rows."""

    def __init__(self, engine: "DecodeEngine", slots: List[int], ids,
                 max_abs, t0: float, number: int, attrs: dict):
        self._engine, self._slots = engine, slots
        self._ids, self._max_abs = ids, max_abs
        self._t0, self._number, self._attrs = t0, number, attrs

    def _read(self) -> Tuple[List[int], List[float]]:
        # ``ahead``: a later decode step was already enqueued when the
        # wait began, so the device has work while the host reads;
        # ``ready``: this step's ids were there already (the host is late)
        engine = self._engine
        ahead = int(engine.decodes_enqueued > self._number)
        with tracing.span("engine.decode.wait", ahead=ahead,
                          ready=int(self.ready())):
            ids = np.asarray(self._ids)          # blocked on the device
            max_abs = np.asarray(self._max_abs)
        # the start of the prep to the ids on the host
        seconds = time.time() - self._t0
        tracing.record("engine.decode", self._t0, seconds,
                       rows=len(self._slots), **self._attrs)
        engine._note_decode(seconds * 1000.0, ahead)
        return ids[self._slots].tolist(), max_abs[self._slots].tolist()


class DecodeEngine:
    """Model programs + the slot cache and token feed for one replica."""

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
        # a model whose expert layers count what they route is told a
        # decode step's active rows
        self._counts = bool(getattr(model, "counts_active_rows", False))
        # a model whose prefill continues from the slot's cache has its
        # prompts run in pieces of PREFILL_CHUNK, through two programs
        self._resumes = bool(getattr(model, "resumable_prefill", False))
        self._chunk = min(PREFILL_CHUNK, self.max_seq)
        # does the decode program read its key/value rows through
        # ops/pallas/decode_attention (set by _cache_shapes, from the
        # program itself), and the lane tiles its steps read of a leaf
        # over the tiles of all rows (stats()["decode_kv_read_share"]);
        # and does that kernel write the new columns itself: no
        # kv_cache_write beside it (stats()["decode_write_fused"]; None
        # where the program holds no such kernel)
        self._reads_live_tiles = False
        self._write_fused = None
        # do a block-sparse model's prefill programs attend through
        # ops/pallas/sparse_attention (stats()["prefill_sparse_kernel"];
        # None without such a layer or before a prefill was traced): set
        # where a program is traced, from what its layers handed up
        self._sparse_kernel = None
        self.kv_tiles_read = 0
        self.kv_tiles_held = 0
        # the same for a latent cache read through ops/pallas/
        # latent_attention (its tiles are wider), and the positions its
        # steps attended (stats()["decode_positions_read"]): what a
        # roofline of that kernel counts bytes by
        self._reads_live_latents = False
        self.positions_read = 0
        # the same for key/value leaves read through ops/pallas/
        # grouped_decode_attention; and, for a model whose layers keep
        # different kinds of cache (``decode_positions_by_kind``), the
        # positions its steps attended by the kind of leaf they were
        # read from (stats()["decode_positions_by_kind"])
        self._reads_live_groups = False
        by_kind = getattr(model, "decode_positions_by_kind", None)
        self._positions_by_kind = by_kind \
            if by_kind and by_kind(np.zeros((1,), np.int64)) else None
        self.positions_by_kind: Dict[str, int] = {}
        self._cache = self._allocate_cache()
        # bytes of the recurrent states (kind ``state``) all the slots'
        # rows hold: what a decode step, which runs every row, rewrites,
        # and a prefill a slot's share of (the spans' ``state_bytes``)
        self._state_bytes = self.cache_bytes_by_kind()["state"]
        # the next token of every row, on the device (module docstring)
        self._feed = jnp.zeros((self.num_slots,), jnp.int32)
        # by bucket, or by name where a prompt runs in pieces
        self._prefill_fns: Dict[object, object] = {}  # guarded-by: <replica-thread>
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(1, 2))
        self._decode_compiled = False
        # prefill programs enqueued, the positions they computed (padding
        # and all) and the prompts' own tokens: positions / tokens is the
        # padding a deployment pays
        self.prefill_chunks = 0
        self.prefill_positions = 0
        self.prefill_tokens = 0
        # program kind -> did its first call consume the cache it was
        # handed (a runtime may decline a donation and copy instead);
        # written once per kind by the replica thread, read by stats()
        self._donated: Dict[str, bool] = {}
        self._lock = witness.make_lock("DecodeEngine._lock")
        # held while a program is enqueued and the cache rebound, and
        # while stats() copies the counters out of it: a leaf that is
        # being read is not donated under the reader
        self._cache_lock = witness.make_lock("DecodeEngine._cache_lock")
        # seconds the replica thread waited for it since a dispatch span
        # last set this to zero (the span's ``lock_ms``)
        self._lock_wait_s = 0.0
        self._compiles: Dict[str, int] = {}      # guarded-by: _lock
        # decode steps enqueued; collected; collected with their successor
        # already enqueued (Replica.stats()["lookahead_share"])
        self.decodes_enqueued = 0
        self.decode_steps = 0
        self.decodes_ahead = 0
        self.step_ms_ewma = 0.0
        with _engines_lock:
            _engines.add(self)
        _KV_BYTES.labels(replica=self.name).set(self.cache_bytes())

    # -- cache -------------------------------------------------------------
    def _cache_shapes(self):
        """The decode program's cache pytree as shapes (one abstract
        trace: nothing compiles, nothing is allocated). The same trace
        says whether the program holds the decode-attention kernel."""
        tokens = jax.ShapeDtypeStruct((self.num_slots, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((self.num_slots,), jnp.int32)
        program, (_, shapes) = jax.make_jaxpr(
            lambda p, t, q: self._model.apply(
                {"params": p}, t, positions=q, train=False,
                mutable=["cache"]), return_shape=True)(
                    self._params, tokens, pos)
        kernels = kernels_in(program)
        self._reads_live_tiles = "decode_attention" in kernels
        self._write_fused = ("kv_cache_write" not in kernels
                             if self._reads_live_tiles else None)
        self._reads_live_latents = "latent_decode_attention" in kernels
        self._reads_live_groups = "grouped_decode_attention" in kernels
        return shapes["cache"]

    def _allocate_cache(self):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_shapes())

    def cache_bytes(self) -> int:
        return sum(self.cache_bytes_by_kind().values())

    def cache_bytes_by_kind(self) -> Dict[str, int]:
        """Resident cache bytes by kind of leaf (:func:`leaf_kind`):
        ``kv``, ``compressed`` and ``state`` always, another kind where
        the model has such a leaf."""
        out = {"kv": 0, "compressed": 0, "state": 0}
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

    def _program(self, key, impl, name: str):
        fn = self._prefill_fns.get(key)
        if fn is None:
            fn = self._prefill_fns[key] = jax.jit(impl,
                                                  donate_argnums=(1, 2))
            self._note_compile(name)
        return fn

    def _prefill_fn(self, bucket: int):
        return self._program(bucket, self._prefill_impl, f"prefill_{bucket}")

    def _piece_fn(self, program: str):
        """``prefill_chunk`` or ``prefill_last``, the two programs of a
        prompt run in pieces; one shape each, whatever the prompt."""
        return self._program(
            program, {"prefill_chunk": self._prefill_chunk_impl,
                      "prefill_last": self._prefill_last_impl}[program],
            program)

    def _run_donating(self, kind: str, fn, *args):
        """Enqueue a program whose second and third arguments are the
        (donated) cache and feed, rebind both to its first two results
        and start the others' copy to the host; the first call of each
        kind records whether the old leaves were consumed. The rebinding
        is what orders the programs on the device: each takes the
        results of the one before it, whether or not the host has read
        anything."""
        old = None if kind in self._donated \
            else jax.tree.leaves((self._cache, self._feed))
        t0 = time.perf_counter()
        with self._cache_lock:
            self._lock_wait_s += time.perf_counter() - t0
            self._cache, self._feed, *rest = fn(
                self._params, self._cache, self._feed, *args)
        if old is not None:
            self._donated[kind] = all(x.is_deleted() for x in old)
        for x in rest:
            x.copy_to_host_async()
        return rest

    def _prefill_impl(self, params, cache, feed, tokens, prompt_len, slot):
        # batch-1 run over the padded prompt builds a fresh (1, max_seq)
        # cache (flax creates the zero cache inside the traced apply); the
        # model is told the true length, so that a recurrent state is the
        # state after the prompt and not after the padding, and applies
        # its head to the last prompt row alone: logits is (1, 1, vocab)...
        logits, mutated = self._model.apply(
            {"params": params}, tokens,
            positions=jnp.zeros((1,), jnp.int32), lengths=prompt_len[None],
            train=False, mutable=["cache", "kernel_stats"])
        # a sparse layer's prompt kernel hands up the share of key blocks
        # it ran; it rides to the host beside max |logit|
        shares = jax.tree.leaves(mutated.get("kernel_stats", {}))
        if self._dense_len is not None:
            self._sparse_kernel = bool(shares)
        # ...written into the slot row at a traced index (in place: the
        # big cache is donated), so every prompt of this bucket reuses
        # one program regardless of slot; a counter is no slot's row: the
        # prompt's counts are added to it
        cache = jax.tree_util.tree_map_with_path(
            lambda path, big, one: big + one
            if leaf_kind(path) == "counter"
            else jax.lax.dynamic_update_index_in_dim(
                big, one[0], slot, axis=0), cache, mutated["cache"])
        last = logits[0, 0]
        token = jnp.argmax(last).astype(jnp.int32)
        # the slot's first decode step reads its token from the feed
        feed = jax.lax.dynamic_update_index_in_dim(feed, token, slot, axis=0)
        max_abs = jnp.max(jnp.abs(last))
        if shares:
            max_abs = jnp.stack([max_abs, jnp.mean(jnp.stack(shares))])
        return cache, feed, token, max_abs

    def _piece(self, params, cache, tokens, offset, length, slot, output):
        """One piece of ``slot``'s prompt, ``tokens`` (1, PREFILL_CHUNK)
        of which ``length`` are the prompt's, from position ``offset``:
        the model continues from the slot's row of every leaf - a row of
        zeros for the prompt's first piece, whatever the slot's last
        request left - and the row it leaves is written back in place. A
        counter is no slot's row: the model adds to it as it stands."""
        def row(path, big):
            if leaf_kind(path) == "counter":
                return big
            one = jax.lax.dynamic_index_in_dim(big, slot, axis=0)
            return jnp.where(offset == 0, 0, one)

        out, mutated = self._model.apply(
            {"params": params,
             "cache": jax.tree_util.tree_map_with_path(row, cache)},
            tokens, positions=offset[None], lengths=length[None],
            train=False, mutable=["cache"], output=output)
        cache = jax.tree_util.tree_map_with_path(
            lambda path, big, one: one if leaf_kind(path) == "counter"
            else jax.lax.dynamic_update_index_in_dim(
                big, one[0], slot, axis=0), cache, mutated["cache"])
        return out, cache

    def _prefill_chunk_impl(self, params, cache, feed, tokens, offset, slot):
        # a whole piece with more of the prompt to come: the state after
        # it, and no head (nobody reads a token before the prompt's end)
        _, cache = self._piece(
            params, cache, tokens, offset,
            jnp.asarray(tokens.shape[1], jnp.int32), slot, "hidden")
        return cache, feed

    def _prefill_last_impl(self, params, cache, feed, tokens, offset, length,
                           slot):
        # the prompt's last piece (or its only one), the head on its last
        # true row, and the rest as _prefill_impl has it
        logits, cache = self._piece(params, cache, tokens, offset, length,
                                    slot, "logits")
        last = logits[0, 0]
        token = jnp.argmax(last).astype(jnp.int32)
        feed = jax.lax.dynamic_update_index_in_dim(feed, token, slot, axis=0)
        return cache, feed, token, jnp.max(jnp.abs(last))

    def _decode_impl(self, params, cache, feed, positions):
        # a row the host sends -1 for is not active: it runs token 0 at
        # position 0 and leaves its feed entry alone
        active = positions >= 0
        tokens = jnp.where(active, feed, 0)[:, None]
        counted = {"active": active} if self._counts else {}
        logits, mutated = self._model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=jnp.maximum(positions, 0), train=False,
            mutable=["cache"], **counted)
        step_logits = logits[:, 0, :]
        ids = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
        return (mutated["cache"], jnp.where(active, ids, feed), ids,
                jnp.max(jnp.abs(step_logits), axis=-1))

    # -- serving ops -------------------------------------------------------
    def prefill(self, slot: int, prompt: List[int]) -> PendingPrefill:
        """Launch the prompt's prefill, which fills ``slot``'s cache rows
        and puts the first generated token (it comes from prefill
        itself) into the slot's feed entry: one program of the prompt's
        bucket, or, where the model's prefill resumes from its cache,
        the prompt's pieces of ``PREFILL_CHUNK`` tokens back to back
        through ``prefill_chunk`` and, the last one, ``prefill_last``.
        The result collects to (first generated token id, max |logit|)."""
        if not 0 < len(prompt) <= self.max_seq:
            # callers (ServeHandle.submit, Replica._reject) screen this
            # out; fail loudly rather than let the padded copy below
            # raise an opaque broadcast error inside a replica thread
            raise ValueError(
                f"prefill: prompt length {len(prompt)} outside "
                f"(0, max_seq={self.max_seq}]")
        sparse = self._dense_len is not None \
            and len(prompt) > self._dense_len
        if self._resumes:
            chunk = self._chunk
            chunks = -(-len(prompt) // chunk)
            bucket = chunks * chunk
        else:
            chunks, bucket = 1, prompt_bucket(len(prompt), self.max_seq)
        t0 = time.time()
        with tracing.span("engine.prefill.dispatch") as dispatch:
            self._lock_wait_s = 0.0
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            # numpy scalars ride along with the call; a jnp scalar would
            # be a program of its own before it
            if self._resumes:
                last = bucket - chunk
                for at in range(0, last, chunk):
                    self._run_donating(
                        "prefill", self._piece_fn("prefill_chunk"),
                        padded[:, at:at + chunk], np.int32(at),
                        np.int32(slot))
                token, max_abs = self._run_donating(
                    "prefill", self._piece_fn("prefill_last"),
                    padded[:, last:], np.int32(last),
                    np.int32(len(prompt) - last), np.int32(slot))
            else:
                token, max_abs = self._run_donating(
                    "prefill", self._prefill_fn(bucket), padded,
                    np.int32(len(prompt)), np.int32(slot))
            dispatch.set(lock_ms=round(self._lock_wait_s * 1e3, 4))
        self.prefill_chunks += chunks
        self.prefill_positions += bucket
        self.prefill_tokens += len(prompt)
        return PendingPrefill(token, max_abs, t0, dict(
            bucket=bucket, chunks=chunks, prompt_len=len(prompt), slot=slot,
            sparse=sparse, state_bytes=self._state_bytes // self.num_slots))

    def decode(self, slots: List[int], tokens: Optional[List[int]],
               positions: List[int]) -> PendingDecode:
        """Launch one decode step over ALL cache rows (fixed shape — the
        one compiled decode program). Active rows take their token from
        the feed at their real position; inactive rows run token 0 at
        position 0, whose cache write lands where the next prefill
        overwrites it. ``tokens`` is ``None`` where the rows' tokens are
        in the feed (the serving loop, whose last programs put them
        there); a caller that has them on the host instead passes them,
        and they replace the feed first (one transfer more). The result
        collects to (ids, max |logit|s) of ``slots``."""
        if tokens is not None:
            feed = np.zeros((self.num_slots,), np.int32)
            feed[slots] = tokens
            self._feed = jnp.asarray(feed)
        if not self._decode_compiled:
            self._decode_compiled = True
            self._note_compile("decode")
        t0 = time.time()
        with tracing.span("engine.decode.prep"):
            step_pos = np.full((self.num_slots,), -1, np.int32)
            step_pos[slots] = positions
            if slots and step_pos.max() >= self.max_seq:
                # admission caps max_tokens so no write lands past the
                # cache (batcher.ActiveRequest); overrunning silently
                # would overwrite the last KV row and serve garbage
                slot = int(step_pos.argmax())
                raise ValueError(
                    f"decode: slot {slot} position {step_pos[slot]} >= "
                    f"max_seq {self.max_seq} (admission cap violated)")
            attrs = {"state_bytes": self._state_bytes}
            if self._reads_live_tiles or self._reads_live_groups:
                # what the kernel will fetch: a row that is not active
                # runs at position 0 and costs one tile
                kernel = decode_attention if self._reads_live_tiles \
                    else grouped_decode_attention
                read, held = kernel.live_tiles(step_pos, self.max_seq)
                self.kv_tiles_read += read
                self.kv_tiles_held += held
                attrs["kv_read_share"] = round(read / held, 4)
            elif self._reads_live_latents:
                read, held, attended = latent_attention.live_tiles(
                    step_pos, self.max_seq)
                self.kv_tiles_read += read
                self.kv_tiles_held += held
                self.positions_read += attended
                attrs["kv_read_share"] = round(read / held, 4)
            by_kind = self._positions_by_kind(np.maximum(step_pos, 0)) \
                if self._positions_by_kind else {}
            for kind, attended in by_kind.items():
                self.positions_by_kind[kind] = \
                    self.positions_by_kind.get(kind, 0) + attended
                attrs[f"{kind}_positions_read"] = attended
        with tracing.span("engine.decode.dispatch") as dispatch:
            self._lock_wait_s = 0.0
            ids, max_abs = self._run_donating("decode", self._decode_fn,
                                              step_pos)
            dispatch.set(lock_ms=round(self._lock_wait_s * 1e3, 4))
        self.decodes_enqueued += 1
        return PendingDecode(self, list(slots), ids, max_abs, t0,
                             self.decodes_enqueued, attrs)

    def _note_decode(self, ms: float, ahead: int) -> None:
        self.decode_steps += 1
        self.decodes_ahead += ahead
        self.step_ms_ewma = (ms if self.decode_steps == 1
                             else 0.9 * self.step_ms_ewma + 0.1 * ms)

    def expert_counts(self) -> Optional[np.ndarray]:
        """The expert layers' running counts, (layers, 3, experts)
        uint32 in layer order (``models/hybrid.py`` ``RoutedExperts``:
        pairs routed by both programs, decode steps that hit the expert,
        decode steps); ``None`` for a model that counts nothing. The one
        place a counter is read. The counts run modulo 2**32: subtract
        two readings as uint32. Under the lock only a copy on the device
        is enqueued (no program: nothing compiles), behind the step in
        flight; this thread then waits for it without holding the
        engine's next dispatch."""
        return self._expert_counts()[0]

    def _expert_counts(self) -> Tuple[Optional[np.ndarray], float]:
        """:meth:`expert_counts`, and the seconds this thread waited for
        the cache's lock (a dispatch of the replica's thread held it)."""
        if not self._counts:
            return None, 0.0
        t0 = time.perf_counter()
        with self._cache_lock:
            waited = time.perf_counter() - t0
            found = [(jax.tree_util.keystr(path),
                      jax.device_put(x, may_alias=False)) for path, x
                     in jax.tree_util.tree_leaves_with_path(self._cache)
                     if leaf_kind(path) == "counter"]
        by_layer = sorted(found, key=lambda kv: [
            int(n) for n in re.findall(r"\d+", kv[0])])
        return np.stack([np.asarray(x) for _, x in by_layer]), waited

    def stats(self) -> dict:
        """The engine's counters, as one ``engine.stats`` span on the
        caller's thread: a reader of the ring sees what ran beside the
        replica's thread, and ``lock_ms`` is this call's own wait for
        the cache's lock."""
        with tracing.span("engine.stats") as span:
            counts, waited = self._expert_counts()
            span.set(lock_ms=round(waited * 1e3, 4))
            with self._lock:
                compiles = dict(self._compiles)
            return {"compiles": compiles,
                    "compiles_total": sum(compiles.values()),
                    "decode_steps": self.decode_steps,
                    "decode_step_ms_ewma": round(self.step_ms_ewma, 3),
                    # prefill programs enqueued, positions they computed and
                    # the prompts' own tokens (positions / tokens: the padding)
                    "prefill_chunks": self.prefill_chunks,
                    "prefill_positions": self.prefill_positions,
                    "prefill_tokens": self.prefill_tokens,
                    "cache_bytes": self.cache_bytes(),
                    "cache_bytes_by_kind": self.cache_bytes_by_kind(),
                    "cache_donated": (self._donated.get("prefill", False)
                                      and self._donated.get("decode", False)),
                    # lane tiles of a key/value leaf the decode steps read
                    # over the tiles of all rows; None where the decode
                    # program reads whole rows (no decode-attention kernel
                    # in it), holds no keys or values at all, or has not run
                    "decode_kv_read_share": (
                        round(self.kv_tiles_read / self.kv_tiles_held, 4)
                        if self.kv_tiles_held else None),
                    # the attention kernel writes the step's new key and
                    # value columns itself (the decode program holds no
                    # kv_cache_write); None where it holds no such kernel
                    "decode_write_fused": self._write_fused,
                    # a block-sparse model's prefill programs attend
                    # through the prompt kernel (None: no such layer, or
                    # no prefill traced yet)
                    "prefill_sparse_kernel": self._sparse_kernel,
                    # positions the latent kernel's steps attended (a row
                    # that is not active: one), None without that kernel
                    "decode_positions_read": (self.positions_read
                                              if self._reads_live_latents
                                              else None),
                    # positions the decode steps attended, all layers of
                    # a kind together, by the kind of leaf they were read
                    # from (``kv``, ``ring``); None for a model whose
                    # layers all keep one kind
                    "decode_positions_by_kind": (
                        dict(self.positions_by_kind)
                        if self._positions_by_kind else None),
                    # (layers, 3, experts) as nested lists: pairs, decode
                    # steps that hit the expert, decode steps, each modulo
                    # 2**32 (None: the model has no expert layer)
                    "expert_counts": (None if counts is None
                                      else counts.tolist()),
                    "slots": self.num_slots}
