"""Per-slot cache management + the serving program caches.

:class:`DecodeEngine` owns everything jax about one replica. Of a model
it knows one object, ``model.serving()`` (``models/serving.py``): the
decode clone, the kind of every cache variable, four facts about its
programs. It names no layer kind, no cache variable and no kernel: a
layer kind is declared in ``models/hybrid.py``'s ``MIXERS``, a kernel's
counters beside the kernel (``ops/pallas/_backend.py``). Its own rules:

**Programs.** ONE jitted decode program over ALL slots every step: the
shape never changes (a row without a request runs token 0 at position 0,
which the next prefill overwrites), so steady-state decode never
recompiles. One jitted prefill program PER PROMPT-LENGTH BUCKET, batch 1
(``fusion_buffer.bucket_elems`` floored at the quantum: O(log(max_seq))
of them), or, where the contract says ``resumable``, two programs of one
shape that run a prompt in pieces (:meth:`DecodeEngine.prefill`).

**Donation.** Every program donates its cache argument: one cache lives
on the device and no program copies it. ``self._cache`` is rebound from
every call's result and the arrays it held are deleted, so do not hold
``engine._cache`` across a call; ``stats()["cache_donated"]`` says
whether the runtime took the donations.

**Rows.** The cache is whatever pytree the model's ``cache`` collection
declares, every leaf with the slot as axis 0; nothing here asks a leaf
for more. A prefill overwrites its slot's row of every leaf and hands
the model the true ``lengths`` (padding past them is masked where a leaf
has positions and must not enter a recurrence; the head runs on the last
true row alone, so (bucket, vocab) logits never exist). A row that is
not active still runs every step; what it leaves stays finite until the
next prefill replaces it. Two kinds of leaf the engine treats itself:
``state``, whose bytes are the spans' ``state_bytes``, and ``counter``, a
running count and no slot's row: a prefill adds to it, no step reads it
back, ``stats()`` alone copies it to the host, and a model that counts
(``wants_active``) is told a decode step's ``active`` rows.

**Feed.** Sampling is greedy, in-graph. The next token of every row
stays on the device, a ``(slots,)`` int32 array donated with the cache:
a prefill writes its first token there, a decode step reads and writes
it, and the host sends a step one int32 a row (the position, -1 for a
row that is not active) and reads back ids and one max |logit| a row.

**Blocks.** Where the contract declares a ``block_len`` over 1 (a model
that generates by diffusion over blocks) a step does not yield one token
a row. The feed is then a row's block, ``ids`` ``(slots, block_len)``
int32 and ``masked`` (the same, bool: a position's id means nothing
until it is unmasked, whatever the id), donated like the other. A step
is one PASS: every row runs its block's ``block_len`` positions (the
mask id where masked) from the block's first position, which writes the
block's keys and values over the block's own columns of the cache,
provisional until the block moves on, and sees the cache up to the
block's end. The host sends a pass two int32 a row: the block's first
position (-1: not active) and how many positions to unmask. In the graph
(scope ``unmask``): at every masked position the argmax of its logits
and that token's softmax probability in float32, and the ``count``
positions of highest probability among the row's masked ones (ties to
the lower position) take their argmax; the pass returns a row's block
with the ids it unmasked and -1 elsewhere. A count of 0 is the COMMIT
pass over a block that has no mask left: it unmasks nothing, the columns
it writes are the block's final ones, it resets the row's block to all
masked, and the host moves the row's position on by ``block_len``. So
the cache advances at a commit and at nothing else, and the step program
is one program of one shape whatever its rows are doing. A prefill fills
the prompt's whole blocks under the block-causal mask, runs no head,
yields no token (it collects to ``(None, max |hidden|)``) and writes the
row's first block into the feed: the prompt's last ``len mod block_len``
tokens, known, and masks.

**Look-ahead.** :meth:`DecodeEngine.prefill` and
:meth:`DecodeEngine.decode` launch the program and return a
:class:`Pending` result still on the device (its copy to the host
started); ``collect()`` blocks, ``ready()`` does not. The serving loop
enqueues step k+1 before it collects step k (serve/replica.py).
Unpacking a pending result collects it: ``token, max_abs =
engine.prefill(...)`` is the blocking call it always was.
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
from horovod_tpu.ops.pallas._backend import (KERNEL_STATS, SERVED_KERNELS,
                                             kernels_in)
from horovod_tpu.runtime.fusion_buffer import bucket_elems

# prompt-length bucket quantum (tokens). Not a knob: the policy is the
# runtime's, only the unit differs (tokens, not bytes).
PREFILL_BUCKET_QUANTUM = 16
# tokens a piece of a prompt, where the model's prefill can resume from
# the slot's cache (``resumable``): a prompt pads to the next
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
    """``collect()`` -> (first generated token id, max |logit|); the id is
    ``None`` where the prefill yields no token (a block model).
    ``max_abs`` is the program's last result: the scalar, or (max |logit|,
    a reading for each of ``stats``) where the program's kernels handed
    readings up (``_backend.KERNEL_STATS``'s sinks, and the span)."""

    def __init__(self, token, max_abs, t0: float, attrs: dict,
                 stats: Tuple[str, ...]):
        self._token, self._max_abs = token, max_abs
        self._t0, self._attrs, self._stats = t0, attrs, stats

    def _read(self) -> Tuple[int, float]:
        # ``ready``: the value was there when the wait began, so the host
        # was the later of the two
        with tracing.span("engine.prefill.wait",   # blocked on the device
                          ready=int(self.ready())):
            token, readings = int(self._token), np.asarray(self._max_abs)
            token = None if token < 0 else token
        if readings.ndim:
            for name, value in zip(self._stats, readings[1:].tolist()):
                KERNEL_STATS[name](value)
                self._attrs[name] = round(value, 4)
        out = token, float(readings.flat[0])
        # the start of the dispatch to the first token on the host
        tracing.record("engine.prefill", self._t0, time.time() - self._t0,
                       **self._attrs)
        return out


class PendingDecode(Pending):
    """``collect()`` -> (ids, max |logit|s) of the step's rows; of a block
    model's pass a row's ids are its block's, -1 where the pass unmasked
    nothing."""

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
        if not model.causal:
            raise ValueError("hvd.serve() needs a causal (decoder) model")
        self.name = name
        self.num_slots = int(num_slots)
        self.max_seq = int(model.max_seq)
        self.vocab_size = int(model.vocab_size)   # benchmark/tests read it
        self._params = params
        # everything the engine knows of the model (module docstring)
        contract = model.serving()
        self._model = contract.model
        self.leaf_kind = contract.leaf_kind
        self._dense_len = contract.dense_len
        self._counts = contract.wants_active
        self._resumes = contract.resumable
        self._chunk = min(PREFILL_CHUNK, self.max_seq)
        # -> stats()["decode_positions_by_kind"] (None: one kind of leaf)
        self._step_reads = contract.step_reads
        self.positions_by_kind: Dict[str, int] = {}
        # a step's tokens a row, the id fed where one is masked and the
        # positions a pass unmasks (module docstring, Blocks)
        self.block_len = int(contract.block_len)
        self._mask_id = contract.mask_id
        self.unmask = int(contract.unmask)
        if self.block_len > 1 and (
                contract.resumable
                or self.block_len >= PREFILL_BUCKET_QUANTUM
                or PREFILL_BUCKET_QUANTUM % self.block_len):
            # a model tells a pass from a prompt by its length
            raise ValueError(
                f"block_len {self.block_len}: a block model's prompt runs "
                f"as one bucket, a whole number of blocks and longer than "
                f"one (quantum {PREFILL_BUCKET_QUANTUM})")
        # active rows over the steps, those of them that unmasked nothing,
        # the tokens the steps yielded, the blocks whose columns became
        # final (one token a row-pass and no commits where block_len is 1)
        self.row_passes = 0
        self.commit_row_passes = 0
        self.tokens_unmasked = 0
        self.blocks_committed = 0
        # the tiles the steps read of a leaf over the tiles of all rows
        # (stats()["decode_kv_read_share"]) and the positions they attended
        # (["decode_positions_read"], where a kernel's roofline counts
        # bytes by them), through the readers _cache_shapes finds
        self.kv_tiles_read = 0
        self.kv_tiles_held = 0
        self.positions_read = 0
        # did a traced prefill's kernels hand readings up (None: no
        # ``dense_len``, none traced yet), and the readings' names
        self._sparse_kernel = None
        self._kernel_stats: Tuple[str, ...] = ()
        self._cache = self._allocate_cache()
        # bytes of the recurrent states (kind ``state``) all the slots'
        # rows hold: what a decode step, which runs every row, rewrites,
        # and a prefill a slot's share of (the spans' ``state_bytes``)
        self._state_bytes = self.cache_bytes_by_kind()["state"]
        # the next token of every row, or its block, on the device
        # (module docstring)
        self._feed = jnp.zeros((self.num_slots,), jnp.int32) \
            if self.block_len == 1 else {
                "ids": jnp.zeros((self.num_slots, self.block_len),
                                 jnp.int32),
                "masked": jnp.ones((self.num_slots, self.block_len), bool)}
        # by bucket, or by name where a prompt runs in pieces
        self._prefill_fns: Dict[object, object] = {}  # guarded-by: <replica-thread>
        # the kernels the prefill programs hold, as ``decode_kernels`` of
        # the decode program: each program's jaxpr is read when it is
        # first enqueued (the trace the call then reuses), so a grouped
        # product that silently went back to XLA's gathers shows here
        self.prefill_kernels: Tuple[str, ...] = ()
        self._unread: set = set()                # guarded-by: <replica-thread>
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
        says which kernels the program holds (``decode_kernels``: a silent
        fall back to whole rows shows there), the registry what to count
        of them and whether the one that attends also writes the step's
        columns (``_write_fused``; None where none does)."""
        tokens = jax.ShapeDtypeStruct((self.num_slots, self.block_len),
                                      jnp.int32)
        pos = jax.ShapeDtypeStruct((self.num_slots,), jnp.int32)
        program, (_, shapes) = jax.make_jaxpr(
            lambda p, t, q: self._model.apply(
                {"params": p}, t, positions=q, train=False,
                mutable=["cache"]), return_shape=True)(
                    self._params, tokens, pos)
        self.decode_kernels = tuple(dict.fromkeys(kernels_in(program)))
        held = [SERVED_KERNELS[name] for name in self.decode_kernels
                if name in SERVED_KERNELS]
        self._step_readers = [k.live_tiles for k in held if k.live_tiles]
        self._counts_positions = any(k.counts_positions for k in held)
        self._write_fused = not any(
            k.writes_step and not k.live_tiles for k in held) \
            if any(k.live_tiles and k.writes_step for k in held) else None
        return shapes["cache"]

    def _allocate_cache(self):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_shapes())

    def cache_bytes(self) -> int:
        return sum(self.cache_bytes_by_kind().values())

    def cache_bytes_by_kind(self) -> Dict[str, int]:
        """Resident cache bytes by kind of leaf (``leaf_kind``):
        ``kv``, ``compressed`` and ``state`` always, another kind where
        the model has such a leaf."""
        out = {"kv": 0, "compressed": 0, "state": 0}
        for path, x in jax.tree_util.tree_leaves_with_path(self._cache):
            kind = self.leaf_kind(path)
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
            self._unread.add(fn)
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
        if fn in self._unread:
            self._unread.discard(fn)
            held = kernels_in(fn.trace(self._params, self._cache,
                                       self._feed, *args).jaxpr)
            self.prefill_kernels = tuple(dict.fromkeys(
                self.prefill_kernels + tuple(held)))
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

    def _prefill_impl(self, params, cache, feed, tokens, prompt_len, slot,
                      *block):
        # batch-1 run over the padded prompt builds a fresh one-row cache
        # (zeros, inside the traced apply) and (1, 1, vocab) logits (of a
        # block model: ``prompt_len`` is the prompt's whole blocks, and no
        # head runs)...
        logits, mutated = self._model.apply(
            {"params": params}, tokens,
            positions=jnp.zeros((1,), jnp.int32), lengths=prompt_len[None],
            train=False, mutable=["cache", "kernel_stats"],
            **({"output": "hidden"} if block else {}))
        # what the layers' prompt kernels handed up, by the name it was
        # sown under; the layers' mean rides beside max |logit|
        handed: Dict[str, list] = {}
        for path, x in jax.tree_util.tree_leaves_with_path(
                mutated.get("kernel_stats", {})):
            name = [k.key for k in path if hasattr(k, "key")][-1]
            handed.setdefault(name, []).append(x)
        if self._dense_len is not None:
            self._sparse_kernel = bool(handed)
        # ...written into the slot row at a traced index (in place: the
        # big cache is donated), so every prompt of this bucket reuses
        # one program regardless of slot; a counter is no slot's row: the
        # prompt's counts are added to it
        cache = jax.tree_util.tree_map_with_path(
            lambda path, big, one: big + one
            if self.leaf_kind(path) == "counter"
            else jax.lax.dynamic_update_index_in_dim(
                big, one[0], slot, axis=0), cache, mutated["cache"])
        last = logits[0, 0]
        if block:
            # no token: the row's first block (ids, masked) as the host
            # cut it from the prompt's end; ``max_abs`` is of the last
            # hidden row, which the guard reads as it reads a logit's
            token = jnp.asarray(-1, jnp.int32)
            feed = jax.tree.map(
                lambda big, row: jax.lax.dynamic_update_index_in_dim(
                    big, row, slot, axis=0), feed,
                dict(zip(("ids", "masked"), block)))
        else:
            token = jnp.argmax(last).astype(jnp.int32)
            # the slot's first decode step reads its token from the feed
            feed = jax.lax.dynamic_update_index_in_dim(feed, token, slot,
                                                       axis=0)
        max_abs = jnp.max(jnp.abs(last)).astype(jnp.float32)
        if handed:
            self._kernel_stats = tuple(sorted(handed))
            max_abs = jnp.stack([max_abs, *(
                jnp.mean(jnp.stack(handed[name]))
                for name in self._kernel_stats)])
        return cache, feed, token, max_abs

    def _piece(self, params, cache, tokens, offset, length, slot, output):
        """One piece of ``slot``'s prompt, ``tokens`` (1, PREFILL_CHUNK)
        of which ``length`` are the prompt's, from position ``offset``:
        the model continues from the slot's row of every leaf - a row of
        zeros for the prompt's first piece, whatever the slot's last
        request left - and the row it leaves is written back in place. A
        counter is no slot's row: the model adds to it as it stands."""
        def row(path, big):
            if self.leaf_kind(path) == "counter":
                return big
            one = jax.lax.dynamic_index_in_dim(big, slot, axis=0)
            return jnp.where(offset == 0, 0, one)

        out, mutated = self._model.apply(
            {"params": params,
             "cache": jax.tree_util.tree_map_with_path(row, cache)},
            tokens, positions=offset[None], lengths=length[None],
            train=False, mutable=["cache"], output=output)
        cache = jax.tree_util.tree_map_with_path(
            lambda path, big, one: one if self.leaf_kind(path) == "counter"
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

    def _block_pass(self, params, cache, feed, step):
        """One pass of every row's block (module docstring, Blocks).
        ``step``: (2, slots) int32, a row's block start (-1: not active,
        it runs zeros at position 0) and the positions to unmask (0: the
        commit pass)."""
        starts, counts = step[0], step[1]
        active = starts >= 0
        ids, masked = feed["ids"], feed["masked"]
        tokens = jnp.where(active[:, None],
                           jnp.where(masked, self._mask_id, ids), 0)
        counted = {"active": active} if self._counts else {}
        logits, mutated = self._model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=jnp.maximum(starts, 0), train=False,
            mutable=["cache"], **counted)
        with jax.named_scope("unmask"):
            logits = logits.astype(jnp.float32)
            best = jnp.max(logits, axis=-1)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # softmax(logits)[argmax], float32
            sure = 1.0 / jnp.sum(jnp.exp(logits - best[..., None]), axis=-1)
            sure = jnp.where(masked, sure, -jnp.inf)
            at = jnp.arange(self.block_len)
            ahead = (sure[:, None, :] > sure[:, :, None]) | (
                (sure[:, None, :] == sure[:, :, None])
                & (at[None, :] < at[:, None]))          # [row, i, j]: j first
            take = masked & active[:, None] \
                & (jnp.sum(ahead, axis=-1) < counts[:, None])
            commit = active & (counts == 0)
            feed = {"ids": jnp.where(take, first, ids),
                    "masked": (masked & ~take) | commit[:, None]}
            return (mutated["cache"], feed, jnp.where(take, first, -1),
                    jnp.max(jnp.abs(logits), axis=(1, 2)))

    def _decode_impl(self, params, cache, feed, positions):
        if self.block_len > 1:
            return self._block_pass(params, cache, feed, positions)
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
        and puts the first generated token into the slot's feed entry:
        one program of the prompt's bucket, or, where the model's prefill
        resumes, the prompt's pieces of ``PREFILL_CHUNK`` tokens back to
        back through ``prefill_chunk`` (the slot's row of every leaf in,
        zeros for the first piece; no head) and ``prefill_last``. The
        result collects to (first generated token id, max |logit|). Of a
        block model: the prompt's whole blocks fill the cache and its
        last ``len mod block_len`` tokens open the slot's block in the
        feed; the result collects to (``None``, max |hidden|)."""
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
            elif self.block_len > 1:
                whole = len(prompt) - len(prompt) % self.block_len
                ids = np.full((self.block_len,), self._mask_id, np.int32)
                ids[:len(prompt) - whole] = prompt[whole:]
                token, max_abs = self._run_donating(
                    "prefill", self._prefill_fn(bucket), padded,
                    np.int32(whole), np.int32(slot), ids,
                    np.arange(self.block_len) >= len(prompt) - whole)
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
            sparse=sparse, state_bytes=self._state_bytes // self.num_slots),
            self._kernel_stats)

    def decode(self, slots: List[int], tokens: Optional[List[int]],
               positions: List[int],
               unmask: Optional[List[int]] = None) -> PendingDecode:
        """Launch one decode step over ALL cache rows (fixed shape — the
        one compiled decode program). Active rows take their token from
        the feed at their real position; the others run token 0 at
        position 0. ``tokens`` is ``None`` where the rows' tokens are in
        the feed (the serving loop, whose last programs put them there);
        a caller that has them on the host passes them, and they replace
        the feed first (one transfer more). The result collects to (ids,
        max |logit|s) of ``slots``. Of a block model a step is a pass:
        ``positions`` are the rows' block starts, ``unmask`` how many
        positions each row's pass unmasks (0: the commit pass), the
        blocks are in the feed (``tokens`` is ``None``), and a row's ids
        are its block's, -1 where the pass unmasked nothing."""
        if self.block_len > 1:
            if tokens is not None or unmask is None:
                raise ValueError("a block model's pass takes its tokens "
                                 "from the feed and an unmask count a row")
        elif tokens is not None:
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
            if slots and step_pos.max() + self.block_len > self.max_seq:
                # admission caps max_tokens so no write lands past the
                # cache (batcher.ActiveRequest); overrunning silently
                # would overwrite the last KV row and serve garbage
                slot = int(step_pos.argmax())
                raise ValueError(
                    f"decode: slot {slot} position {step_pos[slot]} >= "
                    f"max_seq {self.max_seq} (admission cap violated)")
            attrs = {"state_bytes": self._state_bytes}
            self.row_passes += len(slots)
            if unmask is None:
                self.tokens_unmasked += len(slots)
            else:
                commits, unmasked = unmask.count(0), sum(unmask)
                self.commit_row_passes += commits
                self.blocks_committed += commits
                self.tokens_unmasked += unmasked
                attrs.update(unmasked=unmasked, committed=commits,
                             block_len=self.block_len)
            # the last position a row's step attends (a row that is not
            # active runs at position 0)
            last = np.maximum(step_pos, 0) + (self.block_len - 1)
            if self._step_readers:
                # what the kernels will fetch: a row that is not active
                # runs at position 0 and costs one tile
                read, held, attended = map(sum, zip(*(
                    live_tiles(last, self.max_seq)
                    for live_tiles in self._step_readers)))
                self.kv_tiles_read += read
                self.kv_tiles_held += held
                self.positions_read += attended
                attrs["kv_read_share"] = round(read / held, 4)
            by_kind = self._step_reads(np.maximum(step_pos, 0)) \
                if self._step_reads else {}
            for kind, attended in by_kind.items():
                self.positions_by_kind[kind] = \
                    self.positions_by_kind.get(kind, 0) + attended
                attrs[f"{kind}_positions_read"] = attended
            if unmask is not None:
                counts = np.zeros((self.num_slots,), np.int32)
                counts[slots] = unmask
                step_pos = np.stack([step_pos, counts])
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
        """The ``counter`` leaves, (layers, 3, experts) uint32 in layer
        order (pairs routed by both programs, decode steps that hit the
        expert, decode steps; modulo 2**32: subtract two readings as
        uint32); ``None`` for a model that counts nothing. The one place a
        counter is read: under the lock only a copy on the device is
        enqueued, behind the step in flight (no program: nothing
        compiles); this thread then waits for it outside the lock."""
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
                     if self.leaf_kind(path) == "counter"]
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
            # read by name (the benchmark; tests/test_engine_contract.py
            # lists the keys); __init__ says what each counter holds
            return {"compiles": compiles,
                    "compiles_total": sum(compiles.values()),
                    "decode_steps": self.decode_steps,
                    "decode_step_ms_ewma": round(self.step_ms_ewma, 3),
                    "prefill_chunks": self.prefill_chunks,
                    "prefill_positions": self.prefill_positions,
                    "prefill_tokens": self.prefill_tokens,
                    "cache_bytes": self.cache_bytes(),
                    "cache_bytes_by_kind": self.cache_bytes_by_kind(),
                    "cache_donated": (self._donated.get("prefill", False)
                                      and self._donated.get("decode", False)),
                    # None, never 1.0, where no step ran through a reader
                    "decode_kv_read_share": (
                        round(self.kv_tiles_read / self.kv_tiles_held, 4)
                        if self.kv_tiles_held else None),
                    "decode_write_fused": self._write_fused,
                    "prefill_sparse_kernel": self._sparse_kernel,
                    "prefill_kernels": list(self.prefill_kernels),
                    "decode_positions_read": (self.positions_read
                                              if self._counts_positions
                                              else None),
                    "decode_positions_by_kind": (
                        dict(self.positions_by_kind)
                        if self._step_reads else None),
                    # (layers, 3, experts) as nested lists, each modulo
                    # 2**32 (None: the model counts nothing)
                    "expert_counts": (None if counts is None
                                      else counts.tolist()),
                    "block_len": self.block_len,
                    "row_passes": self.row_passes,
                    "commit_row_passes": self.commit_row_passes,
                    "tokens_unmasked": self.tokens_unmasked,
                    "blocks_committed": self.blocks_committed,
                    "slots": self.num_slots}
