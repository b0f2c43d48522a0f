"""Unit coverage for the serving plane (serve/; docs/inference.md).

Four pinned-down contracts:

* the continuous batcher's admission policy matrix — token budget as a
  hard cap, slots, deadline-beats-decode-block — on a fake clock (the
  batcher never touches jax, so this is pure scheduling);
* the shared request queue's zero-lost invariant: worker loss returns
  in-flight requests to the FRONT of the line, oldest first, and the
  first completion writer wins;
* the KV-cache engine: prefill + per-token decode must be
  token-for-token identical to greedy generation through the uncached
  ``apply`` (padded prefill garbage and stale slot-reuse rows are
  unreachable by construction), with zero steady-state compiles;
* replica integrity: a NaN logit quarantines the replica and requeues
  its work; ``WorkersDownError`` requeues and re-raises.

The multiprocess half (kill-a-replica-under-load) lives in
tests/test_serve_multiprocess.py.
"""

import math
import threading
import time

import pytest

from horovod_tpu.exceptions import WorkersDownError
from horovod_tpu.serve.batcher import ContinuousBatcher
from horovod_tpu.serve.queue import (KVQueueFrontend, KVQueueReplica,
                                     QueueFull, Completion, Request,
                                     RequestQueue)
from toy_models import toy_transformer, uncached_greedy as _uncached_greedy


def _req(uid, prompt_len=8, max_new=4):
    return Request(uid=uid, prompt=list(range(1, prompt_len + 1)),
                   max_new_tokens=max_new, submitted_s=0.0)


# ---------------------------------------------------------------- batcher

class TestBatcherPolicy:
    def _batcher(self, slots=4, budget=10_000, admission_ms=50.0,
                 block=8):
        return ContinuousBatcher(num_slots=slots, max_batch_tokens=budget,
                                 admission_ms=admission_ms,
                                 decode_block=block)

    def test_idle_replica_admits_immediately(self):
        b = self._batcher()
        assert not b.admission_due(0.0)          # nothing waiting
        b.offer(_req("a"), now=0.0)
        assert b.admission_due(0.0)              # idle: no block to honor
        assert [a.request.uid for a in b.admit(0.0)] == ["a"]
        assert b.occupancy() == 1 and b.waiting() == 0

    def test_token_budget_is_a_hard_cap(self):
        # each request commits prompt(8) + max_new(4) = 12 tokens
        b = self._batcher(budget=25, admission_ms=50.0)
        for uid in ("a", "b", "c"):
            b.offer(_req(uid), now=0.0)
        admitted = b.admit(0.0)
        assert [a.request.uid for a in admitted] == ["a", "b"]
        assert b.committed_tokens() == 24
        # the deadline fires but must NOT override the budget
        assert b.admission_due(9.0)
        assert b.admit(9.0) == []
        # a retired request frees budget; the head then admits
        b.active()[0].enqueued = 4
        assert [a.request.uid for a in b.retire_done()] == ["a"]
        assert [a.request.uid for a in b.admit(9.0)] == ["c"]

    def test_budget_blocked_head_blocks_younger(self):
        # FIFO no-starvation: the big head does not let the small
        # request behind it jump the line
        b = self._batcher(budget=20)
        b.offer(_req("big", prompt_len=30, max_new=4), now=0.0)
        b.offer(_req("small", prompt_len=2, max_new=4), now=0.0)
        assert b.admit(0.0) == []
        assert b.waiting() == 2

    def test_deadline_beats_decode_block(self):
        b = self._batcher(admission_ms=50.0, block=1000)
        b.offer(_req("a"), now=0.0)
        b.admit(0.0)
        b.offer(_req("b"), now=1.0)
        assert not b.admission_due(1.04)     # young + mid-block
        assert b.admission_due(1.051)        # deadline pulls it forward

    def test_decode_block_boundary(self):
        b = self._batcher(slots=1, admission_ms=1e9, block=3)
        b.offer(_req("a", max_new=100), now=0.0)
        b.admit(0.0)
        b.offer(_req("b"), now=0.0)
        for _ in range(2):
            assert not b.admission_due(0.0)
            b.note_step()
        b.note_step()
        assert b.admission_due(0.0)
        b.admit(0.0)                         # slot full: admits nothing,
        assert b.occupancy() == 1            # but resets the block count
        assert not b.admission_due(0.0)

    def test_admission_caps_generation_to_cache(self):
        # prompt(12) + max_new(10) overruns max_seq=16: the effective
        # generation length is capped at admission (the last token is
        # returned, never written, hence the +1) — never silently
        # clamped onto the last KV row mid-decode
        b = ContinuousBatcher(num_slots=2, max_batch_tokens=10_000,
                              admission_ms=50.0, decode_block=8,
                              max_seq=16)
        b.offer(_req("a", prompt_len=12, max_new=10), now=0.0)
        b.offer(_req("b", prompt_len=4, max_new=10), now=0.0)
        capped, fits = b.admit(0.0)
        assert capped.max_tokens == 5 and capped.capped
        assert fits.max_tokens == 10 and not fits.capped
        # the budget charges the EFFECTIVE commitment, not the asked-for
        assert b.committed_tokens() == (12 + 5) + (4 + 10)
        capped.enqueued = 5
        assert capped.done                   # done at the cap, by count:
        assert not capped.generated          # no value has to be here

    def test_slots_cap(self):
        b = self._batcher(slots=2)
        for uid in ("a", "b", "c"):
            b.offer(_req(uid), now=0.0)
        assert len(b.admit(0.0)) == 2
        assert b.waiting() == 1

    def test_batch_rows_retire_evict_drain(self):
        b = self._batcher(slots=2)
        b.offer(_req("a", prompt_len=3, max_new=2), now=0.0)
        b.offer(_req("b", prompt_len=5, max_new=9), now=0.0)
        b.offer(_req("c"), now=0.0)
        b.admit(0.0)
        # right after admission: by slot, position = prompt_len (where
        # the next token writes)
        rows = b.batch_rows()
        assert [(r.slot, r.position) for r in rows] == [(0, 3), (1, 5)]
        a = rows[0]
        a.enqueued += 2              # counted at dispatch: no value yet
        a.position += 2
        assert b.batch_rows() == rows[1:]    # done rows are left out
        assert [d.request.uid for d in b.retire_done()] == ["a"]
        assert [r.slot for r in b.batch_rows()] == [1]
        assert [r.uid for r in b.evict_all()] == ["b"]
        assert [r.uid for r in b.drain_waiting()] == ["c"]
        assert b.occupancy() == 0 and b.waiting() == 0
        assert len(b.admit(0.0)) == 0        # everything really drained


# ------------------------------------------------------------------ queue

class TestRequestQueue:
    def test_submit_pull_complete_result(self):
        q = RequestQueue()
        uid = q.submit([1, 2, 3], max_new_tokens=4)
        assert q.try_result(uid) is None
        (req,) = q.pull(rank=0, max_n=8)
        assert req.uid == uid and q.depth() == 0
        q.complete(Completion(uid=uid, tokens=[9], prompt_len=3, rank=0))
        assert q.result(uid, timeout=1.0).tokens == [9]
        assert q.stats()["inflight"] == 0

    def test_requeue_worker_front_oldest_first(self):
        q = RequestQueue()
        uids = [q.submit([i], max_new_tokens=1) for i in range(3)]
        later = q.submit([9], max_new_tokens=1)
        pulled = q.pull(rank=0, max_n=3)
        assert [r.uid for r in pulled] == uids
        assert q.requeue_worker(0) == 3
        # stranded requests go back to the FRONT, oldest first — ahead
        # of the younger request that was never pulled
        assert [r.uid for r in q.pull(rank=1, max_n=10)] == uids + [later]
        assert q.requeue_worker(0) == 0
        assert q.stats()["requeued"] == 3

    def test_first_completion_wins(self):
        q = RequestQueue()
        uid = q.submit([1], max_new_tokens=1)
        q.pull(rank=0, max_n=1)
        q.complete(Completion(uid=uid, tokens=[1], prompt_len=1, rank=0))
        q.complete(Completion(uid=uid, tokens=[2], prompt_len=1, rank=1))
        assert q.result(uid).rank == 0       # duplicate reply discarded

    def test_results_evicted_after_ttl(self):
        # a serving process must not hold one Completion per request
        # ever served; eviction is amortized on the complete() path
        q = RequestQueue(result_ttl=0.05)
        uid = q.submit([1], max_new_tokens=1)
        q.pull(rank=0, max_n=1)
        q.complete(Completion(uid=uid, tokens=[1], prompt_len=1, rank=0))
        assert q.result(uid, timeout=1.0).tokens == [1]
        time.sleep(0.06)
        uid2 = q.submit([2], max_new_tokens=1)
        q.pull(rank=0, max_n=1)
        q.complete(Completion(uid=uid2, tokens=[2], prompt_len=1, rank=0))
        assert q.try_result(uid) is None          # evicted
        assert q.try_result(uid2) is not None     # fresh result kept
        stats = q.stats()
        assert stats["completed"] == 2            # counter, not dict size
        assert stats["results_held"] == 1

    def test_capacity_and_timeout(self):
        q = RequestQueue(capacity=1)
        q.submit([1], max_new_tokens=1)
        with pytest.raises(QueueFull):
            q.submit([2], max_new_tokens=1)
        with pytest.raises(TimeoutError):
            q.result("nope", timeout=0.05)


# ---------------------------------------------------------- prompt buckets

def test_prompt_bucket_policy():
    from horovod_tpu.serve.kv_cache import prompt_bucket

    # floored at the quantum: every short prompt shares ONE program
    assert prompt_bucket(1, 128) == 16
    assert prompt_bucket(16, 128) == 16
    assert prompt_bucket(17, 128) > 16
    for length in range(1, 129):
        b = prompt_bucket(length, 128)
        assert length <= b <= 128 or b == 128
    # O(log(max_seq)) distinct buckets → bounded warmup compiles
    assert len({prompt_bucket(n, 1024) for n in range(1, 1025)}) <= 8


# ------------------------------------------------------------------ engine

@pytest.fixture(scope="module")
def tiny_lm():
    return toy_transformer(max_seq=48)


def test_prefill_decode_parity_and_isolation(tiny_lm):
    """Two concurrent slots (different prompt buckets) each generate
    token-for-token what the uncached apply generates — proving the
    cache path, the padded prefill, AND cross-slot isolation at once."""
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    eng = DecodeEngine(model, params, num_slots=3)
    prompts = {0: [5, 4, 3, 2, 1], 2: list(range(1, 18))}
    gen, pos = {}, {}
    for slot, p in prompts.items():
        token, max_abs = eng.prefill(slot, p)
        assert math.isfinite(max_abs)
        gen[slot] = [token]
        pos[slot] = len(p)
    for _ in range(5):
        slots = sorted(prompts)
        ids, max_abs = eng.decode(slots, [gen[s][-1] for s in slots],
                                  [pos[s] for s in slots])
        assert all(math.isfinite(m) for m in max_abs)
        for s, t in zip(slots, ids):
            gen[s].append(t)
            pos[s] += 1
    for slot, p in prompts.items():
        assert gen[slot] == _uncached_greedy(model, params, p, 6), slot


def test_slot_reuse_no_stale_leak(tiny_lm):
    """A short prompt re-using the slot a LONGER request just vacated
    must generate exactly what it generates in a fresh engine — the
    previous occupant's stale rows beyond the new prompt are never
    attendable."""
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm

    def run(eng, slot, prompt, n):
        token, _ = eng.prefill(slot, prompt)
        out, p = [token], len(prompt)
        for _ in range(n - 1):
            (t,), _ = eng.decode([slot], [out[-1]], [p])
            out.append(t)
            p += 1
        return out

    used = DecodeEngine(model, params, num_slots=2)
    run(used, 1, list(range(1, 31)), 8)      # long occupant fills rows
    fresh = DecodeEngine(model, params, num_slots=2)
    short = [9, 8, 7, 6]
    assert run(used, 1, short, 6) == run(fresh, 1, short, 6)


def _generate(eng, slot, prompt, n, others=()):
    """Prefill ``slot`` and decode to ``n`` tokens; ``others`` are
    ``[slot, token, position]`` rows that decode alongside (advanced in
    place), so a step writes several rows at different positions."""
    token, _ = eng.prefill(slot, prompt)
    out, p = [token], len(prompt)
    while len(out) < n:
        rows = [[slot, out[-1], p]] + [list(o) for o in others]
        ids, _ = eng.decode(*map(list, zip(*rows)))
        out.append(ids[0])
        p += 1
        for o, t in zip(others, ids[1:]):
            o[1], o[2] = t, o[2] + 1
    return out


def _rows_at_different_positions(eng):
    """Three rows in one step, each at its own position (one of them in
    the second lane tile of a 256-position cache)."""
    prompts = {0: [3, 1, 4], 1: list(range(1, 18)), 2: [7] * 9}
    if eng.max_seq > 128:
        prompts[1] = [(5 * i) % 60 + 1 for i in range(131)]
    gen = {}
    for s, p in prompts.items():
        token, _ = eng.prefill(s, p)
        gen[s] = [token]
    for step in range(4):
        slots = sorted(prompts)
        ids, _ = eng.decode(slots, [gen[s][-1] for s in slots],
                            [len(prompts[s]) + step for s in slots])
        for s, t in zip(slots, ids):
            gen[s].append(t)
    return [(prompts[s], gen[s]) for s in sorted(prompts)]


def _position_zero(eng):
    """A row whose first write is the decode step's, at position 0."""
    out, p = [11], 0
    for _ in range(4):
        (t,), _ = eng.decode([1], [out[-1]], [p])
        out.append(t)
        p += 1
    return [([11], out[1:])]


def _last_position(eng):
    """The decode step writes the cache's last position."""
    prompt = [(3 * i) % 60 + 1 for i in range(eng.max_seq - 1)]
    return [(prompt, _generate(eng, 2, prompt, 2))]


def _inactive_row_then_prefill(eng):
    """Slot 0 idles through four steps (each writes garbage at its
    position 0), then takes a request."""
    busy = [5, 4, 3, 2, 1]
    first = _generate(eng, 1, busy, 5)
    late = [9, 8, 7]
    return [(busy, first), (late, _generate(eng, 0, late, 4))]


def _reuse_after_longer_occupant(eng):
    """A short request in the slot a longer one filled, while another
    row decodes beside it."""
    long_prompt = list(range(1, 31))
    _generate(eng, 1, long_prompt, 8)
    beside = [2, 4, 6, 8, 10]
    token, _ = eng.prefill(0, beside)
    other = [0, token, len(beside)]
    short = [9, 8, 7, 6]
    return [(short, _generate(eng, 1, short, 6, others=[other]))]


def _across_lane_tiles(eng):
    """Writes on both sides of a 128-position tile boundary."""
    prompt = [(7 * i) % 60 + 1 for i in range(126)]
    return [(prompt, _generate(eng, 0, prompt, 5))]


@pytest.mark.parametrize("max_seq,scenario", [
    (48, _rows_at_different_positions),
    (256, _rows_at_different_positions),
    (48, _position_zero),
    (48, _last_position),
    (256, _last_position),
    (48, _inactive_row_then_prefill),
    (48, _reuse_after_longer_occupant),
    (256, _across_lane_tiles),
], ids=lambda v: v if isinstance(v, int) else v.__name__.lstrip("_"))
def test_cache_write_parity(tiny_lm, max_seq, scenario):
    """What the in-place cache write serves is, token for token, what
    the uncached ``apply`` generates."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    if max_seq != model.max_seq:
        model = model.clone(max_seq=max_seq)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32),
                            train=False)["params"]
    eng = DecodeEngine(model, params, num_slots=3)
    for prompt, served in scenario(eng):
        assert served == _uncached_greedy(model, params, prompt,
                                          len(served)), prompt


def test_cache_is_donated(tiny_lm):
    """Every program consumes the cache it is handed (no second copy of
    the KV cache lives through a call); a donation the runtime cannot
    use warns, and fails here."""
    import warnings

    import jax

    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    eng = DecodeEngine(model, params, num_slots=2)
    assert eng.stats()["cache_donated"] is False     # nothing ran yet
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        before = jax.tree.leaves(eng._cache)
        token, _ = eng.prefill(0, [1, 2, 3])
        assert all(x.is_deleted() for x in before)
        assert eng.stats()["cache_donated"] is False  # decode not yet
        before = jax.tree.leaves(eng._cache)
        eng.decode([0], [token], [3])
        assert all(x.is_deleted() for x in before)
    assert eng.stats()["cache_donated"] is True
    assert eng.cache_bytes() == sum(
        x.nbytes for x in jax.tree.leaves(eng._cache))


@pytest.mark.parametrize("cache_len,dtype", [
    (48, "float32"), (256, "float32"), (256, "bfloat16")])
def test_write_token_kernel(cache_len, dtype):
    """The kernel against numpy: one position per row replaced, the rest
    untouched — first and last position, both sides of a lane-tile
    boundary, a position past the end clamped onto the last."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.pallas.kv_cache_write import write_token

    rng = np.random.default_rng(0)
    rows, heads, head_dim = 6, 3, 16
    cache = jnp.asarray(rng.normal(size=(rows, heads, head_dim, cache_len)),
                        dtype)
    new = jnp.asarray(rng.normal(size=(rows, heads, head_dim)), dtype)
    last = cache_len - 1
    positions = [0, last, min(127, last), min(128, last), 5, cache_len + 3]
    want = np.array(cache.astype(jnp.float32))
    for b, p in enumerate(positions):
        want[b, :, :, min(p, last)] = np.array(new[b].astype(jnp.float32))
    got = write_token(cache, new, jnp.asarray(positions, jnp.int32))
    assert got.dtype == cache.dtype
    np.testing.assert_array_equal(np.array(got.astype(jnp.float32)), want)


_RAGGED = {
    # both sides of a lane-tile boundary, the first and the last position
    "ragged": lambda rows, last: [0, 127, 128, 129, last, 5, 300][:rows],
    "all-equal": lambda rows, last: [200] * rows,
    # what a row that is not active runs at
    "inactive": lambda rows, last: [0] * rows,
}


def _stale_cache(rng, shape, positions, dtype):
    """A cache whose rows hold sound values up to their position and
    large finite garbage past it (an earlier occupant's), in the live
    tile and in the dead ones; and the same cache with zeros there."""
    import jax.numpy as jnp
    import numpy as np

    sound = rng.normal(size=shape)
    stale = np.arange(shape[-1])[None, :] > np.asarray(positions)[:, None]
    stale = stale[:, None, None, :]
    garbage = rng.choice([-3e4, 3e4], size=shape)
    return (jnp.asarray(np.where(stale, garbage, sound), dtype),
            jnp.asarray(np.where(stale, 0.0, sound), dtype))


def _decode_case(dtype, pattern):
    """A decode step's operands at seven rows of three lane tiles: the
    query and the new key and value columns, both leaves with an earlier
    occupant's garbage from each row's position on (the position itself
    is the new token's: not yet written) and with zeros there."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(3)
    rows, heads, head_dim, cache_len = 7, 3, 16, 384
    positions = _RAGGED[pattern](rows, cache_len - 1)
    shape = (rows, heads, head_dim, cache_len)
    before = [p - 1 for p in positions]
    k_stale, k_clean = _stale_cache(rng, shape, before, dtype)
    v_stale, v_clean = _stale_cache(rng, shape, before, dtype)
    q, k_new, v_new = (
        jnp.asarray(rng.normal(size=(rows, heads, head_dim)), dtype)
        for _ in range(3))
    return (jnp.asarray(positions, jnp.int32), q, k_new, v_new,
            (k_stale, v_stale), (k_clean, v_clean))


def _bits(x):
    import jax.numpy as jnp
    import numpy as np

    return np.array(x.astype(jnp.float32))


@pytest.mark.parametrize("pattern", sorted(_RAGGED))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decode_attention_kernel(dtype, pattern):
    """The kernel (interpret mode) against ``cached_attention`` over the
    cache with the new columns written in: equal to rounding, and what
    lies past a row's position changes nothing, bit for bit."""
    import numpy as np

    from horovod_tpu.models.transformer import cached_attention
    from horovod_tpu.ops.pallas.decode_attention import decode_attention
    from horovod_tpu.ops.pallas.kv_cache_write import write_token

    pos, q, k_new, v_new, stale, clean = _decode_case(dtype, pattern)
    got, _, _ = decode_attention(q, k_new, v_new, *stale, pos)
    assert got.shape == q.shape and got.dtype == stale[1].dtype
    np.testing.assert_array_equal(
        _bits(got), _bits(decode_attention(q, k_new, v_new, *clean, pos)[0]))
    want = cached_attention(
        q[:, :, None, :],
        write_token(stale[0], k_new, pos).transpose(0, 1, 3, 2),
        write_token(stale[1], v_new, pos).transpose(0, 1, 3, 2),
        pos[:, None])[:, :, 0]
    # XLA rounds its scores and probabilities to the cache's dtype; the
    # kernel keeps both in float32
    tol = 3e-2 if dtype == "bfloat16" else 2e-6
    np.testing.assert_allclose(_bits(got), _bits(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("pattern", sorted(_RAGGED))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decode_attention_writes_as_write_token(dtype, pattern):
    """Both leaves come back bit for bit as ``write_token`` leaves them:
    the new column in lane ``position % 128`` of the row's last live
    tile, every other lane of that tile (the garbage past the position
    included) and every other tile as they went in. And the output is
    bit for bit what the kernel gives over a cache that ``write_token``
    wrote first (placing the same column again changes nothing): the
    score sees the column as a later step reads it back."""
    import numpy as np

    from horovod_tpu.ops.pallas.decode_attention import decode_attention
    from horovod_tpu.ops.pallas.kv_cache_write import write_token

    pos, q, k_new, v_new, (k_cache, v_cache), _ = _decode_case(dtype, pattern)
    got, k_got, v_got = decode_attention(q, k_new, v_new, k_cache, v_cache,
                                         pos)
    for leaf, new, cache in ((k_got, k_new, k_cache), (v_got, v_new, v_cache)):
        assert leaf.dtype == cache.dtype
        want = _bits(cache)
        for row, p in enumerate(np.array(pos)):
            want[row, :, :, p] = _bits(new[row])
        np.testing.assert_array_equal(_bits(leaf), want)
        np.testing.assert_array_equal(
            _bits(leaf), _bits(write_token(cache, new, pos)))
    again, k_again, v_again = decode_attention(q, k_new, v_new, k_got, v_got,
                                               pos)
    np.testing.assert_array_equal(_bits(got), _bits(again))
    np.testing.assert_array_equal(_bits(k_again), _bits(k_got))
    np.testing.assert_array_equal(_bits(v_again), _bits(v_got))


def test_decode_attention_rounds_the_new_columns_first():
    """Float32 columns into a bfloat16 cache are scored as the cache
    holds them, and a position past the end is clamped onto the last."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.pallas.decode_attention import decode_attention

    pos, q, k_new, v_new, (k_cache, v_cache), _ = _decode_case(
        "float32", "ragged")
    pos = pos.at[4].set(k_cache.shape[-1] + 3)
    k_cache, v_cache = (x.astype(jnp.bfloat16) for x in (k_cache, v_cache))
    got = decode_attention(q, k_new, v_new, k_cache, v_cache, pos)
    rounded = decode_attention(q, k_new.astype(jnp.bfloat16),
                               v_new.astype(jnp.bfloat16), k_cache, v_cache,
                               pos)
    for x, y in zip(got, rounded):
        assert x.dtype == jnp.bfloat16
        np.testing.assert_array_equal(_bits(x), _bits(y))
    np.testing.assert_array_equal(_bits(got[1][4, :, :, -1]),
                                  _bits(k_new[4].astype(jnp.bfloat16)))


@pytest.mark.parametrize("cache_len,new_tokens,kernels", [
    # the dense decode step: one kernel, and no write kernel beside it
    (128, 1, ["decode_attention"]),
    # a cache length off the lane tile: a write a leaf, masked attention
    (96, 1, ["kv_cache_write", "kv_cache_write"]),
    # several new tokens a row, a prefill: a slice a row, no kernel
    (128, 3, []),
], ids=["decode", "off-tile", "prefill"])
def test_write_and_attend_selects_by_shape(cache_len, new_tokens, kernels):
    """One new token a row against whole lane tiles goes through the one
    kernel, which writes too; anything else is a write a leaf and the
    masked whole-row contraction. By result, by the leaves and by what
    the traced program holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import (cached_attention,
                                                write_and_attend)
    from horovod_tpu.ops.pallas._backend import kernels_in

    rng = np.random.default_rng(5)
    rows, heads, head_dim = 3, 2, 8
    shape = (rows, heads, head_dim, cache_len)
    positions = [0, cache_len - new_tokens, 40]
    k_cache, _ = _stale_cache(rng, shape, [p - 1 for p in positions],
                              "float32")
    v_cache, _ = _stale_cache(rng, shape, [p - 1 for p in positions],
                              "float32")
    q, k, v = (jnp.asarray(rng.normal(
        size=(rows, new_tokens, heads, head_dim)), jnp.float32)
        for _ in range(3))
    pos = jnp.asarray(positions, jnp.int32)
    names = kernels_in(jax.make_jaxpr(write_and_attend)(
        q, k, v, k_cache, v_cache, pos))
    assert names == kernels
    k_want, v_want = np.array(k_cache), np.array(v_cache)
    for row, p in enumerate(positions):
        k_want[row, :, :, p:p + new_tokens] = np.array(
            k[row]).transpose(1, 2, 0)
        v_want[row, :, :, p:p + new_tokens] = np.array(
            v[row]).transpose(1, 2, 0)
    got, k_got, v_got = write_and_attend(q, k, v, k_cache, v_cache, pos)
    np.testing.assert_array_equal(np.array(k_got), k_want)
    np.testing.assert_array_equal(np.array(v_got), v_want)
    q_pos = pos[:, None] + jnp.arange(new_tokens)
    want = cached_attention(
        q.transpose(0, 2, 1, 3), jnp.asarray(k_want).transpose(0, 1, 3, 2),
        jnp.asarray(v_want).transpose(0, 1, 3, 2), q_pos
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               atol=2e-6, rtol=2e-6)


@pytest.fixture(scope="module")
def tiled_lm():
    """A toy decoder whose cache is two lane tiles long, so that its
    decode step takes the decode-attention kernel."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer

    model = Transformer(vocab_size=61, d_model=32, num_layers=2,
                        num_heads=2, d_ff=64, max_seq=256, causal=True,
                        dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return model, params


def test_dense_engine_decodes_as_the_masked_path(tiled_lm, monkeypatch):
    """Forty decode steps of three rows that cross a tile boundary, a
    row left inactive: the engine on the kernel serves the tokens the
    same model serves on the masked whole-row path."""
    from horovod_tpu.models import transformer
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiled_lm
    prompts = [list(range(1, 101)), [7, 8, 9], list(range(20, 50))]

    def serve(engine):
        firsts = [engine.prefill(slot, prompt).collect()[0]
                  for slot, prompt in enumerate(prompts)]
        rows, out = [0, 1, 2], [firsts]
        for step in range(40):
            ids, _ = engine.decode(
                rows, out[-1], [len(p) + step for p in prompts])
            out.append(ids)
        return out

    kernel = DecodeEngine(model, params, num_slots=4)
    assert "decode_attention" in kernel.decode_kernels
    assert kernel.stats()["decode_write_fused"] is True
    got = serve(kernel)
    monkeypatch.setattr(transformer, "takes_kernel", lambda *_: False)
    masked = DecodeEngine(model, params, num_slots=4)
    assert "decode_attention" not in masked.decode_kernels
    assert masked.stats()["decode_kv_read_share"] is None
    assert masked.stats()["decode_write_fused"] is None
    assert serve(masked) == got
    assert masked.stats()["decode_kv_read_share"] is None


def test_decode_kv_read_share_counts_live_tiles(tiled_lm, tiny_lm):
    """The counter against the share worked out by hand: each row reads
    ``position // 128 + 1`` of its two tiles, a row that is not active
    one; None where the decode program holds no kernel."""
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiled_lm
    engine = DecodeEngine(model, params, num_slots=4)
    assert engine.stats()["decode_kv_read_share"] is None   # no step yet
    engine.prefill(0, [1, 2, 3])
    engine.prefill(2, list(range(1, 131)))
    engine.decode([0], [5], [3])            # rows at 3, -, -, -: 4 of 8
    assert engine.stats()["decode_kv_read_share"] == 0.5
    engine.decode([0, 2], [5, 6], [4, 130])     # 4, -, 130, -: 5 of 8
    engine.decode([2], [6], [127])              # -, -, 127, -: 4 of 8
    engine.decode([0, 2], [5, 6], [128, 255])   # 128, -, 255, -: 6 of 8
    assert engine.stats()["decode_kv_read_share"] == round(19 / 32, 4)
    assert (engine.kv_tiles_read, engine.kv_tiles_held) == (19, 32)

    # 48 positions are no whole lane tile: the masked path, no share
    model, params = tiny_lm
    off_tile = DecodeEngine(model, params, num_slots=2)
    off_tile.prefill(0, [1, 2, 3])
    off_tile.decode([0], [5], [3])
    assert off_tile.stats()["decode_kv_read_share"] is None


def test_zero_steady_state_compiles(tiny_lm):
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    eng = DecodeEngine(model, params, num_slots=2)
    eng.prefill(0, [1, 2, 3])
    eng.decode([0], [1], [3])
    eng.prefill(1, [4, 5])                   # same bucket: no new program
    warm = eng.compiles_total()
    assert warm == 2                          # one prefill bucket + decode
    for step in range(5):
        eng.prefill(step % 2, [7, 8, 9])
        eng.decode([0, 1], [1, 2], [4, 5])
    assert eng.compiles_total() == warm
    eng.prefill(0, list(range(1, 20)))         # new bucket DOES compile
    assert eng.compiles_total() == warm + 1
    # enqueued six steps, none collected yet: a step counts when it is read
    assert eng.decodes_enqueued == 6 and eng.stats()["decode_steps"] == 0
    last = eng.decode([0], [1], [5])
    assert last.collect() is last.collect()   # read once, kept
    assert eng.stats()["decode_steps"] == 1


def test_noncausal_model_rejected(tiny_lm):
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    with pytest.raises(ValueError, match="causal"):
        DecodeEngine(model.clone(causal=False), params, num_slots=1)


# ----------------------------------------------------- replica integrity

class _FakeEngine:
    """Minimal engine double for the replica loop (no jax)."""

    def __init__(self, num_slots=2, prefill_abs=1.0, decode_abs=1.0,
                 decode_exc=None):
        self.num_slots = num_slots
        self.max_seq = 64
        self.decode_steps = 0
        self._prefill_abs = prefill_abs
        self._decode_abs = decode_abs
        self._decode_exc = decode_exc

    def prefill(self, slot, prompt):
        return 1, self._prefill_abs

    def decode(self, slots, tokens, positions):
        if self._decode_exc is not None:
            raise self._decode_exc
        self.decode_steps += 1
        abs_ = (list(self._decode_abs)
                if isinstance(self._decode_abs, (list, tuple))
                else [self._decode_abs] * len(slots))
        return [2] * len(slots), abs_[:len(slots)]

    def compiles_total(self):
        return 0

    def stats(self):
        return {"decode_steps": self.decode_steps}


def _replica(engine, queue, rank=0):
    from horovod_tpu.serve.api import ServePolicy
    from horovod_tpu.serve.replica import Replica, _LocalTransport

    return Replica(engine, _LocalTransport(queue, rank),
                   ServePolicy(slots=engine.num_slots, max_new_tokens=4,
                               admission_ms=1.0, decode_block=2),
                   rank=rank)


def test_nan_prefill_quarantines_and_requeues():
    q = RequestQueue()
    rep = _replica(_FakeEngine(prefill_abs=float("nan")), q)
    q.submit([1, 2], max_new_tokens=4)
    rep._iterate()
    assert rep.quarantined
    # zero lost: the request is back in line for another replica
    assert q.depth() == 1 and q.stats()["requeued"] == 1

def test_nan_decode_quarantines_and_requeues():
    q = RequestQueue()
    rep = _replica(_FakeEngine(decode_abs=float("inf")), q)
    q.submit([1, 2], max_new_tokens=4)
    rep._iterate()
    assert rep.quarantined
    assert q.depth() == 1 and q.stats()["requeued"] == 1


def test_workers_down_requeues_and_reraises():
    q = RequestQueue()
    rep = _replica(_FakeEngine(decode_exc=WorkersDownError("reform")), q)
    q.submit([1, 2], max_new_tokens=4)
    with pytest.raises(WorkersDownError):
        rep.run()
    assert not rep.quarantined               # elastic, not integrity
    assert q.depth() == 1 and q.stats()["requeued"] == 1


def test_replica_rejects_unservable_prompts():
    """A prompt longer than the cache (or empty) arriving over the
    transport — bypassing ServeHandle's validation — must be answered
    with finish="rejected", not crash the loop or strand its caller."""
    q = RequestQueue()
    rep = _replica(_FakeEngine(), q)             # max_seq = 64
    uid_long = q.submit(list(range(100)), max_new_tokens=4)
    uid_empty = q.submit([], max_new_tokens=4)
    rep._iterate()
    assert q.result(uid_long, timeout=1.0).finish == "rejected"
    assert q.result(uid_empty, timeout=1.0).finish == "rejected"
    assert not rep.quarantined and q.stats()["inflight"] == 0


def test_loop_error_quarantines_and_requeues():
    """A non-elastic exception escaping the step must not silently kill
    the replica thread (stranding in-flight callers): the replica
    requeues its work and parks quarantined."""
    q = RequestQueue()
    rep = _replica(_FakeEngine(decode_exc=RuntimeError("boom")), q)
    q.submit([1, 2], max_new_tokens=4)
    t = threading.Thread(target=rep.run, daemon=True)
    t.start()
    deadline = time.monotonic() + 5.0
    while not rep.quarantined and time.monotonic() < deadline:
        time.sleep(0.01)
    rep.stop()
    t.join(timeout=5.0)
    assert rep.quarantined and not t.is_alive()
    assert q.depth() == 1 and q.stats()["requeued"] == 1


def test_guard_observes_every_slot():
    """The integrity guard's EWMA state must see EVERY slot's max-|logit|
    each step — a non-finite first slot must not short-circuit the
    observations of the slots behind it."""
    class _CountingGuard:
        def __init__(self):
            self.seen = []

        def observe(self, value):
            self.seen.append(value)

    q = RequestQueue()
    rep = _replica(_FakeEngine(decode_abs=[float("nan"), 5.0]), q)
    rep.guard = _CountingGuard()
    q.submit([1, 2], max_new_tokens=4)
    q.submit([3, 4], max_new_tokens=4)
    rep._iterate()                               # prefill x2 + decode
    assert rep.quarantined                       # nan still trips it
    assert 5.0 in rep.guard.seen                 # second slot observed


def test_healthy_replica_completes():
    q = RequestQueue()
    rep = _replica(_FakeEngine(), q)
    uid = q.submit([1, 2], max_new_tokens=3)
    for _ in range(4):
        rep._iterate()
    done = q.result(uid, timeout=1.0)
    assert done.tokens == [1, 2, 2] and done.rank == 0
    assert not rep.quarantined and rep.completed == 1


# ------------------------------------------- the loop, one step ahead

def _ahead_replica(engine, queue, max_new_tokens=64):
    """A replica that checks admission in every pass (so a slot freed in
    one pass is prefilled in the next), over a real engine."""
    from horovod_tpu.serve.api import ServePolicy
    from horovod_tpu.serve.replica import Replica, _LocalTransport

    return Replica(engine, _LocalTransport(queue, 0),
                   ServePolicy(slots=engine.num_slots, admission_ms=0.0,
                               decode_block=1,
                               max_new_tokens=max_new_tokens), rank=0)


def _blocking_greedy(eng, slot, prompt, n):
    """``n`` tokens through the engine's blocking calls, each step fed
    from the host."""
    token, _ = eng.prefill(slot, prompt)
    out = [token]
    while len(out) < n:
        (token,), _ = eng.decode([slot], [out[-1]], [len(prompt) + len(out) - 1])
        out.append(token)
    return out


def _toy(kind, request):
    if kind == "transformer":
        return request.getfixturevalue("tiny_lm")
    from toy_models import family

    sala = family("sala")
    return sala.model, sala.params


@pytest.mark.parametrize("kind", ["transformer", "hybrid"])
def test_loop_ahead_serves_what_the_blocking_calls_produce(kind, request):
    """Admissions, retirements by count, a one-token request, and slots
    prefilled in the pass after the one that freed them: every request's
    tokens are what ``prefill()`` / ``decode()`` give it alone, fed from
    the host at every step."""
    import numpy as np

    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = _toy(kind, request)
    rng = np.random.default_rng(3)
    sizes = [(5, 3), (17, 6), (9, 1), (3, 4), (20, 2), (12, 5)]
    prompts = [rng.integers(1, 60, n).tolist() for n, _ in sizes]
    eng = DecodeEngine(model, params, num_slots=2)
    q = RequestQueue()
    uids = [q.submit(p, max_new_tokens=new)
            for p, (_, new) in zip(prompts, sizes)]
    rep = _ahead_replica(eng, q)

    events = []                     # (pass, "prefill" | "decode", slots)
    passes = [0]
    prefill, decode = eng.prefill, eng.decode

    def prefill_logged(slot, prompt):
        events.append((passes[0], "prefill", (slot,)))
        return prefill(slot, prompt)

    def decode_logged(slots, tokens, positions):
        assert tokens is None          # the loop never feeds from the host
        events.append((passes[0], "decode", tuple(slots)))
        return decode(slots, tokens, positions)

    eng.prefill, eng.decode = prefill_logged, decode_logged
    while rep.completed < len(uids) and passes[0] < 40:
        passes[0] += 1
        rep._iterate()
    assert rep.completed == len(uids) and not rep.quarantined

    fresh = DecodeEngine(model, params, num_slots=2)
    for uid, prompt, (_, new) in zip(uids, prompts, sizes):
        done = q.result(uid, timeout=1.0)
        assert done.finish == "length" and len(done.tokens) == new
        assert done.tokens == _blocking_greedy(fresh, 1, prompt, new), uid
    # a slot that a decode step of pass p still wrote is prefilled in
    # pass p + 1, before that step's ids were read
    decoded = {p: slots for p, what, slots in events if what == "decode"}
    reused = [(p, slot) for p, what, (slot, *_) in events
              if what == "prefill" and slot in decoded.get(p - 1, ())]
    assert len(reused) >= 2, events
    stats = rep.stats()
    assert stats["decode_steps"] == len(decoded)
    assert 0.5 < stats["lookahead_share"] < 1.0
    assert stats["engine"]["cache_donated"]


def test_steady_loop_enqueues_step_k_before_it_reads_step_k_minus_1(
        ring, tiny_lm):
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    eng = DecodeEngine(model, params, num_slots=2)
    q = RequestQueue()
    uids = [q.submit([3, 1, 4], max_new_tokens=40),
            q.submit([1, 5, 9, 2], max_new_tokens=40)]
    rep = _ahead_replica(eng, q)
    for _ in range(45):
        rep._iterate()
    assert [len(q.result(u, timeout=1.0).tokens) for u in uids] == [40, 40]
    spans = ring.spans()
    dispatches = [s for s in spans if s["name"] == "engine.decode.dispatch"]
    waits = [s for s in spans if s["name"] == "engine.decode.wait"]
    steps = {("serve.step", s["sid"]): s for s in spans
             if s["name"] == "serve.step"}
    assert len(dispatches) == len(waits) == 39
    for k in range(1, 39):
        # inside one serve.step: step k goes out, then step k-1 comes in
        sent, read = dispatches[k], waits[k - 1]
        assert sent["t"] + sent["dur"] <= read["t"]
        assert sent["parent"] in steps
        retire = next(s for s in spans if s["name"] == "serve.retire"
                      and ("serve.retire", s["sid"]) == read["parent"])
        assert retire["parent"] == sent["parent"]
        assert read["ahead"] == 1
    assert waits[-1]["ahead"] == 0        # nothing left to enqueue
    assert rep.stats()["lookahead_share"] >= 0.95
    assert rep.stats()["avg_occupancy"] == 2.0


class _Poisoned:
    """A pending decode step whose guard values read non-finite."""

    on_host = False

    def __init__(self, pending):
        self._pending = pending
        self.ready = pending.ready

    def collect(self):
        ids, max_abs = self._pending.collect()
        return ids, [float("inf")] * len(max_abs)


def test_guard_one_step_late_delivers_nothing_of_the_bad_step(tiny_lm):
    """A non-finite logit in decode step 3 is seen after step 4 is
    enqueued: step 4 is never read, the request whose last token step 3
    made is not delivered, and everything the replica holds goes back to
    the queue once. What was complete before step 3 is delivered."""
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    eng = DecodeEngine(model, params, num_slots=3)
    q = RequestQueue()
    prompts = [[5, 4, 3], [2, 7, 1, 8], [6, 6], [9, 1], [4, 2, 4], [7]]
    news = [3, 4, 10, 10, 10, 10]   # the first ends in step 2, the second in 3
    uids = [q.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    rep = _ahead_replica(eng, q)

    collected = []
    decode = eng.decode

    class _Counted:
        on_host = False

        def __init__(self, pending, number):
            self._pending, self._number = pending, number
            self.ready = pending.ready

        def collect(self):
            collected.append(self._number)
            return self._pending.collect()

    def poisoning(slots, tokens, positions):
        number = eng.decodes_enqueued + 1
        pending = decode(slots, tokens, positions)
        return _Counted(_Poisoned(pending) if number == 3 else pending,
                        number)

    eng.decode = poisoning
    for _ in range(3):
        rep._iterate()
    assert not rep.quarantined and rep.completed == 1
    held = {a.request.uid: a for a in rep.batcher.active() + rep._unread}
    assert set(held) == set(uids[1:4]) and rep.batcher.waiting() == 1
    rep._iterate()                               # enqueues 4, reads 3
    assert rep.quarantined and rep.completed == 1
    assert eng.decodes_enqueued == 4 and collected == [1, 2, 3]
    assert rep._ahead is None and not rep._unread and not rep._first_tokens
    first = q.result(uids[0], timeout=1.0)
    assert first.tokens == _uncached_greedy(model, params, prompts[0], 3)
    for uid in uids[1:]:
        with pytest.raises(TimeoutError):
            q.result(uid, timeout=0)
    # nothing of step 3 or later was appended to any request
    assert len(held[uids[1]].generated) == 3     # prefill + steps 1, 2
    assert len(held[uids[2]].generated) == 3
    assert q.depth() == 5 and q.stats()["requeued"] == 5
    assert [r.requeues for r in q.pull(1, 8)] == [1] * 5
    # the engine is whole: its cache is the last program's result
    del eng.decode
    assert _blocking_greedy(eng, 0, prompts[2], 5) == \
        _uncached_greedy(model, params, prompts[2], 5)


@pytest.mark.parametrize("how", ["stop", "workers_down"])
def test_teardown_with_a_step_in_flight(tiny_lm, how):
    """``stop()`` and a ``WorkersDownError`` find a decode step enqueued
    and unread: it is dropped, the engine stays usable, and the elastic
    path requeues everything once (``stop()`` requeues nothing, as
    ever)."""
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model, params = tiny_lm
    eng = DecodeEngine(model, params, num_slots=2)
    q = RequestQueue()
    prompts = [[3, 1, 4, 1], [5, 9], [2, 6, 5]]
    for p in prompts:
        q.submit(p, max_new_tokens=30)
    rep = _ahead_replica(eng, q)
    decode = eng.decode

    def interrupted(slots, tokens, positions):
        if eng.decodes_enqueued == 3:
            assert rep._ahead is not None        # step 3 is in flight
            if how == "stop":
                rep.stop()
            else:
                raise WorkersDownError("reform")
        return decode(slots, tokens, positions)

    eng.decode = interrupted
    if how == "stop":
        rep.run()
        assert eng.decodes_enqueued == 4 and eng.decode_steps == 3
        assert q.stats()["requeued"] == 0 and rep.completed == 0
    else:
        with pytest.raises(WorkersDownError):
            rep.run()
        assert eng.decodes_enqueued == 3 and eng.decode_steps == 2
        assert q.depth() == 3 and q.stats()["requeued"] == 3
        assert rep.batcher.occupancy() == 0 and rep.batcher.waiting() == 0
    assert not rep.quarantined
    assert rep._ahead is None and not rep._first_tokens
    del eng.decode
    assert _blocking_greedy(eng, 1, prompts[0], 6) == \
        _uncached_greedy(model, params, prompts[0], 6)


# ----------------------------------------------------------- policy / api

def test_policy_from_env_and_overrides(monkeypatch):
    from horovod_tpu.serve.api import ServePolicy

    monkeypatch.setenv("HOROVOD_SERVE_SLOTS", "3")
    monkeypatch.setenv("HOROVOD_SERVE_ADMISSION_MS", "12.5")
    p = ServePolicy.from_env(max_new_tokens=7)
    assert p.slots == 3 and p.admission_ms == 12.5
    assert p.max_new_tokens == 7
    with pytest.raises(TypeError, match="unknown serve policy knob"):
        ServePolicy.from_env(slotz=3)


class _Tokenizer:
    def encode(self, text):
        return [ord(c) % 50 + 1 for c in text]


def test_submit_validates_prompt_against_max_seq():
    """Oversized / empty prompts are refused AT SUBMIT — the caller gets
    a ValueError now, not a result() timeout after the replica choked;
    a prompt that fits but overruns the cache with its generation budget
    is served truncated with finish="cache_limit"."""
    from horovod_tpu.serve.api import ServeHandle, ServePolicy

    q = RequestQueue()
    rep = _replica(_FakeEngine(), q)             # max_seq = 64
    handle = ServeHandle([rep], q, ServePolicy(max_new_tokens=4))
    try:
        with pytest.raises(ValueError, match="empty"):
            handle.submit([])
        with pytest.raises(ValueError, match="max_seq"):
            handle.submit([1] * 65)
        done = handle.generate([1] * 64, timeout=10.0)  # exactly fits
        assert done.finish == "cache_limit"       # cache, not budget
        assert len(done.tokens) == 1              # prefill token only
        done = handle.generate([1] * 8, timeout=10.0)
        assert done.finish == "length" and len(done.tokens) == 4
    finally:
        handle.close()


def test_serve_end_to_end_in_process(tiny_lm):
    import horovod_tpu as hvd
    from horovod_tpu.serve import serve_state

    model, params = tiny_lm
    with hvd.serve(model, params, tokenizer=_Tokenizer(), replicas=2,
                   slots=2, max_new_tokens=5, admission_ms=5.0,
                   decode_block=2) as handle:
        assert serve_state()["count"] == 1   # the /serve route sees us
        uids = [handle.submit([1 + i, 2, 3]) for i in range(6)]
        uids.append(handle.submit("hi"))     # tokenizer path
        outs = [handle.result(u, timeout=120.0) for u in uids]
        assert all(len(o.tokens) == 5 for o in outs)
        assert all(0 <= t < model.vocab_size
                   for o in outs for t in o.tokens)
        assert all(o.latency_s >= o.ttft_s >= 0.0 for o in outs)
        # parity with the uncached reference through the full stack
        assert outs[0].tokens == _uncached_greedy(
            model, params, [1, 2, 3], 5)
        # per replica: one prompt bucket + the decode program, at most
        assert handle.compiles_total() <= 4
        stats = handle.stats()
        assert stats["queue"]["completed"] == 7
    assert serve_state()["count"] == 0


# --------------------------------------------------------- KV transport

def test_kv_frontend_redispatches_dead_replica():
    """Single-process version of the chaos cell's queue semantics: a
    replica that pulled work and went silent is declared dead after the
    stale window and its request re-dispatched to a live replica; the
    late duplicate reply (if any) is deduplicated first-wins."""
    from horovod_tpu.run.rendezvous import KVStoreClient, RendezvousServer

    server = RendezvousServer(host="127.0.0.1")
    port = server.start()
    try:
        def client():
            return KVStoreClient("127.0.0.1", port, scope="serve",
                                 timeout=5.0)

        front = KVQueueFrontend(client(), stale_seconds=0.4)
        dead = KVQueueReplica(client(), rank=1)
        live = KVQueueReplica(client(), rank=2)
        dead.heartbeat()
        live.heartbeat()
        assert front.wait_for_replicas(2, timeout=5.0) == [1, 2]

        req = Request(uid="r1", prompt=[1, 2, 3], max_new_tokens=2)
        assert front.submit(req, rank=1) == 1
        (got,) = dead.poll(4)
        assert got.uid == "r1"               # pulled... then rank 1 dies
        deadline = time.monotonic() + 5.0
        while 1 in front.live_replicas() and time.monotonic() < deadline:
            live.heartbeat()
            time.sleep(0.05)
        assert front.live_replicas() == [2]
        assert front.poll_responses() == []  # triggers the re-dispatch
        assert front.requeued == 1 and front.dead_ranks == {1}
        (redis,) = live.poll(4)
        assert redis.uid == "r1"
        live.complete(Completion(uid="r1", tokens=[5, 6], prompt_len=3,
                                 rank=2))
        deadline = time.monotonic() + 5.0
        while front.pending() and time.monotonic() < deadline:
            front.poll_responses()
            time.sleep(0.02)
        assert front.pending() == 0
        assert front._done["r1"].rank == 2
        # a zombie reply from the dead rank arrives late: first wins
        dead.complete(Completion(uid="r1", tokens=[9, 9], prompt_len=3,
                                 rank=1))
        assert front.poll_responses() == []
        assert front._done["r1"].rank == 2
        front.stop_fleet()
        assert dead.stopped() and live.stopped()
    finally:
        server.stop()
