"""Unit + e2e coverage for the paged KV-cache subsystem (serve/paging.py;
docs/inference.md "Paged KV cache").

Pinned-down contracts:

* the :class:`PagePool` block allocator — refcounted free list, scratch
  page 0 never allocated, reclaim hook re-entrancy, exhaustion;
* the :class:`PrefixCache` — rolling-hash block walk, exact replay
  entries, LRU eviction dropping page refs, pressure reclaim;
* page-aware admission in the :class:`ContinuousBatcher` — pool pages
  as the committed capacity, the prefix-probe discount, preempt-newest
  back to the queue FRONT;
* the :class:`PagedDecodeEngine` — token-for-token parity with the
  uncached ``apply`` through page-table gathers, copy-on-write isolation
  between prefix sharers, exact-replay with ZERO prefill compute, zero
  steady-state compiles under slot churn + page growth + COW + hits,
  exhaustion rollback;
* e2e through ``hvd.serve()``: preemption under pool pressure resumes
  from the queue front and still delivers the FULL token budget, and the
  chaos cell — a replica killed mid-decode reclaims every request-held
  page (``request_held == 0``) while the survivor completes the work.
"""

import math

import pytest

from horovod_tpu.serve.batcher import ContinuousBatcher
from horovod_tpu.serve.paging import (PagePool, PagePoolExhausted,
                                      PrefixCache, auto_pool_pages)
from horovod_tpu.serve.queue import Request
from toy_models import toy_transformer, uncached_greedy as _uncached_greedy


def _req(uid, prompt, max_new=8):
    return Request(uid=uid, prompt=list(prompt), max_new_tokens=max_new,
                   submitted_s=0.0)


# --------------------------------------------------------------- PagePool

class TestPagePool:
    def test_alloc_ref_unref_cycle(self):
        pool = PagePool(pages=5, page_tokens=16)
        assert pool.allocatable == 4
        got = [pool.alloc() for _ in range(4)]
        assert sorted(got) == [1, 2, 3, 4]      # page 0 is scratch
        assert pool.free_count() == 0 and pool.used_count() == 4
        pool.ref(got[0])
        assert pool.refcount(got[0]) == 2
        assert pool.unref(got[0]) is False      # still shared
        assert pool.unref(got[0]) is True       # last ref frees
        assert pool.free_count() == 1
        assert pool.alloc() == got[0]           # recycled

    def test_exhaustion_and_bad_refs(self):
        pool = PagePool(pages=3, page_tokens=16)
        pool.alloc(), pool.alloc()
        with pytest.raises(PagePoolExhausted):
            pool.alloc()
        with pytest.raises(ValueError):
            pool.ref(0)                         # scratch is unallocatable
        with pytest.raises(ValueError):
            pool.unref(1_000)

    def test_reclaim_hook_runs_outside_lock(self):
        """The hook re-enters pool.unref — it would deadlock if alloc
        held the pool lock across the callback."""
        pool = PagePool(pages=3, page_tokens=16)
        held = [pool.alloc(), pool.alloc()]
        pool.set_reclaim_hook(lambda: pool.unref(held.pop()))
        assert pool.alloc() in (1, 2)           # reclaimed and reissued
        assert pool.stats()["reclaims"] == 1

    def test_too_small_pool_rejected(self):
        with pytest.raises(ValueError):
            PagePool(pages=1, page_tokens=16)

    def test_auto_pool_pages_halves_dense_capacity(self):
        # bench --tiny shape: 4 slots x 96 tokens dense -> 192 paged
        # token rows (12 pages of 16) = exactly 2x lower KV bytes
        assert auto_pool_pages(4, 96, 16) == 12
        # floor: one max_seq request + scratch always fits
        assert auto_pool_pages(1, 48, 16) == 4


# ------------------------------------------------------------ PrefixCache

class TestPrefixCache:
    def _cache(self, pages=8, capacity=16):
        pool = PagePool(pages=pages, page_tokens=4)
        return pool, PrefixCache(pool, capacity)

    def test_block_walk_and_probe(self):
        pool, cache = self._cache()
        prompt = list(range(10))                # 2 full blocks + tail 2
        pages = [pool.alloc() for _ in range(3)]
        cache.insert(prompt, pages, first_token=7, max_abs=1.0)
        assert cache.probe(prompt) == 2
        assert cache.probe(prompt[:8] + [99, 98]) == 2   # same blocks
        assert cache.probe([99] + prompt[1:]) == 0       # first differs
        hit, exact = cache.lookup(prompt[:8] + [99, 98])
        assert hit == pages[:2] and exact is None
        hit, exact = cache.lookup(prompt)
        assert exact is not None
        assert list(exact[0]) == pages and exact[1] == 7

    def test_insert_refs_and_eviction_unrefs(self):
        pool, cache = self._cache()
        prompt = list(range(8))                 # 2 full blocks
        pages = [pool.alloc(), pool.alloc()]
        cache.insert(prompt, pages, 1, 1.0)     # 2 block + 1 exact entry
        assert len(cache) == 3
        # blocks ref once each; the exact entry refs both again
        assert pool.refcount(pages[0]) == 3
        assert pool.refcount(pages[1]) == 3
        cache.release_all()
        assert len(cache) == 0
        assert pool.refcount(pages[0]) == 1     # caller's refs survive
        assert pool.refcount(pages[1]) == 1

    def test_capacity_trim_evicts_lru(self):
        pool, cache = self._cache(capacity=2)
        pages = [pool.alloc(), pool.alloc()]
        cache.insert(list(range(8)), pages, 1, 1.0)
        assert len(cache) == 2                  # block 0 (LRU) trimmed
        assert cache.evictions == 1
        assert cache.probe(list(range(8))) == 0  # depth-0 gone: no chain

    def test_reclaim_one_frees_under_pressure(self):
        pool, cache = self._cache(pages=4)      # 3 allocatable
        pages = [pool.alloc(), pool.alloc()]
        cache.insert(list(range(8)), pages, 1, 1.0)
        pool.unref(pages[0]), pool.unref(pages[1])   # cache is sole owner
        pool.set_reclaim_hook(cache.reclaim_one)
        for _ in range(3):                      # 1 free + 2 reclaimable
            pool.alloc()
        assert len(cache) == 0                  # pressure drained the LRU
        with pytest.raises(PagePoolExhausted):
            pool.alloc()                        # cache empty, truly full

    def test_hash_collision_verified_against_tokens(self):
        pool, cache = self._cache()
        page = pool.alloc()
        cache.insert([1, 2, 3, 4], [page], 1, 1.0)
        # same (depth, hash) key would need hash([1,2,3,4]) == hash of a
        # different block; lookup verifies stored tokens so a mismatch
        # is a miss, never a wrong page
        hit, _ = cache.lookup([1, 2, 3, 5])
        assert hit == []


# ----------------------------------------------- page-aware admission

class TestPagedAdmission:
    def _batcher(self, pool_pages=4, page_tokens=16, probe=None,
                 slots=4, max_seq=48):
        return ContinuousBatcher(
            num_slots=slots, max_batch_tokens=10_000, admission_ms=50.0,
            decode_block=8, max_seq=max_seq, page_tokens=page_tokens,
            pool_pages=pool_pages, prefix_probe=probe)

    def test_pool_pages_cap_admission(self):
        # each request: prompt 17 + max_new 32 -> 48 written -> 3 pages
        b = self._batcher()
        for uid in ("a", "b"):
            b.offer(_req(uid, range(1, 18), max_new=32), now=0.0)
        admitted = b.admit(0.0)
        assert [a.request.uid for a in admitted] == ["a"]
        assert admitted[0].page_cost == 3
        assert b.committed_pages() == 3         # 3 + 3 > 4: b waits
        assert b.waiting() == 1

    def test_prefix_probe_discounts_page_cost(self):
        b = self._batcher(probe=lambda prompt: 1)
        for uid in ("a", "b"):
            b.offer(_req(uid, range(1, 18), max_new=32), now=0.0)
        admitted = b.admit(0.0)
        assert [a.request.uid for a in admitted] == ["a", "b"]
        assert all(a.page_cost == 2 for a in admitted)

    def test_single_request_capped_to_pool(self):
        # pool capacity 4*16 = 64 tokens; prompt 40 + max_new 64 would
        # write past it -> max_tokens capped (finish="cache_limit"),
        # the paged analogue of the dense max_seq cap
        b = self._batcher(max_seq=None)
        b.offer(_req("a", range(40), max_new=64), now=0.0)
        (a,) = b.admit(0.0)
        assert a.max_tokens == 4 * 16 - 40 + 1 == 25
        assert a.capped

    def test_preempt_newest_to_queue_front(self):
        b = self._batcher(pool_pages=100)
        for uid in ("old", "mid", "new"):
            b.offer(_req(uid, range(1, 9)), now=0.0)
        b.admit(0.0)
        assert b.occupancy() == 3
        victim = b.preempt_newest(now=1.0)
        assert victim.request.uid == "new"
        assert b.preemptions == 1
        assert victim.request.requeues == 1
        # requeued to the FRONT: next admission re-admits it first
        b.offer(_req("younger", range(1, 9)), now=1.0)
        readmitted = b.admit(1.0)
        assert [a.request.uid for a in readmitted] == ["new", "younger"]
        # exclude_slot protects the slot mid-prefill
        mid = next(a for a in b.active() if a.request.uid == "mid")
        survivor = b.preempt_newest(exclude_slot=None, now=2.0)
        assert survivor.request.uid == "younger"
        assert b.preempt_newest(exclude_slot=mid.slot, now=2.0) \
               .request.uid != "mid"

    def test_dense_batcher_unaffected(self):
        b = ContinuousBatcher(num_slots=4, max_batch_tokens=10_000,
                              admission_ms=50.0, decode_block=8)
        b.offer(_req("a", range(1, 9)), now=0.0)
        (a,) = b.admit(0.0)
        assert a.page_cost == 0
        assert b.committed_pages() == 0


# -------------------------------------------------------- engine (jax)

@pytest.fixture(scope="module")
def tiny_lm():
    return toy_transformer(max_seq=48)


def _engine(model, params, slots=3, **kw):
    """Direct-call engines get a roomy pool (the replica loop is what
    answers PagePoolExhausted; tests that WANT pressure size it down)."""
    from horovod_tpu.serve.paging import PagedDecodeEngine

    kw.setdefault("page_tokens", 16)
    kw.setdefault("pool_pages", 12)
    return PagedDecodeEngine(model, params, num_slots=slots, **kw)


def _generate(eng, slot, prompt, n):
    token, max_abs = eng.prefill(slot, prompt)
    assert math.isfinite(max_abs)
    out, pos = [token], len(prompt)
    for _ in range(n - 1):
        (t,), _ = eng.decode([slot], [out[-1]], [pos])
        out.append(t)
        pos += 1
    return out


def test_paged_parity_across_buckets(tiny_lm):
    """Gathering K/V through traced page tables must be token-for-token
    identical to the uncached apply — across prompt buckets, including
    prompts that span multiple pages."""
    model, params = tiny_lm
    eng = _engine(model, params)
    for slot, prompt in ((0, [5, 4, 3, 2, 1]), (1, list(range(1, 18))),
                        (2, list(range(2, 37)))):
        assert _generate(eng, slot, prompt, 6) == \
            _uncached_greedy(model, params, prompt, 6), len(prompt)


def test_shared_prefix_cow_isolation(tiny_lm):
    """Two requests share a 16-token prefix block; the second reuses the
    first's page and must copy-on-write before its first divergent
    write — both must still match the uncached reference exactly."""
    model, params = tiny_lm
    eng = _engine(model, params)
    shared = list(range(1, 17))
    a, b = shared + [20, 21], shared + [30]
    token_a, _ = eng.prefill(0, a)
    cows0 = eng.cow_copies
    token_b, _ = eng.prefill(1, b)
    assert eng.reused_tokens >= 16              # block hit on b's prefill
    gen = {0: [token_a], 1: [token_b]}
    pos = {0: len(a), 1: len(b)}
    for _ in range(5):
        ids, _ = eng.decode([0, 1], [gen[0][-1], gen[1][-1]],
                            [pos[0], pos[1]])
        for s, t in zip((0, 1), ids):
            gen[s].append(t)
            pos[s] += 1
    assert eng.cow_copies > cows0               # sharing actually copied
    assert gen[0] == _uncached_greedy(model, params, a, 6)
    assert gen[1] == _uncached_greedy(model, params, b, 6)


def test_exact_replay_zero_prefill_compute(tiny_lm):
    """A byte-identical repeat prompt replays the cached pages + first
    token: computed_tokens must NOT move (zero prefill compute), and the
    replayed slot must still decode exactly like the reference."""
    model, params = tiny_lm
    eng = _engine(model, params)
    prompt = list(range(3, 24))
    first = _generate(eng, 0, prompt, 4)
    computed = eng.computed_tokens
    repeat = _generate(eng, 1, prompt, 4)
    assert eng.exact_hits == 1
    assert eng.computed_tokens == computed      # nothing recomputed
    assert repeat == first == _uncached_greedy(model, params, prompt, 4)
    assert eng.prefix_hit_rate() > 0


def test_zero_steady_state_compiles_canary(tiny_lm):
    """Slot churn + page-table growth + COW + prefix hits + preemption
    release must all run through the already-compiled programs: ONE
    decode program, one prefill program per bucket, one COW copy."""
    model, params = tiny_lm
    eng = _engine(model, params, slots=2)
    eng.prefill(0, [1] * 16)                    # bucket 16
    eng.prefill(0, list(range(2, 22)))          # bucket 32
    eng.decode([0], [1], [20])
    warm = eng.compiles_total()
    shared = list(range(2, 18))
    for step in range(6):
        slot = step % 2
        eng.prefill(slot, shared + [25 + step])  # block hit + suffix
        (t,), _ = eng.decode([slot], [3], [17])  # COW + table growth
        eng.decode([slot], [t], [18])
    eng.release_slot(0)                         # preemption release path
    eng.prefill(0, shared + [40])
    eng.decode([0, 1], [1, 2], [17, 19])
    assert eng.compiles_total() == warm
    assert eng.cow_copies > 0
    stats = eng.stats()
    assert stats["pages"]["prefix_hit_rate"] > 0
    assert stats["compiles"]["page_copy"] == 1


def test_exhaustion_rolls_back_and_recovers(tiny_lm):
    """A prefill the pool cannot hold must raise PagePoolExhausted and
    roll back every ref it took — the pool is exactly as before, and the
    same prefill succeeds once a victim releases."""
    model, params = tiny_lm
    eng = _engine(model, params, slots=2, pool_pages=4)  # 3 allocatable
    eng.prefill(0, list(range(1, 34)))          # 33 tokens -> 3 pages
    assert eng.pool.free_count() == 0
    with pytest.raises(PagePoolExhausted):
        eng.prefill(1, list(range(40, 57)))     # needs 2 fresh pages
    assert eng.pool.free_count() == 0           # rollback: nothing leaked
    assert eng._tables[1] == []
    eng.release_slot(0)                         # victim preempted
    token, _ = eng.prefill(1, list(range(40, 57)))
    assert isinstance(token, int)
    assert eng.page_stats()["request_held"] >= 2


def test_release_all_reclaims_every_request_page(tiny_lm):
    """Quarantine path: request_held == 0 after release_all — the pool
    analogue of the fusion-buffer ``leases == 0`` chaos pin."""
    model, params = tiny_lm
    eng = _engine(model, params, slots=3)
    for slot, n in ((0, 5), (1, 20), (2, 33)):
        _generate(eng, slot, list(range(1, n + 1)), 3)
    assert eng.page_stats()["request_held"] > 0
    eng.release_all()
    stats = eng.page_stats()
    assert stats["request_held"] == 0
    # every page is either free or held only by the prefix cache
    assert stats["free"] + len(eng.prefix.held_pages()) \
        == eng.pool.allocatable


def test_paged_engine_reports_no_kv_read_share():
    """The paged decode step gathers every mapped page into a row view
    and attends over all of it (``cached_attention``), also where that
    view is whole lane tiles long (7 + 1 pages of 16): no kernel in its
    program, no share in its stats."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer
    from horovod_tpu.ops.pallas._backend import kernels_in

    model = Transformer(vocab_size=61, d_model=32, num_layers=1,
                        num_heads=2, d_ff=64, max_seq=112, causal=True,
                        dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    eng = _engine(model, params, pool_pages=24)
    assert eng._table_arr.shape[1] * eng.page_tokens == 128
    _generate(eng, 0, [1, 2, 3], 4)
    assert eng.stats()["decode_steps"] == 3
    assert eng.stats()["decode_kv_read_share"] is None
    assert eng.stats()["decode_write_fused"] is None
    program = jax.make_jaxpr(eng._decode_impl)(
        eng._params, eng._cache, jnp.zeros((3, 1), jnp.int32),
        jnp.zeros((3,), jnp.int32), jnp.asarray(eng._table_arr))
    assert kernels_in(program) == []


def test_paged_pool_bytes_in_memory_ledger(tiny_lm):
    """kv_pages is a first-class device subsystem: the pool registry
    feeds memory.py's ledger and the reconciliation set."""
    from horovod_tpu import memory
    from horovod_tpu.serve import paging

    model, params = tiny_lm
    eng = _engine(model, params)
    assert "kv_pages" in memory.DEVICE_SUBSYSTEMS
    assert paging.total_pool_bytes() >= eng.cache_bytes() > 0
    ledger = memory.tracker().ledger()
    assert ledger["subsystems"]["kv_pages"]["bytes"] >= eng.cache_bytes()


def test_policy_paged_knobs_from_env(monkeypatch):
    from horovod_tpu.serve.api import ServePolicy

    monkeypatch.setenv("HOROVOD_SERVE_PAGED", "1")
    monkeypatch.setenv("HOROVOD_SERVE_PAGE_TOKENS", "32")
    monkeypatch.setenv("HOROVOD_SERVE_PAGE_POOL", "64")
    monkeypatch.setenv("HOROVOD_SERVE_PREFIX_CACHE", "9")
    policy = ServePolicy.from_env()
    assert policy.paged and policy.page_tokens == 32
    assert policy.page_pool == 64 and policy.prefix_cache == 9
    policy = ServePolicy.from_env(paged=False)
    assert not policy.paged


def test_non_power_of_two_page_tokens_rejected(tiny_lm):
    model, params = tiny_lm
    with pytest.raises(ValueError, match="power of two"):
        _engine(model, params, page_tokens=12)


def test_pool_too_small_for_max_seq_rejected(tiny_lm):
    model, params = tiny_lm
    with pytest.raises(ValueError, match="max_seq"):
        _engine(model, params, pool_pages=3)    # 2 allocatable < 3 blocks


# ------------------------------------------------------------ e2e serve

def test_preempted_request_completes_full_budget(tiny_lm):
    """The ISSUE 17 regression pin: under pool pressure the newest
    request is preempted to the queue FRONT and — once pages free — must
    complete with its FULL token budget, counted as a requeue, never
    lost, never truncated."""
    from horovod_tpu.serve import serve as hvd_serve

    model, params = tiny_lm
    handle = hvd_serve(model, params, replicas=1, paged=True,
                       page_tokens=16, page_pool=5, prefix_cache=16,
                       slots=4, max_new_tokens=32, admission_ms=5.0,
                       decode_block=4, max_batch_tokens=4096,
                       quarantine=False)
    try:
        shared = list(range(1, 17))             # one full shared block
        uids = [handle.submit(shared + [17 + i]) for i in range(3)]
        outs = [handle.result(u, timeout=120.0) for u in uids]
        assert all(len(o.tokens) == 32 for o in outs)   # full budget
        assert all(o.finish == "length" for o in outs)
        replica = handle._replicas[0]
        assert replica.engine.preemptions >= 1
        assert sum(o.requeues for o in outs) >= 1
        assert replica.stats()["pages"]["request_held"] == 0
    finally:
        handle.close()


def test_chaos_replica_death_reclaims_pages(tiny_lm):
    """Chaos cell: one replica's decode dies mid-flight. Its requests
    requeue (zero lost), the survivor completes them, and the dead
    replica's pool holds ZERO request pages (request_held == 0)."""
    import time as _time

    from horovod_tpu.serve import serve as hvd_serve

    model, params = tiny_lm
    handle = hvd_serve(model, params, replicas=2, paged=True,
                       page_tokens=16, slots=4, max_new_tokens=4,
                       admission_ms=5.0, decode_block=4,
                       max_batch_tokens=4096, quarantine=True)
    try:
        victim = handle._replicas[0]

        def killed_decode(slots, tokens, positions):
            raise RuntimeError("chaos: replica killed mid-decode")

        victim.engine.decode = killed_decode
        uids, deadline = [], _time.monotonic() + 30.0
        while not victim.quarantined and _time.monotonic() < deadline:
            uids.append(handle.submit(list(range(1, 9)) + [len(uids) % 50]))
            _time.sleep(0.02)
        assert victim.quarantined, "victim replica never pulled work"
        outs = [handle.result(u, timeout=120.0) for u in uids]
        assert all(len(o.tokens) == 4 for o in outs)    # zero lost
        assert all(o.rank == 1 for o in outs if o.requeues)
        assert victim.engine.page_stats()["request_held"] == 0
    finally:
        handle.close()
