"""Generation by diffusion over blocks behind the dense serving engine
(SDAR-30B-A3B-Chat at ``benchmark/configs/sdar-30b-a3b.json``'s toy
sizes, seeded weights, against ``benchmark/reference_sdar.py``): the
served trajectory against the published loop, the block-causal prefill
and every pass's logits against the reference's forward, the three
kernel forms a block needs against their ``jax.numpy`` forms, the
request's count at dispatch against what the collected pass delivers,
and ``hvd.serve()`` end to end. ``tests/test_engine_contract.py`` serves
a block model of its own through the contract; the planted faults and
the float8 control are ``benchmark/tests/test_serve_sdar.py``'s.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sdar
from horovod_tpu.models import hybrid
from horovod_tpu.ops.pallas import grouped_decode_attention as grouped
from horovod_tpu.ops.pallas import kv_cache_write
from horovod_tpu.serve.batcher import BlockRequest
from horovod_tpu.serve.kv_cache import DecodeEngine
from horovod_tpu.serve.queue import Request
from toy_models import (kexaone, granite, sdar, sdar_reference, tokens)

flash = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")


def block_request(engine, prompt, max_new, slot=0):
    return BlockRequest(
        slot=slot, request=Request(uid="u", prompt=list(prompt),
                                   max_new_tokens=max_new),
        prompt_len=len(prompt), position=len(prompt), max_tokens=max_new,
        block_len=engine.block_len, unmask=engine.unmask)


def serve_alone(engine, prompt, max_new, slot=0):
    """One request through the engine as the replica's loop drives it
    (prefill, then passes until the request is done by its own count),
    asserting at every pass that what was counted at dispatch is what the
    collected pass delivers. Returns the request and the passes run."""
    req = block_request(engine, prompt, max_new, slot)
    token, _ = engine.prefill(slot, list(prompt)).collect()
    assert token is None
    passes = 0
    while not req.done:
        pending = engine.decode([slot], None, [req.position],
                                [req.next_unmask()])
        counted = req.dispatched()
        ids, max_abs = pending.collect()
        assert req.take(ids[0]) == counted and np.isfinite(max_abs[0])
        passes += 1
    assert req.received == req.target == req.enqueued
    return req, passes


@pytest.fixture(scope="module")
def engine():
    cfg, params, model = sdar()
    return DecodeEngine(model, params, num_slots=2)


# prompts of every length modulo the block, one shorter than a block, and
# answers that end inside a block (10, 7, 13: 47, 45, 52 in all) and on
# its edge (8: 44)
@pytest.mark.parametrize("prompt_len,max_new", [
    (37, 10), (36, 8), (38, 7), (39, 13), (3, 6)])
def test_the_served_trajectory_is_the_published_loops(engine, prompt_len,
                                                      max_new):
    """Token for token and pass for pass, the float32 program behind the
    engine against the reference's loop, which recomputes the whole
    sequence every pass and keeps no cache."""
    cfg, params, _ = sdar()
    prompt = tokens(prompt_len, seed=prompt_len).tolist()
    req, passes = serve_alone(engine, prompt, max_new, slot=prompt_len % 2)
    want_ids, want_passes, run = reference_sdar.generate(
        params, prompt, reference_sdar.frozen(cfg), max_new)
    answer = req.answer()
    assert answer["tokens"] + answer["cut"] == want_ids
    assert answer["passes"] == want_passes
    assert len(answer["tokens"]) == max_new
    assert len(answer["cut"]) == -(prompt_len + max_new) % 4
    # the reference's passes are the denoising ones; the engine commits
    # every block but the last in a pass of its own
    blocks = len({start for start, _ in run})
    assert passes == len(run) + blocks - 1


def block_logits(engine, blocks, starts):
    """One teacher-forced pass over all of the engine's rows, as
    ``_block_pass`` runs the model: the (rows, block, vocab) logits; the
    cache keeps the pass's columns."""
    if not hasattr(engine, "pass_for_tests"):
        model = engine._model
        engine.pass_for_tests = jax.jit(lambda p, c, t, q: model.apply(
            {"params": p, "cache": c}, t, positions=jnp.maximum(q, 0),
            train=False, mutable=["cache"], active=q >= 0))
    logits, mutated = engine.pass_for_tests(
        engine._params, engine._cache, jnp.asarray(blocks, jnp.int32),
        jnp.asarray(starts, jnp.int32))
    engine._cache = mutated["cache"]
    return np.asarray(logits)


# float32 against float32 on the CPU; bfloat16 (weights, activations and
# cache, as the cell runs) against the float32 reference over the same
# bfloat16 weights: logits of a standard deviation of 1.2-1.4, the widest
# of every logit of every pass measured 0.17-0.24 apart (0.10-0.16 in the
# mean of the passes' widest): what bfloat16 rounds in six layers whose
# outputs are three quarters of the stream, an expert chosen otherwise
# among them (the planted faults' served tokens lie 0.9-5.9 under the
# reference's best at this size: benchmark/tests/test_serve_sdar.py)
@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5), ("bfloat16", 0.4)])
@pytest.mark.parametrize("prompt_len", [36, 37, 38, 39])
def test_prefill_then_passes_are_the_references_forward(dtype, tol,
                                                        prompt_len):
    """The block-causal prefill of the prompt's whole blocks, then every
    pass of the reference's own trajectory through the slot's cache
    (teacher forced, the commit among them), against the logits the
    reference computed for that pass from the whole sequence."""
    cfg, params, model = sdar(dtype=dtype)
    eng = DecodeEngine(model, params, num_slots=2)
    prompt = tokens(prompt_len, seed=prompt_len + 7).tolist()
    ids, when, run = reference_sdar.generate(
        params, prompt, reference_sdar.frozen(dict(cfg, dtype="float32")),
        14)
    eng.prefill(1, prompt).collect()
    final = np.asarray(prompt + ids)
    known = np.concatenate([np.full(prompt_len, -1), when])
    number = {}
    for start, want in run:
        here = slice(start, start + 4)
        n = number[start] = number.get(start, -1) + 1
        if n == 0 and start > prompt_len - prompt_len % 4:
            # the block before is finished: its commit pass
            before = slice(start - 4, start)
            block_logits(eng, [[0] * 4, final[before]], [-1, start - 4])
        state = np.where(known[here] >= n, cfg["mask_id"], final[here])
        got = block_logits(eng, [[0] * 4, state], [-1, start])[1]
        assert np.abs(got - want).max() < tol, (start, n)


def test_the_prompt_form_is_block_causal():
    """The model without a cache over a whole sequence (the flash
    kernel's block form) against the reference's forward; a causal mask
    inside the block is another model."""
    cfg, params, model = sdar()
    toks = tokens(203, seed=3)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :203] = toks
    got = np.asarray(model.apply({"params": params}, jnp.asarray(padded)))
    want = sdar_reference(cfg, params, padded[0])
    # row 200 starts a block: 201-203 of the padding lie in it
    assert np.abs(got[0, :200] - want[:200]).max() < 5e-5
    causal = np.asarray(model.clone(block_len=1).apply(
        {"params": params}, jnp.asarray(padded)))
    assert np.abs(causal[0, :200] - want[:200]).max() > 1e-2


# ---------------------------------------------------------------- kernels

def _qkv(seq, seed, heads=2, dim=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (1, heads, seq, dim), jnp.float32)
            for k in keys]


# one side resident (256, 512: the causal tile walk) and the general
# kernels (blocks of 128 x 256 over 512)
@pytest.mark.parametrize("seq,block_len,blocks", [
    (256, 4, {}), (512, 4, {}), (512, 8, dict(
        block_q=128, block_k=256, bwd_block_q=128, bwd_block_k=256))])
def test_flash_attention_with_a_block_length(seq, block_len, blocks):
    q, k, v, do = _qkv(seq, seq + block_len)
    kernel = lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True, block_len=block_len, **blocks)
    plain = lambda q, k, v: flash.attention_reference(
        q, k, v, causal=True, block_len=block_len)
    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    assert jnp.abs(out - want).max() < 5e-6
    for got, ref in zip(vjp(do), want_vjp(do)):
        assert jnp.abs(got - ref).max() < 5e-6
    # and it is not the causal mask
    assert jnp.abs(out - flash.attention_reference(
        q, k, v, causal=True)).max() > 1e-2


def test_a_block_length_of_one_is_todays_causal_kernel():
    q, k, v, _ = _qkv(256, 1)
    today = jax.make_jaxpr(lambda *a: jax.grad(lambda *b: flash.flash_attention(
        *b, causal=True).sum(), argnums=(0, 1, 2))(*a))(q, k, v)
    one = jax.make_jaxpr(lambda *a: jax.grad(lambda *b: flash.flash_attention(
        *b, causal=True, block_len=1).sum(), argnums=(0, 1, 2))(*a))(q, k, v)
    assert str(today) == str(one)
    assert (flash.flash_attention(q, k, v, causal=True)
            == flash.flash_attention(q, k, v, causal=True,
                                     block_len=1)).all()


@pytest.mark.parametrize("bad", [dict(causal=False), dict(q_offset=4),
                                 dict(block_len=3)])
def test_a_block_length_needs_whole_blocks_from_position_zero(bad):
    q, k, v, _ = _qkv(256, 2)
    with pytest.raises(ValueError, match="block_len"):
        flash.flash_attention(q, k, v, **dict(
            dict(causal=True, block_len=4), **bad))


def test_a_blocks_columns_are_written_in_place():
    rows, heads, dim, length, block = 3, 2, 16, 256, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    cache = jax.random.normal(keys[0], (rows, heads, dim, length))
    new = jax.random.normal(keys[1], (rows, block, heads, dim))
    starts = jnp.asarray([0, 124, 252], jnp.int32)    # a tile's last lanes
    got = np.asarray(kv_cache_write.write_block(cache, new, starts))
    want = np.asarray(cache).copy()
    for b, start in enumerate(np.asarray(starts)):
        want[b, :, :, start:start + block] = np.asarray(
            new[b]).transpose(1, 2, 0)
    assert (got == want).all()
    with pytest.raises(ValueError, match="does not divide"):
        kv_cache_write.write_block(cache, jnp.zeros((rows, 3, heads, dim)),
                                   starts)


def test_a_blocks_queries_attend_the_cache_up_to_the_blocks_end():
    rows, groups, per, dim, length, block = 3, 2, 4, 16, 1024, 4
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (rows, block, groups, per, dim))
    k_cache = jax.random.normal(keys[1], (rows, groups, dim, length))
    v_cache = jax.random.normal(keys[2], (rows, groups, dim, length))
    starts = jnp.asarray([0, 508, 1020], jnp.int32)   # across a tile's edge
    got = grouped.grouped_block_attention(q, k_cache, v_cache, starts,
                                          dim ** -0.5)
    s = jnp.einsum("btgrd,bgdj->btgrj", q, k_cache) * dim ** -0.5
    seen = jnp.arange(length)[None, :] < (starts + block)[:, None]
    s = jnp.where(seen[:, None, None, None], s, -jnp.inf)
    want = jnp.einsum("btgrj,bgdj->btgrd", jax.nn.softmax(s, axis=-1),
                      v_cache)
    assert jnp.abs(got - want).max() < 2e-6
    # what the engine counts for the step: tiles up to the block's end
    assert grouped.live_tiles(np.asarray(starts) + block - 1, length) == (
        1 + 1 + 2, 3 * 2, 4 + 512 + 1024)


# ------------------------------------------------------------ the request

@pytest.mark.parametrize("prompt_len,max_new,block,unmask", [
    (37, 10, 4, 2), (36, 9, 4, 2), (39, 1, 4, 2), (5, 17, 4, 1),
    (10, 6, 8, 4), (7, 7, 2, 2)])
def test_a_requests_count_is_fixed_at_dispatch(prompt_len, max_new, block,
                                               unmask):
    """The schedule alone (no model): what ``dispatched`` counts is what
    a pass that unmasks that many positions delivers, the request is done
    by length with whole blocks generated, and the answer is cut where it
    ends."""
    req = BlockRequest(
        slot=0, request=Request(uid="u", prompt=[1] * prompt_len,
                                max_new_tokens=max_new),
        prompt_len=prompt_len, position=prompt_len, max_tokens=max_new,
        block_len=block, unmask=unmask)
    whole = -(-(prompt_len + max_new) // block) * block
    assert req.target == whole - prompt_len
    assert req.committed_tokens == whole
    first = prompt_len - prompt_len % block
    assert req.position == first
    rng = np.random.default_rng(prompt_len)
    masked = np.arange(block) >= prompt_len % block
    commits = 0
    while not req.done:
        start, count = req.position, req.next_unmask()
        assert req.dispatched() == count
        ids = np.full(block, -1)
        if count:
            took = rng.permutation(np.flatnonzero(masked))[:count]
            ids[took] = 100 + start + took
            masked[took] = False
            assert req.position == start
        else:
            assert not masked.any() and req.position == start + block
            masked[:] = True
            commits += 1
        assert req.take(ids.tolist()) == count
    answer = req.answer()
    assert answer["tokens"] == [100 + prompt_len + i for i in range(max_new)]
    assert len(answer["cut"]) == whole - prompt_len - max_new
    # every block but the last is committed
    assert commits == (whole - first) // block - 1
    assert all(0 <= p < block // unmask for p in answer["passes"])


# ------------------------------------------------------------- hvd.serve()

def test_hvd_serve_serves_a_block_model():
    import horovod_tpu as hvd

    cfg, params, model = sdar()
    prompts = [tokens(n, seed=40 + n).tolist() for n in (21, 34, 50, 7)]
    news = [9, 12, 5, 16]
    handle = hvd.serve(model, params, slots=2, max_new_tokens=32,
                       max_batch_tokens=2 * cfg["max_seq"])
    try:
        uids = [handle.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        done = [handle.result(uid, timeout=300.0) for uid in uids]
        stats = handle.stats()["replicas"][0]
    finally:
        handle.close()
    frozen = reference_sdar.frozen(cfg)
    for prompt, n, out in zip(prompts, news, done):
        ids, when, _ = reference_sdar.generate(params, prompt, frozen, n)
        assert out.finish == "length" and out.tokens == ids[:n]
        assert out.cut == ids[n:] and out.passes == when
        assert 0 < out.ttft_s <= out.latency_s
    engine = stats["engine"]
    assert engine["block_len"] == 4 and engine["cache_donated"]
    # every generated position was unmasked once; a commit unmasks none
    assert engine["tokens_unmasked"] == sum(
        len(out.tokens) + len(out.cut) for out in done)
    assert engine["blocks_committed"] == engine["commit_row_passes"] > 0
    assert engine["row_passes"] > engine["tokens_unmasked"] / 2
    assert engine["compiles"]["decode"] == 1


def test_a_block_model_needs_mixers_with_a_block_form():
    model = hybrid.HybridDecoder(
        vocab_size=61, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
        head_dim=16, mixers=("full", "lightning"), max_seq=64, block_len=4,
        mask_id=60, denoising_steps=2)
    with pytest.raises(ValueError, match="block form"):
        model.serving()
    with pytest.raises(ValueError, match="mask_id"):
        model.clone(mixers=("full",), mask_id=None).serving()


# ------------------------------------------- the repair in touched code

# one forward of the two accepted configurations that build their layers
# through ``hybrid._grouped_query``, read at the parent commit (f76a6b4)
# before ``rotary`` came from the model's field: the parameter tree's
# paths and shapes, the last row's first logits and the logits' absolute
# sum at the toy sizes over ``tokens(96, seed=5)``. The values stay the
# parent's. Since PR 47 a token's grouped expert products are added to
# its sum in the order of the experts, not of ``k`` (granite's toy holds
# 8 of 16 experts, so a token has several pairs here): the same float32
# products in another order of addition, which moved granite's three
# logits by 1.7e-6 at the most, so ``ATOL`` is 5e-6 where it was 1e-6
BEFORE = {
    kexaone: ("8a5e997a59a90361", [0.3181234896183014, 0.7071113586425781,
                                   1.1480897665023804], 62244.125),
    granite: ("b9339c8cc28d9b7a", [-0.2803247272968292, -2.2304561138153076,
                                   3.037230968475342], 61230.390625)}
ATOL = 5e-6


@pytest.mark.parametrize("family", [kexaone, granite],
                         ids=["kexaone", "granite"])
def test_the_accepted_grouped_query_models_build_what_they_built(family):
    import hashlib

    cfg, params, model = family()
    tree, first, total = BEFORE[family]
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    paths = sorted((jax.tree_util.keystr(p), tuple(x.shape)) for p, x
                   in jax.tree_util.tree_leaves_with_path(init))
    assert hashlib.sha256(repr(paths).encode()).hexdigest()[:16] == tree
    for i, kind in enumerate(model.mixers):
        if kind in (hybrid.FULL, hybrid.WINDOW):
            fields = hybrid.mixer_of(kind).fields(model, i)
            assert fields["rotary"] == (kind == hybrid.WINDOW)
            assert fields["rope_theta"] == model.rope_theta
            assert fields["block_len"] == 1
    logits = np.asarray(model.apply(
        {"params": params}, jnp.asarray(tokens(96, seed=5)[None], jnp.int32)))
    assert np.allclose(logits[0, -1, :3], first, rtol=0, atol=ATOL)
    assert abs(np.abs(logits).sum() - total) < 1e-2 * 1e-3 * total
