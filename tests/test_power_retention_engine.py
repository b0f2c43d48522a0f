"""The dense serving engine over power retention's cache of states alone
(Brumby's toy configuration, ``tests/toy_models.py``): prompts in pieces
that carry the slot's state, the counters and spans of a prefill, a
counter that rides through the pieces, an idle slot's state. The
mechanism and the model are ``tests/test_power_retention.py``; prefill
in pieces, then decode, against the reference's one forward is this
family's share of ``tests/test_engine_contract.py``, with a reused slot
and the state the padding would leave, and ``hvd.serve()`` of
``tests/test_engine_serving.py``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.runners.serve_brumby import build_model
from horovod_tpu.models import hybrid
from horovod_tpu.serve import kv_cache
from horovod_tpu.serve.kv_cache import DecodeEngine
from toy_models import (BRUMBY_CFG as CFG, brumby_reference as reference,
                        brumby_weights as weights, prefill_spans,
                        step_logits, tokens)

F32_TOL = 5e-5
GROUPS, D = 2, 32
TURNS = D // 2 + 1          # rows of distances: the cache pads to these


@pytest.fixture(scope="module")
def served():
    return weights(), build_model(CFG)


# the engine cuts a prompt into pieces of PREFILL_CHUNK (set to the toy
# mixer's own chunk, 256) and pads the last one
CHUNK = 256


@pytest.fixture
def pieces(monkeypatch):
    monkeypatch.setattr(kv_cache, "PREFILL_CHUNK", CHUNK)
    return CHUNK


def greedy(engine, slot, prompt, steps):
    """The tokens the engine serves ``prompt`` in ``slot``: the prefill's
    first and ``steps`` decode steps after it, each fed from the feed."""
    first, _ = engine.prefill(slot, prompt)
    out = [first]
    for t in range(steps):
        ids, _ = engine.decode([slot], None, [len(prompt) + t])
        out.append(ids[0])
    return out


@pytest.mark.parametrize("second_len", [150, CHUNK + 40],
                         ids=["one_piece", "two_pieces"])
def test_a_slots_second_request_starts_from_an_empty_state(served, pieces,
                                                           second_len):
    """A prompt's first piece continues from zeros and not from what the
    slot's last request left (a prompt in pieces reads the slot's row,
    where a prompt in one program made a fresh one): the second request
    of a slot is served the tokens a fresh engine serves it."""
    params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    greedy(engine, 1, tokens(2 * CHUNK + 30, seed=50).tolist(), 12)
    # what the first request left there is nothing like zeros
    left = np.asarray(jax.tree.leaves(engine._cache)[0][1])
    assert np.abs(left).max() > 1e-3
    second = tokens(second_len, seed=51).tolist()
    fresh = DecodeEngine(model, params, num_slots=2)
    assert greedy(engine, 1, second, 24) == greedy(fresh, 1, second, 24)


def test_the_prefill_counters_and_the_spans_chunks(served, pieces):
    """``stats()``: programs enqueued, positions computed (the padding
    with them) and the prompts' own tokens; the ``engine.prefill`` span
    stays one a prompt and carries ``chunks``, ``bucket`` the positions
    computed."""
    params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    began = time.time()
    lengths = [41, CHUNK, 2 * CHUNK + 5]
    for slot, n in zip((0, 1, 0), lengths):
        engine.prefill(slot, tokens(n, seed=n).tolist()).collect()
    stats = engine.stats()
    assert stats["prefill_chunks"] == 1 + 1 + 3
    assert stats["prefill_positions"] == 5 * CHUNK
    assert stats["prefill_tokens"] == sum(lengths)
    assert stats["compiles"] == {"prefill_last": 1, "prefill_chunk": 1}
    assert stats["cache_donated"] is False      # no decode step yet
    assert engine._donated["prefill"] is True
    assert [(s["prompt_len"], s["chunks"], s["bucket"])
            for s in prefill_spans(began)] == [
                (41, 1, CHUNK), (CHUNK, 1, CHUNK),
                (2 * CHUNK + 5, 3, 3 * CHUNK)]


def test_a_counter_rides_through_the_pieces(pieces):
    """A model that resumes may also count (an expert layer's
    ``expert_counts`` is no slot's row): every piece adds its own true
    tokens' pairs to the counter as it stands, the first piece's zeroing
    does not touch it, and the padding of the last is not counted."""
    model = hybrid.HybridDecoder(
        vocab_size=64, d_model=32, d_ff=64, num_heads=2, num_kv_heads=1,
        head_dim=16, mixers=(hybrid.POWER_RETENTION,) * 2,
        mlps=(hybrid.DENSE_MLP, hybrid.EXPERTS_MLP),
        experts=dict(num_experts=4, top_k=2, d_ff=32), scale_depth=None,
        max_seq=1024, dtype=jnp.float32)
    assert model.resumable_prefill and model.counts_active_rows
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = DecodeEngine(model, params, num_slots=2)
    # one program for the three lengths: a token's row sees nothing of
    # the padding after it
    forward = jax.jit(lambda toks: model.apply({"params": params}, toks))
    total = 0
    for slot, n in ((1, 2 * CHUNK + 40), (1, 90), (0, CHUNK)):
        prompt = tokens(n, seed=n) % 64
        padded = np.zeros((1, 3 * CHUNK), np.int32)
        padded[0, :n] = prompt
        want = np.asarray(forward(padded))[0, n - 1]
        first, max_abs = engine.prefill(slot, prompt.tolist())
        assert first == want.argmax()
        assert abs(max_abs - np.abs(want).max()) < F32_TOL
        total += n
        counts = engine.expert_counts()
        assert counts.shape == (1, 3, 4)
        assert counts[0, 0].sum() == 2 * total and not counts[0, 1:].any()


def test_a_piece_is_no_longer_than_the_model_allows():
    """``max_seq`` under PREFILL_CHUNK (the constant as it stands, 1,024):
    the pieces are ``max_seq`` long, and the model takes them."""
    cfg = dict(CFG, max_seq=128)
    model = build_model(cfg)
    engine = DecodeEngine(model, weights(), num_slots=1)
    toks = tokens(100, seed=60)
    first, _ = engine.prefill(0, toks[:90].tolist())
    assert first == reference(toks[:90])[-1].argmax()
    assert engine.stats()["prefill_positions"] == 128


def test_the_cache_holds_states_and_nothing_else(served):
    """No leaf with a position axis: every byte is ``state``, the
    key/value read share is ``None`` (nothing to divide by, and no
    warning), and the donation is taken."""
    import warnings

    params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    by_kind = engine.cache_bytes_by_kind()
    assert by_kind == {"kv": 0, "compressed": 0,
                       "state": 2 * 2 * GROUPS * TURNS * D * (D + 1) * 4}
    assert engine.cache_bytes() == by_kind["state"]
    leaves = jax.tree_util.tree_leaves_with_path(engine._cache)
    assert sorted(x.shape for _, x in leaves) == sorted(
        [(2, GROUPS, TURNS, D, D), (2, GROUPS, TURNS, D)] * 2)
    assert "decode_attention" not in engine.decode_kernels
    assert engine._dense_len is None
    first, _ = engine.prefill(0, tokens(41).tolist())
    engine.decode([0], [first], [41]).collect()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        stats = engine.stats()
    assert stats["decode_kv_read_share"] is None
    assert stats["cache_bytes_by_kind"] == by_kind
    assert stats["cache_donated"] is True


def test_an_idle_slots_state_stays_finite(served):
    """The decode program runs every slot every step: a row that is not
    active runs token 0 at position 0 and rewrites its state. That state
    is a geometric series in the token's own gates (the slowest keeps
    0.999 a step), so it nears a finite fixed point: after three
    thousand steps it is finite and has all but stopped growing, and the
    active row beside it is untouched by it."""
    params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    toks = tokens(60, seed=40)
    want = reference(toks)
    first, _ = engine.prefill(0, toks[:20].tolist())
    assert first == want[19].argmax()

    @jax.jit
    def idle(cache, n):
        def one(_, cache):
            _, mutated = engine._model.apply(
                {"params": params, "cache": cache},
                jnp.zeros((2, 1), jnp.int32),
                positions=jnp.zeros((2,), jnp.int32), train=False,
                mutable=["cache"])
            # only row 1 idles: row 0 keeps the prompt's state
            return jax.tree.map(lambda old, new: old.at[1].set(new[1]),
                                cache, mutated["cache"])
        return jax.lax.fori_loop(0, n, one, cache)

    before = idle(engine._cache, 2000)
    engine._cache = idle(before, 1000)
    for (_, then), (_, now) in zip(
            jax.tree_util.tree_leaves_with_path(before),
            jax.tree_util.tree_leaves_with_path(engine._cache)):
        now, then = np.asarray(now[1]), np.asarray(then[1])
        assert np.isfinite(now).all()
        assert np.abs(now).max() <= 1.5 * np.abs(then).max()
    for t in range(20, 30):
        got = step_logits(engine, [toks[t], 0], [t, 0])[0]
        assert np.abs(got - want[t]).max() < F32_TOL, t


