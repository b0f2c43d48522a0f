"""``ops/pallas/expert_combine.py``: the way back from a grouped expert
product in one pass (interpret mode here; ``tests/test_tpu_compile.py``
puts it before Mosaic at the cells' shapes), and that the prefill
programs of the models that hold a share of their experts run it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import hybrid
from horovod_tpu.ops.pallas import expert_combine as combine_mod
from horovod_tpu.ops.pallas._backend import kernels_in
from horovod_tpu.ops.pallas.expert_combine import expert_combine
from horovod_tpu.serve.kv_cache import DecodeEngine

from toy_models import granite, kexaone, tokens

F32 = jnp.float32


def plain(y, at, valid, weights, acc=None):
    """``sum_k`` as XLA's loop added it: one gather and one select-add a
    ``k``, k ascending. ``at``: (tokens, top_k), a pair's row of ``y``."""
    out = jnp.zeros((at.shape[0], y.shape[1]), F32) if acc is None else acc
    for k in range(at.shape[1]):
        out = out + jnp.where(valid[:, k, None],
                              y[at[:, k]] * weights[:, k, None], 0.0)
    return np.asarray(out)


def pairs(tokens_, top_k, d, share, seed=0):
    """``share`` of ``tokens_ x top_k`` pairs valid (token 3 has none)
    and their products ``y`` as a grouped form leaves them: the valid
    pairs' rows first, in a random order, garbage after them. Returns
    the kernel's operands (``y``, ``token``, ``weight``, ``live``) and
    the per-token view of the same pairs (``at``, ``valid``,
    ``weights``) for :func:`plain`."""
    rng = np.random.default_rng(seed)
    valid = rng.random((tokens_, top_k)) < share
    valid[3] = False
    weights = rng.random((tokens_, top_k)).astype(np.float32)
    flat = valid.reshape(-1)
    live = int(flat.sum())
    # valid pairs at the sorted places 0 .. live - 1, the others after
    order = np.concatenate([rng.permutation(np.flatnonzero(flat)),
                            rng.permutation(np.flatnonzero(~flat))])
    at = np.empty(flat.size, np.int32)
    at[order] = np.arange(flat.size)
    y = rng.standard_normal((flat.size, d)).astype(np.float32)
    y[live:] = np.nan
    return (jnp.asarray(y), jnp.asarray(order // top_k, jnp.int32),
            jnp.asarray(weights.reshape(-1)[order]), live,
            jnp.asarray(at.reshape(tokens_, top_k)), jnp.asarray(valid),
            jnp.asarray(weights))


# the same products in another order of addition (a token's rows in the
# order they lie in, not in the order of k): float32 rounding apart
CLOSE = dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("share", [1.0, 1 / 16], ids=["all", "sixteenth"])
@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("top_k", [4, 8, 10])
def test_the_sum_of_a_tokens_valid_pairs(top_k, d, share):
    """Against the plain sum at the cells' ``top_k``, one and three lane
    tiles wide, every pair valid and a sixteenth of them; a token with no
    valid pair gets zeros; 77 tokens are no whole sublane tile; the rows
    past ``live`` hold NaN and are not read."""
    y, token, weight, live, at, valid, weights = pairs(77, top_k, d, share)
    got = np.asarray(expert_combine(y, token, weight, live, 77))
    assert got.shape == (77, d) and got.dtype == np.float32
    assert not got[3].any()
    np.testing.assert_allclose(got, plain(y, at, valid, weights), **CLOSE)


def test_rows_past_a_chunk_and_no_whole_last_chunk():
    """More rows than a grid step's ``CHUNK``, the last chunk ragged and
    the live rows ending inside the second: the small operands are padded
    to whole chunks, and the chunks past the live rows are not visited."""
    tokens_ = (2 * combine_mod.CHUNK + 300) // 4
    y, token, weight, live, at, valid, weights = pairs(
        tokens_, 4, 128, 0.45, seed=2)
    assert combine_mod.CHUNK < live < 2 * combine_mod.CHUNK < y.shape[0]
    got = np.asarray(expert_combine(y, token, weight, live, tokens_))
    np.testing.assert_allclose(got, plain(y, at, valid, weights), **CLOSE)


def test_no_live_row_gives_the_sum_back():
    """``live`` 0: nothing is walked; zeros, or ``acc`` as it was."""
    y, token, weight, _, _, _, _ = pairs(16, 4, 128, 1.0)
    assert not np.asarray(expert_combine(y, token, weight, 0, 16)).any()
    acc = jnp.full((16, 128), 2.5, F32)
    np.testing.assert_array_equal(
        np.asarray(expert_combine(y, token, weight, 0, 16, acc)), 2.5)


def test_a_second_turn_of_room_adds_to_the_first():
    """``experts_grouped_held``'s loop: the sorted pairs ``room`` at a
    time, a turn's products in a ``y`` of their own, the live rows of
    the second turn what is left; the second call adds to the first's
    sum through ``acc``."""
    y, token, weight, live, at, valid, weights = pairs(64, 8, 256, 0.7,
                                                       seed=3)
    room = 256
    assert room < live < 2 * room
    out = None
    for start in (0, room):
        turn = slice(start, start + room)
        out = expert_combine(y[turn], token[turn], weight[turn],
                             live - start, 64, out)
    np.testing.assert_allclose(np.asarray(out),
                               plain(y, at, valid, weights), **CLOSE)


def test_what_does_not_fit_raises():
    """No silent path back to XLA's gathers: rows that are not float32,
    or more tokens than VMEM holds a lane tile of, raise."""
    y, token, weight, live, _, _, _ = pairs(16, 4, 128, 1.0)
    with pytest.raises(ValueError, match="float32"):
        expert_combine(y.astype(jnp.bfloat16), token, weight, live, 16)
    with pytest.raises(ValueError, match="VMEM"):
        expert_combine(y, token, weight, live, 2 ** 20)


def loops(jaxpr):
    """Every ``scan`` and ``while`` of a jaxpr as (trips or None, the
    shapes it carries), kernels' bodies left out."""
    found = []
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in ("scan", "while"):
            found.append((eqn.params.get("length"),
                          [(v.aval.shape, v.aval.dtype)
                           for v in eqn.outvars]))
        for value in eqn.params.values():
            inner = value if isinstance(value, (tuple, list)) else (value,)
            for sub in inner:
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    found.extend(loops(sub))
    return found


@pytest.mark.parametrize("toy", [granite, kexaone],
                         ids=["granite", "kexaone"])
def test_a_held_shares_prefill_runs_the_kernel(toy):
    """A toy granite (8 of 16 experts held) and a toy K-EXAONE (1 of 16):
    a 141-token prompt is past ``MASKED_PAIRS``, so its prefill program
    groups the pairs; the engine reads the program's kernels when it
    first enqueues it, and no loop of ``top_k`` trips carries a (tokens,
    d) float32 sum any more."""
    cfg, params, model = toy()
    engine = DecodeEngine(model, params, num_slots=2)
    assert engine.prefill_kernels == () \
        and engine.stats()["prefill_kernels"] == []
    prompt = tokens(141).tolist()
    engine.prefill(0, prompt).collect()
    assert combine_mod.KERNEL in engine.prefill_kernels
    assert combine_mod.KERNEL in engine.stats()["prefill_kernels"]
    assert combine_mod.KERNEL not in engine.decode_kernels   # masked
    # the same prompt again enqueues the program it has: nothing is read
    # or traced twice
    before = engine.compiles_total()
    engine.prefill(1, prompt).collect()
    assert engine.compiles_total() == before
    program = jax.make_jaxpr(
        lambda p, t: model.apply({"params": p}, t,
                                 positions=jnp.zeros((1,), jnp.int32),
                                 lengths=jnp.asarray([141]), train=False,
                                 mutable=["cache"]))(
        params, jnp.zeros((1, 256), jnp.int32))
    assert combine_mod.KERNEL in kernels_in(program)
    sums = ((256, cfg["d_model"]), jnp.dtype("float32"))
    for trips, carried in loops(program):
        assert not (trips == cfg["top_k"] and sums in carried), carried


@pytest.mark.parametrize("padded", [0, 1, 2], ids=["none", "one", "two"])
def test_a_chunk_of_padding_runs_nothing(monkeypatch, padded):
    """A long prompt goes ``HELD_TOKENS`` at a time, and a chunk that is
    all padding (every pair "not here") is a ``cond`` not taken: zeros,
    and the other chunks' sums are what one turn over the whole prompt
    gives."""
    rng = np.random.default_rng(7)
    tokens_, top_k, d, f, held = 192, 4, 128, 64, 4
    x = jnp.asarray(rng.standard_normal((tokens_, d)), jnp.bfloat16)
    chosen = rng.integers(0, 16, size=(tokens_, top_k))
    chosen = np.where(chosen < held, chosen, held)     # 4 of 16 held
    chosen[tokens_ - 64 * padded:] = held
    chosen = jnp.asarray(chosen, jnp.int32)
    weights = jnp.asarray(rng.random((tokens_, top_k)), F32)
    gate, up = (jnp.asarray(rng.standard_normal((held, d, f)) * 0.1,
                            jnp.bfloat16) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((held, f, d)) * 0.1, jnp.bfloat16)
    args = (x, chosen, weights, gate, up, down)
    grouped = lambda *a: hybrid.experts_grouped_held(*a, held / 16)
    whole = np.asarray(grouped(*args))
    monkeypatch.setattr(hybrid, "HELD_TOKENS", 64)
    assert "cond" in str(jax.make_jaxpr(grouped)(*args))
    got = np.asarray(grouped(*args))
    assert not got[tokens_ - 64 * padded:].any()
    np.testing.assert_allclose(got, whole, **CLOSE)


def test_sorted_pairs_is_a_stable_sort():
    """The bookkeeping the two grouped forms share, against numpy: the
    pairs by expert, an expert's in the order of their tokens, those not
    here last, each with its weight; ``counts`` are the groups."""
    rng = np.random.default_rng(5)
    chosen = jnp.asarray(rng.integers(0, 7, size=(50, 4)), jnp.int32)
    weights = jnp.asarray(rng.random((50, 4)), F32)
    token, weight, counts = hybrid.sorted_pairs(chosen, weights, 6)
    flat = np.asarray(chosen).reshape(-1)
    order = np.argsort(flat, kind="stable")
    np.testing.assert_array_equal(token, order // 4)
    np.testing.assert_array_equal(weight,
                                  np.asarray(weights).reshape(-1)[order])
    np.testing.assert_array_equal(counts, np.bincount(flat, minlength=7)[:6])
