"""Window and full grouped-query attention, the ring cache and norms on
the sublayers' outputs (K-EXAONE-236B-A23B; ``benchmark/configs/
k-exaone-236b-a23b.json``'s toy sizes, all eight layers: three window
layers to a full one, a dense layer and seven with 1 of 16 experts)
against ``benchmark/reference_kexaone.py``. Logits have a standard
deviation of about 1.0 here; float32 against float32 differs by the
order of sums (``F32_TOL``).

The toy's window is 64 in a ring of 128 (a ring is whole lane tiles), so
its ring holds positions outside the window, which the step's mask has
to leave out; ``window=128`` is the geometry the cell runs, ring and
window alike, where every column of the ring is seen. Both are driven
past two wraps of the ring. What every family promises behind the engine
is ``tests/test_engine_contract.py``, which this family joined.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_kexaone as kref
from benchmark import weights_kexaone
from horovod_tpu.models import hybrid
from horovod_tpu.ops.pallas import grouped_decode_attention as gda
from horovod_tpu.ops.pallas._backend import kernels_in
from horovod_tpu.serve.kv_cache import DecodeEngine
from toy_models import kexaone, kexaone_reference, step_logits, tokens

F32_TOL = 5e-5


@pytest.mark.parametrize("length", [8, 301])
def test_window_full_forward_matches_the_plain_reference(length):
    """8 tokens: under the window, and the expert layers multiply every
    held expert by every row; 301: four blocks of the band and a ragged
    fifth, and the pairs that are here are grouped."""
    cfg, params, model = kexaone()
    toks = tokens(length)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    want = kexaone_reference(cfg, params, toks)
    assert np.abs(got - want).max() < F32_TOL
    control = kexaone_reference(cfg, params, toks, "fp8")
    assert np.abs(control - want).max() > 100 * F32_TOL


def test_parameter_layout_is_the_weight_makers():
    cfg, params, model = kexaone()
    init = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(params)))
    assert weights_kexaone.count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("window,prompt_len,steps", [
    (64, 150, 300),     # ring 128 past the window: masked columns
    (128, 200, 270),    # the cell's geometry: ring and window alike
    (128, 20, 300),     # a prompt shorter than the window
], ids=["window-64", "window-128", "short-prompt"])
def test_prefill_then_decode_past_two_wraps_of_the_ring(window, prompt_len,
                                                        steps):
    """A padded prefill leaves the prompt's last positions in the ring at
    ``position mod ring``; decode steps then write over the oldest column
    more than twice round, through the full layers' kernel and the window
    layers' masked ring, against the reference's one forward."""
    cfg, params, model = kexaone(window=window)
    toks = tokens(prompt_len + steps, seed=prompt_len)
    want = kexaone_reference(cfg, params, toks)
    engine = DecodeEngine(model, params, num_slots=2)
    assert steps > 2 * hybrid.ring_len(window)
    first, _ = engine.prefill(1, toks[:prompt_len].tolist())
    assert first == want[prompt_len - 1].argmax()
    for t in range(prompt_len, prompt_len + steps):
        got = step_logits(engine, [0, toks[t]], [-1, t])[1]
        assert np.abs(got - want[t]).max() < F32_TOL, t
    by_kind = engine.cache_bytes_by_kind()
    slots, groups, d = 2, cfg["num_kv_heads"], cfg["head_dim"]
    assert by_kind["ring"] == 6 * 2 * slots * groups * d * 128 * 4
    assert by_kind["kv"] == 2 * 2 * slots * groups * d * cfg["max_seq"] * 4


def test_a_shorter_request_never_reads_the_slots_earlier_occupant():
    """After a long occupant every column of the slot's rings and every
    full-layer position is stale. A short prompt's prefill writes the
    columns it reaches; the others, and whatever the engine holds of the
    earlier request past the new one's position, are poisoned here with
    huge values, and no logit moves."""
    cfg, params, model = kexaone(window=128)
    engine = DecodeEngine(model, params, num_slots=1)
    long = tokens(330, seed=20)
    engine.prefill(0, long[:300].tolist())
    for t in range(300, 330):
        engine.decode([0], [int(long[t])], [t])
    short = tokens(60, seed=21)
    want = kexaone_reference(cfg, params, short)
    first, _ = engine.prefill(0, short[:40].tolist())
    assert first == want[39].argmax()

    def poisoned_past(position):   # nothing past ``position`` is ours
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.where(
                jnp.arange(leaf.shape[-1]) > position, 1e4, leaf)
            if engine.leaf_kind(path) in ("kv", "ring") else leaf, engine._cache)

    engine._cache = poisoned_past(39)
    for t in range(40, 60):
        got = step_logits(engine, [short[t]], [t])[0]
        assert np.abs(got - want[t]).max() < F32_TOL, t
        engine._cache = poisoned_past(t)    # the step wrote column ``t``


def test_the_padding_in_the_ring_would_be_seen():
    """The broken path the true length guards against, in the cell's
    geometry (ring and window alike): a prefill that takes the bucket for
    the prompt's length leaves the padding's keys in the ring's columns,
    and the next logits are off by far more than any tolerance here."""
    cfg, params, model = kexaone(window=128)
    toks = tokens(204, seed=30)
    want = kexaone_reference(cfg, params, toks)
    engine = DecodeEngine(model, params, num_slots=1)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :203] = toks[:203]
    _, mutated = engine._model.apply(
        {"params": params}, jnp.asarray(padded),
        positions=jnp.zeros((1,), jnp.int32),
        lengths=jnp.asarray([256], jnp.int32), train=False,
        mutable=["cache"])
    engine._cache = mutated["cache"]
    got = step_logits(engine, [toks[203]], [203])[0]
    assert np.abs(got - want[203]).max() > 100 * F32_TOL


@pytest.mark.parametrize("fault", kref.FAULTS + kref.TOY_FAULTS)
def test_a_planted_fault_is_told_from_a_sound_program(fault):
    """A window layer read as a full one, a ring read one position too
    far (the key at ``t - window``) and queries and keys left unnormed,
    each planted in the reference: the sound program lies thousands of
    tolerances from each, at every position the fault can reach."""
    cfg, params, model = kexaone()
    toks = tokens(200, seed=3)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    broken = kexaone_reference(cfg, params, toks, fault=fault)
    reach = 0 if fault == "qk_norm_dropped" else cfg["window"]
    assert np.abs(got - broken)[reach + 1:].max() > 1000 * F32_TOL
    if reach:       # under the window the three models are one
        assert np.abs(got - broken)[:reach].max() < F32_TOL


def test_norms_sit_on_the_sublayers_outputs():
    """One layer by hand: ``h += rms(Attn(h))``, ``h += rms(Mlp(h))``,
    no norm on either input, against the module and the reference."""
    cfg, params, model = kexaone(num_layers=1, mixers=("window",),
                                 layer_indices=(0,))
    assert set(params["layer_0"]) == {"mixer", "mixer_norm", "mlp",
                                      "mlp_norm"}
    toks = tokens(40, seed=5)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    assert np.abs(got - kexaone_reference(cfg, params, toks)).max() < F32_TOL
    pre = hybrid.HybridDecoder(**{
        **{f: getattr(model, f) for f in model.__dataclass_fields__
           if f not in ("parent", "name")}, "norms": hybrid.NORM_INPUT})
    renamed = dict(params, layer_0=dict(
        params["layer_0"], input_norm=params["layer_0"]["mixer_norm"],
        post_norm=params["layer_0"]["mlp_norm"]))
    for gone in ("mixer_norm", "mlp_norm"):
        renamed["layer_0"].pop(gone)
    other = np.asarray(pre.apply({"params": renamed},
                                 jnp.asarray(toks)[None]))[0]
    assert np.abs(got - other).max() > 1000 * F32_TOL


@pytest.mark.parametrize("seq,window", [
    (40, 64),         # shorter than the window
    (64, 64), (301, 64), (512, 128),
    (2200, 128),      # more blocks than one turn of the loop scores
], ids=lambda v: str(v))
def test_banded_blocks_are_the_masked_full_scores(seq, window):
    rng = np.random.default_rng(seq)
    heads, groups, d = 4, 2, 16
    q = jnp.asarray(rng.normal(size=(1, seq, heads, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, seq, groups, d)), jnp.float32)
            for _ in range(2))
    got = hybrid.window_prompt_attention(q, k, v, window, d ** -0.5,
                                         jnp.float32)
    want = kref.attention(q[0], k[0], v[0], window) if seq % 128 == 0 \
        or seq < 128 else kref.attention(
            *(jnp.pad(t[0], ((0, -seq % 128), (0, 0), (0, 0)))
              for t in (q, k, v)), window)[:seq]
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 2e-5


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_grouped_decode_kernel_reads_the_live_tiles_alone(dtype, tol):
    """Eight queries a key/value head against tiles of 512 positions:
    rows at the first position, at a tile's edge, inside a tile and at the
    cache's end; what lies past a row's position (an earlier occupant's
    keys) weighs nothing."""
    rng = np.random.default_rng(1)
    rows, groups, per, d, seq = 5, 2, 8, 16, 2048
    pos = jnp.asarray([0, 511, 512, 1300, 2047], jnp.int32)
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, groups, per, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(rows, groups, d, seq)), dtype)
            for _ in range(2))
    s = jnp.einsum("bgrd,bgds->bgrs", f32(q), f32(k)) * d ** -0.5
    live = jnp.arange(seq)[None, None, None] <= pos[:, None, None, None]
    want = np.asarray(jnp.einsum(
        "bgrs,bgds->bgrd", jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1),
        f32(v)))
    got = gda.grouped_decode_attention(q, k, v, pos, d ** -0.5)
    assert got.dtype == v.dtype
    assert np.abs(np.asarray(f32(got)) - want).max() < tol
    stale = jnp.where(jnp.arange(seq)[None, None, None]
                      > pos[:, None, None, None], 1e4, 0).astype(dtype)
    again = gda.grouped_decode_attention(q, k + stale, v + stale, pos,
                                         d ** -0.5)
    assert np.array_equal(np.asarray(f32(again)), np.asarray(f32(got)))
    assert gda.live_tiles(np.asarray(pos), seq) == (
        1 + 1 + 2 + 3 + 4, 20, int(np.asarray(pos).sum()) + 5)


def _moe(cfg, params, first, count):
    p = params["layer_1"]["moe"]
    held = {k: p[k] for k in ("router", "router_bias", "shared")}
    held.update({k: p[k][first:first + count]
                 for k in ("experts_gate", "experts_up", "experts_down")})
    layer = hybrid.RoutedExperts(
        num_experts=cfg["num_experts"], top_k=cfg["top_k"],
        d_ff=cfg["expert_d_ff"], shared=1, scaling=cfg["routed_scaling"],
        first=first, count=count, dtype=jnp.float32)
    return layer, {"params": held}


@pytest.mark.parametrize("skew,block", [(0.0, 4096), (8.0, 4096), (0.0, 64)],
                         ids=["even", "uneven-two-turns", "in-blocks"])
def test_a_share_groups_the_pairs_that_are_here(monkeypatch, skew, block):
    """4 of 16 toy experts held: the grouped product takes the sorted
    pairs ``room`` at a time (twice an even router's share). A router
    biased towards the held experts sends more than that here, and the
    loop takes a second turn; a long prompt goes ``HELD_TOKENS`` at a
    time. Either way no pair is dropped."""
    monkeypatch.setattr(hybrid, "HELD_TOKENS", block)
    cfg, params, _ = kexaone(experts_count=16)
    layer, held = _moe(cfg, params, 4, 4)
    held["params"]["router_bias"] = held["params"]["router_bias"].at[
        4:8].set(skew)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 256, 128)),
                    jnp.float32)
    assert "grouped_product" in kernels_in(
        jax.make_jaxpr(layer.apply)(held, x))
    chosen, _ = hybrid.route(x, held["params"]["router"],
                             held["params"]["router_bias"], cfg["top_k"],
                             cfg["routed_scaling"])
    here = int(((chosen >= 4) & (chosen < 8)).sum())
    room = 2 * 256 * cfg["top_k"] * 4 // 16
    assert (here > room) == (skew > 0)
    share = kref.frozen(dict(cfg, experts_first=4, experts_count=4))
    want = np.asarray(kref.routed(kref._matmul("f32"), x[0],
                                  held["params"], share))
    got = np.asarray(layer.apply(held, x))[0]
    assert np.abs(got - want).max() < F32_TOL


def test_the_decode_span_says_what_was_read_of_each_kind():
    """``engine.decode`` carries the positions its step attended in the
    full layers' rows and in the window layers' rings, and the share of
    the rows' tiles the kernel fetched; ``stats()`` sums them."""
    import time

    from horovod_tpu import tracing

    _, params, model = kexaone()
    engine = DecodeEngine(model, params, num_slots=2)
    assert "grouped_decode_attention" in engine.decode_kernels \
        and "decode_attention" not in engine.decode_kernels
    assert engine.stats()["decode_positions_by_kind"] == {}
    first, _ = engine.prefill(0, tokens(141).tolist())
    began = time.time()
    engine.decode([0], [first], [141]).collect()
    span, = [s for s in tracing.spans()
             if s["name"] == "engine.decode" and s["t"] >= began]
    # row 0 at position 141, row 1 not active (position 0)
    assert span["kv_positions_read"] == 2 * (142 + 1)
    assert span["ring_positions_read"] == 6 * (64 + 1)
    assert span["kv_read_share"] == 1.0       # 512 positions: one tile
    stats = engine.stats()
    assert stats["decode_positions_by_kind"] == {"kv": 286, "ring": 390}
    assert stats["cache_bytes_by_kind"]["ring"] > 0
    assert stats["decode_positions_read"] is None


def test_positions_a_step_attends_by_kind_of_leaf():
    _, _, model = kexaone()
    assert model.decode_positions_by_kind(np.asarray([0, 9, 500])) == {
        "kv": 2 * (1 + 10 + 501), "ring": 6 * (1 + 10 + 64)}
    from toy_models import xing
    assert xing()[2].decode_positions_by_kind(np.asarray([3])) is None
