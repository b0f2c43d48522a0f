"""granite-4.0-h-small as a whole (``benchmark/configs/
granite-4.0-h-small.json``'s toy sizes: nine Mamba-2 layers to one
position-free grouped-query layer, a share of the experts on every
layer, a tied head, four fixed multipliers) against ``benchmark/
reference_granite.py``; the slot cache's new leaves behind the dense
engine, the three planted faults of ``benchmark/controls_granite.py``,
the spans' ``state_bytes`` and the programs' scopes. The mixer alone is
``tests/test_state_space.py``. Logits have a standard deviation of about
1.6 here; float32 against float32 differs by the order of sums
(``F32_TOL``).
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import controls_granite, flops_granite, weights_granite
from horovod_tpu import tracing
from horovod_tpu.models import hybrid
from horovod_tpu.serve.kv_cache import DecodeEngine
from toy_models import granite, granite_reference, step_logits, tokens

F32_TOL = 5e-5
HEADS, P, N, TAPS = 8, 32, 16, 4
CHANNELS = HEADS * P + 2 * N


def short():
    """The family cut to three layers, a full one between two state-space
    ones: what a fault or a multiplier changes shows as well, and the
    reference (a Python loop over the layers) compiles in a third of the
    time."""
    return granite(num_layers=3, mixers=("mamba2", "full", "mamba2"),
                   layer_indices=(0, 1, 2))


# ------------------------------------------------------------- the model

@pytest.mark.parametrize("length", [8, 301])
def test_the_forward_matches_the_plain_reference(length):
    """8 tokens: under a chunk, and the expert layers multiply every
    held expert by every row; 301: four chunks and a ragged fifth, and
    the pairs that are here are grouped."""
    cfg, params, model = granite()
    toks = tokens(length)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    want = granite_reference(cfg, params, toks)
    assert np.abs(got - want).max() < F32_TOL
    control = granite_reference(cfg, params, toks, "fp8")
    assert np.abs(control - want).max() > 100 * F32_TOL


def test_parameter_layout_is_the_weight_makers():
    cfg, params, model = granite()
    init = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(params)))
    assert weights_granite.count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    # tied: no head of its own; a softmax router: no correction bias
    assert "head" not in params
    assert set(params["layer_0"]["moe"]) == {
        "router", "experts_gate", "experts_up", "experts_down", "shared"}
    # no positions and no QK-norm on the one attention layer
    assert set(params["layer_5"]["mixer"]) == {"query", "key", "value",
                                               "out"}


def test_the_state_space_weights_have_a_trained_models_spread():
    """``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
    step in [1e-3, 1e-1], ``D = 1`` (the Mamba-2 reference
    initialisation); the final norm's scale has its own mean."""
    cfg, _, _ = granite()
    m = weights_granite.make_params(dict(cfg, final_norm_mean=20.0), 11)
    layer = m["layer_2"]["mixer"]
    a = np.exp(np.asarray(layer["A_log"]))
    dt = np.asarray(jax.nn.softplus(layer["dt_bias"]))
    assert (a >= 1).all() and (a <= 16).all() and a.std() > 1
    assert (dt >= 1e-3 * 0.99).all() and (dt <= 1e-1 * 1.01).all()
    assert (np.asarray(layer["D"]) == 1).all()
    assert abs(float(np.mean(m["final_norm"]["scale"])) - 20.0) < 0.5
    assert abs(float(np.mean(m["layer_2"]["post_norm"]["scale"])) - 1) < 0.05


def test_a_reused_slot_never_sees_its_earlier_occupant():
    """After a long occupant the slot's state, tail and full-layer rows
    are another request's. A short prompt's prefill overwrites the whole
    row of both new leaves; here every other slot, and whatever the
    engine holds of the full layer past the new request's position, is
    poisoned with huge values as well, and no logit moves."""
    cfg, params, model = granite()
    engine = DecodeEngine(model, params, num_slots=2)
    long = tokens(330, seed=20)
    engine.prefill(1, long[:300].tolist())
    for t in range(300, 330):
        engine.decode([1], [int(long[t])], [t])
    before = jax.tree.map(np.asarray, engine._cache)
    short = tokens(30, seed=21)
    want = granite_reference(cfg, params, short)
    first, _ = engine.prefill(1, short[:2].tolist())   # under the reach
    assert first == want[1].argmax()
    after = jax.tree.map(np.asarray, engine._cache)
    for path, leaf in jax.tree_util.tree_leaves_with_path(after):
        kind = engine.leaf_kind(path)
        if kind in ("state", "conv"):
            old = dict(jax.tree_util.tree_leaves_with_path(before))[path]
            assert np.abs(leaf[1] - old[1]).max() > 1e-3, path

    def poisoned(position):
        def one(path, leaf):
            kind = engine.leaf_kind(path)
            if kind == "kv":      # nothing past ``position`` is ours
                return jnp.where(
                    jnp.arange(leaf.shape[-1]) > position, 1e4, leaf)
            if kind in ("state", "conv"):    # slot 0 is nobody's
                return leaf.at[0].set(1e4)
            return leaf
        return jax.tree_util.tree_map_with_path(one, engine._cache)

    engine._cache = poisoned(1)
    for t in range(2, 30):
        got = step_logits(engine, [0, short[t]], [-1, t])[1]
        assert np.abs(got - want[t]).max() < F32_TOL, t
        engine._cache = poisoned(t)


@pytest.mark.parametrize("fault", controls_granite.FAULTS)
def test_a_planted_fault_is_told_from_a_sound_program(fault):
    """A slot that keeps its earlier occupant's state, a convolution tail
    dropped between prefill and decode, and a prompt's padding run
    through the recurrence, each planted in the reference
    (``benchmark/controls_granite.py``): up to the prompt's end the
    faulty model is the sound one, and the sound program lies thousands
    of tolerances from it after."""
    cfg, params, model = short()
    prompt, served = tokens(150, seed=3), tokens(40, seed=4)
    toks = np.concatenate([prompt, served])
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    broken = controls_granite.forward(fault, cfg)
    at, rows = jnp.asarray(150, jnp.int32), jnp.arange(190)
    if fault == "stale_state":
        other = tokens(190, seed=5)
        left = controls_granite.final_states(cfg)(
            params, jnp.asarray(other, jnp.int32), 170)
        assert len(left) == 2 and left[0].shape == (HEADS, P, N)
        low = broken(params, jnp.asarray(toks, jnp.int32), rows, at, left)
    elif fault == "tail_dropped":
        low = broken(params, jnp.asarray(toks, jnp.int32), rows, at,
                     jnp.asarray(0, jnp.int32))
    else:
        ids, pad, where = controls_granite.padded(prompt, served, 256, 320)
        assert pad == 106 and where[0] == 149 and where[1] == 256
        low = broken(params, jnp.asarray(ids), jnp.asarray(np.concatenate(
            [np.arange(149), where])), at, jnp.asarray(pad, jnp.int32))
        # rows 0..148 the prompt's, then the 40 rows that predict the
        # served tokens: row 149 and the rows past the padding
        got = np.concatenate([got[:149], got[149:189]])
    low = np.asarray(low)[:len(got)]
    assert np.abs(got - low)[150:].max() > 1000 * F32_TOL
    assert np.abs(got - low)[:150].max() < F32_TOL
    if fault == "tail_dropped":     # three steps see zeros, no more rows
        assert np.abs(got - low)[150:153].max() > 1000 * F32_TOL


def test_a_sound_forward_through_the_controls_is_the_reference():
    """The swapped functions with nothing wrong (no padding, the state
    the prompt itself leaves) are the sound reference: the faults are in what they are
    given, not in how they compute."""
    cfg, params, _ = short()
    toks = tokens(100, seed=8)
    want = granite_reference(cfg, params, toks)
    rows, at = jnp.arange(100), jnp.asarray(60, jnp.int32)
    left = controls_granite.final_states(cfg)(
        params, jnp.asarray(toks, jnp.int32), 60)   # its own state there
    same = controls_granite.forward("stale_state", cfg)(
        params, jnp.asarray(toks, jnp.int32), rows, at, left)
    assert np.abs(np.asarray(same) - want).max() < F32_TOL
    same = controls_granite.forward("padding_in_recurrence", cfg)(
        params, jnp.asarray(toks, jnp.int32), rows, at,
        jnp.asarray(0, jnp.int32))
    assert np.abs(np.asarray(same) - want).max() < F32_TOL


# --------------------------------------------------- head and multipliers

@pytest.mark.parametrize("field,other,moved", [
    ("scale_emb", 1.0, 1000), ("residual_multiplier", 1.0, 1000),
    # one layer of ten, and its scores nearly flat either way
    ("attention_scale", None, 100), ("logits_divisor", 1.0, 1000),
    ("tied_head", False, 1000), ("qk_norm", True, 100)])
def test_the_head_is_tied_and_each_multiplier_is_applied(field, other,
                                                         moved):
    """The model against the reference is the first test; here each of
    the four multipliers, the tied head and the absent QK-norm is shown
    to matter: with the field at its neutral default the logits move by
    ``moved`` tolerances (a head of its own, and QK-norms, need
    parameters the weight maker does not make: given here)."""
    cfg, params, model = short()
    toks = jnp.asarray(tokens(50, seed=6))[None]
    want = np.asarray(model.apply({"params": params}, toks))[0]
    changed = hybrid.HybridDecoder(**{
        **{f: getattr(model, f) for f in model.__dataclass_fields__
           if f not in ("parent", "name")}, field: other})
    given = params
    if field == "tied_head":
        given = dict(params, head=params["token_embed"]["embedding"].T)
        assert np.abs(np.asarray(changed.apply({"params": given}, toks))[0]
                      - want).max() < F32_TOL      # the same matrix: tied
        given = dict(params, head=params["token_embed"]["embedding"].T * 2)
    if field == "qk_norm":
        norm = {"scale": jnp.ones((cfg["head_dim"],))}
        given = dict(params, layer_1=dict(params["layer_1"], mixer=dict(
            params["layer_1"]["mixer"], q_norm=norm, k_norm=norm)))
    got = np.asarray(changed.apply({"params": given}, toks))[0]
    assert np.abs(got - want).max() > moved * F32_TOL


# ------------------------------------------------ cache, spans and scopes

def test_the_slot_cache_holds_states_tails_rows_and_counters():
    cfg, params, model = granite()
    engine = DecodeEngine(model, params, num_slots=3)
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(engine._cache):
        kinds.setdefault(engine.leaf_kind(path), []).append(leaf)
    assert {k: len(v) for k, v in kinds.items()} == {
        "state": 9, "conv": 9, "kv": 2, "counter": 10}
    assert all(x.shape == (3, HEADS, P, N) and x.dtype == jnp.float32
               for x in kinds["state"])
    assert all(x.shape == (3, 3 * CHANNELS) for x in kinds["conv"])
    by_kind = engine.cache_bytes_by_kind()
    assert by_kind["state"] == 9 * 3 * HEADS * P * N * 4
    assert by_kind["conv"] == 9 * 3 * 3 * CHANNELS * 4
    assert by_kind["kv"] == 2 * 3 * 2 * 16 * cfg["max_seq"] * 4
    assert engine.stats()["cache_bytes_by_kind"] == by_kind
    assert model.resumable_prefill is False and model.counts_active_rows
    # what a step has to move of them, as the roofline counts it
    assert flops_granite.ssm_step_bytes(3, 9, HEADS, P, N, 1, TAPS, 4) \
        == 2 * (by_kind["state"] + by_kind["conv"])
    assert flops_granite.ssm_step_bytes(64, 9, 128, 64, 128, 1, 4) \
        == 2 * 64 * 9 * (4194304 + 3 * 8448 * 2)


def test_the_spans_say_what_state_a_program_owns():
    """``engine.prefill`` carries the bytes of the recurrent states its
    slot's row holds, ``engine.decode`` those of every row (a step runs
    them all), beside the positions read of the full layer's rows."""
    _, params, model = granite()
    engine = DecodeEngine(model, params, num_slots=2)
    state = engine.cache_bytes_by_kind()["state"]
    began = time.time()
    first, _ = engine.prefill(0, tokens(141).tolist())
    engine.decode([0], [first], [141]).collect()
    spans = {s["name"]: s for s in tracing.spans() if s["t"] >= began
             and s["name"] in ("engine.prefill", "engine.decode")}
    assert spans["engine.prefill"]["state_bytes"] == state // 2
    assert spans["engine.decode"]["state_bytes"] == state
    assert spans["engine.decode"]["kv_positions_read"] == 142 + 1
    assert spans["engine.decode"]["ring_positions_read"] == 0


def test_the_programs_name_their_scopes():
    """``ssm`` round the whole mixer, inside it ``ssm_scan`` in a
    prompt's program and ``ssm_step`` in a decode step's, and the
    trunk's ``full_attention``, ``moe`` and ``head`` as they were."""
    _, params, model = granite()
    engine = DecodeEngine(model, params, num_slots=2)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    found = lambda text: set(re.findall(
        r"(?<=/)(ssm_scan|ssm_step|ssm|full_attention|moe|head)(?=/)", text))
    decode = engine._decode_fn.lower(
        params, engine._cache, i32(2), i32(2)).as_text(debug_info=True)
    assert found(decode) == {"ssm", "ssm_step", "full_attention", "moe",
                             "head"}
    assert "ssm/ssm_step/" in decode
    prefill = engine._prefill_fn(64).lower(
        params, engine._cache, i32(2), i32(1, 64), i32(), i32()
    ).as_text(debug_info=True)
    assert found(prefill) == {"ssm", "ssm_scan", "full_attention", "moe",
                              "head"}
    assert "ssm/ssm_scan/" in prefill
