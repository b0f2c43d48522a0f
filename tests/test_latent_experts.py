"""Latent attention, routed experts and hyper-connected residual streams
(Xing4.0-29B-A4B; ``benchmark/configs/xing4-29b-a4b.json``'s toy sizes,
three of its six layers: one dense, two with experts) against
``benchmark/reference_xing.py``. Logits have a standard deviation of
about 0.23 here; float32 against float32 differs by the order of sums
(``F32_TOL``); what bfloat16 may cost is the cell's own limit.

The model is ``horovod_tpu/models/hybrid.py``'s, as MiniCPM-SALA's is
(``tests/test_hybrid_model.py``); the engine over its latent cache and
its expert counters is ``tests/test_latent_experts_engine.py``, and what
every family promises behind the engine ``tests/test_engine_contract.py``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granite as gref
from benchmark import reference_kexaone as kref
from benchmark import reference_xing as xref
from benchmark import weights_xing
from benchmark.runners import serve_xing
from horovod_tpu.models import hybrid
from horovod_tpu.ops.pallas._backend import kernels_in
from toy_models import (REPO, SEED, granite, kexaone, tokens, xing, xing_cfg,
                        xing_reference as xreference)

F32_TOL = 2e-5


@pytest.mark.parametrize("length", [8, 301])
def test_latent_experts_forward_matches_the_plain_reference(length):
    """8 tokens: the expert layers multiply every held expert by every
    row under a 0/1 mask; 301: they group the pairs (``grouped_product``)."""
    cfg, params, model = xing()
    toks = tokens(length)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    want = xreference(cfg, params, toks)
    assert np.abs(got - want).max() < F32_TOL
    control = xreference(cfg, params, toks, "fp8")
    assert np.abs(control - want).max() > 100 * F32_TOL


def test_latent_experts_parameter_layout_is_the_weight_makers():
    cfg, params, model = xing()
    init = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(params)))
    assert weights_xing.count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))


def test_the_full_configuration_is_9_585_gb_in_bfloat16():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4-29b-a4b.json")) as f:
        cfg = json.load(f)["as_run"]
    assert weights_xing.count(cfg) == 4_792_669_828
    assert round(2 * weights_xing.count(cfg) / 1e9, 3) == 9.585


def test_yarn_frequencies_are_the_references():
    cfg = xing_cfg()
    yarn = cfg["yarn"]
    got = hybrid.yarn_frequencies(
        cfg["rope_dim"], cfg["rope_theta"], yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"])
    assert np.allclose(got, xref.yarn_frequencies(xref.frozen(cfg)),
                       rtol=1e-6)
    # the published sizes: plain up to pair 10, divided by 64 from 23 on
    full = np.asarray(hybrid.yarn_frequencies(64, 10000, 64, 4096, 32, 1))
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert np.allclose(full[:11], plain[:11], rtol=1e-6)
    assert np.allclose(full[23:], plain[23:] / 64, rtol=1e-6)
    assert np.all((full[11:23] < plain[11:23])
                  & (full[11:23] > plain[11:23] / 64))
    assert hybrid.latent_scale(192, dict(factor=64, mscale_all_dim=1)) \
        == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


def test_sinkhorn_makes_rows_and_columns_sum_to_one():
    z = jnp.asarray(np.random.default_rng(3).normal(size=(4, 4, 7)) * 2,
                    jnp.float32)
    m = np.asarray(hybrid.sinkhorn(z, 20, 1e-6))
    assert np.abs(m.sum(axis=0) - 1).max() < 1e-4
    assert np.abs(m.sum(axis=1) - 1).max() < 1e-3
    once = np.asarray(hybrid.sinkhorn(z, 1, 1e-6))
    assert np.abs(once.sum(axis=1) - 1).max() > 0.05


def test_absorbed_decode_attention_is_expanded_attention():
    """The decode step's form (the query taken into the latent's space,
    the sum taken over latents and brought out through ``W_V``) against
    keys and values expanded from every cached latent, in float32."""
    rng = np.random.default_rng(5)
    batch, heads, rank, nope, turned, v_dim, seq = 3, 4, 32, 16, 8, 16, 128
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q_nope, q_rope = draw(batch, heads, nope), draw(batch, heads, turned)
    kv_b = draw(rank, heads, nope + v_dim) * 0.2
    latent, rope_key = draw(batch, rank, seq), draw(batch, turned, seq)
    positions = jnp.asarray([5, 127, 64], jnp.int32)
    got = np.asarray(hybrid.latent_step_attention(
        q_nope, q_rope, kv_b, latent, rope_key, positions, 0.2,
        jnp.float32))
    expanded = jnp.einsum("bks,khn->bshn", latent, kv_b)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    s = (jnp.einsum("bhn,bshn->bhs", q_nope, k_nope)
         + jnp.einsum("bhr,brs->bhs", q_rope, rope_key)) * 0.2
    s = jnp.where(jnp.arange(seq)[None, None] <= positions[:, None, None],
                  s, -jnp.inf)
    want = np.asarray(jnp.einsum("bhs,bshv->bhv", jax.nn.softmax(s, -1), v))
    assert np.abs(got - want).max() < 2e-5


@pytest.mark.parametrize("seq,positions,dtype,tol", [
    (256, [0, 255, 100], "float32", 1e-5),        # one tile a row
    (2048, [5, 2047, 1024, 1023], "float32", 1e-5),   # two tiles of 1024
    (384, [383, 7, 128], "float32", 1e-5),        # three tiles of 128
    (100, [99, 0], "float32", 1e-5),              # off the lane tile: whole
    (3072, [1023, 3000], "bfloat16", 3e-2)])
def test_the_latent_decode_kernel_is_masked_softmax_over_the_latents(
        seq, positions, dtype, tol):
    """``ops/pallas/latent_attention`` (interpret mode here) against the
    whole-row masked softmax in float32: rows that end on a tile's first
    and last position, rows of one tile beside rows of several, and dead
    tiles whose contents must not matter."""
    from horovod_tpu.ops.pallas import latent_attention

    rng = np.random.default_rng(seq)
    rows, heads, rank, turned = len(positions), 4, 32, 8
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    qt, q_rope = draw(rows, heads, rank), draw(rows, heads, turned)
    latent, rope_key = draw(rows, rank, seq), draw(rows, turned, seq)
    pos = jnp.asarray(positions, jnp.int32)
    f32 = lambda t: t.astype(jnp.float32)
    s = (jnp.einsum("bhk,bks->bhs", f32(qt), f32(latent))
         + jnp.einsum("bhr,brs->bhs", f32(q_rope), f32(rope_key))) * 0.1
    live = jnp.arange(seq)[None, None] <= pos[:, None, None]
    want = np.asarray(jnp.einsum(
        "bhs,bks->bhk", jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1),
        f32(latent)))
    got = latent_attention.latent_decode_attention(
        qt, q_rope, latent, rope_key, pos, 0.1)
    assert got.dtype == latent.dtype
    assert np.abs(np.asarray(f32(got)) - want).max() < tol
    # what lies past a row's position (an earlier occupant's latents)
    # weighs nothing
    stale = jnp.where(jnp.arange(seq)[None, None] > pos[:, None, None],
                      1e4, 0).astype(latent.dtype)
    again = latent_attention.latent_decode_attention(
        qt, q_rope, latent + stale, rope_key + stale, pos, 0.1)
    assert np.array_equal(np.asarray(f32(again)), np.asarray(f32(got)))


def _routed_layer(cfg, first, count, shared):
    return hybrid.RoutedExperts(
        num_experts=cfg["num_experts"], top_k=cfg["top_k"],
        d_ff=cfg["expert_d_ff"], shared=shared,
        scaling=cfg["routed_scaling"], first=first, count=count,
        dtype=jnp.float32)


def _is_grouped(layer, variables, x):
    """Which form of the product the layer chose for ``x``'s size."""
    return "grouped_product" in kernels_in(
        jax.make_jaxpr(layer.apply)(variables, x))


def _held(p, first, count, shared=True):
    part = {k: p[k] for k in ("router", "router_bias") if k in p}
    part.update({k: p[k][first:first + count]
                 for k in ("experts_gate", "experts_up", "experts_down")})
    if shared:
        part["shared"] = p["shared"]
    return part


def _layout(name):
    """The expert layer of a toy model, whole: its configuration, its
    parameters, the reference's ``routed`` and the experts a share."""
    if name == "8-experts-top-4":
        cfg, params, _ = xing()
        return cfg, params["layer_1"]["moe"], xref, 4
    if name == "128-experts-top-8":
        # K-EXAONE's router at its published count: 128 experts, top-8,
        # scaling 2.5, sixteen shares of 8 (the toy's own widths)
        cfg, params, _ = kexaone(num_experts=128, experts_count=128,
                                 top_k=8)
        return cfg, params["layer_1"]["moe"], kref, 8
    # granite's router at its published count: 72 experts, top-10, a
    # softmax over the chosen logits, two shares of 36, a shared MLP of a
    # width of its own (the toy's own widths)
    cfg, params, _ = granite(num_experts=72, experts_count=72, top_k=10)
    return cfg, params["layer_1"]["moe"], gref, 36


@pytest.mark.parametrize("layout", ["8-experts-top-4", "128-experts-top-8",
                                    "72-experts-top-10-softmax"])
@pytest.mark.parametrize("seq", [150, 2], ids=["grouped", "masked"])
def test_the_expert_layers_shares_add_up(seq, layout):
    """The toy experts held in shares (8 as (0, 4) + (4, 4); 128 as
    sixteen shares of 8; 72 as two of 36) and whole: the routed parts
    summed, with the shared expert counted once, are the whole layer of
    the reference; each share routes over all the router's outputs. Both
    forms of the product, each reached by its size: 300 tokens are 1,200
    (2,400; 3,000) pairs, past ``MASKED_PAIRS`` (16 since PR 46) an
    expert, 4 tokens 16 (32; 40), at or under ``MASKED_PAIRS`` an expert
    of the pairs a share expects."""
    cfg, p, ref, count = _layout(layout)
    experts = cfg["num_experts"]

    def layer(first, count, shared):
        if ref is gref:
            return hybrid.RoutedExperts(
                num_experts=experts, top_k=cfg["top_k"],
                d_ff=cfg["expert_d_ff"], shared=shared,
                shared_d_ff=cfg["shared_d_ff"],
                scoring=hybrid.SOFTMAX_ROUTER, first=first, count=count,
                dtype=jnp.float32)
        return hybrid.RoutedExperts(
            num_experts=experts, top_k=cfg["top_k"],
            d_ff=cfg["expert_d_ff"], shared=shared,
            scaling=cfg["routed_scaling"], first=first, count=count,
            dtype=jnp.float32)

    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, seq, 128)),
                    jnp.float32)
    for held in (experts, count):
        assert _is_grouped(layer(0, held, 1),
                           {"params": _held(p, 0, held)}, x) == (seq == 150)
    want = np.asarray(ref.routed(ref._matmul("f32"), x.reshape(-1, 128),
                                 p, ref.frozen(cfg))).reshape(x.shape)
    whole = layer(0, experts, 1).apply({"params": _held(p, 0, experts)}, x)
    assert np.abs(np.asarray(whole) - want).max() < F32_TOL
    parts = [layer(first, count, int(first == 0)).apply(
        {"params": _held(p, first, count, shared=first == 0)}, x)
        for first in range(0, experts, count)]
    assert np.abs(np.asarray(sum(parts)) - want).max() < F32_TOL
    # the reference given the same share computes the same part
    share = ref.frozen(dict(cfg, experts_first=count, experts_count=count,
                            shared_experts=0))
    part = np.asarray(ref.routed(
        ref._matmul("f32"), x.reshape(-1, 128),
        _held(p, count, count, shared=False), share)).reshape(x.shape)
    assert np.abs(np.asarray(parts[1]) - part).max() < F32_TOL


@pytest.mark.parametrize("form", ["grouped", "masked"])
def test_a_token_whose_experts_are_all_absent_gets_the_shared_part(form):
    cfg, params, _ = xing()
    p = params["layer_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(11).normal(size=(1, 400, 128)),
                    jnp.float32)
    chosen, _ = hybrid.route(x, p["router"], p["router_bias"], cfg["top_k"],
                             cfg["routed_scaling"])
    absent = np.asarray((chosen >= 4).all(axis=-1))[0]
    assert absent.sum() >= 3            # some tokens chose 4..7 only
    if form == "masked":    # two such tokens and two others: 16 pairs
        keep = np.concatenate([np.flatnonzero(absent)[:2],
                               np.flatnonzero(~absent)[:2]])
        x, absent = x[:, keep], absent[keep]
    layer, held = _routed_layer(cfg, 0, 4, 1), {"params": _held(p, 0, 4)}
    assert _is_grouped(layer, held, x) == (form == "grouped")
    got = layer.apply(held, x)
    shared = hybrid.GatedMlp(cfg["expert_d_ff"], dtype=jnp.float32).apply(
        {"params": p["shared"]}, x)
    assert np.abs(np.asarray(got - shared))[0, absent].max() < 1e-6
    assert np.abs(np.asarray(got - shared))[0, ~absent].max() > 1e-3


def _served_gaps(want, served):
    """How far the served tokens' reference logits lie below the
    reference's best: the widest and the 99th percentile
    (``benchmark/runners/serve_xing.py``)."""
    return serve_xing.summed_up([serve_xing.served_gaps(want, served)])


def _broken(monkeypatch, control):
    """One piece of the mathematics left out of the timed path."""
    sound = {name: getattr(hybrid, name)
             for name in ("sinkhorn", "route", "rope")}
    if control == "h_res_identity":
        def identity(z, iters, eps):
            eye = jnp.eye(z.shape[0]).reshape(
                z.shape[:2] + (1,) * (z.ndim - 2))
            return jnp.broadcast_to(eye, z.shape)
        monkeypatch.setattr(hybrid, "sinkhorn", identity)
    elif control == "one_sinkhorn_iteration":
        monkeypatch.setattr(hybrid, "sinkhorn", lambda z, iters, eps:
                            sound["sinkhorn"](z, 1, eps))
    elif control == "router_scaling_dropped":
        monkeypatch.setattr(hybrid, "route", lambda x, w, b, k, scaling:
                            sound["route"](x, w, b, k, 1.0))
    elif control == "rotary_key_unrotated":
        # the key is the one call with a single head
        monkeypatch.setattr(
            hybrid, "rope", lambda x, at, theta, freq=None:
            x.astype(jnp.float32) if x.shape[-2] == 1
            else sound["rope"](x, at, theta, freq))


@pytest.mark.parametrize("control", [
    None, "h_res_identity", "one_sinkhorn_iteration",
    "router_scaling_dropped", "rotary_key_unrotated", "float8_forward"])
def test_controls_fail_the_cells_limit_at_toy_size(monkeypatch, control):
    """The cell's toy configuration, 400 tokens teacher forced: the
    tokens the program puts first lie within the cell's two
    ``served_logit_gap`` limits (the widest gap, the 99th percentile) of
    the float32 reference's best; with
    ``H_res`` forced to the identity, Sinkhorn cut to one iteration, the
    router's scaling factor dropped or the rotary key left unrotated they
    do not (each passes at least one of the two), nor do the tokens a
    float8 forward puts first.

    The program computes in float32 here and the streams start at 0.02:
    a router's choice is not continuous, so in bfloat16 one near tie
    resolved otherwise than in the reference moves a logit by as much as
    the smaller of these faults do (``benchmark/weights_xing.py``); in
    float32 no tie flips, the sound program's gap is 0, and what each
    fault alone does to the logits shows. What bfloat16 costs is the
    float8 control's business, and the rehearsals'
    (``benchmark/tests/test_serve_xing.py``).

    The queries' expansion ``q_b`` is taken 20 times as large: at the
    toy's widths matrices of 0.02 give attention scores a standard
    deviation of 0.04 (sqrt(16) x 0.14 x 0.11 and sqrt(8) x 0.14 x 0.23,
    times the scale 0.41), a softmax that is all but uniform and to
    which a position means nothing, where the full size's are near 0.9
    (sqrt(128) x 0.55 x 0.45 and sqrt(64) x 0.55 x 1.2, times 0.145):
    with the toy's scores spread as the full size's are, an unrotated
    key shows (without it, widest 0.14 and 99th percentile 0.005: under
    both limits).

    These are not the weights the cell runs (embedding 1, bfloat16, no
    such scaling). At those, on the chip, the same controls teacher
    forced through the reference (``benchmark/controls_xing.py``) read:
    ``H_res`` the identity, the scaling factor dropped and the float8
    forward fail the 99th percentile's limit, one Sinkhorn iteration
    lies just under it and the unrotated key passes both
    (``benchmark/limits/xing-serve-c1.json`` ``set_from``
    ``controls_at_the_cells_weights``; PERF.md section 7)."""
    with open(os.path.join(REPO, "benchmark", "limits",
                           "xing-serve-c1.json")) as f:
        limits = json.load(f)
    cfg = xing_cfg("float32", dim_model_base=1600, embed_std=0.02)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 20.0 if "q_b" in jax.tree_util.keystr(path)
        else x, weights_xing.make_params(cfg, SEED))
    toks = np.random.default_rng(SEED).integers(1, cfg["vocab_size"], 400)
    want = xreference(cfg, params, toks)
    if control == "float8_forward":
        served = xreference(cfg, params, toks, "fp8").argmax(-1)
    else:
        _broken(monkeypatch, control)
        served = np.asarray(serve_xing.build_model(cfg).apply(
            {"params": params}, jnp.asarray(toks)[None]))[0].argmax(-1)
    widest, p99 = _served_gaps(want, served)
    failed = (widest > limits["served_logit_gap"]
              or p99 > limits["served_logit_gap_p99"])
    assert (widest < 1e-4) if control is None else failed, (widest, p99)


@pytest.mark.parametrize("iters", [1, 20])
def test_h_res_keeps_the_sum_of_the_streams_whatever_the_passes(iters):
    """Sinkhorn's last pass divides every column by its sum, so after one
    pass as after twenty ``sum_i (H_res X)[i] = sum_j X[j]`` (to
    ``hc_eps``): the head, which reads the sum of the streams, sees
    ``H_res`` only through what a later ``H_pre`` reads unevenly. That is
    why, at the cell's own weights, ``H_res`` forced to the identity or
    cut to one pass moves the logits by less than either limit
    (``benchmark/limits/xing-serve-c1.json`` ``set_from``)."""
    z = jnp.asarray(np.random.default_rng(iters).normal(size=(4, 4, 3, 5))
                    * 2.0, jnp.float32)
    m = np.asarray(hybrid.sinkhorn(z, iters, 1e-6))
    assert np.abs(m.sum(axis=0) - 1.0).max() < 1e-4         # columns
    x = np.random.default_rng(7).normal(size=(4, 3, 5, 8))
    mixed = np.einsum("ijbs,jbsc->ibsc", m, x)
    assert np.abs(mixed.sum(axis=0) - x.sum(axis=0)).max() < 1e-4
    rows = np.abs(m.sum(axis=1) - 1.0).max()
    assert rows < 2e-3 if iters == 20 else rows > 0.1
