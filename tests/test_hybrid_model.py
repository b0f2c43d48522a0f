"""The hybrid decoder (block-sparse attention beside lightning linear
attention, ``horovod_tpu/models/hybrid.py``) and the dense serving
engine's three kinds of cache leaf, against the plain reference
(``benchmark/reference_sala.py``: one full forward in float32, no cache,
no chunks, its own block selection) at toy sizes with seeded weights.

Tolerances, on logits whose standard deviation is about 0.06 here:

* ``F32_TOL`` 2e-5 - program and reference both in float32 on the CPU:
  what differs is the order of float32 sums (chunked scan against the
  closed form, blocks of queries, a cache row against a fresh tensor).
  Measured 1e-7 to 3e-6.
* ``BF16_TOL`` 5e-3 - the program in bfloat16 (activations and matrix
  multiplications) against the float32 reference: measured 1.5e-3 to
  1.6e-3 over three token draws, so three times the sound reading. The
  float8 control (the reference's dense multiplications in float8_e4m3)
  reads 1.2e-2 to 2.0e-2 on the same inputs, so it fails both.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sala as ref
from benchmark import weights_sala
from benchmark.runners.serve_sala import build_model
from horovod_tpu import tracing
from horovod_tpu.models import hybrid
from horovod_tpu.serve.kv_cache import (DecodeEngine, leaf_kind,
                                         prompt_bucket)

F32_TOL, BF16_TOL = 2e-5, 5e-3
SPARSE = dict(kernel=8, stride=4, block_size=16, topk=6, init_blocks=1,
              window_size=32, dense_len=128)
CFG = dict(vocab_size=512, d_model=128, d_ff=256, num_heads=4,
           num_kv_heads=2, head_dim=32,
           mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                        "lightning-attn"],
           layer_indices=[0, 1, 2, 3], published_depth=32, scale_emb=12,
           scale_depth=1.4, dim_model_base=32, rope_theta=10000,
           rms_norm_eps=1e-6, sparse=SPARSE, max_seq=1024,
           dtype="float32", param_dtype="bfloat16")
SEED = 7


_forward = jax.jit(ref.forward, static_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def weights(kinds):
    cfg = dict(CFG, mixer_types=list(kinds),
               layer_indices=list(range(len(kinds))))
    return cfg, weights_sala.make_params(cfg, SEED)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG["vocab_size"], n)


def reference(cfg, params, toks, precision="f32"):
    return np.asarray(_forward(params, jnp.asarray(toks, jnp.int32),
                               ref.frozen(cfg), precision))


ALL = tuple(CFG["mixer_types"])


@pytest.mark.parametrize("kinds", [("lightning-attn",) * 2,
                                   ("minicpm4",) * 2, ALL],
                         ids=["lightning", "sparse", "mixed"])
@pytest.mark.parametrize("length", [100, 301],
                         ids=["under_dense_len", "past_dense_len"])
def test_forward_matches_the_plain_reference(kinds, length):
    cfg, params = weights(kinds)
    toks = tokens(length)
    got = np.asarray(build_model(cfg).apply(
        {"params": params}, jnp.asarray(toks)[None]))[0]
    want = reference(cfg, params, toks)
    assert np.abs(got - want).max() < F32_TOL
    control = reference(cfg, params, toks, "fp8")
    assert np.abs(control - want).max() > 100 * F32_TOL


def test_parameter_layout_is_the_weight_makers():
    cfg, params = weights(ALL)
    init = build_model(cfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(params)))
    assert weights_sala.count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("length,chunk", [(75, 16), (64, 64), (130, 256)])
def test_chunked_lightning_is_the_recurrence(length, chunk):
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, length, 4, 32)), jnp.float32)
               for _ in range(3))
    slopes = hybrid.lightning_slopes(4, 3, 32)
    lengths = jnp.asarray([length, length - 9], jnp.int32)
    out, last = hybrid.lightning_chunked(q, k, v, slopes, lengths, chunk)
    state = jnp.zeros((2, 4, 32, 32), jnp.float32)
    steps, states = [], []
    for t in range(length):
        state, o = hybrid.lightning_step(state, q[:, t], k[:, t], v[:, t],
                                         slopes)
        steps.append(o)
        states.append(state)
    assert np.abs(np.asarray(out) - np.stack(steps, 1)).max() < 1e-4
    # the state after each row's own length, not after the padding
    for row, n in enumerate([length, length - 9]):
        assert np.abs(np.asarray(last[row])
                      - np.asarray(states[n - 1][row])).max() < 1e-4
    assert np.abs(np.asarray(last[1])
                  - np.asarray(states[-1][1])).max() > 1e-2


@pytest.mark.parametrize("dtype,share", [("float32", 1.0),
                                         ("bfloat16", 0.99)])
def test_block_selection_agrees_with_the_references(dtype, share):
    """(query, block) choices: all of them in float32; in bfloat16 a near
    tie between two blocks may fall the other way."""
    rng = np.random.default_rng(2)
    seq, heads, groups, d = 384, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(seq, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(seq, groups, d)), jnp.float32)
    want = np.asarray(ref.block_choice(q, k, SPARSE))    # (g, seq, blocks)
    q, k = q.astype(dtype), k.astype(dtype)
    kc = hybrid.compress_keys(k[None], SPARSE["kernel"], SPARSE["stride"])
    s = jnp.einsum("btgrd,bjgd->bgtrj",
                   q.reshape(1, seq, groups, heads // groups, d),
                   kc.astype(dtype),
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    at = jnp.arange(seq)[None, None, :]
    got = np.asarray(hybrid.select_blocks(
        hybrid.block_scores(s, at, seq // SPARSE["block_size"], SPARSE),
        at, SPARSE))[0]
    sparse_rows = np.arange(seq) >= SPARSE["dense_len"]
    assert (got == want)[:, sparse_rows].mean() >= share
    assert (got == want)[:, ~sparse_rows].all()
    # every sparse query takes exactly topk blocks, its own among them
    assert (got[:, sparse_rows].sum(-1) == SPARSE["topk"]).all()
    assert got[0, np.arange(seq), np.arange(seq) // 16].all()


def test_bfloat16_forward_stays_within_rounding_of_the_reference():
    """Where a bfloat16 near tie selects another block than the
    reference, the logits still agree to bfloat16 rounding; the float8
    control does not."""
    cfg, params = weights(ALL)
    toks = tokens(301, seed=3)
    got = np.asarray(build_model(dict(cfg, dtype="bfloat16")).apply(
        {"params": params}, jnp.asarray(toks)[None]))[0]
    want = reference(cfg, params, toks)
    assert np.abs(got - want).max() < BF16_TOL
    assert np.abs(reference(cfg, params, toks, "fp8") - want).max() \
        > BF16_TOL


# ------------------------------------------------------------- the engine

def step_logits(engine, step_tokens, positions):
    """One decode step over all of the engine's rows, as ``_decode_impl``
    runs it, returning the logits it would take the argmax of."""
    logits, mutated = jax.jit(lambda p, c, t, q: engine._model.apply(
        {"params": p, "cache": c}, t, positions=q, train=False,
        mutable=["cache"]))(engine._params, engine._cache,
                            jnp.asarray(step_tokens, jnp.int32)[:, None],
                            jnp.asarray(positions, jnp.int32))
    engine._cache = mutated["cache"]
    return np.asarray(logits[:, 0])


@pytest.fixture(scope="module")
def served():
    cfg, params = weights(ALL)
    return cfg, params, build_model(cfg)


# 203 is a multiple of neither the stride (4), the block (16) nor the
# lightning chunk (256); its bucket is 256
@pytest.mark.parametrize("prompt_len", [203, 61])
def test_prefill_then_decode_is_the_references_one_forward(served,
                                                           prompt_len):
    cfg, params, model = served
    total = prompt_len + 40
    toks = tokens(total, seed=prompt_len)
    want = reference(cfg, params, toks)
    engine = DecodeEngine(model, params, num_slots=3)
    assert prompt_bucket(prompt_len, model.max_seq) > prompt_len
    first, max_abs = engine.prefill(1, toks[:prompt_len].tolist())
    assert first == want[prompt_len - 1].argmax()
    assert abs(max_abs - np.abs(want[prompt_len - 1]).max()) < F32_TOL
    for t in range(prompt_len, total):       # teacher forced
        step = np.zeros(3, np.int64)
        at = np.zeros(3, np.int64)
        step[1], at[1] = toks[t], t
        got = step_logits(engine, step, at)[1]
        assert np.abs(got - want[t]).max() < F32_TOL, t


def test_a_decode_batch_with_rows_on_both_sides_of_dense_len(served):
    cfg, params, model = served
    lens = {0: 60, 2: 231}           # dense_len is 128; row 0 crosses no
    new = 24                         # boundary, row 2 is past it
    seqs = {s: tokens(n + new, seed=s + 10) for s, n in lens.items()}
    want = {s: reference(cfg, params, seqs[s]) for s in lens}
    engine = DecodeEngine(model, params, num_slots=3)
    for s, n in lens.items():
        first, _ = engine.prefill(s, seqs[s][:n].tolist())
        assert first == want[s][n - 1].argmax()
    for i in range(new):
        slots = sorted(lens)
        ids, _ = engine.decode(
            slots, [int(seqs[s][lens[s] + i]) for s in slots],
            [lens[s] + i for s in slots])
        for s, token in zip(slots, ids):
            assert token == want[s][lens[s] + i].argmax(), (s, i)
    assert engine.stats()["cache_donated"]


def test_a_slot_is_reused_after_a_longer_occupant(served):
    cfg, params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    long = tokens(330, seed=20)
    engine.prefill(0, long[:300].tolist())
    for t in range(300, 330):
        engine.decode([0], [int(long[t])], [t])
    short = tokens(170, seed=21)         # past dense_len, so it selects
    want = reference(cfg, params, short)
    first, _ = engine.prefill(0, short[:150].tolist())
    assert first == want[149].argmax()
    for t in range(150, 170):
        got = step_logits(engine, [short[t], 0], [t, 0])[0]
        assert np.abs(got - want[t]).max() < F32_TOL, t


def test_the_state_after_the_padding_would_be_seen(served):
    """The broken path the true length guards against: a prefill that
    hands the model the bucket in place of the prompt's length leaves the
    state after the padding, and the next logits are off by far more
    than any tolerance here."""
    cfg, params, model = served
    toks = tokens(204, seed=30)
    want = reference(cfg, params, toks)
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


def test_cache_kinds_and_the_sparse_attribute(served):
    _, params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    by_kind = engine.cache_bytes_by_kind()
    seq, d = model.max_seq, model.head_dim
    windows = (seq - SPARSE["kernel"]) // SPARSE["stride"] + 1
    assert by_kind == {
        "kv": 2 * 2 * model.num_kv_heads * d * seq * 4,
        "compressed": 2 * model.num_kv_heads * d * (-(-windows // 128) * 128)
        * 4,
        "state": 3 * 2 * model.num_heads * d * d * 4}
    assert engine.stats()["cache_bytes_by_kind"] == by_kind
    assert engine.cache_bytes() == sum(by_kind.values())
    for path, leaf in jax.tree_util.tree_leaves_with_path(engine._cache):
        assert leaf.shape[0] == 2, path         # the slot is axis 0
    engine.prefill(0, tokens(41).tolist()).collect()
    engine.prefill(1, tokens(141).tolist()).collect()
    flags = {s["prompt_len"]: s["sparse"] for s in tracing.spans()
             if s["name"] == "engine.prefill"
             and s["prompt_len"] in (41, 141)}
    assert flags == {41: False, 141: True}


def test_hybrid_decode_reports_no_kv_read_share(served):
    """``HybridDecoder`` has its own attention over the whole row (its
    256-position cache is two whole lane tiles): no decode-attention
    kernel in its program, which still writes its key, value and
    compressed-key leaves through ``kv_cache_write``, and the engine
    says None, not 1.0 (and None, not False, of a fused write)."""
    from horovod_tpu.ops.pallas._backend import kernels_in

    _, params, model = served
    engine = DecodeEngine(model, params, num_slots=2)
    assert model.max_seq % 128 == 0 and not engine._reads_live_tiles
    program = jax.make_jaxpr(engine._decode_impl)(
        params, engine._cache, engine._feed, jnp.zeros((2,), jnp.int32))
    kernels = set(kernels_in(program))
    assert "kv_cache_write" in kernels and "decode_attention" not in kernels
    assert engine.stats()["decode_write_fused"] is None
    first, _ = engine.prefill(0, tokens(41).tolist())
    began = time.time()
    engine.decode([0], [first], [41]).collect()
    assert engine.stats()["decode_steps"] == 1
    assert engine.stats()["decode_kv_read_share"] is None
    mine = [s for s in tracing.spans()
            if s["name"] == "engine.decode" and s["t"] >= began]
    assert len(mine) == 1 and "kv_read_share" not in mine[0]
    assert "write_fused" not in mine[0]


def test_the_paged_engine_refuses_a_model_without_pages(served):
    from horovod_tpu.serve.paging import PagedDecodeEngine

    _, params, model = served
    with pytest.raises(ValueError, match="key/value models only"):
        PagedDecodeEngine(model, params, num_slots=2)


def test_serving_through_hvd_serve(served):
    """The model behind the public entry point: ``hvd.serve()`` ->
    ``Replica`` -> ``ContinuousBatcher`` -> ``DecodeEngine``."""
    import horovod_tpu as hvd

    cfg, params, model = served
    hvd.init()
    try:
        handle = hvd.serve(model, params, slots=2, max_new_tokens=8,
                           max_batch_tokens=2048)
        try:
            prompts = [tokens(n, seed=n).tolist() for n in (150, 37, 260)]
            uids = [handle.submit(p, max_new_tokens=8) for p in prompts]
            for prompt, uid in zip(prompts, uids):
                done = handle.result(uid, timeout=300.0)
                full = np.asarray(prompt + list(done.tokens))
                want = reference(cfg, params, full)
                rows = want[len(prompt) - 1:len(full) - 1]
                assert list(done.tokens) == rows.argmax(-1).tolist()
            with pytest.raises(ValueError, match="key/value models only"):
                hvd.serve(model, params, slots=2, paged=True)
        finally:
            handle.close()
    finally:
        hvd.shutdown()


def _toy_transformer(max_seq):
    from horovod_tpu.models.transformer import Transformer

    model = Transformer(vocab_size=61, d_model=32, num_layers=2,
                        num_heads=2, d_ff=64, max_seq=max_seq, causal=True,
                        dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32),
                             train=False)["params"]


@pytest.mark.parametrize("prompt_len", [5, 16, 23, 32, 57])
def test_gpt2_toy_serving_is_unchanged_by_the_one_row_head(prompt_len):
    """The old trunk's prefill now applies its head to the last prompt
    row alone (no (bucket, vocab) logits): the first token and the
    largest logit are those of the uncached forward's row
    ``prompt_len - 1``, wherever the prompt ends in its bucket."""
    model, params = _toy_transformer(max_seq=64)
    toks = np.random.default_rng(prompt_len).integers(1, 61, prompt_len)
    want = np.asarray(model.apply({"params": params},
                                  jnp.asarray(toks)[None], train=False))[0]
    engine = DecodeEngine(model, params, num_slots=2)
    first, max_abs = engine.prefill(1, toks.tolist())
    assert first == want[-1].argmax()
    assert abs(max_abs - np.abs(want[-1]).max()) < 1e-5


@pytest.mark.parametrize("which", ["transformer", "sala", "xing"])
def test_a_model_that_cannot_resume_keeps_its_bucket_programs(monkeypatch,
                                                              which):
    """Only a model that says its prefill resumes from its cache
    (``resumable_prefill``: every mixer a power retention) has its
    prompts run in pieces. The dense trunk lacks the property, a model
    with block-sparse, lightning or latent layers answers false: each
    keeps one ``prefill_<bucket>`` program a bucket, one program a
    prompt, however small the piece would be."""
    from horovod_tpu.serve import kv_cache

    monkeypatch.setattr(kv_cache, "PREFILL_CHUNK", 32)
    if which == "transformer":
        model, params = _toy_transformer(max_seq=256)
        assert not hasattr(model, "resumable_prefill")
    else:
        if which == "sala":
            cfg, params = weights(ALL)
            model = build_model(cfg)
        else:
            _, params, model = xing()
        assert model.resumable_prefill is False
    engine = DecodeEngine(model, params, num_slots=2)
    began = time.time()
    for slot, n in enumerate((41, 100)):
        engine.prefill(slot, (tokens(n, seed=n) % 61).tolist()).collect()
    stats = engine.stats()
    assert stats["compiles"] == {"prefill_64": 1, "prefill_128": 1}
    assert stats["prefill_chunks"] == 2
    assert stats["prefill_positions"] == 64 + 128
    assert stats["prefill_tokens"] == 141
    assert [(s["prompt_len"], s["chunks"], s["bucket"])
            for s in tracing.spans()
            if s["name"] == "engine.prefill" and s["t"] >= began] == [
                (41, 1, 64), (100, 1, 128)]


# ---------------------------------------------------------------------------
# Latent attention, routed experts and hyper-connected residual streams
# (Xing4.0-29B-A4B; ``benchmark/configs/xing4-29b-a4b.json``'s toy sizes,
# three of its six layers: one dense, two with experts) against
# ``benchmark/reference_xing.py``. Logits have a standard deviation of
# about 0.23 here; float32 against float32 differs by the order of sums
# (``F32_TOL``); what bfloat16 may cost is the cell's own limit.

from benchmark import reference_xing as xref  # noqa: E402
from benchmark import weights_xing  # noqa: E402
from benchmark.runners import serve_xing  # noqa: E402


def xing_cfg(dtype="float32", **changes):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4-29b-a4b.json")) as f:
        published = json.load(f)
    cfg = dict(published["as_run"], **published["rehearse"])
    cfg.update(num_layers=3, layer_indices=[0, 2, 3], max_seq=512,
               dtype=dtype, param_dtype=dtype)
    cfg.update(changes)
    return cfg


@functools.lru_cache(maxsize=None)
def xing(dtype="float32"):
    cfg = xing_cfg(dtype)
    return cfg, weights_xing.make_params(cfg, SEED), \
        serve_xing.build_model(cfg)


_xforward = jax.jit(xref.forward, static_argnums=(2, 3))


def xreference(cfg, params, toks, precision="f32"):
    return np.asarray(_xforward(params, jnp.asarray(toks, jnp.int32),
                                xref.frozen(cfg), precision))


@pytest.mark.parametrize("length", [8, 301])
def test_latent_experts_forward_matches_the_plain_reference(length):
    """8 tokens: the expert layers multiply every held expert by every
    row under a 0/1 mask; 301: they group the pairs (``ragged_dot``)."""
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
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
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


def test_the_latent_kernels_tiles_and_the_engines_counters():
    from horovod_tpu.ops.pallas import latent_attention

    assert [latent_attention.tile_of(n) for n in (8192, 1024, 512, 384,
                                                  100)] \
        == [1024, 1024, 512, 128, 100]
    # tiles read, tiles held, positions attended; a row that is not
    # active (-1) runs at position 0
    assert latent_attention.live_tiles([0, 5000, -1], 8192) == (7, 24, 5003)
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    assert engine._reads_live_latents and not engine._reads_live_tiles
    assert engine.stats()["decode_positions_read"] == 0
    first, _ = engine.prefill(0, tokens(41).tolist())
    engine.decode([0], [first], [41]).collect()
    stats = engine.stats()
    assert stats["decode_positions_read"] == 42 + 1
    assert stats["decode_kv_read_share"] == 1.0     # 512 positions: a tile
    assert stats["decode_write_fused"] is None
    sala = DecodeEngine(*weights_and_model_sala())
    assert sala.stats()["decode_positions_read"] is None


def _routed_layer(cfg, first, count, shared):
    return hybrid.RoutedExperts(
        num_experts=cfg["num_experts"], top_k=cfg["top_k"],
        d_ff=cfg["expert_d_ff"], shared=shared,
        scaling=cfg["routed_scaling"], first=first, count=count,
        dtype=jnp.float32)


def _is_grouped(layer, variables, x):
    """Which form of the product the layer chose for ``x``'s size."""
    return "ragged_dot" in str(jax.make_jaxpr(layer.apply)(variables, x))


def _held(p, first, count, shared=True):
    part = {k: p[k] for k in ("router", "router_bias")}
    part.update({k: p[k][first:first + count]
                 for k in ("experts_gate", "experts_up", "experts_down")})
    if shared:
        part["shared"] = p["shared"]
    return part


@pytest.mark.parametrize("seq", [50, 2], ids=["grouped", "masked"])
def test_the_expert_layers_shares_add_up(seq):
    """Eight toy experts held as (0, 4) + (4, 4) and as (0, 8): the
    routed parts summed, with the shared expert counted once, are the
    whole layer of the reference; each share routes over all eight
    router outputs. Both forms of the product, each reached by its size:
    100 tokens are 400 pairs, 4 tokens 16 = ``MASKED_PAIRS`` x 4."""
    cfg, params, _ = xing()
    p = params["layer_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, seq, 128)),
                    jnp.float32)
    for count in (8, 4):
        assert _is_grouped(_routed_layer(cfg, 0, count, 1),
                           {"params": _held(p, 0, count)}, x) == (seq == 50)
    want = np.asarray(xref.routed(xref._matmul("f32"), x.reshape(-1, 128),
                                  p, xref.frozen(cfg))).reshape(x.shape)
    whole = _routed_layer(cfg, 0, 8, 1).apply(
        {"params": _held(p, 0, 8)}, x)
    assert np.abs(np.asarray(whole) - want).max() < F32_TOL
    low = _routed_layer(cfg, 0, 4, 1).apply(
        {"params": _held(p, 0, 4)}, x)
    high = _routed_layer(cfg, 4, 4, 0).apply(
        {"params": _held(p, 4, 4, shared=False)}, x)
    assert np.abs(np.asarray(low + high) - want).max() < F32_TOL
    # the reference given the same share computes the same part
    share = xref.frozen(dict(cfg, experts_first=4, experts_count=4,
                             shared_experts=0))
    part = np.asarray(xref.routed(
        xref._matmul("f32"), x.reshape(-1, 128),
        _held(p, 4, 4, shared=False), share)).reshape(x.shape)
    assert np.abs(np.asarray(high) - part).max() < F32_TOL


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


def xstep_logits(engine, toks, positions):
    """One teacher-forced decode step over every row, as ``_decode_impl``
    runs it, returning the logits it would take the argmax of; rows at
    position -1 are not active."""
    if not hasattr(engine, "step_for_tests"):
        engine.step_for_tests = jax.jit(
            lambda p, c, t, q: engine._model.apply(
                {"params": p, "cache": c}, jnp.where(q >= 0, t, 0)[:, None],
                positions=jnp.maximum(q, 0), train=False, active=q >= 0,
                mutable=["cache"]))
    logits, mutated = engine.step_for_tests(
        engine._params, engine._cache, jnp.asarray(toks, jnp.int32),
        jnp.asarray(positions, jnp.int32))
    engine._cache = mutated["cache"]
    return np.asarray(logits[:, 0])


@pytest.mark.parametrize("prompt_len", [203, 61])
def test_latent_prefill_then_decode_is_the_references_one_forward(
        prompt_len):
    """A padded bucket (256 for 203, 64 for 61), then decode steps through
    the slot cache's latent leaves, against the reference's one forward
    over the whole sequence, on logits."""
    cfg, params, model = xing()
    total = prompt_len + 30
    toks = tokens(total, seed=prompt_len)
    want = xreference(cfg, params, toks)
    engine = DecodeEngine(model, params, num_slots=3)
    assert prompt_bucket(prompt_len, model.max_seq) > prompt_len
    first, max_abs = engine.prefill(1, toks[:prompt_len].tolist())
    assert first == want[prompt_len - 1].argmax()
    assert abs(max_abs - np.abs(want[prompt_len - 1]).max()) < F32_TOL
    for t in range(prompt_len, total):       # teacher forced
        got = xstep_logits(engine, [0, toks[t], 0], [-1, t, -1])[1]
        assert np.abs(got - want[t]).max() < F32_TOL, t


def test_a_latent_slot_is_reused_after_a_longer_occupant():
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    long = tokens(330, seed=20)
    engine.prefill(0, long[:300].tolist())
    for t in range(300, 330):
        engine.decode([0], [int(long[t])], [t])
    short = tokens(170, seed=21)
    want = xreference(cfg, params, short)
    first, _ = engine.prefill(0, short[:150].tolist())
    assert first == want[149].argmax()
    for t in range(150, 170):
        got = xstep_logits(engine, [short[t], 0], [t, -1])[0]
        assert np.abs(got - want[t]).max() < F32_TOL, t
    assert engine.stats()["cache_donated"]


def test_latent_cache_kinds_and_the_expert_counter():
    """The cache's only per-position leaves are the latent and the rotary
    key; the counter counts (token, expert) pairs of prompts' true
    tokens and of active decode rows, and nothing else."""
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=3)
    seq, layers, held = model.max_seq, cfg["num_layers"], 8
    assert engine.cache_bytes_by_kind() == {
        "kv": 0, "compressed": 0, "state": 0,
        "latent": layers * 3 * seq * (cfg["kv_rank"] + cfg["rope_dim"]) * 4,
        "counter": (layers - 1) * 3 * held * 4}
    names = {jax.tree_util.keystr(path[-1:]) for path, _ in
             jax.tree_util.tree_leaves_with_path(engine._cache)}
    assert names == {"['latent']", "['rope_key']", "['expert_counts']"}
    assert engine.stats()["expert_counts"] == np.zeros(
        (2, 3, held), int).tolist()
    engine.prefill(0, tokens(41).tolist()).collect()    # bucket 64
    engine.prefill(2, tokens(141).tolist()).collect()   # bucket 256
    counts = engine.expert_counts()
    assert counts.shape == (2, 3, held)
    assert (counts[:, 0].sum(axis=1) == (41 + 141) * cfg["top_k"]).all()
    assert not counts[:, 1:].any()
    for step in range(3):     # slot 1 is not active: not counted
        engine.decode([0, 2], [5, 7], [41 + step, 141 + step]).collect()
    counts = engine.expert_counts() - counts
    assert (counts[:, 0].sum(axis=1) == 3 * 2 * cfg["top_k"]).all()
    assert (counts[:, 2] == 3).all()
    assert (counts[:, 1] <= 3).all() and (counts[:, 1] <= counts[:, 0]).all()
    assert (counts[:, 1].sum(axis=1) >= 3 * cfg["top_k"]).all()
    assert engine.stats()["cache_donated"]
    assert DecodeEngine(*weights_and_model_sala()).stats()[
        "expert_counts"] is None


def test_the_expert_counter_wraps_and_differences_stay_right():
    """The counts run modulo 2**32 and are never reset: a reading before
    the wrap and one after differ, in uint32, by what was counted; and a
    reading builds no program."""
    from benchmark import harness

    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    engine._cache = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, 2 ** 32 - 7)
        if leaf_kind(path) == "counter" else x, engine._cache)
    compiles = harness.CompileCounter()
    before = engine.expert_counts()
    assert before.dtype == np.uint32 and (before == 2 ** 32 - 7).all()
    assert compiles.compiles == 0
    engine.prefill(1, tokens(41).tolist()).collect()
    after = engine.expert_counts()
    assert (after[:, 0] < before[:, 0]).any()          # wrapped
    assert ((after - before)[:, 0].sum(axis=1) == 41 * cfg["top_k"]).all()
    stats = {"engine": {"expert_counts": after.tolist()}}, \
        {"engine": {"expert_counts": before.tolist()}}
    assert (serve_xing.counted_between(stats[1], stats[0])[:, 0].sum(axis=1)
            == 41 * cfg["top_k"]).all()


def weights_and_model_sala():
    cfg, params = weights(ALL)
    return build_model(cfg), params, 2


def test_no_decode_step_expands_a_latent():
    """The decode program holds no tensor of per-head keys or values over
    the cache's positions: nothing of shape (.., heads, .., max_seq) with
    a head's key or value width."""
    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    program = jax.make_jaxpr(engine._decode_impl)(
        params, engine._cache, engine._feed, jnp.zeros((2,), jnp.int32))
    heads, seq = cfg["num_heads"], model.max_seq
    widths = {cfg["nope_dim"], cfg["v_dim"], cfg["nope_dim"] + cfg["v_dim"],
              cfg["nope_dim"] + cfg["rope_dim"]}

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield tuple(getattr(var.aval, "shape", ()))
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from shapes(inner)

    for shape in shapes(program.jaxpr):
        assert not (seq in shape and heads in shape
                    and widths & set(shape)), shape


def test_latent_experts_serving_through_hvd_serve():
    import horovod_tpu as hvd

    cfg, params, model = xing()
    hvd.init()
    try:
        handle = hvd.serve(model, params, slots=2, paged=False,
                           max_new_tokens=8, max_batch_tokens=2048)
        try:
            prompts = [tokens(n, seed=n).tolist() for n in (150, 37, 260)]
            uids = [handle.submit(p, max_new_tokens=8) for p in prompts]
            for prompt, uid in zip(prompts, uids):
                done = handle.result(uid, timeout=300.0)
                full = np.asarray(prompt + list(done.tokens))
                want = xreference(cfg, params, full)
                rows = want[len(prompt) - 1:len(full) - 1]
                assert list(done.tokens) == rows.argmax(-1).tolist()
            counts = np.asarray(
                handle.stats()["replicas"][0]["engine"]["expert_counts"])
            # every prompt token and every decoded token but each
            # request's last (it is sampled and never fed back)
            assert (counts[:, 0].sum(axis=1)
                    == (150 + 37 + 260 + 3 * 7) * cfg["top_k"]).all()
        finally:
            handle.close()
    finally:
        hvd.shutdown()


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
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "limits",
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


def test_stats_reads_the_counters_while_the_engine_donates_them():
    """``stats()`` copies the expert counters out of a cache that every
    program donates: readers on other threads, more of them than cores
    care for and with a short switch interval, see no deleted array and
    counts that only grow, while the engine's own thread decodes."""
    import sys
    import threading

    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    first, _ = engine.prefill(0, tokens(41).tolist())
    stop, errors = threading.Event(), []
    seen = [[] for _ in range(6)]

    def reader(mine):
        while not stop.is_set():
            try:
                mine.append(int(engine.expert_counts()[:, 0].sum()))
            except Exception as exc:        # reported below
                errors.append(exc)
                return

    threads = [threading.Thread(target=reader, args=(s,)) for s in seen]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for step in range(40):
            engine.decode([0], [first], [41 + step]).collect()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert all(mine == sorted(mine) for mine in seen)
    assert sum(len(mine) for mine in seen) > 6
    layers = cfg["num_layers"] - 1
    assert int(engine.expert_counts()[:, 0].sum()) \
        == (41 + 40) * cfg["top_k"] * layers
    assert engine.stats()["cache_donated"]
