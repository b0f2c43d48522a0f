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
from horovod_tpu.serve.kv_cache import DecodeEngine, prompt_bucket

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


@pytest.mark.parametrize("prompt_len", [5, 16, 23, 32, 57])
def test_gpt2_toy_serving_is_unchanged_by_the_one_row_head(prompt_len):
    """The old trunk's prefill now applies its head to the last prompt
    row alone (no (bucket, vocab) logits): the first token and the
    largest logit are those of the uncached forward's row
    ``prompt_len - 1``, wherever the prompt ends in its bucket."""
    from horovod_tpu.models.transformer import Transformer

    model = Transformer(vocab_size=61, d_model=32, num_layers=2,
                        num_heads=2, d_ff=64, max_seq=64, causal=True,
                        dtype=jnp.float32)
    toks = np.random.default_rng(prompt_len).integers(1, 61, prompt_len)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    want = np.asarray(model.apply({"params": params},
                                  jnp.asarray(toks)[None], train=False))[0]
    engine = DecodeEngine(model, params, num_slots=2)
    first, max_abs = engine.prefill(1, toks.tolist())
    assert first == want[-1].argmax()
    assert abs(max_abs - np.abs(want[-1]).max()) < 1e-5
