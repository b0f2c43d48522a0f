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

The configuration, the weights and the reference's forward are
``tests/toy_models.py``'s; what the model promises behind the engine
beside the other families is in ``tests/test_engine_contract.py``, and
the same module's latent attention and routed experts in
``tests/test_latent_experts.py``.
"""

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
from horovod_tpu.serve.kv_cache import DecodeEngine
from toy_models import (SALA_ALL as ALL, SPARSE, sala_reference as reference,
                        sala_weights as weights, tokens)

F32_TOL, BF16_TOL = 2e-5, 5e-3


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
# (what every family promises behind it: tests/test_engine_contract.py)

@pytest.fixture(scope="module")
def served():
    cfg, params = weights(ALL)
    return cfg, params, build_model(cfg)


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
    assert model.max_seq % 128 == 0 and "decode_attention" not in engine.decode_kernels
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
