"""What every model behind the dense serving engine promises, once for
all of them: the five families of ``tests/toy_models.py`` (MiniCPM-SALA,
Brumby, Xing, K-EXAONE, granite: each a ``HybridDecoder`` with a cache of its own kinds,
against its plain reference's one forward in float32). A new family is
one more entry of ``toy_models.family`` and of its ``FAMILIES``, not a
copy of these cases; what only one family has (its mixers, its cache's
kinds, its counters) is tested in its own file, and the same families
behind ``hvd.serve()`` in ``tests/test_engine_serving.py``.
"""

import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import hybrid
from horovod_tpu.serve import kv_cache
from horovod_tpu.serve.kv_cache import DecodeEngine, prompt_bucket
from toy_models import (FAMILIES, family, prefill_spans, step_logits, tokens,
                        toy_transformer, uncached_greedy)

# A model that resumes (Brumby) has its prompts cut into pieces of
# PREFILL_CHUNK, set here to the toy mixer's own chunk, and the last one
# padded; the others pad a prompt to its bucket.
CHUNK = 256


@pytest.fixture
def pieces(monkeypatch):
    monkeypatch.setattr(kv_cache, "PREFILL_CHUNK", CHUNK)
    return CHUNK


@pytest.fixture(scope="module")
def engines():
    """One engine of three slots a family for the cases that need none of
    their own: building one traces its programs anew. A slot's second
    prompt starts where a fresh engine's would, which
    ``test_a_slot_is_reused_after_a_longer_occupant`` proves on an engine
    of its own."""
    made = {}

    def engine(name):
        if name not in made:
            fam = family(name)
            made[name] = DecodeEngine(fam.model, fam.params, num_slots=3)
        return made[name]

    return engine


# 203 is a multiple of neither SALA's stride (4), its block (16) nor the
# lightning chunk (256): its bucket is 256, and 61's 64 lies under
# SALA's dense_len. For Brumby 203 and 61 are one padded piece, 300 two,
# the second mostly padding; then the lengths round a piece's edge, whole
# pieces alone, and three pieces and 17.
@pytest.mark.parametrize("name,prompt_len", [
    ("sala", 203), ("sala", 61),
    *(("brumby", n) for n in (203, 61, 300, CHUNK - 1, CHUNK, CHUNK + 1,
                              2 * CHUNK, 3 * CHUNK + 17)),
    ("xing", 203), ("xing", 61),
    # 203 lies past K-EXAONE's ring of 128 (its last 128 positions wrap
    # into it), 61 under its window of 64
    ("kexaone", 203), ("kexaone", 61),
    # 203 is three of granite's chunks (64) and 11, 61 under one; 2 lies
    # under its convolution's reach (a tail of three rows, one of zeros)
    ("granite", 203), ("granite", 61), ("granite", 2)])
def test_prefill_then_decode_is_the_references_one_forward(pieces, engines,
                                                           name, prompt_len):
    """A padded prefill (a bucket, or pieces that carry the slot's state),
    then decode steps through the slot's cache, against the reference's
    one forward over the whole sequence, on logits."""
    fam = family(name)
    total = prompt_len + 40
    toks = tokens(total, seed=prompt_len)
    want = fam.reference(toks)
    engine = engines(name)
    began = time.time()
    first, max_abs = engine.prefill(1, toks[:prompt_len].tolist())
    span, = prefill_spans(began)
    programs = set(engine.stats()["compiles"])
    if fam.model.resumable_prefill:
        # the last piece is padded (or, for whole pieces, exactly full):
        # two programs of one shape, whatever the prompts
        chunks = -(-prompt_len // pieces)
        assert span["chunks"] == chunks and span["bucket"] == chunks * pieces
        assert 0 <= span["bucket"] - prompt_len < pieces
        assert programs <= {"prefill_chunk", "prefill_last"}
        assert "prefill_last" in programs
        assert chunks == 1 or "prefill_chunk" in programs
    else:
        bucket = prompt_bucket(prompt_len, fam.model.max_seq)
        assert span["chunks"] == 1 and span["bucket"] == bucket > prompt_len
        assert f"prefill_{bucket}" in programs
    assert first == want[prompt_len - 1].argmax()
    assert abs(max_abs - np.abs(want[prompt_len - 1]).max()) < fam.tol
    for t in range(prompt_len, total):       # teacher forced
        got = step_logits(engine, [0, toks[t], 0], [-1, t, -1])[1]
        assert np.abs(got - want[t]).max() < fam.tol, t


@pytest.mark.parametrize("name", FAMILIES)
def test_a_slot_is_reused_after_a_longer_occupant(name):
    fam = family(name)
    engine = DecodeEngine(fam.model, fam.params, num_slots=2)
    long = tokens(330, seed=20)
    engine.prefill(0, long[:300].tolist())
    for t in range(300, 330):
        engine.decode([0], [int(long[t])], [t])
    short = tokens(170, seed=21)     # past SALA's dense_len: it selects
    want = fam.reference(short)
    first, _ = engine.prefill(0, short[:150].tolist())
    assert first == want[149].argmax()
    for t in range(150, 170):
        got = step_logits(engine, [short[t], 0], [t, -1])[0]
        assert np.abs(got - want[t]).max() < fam.tol, t
    assert engine.stats()["cache_donated"]


@pytest.mark.parametrize("name", ["sala", "brumby", "granite"])
def test_the_state_after_the_padding_would_be_seen(name):
    """The broken path the true length guards against: a prefill that
    hands the model the bucket in place of the prompt's length leaves the
    state after the padding, and the next logits are off by far more
    than any tolerance here."""
    fam = family(name)
    toks = tokens(204, seed=30)
    want = fam.reference(toks)
    engine = DecodeEngine(fam.model, fam.params, num_slots=1)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :203] = toks[:203]
    _, mutated = engine._model.apply(
        {"params": fam.params}, jnp.asarray(padded),
        positions=jnp.zeros((1,), jnp.int32),
        lengths=jnp.asarray([256], jnp.int32), train=False,
        mutable=["cache"])
    engine._cache = mutated["cache"]
    got = step_logits(engine, [toks[203]], [203])[0]
    assert np.abs(got - want[203]).max() > 100 * fam.tol


@pytest.mark.parametrize("name", ["sala", "brumby", "granite"])
def test_the_paged_engine_refuses_a_model_without_pages(name):
    from horovod_tpu.serve.paging import PagedDecodeEngine

    fam = family(name)
    with pytest.raises(ValueError, match=fam.no_pages):
        PagedDecodeEngine(fam.model, fam.params, num_slots=2)


# what ``DecodeEngine.stats()`` publishes, written out: the benchmark's
# runners and layer metrics read these by name
STATS_KEYS = {
    "compiles", "compiles_total", "decode_steps", "decode_step_ms_ewma",
    "prefill_chunks", "prefill_positions", "prefill_tokens", "cache_bytes",
    "cache_bytes_by_kind", "cache_donated", "decode_kv_read_share",
    "decode_write_fused", "prefill_sparse_kernel", "prefill_kernels",
    "decode_positions_read",
    "decode_positions_by_kind", "expert_counts", "block_len", "row_passes",
    "commit_row_passes", "tokens_unmasked", "blocks_committed", "slots"}


@pytest.mark.parametrize("name", ["trunk", *FAMILIES])
def test_every_cache_leaf_has_a_declared_kind(engines, name):
    """The contract's ``cache_kinds`` covers the model's whole cache (a
    variable no layer declared would count as ``other``), and ``stats()``
    has the keys it always had, no more and no fewer."""
    engine = DecodeEngine(*toy_transformer(128), num_slots=2) \
        if name == "trunk" else engines(name)
    leaves = jax.tree_util.tree_leaves_with_path(engine._cache)
    kinds = {engine.leaf_kind(path) for path, _ in leaves}
    assert leaves and "other" not in kinds
    stats = engine.stats()
    assert set(stats) == STATS_KEYS
    assert kinds <= set(stats["cache_bytes_by_kind"])
    assert all(stats["cache_bytes_by_kind"][kind] > 0 for kind in kinds)


class RunningMean(nn.Module):
    """A layer kind that lives in this file alone: the mean of a value
    projection over the tokens so far, whose whole cache is one running
    sum a slot."""

    decode: bool = False
    eps: float = 1e-6
    dtype: object = jnp.float32
    param_dtype: object = jnp.float32

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        batch, seq, width = x.shape
        value = nn.Dense(width, use_bias=False, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="value")(x)
        if self.decode and seq == 1:
            total = self.variable("cache", "running_sum", jnp.zeros,
                                  (batch, width), jnp.float32)
            total.value = total.value + value[:, 0]
            return total.value[:, None] / (positions + 1)[:, None, None]
        sums = jnp.cumsum(value, axis=1)
        if self.decode:         # the sum after the prompt's true tokens
            last = (seq if lengths is None else lengths) - 1
            self.variable("cache", "running_sum", jnp.zeros, (batch, width),
                          jnp.float32).value = jnp.take_along_axis(
                              sums, jnp.broadcast_to(last, (batch,))[
                                  :, None, None], axis=1)[:, 0]
        return sums / jnp.arange(1, seq + 1)[None, :, None]


def test_a_layer_kind_declared_outside_the_package_is_served(monkeypatch):
    """One entry of ``hybrid.MIXERS`` is all a new kind of layer needs:
    nothing under ``horovod_tpu/serve/`` knows it, and the engine reports
    its cache under the kind it declared and decodes what the uncached
    forward does."""
    monkeypatch.setitem(hybrid.MIXERS, "running_mean", hybrid.Mixer(
        RunningMean, lambda decoder, i: dict(decode=decoder.decode),
        {"running_sum": "tally"}))
    model = hybrid.HybridDecoder(
        vocab_size=61, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
        head_dim=16, mixers=("running_mean", "running_mean"), max_seq=64,
        dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = DecodeEngine(model, params, num_slots=2)
    assert engine.cache_bytes_by_kind() == {
        "kv": 0, "compressed": 0, "state": 0, "tally": 2 * 2 * 32 * 4}
    prompt = [5, 17, 3, 44, 9]
    got = [engine.prefill(1, prompt).collect()[0]]
    for step in range(7):
        got.append(engine.decode([1], None, [len(prompt) + step])
                   .collect()[0][0])
    assert got == uncached_greedy(model, params, prompt, 8)
    assert engine.stats()["cache_bytes_by_kind"]["tally"] == 512
    with pytest.raises(ValueError, match="unknown mixer 'running_sum'"):
        hybrid.mixer_of("running_sum")


def uncached_block_greedy(model, params, prompt, n):
    """Reference for a block model: the published loop of generation by
    diffusion over blocks with the model's own cache-free forward, the
    whole sequence recomputed every pass (row ``t`` of a block-causal
    model sees its own block and nothing after it). Returns the ``n``
    generated ids, whole blocks of them."""
    block, unmask = model.block_len, model.block_len // model.denoising_steps
    forward = jax.jit(lambda params, toks: model.apply(
        {"params": params}, toks, train=False))
    total = -(-(len(prompt) + n) // block) * block
    ids = np.full((total,), model.mask_id, np.int32)
    ids[:len(prompt)] = prompt
    masked = np.arange(total) >= len(prompt)
    padded = np.zeros((1, model.max_seq), np.int32)
    for start in range(len(prompt) - len(prompt) % block, total, block):
        here = slice(start, start + block)
        while masked[here].any():
            padded[0, :total] = np.where(masked, model.mask_id, ids)
            logits = np.asarray(forward(params, padded)[0, here],
                                np.float64)
            sure = np.where(masked[here], np.exp(
                logits.max(-1) - np.log(np.exp(logits).sum(-1))), -np.inf)
            for j in sorted(range(block), key=lambda j: (-sure[j], j))[
                    :min(unmask, masked[here].sum())]:
                ids[start + j] = logits[j].argmax()
                masked[start + j] = False
    return ids[len(prompt):].tolist()


def test_a_block_model_of_its_own_is_served_through_the_contract():
    """What the engine is told of a model that generates by blocks is
    three fields of the contract (``block_len``, ``mask_id``, ``unmask``):
    a decoder of dense full layers with a block of 8 that unmasks 4 a pass
    - no size and no schedule of the benchmark's - decodes what the
    cache-free loop over its own forward does, through ``hvd.serve()``."""
    import horovod_tpu as hvd

    model = hybrid.HybridDecoder(
        vocab_size=61, d_model=32, d_ff=64, num_heads=4, num_kv_heads=2,
        head_dim=8, mixers=("full", "full"), rotary=("full",), max_seq=64,
        block_len=8, mask_id=60, denoising_steps=2, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(5),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    contract = model.serving()
    assert (contract.block_len, contract.mask_id, contract.unmask) \
        == (8, 60, 4)
    engine = DecodeEngine(model, params, num_slots=2)
    assert engine.decode_kernels == ("kv_cache_write_block",
                                     "grouped_decode_attention")
    assert engine.cache_bytes_by_kind() == {
        "kv": 2 * 2 * 2 * 2 * 8 * 64 * 4, "compressed": 0, "state": 0}
    handle = hvd.serve(model, params, slots=2, max_new_tokens=32,
                       max_batch_tokens=128)
    try:
        prompts = [[5, 17, 3, 44, 9], [7] * 16, [1, 2, 3]]
        uids = [handle.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, (13, 8, 20))]
        done = [handle.result(uid, timeout=120.0) for uid in uids]
    finally:
        handle.close()
    for prompt, n, out in zip(prompts, (13, 8, 20), done):
        want = uncached_block_greedy(model, params, prompt, n)
        assert out.tokens == want[:n] and out.cut == want[n:]
        assert set(out.passes) <= {0, 1}
