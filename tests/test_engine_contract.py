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

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.serve import kv_cache
from horovod_tpu.serve.kv_cache import DecodeEngine, prompt_bucket
from toy_models import FAMILIES, family, prefill_spans, step_logits, tokens

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
