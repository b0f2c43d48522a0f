"""Every model family behind the public entry point, ``hvd.serve()``, and
what the dense engine's prefill promises whatever the model: the five
families of ``tests/toy_models.py`` and the toy GPT-2 trunk (the
engine's own contract, on logits, is ``tests/test_engine_contract.py``:
a file is what tier-1's ``--dist loadfile`` schedules).
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.serve import kv_cache
from horovod_tpu.serve.kv_cache import DecodeEngine
from toy_models import (FAMILIES, family, prefill_spans, tokens,
                        toy_transformer)


@pytest.mark.parametrize("name", FAMILIES)
def test_serving_through_hvd_serve(name):
    """The model behind the public entry point: ``hvd.serve()`` ->
    ``Replica`` -> ``ContinuousBatcher`` -> ``DecodeEngine``, the same
    path for every dense model; more requests than slots, so that slots
    are reused."""
    import horovod_tpu as hvd

    fam = family(name)
    hvd.init()
    try:
        handle = hvd.serve(fam.model, fam.params, slots=2, paged=False,
                           max_new_tokens=8, max_batch_tokens=2048)
        try:
            prompts = [tokens(n, seed=n).tolist() for n in (150, 37, 260)]
            uids = [handle.submit(p, max_new_tokens=8) for p in prompts]
            for prompt, uid in zip(prompts, uids):
                done = handle.result(uid, timeout=300.0)
                full = np.asarray(prompt + list(done.tokens))
                want = fam.reference(full)
                rows = want[len(prompt) - 1:len(full) - 1]
                assert list(done.tokens) == rows.argmax(-1).tolist()
            engine = handle.stats()["replicas"][0]["engine"]
            if name == "brumby":        # a cache of states alone
                assert engine["cache_bytes_by_kind"]["state"] \
                    == engine["cache_bytes"] > 0
                assert engine["decode_kv_read_share"] is None
            if name == "xing":
                # every prompt token and every decoded token but each
                # request's last (it is sampled and never fed back)
                counts = np.asarray(engine["expert_counts"])
                assert (counts[:, 0].sum(axis=1)
                        == (150 + 37 + 260 + 3 * 7) * fam.cfg["top_k"]).all()
            if name == "kexaone":
                # rings on the six window layers, max_seq-long rows on
                # the two full ones; what the decode steps attended of
                # each (a request's last token is never fed back)
                by_kind = engine["cache_bytes_by_kind"]
                width = fam.cfg["num_kv_heads"] * fam.cfg["head_dim"] * 4
                assert by_kind["ring"] == 6 * 2 * 2 * 128 * width
                assert by_kind["kv"] == 2 * 2 * 2 * fam.cfg["max_seq"] * width
                read = engine["decode_positions_by_kind"]
                contexts = [n + t for n in (150, 37, 260)
                            for t in range(1, 8)]
                inactive = engine["decode_steps"] * 2 - len(contexts)
                assert read["kv"] == 2 * (sum(contexts) + inactive)
                assert read["ring"] == 6 * (sum(min(c, 64) for c in contexts)
                                            + inactive)
                assert 0 < engine["decode_kv_read_share"] <= 1.0
            if name == "granite":
                # nine state-space layers' float32 states and convolution
                # tails beside the one full layer's max_seq-long rows, and
                # the experts' counts of all ten layers in one slot cache
                by_kind = engine["cache_bytes_by_kind"]
                ssm = fam.cfg["ssm"]
                inner = ssm["num_heads"] * ssm["head_dim"]
                assert by_kind["state"] == 9 * 2 * inner * ssm["d_state"] * 4
                assert by_kind["conv"] == 9 * 2 * 3 * (
                    inner + 2 * ssm["d_state"]) * 4
                assert by_kind["kv"] == 2 * 2 * fam.cfg["max_seq"] \
                    * fam.cfg["num_kv_heads"] * fam.cfg["head_dim"] * 4
                counts = np.asarray(engine["expert_counts"])
                assert counts.shape == (10, 3, fam.cfg["experts_count"])
                # this chip's half of the experts: about half of the pairs
                pairs = (150 + 37 + 260 + 3 * 7) * fam.cfg["top_k"]
                assert (counts[:, 0].sum(axis=1) < pairs).all()
                assert 0.3 * pairs < counts[:, 0].sum(axis=1).mean() \
                    < 0.7 * pairs
                assert engine["decode_positions_by_kind"]["ring"] == 0
            if fam.no_pages:
                with pytest.raises(ValueError, match=fam.no_pages):
                    hvd.serve(fam.model, fam.params, slots=2, paged=True)
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
    model, params = toy_transformer(max_seq=64)
    toks = np.random.default_rng(prompt_len).integers(1, 61, prompt_len)
    want = np.asarray(model.apply({"params": params},
                                  jnp.asarray(toks)[None], train=False))[0]
    engine = DecodeEngine(model, params, num_slots=2)
    first, max_abs = engine.prefill(1, toks.tolist())
    assert first == want[-1].argmax()
    assert abs(max_abs - np.abs(want[-1]).max()) < 1e-5


@pytest.mark.parametrize("which", ["transformer", "sala", "xing", "granite"])
def test_a_model_that_cannot_resume_keeps_its_bucket_programs(monkeypatch,
                                                              which):
    """Only a model that says its prefill resumes from its cache
    (``resumable_prefill``: every mixer a power retention) has its
    prompts run in pieces. The dense trunk lacks the property, a model
    with block-sparse, lightning or latent layers, or with a full layer
    among its state-space layers, answers false: each
    keeps one ``prefill_<bucket>`` program a bucket, one program a
    prompt, however small the piece would be."""
    monkeypatch.setattr(kv_cache, "PREFILL_CHUNK", 32)
    if which == "transformer":
        model, params = toy_transformer(max_seq=256)
        assert not hasattr(model, "resumable_prefill")
    else:
        model, params = family(which).model, family(which).params
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
            for s in prefill_spans(began)] == [(41, 1, 64), (100, 1, 128)]
