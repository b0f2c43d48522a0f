"""The SDAR-30B-A3B-Chat cell rehearsed at toy sizes on the CPU: sound,
traced and untraced, against the float8 control and its planted faults;
the layout of the one forward that teacher-forces a served trajectory
against the reference's own loop; the readers of the pass counters, of
the sampler's scope and of a pass's bytes. Run by hand (see conftest.py);
about four minutes on the CPU. The engine against the reference's loop,
the kernels' block forms and the request's schedule are
``tests/test_block_diffusion.py``."""

import json
import types

import numpy as np
import pytest

from benchmark import controls_sdar, flops_sdar, harness, reference_sdar
from benchmark import run, scopes_sdar, weights_sdar
from benchmark.runners import serve_sdar

CELL = "sdar-serve-reason-c1"


def rehearse(capsys, *extra, trace="0"):
    run.main(["--workload", CELL, "--seed", "2147483701", "--seconds", "3",
              "--trace", trace, *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_no_result_without_a_chip(capsys):
    with pytest.raises(SystemExit) as refusal:
        rehearse(capsys)
    assert refusal.value.code not in (0, None)


def test_a_program_that_yields_one_token_a_step_stops_before_any_compile(
        monkeypatch):
    """What the parent commit does with this cell's files laid over it."""
    from horovod_tpu.models import hybrid

    _, _, _, config, _, _ = harness.load_cell(CELL, True)
    monkeypatch.delitem(hybrid.HybridDecoder.__dataclass_fields__,
                        "block_len")
    with pytest.raises(SystemExit, match="one token a step"):
        serve_sdar.build_model(config)


def test_a_sound_traced_rehearsal_is_correct_and_reads_its_metrics(capsys):
    """The CPU's trace carries no ``op_name`` paths and a CPU has no
    peak, so the shares by scope and the rooflines have nothing to read
    here and are left out; the accepted ``.serve`` metrics that need no
    steady step are read, the expert load from its counter, and the two
    metrics of the pass counters. The toy's prompts (40-400) reach three
    buckets and every length modulo the block."""
    result, lines = rehearse(capsys, "--rehearse", trace="1")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    checks = [l.split()[1].rstrip(":") for l in lines
              if l.startswith("check ")]
    assert checks == ["served_logit_gap", "served_logit_gap_p99",
                      "unmask_choice_gap", "unmask_choice_gap_p99",
                      "compiles_in_window", "replica_quarantined",
                      "cache_donated"]
    assert any("'block_len': 4" in l and "'kv': 3145728" in l for l in lines)
    assert {"ttft_ms_p95.serve", "tpot_ms_p95.serve",
            "batch_occupancy.serve", "device_idle_share.serve",
            "expert_load_max_over_mean.serve", "tokens_per_pass.serve",
            "commit_pass_share.serve"} <= set(result["metrics"])
    assert not {"moe_time_share.serve", "unmask_time_share.serve",
                "full_attn_time_share.serve", "grouped_decode_roofline",
                "moe_decode_roofline"} & set(result["metrics"])
    # two tokens a denoising pass, none a commit; short answers, whose
    # last block has no commit, read above the cell's 1.33
    assert 1.3 < result["metrics"]["tokens_per_pass.serve"]["value"] < 2.0
    assert 10 < result["metrics"]["commit_pass_share.serve"]["value"] < 34


def test_an_untraced_rehearsal_reads_the_end_to_end_metrics(capsys):
    result, _ = rehearse(capsys, "--rehearse")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "serve_out_tok_s",
                                      "latency_ms_p95"}


def _served(config, seed, sizes):
    """``(prompt, completion)`` pairs served by the toy program through
    ``hvd.serve()``, float32."""
    import horovod_tpu as hvd

    config = dict(config, dtype="float32")
    params = weights_sdar.make_params(config, seed)
    handle = hvd.serve(serve_sdar.build_model(config), params, slots=2,
                       max_new_tokens=64,
                       max_batch_tokens=2 * config["max_seq"])
    rng = np.random.default_rng([seed, 9])
    try:
        prompts = [rng.integers(1, config["vocab_size"] - 1, n).tolist()
                   for n, _ in sizes]
        uids = [handle.submit(p, max_new_tokens=new)
                for p, (_, new) in zip(prompts, sizes)]
        return [(p, handle.result(uid, timeout=600.0))
                for p, uid in zip(prompts, uids)]
    finally:
        handle.close()
        hvd.shutdown()


SIZES = ((333, 41), (150, 30), (601, 64), (90, 50))


def test_the_one_forward_computes_what_the_loop_computed():
    """``controls_sdar.trajectory`` lays a served request out as one
    sequence (the committed tokens, then every block before each of its
    passes as a copy of its own); the reference over it reads, at every
    pass, the very logits its loop computed for that pass from the whole
    sequence as it then stood."""
    import jax.numpy as jnp

    _, _, _, config, _, _ = harness.load_cell(CELL, True)
    config = dict(config, max_seq=512)
    params = weights_sdar.make_params(config, 3)
    prompt = np.random.default_rng(0).integers(1, 500, 38).tolist()
    ids, when, passes = reference_sdar.generate(
        params, prompt, reference_sdar.frozen(config), 13)
    *arrays, reads = controls_sdar.trajectory(prompt, ids, when, config)
    assert len(reads) == len(passes)
    assert arrays[0].shape == (52 + 4 * len(reads),)
    # copy 0 is the sequence; a block's copies are its passes, in order
    assert (arrays[3][:52] == 0).all() and arrays[3][52:].max() == 2
    assert (arrays[1][52:56] == np.arange(36, 40)).all()
    rows = np.concatenate([r[0] for r in reads])
    served = np.concatenate([r[3] for r in reads])
    best, first, sure, picked = controls_sdar.forward(config)(
        params, *(jnp.asarray(a) for a in arrays), jnp.asarray(rows),
        jnp.asarray(served[None]))
    looped = np.concatenate([logits for _, logits in passes])
    assert np.abs(np.asarray(best) - looped.max(-1)).max() < 5e-5
    assert (np.asarray(first) == looped.argmax(-1)).all()
    token, choice = controls_sdar.gaps(reads, np.asarray(best),
                                       np.asarray(sure),
                                       np.asarray(picked)[0])
    # the loop serves its own first tokens and its own surest positions
    assert token.size == 52 - 38 and not token.any() and not choice.any()
    # 38 mod 4 = 2 known positions: the first block has one pass
    assert reads[0][1].tolist() == [False, False, True, True]
    assert reads[0][2].tolist() == [False, False, True, True]
    assert [int(r[1].sum()) for r in reads[1:3]] == [4, 2]


def test_the_float8_control_and_the_planted_faults_fail_a_limit():
    """At the toy widths, on trajectories the float32 program served: the
    float8 reference's own first tokens and choices, and those of a
    reference with a causal mask inside the block, with the commit pass
    skipped (half a finished block's keys those of [MASK]) or with [MASK]
    embedded as token 0, lie further from the float32 reference than the
    cell's limits allow by at least one of the four numbers; the
    program's own pass all four (exactly: float32 against float32). The
    sampler that ranks positions by their largest logit serves the
    program's own tokens and is told by the choice gaps alone: over the
    program's 0 here, and over the cell's limit only at the cell's size
    (``benchmark/limits/sdar-serve-reason-c1.json``): 47 passes of a
    vocabulary of 512 do not reach it."""
    _, _, _, config, mix, limits = harness.load_cell(CELL, True)
    config = dict(config, max_seq=1024)
    sample = _served(config, 41, SIZES)
    mix = dict(mix, prompt_len={"max": 601}, new_tokens={"max": 64})
    gaps = serve_sdar.reference_gaps(config, mix, 41, sample, "fp8",
                                     faults=True)
    print(gaps)
    failed = lambda found: [name for name, key in serve_sdar.LIMITS
                            if found[key] > limits[name]]
    assert not failed(gaps) and gaps["flips"] == 0
    assert gaps["widest_gap"] == gaps["widest_choice_gap"] == 0.0
    assert gaps["tokens"] == sum(len(c.tokens) + len(c.cut)
                                 for _, c in sample)
    assert "served_logit_gap_p99" in failed(gaps["control"])
    assert set(gaps["faults"]) == set(controls_sdar.FAULTS)
    for name in controls_sdar.RUN_APART:
        assert "served_logit_gap_p99" in failed(gaps["faults"][name]), name
    ranked = gaps["faults"]["confidence_from_logit"]
    assert ranked["widest_gap"] == 0.0 and ranked["p99_choice_gap"] > 0.02


def test_scopes_are_read_innermost_and_the_pass_program_apart():
    decode = "jit(_decode_impl)/%s/reduce_max"
    assert scopes_sdar.scope_of([decode % "unmask"]) == "unmask"
    assert scopes_sdar.scope_of(
        ["jit(_decode_impl)/HybridDecoder/layer_1/moe/dot_general"]) == "moe"
    assert scopes_sdar.scope_of(
        ["jit(_decode_impl)/HybridDecoder/layer_5/mixer/full_attention/"
         "pallas_call"]) == "full_attention"
    assert scopes_sdar.scope_of(
        ["jit(_decode_impl)/HybridDecoder/head/dot_general"]) == "head"
    assert scopes_sdar.scope_of(
        ["jit(f)/HybridDecoder/layer_5/mixer/query/dot"]) == "other"
    ragged = "%ragged-dot-none.3 = bf16[2048,768]{1,0} custom-call(%a, %b)"
    assert scopes_sdar.scope_of([], ragged) == "moe"
    summary = {"trace": {"busy_s": 2.0,
                         "scope_s": {"moe": 1.0, "unmask": 0.1,
                                     "full_attention": 0.05}}}
    read = lambda name: run.load_module("layer_metrics", name).read
    assert read("unmask_time_share.serve")(summary) == pytest.approx(5.0)
    assert read("full_attn_time_share.serve")(summary) == pytest.approx(2.5)
    assert read("moe_time_share.serve")(summary) == pytest.approx(50.0)
    # a program without the scope (the parent commit; another model)
    assert read("unmask_time_share.serve")(
        {"trace": {"busy_s": 2.0, "scope_s": {"moe": 1.0}}}) is None
    assert read("unmask_time_share.serve")({}) is None


def test_the_pass_counters_read_tokens_over_row_passes(capsys):
    """A window of the cell: 64 rows, 2,300 passes of which a third are
    commits; and the least bytes of a pass (``flops_sdar.pass_bytes``):
    six layers' attention matrices, all 128 experts and the router, the
    head, and the keys and values of the positions attended."""
    _, _, _, config, _, _ = harness.load_cell(CELL, False)
    read = lambda name: run.load_module("layer_metrics", name).read
    passes = {"row_passes": 147200, "commit_row_passes": 49000,
              "tokens_unmasked": 196400, "blocks_committed": 49000}
    summary = {"window_passes": passes, "config": config,
               "decode_steps": 2300, "platform": "tpu",
               "device_kind": "TPU v5 lite",
               "window_positions_by_kind": {"kv": 6 * 64 * 1000.0}}
    assert read("tokens_per_pass.serve")(summary) \
        == pytest.approx(196400 / 147200)
    assert read("commit_pass_share.serve")(summary) \
        == pytest.approx(100 * 49000 / 147200)
    said = capsys.readouterr().out
    weights = 6 * (18_874_368 + 128 * 4_718_592 + 262_144) * 2 \
        + 2048 * 151936 * 2
    assert flops_sdar.pass_bytes(config, 6 * 128, 6 * 64 * 1000) \
        == weights + 6 * 64 * 1000 * 2 * 4 * 128 * 2
    assert f"{weights + 6 * 64 * 1000 * 2048:.0f} bytes" in said
    assert flops_sdar.block_write_bytes(64, 4, 4, 128) == 64 * 4 * 2048
    # a program that counts no passes (the parent commit), an idle window
    for name in ("tokens_per_pass.serve", "commit_pass_share.serve"):
        assert read(name)({"window_passes": None}) is None
        assert read(name)({}) is None
        assert read(name)({"window_passes": dict(passes, row_passes=0)}) \
            is None
