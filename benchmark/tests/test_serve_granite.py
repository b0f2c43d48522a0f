"""The granite-4.0-h-small cell rehearsed at toy sizes on the CPU: sound,
traced and untraced, against the float8 control and its planted faults;
the readers of the device seconds by this model's scopes and of the
bytes a decode step's state-space layers have to move. Run by hand (see
conftest.py); about three minutes on the CPU. The mixer, the router, the
multipliers and the faults against the reference are
``tests/test_state_space.py`` and ``tests/test_state_space_model.py``."""

import json

import pytest

from benchmark import controls_granite, flops_granite, harness, run
from benchmark import scopes_granite
from benchmark.runners import serve_granite

CELL = "granite-serve-chat-c1"


def rehearse(capsys, *extra, trace="0"):
    run.main(["--workload", CELL, "--seed", "2147483677", "--seconds", "3",
              "--trace", trace, *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_no_result_without_a_chip(capsys):
    with pytest.raises(SystemExit) as refusal:
        rehearse(capsys)
    assert refusal.value.code not in (0, None)


def test_a_program_without_the_mixer_stops_before_any_compile(monkeypatch):
    """What the parent commit does with this cell's files laid over it."""
    from horovod_tpu.models import hybrid

    _, _, _, config, _, _ = harness.load_cell(CELL, True)
    monkeypatch.delattr(hybrid, "MAMBA2")
    with pytest.raises(SystemExit, match="no state-space mixer"):
        serve_granite.build_model(config)


def test_a_sound_traced_rehearsal_is_correct_and_reads_its_metrics(capsys):
    """The CPU's trace carries no ``op_name`` paths and a CPU has no
    peak, so the shares by scope and the rooflines have nothing to read
    here and are left out; the accepted ``.serve`` metrics that need no
    steady step are read, and the expert load from its counter. The
    toy's prompts (40-400) reach three buckets, several chunks of 64 and
    a ragged last one."""
    result, lines = rehearse(capsys, "--rehearse", trace="1")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 4
    checks = [l.split()[1].rstrip(":") for l in lines
              if l.startswith("check ")]
    assert checks == ["served_logit_gap", "served_logit_gap_p99",
                      "compiles_in_window", "replica_quarantined",
                      "cache_donated"]
    # a state and a tail a state-space layer beside the full layer's rows
    assert any("'kv': 524288, 'compressed': 0, 'state': 589824, "
               "'conv': 62208, 'counter': 960" in l for l in lines)
    assert {"ttft_ms_p95.serve", "tpot_ms_p95.serve",
            "batch_occupancy.serve", "device_idle_share.serve",
            "expert_load_max_over_mean.serve"} <= set(result["metrics"])
    assert not {"moe_time_share.serve", "ssm_time_share.serve",
                "ssm_scan_time_share.serve", "ssm_step_roofline",
                "full_attn_time_share.serve", "grouped_decode_roofline"} \
        & set(result["metrics"])


def test_an_untraced_rehearsal_reads_the_end_to_end_metrics(capsys):
    result, _ = rehearse(capsys, "--rehearse")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "serve_out_tok_s",
                                      "latency_ms_p95"}


def test_the_float8_control_and_the_planted_faults_fail_a_limit():
    """At the toy widths the float8 reference's own first tokens, and
    those of a reference with the slot's earlier occupant's state left
    in it, with the convolution's tail dropped after the prompt or with
    the prompt's padding run through the recurrence, lie further below
    the float32 reference's best than the cell's limits allow; a slot
    that served another request's tokens and an altered token lie past
    the widest gap's limit. The program's served tokens pass both (the
    sound rehearsals above)."""
    import numpy as np

    _, _, _, config, mix, limits = harness.load_cell(CELL, True)
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(1, config["vocab_size"], n).tolist()
    sample = [(draw(n), draw(100)) for n in (200, 300, 390)]
    gaps = serve_granite.reference_gaps(config, mix, 41, sample, "fp8",
                                        faults=True)
    print(gaps)
    assert gaps["control_p99_gap"] > 1.5 * limits["served_logit_gap_p99"]
    assert gaps["control_widest_gap"] >= gaps["control_p99_gap"]
    assert 1.0 < gaps["logits_std"] < 2.5 and gaps["echo_share"] < 0.05
    faults = gaps["faults"]
    assert set(faults) == set(controls_granite.FAULTS) | {
        "another_slots_cache", "one_altered_token"}
    for name in controls_granite.FAULTS:
        assert faults[name]["p99_gap"] > limits["served_logit_gap_p99"], name
    assert faults["another_slots_cache"]["widest_gap"] \
        > limits["served_logit_gap"]
    assert faults["one_altered_token"]["median_gap"] \
        > limits["served_logit_gap"]
    assert faults["one_altered_token"]["positions"] == 300


def test_scopes_are_read_innermost_and_the_decode_program_apart():
    decode = "jit(_decode_impl)/HybridDecoder/layer_1/%s/dot_general"
    assert scopes_granite.scope_of([decode % "moe"]) == "moe"
    assert scopes_granite.scope_of([decode % "mixer/ssm"]) == "ssm"
    assert scopes_granite.scope_of([decode % "mixer/ssm/ssm_step"]) \
        == "ssm_step"
    assert scopes_granite.scope_of(
        ["jit(_prefill_impl)/HybridDecoder/layer_0/mixer/ssm/ssm_scan/"
         "while/body/dot_general"]) == "ssm_scan"
    assert scopes_granite.scope_of(
        ["jit(_prefill_impl)/HybridDecoder/layer_5/mixer/full_attention/"
         "pallas_call"]) == "full_attention"
    assert scopes_granite.scope_of(
        ["jit(f)/HybridDecoder/layer_5/mixer/query/dot"]) == "other"
    ragged = "%ragged-dot-none.3 = bf16[40960,768]{1,0} custom-call(%a, %b)"
    assert scopes_granite.scope_of([], ragged) == "moe"
    summary = {"trace": {"busy_s": 2.0,
                         "scope_s": {"moe": 1.0, "ssm": 0.2, "ssm_scan": 0.1,
                                     "ssm_step": 0.3,
                                     "full_attention": 0.05}}}
    read = lambda name: run.load_module("layer_metrics", name).read
    assert read("ssm_time_share.serve")(summary) == pytest.approx(30.0)
    assert read("ssm_scan_time_share.serve")(summary) == pytest.approx(5.0)
    assert read("full_attn_time_share.serve")(summary) == pytest.approx(2.5)
    assert read("moe_time_share.serve")(summary) == pytest.approx(50.0)
    # a decode-only slice has no scan: the whole mixer is still read
    no_scan = {"trace": {"busy_s": 2.0, "scope_s": {"ssm": 0.2,
                                                    "ssm_step": 0.3}}}
    assert read("ssm_time_share.serve")(no_scan) == pytest.approx(25.0)
    assert read("ssm_scan_time_share.serve")(no_scan) is None
    # a program without the scopes (the parent commit; another model)
    for name in ("ssm_time_share.serve", "ssm_scan_time_share.serve",
                 "ssm_step_roofline"):
        assert read(name)({"trace": {"busy_s": 2.0, "scope_s": {"moe": 1.0},
                                     "decode_scope_s": {"moe": 1.0},
                                     "modules": [], "events": []},
                           "config": {}, "platform": "tpu",
                           "device_kind": "TPU v5 lite", "slots": 4}) is None
        assert read(name)({}) is None


def test_the_roofline_counts_the_states_and_the_tails_of_every_slot():
    """Two decode steps of the cell: 2 x 64 slots x 9 layers x 2 x (4.19
    MB of state + 50.7 kB of tail) = 9.78 GB, 11.9 ms at 819 GB/s,
    against time under ``ssm_step`` in the decode program that makes it
    80%. A CPU, a slice with no decode step, a program with no such
    scope read nothing."""
    a_step = flops_granite.ssm_step_bytes(64, 9, 128, 64, 128, 1, 4)
    assert a_step == 64 * 9 * 2 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    reader = run.load_module("layer_metrics", "ssm_step_roofline")
    least = 2 * a_step / 819e9
    summary = {"trace": {"modules": [("jit__decode_impl(123)", 0, 5),
                                     ("jit__prefill_impl(9)", 7, 5),
                                     ("jit__decode_impl(123)", 9, 5)],
                         "events": [], "busy_s": 1.0,
                         "scope_s": {"ssm_step": 9.0},
                         "decode_scope_s": {"ssm_step": least / 0.8}},
               "config": {"mixers": ["mamba2"] * 5 + ["full"]
                          + ["mamba2"] * 4,
                          "ssm": {"num_heads": 128, "head_dim": 64,
                                  "d_state": 128, "n_groups": 1,
                                  "d_conv": 4}},
               "slots": 64, "platform": "tpu", "device_kind": "TPU v5 lite"}
    assert reader.read(summary) == pytest.approx(80.0)
    assert reader.read(dict(summary, platform="cpu")) is None
    assert reader.read(dict(summary, trace=dict(
        summary["trace"], modules=[("jit__prefill_impl(9)", 7, 5)]))) is None
    assert reader.read(dict(summary, trace=dict(
        summary["trace"], decode_scope_s={"moe": 1.0}))) is None
    assert reader.read(dict(summary, config={"mixers": ["full"]})) is None
