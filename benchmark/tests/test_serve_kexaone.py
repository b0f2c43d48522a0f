"""The K-EXAONE-236B-A23B cell rehearsed at toy sizes on the CPU: sound,
traced and untraced, against the float8 control and its planted faults;
the readers of the device seconds by this model's scopes, of the
positions its decode steps attend by kind of leaf, and of the bytes a
full layer's decode kernel has to move. Run by hand (see conftest.py);
about three minutes on the CPU. The mixers, the ring and the norms'
placement against the reference are ``tests/test_window_attention.py``."""

import json

import pytest

from benchmark import flops_kexaone, harness, reference_kexaone, run
from benchmark import scopes_kexaone
from benchmark.runners import serve_kexaone

CELL = "kexaone-serve-mixed-c1"


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
    monkeypatch.delattr(hybrid, "WINDOW")
    with pytest.raises(SystemExit, match="no window mixer"):
        serve_kexaone.build_model(config)


def test_a_sound_traced_rehearsal_is_correct_and_reads_its_metrics(capsys):
    """The CPU's trace carries no ``op_name`` paths and a CPU has no
    peak, so the shares by scope and the roofline have nothing to read
    here and are left out; the accepted ``.serve`` metrics that need no
    steady step are read (three seconds of eight toy layers' prefills
    seldom hold a step with every slot decoding, which the span readers
    ask for), the expert load from its counter and the ring's share of
    the positions read from the engine's. The toy's prompts (160-600) lie past its ring
    of 128, so every ring wraps in the prefill and again in decode."""
    result, lines = rehearse(capsys, "--rehearse", trace="1")
    assert result["correct"] is True and result["failed"] == 0
    # 19-27 alone; 8 beside five busy workers (eight toy layers' prefills)
    assert result["attempted"] > 4
    checks = [l.split()[1].rstrip(":") for l in lines
              if l.startswith("check ")]
    assert checks == ["served_logit_gap", "served_logit_gap_p99",
                      "compiles_in_window", "replica_quarantined",
                      "cache_donated"]
    # max_seq-long rows on the full layers, rings on the window layers
    assert any("'kv': 1048576, 'compressed': 0, 'state': 0, 'ring': 393216"
               in l and "'counter': 84" in l for l in lines)
    assert {"ttft_ms_p95.serve", "tpot_ms_p95.serve",
            "batch_occupancy.serve", "device_idle_share.serve",
            "expert_load_max_over_mean.serve",
            "ring_read_share.serve"} <= set(result["metrics"])
    # six window layers of at most 64 positions to two full ones of a
    # few hundred
    assert 20.0 < result["metrics"]["ring_read_share.serve"]["value"] < 60.0
    assert not {"moe_time_share.serve", "window_time_share.serve",
                "full_attn_time_share.serve", "grouped_decode_roofline"} \
        & set(result["metrics"])


def test_an_untraced_rehearsal_reads_the_end_to_end_metrics(capsys):
    result, _ = rehearse(capsys, "--rehearse")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "serve_out_tok_s",
                                      "latency_ms_p95"}


def test_the_float8_control_and_the_planted_faults_fail_a_limit():
    """At the toy widths the float8 reference's own first tokens, and
    those of a reference with a window layer read as a full one or with a
    ring read one position too far, lie further below the float32
    reference's best than the cell's limits allow; a slot that served
    another request's tokens and an altered token lie past the widest
    gap's limit. The program's served tokens pass both (the sound
    rehearsals above)."""
    import numpy as np

    _, _, _, config, mix, limits = harness.load_cell(CELL, True)
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(1, config["vocab_size"], n).tolist()
    sample = [(draw(n), draw(100)) for n in (200, 300, 450)]
    gaps = serve_kexaone.reference_gaps(config, mix, 41, sample, "fp8",
                                        faults=True)
    assert gaps["control_p99_gap"] > 1.5 * limits["served_logit_gap_p99"]
    assert gaps["control_widest_gap"] >= gaps["control_p99_gap"]
    faults = gaps["faults"]
    assert set(faults) == set(reference_kexaone.FAULTS) | {
        "another_slots_cache", "one_altered_token"}
    # a ring read one position too far reads 0.20 here, 0.37 at full size
    for name in reference_kexaone.FAULTS:
        assert faults[name]["p99_gap"] > limits["served_logit_gap_p99"], name
    assert faults["another_slots_cache"]["widest_gap"] \
        > limits["served_logit_gap"]
    assert faults["one_altered_token"]["median_gap"] \
        > limits["served_logit_gap"]
    assert faults["one_altered_token"]["positions"] == 300


def test_scopes_are_read_innermost_and_the_decode_program_apart():
    decode = "jit(_decode_impl)/HybridDecoder/layer_1/%s/dot_general"
    assert scopes_kexaone.scope_of([decode % "moe"]) == "moe"
    assert scopes_kexaone.scope_of([decode % "mixer/window_attention"]) \
        == "window_attention"
    assert scopes_kexaone.scope_of(
        ["jit(_prefill_impl)/HybridDecoder/layer_3/mixer/full_attention/"
         "pallas_call"]) == "full_attention"
    assert scopes_kexaone.scope_of(
        ["jit(f)/HybridDecoder/layer_1/mixer/query/dot"]) == "other"
    ragged = "%ragged-dot-none.3 = bf16[16384,2048]{1,0} custom-call(%a, %b)"
    assert scopes_kexaone.scope_of([], ragged) == "moe"
    assert scopes_kexaone.scope_of([], "%copy-done.7 = bf16[8] x") == "other"
    summary = {"trace": {"busy_s": 2.0,
                         "scope_s": {"moe": 1.0, "window_attention": 0.1,
                                     "full_attention": 0.3}}}
    read = lambda name: run.load_module("layer_metrics", name).read
    assert read("window_time_share.serve")(summary) == pytest.approx(5.0)
    assert read("full_attn_time_share.serve")(summary) == pytest.approx(15.0)
    assert read("moe_time_share.serve")(summary) == pytest.approx(50.0)
    for name in ("window_time_share.serve", "full_attn_time_share.serve"):
        assert read(name)({"trace": {"busy_s": 2.0, "scope_s": {}}}) is None
        assert read(name)({}) is None


def test_the_rings_share_is_of_the_positions_the_counter_counted():
    reader = run.load_module("layer_metrics", "ring_read_share.serve")
    assert reader.read({"window_positions_by_kind": {
        "kv": 2 * 32 * 3800.0, "ring": 6 * 32 * 128.0}}) == pytest.approx(
            100 * 768 / (768 + 7600))
    assert reader.read({"window_positions_by_kind": None}) is None
    assert reader.read({"window_positions_by_kind": {"kv": 0, "ring": 0}}) \
        is None
    assert reader.read({}) is None


def test_the_roofline_counts_the_positions_attended():
    """Four calls (two steps of two full layers) over 120,000 attended
    positions a step a layer: 4 x 120,000 x 4,096 B = 1.97 GB, 2.4 ms at
    819 GB/s, against kernel time that makes it 40%. Other kernels'
    events, a CPU, a program that counts nothing read nothing."""
    assert flops_kexaone.grouped_decode_bytes(120_000, 8, 128) \
        == 120_000 * 4096
    assert flops_kexaone.grouped_decode_flops(10, 64, 128) == 10 * 4 * 8192
    reader = run.load_module("layer_metrics", "grouped_decode_roofline")
    call = ("%grouped_decode_attention.3 = bf16[32,8,8,128]{3,2,1,0:T(8,128)"
            "(2,1)} custom-call(%a, %b, %c, %d), "
            "custom_call_target=\"tpu_custom_call\"")
    other = ("%kv_cache_write.4 = bf16[32,8,128,16384]{3,2,1,0} "
             "custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    least = 4 * 120_000 * 4096 / 819e9
    summary = {"trace": {"events": [(call, i, least / 0.4 / 4 * 1e9)
                                    for i in range(4)] + [(other, 9, 5e6)]},
               "traced_positions_by_kind": {"kv": 240_000.0,
                                            "ring": 24_576.0},
               "config": {"num_kv_heads": 8, "head_dim": 128,
                          "mixers": ["window", "window", "window", "full"]
                          * 2},
               "platform": "tpu", "device_kind": "TPU v5 lite"}
    assert reader.read(summary) == pytest.approx(40.0)
    assert reader.read(dict(summary, platform="cpu")) is None
    assert reader.read(dict(summary, traced_positions_by_kind=None)) is None
    assert reader.read(dict(summary, trace={"events": [(other, 9, 5e6)]})) \
        is None
    assert reader.read({}) is None
