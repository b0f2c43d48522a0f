"""The Brumby-14B-Base cell rehearsed at toy sizes on the CPU: sound,
with the gate dropped, with the state taken after the padding, and
against the float8 control; the reader of the device seconds by this
model's scopes and the bytes a decode step has to move. Run by hand (see
conftest.py); about two minutes on the CPU."""

import json

import pytest

from benchmark import flops_brumby, harness, run, scopes_brumby
from benchmark.runners import serve_brumby

CELL = "brumby-serve-c1"


def rehearse(capsys, *extra, trace="0"):
    run.main(["--workload", CELL, "--seed", "2147483677", "--seconds", "3",
              "--trace", trace, *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def failed_on_the_gap(result, lines):
    return result["correct"] is False and any(
        l.startswith("check served_logit_gap") and l.endswith("FAILED")
        for l in lines)


def test_no_result_without_a_chip(capsys):
    with pytest.raises(SystemExit) as refusal:
        rehearse(capsys)
    assert refusal.value.code not in (0, None)


def test_a_sound_rehearsal_is_correct(capsys):
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_out_tok_s",
                                      "latency_ms_p95"}
    assert result["attempted"] > 8
    checks = [l.split()[1].rstrip(":") for l in lines
              if l.startswith("check ")]
    assert checks == ["served_logit_gap", "compiles_in_window",
                      "replica_quarantined", "cache_donated"]
    # a cache of states alone, and nothing to take a read share of
    assert any("'kv': 0, 'compressed': 0, 'state': " in l
               and "'decode_kv_read_share': None" in l for l in lines)


def test_a_traced_rehearsal_reads_the_serving_metrics(capsys):
    """The CPU's trace carries no ``op_name`` paths and a CPU has no
    peak, so the share by scope and the roofline have nothing to read
    here and are left out; every accepted ``.serve`` metric is read."""
    result, _ = rehearse(capsys, "--rehearse", trace="1")
    assert result["correct"] is True
    assert {"ttft_ms_p95.serve", "tpot_ms_p95.serve",
            "batch_occupancy.serve", "device_idle_share.serve",
            "queue_wait_ms_p95.serve", "prefill_ms_step.serve",
            "decode_call_ms.serve", "loop_host_ms_step.serve",
            "prefill_tok_s.serve"} <= set(result["metrics"])
    assert not {"retention_time_share.serve", "retention_step_roofline",
                "retention_read_roofline"} & set(result["metrics"])


def test_a_dropped_gate_is_not_correct(capsys, monkeypatch):
    """A timed path that forgets nothing (every gate taken as 1, in the
    prefill and in the decode step alike) serves other tokens."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid

    chunked, step = hybrid.retention_chunked, hybrid.retention_step
    monkeypatch.setattr(
        hybrid, "retention_chunked",
        lambda q, k, v, g, *rest: chunked(q, k, v, jnp.zeros_like(g), *rest))
    monkeypatch.setattr(
        hybrid, "retention_step",
        lambda s, z, q, k, v, g, *rest: step(s, z, q, k, v,
                                             jnp.zeros_like(g), *rest))
    assert failed_on_the_gap(*rehearse(capsys, "--rehearse"))


def test_a_state_taken_after_the_padding_is_not_correct(capsys,
                                                        monkeypatch):
    from horovod_tpu.models import hybrid

    sound = hybrid.retention_chunked
    monkeypatch.setattr(
        hybrid, "retention_chunked",
        lambda q, k, v, g, lengths=None, *rest: sound(q, k, v, g, None,
                                                       *rest))
    assert failed_on_the_gap(*rehearse(capsys, "--rehearse"))


def test_the_float8_control_fails_where_the_program_passes():
    """At the toy widths the float8 reference's own first tokens lie
    further below the float32 reference's best than the cell's limit
    allows; the program's served tokens do not (the sound rehearsals'
    gap is printed by the test above)."""
    import numpy as np

    _, _, _, config, mix, limits = harness.load_cell(CELL, True)
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(1, config["vocab_size"], n).tolist()
    sample = [(draw(n), draw(16)) for n in (150, 333, 400)]
    gaps = serve_brumby.reference_gaps(config, mix, 41, sample, "fp8")
    assert gaps["control_widest_gap"] > 1.5 * limits["served_logit_gap"]


def test_scopes_are_read_innermost_and_holders_left_out():
    path = "jit(_decode_impl)/HybridDecoder/layer_0/mixer/%s/dot_general"
    assert scopes_brumby.scope_of([path % "retention_step"]) \
        == "retention_step"
    assert scopes_brumby.scope_of(
        ["jit(_prefill_impl)/HybridDecoder/layer_2/mixer/retention_chunk/"
         "while/body/mul"]) == "retention_chunk"
    assert scopes_brumby.scope_of(
        ["jit(f)/HybridDecoder/layer_1/mlp/gate/dot"]) == "mlp"
    assert scopes_brumby.scope_of(
        ["jit(f)/HybridDecoder/layer_1/mixer/gate/dot"]) == "other"
    assert scopes_brumby.scope_of([]) == "other"
    summary = {"trace": {"busy_s": 2.0, "scope_s": {
        "retention_step": 0.5, "retention_chunk": 0.25}}}
    assert scopes_brumby.seconds(summary, "retention_step") == 0.5
    assert scopes_brumby.seconds(summary, "head") is None
    assert scopes_brumby.seconds({}, "retention_step") is None
    reader = run.load_module("layer_metrics", "retention_time_share.serve")
    assert reader.read(summary) == pytest.approx(37.5)
    assert reader.read({"trace": {"busy_s": 2.0, "scope_s": {}}}) is None
    assert reader.read({}) is None


def test_the_roofline_counts_every_slot_in_every_layer_twice():
    """32 slots x 4 layers x (8 x 8256 x 129 x 4 B) read once and written
    once = 8.72 GB a step; 100 steps in 1.5974 device seconds under the
    scope is 66.7% of what 819 GB/s allows; nothing to read without the
    scope (a program that lacks it), without decode steps, or on a CPU."""
    assert flops_brumby.state_width(128) == 8256
    assert flops_brumby.state_bytes(8, 128) == 8 * 8256 * 129 * 4
    moved = flops_brumby.retention_step_bytes(32, 4, 8, 128)
    assert moved == 2 * 32 * 4 * 8 * 8256 * 129 * 4 == 8_724_676_608
    reader = run.load_module("layer_metrics", "retention_step_roofline")
    took = 100 * moved / 819e9 / (2 / 3)
    summary = {
        "trace": {"busy_s": 3.0, "scope_s": {"retention_step": took},
                  "modules": [("jit__decode_impl(1)", i, 1) for i in
                              range(100)] + [("jit__prefill_impl", 0, 1)]},
        "config": {"num_layers": 4, "num_kv_heads": 8, "head_dim": 128},
        "slots": 32, "platform": "tpu", "device_kind": "TPU v5 lite"}
    assert reader.read(summary) == pytest.approx(100 * 2 / 3)
    assert reader.read(dict(summary, platform="cpu")) is None
    assert reader.read(dict(summary, trace=dict(
        summary["trace"], scope_s={"mlp": 1.0}))) is None
    assert reader.read(dict(summary, trace=dict(
        summary["trace"], modules=[]))) is None
    assert reader.read({"trace": {}}) is None


def test_the_read_roofline_takes_its_shapes_from_the_calls():
    """A call of 8 states x 1,280 queries is 2 x 8 x 1280 x 8256 x 128 =
    21.6 GFLOP, 0.110 ms at 197 TFLOP/s (its 19.6 MB would take 0.024 ms:
    bound by compute); three calls in 0.5 ms read 65.9%. Other kernels'
    events, a CPU, and a trace without the kernel read nothing."""
    flop = flops_brumby.retention_read_flops(8, 1280, 128)
    assert flop == 2 * 8 * 1280 * 8256 * 128
    moved = flops_brumby.retention_read_bytes(8, 1280, 128)
    assert moved == 8 * (1280 * 128 * 2 + 8256 * (128 * 2 + 4)
                         + 1280 * 129 * 4)
    assert flop / 197e12 > moved / 819e9
    reader = run.load_module("layer_metrics", "retention_read_roofline")
    call = ("%retention_read.7 = (f32[8,1280,128]{2,1,0:T(8,128)}, "
            "f32[8,1280,128]{2,1,0:T(8,128)}) custom-call(%a, %b, %c), "
            "custom_call_target=\"tpu_custom_call\"")
    other = ("%retention_step.4 = (f32[32,8,65,128,128]{4,3,2,1,0}) "
             "custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    took = 3 * flop / 197e12 / 0.659
    summary = {"trace": {"events": [(call, i, took / 3 * 1e9)
                                    for i in range(3)] + [(other, 9, 5e6)]},
               "platform": "tpu", "device_kind": "TPU v5 lite"}
    assert reader.read(summary) == pytest.approx(65.9)
    assert reader.read(dict(summary, platform="cpu")) is None
    assert reader.read(dict(summary, trace={"events": [(other, 9, 5e6)]})) \
        is None
    assert reader.read({}) is None
