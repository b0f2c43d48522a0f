"""The MiniCPM-SALA cell rehearsed at toy sizes on the CPU: sound, with
the lightning state taken after the padding, and against the float8
control; and the reader of the device seconds by scope. Run by hand (see
conftest.py); about two minutes on the CPU."""

import json

import pytest

from benchmark import harness, run, scopes
from benchmark.runners import serve_sala

CELL = "sala-serve-long-c1"
NEW_LAYER_METRICS = {"prefill_tok_s.serve", "lightning_time_share.serve",
                     "sparse_time_share.serve"}


def rehearse(capsys, *extra, trace="0"):
    run.main(["--workload", CELL, "--seed", "2147483677", "--seconds", "3",
              "--trace", trace, *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


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
    # every prompt of the toy mix is past the toy dense_len, as the
    # cell's are past the published one
    assert any("'state'" in l and "'compressed'" in l for l in lines)


def test_a_traced_rehearsal_reads_the_span_metric(capsys):
    """The CPU's trace carries no ``op_name`` paths, so the two shares by
    scope have nothing to read here and are left out; the span metric and
    every accepted ``.serve`` metric are read."""
    result, _ = rehearse(capsys, "--rehearse", trace="1")
    assert result["correct"] is True
    assert "prefill_tok_s.serve" in result["metrics"]
    assert {"ttft_ms_p95.serve", "prefill_ms_step.serve",
            "decode_call_ms.serve"} <= set(result["metrics"])


def test_a_state_taken_after_the_padding_is_not_correct(capsys,
                                                        monkeypatch):
    from horovod_tpu.models import hybrid

    sound = hybrid.lightning_chunked

    def after_padding(q, k, v, slopes, lengths=None, chunk=256):
        return sound(q, k, v, slopes, None, chunk)

    monkeypatch.setattr(hybrid, "lightning_chunked", after_padding)
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is False
    assert any(l.startswith("check served_logit_gap") and
               l.endswith("FAILED") for l in lines)


def test_the_float8_control_fails_where_the_program_passes():
    """At the toy widths the float8 reference's own first tokens lie
    further below the float32 reference's best than the cell's limit
    allows (measured 0.023-0.024 against 0.012 over 1200 positions); the
    program's served tokens do not (the sound rehearsals read 0-0.0005)."""
    import numpy as np

    _, _, _, config, _, limits = harness.load_cell(CELL, True)
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(1, config["vocab_size"], n).tolist()
    sample = [(draw(n), draw(400)) for n in (150, 333, 471)]
    gaps = serve_sala.reference_gaps(config, 41, sample, "fp8")
    assert gaps["control_widest_gap"] > 1.5 * limits["served_logit_gap"]


def test_scopes_are_read_innermost_and_holders_left_out():
    path = "jit(_prefill_impl)/HybridDecoder/layer_0/mixer/%s/dot_general"
    assert scopes.scope_of([path % "sparse_attn"]) == "sparse_attn"
    assert scopes.scope_of(["jit(f)/HybridDecoder/layer_1/mlp/gate/dot"]) \
        == "mlp"
    assert scopes.scope_of(["jit(f)/HybridDecoder/layer_1/mixer/gate/dot"]) \
        == "other"
    assert scopes.scope_of([]) == "other"
    assert scopes._HOLDER.search("%while.3 = (s32[], bf16[8]) while(%t)")
    assert not scopes._HOLDER.search("%fusion.1 = bf16[8] fusion(%while.3)")
    summary = {"trace": {"busy_s": 2.0, "scope_s": {
        "lightning": 0.1, "sparse_attn": 0.5, "sparse_select": 0.1}}}
    assert scopes.share(summary, ("lightning",)) == pytest.approx(5.0)
    assert scopes.share(summary, ("sparse_select", "sparse_attn")) \
        == pytest.approx(30.0)
    assert scopes.share({"trace": {"busy_s": 2.0, "scope_s": {}}},
                        ("lightning",)) is None
    assert scopes.share({}, ("lightning",)) is None
