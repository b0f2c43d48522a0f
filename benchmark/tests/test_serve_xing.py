"""The Xing4.0-29B-A4B cell rehearsed at toy sizes on the CPU: sound,
with the router's scaling factor dropped, and against the float8
control; the readers of the device seconds by this model's scopes, of
the expert counter and of the bytes a decode step's expert layers have to
move. Run by hand (see conftest.py); about two minutes on the CPU. The
other controls of the issue (``H_res`` forced to the identity, Sinkhorn
cut to one iteration, the rotary key left unrotated) fail the cell's
limit in ``tests/test_hybrid_model.py``, on the same toy configuration."""

import json

import pytest

from benchmark import flops_xing, harness, run, scopes_xing
from benchmark.runners import serve_xing

CELL = "xing-serve-c1"


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
    monkeypatch.delattr(hybrid, "LATENT")
    with pytest.raises(SystemExit, match="no latent mixer"):
        serve_xing.build_model(config)


def test_a_sound_traced_rehearsal_is_correct_and_reads_its_metrics(capsys):
    """The CPU's trace carries no ``op_name`` paths and a CPU has no
    peak, so the shares by scope and the roofline have nothing to read
    here and are left out; every accepted ``.serve`` metric is read, and
    the expert load from the counter."""
    result, lines = rehearse(capsys, "--rehearse", trace="1")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    checks = [l.split()[1].rstrip(":") for l in lines
              if l.startswith("check ")]
    assert checks == ["served_logit_gap", "served_logit_gap_p99",
                      "compiles_in_window", "replica_quarantined",
                      "cache_donated"]
    # a cache of latents and counters alone
    assert any("'kv': 0, 'compressed': 0, 'state': 0, 'latent': " in l
               and "'counter': " in l for l in lines)
    assert {"ttft_ms_p95.serve", "tpot_ms_p95.serve",
            "batch_occupancy.serve", "device_idle_share.serve",
            "queue_wait_ms_p95.serve", "prefill_ms_step.serve",
            "decode_call_ms.serve", "loop_host_ms_step.serve",
            "prefill_tok_s.serve", "expert_load_max_over_mean.serve"} \
        <= set(result["metrics"])
    assert 1.0 <= result["metrics"][
        "expert_load_max_over_mean.serve"]["value"] < 2.0
    assert not {"moe_time_share.serve", "latent_time_share.serve",
                "hyper_time_share.serve", "moe_decode_roofline"} \
        & set(result["metrics"])


def test_an_untraced_rehearsal_reads_the_end_to_end_metrics(capsys):
    result, _ = rehearse(capsys, "--rehearse")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "serve_out_tok_s",
                                      "latency_ms_p95"}


def test_a_dropped_scaling_factor_is_not_correct(capsys, monkeypatch):
    """A timed path whose routed experts weigh half of what the
    configuration says (``routed_scaling_factor`` taken as 1) serves
    other tokens."""
    from horovod_tpu.models import hybrid

    sound = hybrid.route
    monkeypatch.setattr(hybrid, "route", lambda x, w, b, k, scaling:
                        sound(x, w, b, k, 1.0))
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is False and any(
        l.startswith("check served_logit_gap") and l.endswith("FAILED")
        for l in lines)


def test_the_float8_control_fails_where_the_program_passes():
    """At the toy widths the float8 reference's own first tokens lie
    further below the float32 reference's best than the cell's limit
    allows; the program's served tokens do not (the sound rehearsals'
    gap is printed by the tests above)."""
    import numpy as np

    _, _, _, config, mix, limits = harness.load_cell(CELL, True)
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(1, config["vocab_size"], n).tolist()
    sample = [(draw(n), draw(100)) for n in (50, 200, 300)]
    gaps = serve_xing.reference_gaps(config, mix, 41, sample, "fp8")
    assert gaps["control_p99_gap"] > 1.5 * limits["served_logit_gap_p99"]
    assert gaps["control_widest_gap"] >= gaps["control_p99_gap"]


def test_the_faults_are_planted_in_the_reference_and_leave_it_plain():
    """``benchmark/controls_xing.py``: each fault's forward differs from
    the sound reference's, the reference's own functions are back in
    place afterwards, and ``reference_gaps`` reads every fault, a slot
    that read another request's latents and an altered token past the
    widest gap's limit."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import controls_xing, reference_xing, weights_xing

    _, _, _, config, mix, limits = harness.load_cell(CELL, True)
    sound = reference_xing.hyper_maps, reference_xing._rope
    params = weights_xing.make_params(config, 41)
    ids = jnp.asarray(np.random.default_rng(6).integers(
        1, config["vocab_size"], 128), jnp.int32)
    rows = jnp.arange(64, 128)
    want = np.asarray(reference_xing.forward(
        params, ids, reference_xing.frozen(config), "f32", rows))
    for fault in controls_xing.FAULTS:
        got = np.asarray(controls_xing.forward(fault, config)(
            params, ids, rows))
        assert np.abs(got - want).max() > 1e-4, fault
    assert (reference_xing.hyper_maps, reference_xing._rope) == sound
    again = np.asarray(reference_xing.forward(
        params, ids, reference_xing.frozen(config), "f32", rows))
    assert np.array_equal(again, want)

    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(1, config["vocab_size"], n).tolist()
    sample = [(draw(n), draw(40)) for n in (50, 120)]
    faults = serve_xing.reference_gaps(config, mix, 41, sample,
                                       faults=True)["faults"]
    assert set(faults) == set(controls_xing.FAULTS) | {
        "another_slots_latents", "one_altered_token"}
    assert faults["another_slots_latents"]["widest_gap"] \
        > limits["served_logit_gap"]
    assert faults["one_altered_token"]["median_gap"] \
        > limits["served_logit_gap"]
    assert faults["one_altered_token"]["positions"] == 80


def test_scopes_are_read_innermost_and_the_decode_program_apart():
    decode = "jit(_decode_impl)/HybridDecoder/layer_1/%s/dot_general"
    assert scopes_xing.scope_of([decode % "moe"]) == "moe"
    assert scopes_xing.scope_of([decode % "moe/shared/gate"]) == "moe"
    assert scopes_xing.scope_of([decode % "mixer/latent_step"]) \
        == "latent_step"
    assert scopes_xing.scope_of(
        ["jit(_prefill_impl)/HybridDecoder/layer_2/mixer/latent_prompt/"
         "pallas_call"]) == "latent_prompt"
    assert scopes_xing.scope_of(
        ["jit(f)/HybridDecoder/layer_0/hyper/hyper_mixer/while/body/div"]) \
        == "hyper"
    assert scopes_xing.scope_of(["jit(f)/HybridDecoder/layer_0/mlp/up/dot"]) \
        == "mlp"
    assert scopes_xing.scope_of(
        ["jit(f)/HybridDecoder/layer_1/mixer/q_a/dot"]) == "other"
    # XLA's grouped-product kernel has no path: its name stands for it
    ragged = "%ragged-dot-none.3 = bf16[32768,1024]{1,0} custom-call(%a, %b)"
    assert scopes_xing.scope_of([], ragged) == "moe"
    assert scopes_xing.scope_of([], "%ragged-dot-metadata.1 = s32[] x") \
        == "moe"
    assert scopes_xing.scope_of([], "%copy-done.7 = bf16[8] x") == "other"
    summary = {"trace": {"busy_s": 2.0,
                         "scope_s": {"moe": 1.0, "latent_step": 0.25,
                                     "latent_prompt": 0.25, "hyper": 0.1},
                         "decode_scope_s": {"moe": 0.6}}}
    assert scopes_xing.seconds(summary, "moe") == 1.0
    assert scopes_xing.seconds(summary, "moe", "decode_scope_s") == 0.6
    assert scopes_xing.seconds(summary, "head") is None
    assert scopes_xing.seconds({}, "moe") is None
    read = lambda name: run.load_module("layer_metrics", name).read
    assert read("moe_time_share.serve")(summary) == pytest.approx(50.0)
    assert read("latent_time_share.serve")(summary) == pytest.approx(25.0)
    assert read("hyper_time_share.serve")(summary) == pytest.approx(5.0)
    for name in ("moe_time_share.serve", "latent_time_share.serve",
                 "hyper_time_share.serve"):
        assert read(name)({"trace": {"busy_s": 2.0, "scope_s": {}}}) is None
        assert read(name)({}) is None


def test_the_expert_load_is_the_busiest_expert_over_the_mean():
    reader = run.load_module("layer_metrics",
                             "expert_load_max_over_mean.serve")
    even = [[100] * 8, [100] * 8]
    assert reader.read({"expert_pairs": even}) == pytest.approx(1.0)
    skewed = [[100] * 8, [240] + [80] * 7]      # 240 of a mean of 100
    assert reader.read({"expert_pairs": skewed}) == pytest.approx(2.4)
    assert reader.read({"expert_pairs": [[0] * 8]}) is None
    assert reader.read({}) is None


def test_the_roofline_counts_the_experts_the_steps_hit():
    """Five layers, 100 traced steps, the counter round the slice saying
    that 63 of 64 experts were hit a step (6,300 hits in 100 counted
    steps a layer): 100 x 5 x (63 + 1 shared) experts of 22 MB and the
    router's 0.46 MB = 705 GB... at 819 GB/s against 1.2 s under the
    scope in the decode program; nothing to read without the scope, the
    counter, decode steps, or on a CPU."""
    assert flops_xing.expert_bytes(3584, 1024) == 3 * 3584 * 1024 * 2
    assert flops_xing.moe_step_fixed_bytes(3584, 1024, 1, 64) \
        == 3 * 3584 * 1024 * 2 + 3584 * 64 * 2
    assert flops_xing.moe_pair_flops(3584, 1024) == 6 * 3584 * 1024
    moved = flops_xing.moe_decode_bytes(100, 100 * 5 * 63, 5, 3584, 1024,
                                        1, 64)
    assert moved == 100 * 5 * (64 * 3 * 3584 * 1024 * 2 + 3584 * 64 * 2)
    reader = run.load_module("layer_metrics", "moe_decode_roofline")
    counts = [[[9000] * 64, [197] * 63 + [189], [200] * 64]] * 5
    assert sum(counts[0][1]) == 200 * 63
    took = moved / 819e9 / 0.75
    summary = {
        "trace": {"busy_s": 3.0, "scope_s": {"moe": 2 * took},
                  "decode_scope_s": {"moe": took},
                  "modules": [("jit__decode_impl(1)", i, 1) for i in
                              range(100)] + [("jit__prefill_impl", 0, 1)]},
        "traced_expert_counts": counts,
        "config": {"d_model": 3584, "expert_d_ff": 1024,
                   "shared_experts": 1, "num_experts": 64,
                   "experts_count": 64},
        "platform": "tpu", "device_kind": "TPU v5 lite"}
    assert reader.read(summary) == pytest.approx(75.0)
    assert reader.read(dict(summary, platform="cpu")) is None
    assert reader.read(dict(summary, traced_expert_counts=None)) is None
    assert reader.read(dict(summary, trace=dict(
        summary["trace"], decode_scope_s={"mlp": 1.0}))) is None
    assert reader.read(dict(summary, trace=dict(
        summary["trace"], modules=[]))) is None
    assert reader.read({"trace": {}}) is None


def test_the_latent_roofline_counts_the_positions_attended():
    """Twelve calls (two steps of six layers) over 170,000 attended
    positions a step: 12 x 170,000 x 576 x 2 B = 2.35 GB, 2.87 ms at
    819 GB/s, against 4.1 ms of kernel time: 70%. Other kernels' events,
    a CPU, a program without the kernel (no counter) read nothing."""
    assert flops_xing.latent_decode_bytes(170_000, 512, 64) \
        == 170_000 * 576 * 2
    reader = run.load_module("layer_metrics", "latent_decode_roofline")
    call = ("%latent_decode_attention.3 = bf16[64,32,512]{2,1,0:T(8,128)"
            "(2,1)} custom-call(%a, %b, %c, %d, %e), "
            "custom_call_target=\"tpu_custom_call\"")
    other = ("%kv_cache_write.4 = bf16[64,1,512,8192]{3,2,1,0} "
             "custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    least = 12 * 170_000 * 576 * 2 / 819e9
    summary = {"trace": {"events": [(call, i, least / 0.7 / 12 * 1e9)
                                    for i in range(12)] + [(other, 9, 5e6)]},
               "traced_positions_a_step": 170_000.0,
               "config": {"kv_rank": 512, "rope_dim": 64},
               "platform": "tpu", "device_kind": "TPU v5 lite"}
    assert reader.read(summary) == pytest.approx(70.0)
    assert reader.read(dict(summary, platform="cpu")) is None
    assert reader.read(dict(summary, traced_positions_a_step=None)) is None
    assert reader.read(dict(summary, trace={"events": [(other, 9, 5e6)]})) \
        is None
    assert reader.read({}) is None
