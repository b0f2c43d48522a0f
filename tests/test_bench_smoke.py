"""bench.py smoke coverage (tier-1 safe).

The benchmark harness is driver-facing: a module-level typo or a stale
API call would otherwise only surface in a perf run. Import it and run
the two microbench suites in --tiny mode — every code path (runtime
enqueue, program-cache warmup checks, flight-recorder A/B, the ZeRO-1
replicated-vs-sharded comparison and its JSON schema) in seconds.
"""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench(hvd):
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import bench as bench_mod

    return bench_mod


def test_bench_imports_and_flags(bench):
    # the sweep's workload table stays importable and complete
    assert callable(bench.collectives_main)
    assert callable(bench.sharded_optimizer_main)
    assert callable(bench.control_plane_main)
    assert "resnet50" in bench.CNN_CONFIGS


def test_collectives_suite_tiny(bench, capsys):
    result = bench.collectives_main(tiny=True)
    assert result["tiny"] is True
    assert result["unit"] == "ms"
    assert result["sizes"], "no size rows emitted"
    # the emitted line is valid single-line JSON (driver contract)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["metric"] == result["metric"]


def test_integrity_suite_tiny(bench, capsys, monkeypatch):
    """PR 10 acceptance shape: the --integrity microbench emits one JSON
    line with the off/default/every-dispatch p50s and the zero-compile
    canary; the env knobs it toggles are restored afterwards."""
    monkeypatch.delenv("HOROVOD_INTEGRITY", raising=False)
    result = bench.integrity_main(tiny=True)
    assert result["tiny"] is True
    assert result["unit"] == "%"
    assert result["goal"] == "< 1%"
    assert result["p50_ms_integrity_off"] > 0
    assert result["p50_ms_default_interval"] > 0
    assert result["p50_ms_every_dispatch"] > 0
    # warmup compiled the digest program; the timed phases reuse it
    assert result["steady_state_compiles"] == 0
    assert result["digest_checks_timed_phase"] >= 1
    assert os.environ.get("HOROVOD_INTEGRITY") is None
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["value"] == result["value"]


def test_sharded_optimizer_tiny(bench, capsys):
    result = bench.sharded_optimizer_main(tiny=True)
    assert result["tiny"] is True
    b = result["opt_state_bytes_per_chip"]
    assert 0 < b["sharded"] < b["replicated"]
    # sharded state must actually shrink toward 1/N (padding-limited on
    # toy shapes, so just require a real reduction)
    assert result["state_bytes_reduction_x"] > 1.5
    assert result["steady_state_program_builds"] == 0
    # per-stage rows (ZeRO 1/2/3): stage 2 halves the gradient wire
    # bytes (RS only, no grad AG); stage 3 additionally shards params at
    # rest; every stage keeps the zero-steady-state-compile invariant
    stages = result["stages"]
    assert set(stages) == {"stage1", "stage2", "stage3"}
    s1, s2, s3 = stages["stage1"], stages["stage2"], stages["stage3"]
    for row in (s1, s2, s3):
        assert row["steady_state_builds"] == 0
        assert set(row["bytes_per_chip"]) == {
            "params", "grads", "optimizer_state"}
    assert s2["grad_wire_bytes_per_step"] * 2 == s1[
        "grad_wire_bytes_per_step"]
    assert s3["grad_wire_bytes_per_step"] == s2["grad_wire_bytes_per_step"]
    assert s2["bytes_per_chip"]["grads"] < s1["bytes_per_chip"]["grads"]
    assert s3["bytes_per_chip"]["params"] < s1["bytes_per_chip"]["params"]
    assert 0.0 <= s3["gather_hidden_fraction"] <= 1.0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["value"] == result["value"]


def test_tiny_flagship_emits_step_breakdown(bench, capsys, monkeypatch):
    """PR 6 acceptance: bare ``python bench.py --tiny`` — here its entry
    function — emits a headline carrying step_breakdown +
    comm_hidden_fraction from the step profiler."""
    result = bench.tiny_main()
    # tiny_main enables the step profiler via os.environ + configure();
    # undo BOTH the env var and the module state, or every later test in
    # the session sees profiler.enabled() == True
    monkeypatch.delenv("HOROVOD_PROFILE", raising=False)
    from horovod_tpu import profiler
    profiler.configure()
    assert result["tiny"] is True
    phases = result["step_breakdown"]
    assert set(phases) == {"host", "compute", "exposed_comm", "optimizer"}
    assert sum(phases.values()) > 0
    assert 0.0 <= result["comm_hidden_fraction"] <= 1.0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["step_breakdown"] == phases


# ---------------------------------------------------------------------------
# bench_compare regression gate
# ---------------------------------------------------------------------------

_REPO_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture
def bench_compare():
    if _REPO_TOOLS not in sys.path:
        sys.path.insert(0, _REPO_TOOLS)
    import bench_compare as mod

    return mod


def _artifact(path, rows):
    tail = "\n".join(["benchmark log noise"]
                     + [json.dumps(r) for r in rows])
    with open(path, "w") as f:
        json.dump({"n": 1, "cmd": "python bench.py", "rc": 0,
                   "tail": tail}, f)
    return str(path)


_BASE_ROW = {"metric": "images/sec/chip (ResNet-50 synthetic)",
             "value": 2000.0, "unit": "images/sec/chip", "mfu": 0.5,
             "step_breakdown": {"host": 0.002, "compute": 0.04,
                                "exposed_comm": 0.003, "optimizer": 0.005}}


def test_bench_compare_clean_pass(bench_compare, tmp_path, capsys):
    base = _artifact(tmp_path / "base.json", [_BASE_ROW])
    cand_row = dict(_BASE_ROW, value=1980.0)  # -1%: inside the gate
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 0
    out = capsys.readouterr().out
    assert "no regressions" in out


def test_bench_compare_degraded_candidate_fails(bench_compare, tmp_path,
                                                capsys):
    base = _artifact(tmp_path / "base.json", [_BASE_ROW])
    cand_row = dict(_BASE_ROW, value=1500.0)  # -25% throughput
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    rc = bench_compare.main(["--baseline", base, "--candidate", cand,
                             "--threshold-pct", "5"])
    assert rc == 1
    assert "REGRESSION" in capsys.readouterr().out


def test_bench_compare_phase_regression_fails(bench_compare, tmp_path,
                                              capsys):
    # throughput flat but exposed comm tripled: the phase row catches it
    base = _artifact(tmp_path / "base.json", [_BASE_ROW])
    cand_row = dict(_BASE_ROW)
    cand_row["step_breakdown"] = dict(_BASE_ROW["step_breakdown"],
                                      exposed_comm=0.009)
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    assert "exposed_comm seconds" in capsys.readouterr().out


def test_bench_compare_expands_summary_and_skips_tiny(bench_compare,
                                                      tmp_path):
    # truncated-run shape: the only row is a cumulative summary line
    summary = {"metric": "summary — all headlines", "value": 1.0,
               "unit": "tokens/sec/chip",
               "results": [_BASE_ROW,
                           {"metric": "tiny row", "value": 5.0,
                            "unit": "ms", "tiny": True}]}
    rows = bench_compare.derived_rows(
        bench_compare.parse_artifact(
            _artifact(tmp_path / "sum.json", [summary])))
    assert "images/sec/chip (ResNet-50 synthetic)" in rows
    assert not any("tiny" in k for k in rows)
    assert not any(k.startswith("summary") for k in rows)


@pytest.mark.parametrize("base,cand", [("BENCH_r05.json",
                                        "BENCH_r06.json")])
def test_bench_compare_real_artifacts(bench_compare, base, cand):
    """The repo's own trajectory must pass its own gate, over every pair
    of consecutive chip records the repository still holds. r05 -> r06 is
    ISSUE 12's acceptance: the bucket-wise gradient release round clears
    the gate against r05 — ResNet-50 and Inception-V3 MFU up well past
    the 5% threshold, nothing else regressed. (r04 -> r05 went with the
    r01-r04 records in PR 21.)"""
    assert bench_compare.main([os.path.join(_REPO, base),
                               os.path.join(_REPO, cand)]) == 0


def test_bench_compare_memory_row_regression_fails(bench_compare,
                                                   tmp_path, capsys):
    """ISSUE 13 acceptance: memory rows are direction-aware. Throughput
    flat but the grads footprint doubled — the bytes sub-metric (lower
    is better) fails the gate on its own."""
    base_row = dict(_BASE_ROW,
                    bytes_per_chip={"params": 4.0e8, "grads": 4.0e8},
                    peak_hbm_bytes=1.2e9)
    base = _artifact(tmp_path / "base.json", [base_row])
    cand_row = dict(base_row,
                    bytes_per_chip={"params": 4.0e8, "grads": 8.0e8})
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "grads bytes" in out
    assert "lower is better" in out


def test_bench_compare_memory_rows_clean_pass(bench_compare, tmp_path,
                                              capsys):
    # identical footprints (and a peak watermark) compare clean
    row = dict(_BASE_ROW, bytes_per_chip={"params": 4.0e8},
               peak_hbm_bytes=1.2e9)
    base = _artifact(tmp_path / "base.json", [row])
    cand = _artifact(tmp_path / "cand.json", [dict(row)])
    assert bench_compare.main([base, cand]) == 0
    out = capsys.readouterr().out
    assert "params bytes" in out
    assert "peak_hbm bytes" in out


def test_bench_compare_serve_p99_regression_fails(bench_compare,
                                                  tmp_path, capsys):
    """ISSUE 15 satellite: serving tail latencies are direction-aware
    sub-metrics. Throughput flat but the candidate's p99 latency
    tripled — the ms row (lower is better) fails the gate on its own."""
    serve_row = {"metric": "tokens/sec/chip (serving, continuous "
                           "batching)",
                 "value": 5000.0, "unit": "tokens/sec/chip",
                 "p50_latency_ms": 80.0, "p99_latency_ms": 200.0,
                 "p50_ttft_ms": 20.0, "p99_ttft_ms": 60.0}
    base = _artifact(tmp_path / "base.json", [serve_row])
    cand = _artifact(tmp_path / "cand.json",
                     [dict(serve_row, p99_latency_ms=600.0)])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "p99_latency_ms" in out
    assert "lower is better" in out
    # p50 + TTFT rows held steady and compare clean
    assert "        ok  tokens/sec/chip (serving, continuous batching) " \
           "[p50_latency_ms]" in out


def test_bench_compare_serve_rows_clean_pass(bench_compare, tmp_path,
                                             capsys):
    row = {"metric": "tokens/sec/chip (serving)", "value": 5000.0,
           "unit": "tokens/sec/chip", "p50_latency_ms": 80.0,
           "p99_latency_ms": 200.0, "p50_ttft_ms": 20.0,
           "p99_ttft_ms": 60.0}
    base = _artifact(tmp_path / "base.json", [row])
    cand = _artifact(tmp_path / "cand.json", [dict(row)])
    assert bench_compare.main([base, cand]) == 0
    out = capsys.readouterr().out
    for key in ("p50_latency_ms", "p99_latency_ms", "p50_ttft_ms",
                "p99_ttft_ms"):
        assert key in out


def test_bench_compare_usage_errors(bench_compare, tmp_path):
    assert bench_compare.main([]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    good = _artifact(tmp_path / "good.json", [_BASE_ROW])
    assert bench_compare.main([str(bad), good]) == 2


def test_serve_suite_tiny(bench, capsys):
    """PR 11 acceptance shape: ``bench.py --serve --tiny`` sustains
    Poisson traffic on 2 in-process replicas with batch occupancy > 1,
    compiles NOTHING after the per-bucket warmup, and reports the
    serving headline as one JSON line."""
    result = bench.serve_main(tiny=True)
    assert result["tiny"] is True
    assert result["unit"] == "tokens/sec/chip"
    assert result["value"] > 0
    assert result["replicas"] == 2
    assert result["requests"] == 16
    assert result["avg_batch_occupancy"] > 1.0
    assert result["steady_state_compiles"] == 0
    assert result["warmup_compiles"] > 0
    assert result["p99_latency_ms"] >= result["p50_latency_ms"] > 0
    assert result["p99_ttft_ms"] >= result["p50_ttft_ms"] > 0
    # ISSUE 13 satellite: KV-cache footprint rides the serving headline
    assert result["kv_cache_bytes_per_chip"] > 0
    assert 0.0 <= result["kv_utilization"] <= 1.0
    # ISSUE 15: the interleaved tracing A/B rode along (goal < 1% on
    # decode p50 — asserted loosely here, --tiny numbers are noisy) and
    # the SLO plane scored every request in the run
    assert isinstance(result["tracing_overhead_pct"], float)
    assert result["spans_recorded"] > 0
    assert result["slo_requests_scored"] >= result["requests"]
    assert set(result["slo_burn_rate"]) == \
        {"ttft", "latency", "availability"}
    for obj, budget in result["slo_error_budget_remaining"].items():
        assert 0.0 <= budget <= 1.0, obj
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["value"] == result["value"]


def test_memory_suite_tiny(bench, capsys):
    """ISSUE 13 acceptance shape: ``bench.py --memory --tiny`` runs the
    interleaved tracker-off/tracker-on A/B and reports the overhead
    headline plus the per-subsystem footprint as one JSON line."""
    result = bench.memory_main(tiny=True)
    assert result["tiny"] is True
    assert result["unit"] == "%"
    assert result["goal"] == "< 1%"
    assert result["p50_ms_memory_off"] > 0
    assert result["p50_ms_memory_on"] > 0
    assert result["samples_taken"] >= 1
    per_chip = result["bytes_per_chip"]
    assert per_chip and per_chip.get("grads", 0) > 0
    assert result["peak_hbm_bytes"] > 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["value"] == result["value"]


def test_bench_compare_deflated_busbw_fails(bench_compare, tmp_path,
                                            capsys):
    """ISSUE 16 satellite: comms rows are higher-is-better sub-metrics.
    Throughput flat but the candidate's bus bandwidth halved — the GB/s
    row fails the gate on its own."""
    base_row = dict(_BASE_ROW, busbw_gbs=40.0, comms_utilization=0.8)
    base = _artifact(tmp_path / "base.json", [base_row])
    cand_row = dict(base_row, busbw_gbs=20.0, comms_utilization=0.4)
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "busbw_gbs" in out
    assert "comms_utilization" in out
    assert "higher is better" in out


def test_bench_compare_comms_rows_clean_pass(bench_compare, tmp_path,
                                             capsys):
    row = dict(_BASE_ROW, busbw_gbs=40.0, comms_utilization=0.8)
    base = _artifact(tmp_path / "base.json", [row])
    cand = _artifact(tmp_path / "cand.json", [dict(row)])
    assert bench_compare.main([base, cand]) == 0
    out = capsys.readouterr().out
    assert "[busbw_gbs]" in out
    assert "[comms_utilization]" in out


def test_bench_compare_inflated_kv_bytes_fails(bench_compare, tmp_path,
                                               capsys):
    """ISSUE 17 satellite: kv_cache_bytes_per_chip is a lower-is-better
    bytes row. Throughput flat but the candidate's KV footprint doubled
    (paged engine regressed to dense-sized pools) — the bytes row fails
    the gate on its own."""
    base_row = dict(_BASE_ROW, kv_cache_bytes_per_chip=98304.0)
    base = _artifact(tmp_path / "base.json", [base_row])
    cand_row = dict(base_row, kv_cache_bytes_per_chip=196608.0)
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "kv_cache bytes" in out
    assert "lower is better" in out


def test_bench_compare_collapsed_prefix_hit_rate_fails(bench_compare,
                                                       tmp_path, capsys):
    """ISSUE 17 satellite: prefix_hit_rate is a higher-is-better
    fraction — a collapsed hit rate (prefix cache silently disabled)
    gates like a throughput regression even when latency holds."""
    base_row = dict(_BASE_ROW, prefix_hit_rate=0.8)
    base = _artifact(tmp_path / "base.json", [base_row])
    cand_row = dict(base_row, prefix_hit_rate=0.1)
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "prefix_hit_rate" in out
    assert "higher is better" in out


def test_bench_compare_paged_rows_clean_pass(bench_compare, tmp_path,
                                             capsys):
    row = dict(_BASE_ROW, kv_cache_bytes_per_chip=98304.0,
               prefix_hit_rate=0.8)
    base = _artifact(tmp_path / "base.json", [row])
    cand = _artifact(tmp_path / "cand.json", [dict(row)])
    assert bench_compare.main([base, cand]) == 0
    out = capsys.readouterr().out
    assert "[kv_cache bytes]" in out
    assert "[prefix_hit_rate]" in out


def test_comms_suite_tiny(bench, capsys):
    """ISSUE 16 satellite shape: ``bench.py --comms --tiny`` runs the
    interleaved tracker-off/tracker-on A/B and reports the overhead
    headline as one JSON line with zero steady-state compiles."""
    result = bench.comms_main(tiny=True)
    assert result["tiny"] is True
    assert result["unit"] == "%"
    assert result["goal"] == "< 1%"
    assert result["p50_ms_comms_off"] > 0
    assert result["p50_ms_comms_on"] > 0
    assert result["steady_state_compiles"] == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["value"] == result["value"]


_STAGE_ROW = {
    "update_p50_ms": 3.0,
    "bytes_per_chip": {"params": 4096, "grads": 4096,
                       "optimizer_state": 12288},
    "grad_wire_bytes_per_step": 8192,
    "wire_bytes_per_step": 8192,
    "steady_state_builds": 0,
}


def test_bench_compare_stage_wire_regression_fails(bench_compare,
                                                   tmp_path, capsys):
    """ISSUE 20 satellite: per-stage ZeRO rows gate direction-aware. The
    headline holds but stage 2's gradient wire bytes double back to the
    allreduce cost (the reduce-scatter release silently fell back) — the
    bytes row fails the gate on its own."""
    base_row = dict(_BASE_ROW, stages={
        "stage1": dict(_STAGE_ROW),
        "stage2": dict(_STAGE_ROW, grad_wire_bytes_per_step=4096,
                       bytes_per_chip={"params": 4096, "grads": 512,
                                       "optimizer_state": 12288}),
    })
    base = _artifact(tmp_path / "base.json", [base_row])
    cand_row = dict(_BASE_ROW, stages={
        "stage1": dict(_STAGE_ROW),
        "stage2": dict(_STAGE_ROW, grad_wire_bytes_per_step=8192,
                       bytes_per_chip={"params": 4096, "grads": 512,
                                       "optimizer_state": 12288}),
    })
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "[stage2 grad_wire_bytes_per_step]" in out
    assert "lower is better" in out


def test_bench_compare_stage_rows_gate_builds_and_hidden(bench_compare,
                                                         tmp_path,
                                                         capsys):
    """Steady-state builds regressing 0 -> N and a collapsed stage-3
    comm-hidden fraction both fail; identical artifacts pass with the
    stage rows compared."""
    base_row = dict(_BASE_ROW, stages={
        "stage3": dict(_STAGE_ROW, steady_state_builds=2,
                       gather_hidden_fraction=0.6)})
    base = _artifact(tmp_path / "base.json", [base_row])
    cand_row = dict(_BASE_ROW, stages={
        "stage3": dict(_STAGE_ROW, steady_state_builds=4,
                       gather_hidden_fraction=0.1)})
    cand = _artifact(tmp_path / "cand.json", [cand_row])
    assert bench_compare.main([base, cand]) == 1
    out = capsys.readouterr().out
    assert "[stage3 steady_state_builds]" in out
    assert "[stage3 gather_hidden_fraction]" in out

    same = _artifact(tmp_path / "same.json", [base_row])
    assert bench_compare.main([base, same]) == 0
    out = capsys.readouterr().out
    assert "[stage3 update_p50_ms]" in out
    assert "[stage3 params bytes]" in out
