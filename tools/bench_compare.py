#!/usr/bin/env python
"""Perf regression gate: diff two bench.py artifact trajectories.

The driver snapshots each round's ``python bench.py`` output as
``BENCH_rNN.json`` — ``{"n", "cmd", "rc", "tail"}`` where ``tail`` holds
the run's last stdout lines, a mix of log text and the one-JSON-line-per-
headline protocol (bench.py prints a cumulative ``summary`` line whose
``results`` array re-states every completed headline, so even an rc=124
truncated artifact carries everything that finished). This tool parses
both artifacts, matches headlines by metric name, and fails loudly when
the candidate regresses past the threshold:

    python tools/bench_compare.py BENCH_r05.json BENCH_r06.json
    python tools/bench_compare.py --baseline BENCH_r05.json \
        --candidate /tmp/new.json --threshold-pct 3

Direction comes from the unit: rates (``*/sec*``), ``mfu`` and
``x``-factors are higher-is-better; ``ms``/``us``/``seconds``/``bytes``
are lower-is-better. Rows marked ``"tiny": true`` (smoke-test mode —
bench.py's own docs call the numbers meaningless) are ignored. The
embedded per-headline MFU, step-phase seconds (``step_breakdown``,
PR 6), serving tail latencies (p50/p99 request latency and TTFT,
``ms`` so lower-is-better), and comms bandwidth rows (``busbw_gbs`` /
``comms_utilization``, rates so higher-is-better — a deflated bus
bandwidth gates like a throughput regression) are compared as derived
sub-metrics; phases
under 1 ms are skipped (pure jitter at that scale). Exit status: 0 clean, 1 regression(s),
2 usage/parse error.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

# derived step-phase rows below this baseline value are noise, not signal
MIN_PHASE_SECONDS = 1e-3

LOWER_IS_BETTER_UNITS = ("ms", "us", "seconds", "s", "bytes", "builds")


def parse_artifact(path: str) -> Dict[str, dict]:
    """Metric-name -> headline dict for one artifact. Later lines win
    (bench.py re-emits the cumulative summary after every workload), and
    a summary's ``results`` array is expanded so truncated runs still
    contribute every completed headline."""
    with open(path) as f:
        try:
            doc = json.load(f)
        except ValueError as exc:
            raise ValueError(f"{path}: not JSON: {exc}")
    if isinstance(doc, dict) and isinstance(doc.get("tail"), str):
        lines = doc["tail"].splitlines()
    elif isinstance(doc, dict) and "metric" in doc:
        lines = [json.dumps(doc)]
    elif isinstance(doc, list):
        lines = [json.dumps(o) for o in doc]
    else:
        raise ValueError(f"{path}: no 'tail' field and not a headline "
                         "document")

    rows: Dict[str, dict] = {}

    def take(obj: dict) -> None:
        if not isinstance(obj, dict) or "metric" not in obj:
            return
        for sub in obj.get("results") or ():
            take(sub)
        if obj.get("tiny"):
            return
        if obj["metric"].startswith("summary"):
            return  # its results were expanded above; the row itself
            # just mirrors the flagship and would double-count it
        if isinstance(obj.get("value"), (int, float)):
            rows[obj["metric"]] = obj

    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        take(obj)
    return rows


def higher_is_better(metric: str, unit: Optional[str]) -> bool:
    u = (unit or "").strip().lower()
    if u in LOWER_IS_BETTER_UNITS:
        return False
    if metric.endswith("[mfu]") or "/sec" in u or u in ("x", ""):
        return True
    return True


def derived_rows(rows: Dict[str, dict]) -> Dict[str, Tuple[float, str]]:
    """Flatten headlines to comparable (value, unit) rows, adding the
    per-headline MFU, step-phase, and memory sub-metrics. Memory rows
    ("bytes" unit) are direction-aware via LOWER_IS_BETTER_UNITS: a
    watermark or per-subsystem footprint growth gates like a perf
    regression."""
    flat: Dict[str, Tuple[float, str]] = {}
    for metric, obj in rows.items():
        flat[metric] = (float(obj["value"]), obj.get("unit") or "")
        if isinstance(obj.get("mfu"), (int, float)):
            flat[f"{metric} [mfu]"] = (float(obj["mfu"]), "mfu")
        breakdown = obj.get("step_breakdown")
        if isinstance(breakdown, dict):
            for phase, seconds in breakdown.items():
                if isinstance(seconds, (int, float)):
                    flat[f"{metric} [{phase} seconds]"] = (
                        float(seconds), "seconds")
        per_chip = obj.get("bytes_per_chip")
        if isinstance(per_chip, dict):
            for subsystem, nbytes in per_chip.items():
                if isinstance(nbytes, (int, float)):
                    flat[f"{metric} [{subsystem} bytes]"] = (
                        float(nbytes), "bytes")
        # ZeRO per-stage rows (bench.py --sharded-optimizer): update
        # latency and every bytes-dimensioned row gate lower-is-better;
        # steady-state builds get the "builds" unit so a compile-cache
        # miss after warmup gates too; the stage-3 comm-hidden fraction
        # is a rate (higher-is-better)
        stages = obj.get("stages")
        if isinstance(stages, dict):
            for sname, row in stages.items():
                if not isinstance(row, dict):
                    continue
                if isinstance(row.get("update_p50_ms"), (int, float)):
                    flat[f"{metric} [{sname} update_p50_ms]"] = (
                        float(row["update_p50_ms"]), "ms")
                for key in ("grad_wire_bytes_per_step",
                            "wire_bytes_per_step"):
                    if isinstance(row.get(key), (int, float)):
                        flat[f"{metric} [{sname} {key}]"] = (
                            float(row[key]), "bytes")
                if isinstance(row.get("steady_state_builds"),
                              (int, float)):
                    flat[f"{metric} [{sname} steady_state_builds]"] = (
                        float(row["steady_state_builds"]), "builds")
                if isinstance(row.get("gather_hidden_fraction"),
                              (int, float)):
                    flat[f"{metric} [{sname} gather_hidden_fraction]"] = (
                        float(row["gather_hidden_fraction"]), "fraction")
                sub = row.get("bytes_per_chip")
                if isinstance(sub, dict):
                    for subsystem, nbytes in sub.items():
                        if isinstance(nbytes, (int, float)):
                            flat[f"{metric} [{sname} {subsystem} "
                                 f"bytes]"] = (float(nbytes), "bytes")
        if isinstance(obj.get("peak_hbm_bytes"), (int, float)):
            flat[f"{metric} [peak_hbm bytes]"] = (
                float(obj["peak_hbm_bytes"]), "bytes")
        if isinstance(obj.get("kv_cache_bytes_per_chip"), (int, float)):
            flat[f"{metric} [kv_cache bytes]"] = (
                float(obj["kv_cache_bytes_per_chip"]), "bytes")
        # paged KV cache (bench.py --serve under HOROVOD_SERVE_PAGED /
        # --prefix-heavy): prefix reuse is a rate — "fraction" makes it
        # higher-is-better, so a collapsed hit rate gates like a
        # throughput regression while kv_cache bytes gate growth above
        if isinstance(obj.get("prefix_hit_rate"), (int, float)):
            flat[f"{metric} [prefix_hit_rate]"] = (
                float(obj["prefix_hit_rate"]), "fraction")
        # serving tail latencies (bench.py --serve): "ms" unit makes them
        # lower-is-better, so a p99 blow-up gates even when tokens/s holds
        for key in ("p50_latency_ms", "p99_latency_ms",
                    "p50_ttft_ms", "p99_ttft_ms"):
            if isinstance(obj.get(key), (int, float)):
                flat[f"{metric} [{key}]"] = (float(obj[key]), "ms")
        # comms plane (bench.py comms_rows, docs/comms.md): bus bandwidth
        # and roofline utilization are rates — higher-is-better by
        # default, so a deflated busbw gates like a throughput regression
        if isinstance(obj.get("busbw_gbs"), (int, float)):
            flat[f"{metric} [busbw_gbs]"] = (
                float(obj["busbw_gbs"]), "GB/s")
        if isinstance(obj.get("comms_utilization"), (int, float)):
            flat[f"{metric} [comms_utilization]"] = (
                float(obj["comms_utilization"]), "fraction")
        # goodput ledger (bench.py goodput_rows, docs/goodput.md): the
        # productive fraction of wall-clock is higher-is-better — a
        # candidate that burns its steps on stalls or replays gates like
        # a throughput regression even when step latency holds
        if isinstance(obj.get("goodput_fraction"), (int, float)):
            flat[f"{metric} [goodput_fraction]"] = (
                float(obj["goodput_fraction"]), "fraction")
    return flat


def compare(baseline: Dict[str, Tuple[float, str]],
            candidate: Dict[str, Tuple[float, str]],
            threshold_pct: float) -> Tuple[List[str], List[str]]:
    """Returns (report lines, regression lines)."""
    report: List[str] = []
    regressions: List[str] = []
    common = sorted(set(baseline) & set(candidate))
    for metric in common:
        base, unit = baseline[metric]
        cand, _ = candidate[metric]
        if unit == "seconds" and base < MIN_PHASE_SECONDS:
            continue
        if base == 0:
            continue
        delta_pct = (cand - base) / abs(base) * 100.0
        hib = higher_is_better(metric, unit)
        worse_pct = -delta_pct if hib else delta_pct
        verdict = "REGRESSION" if worse_pct > threshold_pct else "ok"
        line = (f"{verdict:>10}  {metric}: {base:g} -> {cand:g} {unit} "
                f"({delta_pct:+.2f}%, {'higher' if hib else 'lower'} is "
                f"better, threshold {threshold_pct:g}%)")
        report.append(line)
        if verdict == "REGRESSION":
            regressions.append(line)
    only_base = sorted(set(baseline) - set(candidate))
    only_cand = sorted(set(candidate) - set(baseline))
    for metric in only_base:
        report.append(f"{'missing':>10}  {metric}: in baseline only "
                      "(not compared)")
    for metric in only_cand:
        report.append(f"{'new':>10}  {metric}: in candidate only "
                      "(not compared)")
    return report, regressions


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="Fail when a bench.py artifact regresses vs a "
                    "baseline artifact.")
    parser.add_argument("files", nargs="*",
                        help="BASELINE CANDIDATE (positional form)")
    parser.add_argument("--baseline", help="baseline BENCH_*.json")
    parser.add_argument("--candidate", help="candidate BENCH_*.json")
    parser.add_argument("--threshold-pct", type=float, default=5.0,
                        help="worsening beyond this %% fails the gate "
                             "(default 5; rates/MFU measured round-to-"
                             "round jitter is well under that)")
    args = parser.parse_args(argv)

    baseline_path = args.baseline
    candidate_path = args.candidate
    positional = list(args.files)
    if baseline_path is None and positional:
        baseline_path = positional.pop(0)
    if candidate_path is None and positional:
        candidate_path = positional.pop(0)
    if positional or baseline_path is None or candidate_path is None:
        parser.print_usage(sys.stderr)
        sys.stderr.write("bench_compare: need exactly a baseline and a "
                         "candidate artifact\n")
        return 2

    try:
        base_rows = derived_rows(parse_artifact(baseline_path))
        cand_rows = derived_rows(parse_artifact(candidate_path))
    except (OSError, ValueError) as exc:
        sys.stderr.write(f"bench_compare: {exc}\n")
        return 2
    if not base_rows:
        sys.stderr.write(f"bench_compare: no headline rows in "
                         f"{baseline_path!r}\n")
        return 2
    if not cand_rows:
        sys.stderr.write(f"bench_compare: no headline rows in "
                         f"{candidate_path!r}\n")
        return 2

    report, regressions = compare(base_rows, cand_rows,
                                  args.threshold_pct)
    compared = sum(1 for line in report
                   if line.lstrip().startswith(("ok", "REGRESSION")))
    print(f"bench_compare: {baseline_path} -> {candidate_path} "
          f"({compared} compared metrics)")
    for line in report:
        print(line)
    if regressions:
        print(f"bench_compare: {len(regressions)} regression(s) past "
              f"{args.threshold_pct:g}%", file=sys.stderr)
        return 1
    if not compared:
        sys.stderr.write("bench_compare: artifacts share no comparable "
                         "metrics\n")
        return 2
    print("bench_compare: no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
