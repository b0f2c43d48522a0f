"""The decode step's grouped-query attention kernel
(``grouped_decode_attention``, ``horovod_tpu/ops/pallas/
grouped_decode_attention.py``) against the bytes it has to move: the
least seconds - for every execution of the kernel in the traced slice
the keys and values of the positions its rows attend, read once
(``benchmark/flops_kexaone.py``; positions attended a decode step from
the engine's counter round the slice, never from the shapes) at the
chip's memory bandwidth (``benchmark/peaks.json``) - over the seconds
the kernel's events took. Bound by memory: 16 operations a byte at 8
queries a key/value head. Nothing to read where the decode program
holds no such kernel."""

import re

from benchmark import flops, flops_kexaone, harness

_CALL = re.compile(r"^%grouped_decode_attention[.\d]* = ")


def read(summary):
    trace = summary.get("trace")
    by_kind = summary.get("traced_positions_by_kind")
    if not trace or not by_kind or "config" not in summary:
        return None
    if summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    calls = [ns for name, _, ns in trace["events"] if _CALL.match(name)]
    cfg = summary["config"]
    layers = list(cfg.get("mixers", ())).count("full")
    if not calls or not layers or not by_kind.get("kv"):
        return None
    # one call a full layer a step; the counter has the layers together
    a_call = by_kind["kv"] / layers
    moved = len(calls) * flops_kexaone.grouped_decode_bytes(
        a_call, cfg["num_kv_heads"], cfg["head_dim"])
    least = moved / flops.peaks(summary["device_kind"])["hbm_bytes_per_s"]
    took = sum(calls) * 1e-9
    harness.say(f"grouped_decode_roofline: bound by memory; {len(calls)} "
                f"calls x {a_call:.0f} positions attended a step: "
                f"{moved:.0f} bytes, least {least:.6f} s of {took:.6f} s")
    return 100.0 * least / took
