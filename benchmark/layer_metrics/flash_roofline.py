"""The flash-attention kernels' share of their roofline in the traced
slice: the least time the chip could take for every call seen - the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
the shape functions of benchmark/flops.py and benchmark/peaks.json -
over the time the calls took. An earlier line says which bound it is."""

import collections

from benchmark import flops, harness, trace


def read(summary):
    reduced = summary.get("trace")
    if "tokens" not in summary or not reduced:
        return None
    if summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    cfg = summary["config"]
    peak = flops.peaks(summary["device_kind"])
    shape = dict(batch=summary["rows"] // summary["chips"],
                 heads=cfg["num_heads"], seq=summary["seq"],
                 head_dim=cfg["d_model"] // cfg["num_heads"])
    calls_of = collections.Counter(n for n, _, _ in reduced["events"])
    least = took = 0.0
    bounds = {}
    for name, seconds in reduced["per_name_s"].items():
        kind = trace.flash_kind(name)
        if kind is None:
            continue
        calls = calls_of[name]
        by_flops = (flops.flash_flops(kind, causal=cfg["causal"], **shape)
                    / peak["bf16_flops_per_s"])
        by_bytes = flops.flash_bytes(kind, **shape) / peak["hbm_bytes_per_s"]
        least += calls * max(by_flops, by_bytes)
        took += seconds
        bounds[kind] = "compute" if by_flops >= by_bytes else "memory"
    if not took:
        return None
    harness.say(f"flash_roofline: bound by {bounds}; least {least:.6f} s "
                f"of {took:.6f} s")
    return 100.0 * least / took
