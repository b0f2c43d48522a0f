"""The prefill's read of the state between chunks (the ``retention_read``
kernel) against what the chip allows: the least seconds - for every call
in the traced slice the larger of its operations over the peak FLOP/s and
its bytes over the peak bytes/s (``benchmark/flops_brumby.py``,
``benchmark/peaks.json``), the call's states and queries read from the
shape of its result - over the seconds the calls took. An earlier line
says which bound it is."""

import re

from benchmark import flops, flops_brumby, harness

# %retention_read.3 = (f32[8,1280,128]{...}, f32[...]) custom-call(...
_CALL = re.compile(r"^%retention_read[.\d]* = \(?f32\[(\d+),(\d+),(\d+)\]")


def read(summary):
    trace = summary.get("trace")
    if not trace or summary.get("platform") == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    least = took = 0.0
    peak = bound = None
    for name, _, nanoseconds in trace["events"]:
        call = _CALL.match(name)
        if not call:
            continue
        states, queries, head_dim = map(int, call.groups())
        peak = peak or flops.peaks(summary["device_kind"])
        by_flops = flops_brumby.retention_read_flops(
            states, queries, head_dim) / peak["bf16_flops_per_s"]
        by_bytes = flops_brumby.retention_read_bytes(
            states, queries, head_dim) / peak["hbm_bytes_per_s"]
        bound = "compute" if by_flops >= by_bytes else "memory"
        least += max(by_flops, by_bytes)
        took += nanoseconds * 1e-9
    if not took:
        return None
    harness.say(f"retention_read_roofline: bound by {bound}; least "
                f"{least:.6f} s of {took:.6f} s")
    return 100.0 * least / took
