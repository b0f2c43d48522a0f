"""The decode step's latent-attention kernel
(``latent_decode_attention``, ``horovod_tpu/ops/pallas/
latent_attention.py``) against the bytes it has to move: the least
seconds - for every execution of the kernel in the traced slice the
latents and rotary keys of the positions its rows attend, read once
(``benchmark/flops_xing.py``; positions attended a decode step from the
engine's counter round the slice, never from the shapes) at the chip's
memory bandwidth (``benchmark/peaks.json``) - over the seconds the
kernel's events took. Bound by memory. Nothing to read where the decode
program holds no such kernel."""

import re

from benchmark import flops, flops_xing, harness

_CALL = re.compile(r"^%latent_decode_attention[.\d]* = ")


def read(summary):
    trace = summary.get("trace")
    a_step = summary.get("traced_positions_a_step")
    if not trace or not a_step or "config" not in summary:
        return None
    if summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    calls = [ns for name, _, ns in trace["events"] if _CALL.match(name)]
    if not calls:
        return None
    cfg = summary["config"]
    # one call a layer a step, each over the step's positions
    moved = len(calls) * flops_xing.latent_decode_bytes(
        a_step, cfg["kv_rank"], cfg["rope_dim"])
    least = moved / flops.peaks(summary["device_kind"])["hbm_bytes_per_s"]
    took = sum(calls) * 1e-9
    harness.say(f"latent_decode_roofline: bound by memory; {len(calls)} "
                f"calls x {a_step:.0f} positions attended a step: "
                f"{moved:.0f} bytes, least {least:.6f} s of {took:.6f} s")
    return 100.0 * least / took
