"""A decode step's expert layers (everything under the ``moe`` scope
inside ``jit__decode_impl``: router, masks or grouping, routed and shared
experts) against the bytes their semantics require: the least seconds -
for the decode steps of the traced slice, the experts their active rows
really chose (the program's device-resident counter: decode steps that
hit each expert over decode steps, read round the slice; never from the
shapes), the shared expert and the router, each read once a step
(``benchmark/flops_xing.py``) at the chip's memory bandwidth
(``benchmark/peaks.json``) - over the device seconds under the scope in
the decode program. Bound by memory: four pairs an expert a step."""

import numpy as np

from benchmark import flops, flops_xing, harness, scopes_xing


def read(summary):
    trace = summary.get("trace")
    took = scopes_xing.seconds(summary, "moe", "decode_scope_s")
    counts = summary.get("traced_expert_counts")
    if not trace or not took or not counts or "config" not in summary:
        return None
    if summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    steps = sum(1 for name, _, _ in trace["modules"]
                if name.startswith("jit__decode_impl"))
    counts = np.asarray(counts, np.float64)     # (layers, 3, experts)
    counted = counts[:, 2, 0]                   # decode steps, by layer
    if not steps or not counted.all():
        return None
    # experts hit a step, by layer, as counted round the slice; times the
    # steps the trace itself holds
    hit_a_step = counts[:, 1].sum(axis=1) / counted
    cfg = summary["config"]
    moved = flops_xing.moe_decode_bytes(
        steps, steps * hit_a_step.sum(), len(hit_a_step), cfg["d_model"],
        cfg["expert_d_ff"], cfg["shared_experts"], cfg["num_experts"])
    least = moved / flops.peaks(summary["device_kind"])["hbm_bytes_per_s"]
    harness.say(f"moe_decode_roofline: bound by memory; {steps} decode "
                f"steps, experts hit a step by layer "
                f"{[round(float(h), 2) for h in hit_a_step]} of "
                f"{cfg['experts_count']}: {moved:.0f} bytes, least "
                f"{least:.6f} s of {took:.6f} s")
    return 100.0 * least / took
