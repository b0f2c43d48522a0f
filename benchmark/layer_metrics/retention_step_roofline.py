"""The decode step's recurrence (the ``retention_step`` kernel and the few
small operations round it, all under the ``retention_step`` scope) against
the bytes it has to move: the least seconds - every execution of the
decode program in the traced slice reading and writing every slot's state
and normaliser in every layer once (``benchmark/flops_brumby.py``) at the
chip's memory bandwidth (``benchmark/peaks.json``) - over the device
seconds under the scope. Bound by memory: the step does two operations a
byte."""

from benchmark import flops, flops_brumby, harness, scopes_brumby


def read(summary):
    trace = summary.get("trace")
    took = scopes_brumby.seconds(summary, "retention_step")
    if not trace or not took or "config" not in summary:
        return None
    if summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    steps = sum(1 for name, _, _ in trace["modules"]
                if name.startswith("jit__decode_impl"))
    if not steps:
        return None
    cfg = summary["config"]
    moved = flops_brumby.retention_step_bytes(
        summary["slots"], cfg["num_layers"], cfg["num_kv_heads"],
        cfg["head_dim"])
    least = steps * moved / flops.peaks(
        summary["device_kind"])["hbm_bytes_per_s"]
    harness.say(f"retention_step_roofline: bound by memory; {steps} decode "
                f"steps x {moved} bytes: least {least:.6f} s of "
                f"{took:.6f} s")
    return 100.0 * least / took
