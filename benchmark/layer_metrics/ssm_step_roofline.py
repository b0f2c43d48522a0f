"""A decode step's state-space recurrence (everything under the
``ssm_step`` scope: the convolution over the tail and the new row, the
tail's update, the state's update and its read) against the bytes it
has to move: the least seconds - every execution of the decode program
in the traced slice reading and writing every slot's state and
convolution tail in every state-space layer once
(``benchmark/flops_granite.py``) at the chip's memory bandwidth
(``benchmark/peaks.json``) - over the device seconds under the scope in
the decode program. Bound by memory: about three operations a byte. The
bytes are of the work, whatever implements it."""

from benchmark import flops, flops_granite, harness, scopes_xing


def read(summary):
    trace = summary.get("trace")
    took = scopes_xing.seconds(summary, "ssm_step", "decode_scope_s")
    if not trace or not took or "config" not in summary:
        return None
    if summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    steps = sum(1 for name, _, _ in trace["modules"]
                if name.startswith("jit__decode_impl"))
    cfg = summary["config"]
    layers = list(cfg.get("mixers", ())).count("mamba2")
    if not steps or not layers:
        return None
    ssm = cfg["ssm"]
    moved = flops_granite.ssm_step_bytes(
        summary["slots"], layers, ssm["num_heads"], ssm["head_dim"],
        ssm["d_state"], ssm["n_groups"], ssm["d_conv"])
    least = steps * moved / flops.peaks(
        summary["device_kind"])["hbm_bytes_per_s"]
    harness.say(f"ssm_step_roofline: bound by memory; {steps} decode steps "
                f"x {moved} bytes: least {least:.6f} s of {took:.6f} s")
    return 100.0 * least / took
