"""Share of the traced slice (a few seconds of the closed loop after the window) in
which no operation ran on the device: 1 - union of operation intervals
over the slice, averaged over the chips. A running collective counts as
busy."""


def read(summary):
    trace = summary.get("trace")
    if "served_tokens" not in summary or not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
