"""Time per step during which a collective (all-reduce and its kin) ran
on the first device, from the traced slice."""

from benchmark import trace


def read(summary):
    reduced = summary.get("trace")
    if not reduced or summary.get("chips", 1) < 2 or "trace_steps" not in summary:
        return None
    total, _ = trace.collective_seconds(reduced["events"])
    return total * 1e3 / summary["trace_steps"]
