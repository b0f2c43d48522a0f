"""The part of the collectives' time per step during which no other
operation ran on the first device: the exchange that compute does not
hide."""

from benchmark import trace


def read(summary):
    reduced = summary.get("trace")
    if not reduced or summary.get("chips", 1) < 2 or "trace_steps" not in summary:
        return None
    _, exposed = trace.collective_seconds(reduced["events"])
    return exposed * 1e3 / summary["trace_steps"]
