"""Time per step in which the first device did nothing but exchange
gradients: its synchronous collectives, and the starts and dones of its
asynchronous collective fusions, where the core waits for what the
fusions between them did not cover (``benchmark/exchange.py``). On a
program without asynchronous fusions it reads what
``allreduce_exposed_ms_step.dp4`` reads."""

from benchmark import exchange


def read(summary):
    reduced = summary.get("trace")
    if not reduced or summary.get("chips", 1) < 2 or "trace_steps" not in summary:
        return None
    return (exchange.exposed_seconds(reduced["events"]) * 1e3
            / summary["trace_steps"])
