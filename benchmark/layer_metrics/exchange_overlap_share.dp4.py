"""Of the time in which a reduction was open on the first device (a
synchronous collective running, or an asynchronous collective fusion
started and not yet done), the share that was not spent in the
exchange's own events alone (``exchange_exposed_ms_step.dp4``): the part
that other operations covered. 0 where every collective is synchronous
and alone on the core."""

from benchmark import exchange


def read(summary):
    reduced = summary.get("trace")
    if not reduced or summary.get("chips", 1) < 2 or "trace_steps" not in summary:
        return None
    opened = exchange.open_seconds(reduced["events"])
    if not opened:
        return None
    return 100.0 * (1.0 - exchange.exposed_seconds(reduced["events"]) / opened)
