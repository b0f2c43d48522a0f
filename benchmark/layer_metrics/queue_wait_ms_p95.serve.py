"""95th percentile of the program's ``request.queue_wait`` spans (submit
to admission) over the requests admitted in steady steps: the part of
``ttft`` that is not the prefill."""

from benchmark import harness, spans


def read(summary):
    if "served_tokens" not in summary:
        return None
    waits = spans.steady(summary, "request.queue_wait")
    if not waits:
        return None
    return harness.percentile([s["dur"] for s in waits], 95) * 1e3
