"""Mean duration of the feed's ``input.wait`` spans (the consumer's
``q.get()`` in ``prefetch_to_device``) over the window's last 200 steps:
time a step was blocked on its input. A traced run draws the slice's
``trace_steps`` batches in one burst after the window; those are left
out."""

from benchmark import spans


def read(summary):
    if "tokens" not in summary:
        return None
    waits = spans.steady(summary, "input.wait")
    if not waits:
        return None
    if summary.get("trace"):
        waits = waits[:-summary["trace_steps"]]
    waits = waits[-200:]
    if not waits:
        return None
    return sum(s["dur"] for s in waits) * 1e3 / len(waits)
