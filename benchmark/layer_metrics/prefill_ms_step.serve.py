"""Time in the engine's prefill calls (``engine.prefill`` spans: dispatch
and the wait for the first token) per steady step of the serving loop."""

from benchmark import spans


def read(summary):
    if "served_tokens" not in summary:
        return None
    split = spans.serving_split(summary)
    return split and split["engine.prefill"]
