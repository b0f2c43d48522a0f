"""95th percentile, over the requests completed in the window, of
(``latency_s`` - ``ttft_s``) / (tokens - 1), from the program's own
``Completion`` fields: the decode step as a caller feels it, prefills of
other requests in between included."""

from benchmark import harness


def read(summary):
    if not summary.get("tpot_s"):
        return None
    return harness.percentile(summary["tpot_s"], 95) * 1e3
