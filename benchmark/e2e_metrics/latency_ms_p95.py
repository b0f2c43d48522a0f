"""95th percentile, over the requests completed in the window, of the
time from the caller's submit to its reply in hand, by the benchmark's
own clock (the driver thread polls every 10 ms)."""

from benchmark import harness


def read(summary):
    if not summary.get("latency_s"):
        return None
    return harness.percentile(summary["latency_s"], 95) * 1e3
