"""95th percentile of ``Completion.ttft_s`` (the program's own span from
submit to first generated token: admission wait plus prefill) over the
requests completed in the window."""

from benchmark import harness


def read(summary):
    if not summary.get("ttft_s"):
        return None
    return harness.percentile(summary["ttft_s"], 95) * 1e3
