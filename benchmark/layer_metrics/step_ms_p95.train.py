"""95th percentile of the gaps between step completions in the window
(host clock, one step kept in flight): the step builder's steadiness."""

from benchmark import harness


def read(summary):
    if "step_s" not in summary:
        return None
    return harness.percentile(summary["step_s"], 95) * 1e3
