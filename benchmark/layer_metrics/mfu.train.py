"""Model FLOP/s utilisation: the operations the forward and backward
passes require per token (benchmark/flops.py) times tokens per second
per chip, over the chip's published bf16 peak (benchmark/peaks.json)."""

from benchmark import flops


def read(summary):
    if "flops_per_token" not in summary or summary["platform"] == "cpu":
        return None   # a CPU (rehearsals) has no peak: not measured
    rate = summary["tokens"] / summary["window_s"] / summary["chips"]
    peak = flops.peaks(summary["device_kind"])["bf16_flops_per_s"]
    return 100.0 * summary["flops_per_token"] * rate / peak
