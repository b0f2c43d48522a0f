"""Share of the device's busy time in the traced slice spent in Pallas
(Mosaic) kernels; in the training cells these are the flash-attention
forward and its two backward kernels, three per layer per step."""

from benchmark import trace


def read(summary):
    reduced = summary.get("trace")
    if "tokens" not in summary or not reduced or not reduced["busy_s"]:
        return None
    kernel_s = sum(s for name, s in reduced["per_name_s"].items()
                   if trace.is_mosaic(name))
    if not kernel_s:
        return None
    return 100.0 * kernel_s / reduced["busy_s"]
