"""Mean duration of the engine's decode call (``engine.decode`` spans)
over the steady steps. Beside ``decode_step_ms.serve`` (the program's
time on the device) the difference is the rows' preparation, the
dispatch and the readback."""

from benchmark import spans


def read(summary):
    if "served_tokens" not in summary:
        return None
    split = spans.serving_split(summary)
    return split and split["engine.decode"]
