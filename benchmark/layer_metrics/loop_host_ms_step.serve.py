"""Mean, over the steady steps of the serving loop, of the step's
duration minus the time it was blocked on the device
(``engine.prefill.wait`` and ``engine.decode.wait`` inside it): host
time per step during which the loop does not wait for the chip. An
earlier line gives the whole split and the ring's rate."""

from benchmark import harness, spans


def read(summary):
    if "served_tokens" not in summary:
        return None
    split = spans.serving_split(summary)
    if split is None:
        return None
    spans.say_serving_split(summary, harness.say)
    return split["loop_host_ms"]
