"""Prompt tokens prefilled per second of ``engine.prefill`` (dispatch and
the wait for the first token), over the steady steps of the serving
loop: the spans' ``prompt_len`` over their durations."""

from benchmark import spans


def read(summary):
    if "served_tokens" not in summary:
        return None
    prefills = [s for s in spans.steady(summary, "engine.prefill") or ()
                if "prompt_len" in s]
    seconds = spans.total(prefills)
    if not seconds:
        return None
    return sum(s["prompt_len"] for s in prefills) / seconds
