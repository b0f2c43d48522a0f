"""Device time per execution of the decode program in the traced slice
(the trace's module line names each program by its jitted function)."""


def read(summary):
    trace = summary.get("trace")
    if "served_tokens" not in summary or not trace:
        return None
    runs = [d for name, _, d in trace["modules"]
            if name.startswith("jit__decode_impl")]
    if not runs:
        return None
    return sum(runs) / len(runs) * 1e-6
