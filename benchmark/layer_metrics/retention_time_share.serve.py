"""Share of the traced slice's device busy time spent under the
power-retention mixer's scopes: ``retention_step`` (the recurrence of a
decode step: the state's update and its read) and ``retention_chunk``
(the prefill's chunked form). An earlier line gives the two apart."""

from benchmark import harness, scopes, scopes_brumby


def read(summary):
    names = ("retention_step", "retention_chunk")
    value = scopes.share(summary, names)
    if value is not None:
        busy = summary["trace"]["busy_s"]
        harness.say("retention_time_share.serve: " + ", ".join(
            f"{n} {100.0 * (scopes_brumby.seconds(summary, n) or 0.0) / busy:.2f}%"
            for n in names) + f" of {busy:.3f} busy seconds")
    return value
