"""Share of the steady decode steps whose ids were already on the device's
side of the readback when the loop came to wait for them
(``engine.decode.wait`` spans with ``ready=1`` over all that carry the
attribute): the host was the later of the two. It rises before
``starved_step_share.serve`` does, because one step is always enqueued
ahead: the margin a longer host pass or a shorter decode program spends."""

from benchmark import harness, stalls


def read(summary):
    if "served_tokens" not in summary:
        return None
    late = stalls.host_late(summary)
    if late is None:
        return None
    stalls.say_once(summary, harness.say)
    harness.say(f"host_late_share.serve: {late[0]} of {late[1]} steady "
                f"decode waits found their result ready")
    return 100.0 * late[0] / late[1]
