"""Share of the traced slice's device busy time spent under
``ssm_scan``: the chunked recurrence of the state-space layers' prompts
alone (the scores and decays inside a chunk, the state between chunks),
without their projections, convolution and gated norm. What a kernel for
the prompt's scan could take from."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "ssm_scan_time_share.serve",
                                  ("ssm_scan",))
