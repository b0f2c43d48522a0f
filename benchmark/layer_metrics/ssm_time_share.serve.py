"""Share of the traced slice's device busy time spent under the
state-space layers' ``ssm`` scope - the whole mixer: its two
projections, the convolution, the recurrence (a prompt's chunked scan,
``ssm_scan``, and a decode step's update and read, ``ssm_step``) and the
gated norm - in the decode program and the prefill programs together.
An earlier line gives the three parts apart."""

from benchmark import scopes_granite, scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "ssm_time_share.serve",
                                  scopes_granite.SSM)
