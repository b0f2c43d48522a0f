"""Share of the traced slice's device busy time spent under the expert
layers' ``moe`` scope (router, grouping, routed and shared experts), in
the decode program and the prefill programs together."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "moe_time_share.serve", ("moe",))
