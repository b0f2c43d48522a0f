"""Share of the traced slice's device busy time spent under the window
layers' ``window_attention`` scope: a prompt's banded blocks and the
ring they leave, a decode step's column write and its read of the ring;
the layers' projections and norms are outside it. An earlier line gives
the share beside the full layers'."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "window_time_share.serve",
                                  ("window_attention",))
