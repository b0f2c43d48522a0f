"""Share of the traced slice's device busy time spent under the
``hyper`` scope: the hyper-connections' three maps (projection, sigmoids,
Sinkhorn), the mixing of the streams into each sublayer's input and the
update of the streams after it."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "hyper_time_share.serve",
                                  ("hyper",))
