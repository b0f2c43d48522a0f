"""Share of the traced slice's device busy time spent under the engine's
``unmask`` scope: a pass's in-graph sampler over the (slots x block,
vocab) float32 logits - argmax, the softmax probability of it, the choice
of the surest masked positions - and the new feed."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "unmask_time_share.serve",
                                  ("unmask",))
