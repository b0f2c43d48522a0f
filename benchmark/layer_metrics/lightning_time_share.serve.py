"""Share of the traced slice's device busy time spent under the model's
``lightning`` scope (the linear-attention layers' chunked scan in a
prefill, their recurrence in a decode step)."""

from benchmark import scopes


def read(summary):
    return scopes.share(summary, ("lightning",))
