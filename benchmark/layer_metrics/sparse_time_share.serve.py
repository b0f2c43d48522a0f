"""Share of the traced slice's device busy time spent under the model's
``sparse_select`` and ``sparse_attn`` scopes (the block-sparse layers'
block selection and their attention over the selected blocks)."""

from benchmark import scopes


def read(summary):
    return scopes.share(summary, ("sparse_select", "sparse_attn"))
