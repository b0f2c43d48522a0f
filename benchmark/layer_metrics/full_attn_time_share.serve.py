"""Share of the traced slice's device busy time spent under the full
layers' ``full_attention`` scope: a prompt's causal attention through
the flash kernel and its rows' write into the cache, a decode step's
column write and its read of the live tiles
(``grouped_decode_attention``); the layers' projections and norms are
outside it."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "full_attn_time_share.serve",
                                  ("full_attention",))
