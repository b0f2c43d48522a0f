"""Share of the traced slice's device busy time spent under latent
attention's scopes: ``latent_step`` (a decode step's absorbed form over
the cached latents) and ``latent_prompt`` (a prefill's expanded keys and
values through the flash kernel). An earlier line gives the two apart."""

from benchmark import scopes_xing


def read(summary):
    return scopes_xing.said_share(summary, "latent_time_share.serve",
                                  ("latent_step", "latent_prompt"))
