"""Device time per round of latent attention: the ops whose innermost
model scope is ``mla`` (``models/attention.mla_full``: projections,
rotary, the query-chunked attention core and the output projection,
forward, recompute and backward)."""
from harness import subscopes


def read(ctx):
    return subscopes.ms_per_round(ctx, "mla")
