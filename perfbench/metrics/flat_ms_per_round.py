"""Device time per round of the flat layer: the ops whose innermost
layer scope is ``flat`` (``core/flat`` pack and unpack of the (C, N)
and (N,) buffers, and the round-start broadcast to the client axis)."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "flat")
