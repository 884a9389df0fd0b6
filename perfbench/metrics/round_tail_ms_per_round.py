"""Device time per round of the round tail: the ops whose innermost
layer scope is ``round_tail`` (everything after the local-step scan:
guards, aggregation, server update, metrics; the flat layer's passes
inside it count as ``flat``)."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "round_tail")
