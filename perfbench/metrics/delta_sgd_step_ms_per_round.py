"""Device time per round of the flat Δ-SGD step: the ops whose innermost
layer scope is ``delta_sgd`` (the kernel pair and the jnp around it, the
``where(valid, G, 0)`` select and the η/θ rule). It holds what
``delta_sgd_ms_per_round`` finds by the pair's operand shape."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "delta_sgd")
