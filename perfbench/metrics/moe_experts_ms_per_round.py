"""Device time per round of the held experts: the ops whose innermost
model scope is ``moe_experts`` (the grouped matrix products of
``kernels/grouped_matmul``, forward, recompute and backward)."""
from harness import subscopes


def read(ctx):
    return subscopes.ms_per_round(ctx, "moe_experts")
