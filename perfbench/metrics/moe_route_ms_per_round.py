"""Device time per round of MoE routing and dispatch: the ops whose
innermost model scope is ``moe_route`` (``models/moe.apply_moe``: the
float32 router, sigmoid, biased top-k, weights, the sort of the
token-expert pairs by held expert and the gather of their rows)."""
from harness import subscopes


def read(ctx):
    return subscopes.ms_per_round(ctx, "moe_route")
