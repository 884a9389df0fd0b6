"""Device time per round of the clients' gradient evaluation: the ops
whose innermost layer scope is ``client_grad`` (the vmapped model
forward and backward of every local step in the round body)."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "client_grad")
