"""Share of the traced training window in which no op ran on the device
(1 - union of op intervals / window), averaged over the chips."""
from harness import trace


def read(ctx):
    share = trace.idle_share(ctx["trace"])
    return None if share is None else 100.0 * share
