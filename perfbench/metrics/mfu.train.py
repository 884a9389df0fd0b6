"""Whole-step share of the chips' bf16 peak: the model operations of a
round (forward and backward of every client step, from the
configuration's shapes; recompute not counted) times the rounds of the
traced window, over the window and the chips' peak."""
from harness import trace


def read(ctx):
    c, tr = ctx["counts"], ctx["trace"]
    if not tr["window"] or not c.get("rounds"):
        return None
    seconds = trace.window_ns(tr) / 1e9
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * c["flops_per_round"] * c["rounds"] / seconds / peak
