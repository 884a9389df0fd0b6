"""Summed device time of the Δ-SGD kernel pair's events per round (the
Pallas calls on the packed (C, N/128, 128) float32 client buffer)."""
from harness import trace


def read(ctx):
    shape = ctx["counts"].get("pair_shape")
    if not shape or not ctx["counts"].get("rounds"):
        return None
    ns, n = trace.op_time_ns(
        ctx["trace"], lambda name: "tpu_custom_call" in name
        and shape in name)
    if not n:
        return None
    return ns / 1e6 / ctx["counts"]["rounds"]
