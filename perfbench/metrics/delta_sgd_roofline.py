"""Roofline share of the Δ-SGD kernel pair (``batched_norms`` and
``batched_apply``): the least time the bytes it must move take at the
chip's HBM bandwidth, over the summed device time of its events. Both
kernels do a few operations per float32 element, so bandwidth bounds
them. The pair's events are the Pallas calls (``tpu_custom_call``) on
the packed (C, N/128, 128) float32 client buffer."""
from harness import trace


def pair(ctx):
    shape = ctx["counts"].get("pair_shape")
    if not shape:
        return None

    def pred(name):
        return "tpu_custom_call" in name and shape in name
    return pred


def read(ctx):
    pred = pair(ctx)
    if pred is None:
        return None
    ns, n = trace.op_time_ns(ctx["trace"], pred)
    if not n or ns <= 0:
        return None
    c = ctx["counts"]
    need = c["pair_bytes_per_round"] * c["rounds"] / ctx["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * need / (ns / 1e9)
