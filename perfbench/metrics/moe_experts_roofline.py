"""Roofline share of the held experts' grouped product: the operations
it must do, counted from the token-expert pairs the program's counter
saw routed to the held experts (gate, up and down projections, forward
and backward: three forwards' worth; the recompute of the
rematerialised forward is not counted), at the chip's bf16 peak, over
the device time of the ``moe_experts`` scope. The product's arithmetic
intensity (hundreds of operations a byte at the experts' widths) puts
it under the compute roof."""
from harness import subscopes


def read(ctx):
    c = ctx["counts"]
    flops = c.get("expert_flops_per_round")
    ms = subscopes.ms_per_round(ctx, "moe_experts")
    if not flops or not ms:
        return None
    need = flops / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (ms / 1e3)
