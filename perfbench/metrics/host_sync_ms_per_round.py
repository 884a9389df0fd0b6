"""Host time per round of the block driver's one host sync: its
``fetch`` span (``launch/train._run_fused``: the batched
``jax.device_get`` of the block's metric rows, after its ``wait`` for
the device)."""


def read(ctx):
    c = ctx["counts"]
    fetch = c.get("spans", {}).get("fetch")
    if not fetch or not c.get("rounds"):
        return None
    return 1e3 * fetch[0] / c["rounds"]
