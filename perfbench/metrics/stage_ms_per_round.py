"""Host time of the block driver's staging span per round
(``launch/train._run_fused`` calls the harness's stage function inside
its ``stage`` span: batches of the block onto the device)."""


def read(ctx):
    c = ctx["counts"]
    stage = c.get("spans", {}).get("stage")
    if not stage or not c.get("rounds"):
        return None
    return 1e3 * stage[0] / c["rounds"]
