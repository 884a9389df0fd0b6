"""Share of the block driver's stagings that ran one block ahead, while
the block before them was on the device (``launch/train._run_fused``
opens a ``stage_ahead`` span inside ``stage`` for each). Over a window
of B blocks it reads (B - 1) / B: the first block stages up front. A
driver with no such span reads nothing."""


def read(ctx):
    spans = ctx["counts"].get("spans", {})
    stage, ahead = spans.get("stage"), spans.get("stage_ahead")
    if not stage or not ahead or not stage[1]:
        return None
    return 100.0 * ahead[1] / stage[1]
