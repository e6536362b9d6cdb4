"""The whole step's share of the chip's bf16 peak: matrix-product FLOPs one
forward + backward step needs (flops.step_flops, from shapes) x steps per
second of the traced window / peak."""

import flops
from _common import steps_in_window


def read(ctx):
    steps = steps_in_window(ctx)
    if steps is None or ctx["peaks"] is None:
        return None
    rate = steps / ctx["view"].window_s
    return 100.0 * flops.step_flops(ctx["config"])["total"] * rate / ctx["peaks"]["bf16_flops_per_s"]
