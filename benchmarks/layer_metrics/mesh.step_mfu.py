"""The whole step's share of the bf16 peak of ALL the chips the run had:
matrix-product FLOPs one forward + backward step needs (flops.step_flops, from
shapes: the whole graph's, whichever way it is split) x steps per second of
the traced window / (chips x one chip's peak)."""

import flops
from _common import steps_in_window
from _mesh import chips


def read(ctx):
    steps = steps_in_window(ctx)
    if steps is None or ctx["peaks"] is None:
        return None
    rate = steps / ctx["view"].window_s
    return 100.0 * flops.step_flops(ctx["config"])["total"] * rate / (chips(ctx) * ctx["peaks"]["bf16_flops_per_s"])
