"""The gather's VJP (scatter-add) against its roofline: the least time the
chip needs for the bytes it must move (flops.scatter_floor; memory bounds it)
over the device time of the ops of that shape class."""

import flops
from _common import steps_in_window


def read(ctx):
    steps = steps_in_window(ctx)
    if steps is None or ctx["peaks"] is None:
        return None
    config = ctx["config"]
    seconds = ctx["view"].op_seconds(lambda name, shapes: flops.is_scatter(config, shapes))
    if seconds <= 0:
        return None
    return 100.0 * flops.scatter_floor(config, ctx["peaks"])["seconds"] * steps / seconds
