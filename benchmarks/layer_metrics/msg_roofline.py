"""Message build and reduce against their roofline: flops.message_floor over
the device time of every op that reads or writes an [N, K, H] / [N*K, H]
tensor and is not the scatter."""

import flops
from _common import steps_in_window


def read(ctx):
    steps = steps_in_window(ctx)
    if steps is None or ctx["peaks"] is None:
        return None
    config = ctx["config"]
    seconds = ctx["view"].op_seconds(
        lambda name, shapes: flops.touches_messages(config, shapes) and not flops.is_scatter(config, shapes))
    if seconds <= 0:
        return None
    return 100.0 * flops.message_floor(config, ctx["peaks"])["seconds"] * steps / seconds
