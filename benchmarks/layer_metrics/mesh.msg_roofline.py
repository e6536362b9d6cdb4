"""Message build and reduce on a row shard against their roofline: a chip's
share of flops.message_floor (the whole graph's, over the chips) over the
device time, first plane, of every op that reads or writes an `[N/chips, K, H]`
/ `[N*K/chips, H]` tensor and is not the scatter (the forward gather's rows
are among them, as in `msg_roofline`)."""

import flops
from _common import steps_in_window
from _mesh import chips, is_shard_scatter, row_shard


def read(ctx):
    steps = steps_in_window(ctx)
    if steps is None or ctx["peaks"] is None:
        return None
    config, n = ctx["config"], chips(ctx)
    shard = row_shard(config, n)
    seconds = ctx["view"].op_seconds(
        lambda name, shapes: flops.touches_messages(shard, shapes) and not is_shard_scatter(config, n, shapes))
    if seconds <= 0:
        return None
    return 100.0 * flops.message_floor(config, ctx["peaks"])["seconds"] / n * steps / seconds
