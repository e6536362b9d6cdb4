"""The gather's VJP on a row shard against its roofline: a chip's share of
the least time the whole graph's scatter-add needs for its bytes
(flops.scatter_floor over the chips: a chip reads its shard's cotangent and
row numbers and owns 1/chips of the sums) over the device time, first plane,
of the ops of the shard's shape class (_mesh.is_shard_scatter)."""

import flops
from _common import steps_in_window
from _mesh import chips, is_shard_scatter


def read(ctx):
    steps = steps_in_window(ctx)
    if steps is None or ctx["peaks"] is None:
        return None
    config, n = ctx["config"], chips(ctx)
    seconds = ctx["view"].op_seconds(lambda name, shapes: is_shard_scatter(config, n, shapes))
    if seconds <= 0:
        return None
    return 100.0 * flops.scatter_floor(config, ctx["peaks"])["seconds"] / n * steps / seconds
