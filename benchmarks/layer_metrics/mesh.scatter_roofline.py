"""The gather's VJP on a row shard against its roofline: a chip's share of
the least time the whole graph's VJP needs for its bytes (flops.scatter_floor
over the chips: a chip reads its shard's cotangent and row numbers and owns
1/chips of the sums) over the device time a step, first plane, of the
backward ops under the program's `gather` scope and of the unnamed copies
that feed them (`_scopes.gather_vjp_ms`): the scatter-add or the per-shard
kernel, and the reduce-scatter of the partial sums, which is the VJP's work
on a mesh and in no floor of its own here (so the share reads lower than the
kernel's alone would).

It cannot pass 100% for `scatter_roofline`'s reason: a chip's ops under the
scope move at least its share of the floor's bytes through HBM at no more
than the peak rate. Nothing to read on one chip."""

import flops
from _mesh import chips
from _scopes import gather_vjp_ms


def read(ctx):
    n = chips(ctx)
    if ctx["peaks"] is None or n == 1:
        return None
    ms = gather_vjp_ms(ctx)
    if not ms:
        return None
    return 100.0 * flops.scatter_floor(ctx["config"], ctx["peaks"])["seconds"] / n * 1e3 / ms
