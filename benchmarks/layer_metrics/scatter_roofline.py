"""The gather's VJP against its roofline on one chip: the least time the chip
needs for the bytes the VJP must move (flops.scatter_floor: read the `[N*K, H]`
cotangent and the row numbers once, write `[N, H]` once; memory bounds it)
over the device time a step of the backward ops under the program's `gather`
scope and of the unnamed copies that feed them (`_scopes.gather_vjp_ms`):
whatever implements the VJP, a scatter-add fusion or gathers and a kernel.

It cannot pass 100% while the floor's premise holds: every byte of the floor
is read or written by an op under that scope (the cotangent comes from
`message`'s backward and the sums go to `dense`'s, both through HBM: 512 MB
do not stay in fast memory), at no more than the peak rate the floor divides
by. A VJP fused into the op that makes the cotangent would break the premise,
not the bound: then this floor is the wrong one and the reader is due again.
Nothing to read on a mesh (the floor is one chip's: `mesh.scatter_roofline`)."""

import flops
from _mesh import chips
from _scopes import gather_vjp_ms


def read(ctx):
    if ctx["peaks"] is None or chips(ctx) != 1:
        return None
    ms = gather_vjp_ms(ctx)
    if not ms:
        return None
    return 100.0 * flops.scatter_floor(ctx["config"], ctx["peaks"])["seconds"] * 1e3 / ms
