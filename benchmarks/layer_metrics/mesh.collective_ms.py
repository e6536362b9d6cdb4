"""Device milliseconds a step in ops that move data between chips (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all, as XLA's TPU
compiler names them), first device plane: the exchange's exposed time
(_mesh.collective_seconds_per_step)."""

from _mesh import collective_seconds_per_step


def read(ctx):
    seconds = collective_seconds_per_step(ctx)
    return None if seconds is None else seconds * 1e3
