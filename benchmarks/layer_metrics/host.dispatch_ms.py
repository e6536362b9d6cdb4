"""Mean time from the start of a `trainer.gnn.call` (host plane) to the start
of that call's execution of the scan program (device plane): the key split
and the enqueue. One of the three parts of `host.gap_ms_per_call`."""

from _scopes import gap_parts


def read(ctx):
    parts = gap_parts(ctx)
    return None if parts is None else parts["dispatch"]
