"""Mean time from the end of an execution of the scan program (device plane)
to the end of its `trainer.gnn.call` (host plane): what the D2H pulls take
once the device is done. One of the three parts of `host.gap_ms_per_call`."""

from _scopes import gap_parts


def read(ctx):
    parts = gap_parts(ctx)
    return None if parts is None else parts["pull_tail"]
