"""Mean time from the end of one `trainer.gnn.call` to the start of the next
(host plane): the loop's reports, its log line, the thread hand-off. One of
the three parts of `host.gap_ms_per_call`."""

from _scopes import gap_parts


def read(ctx):
    parts = gap_parts(ctx)
    return None if parts is None else parts["turn"]
