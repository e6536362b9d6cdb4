"""Feeder's clock, `train_open` to the reply to `train_close`, median over the
window's uploads: serialising, the RPC trips, the fold into the accumulator
and the commit into the pool."""

from _common import median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    return None if runs is None else median([u["t_closed"] - u["t_open"] for u, _ in runs])
