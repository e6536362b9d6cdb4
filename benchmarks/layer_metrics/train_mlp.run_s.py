"""Run manifest `mlp.evaluation.train_seconds`: the host-dispatched MLP loop,
its per-run re-trace and compile included. Median over the window's runs."""

from _common import median, train_seconds, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    return median([train_seconds(m, "mlp") for _, m in runs])
