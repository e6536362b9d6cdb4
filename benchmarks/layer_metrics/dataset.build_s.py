"""Run manifest `dataset.build_seconds` (freeze + finalize), median over the
window's runs."""

from _common import median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    return None if runs is None else median([m["dataset"]["build_seconds"] for _, m in runs])
