"""Run manifest `dataset.build_seconds` (freeze + finalize), mean over the
window's runs."""

from _common import mean, window_runs


def read(ctx):
    runs = window_runs(ctx)
    return None if runs is None else mean([m["dataset"]["build_seconds"] for _, m in runs])
