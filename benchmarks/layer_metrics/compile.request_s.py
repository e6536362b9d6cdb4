"""Seconds inside `jax.monitoring` backend-compile events (compiles and
cache loads alike) of a cycle, `train_open` to published. Median over the
window's cycles."""

from _common import median, window_runs

EVENT = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    return median([
        sum(secs for t, event, secs in ctx["compiles"] if event == EVENT and u["t_open"] <= t <= u["t_done"])
        for u, _ in runs
    ])
