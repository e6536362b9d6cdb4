"""Seconds inside `jax.monitoring` backend-compile events (compiles and
cache loads alike) per cycle of the window."""

from _common import window_runs

EVENT = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    w = ctx["window"]
    total = sum(secs for t, event, secs in ctx["compiles"]
                if event == EVENT and w["window_start"] <= t <= w["window_stop"])
    return total / len(runs)
