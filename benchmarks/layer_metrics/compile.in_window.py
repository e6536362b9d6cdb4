"""Backend compiles (jax.monitoring) between the window's edges: 0 in a run
that warmed up every shape."""

EVENT = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    w = ctx["window"]
    return sum(1 for t, event, _ in ctx["compiles"]
               if event == EVENT and w["window_start"] <= t <= w["window_stop"])
