"""Device time a step of the `optimizer` scope (global norm, clip, AdamW) and
of `sample` (the scan's key split, randint and pool gathers)."""

from _scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, lambda scope, backward: scope in ("optimizer", "sample"))
