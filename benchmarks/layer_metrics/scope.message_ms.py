"""Device time a step of the message path: the `gather` scope's forward ops
and everything under `message` and `reduce`, forward and backward."""

from _scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, lambda scope, backward: scope in ("message", "reduce")
                    or (scope == "gather" and not backward))
