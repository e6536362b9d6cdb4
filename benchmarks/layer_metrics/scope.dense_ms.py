"""Device time a step of the `dense`, `head` and `loss` scopes, forward and
backward: every product that is not per edge, LayerNorm, the pairwise head."""

from _scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, lambda scope, backward: scope in ("dense", "head", "loss"))
