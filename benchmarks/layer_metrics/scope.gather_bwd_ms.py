"""Device time a step of the backward ops under the program's `gather`
scope (the VJP of `neighbor_gather`), whatever implements them."""

from _scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, lambda scope, backward: scope == "gather" and backward)
