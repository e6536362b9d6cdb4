"""How unevenly the chips are loaded: busy time of the busiest device plane
over that of the least busy in the traced window, less one, in percent."""

from _mesh import plane_busy_ns


def read(ctx):
    busy = plane_busy_ns(ctx)
    if busy is None or len(busy) < 2 or min(busy) <= 0:
        return None
    return 100.0 * (max(busy) / min(busy) - 1.0)
