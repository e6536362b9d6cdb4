"""Device time of the scan program per optimizer step."""

from _common import scan_calls, steps_in_window


def read(ctx):
    calls = scan_calls(ctx)
    if calls is None:
        return None
    return sum(m[3] for m in calls) / steps_in_window(ctx) / 1e6
