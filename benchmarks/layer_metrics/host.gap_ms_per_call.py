"""Mean idle time of the device between two consecutive executions of the
scan program (dispatch, the D2H pull of the losses, the event loop's turn)."""

from _common import scan_calls


def read(ctx):
    calls = scan_calls(ctx)
    if calls is None:
        return None
    view = ctx["view"]
    following = view.module_runs(ctx["config"]["scan_program"])
    gaps = []
    for call in calls:
        nxt = next((m for m in following if m[2] > call[2]), None)
        if nxt is not None:
            gaps.append(nxt[2] - (call[2] + call[3]))
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
