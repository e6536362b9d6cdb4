"""`models.gnn.calls.stall_ms` summed over the window's cycles: the time by
which scan call periods exceeded 1.5 x their run's median, by the program's
own always-on count. A cycle that the machine paused inside `train_gnn` has
its name here, where `retrain_s`, a median, does not see it (a pause
elsewhere in a cycle shows in `detail.cycles_s` against the stages)."""

from _common import window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None or ctx["device"]["platform"] != "tpu":
        return None
    stalls = [((m["models"].get("gnn") or {}).get("calls") or {}).get("stall_ms") for _, m in runs]
    return None if None in stalls else sum(stalls)
