"""`models.gnn.calls.stall_ms` of the window's run manifest: the time by
which the run's call periods exceeded 1.5 x their median, by the program's
own always-on count (0 in a run without a host pause)."""


def read(ctx):
    if ctx["window"]["kind"] != "scan_calls" or ctx["device"]["platform"] != "tpu" or not ctx["runs"]:
        return None
    calls = (ctx["runs"][-1]["models"].get("gnn") or {}).get("calls")
    return None if not calls else calls.get("stall_ms")
