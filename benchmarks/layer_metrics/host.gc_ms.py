"""`models.gnn.calls.gc_ms` of the window's run manifest: what Python's
garbage collector took from the run's first scan call's start to its last
call's return, every generation, by the program's own always-on count
(`observability/gcwatch.py`, installed at the trainer's boot). Beside
`host.stall_ms` it says whether the run's pauses were the collector's.
Nothing to read from a program whose manifests do not count it."""


def read(ctx):
    if ctx["window"]["kind"] != "scan_calls" or not ctx["runs"]:
        return None
    calls = (ctx["runs"][-1]["models"].get("gnn") or {}).get("calls")
    return None if not calls else calls.get("gc_ms")
