"""Cycles of the window whose run built its scan step (trace, lowering,
cache key, compile or cache load) instead of being served one the trainer
kept from an earlier run: a count over the window's cycles. Read from the run
manifest's `models.gnn.kept.served`, or, from a program that keeps no such
record, `models.gnn.calls.traced` (1 where the run traced `multi_step`). A
pool that alternates between two placed row counts builds once a placement
where two programs are kept, every cycle where one is. Nothing to read from a
window whose runs say neither, or in another kind of window."""

from _common import window_runs


def built(manifest: dict) -> bool | None:
    gnn = manifest["models"].get("gnn") or {}
    kept = gnn.get("kept") or {}
    if "served" in kept:
        return not kept["served"]
    traced = (gnn.get("calls") or {}).get("traced")
    return None if traced is None else traced > 0


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    builds = [built(m) for _, m in runs]
    return None if None in builds else sum(builds)
