"""Run manifest `gc.ms` summed over the window's cycles: what Python's
garbage collector took from each upload's `train_open` to its run's manifest,
every generation, by the program's own always-on count
(`observability/gcwatch.py`, installed at the trainer's boot). Beside
`host.stall_ms.retrain` it says whether a cycle's pause was the collector's:
a generation-2 collection is also a `python.gc` span in a traced cycle.
Nothing to read from a program whose manifests have no `gc`."""

from _common import window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    values = [(m.get("gc") or {}).get("ms") for _, m in runs]
    return None if None in values else sum(values)
