"""From the start of `train_gnn.train_async` to its first completed scan
call: init, placement, re-trace, compile or cache load, and the first call
itself. Manifest `gnn.evaluation.train_seconds` less the time between the
run's first and last step report. Median over the window's runs."""

from _common import median, train_seconds, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    reports: dict[int, list[float]] = {}
    for t, run, model, *_ in ctx["window"]["step_events"]:
        if model == "gnn":
            reports.setdefault(run, []).append(t)
    spans = [reports[k] for k in sorted(reports)][-len(runs):]
    if len(spans) != len(runs):
        return None
    return median([
        (train_seconds(m, "gnn") or 0.0) - (ts[-1] - ts[0])
        for (_, m), ts in zip(runs, spans)
    ])
