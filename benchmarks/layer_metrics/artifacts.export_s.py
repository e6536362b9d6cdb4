"""Run manifest `models.<m>.evaluation.export_seconds`, both models' summed:
what `service._export` timed around each artifact's save (weights, graph,
sketch, the embedding pass and scorer.dfsc, the digest). In the manifest
since PR 25; the remainder of the wall time is no longer read. Median over
the window's cycles."""

from _common import evaluation, median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    out = []
    for _, m in runs:
        exports = [evaluation(m, model).get("export_seconds") for model in ("mlp", "gnn")]
        out.append(None if None in exports else sum(exports))
    return median(out)
