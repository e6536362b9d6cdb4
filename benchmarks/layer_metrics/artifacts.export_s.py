"""What is left of the run's wall time after dataset build, MLP and GNN
training: saving both artifacts, the embedding pass, scorer.dfsc, the
digests (export has no span of its own yet). Mean over the window's runs."""

from _common import mean, train_seconds, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    out = []
    for _, m in runs:
        stages = m["dataset"]["build_seconds"]
        for model in ("mlp", "gnn"):
            stages += train_seconds(m, model) or 0.0
        out.append(m["wall_s"] - stages)
    return mean(out)
