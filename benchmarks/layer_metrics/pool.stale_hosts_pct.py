"""Hosts of the pool a run trained on that the pool's newest upload no
longer named, as a share of the pool's hosts: the run manifest's
`pool.hosts_stale` over `pool.hosts`, median over the window's cycles. A pool
that holds two uploads of a cluster whose hosts churn keeps the hosts the
scheduler's host GC has dropped since the older one; every step and the export
pay for them. Nothing to read from a program whose manifests have no `pool`,
or in another kind of window."""

from _common import median, window_runs


def read(ctx):
    runs = window_runs(ctx)
    if runs is None:
        return None
    pools = [m.get("pool") for _, m in runs]
    if None in pools:
        return None
    return median([100.0 * p["hosts_stale"] / p["hosts"] if p["hosts"] else None for p in pools])
