"""What placement padded: `models.gnn.placement.decision.pad_pct` of the
window's run manifest, the node rows placed above the cluster's own hosts as a
share of the hosts (the program places a host count at a rung of its ladder of
row counts, `ops/neighbor_agg_pallas.placed_rows`, and every step pays for the
padding rows). 2.4 for 40,000 hosts at 40,960 rows, 0 on a rung. A count of
rows, not a time: read wherever the run ran. Nothing to read from a program
that does not say what it placed."""


def read(ctx):
    if not ctx["runs"]:
        return None
    decision = ((ctx["runs"][-1]["models"].get("gnn") or {}).get("placement") or {}).get("decision") or {}
    return decision.get("pad_pct")
