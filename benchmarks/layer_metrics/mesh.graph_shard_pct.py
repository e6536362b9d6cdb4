"""The fullest device's share of the graph: `models.gnn.placement.graph` of
the window's run manifest, the most bytes one device holds over the graph's
logical bytes. 25 when node rows span four devices, 100 when the graph is
whole on each."""


def read(ctx):
    if ctx["device"]["platform"] != "tpu" or not ctx["runs"]:
        return None
    graph = ((ctx["runs"][-1]["models"].get("gnn") or {}).get("placement") or {}).get("graph")
    if not graph or not graph.get("bytes") or not graph.get("per_device_bytes"):
        return None
    return 100.0 * max(graph["per_device_bytes"]) / graph["bytes"]
