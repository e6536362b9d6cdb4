"""The GNN's hot ops: the neighbor gather and the masked mean (XLA), and the gather's VJP as a Pallas kernel."""

from dragonfly2_tpu.ops.neighbor_agg import masked_mean, neighbor_gather  # noqa: F401
