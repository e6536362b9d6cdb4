"""TPU training loops for the ML scheduling plane.

The reference's trainer/ is an empty shell (config + metrics, no training —
trainer/config/config.go:30-143); the Train RPC contract it was meant to serve
(pkg/rpc/trainer/server/server.go:59) receives download + topology datasets
from the scheduler announcer. Here the trainer is real: JAX/Flax training of
the BandwidthMLP and the TopoScorer GNN, node rows split over a `data` mesh.
"""
