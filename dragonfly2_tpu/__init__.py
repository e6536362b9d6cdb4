"""dragonfly2_tpu — a TPU-native P2P distribution + ML-scheduling framework.

Capability surface modeled on Dragonfly2 (reference: /root/reference, v2.0.9):
manager / scheduler / seed-peer / peer services, piece-granular P2P downloads
with back-to-source fallback, telemetry capture, and the ML scheduling plane
the reference left as TODO (reference scheduler/scheduling/evaluator/evaluator.go:48)
— built here as JAX/Flax models trained on TPU meshes and served through a
batched scorer in the scheduler's parent-selection hot loop.

Layout:
  utils/      ids, digests, DAG, bitsets, FSM, GC registry, rate limiting
  config/     typed configs with defaults + validation
  rpc/        msgpack-framed asyncio RPC (unary + bidi streams)
  telemetry/  columnar download/topology records (zero-copy into JAX)
  scheduler/  resource model, scheduling algorithm, evaluators, service
  daemon/     peer engine: piece storage, conductor, upload server, source clients
  manager/    model registry, cluster config hub, searcher
  trainer/    JAX training loops (MLP bandwidth predictor, GraphSAGE GNN)
  models/     Flax model definitions + scorer export
  ops/        Pallas/XLA kernels for the GNN hot ops
  parallel/   the training run's mesh: node rows over `data`
  cli/        dfget / dfcache / dfstore front-ends
"""

__version__ = "0.1.0"
