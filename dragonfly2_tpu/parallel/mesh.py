"""Device mesh + sharding rules for the trainer.

Scaling model ("How to Scale Your Model" recipe): pick a mesh, annotate
shardings on inputs/params, let XLA insert the collectives, profile. Axes:

  data  — batch/data parallelism: training pairs and graph node rows are
          row-sharded here; XLA inserts the gradient psum and the per-layer
          all-gather that the cross-shard neighbor gather needs (this is the
          sequence-parallel-shaped axis of the GNN: nodes play the role of
          sequence positions).
  model — tensor parallelism: Dense kernels column-sharded on the output dim.

Which mesh a training run gets is decided where the run is placed
(`mesh_for_run`): every device on `data`, `model` 1. One device is
`{data: 1, model: 1}`; a four-chip host is `{data: 4, model: 1}`: node rows
and the pair batch are split, and with them the rows XLA's gather pays for one
by one and the cotangent rows a chip sums (ops.neighbor_agg_pallas, a sorted
table a row shard). `make_mesh` builds any other shape for a
caller that names one (the tests of the `model` rules, `mp_train`).

The reference has no ICI story at all (its parallelism is goroutines + gRPC,
SURVEY.md §2.4); this module is where the TPU build replaces it.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(devices: list | None = None, *, model_parallel: int | None = None) -> Mesh:
    """Build a ("data", "model") mesh over the given (or all) devices.

    model_parallel defaults to the largest power of two ≤ min(4, n_devices)
    that divides the device count — tp stays small (it rides ICI), dp takes
    the rest.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_parallel is None:
        model_parallel = 1
        for cand in (2, 4):
            if n % cand == 0 and cand <= n:
                model_parallel = cand
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by model_parallel={model_parallel}")
    grid = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def mesh_for_run(devices: list | None = None) -> tuple[Mesh, dict]:
    """The mesh a GNN training run is placed on when its caller names none,
    and the record of that for the run manifest: (mesh, decision).

    Every device goes on `data`. `model` splits `[N, K, H]` too (GSPMD
    carries the kernels' columns through), but leaves every device all N*K
    rows a fraction as wide, and XLA's TPU gather and scatter-add cost by the
    row, not by its width. Measured at one size: 65,536 x 16 x 512 on four
    v5e chips ran 12.9 steps/s on `{data: 1, model: 4}` against 26.7 with
    rows over `data` (PERF.md, PR 27). The kernels are megabytes and stay
    whole on each device."""
    devices = list(devices if devices is not None else jax.devices())
    rule = "one_device" if len(devices) == 1 else "rows_over_data"
    return make_mesh(devices, model_parallel=1), {"rule": rule, "devices": len(devices)}


def _shardable(dim: int, mesh: Mesh, axis: str) -> bool:
    return dim % mesh.shape[axis] == 0


def param_leaf_sharding(leaf: Any, mesh: Mesh) -> NamedSharding:
    """Tensor-parallel rule for one leaf: 2-D kernels column-shard the output
    dim over "model" when divisible; 1-D biases follow; else replicate.

    Also applied to optimizer-state leaves (adam m/v mirror param shapes) so
    opt state and params never diverge in sharding.
    """
    shape = getattr(leaf, "shape", ())
    if len(shape) == 2 and _shardable(shape[1], mesh, MODEL_AXIS):
        return NamedSharding(mesh, P(None, MODEL_AXIS))
    if len(shape) == 1 and shape[0] > 1 and _shardable(shape[0], mesh, MODEL_AXIS):
        return NamedSharding(mesh, P(MODEL_AXIS))
    return NamedSharding(mesh, P())


def infer_param_sharding(params: Any, mesh: Mesh) -> Any:
    """Apply param_leaf_sharding across a whole pytree."""
    return jax.tree.map(lambda leaf: param_leaf_sharding(leaf, mesh), params)


def graph_shardings(mesh: Mesh) -> tuple[NamedSharding, ...]:
    """Shardings for TopoGraph's four arrays: node rows over "data". (Its
    `by_dst`, one sorted table per row shard where "data" has several, is
    stacked by shard and split the same way: trainer.train_gnn places it.)"""
    row = NamedSharding(mesh, P(DATA_AXIS))
    return (
        row,  # node_feats [N, F]
        row,  # neighbors  [N, K]
        row,  # mask       [N, K]
        row,  # edge_feats [N, K, E]
    )


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def placement_report(tree: Any) -> dict:
    """Where a placed pytree actually lives, read back from the arrays'
    addressable shards: logical bytes, and the bytes each device holds. A
    tree split four ways reads a quarter per device; four copies read the
    whole size on each; unplaced reads one device."""
    leaves = jax.tree.leaves(tree)
    per_device: dict[int, int] = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = per_device.get(shard.device.id, 0) + shard.data.nbytes
    return {
        "leaves": len(leaves),
        "bytes": int(sum(leaf.nbytes for leaf in leaves)),
        "per_device_bytes": [per_device[d] for d in sorted(per_device)],
    }


def pad_to_multiple(n: int, multiple: int) -> int:
    return int(math.ceil(n / multiple) * multiple)
