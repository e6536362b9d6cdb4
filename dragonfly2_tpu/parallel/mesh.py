"""The device mesh of a training run, and its sharding rules.

Scaling model ("How to Scale Your Model" recipe): pick a mesh, annotate
shardings on inputs and state, let XLA insert the collectives, profile. One
axis, `data`: training pairs and graph node rows are row-sharded over it; XLA
inserts the gradient psum and the per-layer all-gather that the cross-shard
neighbor gather needs (nodes play the role of sequence positions). The state
(params and optimizer moments, megabytes) and the pair pool are replicated.

Which mesh a training run gets is decided here, once (`mesh_for_run`): every
device on `data`. One device is `{data: 1}`; a four-chip host is `{data: 4}`:
node rows and the pair batch are split, and with them the rows XLA's gather
pays for one by one and the cotangent rows a chip sums
(ops.neighbor_agg_pallas, a sorted table a row shard).

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


def mesh_for_run(devices: list | None = None) -> tuple[Mesh, dict]:
    """The mesh a GNN training run is placed on, and the record of that for
    the run manifest: (mesh, decision).

    Every device goes on `data`. Splitting the Dense kernels' columns over a
    second axis instead leaves every device all N*K rows of `[N, K, H]` a
    fraction as wide, and XLA's TPU gather and scatter-add cost by the row,
    not by its width. Measured at one size: 65,536 x 16 x 512 on four v5e
    chips ran 12.9 steps/s with the kernels over four devices against 26.7
    with rows over `data` (PERF.md, PR 27). The kernels are megabytes and
    stay whole on each device."""
    devices = list(devices if devices is not None else jax.devices())
    rule = "one_device" if len(devices) == 1 else "rows_over_data"
    return Mesh(np.asarray(devices), (DATA_AXIS,)), {"rule": rule, "devices": len(devices)}


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
