"""Neighbor gather + masked mean: the GraphSAGE aggregation hot op.

Graph layout is TPU-first (SURVEY.md §7): instead of the reference's Redis
FIFO probe lists per (src, dst) edge (scheduler/networktopology/probes.go),
the topology graph is a *dense padded neighbor table* — `neighbors[N, K]`
int32 with a boolean mask — so aggregation is static-shaped gather + masked
mean + matmul, all of which XLA tiles onto the MXU with no dynamic shapes.

Two ops, both plain XLA in the forward: `neighbor_gather` ([N, H] states ->
[N, K, H], one row a neighbor slot) and `masked_mean` ([N, K, H] -> [N, H]).
The one kernel, ops.neighbor_agg_pallas's `sum_by_destination`, is the
gather's VJP on TPU chips (below).

In the training step the caller names these ops for the device trace
(models/graphsage.py: `neighbor_gather` under the `gather` scope, with its
VJP; `masked_mean` under `reduce`). A kernel that replaces one of them keeps
its forward and its backward, their collectives included, under the same
scope.

The gather's VJP sums N*K cotangent rows into N. The one XLA derives from
`jnp.take` is a scatter-add by unsorted row numbers, which the TPU runs row
by row (17-22 ns a row: over half of a training step, PERF.md). The table is
fixed for a whole run, so a run placed on TPU chips sorts its slots by
destination once, on the host, and `neighbor_gather`, a `custom_vjp` there,
sums in the cheap direction: gather the cotangent rows into destination
order, and add up contiguous runs in a kernel (ops.neighbor_agg_pallas:
`edges_by_destination`, `sum_by_destination`). The table counts its slots
K-major (over `neighbors.T`): the TPU compiler keeps the message tensor, and
so the cotangent, with K major-most, and blocks of that order are bitcasts of
it where blocks of the row-major order cost a copy of the whole cotangent a
layer. On a mesh whose `data` axis splits the node rows, a chip has a table
over its own row shard's slots (`EdgesByShard`): under `shard_map` it
all-gathers the states and takes its rows' slots forward, and backward sums
its own cotangent rows into all N rows with the kernel; the chips' [N, H]
sums are reduce-scattered (`sum_by_shard`). Which of the three a placed run
takes is decided in one place, `neighbor_agg_pallas.gather_vjp_tables`, from
the placed shapes and the mesh. Everywhere else (no table: CPU, float32 or
odd widths, inference, tools) it is `jnp.take` and its derived VJP, to the
letter.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dragonfly2_tpu.parallel.mesh import DATA_AXIS

if TYPE_CHECKING:
    from dragonfly2_tpu.ops.neighbor_agg_pallas import EdgesByDst, EdgesByShard


def neighbor_gather(
    h: jnp.ndarray, neighbors: jnp.ndarray, by_dst: EdgesByDst | EdgesByShard | None = None
) -> jnp.ndarray:
    """Gather node states for each padded neighbor slot.

    h: [N, H] node states; neighbors: [N, K] int32 indices (padding may point
    anywhere valid, typically 0 — the mask zeroes its contribution).
    Returns [N, K, H]. With `by_dst` (the same table, sorted, as placement
    builds it for the kernel: one table, or on a `data` mesh one a row shard)
    the VJP adds up runs of sorted rows; its value is that of `jnp.take`'s, up
    to the order of summation.
    """
    if by_dst is None:
        return jnp.take(h, neighbors, axis=0)
    from dragonfly2_tpu.ops import neighbor_agg_pallas as pk

    # a table is placement's decision (pk.gather_vjp_tables): states it was not built for are a fault
    if h.shape[0] != neighbors.shape[0]:
        raise ValueError(f"{h.shape[0]} states for a table of {neighbors.shape[0]} rows")
    if not pk.kernel_sums(h.shape[1], h.dtype):
        raise ValueError(f"{h.dtype.name}[{h.shape[1]}] states for a table the kernel sums: bfloat16, whole lanes")
    if isinstance(by_dst, pk.EdgesByShard):
        return _gather_by_shard(h, neighbors, by_dst)
    return _gather_sorted_vjp(h, neighbors, by_dst)


@jax.custom_vjp
def _gather_sorted_vjp(h, neighbors, by_dst):
    return jnp.take(h, neighbors, axis=0)


def _gather_fwd(h, neighbors, by_dst):
    return jnp.take(h, neighbors, axis=0), by_dst


def _gather_bwd(by_dst, g):
    from dragonfly2_tpu.ops.neighbor_agg_pallas import sum_by_destination

    return sum_by_destination(by_dst, g), None, None


_gather_sorted_vjp.defvjp(_gather_fwd, _gather_bwd)


def _take_by_shard(h, neighbors, mesh):
    """`jnp.take`'s values on a `data` mesh, as the partitioner's program gives
    them: every chip all-gathers the states and takes its own rows' slots."""

    def take(states, nbr):
        return jnp.take(jax.lax.all_gather(states, DATA_AXIS, tiled=True), nbr, axis=0)

    rows = P(DATA_AXIS)
    return jax.shard_map(take, mesh=mesh, in_specs=(rows, rows), out_specs=rows)(h, neighbors)


@jax.custom_vjp
def _gather_by_shard(h, neighbors, by_shard):
    return _take_by_shard(h, neighbors, by_shard.mesh)


def _gather_by_shard_fwd(h, neighbors, by_shard):
    return _take_by_shard(h, neighbors, by_shard.mesh), by_shard


def _gather_by_shard_bwd(by_shard, g):
    from dragonfly2_tpu.ops.neighbor_agg_pallas import sum_by_shard

    return sum_by_shard(by_shard, g), None, None


_gather_by_shard.defvjp(_gather_by_shard_fwd, _gather_by_shard_bwd)


def masked_mean(x: jnp.ndarray, mask: jnp.ndarray, *, eps: float = 1e-6) -> jnp.ndarray:
    """Mean over axis 1 counting only mask==1 slots. x: [N, K, H], mask: [N, K]."""
    m = mask.astype(x.dtype)[..., None]
    total = jnp.sum(x * m, axis=1)
    count = jnp.sum(m, axis=1)
    return total / (count + eps)
