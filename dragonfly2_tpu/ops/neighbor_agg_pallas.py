"""The one Pallas TPU kernel of the neighbor aggregation: the gather's VJP
(ops.neighbor_agg.neighbor_gather) in the cheap direction, N*K cotangent rows
summed into N, on TPU chips. The neighbor table is fixed for a run, so its
slots are sorted by destination once, on the host (`edges_by_destination`);
the backward gathers the cotangent rows into that order and a kernel adds up
the contiguous runs (`sum_by_destination`). On a mesh whose `data` axis splits
the node rows every chip has a table of its own, over its row shard's slots,
which point into all N rows: it sums its own cotangent rows into [N, H], and
the chips' sums are reduce-scattered (`EdgesByShard`; GSPMD cannot partition a
`pallas_call`, so that runs under `shard_map`). Which VJP a placed run takes
is decided here, once, from its shapes and its mesh (`gather_vjp_tables`).
All that knows the table's format is in this file.

The slots are sorted inside equal blocks of the source rows, each of at most
BLOCK_BYTES: XLA gathers rows out of a table it can hold in VMEM four times
faster than out of one it cannot (3.5 against 14 ns a row on the v5e with
32 MB and 64 MB blocks, PERF.md). The slots count K-major, slot k*N + n for
neighbors[n, k], because that is how the cotangent lies in memory: the TPU
compiler keeps the message tensor [N, K, H] with K major-most (the mean over
K is then a sum over whole [N, H] slabs), so a block, an equal contiguous
range of those slots, is a bitcast of what the backward pass wrote (with K
blocks, block k is g[:, k, :]), and each block is staged into VMEM straight
from there, one after another, and gathered into sorted order in VMEM
(`_sorted_blocks`). Blocks of the row-major order cost a layout copy of the
whole cotangent a layer (PERF.md). The kernel never learns what
a slot number means. It sums, per tile of TILE_DST
destination rows, each block's run of rows that point into the tile, in
windows of WINDOW rows that start on a multiple of ALIGN (a DMA out of a
tiled array starts on a whole tile). Its time goes by the window, and the
windows grow with blocks x tiles: 2.1 times faster than XLA's scatter-add at
16 blocks (512 MB of cotangent rows), 1.4 at 32 (1 GB), slower at 64 (2 GB;
PERF.md), so past MAX_BLOCKS there is no table.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dragonfly2_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple

BLOCK_BYTES = 32 << 20
MAX_BLOCKS = 32
TILE_DST = 256
WINDOW = 384
ALIGN = 16
FIRST, LAST = 1, 2  # flags of a window: it opens / closes its tile
SLOT_ORDER = "k_major"  # the run manifest's word for the table's format
REORDER = "in_place"  # and for how the blocks reach the reorder: read where the backward wrote them
PLATFORM = "tpu"  # the devices Mosaic compiles the kernel for; elsewhere it can only be interpreted


class EdgesByDst(NamedTuple):
    """The neighbor table's transpose, as the kernel walks it.

    perm:  [B, N*K/B] int32: for each of B equal blocks of the slots
           (K-major: row-major over neighbors.T, [K, N], the order the
           compiler keeps the cotangent's rows in), the block's slot numbers,
           counted from the block's first, sorted by the row they point at
           (stable); padded slots are slots like any other
    items: [4, W] int32, the work list by tile: tile of destination rows,
           source block, first row of the window in the block's sorted rows,
           flags. W depends on N, K and B alone; `live` of them count
    local: [W, 1, WINDOW] int32: per row of the window, the row of the tile it
           adds to, or -1: not this window's row
    live:  [1] int32
    """

    perm: jnp.ndarray
    items: jnp.ndarray
    local: jnp.ndarray
    live: jnp.ndarray


@partial(jax.tree_util.register_dataclass, data_fields=["tables"], meta_fields=["mesh"])
@dataclass(frozen=True)
class EdgesByShard:
    """A table for every row shard of a mesh's `data` axis: the shards'
    `EdgesByDst` stacked on a leading axis, which placement splits over
    `data`, so that a chip holds its own alone. A shard's slots are its rows
    of `neighbors` (K-major, as the shard's cotangent lies) and point into all
    N rows. Every shape is the same from shard to shard; `live` is not: a
    shard whose rows point at few tiles walks fewer windows. The mesh is what
    `shard_map` runs the shards' sums on (static: no array of the pytree)."""

    tables: EdgesByDst
    mesh: Mesh


def kernel_sums(width: int, dtype) -> bool:
    """Rows the kernel adds up exactly: bfloat16 (the MXU's passes would round
    float32 rows, 2e-5 of the largest sum on the v5e) and whole lanes wide."""
    return dtype == jnp.bfloat16 and width % 128 == 0


def _source_blocks(slots: int, row_bytes: int) -> int:
    """The fewest equal blocks of the slots that hold at most BLOCK_BYTES of
    rows each and that the windows tile; 0 where there is none up to MAX_BLOCKS."""
    blocks = max(1, -(-slots * row_bytes // BLOCK_BYTES))
    while blocks <= MAX_BLOCKS and slots // blocks >= WINDOW:
        if slots % (blocks * ALIGN) == 0:
            return blocks
        blocks += 1
    return 0


def _table_blocks(slots: int, dst_rows: int, width: int, dtype) -> tuple[int, str]:
    """The source blocks of one table over `slots` cotangent rows
    [slots, width] of `dtype` that point into `dst_rows` rows, or 0 and why
    the kernel does not take those shapes."""
    if not kernel_sums(width, dtype):
        name = None if dtype is None else jnp.dtype(dtype).name
        return 0, f"the kernel sums bfloat16 rows of whole lanes (128), not {name}[{width}]"
    if dst_rows % TILE_DST:
        return 0, f"{dst_rows} destination rows are not whole tiles of {TILE_DST}"
    blocks = _source_blocks(slots, width * 2)
    if not blocks:
        return 0, (f"{slots} slots of {width * 2} bytes do not tile into at most {MAX_BLOCKS} "
                   f"blocks of {BLOCK_BYTES >> 20} MB and whole windows of {WINDOW} rows")
    return blocks, ""


def placed_rows(hosts: int, shards: int = 1) -> int:
    """The node rows a cluster of `hosts` is placed at over `shards` row
    shards: the smallest rung at or above `hosts` of THE ladder, which nothing
    else knows. A rung is whole tiles of TILE_DST, whole row shards, and whole
    sixteenths of the power of two at or above it: eight rungs an octave, so
    at most an eighth of padding (from 8 * TILE_DST hosts up), and a rung is
    placed as it is (32,768 stays 32,768; 40,000 goes to 40,960). It is here,
    beside the rule it serves: a host count is whatever the scheduler saw
    that week, and `why_derived` takes whole tiles alone, so placement hands
    it rungs, which it takes at the widths the trainer ships (256 and 512, up
    to MAX_BLOCKS blocks a shard; from 32,768 rows a shard on, a rung of 512-wide
    rows is whole blocks of BLOCK_BYTES). And what is compiled for the placed
    rows (the scan step, the eager init, the export's forward) is compiled for
    a rung: a host count that moves inside one finds its programs again.
    Padding is paid in every step, a crossing once: sixteen rungs an octave
    would halve the first and double the second."""
    unit = math.lcm(TILE_DST, shards)
    rows = max(hosts, 1)
    while True:
        octave = 1 << (rows - 1).bit_length()
        step = max(octave // 16 // unit, 1) * unit
        rung = pad_to_multiple(rows, step)
        if rung == rows:  # (a turn more than two only where `shards` is no power of two)
            return rows
        rows = rung


def why_derived(shape: tuple[int, int], width: int, dtype, mesh: Mesh) -> str:
    """THE rule for which VJP the gather of a placed run takes, from what the
    program can observe: the placed `neighbors`' shape [N, K], the gathered
    states' width and dtype, and the mesh. "" where the kernel runs, over one
    table a row shard of `data`; else the reason it stays `jnp.take`'s."""
    n, k = shape
    shards = mesh.shape[DATA_AXIS]
    platform = mesh.devices.flat[0].platform
    if platform != PLATFORM:
        return f"{platform} devices: the kernel compiles for {PLATFORM} alone"
    if n % shards:
        return f"{n} node rows are not whole row shards of {shards}"
    return _table_blocks(n // shards * k, n, width, dtype)[1]


def gather_vjp_tables(
    neighbors: np.ndarray, width: int, dtype, mesh: Mesh
) -> tuple[EdgesByDst | EdgesByShard | None, str]:
    """What placement hangs on the graph for the gather's VJP, and why: the
    sorted table (one device), a table per row shard of `data` (more: each
    over the shard's own rows of `neighbors`, pointing into all N), or None
    and `why_derived`'s reason. Numpy, on the host, once per placed run."""
    reason = why_derived(neighbors.shape, width, dtype, mesh)
    if reason:
        return None, reason
    n, shards = neighbors.shape[0], mesh.shape[DATA_AXIS]
    tables = [edges_by_destination(rows, width, dtype, n) for rows in np.split(np.asarray(neighbors), shards)]
    if shards == 1:
        return tables[0], ""
    return EdgesByShard(EdgesByDst(*(np.stack(arrays) for arrays in zip(*tables))), mesh), ""


def gather_vjp_report(
    by_dst: EdgesByDst | EdgesByShard | None, shape: tuple[int, int], width: int, dtype, mesh: Mesh
) -> dict:
    """The run manifest's words for what `gather_vjp_tables` returned to a
    placed run: the table(s) the kernel walks (its time goes by the window, so
    `live_windows` over the shards is what a shard heavy with hubs shows), or
    the rule's reason for none."""
    if by_dst is None:
        return {"path": "derived", "reason": why_derived(shape, width, dtype, mesh)}
    one = isinstance(by_dst, EdgesByDst)
    perm, live = (by_dst.perm[None], by_dst.live) if one else (by_dst.tables.perm, by_dst.tables.live)
    shards, blocks, per_block = perm.shape
    live = np.asarray(live)
    return {
        "path": "sorted_kernel", "slot_order": SLOT_ORDER, "reorder": REORDER, "shards": shards, "blocks": blocks,
        "block_bytes": per_block * width * jnp.dtype(dtype).itemsize,
        "live_windows": {"least": int(live.min()), "most": int(live.max())},
    }


def edges_by_destination(
    neighbors: np.ndarray, width: int, dtype, dst_rows: int | None = None
) -> EdgesByDst | None:
    """Sort a neighbor table's slots by destination for cotangent rows
    [N*K, width] of `dtype`: numpy, on the host, once per placed run (~0.1 s
    for a million slots), inside equal blocks of the K-major slot order (the
    text above BLOCK_BYTES). `dst_rows` is the number of rows the slots point
    into where it is not the table's own N: a row shard's [N/dp, K] points
    into all N, so the tiles come from the destinations, the slots and the
    blocks from the shard's rows. None where the kernel does not apply or
    does not pay (`_table_blocks`): the gather then keeps `jnp.take`'s VJP.
    Every shape depends on N, K, the destination rows and the rows' bytes
    alone: a hub is a longer run of windows for its tile, a tile nobody points
    at one window that adds nothing."""
    n, k = neighbors.shape
    dst_rows = n if dst_rows is None else dst_rows
    blocks, _ = _table_blocks(n * k, dst_rows, width, dtype)
    if not blocks:
        return None
    per_block = n * k // blocks
    flat = np.asarray(neighbors, np.int32).T.reshape(blocks, per_block)  # slots in K-major order
    perm = np.argsort(flat, axis=1, kind="stable").astype(np.int32)
    dst = np.take_along_axis(flat, perm, axis=1)
    tiles = dst_rows // TILE_DST
    # the block's run of rows for each tile: [first[b, t], first[b, t + 1])
    first = np.stack([np.searchsorted(d, np.arange(tiles + 1) * TILE_DST) for d in dst])
    begin = first[:, :-1] // ALIGN * ALIGN
    count = -(-(first[:, 1:] - begin) // WINDOW)
    count[0] = np.maximum(count[0], 1)  # every tile is written, if only with zeros
    block, tile = np.divmod(np.repeat(np.arange(count.size), count.ravel()), tiles)
    nth = np.arange(block.size) - np.repeat(np.cumsum(count) - count.ravel(), count.ravel())
    by_tile = np.argsort(tile, kind="stable")
    block, tile, nth = block[by_tile], tile[by_tile], nth[by_tile]
    start = begin[block, tile] + nth * WINDOW
    # a window counts its own rows of the run, also when it was moved to end with the block
    at = np.minimum(start, per_block - WINDOW)[:, None] + np.arange(WINDOW)
    mine = (at >= np.maximum(start, first[block, tile])[:, None]) & (
        at < np.minimum(start + WINDOW, first[block, tile + 1])[:, None])
    local = np.where(mine, dst[block[:, None], at] - tile[:, None] * TILE_DST, -1)
    edge = np.flatnonzero(np.diff(tile)) + 1
    flags = np.zeros(tile.size, np.int32)
    flags[np.r_[0, edge]] |= FIRST
    flags[np.r_[edge - 1, tile.size - 1]] |= LAST
    # per block: a window for every WINDOW rows, and for every tile one more and its misalignment
    bound = blocks * (tiles + -(-(per_block + (ALIGN - 1) * tiles) // WINDOW))
    items = np.zeros((4, bound), np.int32)  # the grid runs over the live ones alone
    items[:, : tile.size] = tile, block, at[:, 0], flags
    padded = np.full((bound, 1, WINDOW), -1, np.int32)
    padded[: tile.size, 0] = local
    return EdgesByDst(perm, items, padded, np.array([tile.size], np.int32))


def _segment_sum_kernel(tile_ref, block_ref, start_ref, flags_ref, local_ref, *refs):
    """One window: add the rows of WINDOW sorted rows of one source block that
    point into one tile of destination rows. The rows come by DMA out of the
    block's array, the next window's while this one is summed. The
    [TILE_DST, WINDOW] one-hot of the rows' places in the tile is built on the
    VPU and sums them on the MXU: a bfloat16 product with a one-hot is exact,
    the accumulator is float32. (`sum_by_destination` is the call.)"""
    *blocks, out_ref, acc_ref, rows_ref, sem_ref = refs
    i = pl.program_id(0)

    def rows_of(j, block):
        first = pl.multiple_of(start_ref[j], ALIGN)
        return pltpu.make_async_copy(
            blocks[block].at[pl.ds(first, WINDOW), :], rows_ref.at[j % 2], sem_ref.at[j % 2]
        )

    def fetch(j):
        for block in range(len(blocks)):  # one of them holds the window
            pl.when(block_ref[j] == block)(rows_of(j, block).start)

    pl.when(i == 0)(lambda: fetch(i))
    pl.when(i + 1 < pl.num_programs(0))(lambda: fetch(i + 1))
    rows_of(i, 0).wait()  # the wait reads the shapes only

    @pl.when(flags_ref[i] & FIRST != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (TILE_DST, WINDOW), 0)
    rows = rows_ref[i % 2]
    onehot = (row == local_ref[...]).astype(rows.dtype)
    acc_ref[...] += jnp.dot(onehot, rows, preferred_element_type=jnp.float32)

    @pl.when(flags_ref[i] & LAST != 0)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _sorted_blocks(by_dst: EdgesByDst, g: jnp.ndarray) -> list[jnp.ndarray]:
    """The reorder: each block's rows of the cotangent [N, K, H] in its
    sorted order (perm), a [N*K/B, H] array a block. A block is an equal range
    of the K-major rows, [K, N, H], which on the TPU is the cotangent as the
    backward wrote it, so its slice is a bitcast of a range of that buffer
    (one K slice, half of one, or one and a half, as B and K fall). The
    blocks are read one after the other (the barrier): the compiler then
    stages each block's range into VMEM straight out of the cotangent and
    gathers it there. Read together, the slices of all blocks become one
    fusion that copies the whole [N, K, H] to HBM (VMEM holds few blocks of
    BLOCK_BYTES) and the first block's gather reads its table out of HBM,
    four times slower a row (PERF.md)."""
    width = g.shape[-1]
    blocks, per_block = by_dst.perm.shape
    slots = jnp.swapaxes(g, 0, 1).reshape(blocks * per_block, width)
    rows = []
    for block in range(blocks):  # unrolled on purpose: B ops a layer, each block its own slice, in order
        first = block * per_block
        part = jax.lax.slice_in_dim(slots, first, first + per_block)  # dflint: disable=DF012 (above)
        sorted_rows = part.at[by_dst.perm[block]].get(unique_indices=True, mode="promise_in_bounds")
        slots, sorted_rows = jax.lax.optimization_barrier((slots, sorted_rows))  # dflint: disable=DF012 (above)
        rows.append(sorted_rows)
    return rows


def _segment_sums(by_dst: EdgesByDst, rows: list[jnp.ndarray], dst_rows: int) -> jnp.ndarray:
    """The kernel over the blocks' sorted rows: [dst_rows, H], one grid step
    per live window, by tile: a tile's output block stays in VMEM from its
    first window to its last."""
    width, dtype = rows[0].shape[-1], rows[0].dtype
    tile, block, start, flags = by_dst.items
    return pl.pallas_call(
        _segment_sum_kernel,
        out_shape=jax.ShapeDtypeStruct((dst_rows, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(by_dst.live[0],),
            in_specs=[pl.BlockSpec((None, 1, WINDOW), lambda i, *_: (i, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(rows),
            out_specs=pl.BlockSpec((TILE_DST, width), lambda i, tile, *_: (tile[i], 0)),
            scratch_shapes=[
                pltpu.VMEM((TILE_DST, width), jnp.float32),
                pltpu.VMEM((2, WINDOW, width), dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(tile, block, start, flags, by_dst.local, *rows)


@partial(jax.jit, static_argnames="dst_rows")  # traced and lowered once for every layer of a step
def sum_by_destination(by_dst: EdgesByDst, g: jnp.ndarray, dst_rows: int | None = None) -> jnp.ndarray:
    """Cotangent [N, K, H] -> [N, H] (a row shard's [N/dp, K, H] -> the whole
    [dst_rows, H], as its table was built): what a scatter-add by the neighbor
    table gives, with float32 accumulation (`kernel_sums(H, g.dtype)`).
    Gathers, the cheap direction, block by block where the backward wrote
    the block (`_sorted_blocks`), then sums the runs (`_segment_sums`)."""
    return _segment_sums(by_dst, _sorted_blocks(by_dst, g), g.shape[0] if dst_rows is None else dst_rows)


def sum_by_shard(by_shard: EdgesByShard, g: jnp.ndarray) -> jnp.ndarray:
    """Cotangent [N, K, H], rows over `data` -> [N, H], rows over `data`: every
    chip sums its own cotangent rows into all N rows with its own table
    (`sum_by_destination` under `shard_map`), and the chips' [N, H] sums are
    added up and split by rows, in the cotangent's dtype: a reduce-scatter.
    That last step is left to the partitioner, as the sum over the shard axis
    of the stacked sums with its rows constrained to `data`: a `psum_scatter`
    inside the `shard_map` compiles to the same fused collective on the TPU,
    but the compiler drops its `op_name`, and the device trace is read by the
    step's scope names (models/graphsage.STEP_SCOPES)."""
    mesh = by_shard.mesh
    shards = mesh.shape[DATA_AXIS]

    def shard_sums(table, g_rows):
        table = jax.tree.map(lambda a: a[0], table)  # the shard's own of the stack
        return sum_by_destination(table, g_rows, dst_rows=g_rows.shape[0] * shards)[None]

    rows = P(DATA_AXIS)
    sums = jax.shard_map(  # (the pallas_call's out_shape says nothing of mesh axes: no check of them)
        shard_sums, mesh=mesh, in_specs=(rows, rows), out_specs=rows, check_vma=False
    )(by_shard.tables, g)
    return jax.lax.with_sharding_constraint(jax.lax.reduce_sum(sums, axes=(0,)), NamedSharding(mesh, rows))
