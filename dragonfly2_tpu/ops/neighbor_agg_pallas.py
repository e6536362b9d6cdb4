"""The one Pallas TPU kernel of the neighbor aggregation: the gather's VJP
(ops.neighbor_agg.neighbor_gather) in the cheap direction, N*K cotangent rows
summed into N, on one TPU chip. The neighbor table is fixed for a run, so its
slots are sorted by destination once, on the host (`edges_by_destination`);
the backward gathers the cotangent rows into that order and a kernel adds up
the contiguous runs (`sum_by_destination`). All that knows the table's format
is in this file.

The slots are sorted inside equal blocks of the source rows, each of at most
BLOCK_BYTES: XLA gathers rows out of a table it can hold in VMEM four times
faster than out of one it cannot (3.5 against 14 ns a row on the v5e with
32 MB and 64 MB blocks, PERF.md). The slots count K-major, slot k*N + n for
neighbors[n, k], because that is how the cotangent lies in memory: the TPU
compiler keeps the message tensor [N, K, H] with K major-most (the mean over
K is then a sum over whole [N, H] slabs), so a block, an equal contiguous
range of those slots, is a bitcast of what the backward pass wrote (with K
blocks, block k is g[:, k, :]). Blocks of the row-major order cost a layout
copy of the whole cotangent a layer (PERF.md). The kernel never learns what
a slot number means. It sums, per tile of TILE_DST
destination rows, each block's run of rows that point into the tile, in
windows of WINDOW rows that start on a multiple of ALIGN (a DMA out of a
tiled array starts on a whole tile). Its time goes by the window, and the
windows grow with blocks x tiles: 2.1 times faster than XLA's scatter-add at
16 blocks (512 MB of cotangent rows), 1.4 at 32 (1 GB), slower at 64 (2 GB;
PERF.md), so past MAX_BLOCKS there is no table.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 32 << 20
MAX_BLOCKS = 32
TILE_DST = 256
WINDOW = 384
ALIGN = 16
FIRST, LAST = 1, 2  # flags of a window: it opens / closes its tile
SLOT_ORDER = "k_major"  # the run manifest's word for the table's format


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


def edges_by_destination(neighbors: np.ndarray, width: int, dtype) -> EdgesByDst | None:
    """Sort a neighbor table's slots by destination for cotangent rows
    [N*K, width] of `dtype`: numpy, on the host, once per placed run (~0.1 s
    for a million slots), inside equal blocks of the K-major slot order (the
    text above BLOCK_BYTES). None where the kernel does not apply
    (`kernel_sums`, N a multiple of TILE_DST) or does not pay (MAX_BLOCKS): the
    gather then keeps `jnp.take`'s VJP. Every shape depends on N, K and the
    rows' bytes alone: a hub is a longer run of windows for its tile, a tile
    nobody points at one window that adds nothing."""
    n, k = neighbors.shape
    blocks = _source_blocks(n * k, width * 2) if kernel_sums(width, dtype) and n % TILE_DST == 0 else 0
    if not blocks:
        return None
    per_block = n * k // blocks
    flat = np.asarray(neighbors, np.int32).T.reshape(blocks, per_block)  # slots in K-major order
    perm = np.argsort(flat, axis=1, kind="stable").astype(np.int32)
    dst = np.take_along_axis(flat, perm, axis=1)
    tiles = n // TILE_DST
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


@jax.jit  # traced and lowered once for every layer of a step
def sum_by_destination(by_dst: EdgesByDst, g: jnp.ndarray) -> jnp.ndarray:
    """Cotangent [N, K, H] -> [N, H]: what a scatter-add by the neighbor table
    gives, with float32 accumulation (`kernel_sums(H, g.dtype)`). Gathers, the
    cheap direction, block by block (perm permutes a block's slots; the blocks
    are ranges of the K-major rows, [K, N, H], which on the TPU is the
    cotangent as it lies), then one grid step per live window, by tile: a
    tile's output block stays in VMEM from its first window to its last."""
    n, _, width = g.shape
    blocks = jnp.swapaxes(g, 0, 1).reshape(by_dst.perm.shape[0], -1, width)
    rows = [
        part.at[perm].get(unique_indices=True, mode="promise_in_bounds")
        for part, perm in zip(blocks, by_dst.perm)
    ]
    tile, block, start, flags = by_dst.items
    return pl.pallas_call(
        _segment_sum_kernel,
        out_shape=jax.ShapeDtypeStruct((n, width), g.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(by_dst.live[0],),
            in_specs=[pl.BlockSpec((None, 1, WINDOW), lambda i, *_: (i, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(rows),
            out_specs=pl.BlockSpec((TILE_DST, width), lambda i, tile, *_: (tile[i], 0)),
            scratch_shapes=[
                pltpu.VMEM((TILE_DST, width), jnp.float32),
                pltpu.VMEM((2, WINDOW, width), g.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(tile, block, start, flags, by_dst.local, *rows)
