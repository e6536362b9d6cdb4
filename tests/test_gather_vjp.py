"""The gather's VJP over a destination-sorted edge table
(ops.neighbor_agg_pallas, ops.neighbor_agg.neighbor_gather): the host's table
in plain numpy, the segmented-sum kernel interpreted on the CPU, the
`custom_vjp` in a training step, the run manifest's word on it.

FOURTEEN tier-1 tests, most of them loops over cases, in a file of their own,
on purpose (a fifteenth is marked `slow`: it loads the TPU's compiler).
Tier-1 hands files to its workers by their number of tests, largest first, two
at a time (xdist's loadfile), so a file's count decides when it runs and what
every file after it runs beside. The scheduler plane's host-timing assertions
(tests/test_bench_contract.py::test_ml_observability_contract,
test_dispatch.py's thread scaling; 15 tests each) pass at the parent because
they run early, before the JAX-heavy files. As parametrised cases these tests
made a large file: it started with the run, took a worker for 20 s and pushed
those assertions into the JAX-heavy middle, where 6 of 13 whole runs failed
one (ROADMAP D10); a light file of 42 cases alone still moved
test_bench_contract.py behind an 11 s file. Under fifteen tests this file (and
tests/test_mesh_decision.py, which holds the mesh's side of the same VJP)
ranks after every timing-sensitive one and leaves the order of
all files before it as the parent has it. A failure names its case."""

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dragonfly2_tpu.models.features import FEATURE_DIM
from dragonfly2_tpu.models.graphsage import TopoGraph
from dragonfly2_tpu.ops import neighbor_agg_pallas as pk
from dragonfly2_tpu.ops.neighbor_agg import neighbor_gather
from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer import train_gnn
from dragonfly2_tpu.trainer.synthetic import PairBatch

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))

import scope_reduce  # noqa: E402  (the benchmark's reader of the names: plain Python)


def _hub_table(n, k, seed=0):
    """A neighbor table with what the VJP must not trip on: a hub (a quarter
    of all slots point at row 3), padded slots at row 0, and destinations
    nobody points at (every row from 3n/4 up, the kernel's last tile whole)."""
    rng = np.random.default_rng(seed)
    nbr = rng.integers(1, 3 * n // 4, size=(n, k)).astype(np.int32)
    pick = rng.random((n, k))
    nbr[pick < 0.25] = 3
    nbr[pick > 0.9] = 0
    return nbr



def _run_inputs(nbr, cfg):
    """(graph over the neighbor table, pair pool of one batch) for a training run."""
    n, k = nbr.shape
    b = cfg.batch_size
    graph = TopoGraph(np.zeros((n, 12), np.float32), nbr, np.ones((n, k), np.float32),
                      np.zeros((n, k, 4), np.float32))
    pairs = PairBatch(np.zeros(b, np.int32), np.zeros(b, np.int32),
                      np.zeros((b, FEATURE_DIM), np.float32), np.zeros(b, np.float32))
    return graph, pairs


def _table_in_blocks(monkeypatch, nbr, width, dtype, blocks):
    """The table placement would build, its slots in `blocks` source blocks
    (as if the cotangent were `blocks` times what XLA's gather holds)."""
    monkeypatch.setattr(pk, "BLOCK_BYTES", nbr.size * width * 2 // blocks)
    return pk.edges_by_destination(nbr, width, dtype)


def _table(kind, n, k):
    if kind == "hub":
        return _hub_table(n, k, seed=6)
    if kind == "one_row":  # every slot a padded one: the largest hub there is
        return np.zeros((n, k), np.int32)
    return np.random.default_rng(7).integers(0, n, (n, k)).astype(np.int32)


def test_table_sorts_every_slot_once(monkeypatch):
    """The host's part, plain numpy: whatever the graph, the table is a
    permutation of each block's slots in destination order, the slots counted
    K-major (slot k*N + n is neighbors[n, k]), and the kernel's windows count
    every slot exactly once, in its tile. Where the shapes do not tile there
    is no table. A row shard's table (`shards` > 1: its rows of the graph,
    pointing into all N) sorts the shard's slots the same way, over the tiles
    of all N destinations."""
    for n, k in [(512, 16), (256, 24), (1024, 6), (100, 7), (96, 5)]:
        for kind in ["hub", "one_row", "uniform"]:
            for blocks, shards in [(1, 1), (4, 1), (1, 4), (2, 2)]:
                case = (n, k, kind, blocks, shards)
                whole = _table(kind, n, k)
                if n % shards:
                    continue
                for nbr in np.split(whole, shards):
                    rows = nbr.shape[0]
                    monkeypatch.setattr(pk, "BLOCK_BYTES", nbr.size * 128 * 2 // blocks)
                    t = pk.edges_by_destination(nbr, 128, jnp.bfloat16, n)
                    if n % pk.TILE_DST:
                        assert t is None, case
                        continue
                    assert t.perm.shape == (blocks, rows * k // blocks), case
                    per_block = t.perm.shape[1]
                    assert (np.sort(t.perm, axis=1) == np.arange(per_block)).all(), case
                    dst = np.take_along_axis(nbr.T.reshape(blocks, -1), t.perm, axis=1)
                    assert (np.diff(dst, axis=1) >= 0).all(), case
                    slot_k, slot_n = np.divmod(t.perm + np.arange(blocks)[:, None] * per_block, rows)
                    assert (nbr[slot_n, slot_k] == dst).all(), case
                    live = t.live[0]
                    tile, block, start, flags = t.items[:, :live]
                    # by tile, every tile of the N destinations written
                    assert (np.diff(tile) >= 0).all() and set(tile) == set(range(n // pk.TILE_DST)), case
                    assert (start % pk.ALIGN == 0).all() and (start + pk.WINDOW <= per_block).all(), case
                    local = t.local[:live, 0]
                    assert (local >= 0).sum() == rows * k and local.max() < pk.TILE_DST, case
                    got = dst[block[:, None], start[:, None] + np.arange(pk.WINDOW)]
                    assert (np.where(local >= 0, got - tile[:, None] * pk.TILE_DST, -1) == local).all(), case


def test_work_list_shapes_depend_on_n_and_k_alone(monkeypatch):
    tables = [_table_in_blocks(monkeypatch, _table(kind, 512, 16), 128, jnp.bfloat16, 2)
              for kind in ("hub", "one_row", "uniform")]
    shapes = [[x.shape for x in jax.tree.leaves(t)] for t in tables]
    assert shapes[0] == shapes[1] == shapes[2]
    # a row shard's: on N, N/dp, K and the row bytes alone, so the shards' tables stack; `live` differs
    shards = [pk.edges_by_destination(rows, 128, jnp.bfloat16, 1024)
              for kind in ("hub", "uniform") for rows in np.split(_table(kind, 1024, 6), 4)]
    assert len({tuple(x.shape for x in t) for t in shards}) == 1
    assert shards[0].perm.shape == (1, 256 * 6) and shards[0].items.shape[1] == 4 + -(-(256 * 6 + 15 * 4) // pk.WINDOW)
    assert len({int(t.live[0]) for t in shards}) > 1


def test_source_blocks_follow_the_cotangents_bytes():
    """Placement sizes the blocks from N*K*H*itemsize, not from the benchmark's cells."""
    for case in [
        (32768, 16, 512, jnp.bfloat16, 16),   # gnn-32k-512: 512 MB of cotangent rows in 32 MB blocks
        (65536, 16, 256, jnp.bfloat16, 16),   # gnn-64k-256
        (65536, 16, 512, jnp.bfloat16, 32),   # twice the slots or the width: twice the blocks,
        (32768, 16, 1024, jnp.bfloat16, 32),  # never larger ones (14 ns a row against 3.5)
        (2048, 16, 256, jnp.bfloat16, 1),
        (24576, 10, 384, jnp.bfloat16, 6),    # 5.6 blocks' worth: 6 tile it
        (65536, 16, 1024, jnp.bfloat16, None),  # 64 blocks: the kernel's windows would cost more than XLA's scatter
        (256, 1, 128, jnp.bfloat16, None),    # fewer slots than a window
        (32768, 16, 512, jnp.float32, None),
        (32768, 16, 500, jnp.bfloat16, None),
        (32700, 16, 512, jnp.bfloat16, None),
    ]:
        n, k, width, dtype, blocks = case
        t = pk.edges_by_destination(np.zeros((n, k), np.int32), width, dtype)
        if blocks is None:
            assert t is None, case
            continue
        assert isinstance(t, pk.EdgesByDst) and t.perm.shape == (blocks, n * k // blocks), case
        assert t.perm.shape[1] * width * 2 <= pk.BLOCK_BYTES, case


def test_bare_gather_lowers_to_todays_hlo():
    """Without a table (inference, tools, CPU) the op is `jnp.take`, VJP and all."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, 64, size=(64, 4)).astype(np.int32))

    def loss(gather, hh):
        return jnp.sum(gather(hh) ** 2)

    ours = jax.jit(jax.grad(lambda hh: loss(lambda x: neighbor_gather(x, nbr), hh))).lower(h).as_text()
    takes = jax.jit(jax.grad(lambda hh: loss(lambda x: jnp.take(x, nbr, axis=0), hh))).lower(h).as_text()
    assert ours == takes and "scatter" in ours


def test_a_step_without_a_table_is_jnp_takes_on_one_device_and_on_four(monkeypatch):
    """The table's format is the kernel's own business: where placement builds
    none (the CPU here; a `data` mesh of four devices, as the four-chip cell's)
    the served scan step lowers to the text it has with `jnp.take` in the
    gather's place, letter for letter."""
    from dragonfly2_tpu.models import graphsage

    cfg = train_gnn.GNNTrainConfig(hidden=128, embed_dim=16, num_layers=2, batch_size=64)
    graph, pairs = _run_inputs(_hub_table(256, 16), cfg)
    unplaced = train_gnn.init_state(cfg, graph, 0)

    def lowered(n_devices):
        mesh, _ = meshlib.mesh_for_run(jax.devices()[:n_devices])
        train_gnn._kept.clear()  # the test swaps the gather under the program: each text from a build of its own
        state, g, pool, step = train_gnn.shard_for_training_scan(
            unplaced, graph, pairs, mesh, batch_size=64, steps_per_call=2)
        assert g.by_dst is None
        return step.lower(state, g, pool, jax.random.PRNGKey(0)).as_text()

    for n_devices in (1, 4):
        ours = lowered(n_devices)
        with monkeypatch.context() as m:
            m.setattr(graphsage, "neighbor_gather", lambda h, nbr, by_dst=None: jnp.take(h, nbr, axis=0))
            takes = lowered(n_devices)
        assert ours == takes and "scatter" in ours, n_devices


# (n, k, width, dtype, source blocks): small, the interpreter takes 50 ms a window
KERNEL_CASES = [
    (512, 12, 128, jnp.bfloat16, 1),  # two tiles, the hub's run of windows longer
    (512, 12, 128, jnp.bfloat16, 4),  # a window for every block and tile
    (256, 24, 256, jnp.bfloat16, 2),
    (1024, 2, 128, jnp.bfloat16, 4),  # twice as many blocks as K: half a slice g[:, k, :] a block
    (512, 12, 128, jnp.bfloat16, 8),  # fewer blocks than K, and not whole slices: one and a half a block
    (512, 12, 128, jnp.bfloat16, 12),  # as many blocks as K: a block is a slice, as in the 32k/512 and 64k/256 cells
]
# where no table is built: the VJP is jnp.take's
DERIVED_CASES = [
    (512, 12, 96, jnp.bfloat16, 1),   # rows not whole lanes wide
    (100, 7, 33, jnp.bfloat16, 1),    # n not a multiple of the tile
    (100, 7, 33, jnp.float32, 1),
    (512, 12, 128, jnp.float32, 4),   # the MXU would round float32 rows
]


def _parents_reorder(table, g):
    """The reorder as the parent commit composed it, kept as the oracle: the
    K-major view of the cotangent cut into its blocks, all at once, and one
    `.at[perm].get` a block."""
    blocks = jnp.swapaxes(g, 0, 1).reshape(table.perm.shape[0], -1, g.shape[-1])
    return [part.at[perm].get(unique_indices=True, mode="promise_in_bounds") for part, perm in zip(blocks, table.perm)]


def _bits(x):
    return np.asarray(x).view(np.uint16)


def _take_vjp(nbr, g):
    h = jnp.zeros((nbr.shape[0], g.shape[-1]), g.dtype)
    return jax.vjp(lambda x: jnp.take(x, nbr, axis=0), h)[1](g)[0]


def test_gather_vjp_with_the_placed_table(monkeypatch):
    """Against `jax.vjp(jnp.take)` on a graph with a hub, padded slots at row
    0 and destinations nobody points at: the kernel's sum is as close to the
    float32 sum as a bfloat16 result can be; without a table it is the
    derived VJP, bit for bit. Where the kernel runs, the blocks read one by
    one where the cotangent lies give the rows the parent's all-at-once
    slices gave, bit for bit, and so the sum it gave."""
    for case in KERNEL_CASES + DERIVED_CASES:
        n, k, width, dtype, blocks = case
        nbr = _hub_table(n, k, seed=2)
        g = jnp.asarray(np.random.default_rng(3).normal(size=(n, k, width)), dtype)
        h = jnp.zeros((n, width), dtype)
        table = _table_in_blocks(monkeypatch, nbr, width, dtype, blocks)
        assert (table is not None) == (case in KERNEL_CASES), case
        with pltpu.force_tpu_interpret_mode():  # the kernel, where there is a table, on the CPU
            out, vjp = jax.vjp(lambda x: neighbor_gather(x, nbr, jax.tree.map(jnp.asarray, table)), h)
            summed = vjp(g)[0]
            if table is not None:
                placed = jax.tree.map(jnp.asarray, table)
                rows, parents = pk._sorted_blocks(placed, g), _parents_reorder(placed, g)
                parents_sum = pk._segment_sums(placed, parents, n)
        got = np.asarray(summed.astype(jnp.float32))
        assert out.shape == (n, k, width) and out.dtype == dtype, case
        if table is None:
            np.testing.assert_array_equal(got, np.asarray(_take_vjp(nbr, g), np.float32), err_msg=str(case))
            continue
        assert table.perm.shape[0] == blocks == len(rows), case
        for block, (ours, theirs) in enumerate(zip(rows, parents, strict=True)):
            np.testing.assert_array_equal(_bits(ours), _bits(theirs), err_msg=f"{case} block {block}")
        np.testing.assert_array_equal(_bits(summed), _bits(parents_sum), err_msg=str(case))
        want = np.asarray(_take_vjp(nbr, g.astype(jnp.float32)))
        np.testing.assert_allclose(got, want, rtol=2.0 ** -8, atol=2.0 ** -8 * np.abs(want).max(), err_msg=str(case))
        assert not got[3 * n // 4:].any(), case  # nobody points there

    # a table is placement's decision: another graph's, or states the kernel does not sum, are refused
    nbr = _hub_table(512, 12)
    table = _table_in_blocks(monkeypatch, nbr, 128, jnp.bfloat16, 1)
    for states, said in [(jnp.zeros((256, 128), jnp.bfloat16), "256 states for a table of 512 rows"),
                         (jnp.ones((512, 128), jnp.float32), "float32[128] states for a table the kernel sums")]:
        with pytest.raises(ValueError, match=re.escape(said)):
            neighbor_gather(states, nbr, table)


def test_a_placed_step_with_the_table(monkeypatch):
    """A training run at the smallest shapes the kernel takes. The run
    manifest's `placement` (`train_gnn._placement`) names the VJP: no TPU
    here, so placement builds no table and the path is `derived`; with the
    table one TPU chip would get, the kernel's, with its blocks, and the table
    is not counted as the graph's. In the compiled step the `custom_vjp`'s
    rule inherits the scope and JAX's backward marker: the reorder's gathers
    and the kernel (interpreted here: a loop) read as (`gather`, backward), as
    `scope.gather_bwd_ms` reads them, and no scatter is left under `gather`."""
    n, k = 256, 16
    cfg = train_gnn.GNNTrainConfig(hidden=128, embed_dim=16, num_layers=2, batch_size=64)
    graph, pairs = _run_inputs(_hub_table(n, k), cfg)
    mesh, decision = meshlib.mesh_for_run()
    unplaced = train_gnn.init_state(cfg, graph, 0)
    state, g, _, _ = train_gnn.shard_for_training_scan(
        unplaced, graph, pairs, mesh, batch_size=64, steps_per_call=2)
    assert g.by_dst is None
    counts = {"slots": n * k, "max_in_degree": int(np.bincount(graph.neighbors.ravel()).max())}
    assert train_gnn._placement(mesh, decision, n, state, g, 64)["gather_vjp"] == {
        "path": "derived", "reason": "cpu devices: the kernel compiles for tpu alone", **counts}
    assert train_gnn._gathered_states(state) == (128, jnp.bfloat16)
    table = _table_in_blocks(monkeypatch, graph.neighbors, 128, jnp.bfloat16, 2)
    placement = train_gnn._placement(mesh, decision, n, state, g._replace(by_dst=table), 64)
    assert placement["gather_vjp"] == {
        "path": "sorted_kernel", "slot_order": "k_major", "reorder": "in_place", "shards": 1, "blocks": 2,
        "block_bytes": n * k // 2 * 128 * 2, "live_windows": {"least": int(table.live[0]), "most": int(table.live[0])},
        **counts}
    assert placement["graph"]["leaves"] == 4

    with pltpu.force_tpu_interpret_mode():  # (unplaced: the interpreter's callbacks take no mesh)
        step = jax.jit(train_gnn.make_train_step())
        text = step.lower(unplaced, graph._replace(by_dst=table), pairs).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    gather = {name for name in names if scope_reduce.classify(name)[0] == "gather"}
    backward = {name for name in gather if scope_reduce.classify(name)[1]}
    assert any(name.endswith("/gather") for name in backward), sorted(gather)  # the reorder
    assert any("sum_by_destination" in name and "while" in name for name in backward), sorted(gather)  # the sum
    assert any(name.endswith("/gather") for name in gather - backward), sorted(gather)  # the forward
    assert not any("scatter" in name for name in gather), sorted(gather)


def _one_device_mesh():
    return meshlib.mesh_for_run(jax.devices()[:1])[0]


def _three_device_mesh():
    return meshlib.mesh_for_run(jax.devices()[:3])[0]


# (reason's words, neighbors' shape, width, dtype, the mesh): each alone keeps `jnp.take`'s VJP
DERIVED_BY_RULE = {
    "cpu": ("cpu devices", (512, 16), 128, jnp.bfloat16, _one_device_mesh),
    "float32": ("not float32[128]", (512, 16), 128, jnp.float32, _one_device_mesh),
    "lanes": ("not bfloat16[96]", (512, 16), 96, jnp.bfloat16, _one_device_mesh),
    "tiles": ("384 destination rows are not whole tiles of 256", (384, 16), 128, jnp.bfloat16, _one_device_mesh),
    "max_blocks": ("do not tile into at most 32 blocks", (65536, 16), 1024, jnp.bfloat16, _one_device_mesh),
    "row_shards": ("16384 node rows are not whole row shards of 3", (16384, 16), 128, jnp.bfloat16,
                   _three_device_mesh),
}


@pytest.mark.parametrize("rule", DERIVED_BY_RULE)
def test_the_one_rule_says_why_the_vjp_stays_derived(monkeypatch, rule):
    """`neighbor_agg_pallas.why_derived` is the one place that decides which
    VJP a placed run's gather takes, from the placed shapes, the states and
    the mesh; `gather_vjp_tables` returns no table and the rule's reason. The
    CPU's devices are the first reason; to reach the others the test, not the
    program, says the kernel compiles for them (`PLATFORM`)."""
    said, shape, width, dtype, mesh = DERIVED_BY_RULE[rule]
    mesh = mesh()
    if rule != "cpu":
        monkeypatch.setattr(pk, "PLATFORM", "cpu")
    table, reason = pk.gather_vjp_tables(np.zeros(shape, np.int32), width, dtype, mesh)
    assert table is None and said in reason, reason
    assert pk.gather_vjp_report(None, shape, width, dtype, mesh) == {"path": "derived", "reason": reason}
    if rule == "max_blocks":  # 2 GB of cotangent rows: four row shards hold 16 blocks each (ROADMAP R11)
        data4 = meshlib.mesh_for_run(jax.devices()[:4])[0]
        assert pk.why_derived(shape, width, dtype, data4) == ""


def _parents_gather(h, neighbors, by_dst=None):
    """`neighbor_gather` as the parent commit had it: the table's `custom_vjp`
    where the kernel sums the states, else `jnp.take`."""
    from dragonfly2_tpu.ops import neighbor_agg

    if by_dst is not None and pk.kernel_sums(h.shape[1], h.dtype):
        return neighbor_agg._gather_sorted_vjp(h, neighbors, by_dst)
    return jnp.take(h, neighbors, axis=0)


def test_one_device_takes_the_table_and_the_program_it_always_had(monkeypatch):
    """On a mesh of one device the decision returns the table
    `edges_by_destination` builds over the whole graph, array for array, and
    the placed `multi_step` lowers (for the TPU, here, with no chip: Mosaic's
    kernel and all) to the text it has with the parent's `neighbor_gather` in
    the model: no `shard_map`, no collective, nothing of the mesh's path."""
    from dragonfly2_tpu.models import graphsage

    monkeypatch.setattr(pk, "PLATFORM", "cpu")  # the test's word, not the program's: these CPU devices take the kernel
    cfg = train_gnn.GNNTrainConfig(hidden=128, embed_dim=16, num_layers=2, batch_size=64)
    graph, pairs = _run_inputs(_hub_table(512, 16), cfg)
    mesh = _one_device_mesh()
    table, reason = pk.gather_vjp_tables(graph.neighbors, 128, jnp.bfloat16, mesh)
    assert isinstance(table, pk.EdgesByDst) and reason == ""
    for got, want in zip(table, pk.edges_by_destination(graph.neighbors, 128, jnp.bfloat16), strict=True):
        assert got.dtype == want.dtype and got.shape == want.shape and (got == want).all()

    def lowered():
        train_gnn._kept.clear()  # the test swaps the gather under the program: each text from a build of its own
        state, g, pool, step = train_gnn.shard_for_training_scan(
            train_gnn.init_state(cfg, graph, 0), graph, pairs, mesh, batch_size=64, steps_per_call=2)
        assert isinstance(g.by_dst, pk.EdgesByDst) and {len(leaf.sharding.device_set) for leaf in g.by_dst} == {1}
        return step.trace(state, g, pool, jax.random.PRNGKey(0)).lower(lowering_platforms=("tpu",)).as_text()

    ours = lowered()
    with monkeypatch.context() as m:
        m.setattr(graphsage, "neighbor_gather", _parents_gather)
        parents = lowered()
    assert ours == parents and "tpu_custom_call" in ours
    assert "shard_map" not in ours and "all_gather" not in ours and "all_reduce" not in ours


def _computations(text):
    """{name: body} of every computation of a compiled module's text."""
    return {m.group(1): m.group(2) for m in re.finditer(r"^(%\S+) \(.*?\) -> .*?\{\n(.*?)\n\}", text, re.M | re.S)}


@pytest.mark.slow  # loads the TPU's compiler: alone in its process, never under tier-1's workers
def test_no_layout_copy_of_the_cotangent_on_a_described_v5e():
    """`gnn-32k-512`'s placed step (32,768 x 16 x 512, the table one TPU chip
    gets: 16 blocks, a K slice each) and `gnn-40k-512`'s (40,960 rows: 20
    blocks of 0.8 slices), compiled for a described v5e with no chip attached
    (30-45 s each). The compiler keeps the message tensor K-major,
    `bf16[N,16,H]{2,0,1}`; blocks of the row-major slot order made it turn the
    cotangent with a `copy` of the whole `[N, K, H]` a layer
    (`message/add_any`, 1.7 ms each on the chip). With K-major blocks the
    entry computation has none. And the reorder reads each block where the
    backward wrote it: no fusion writes the blocks' slices together (the
    parent's `slice_bitcast_fusion`, a second copy of `[N, K, H]` a layer, 1.45
    ms on the chip), and every reorder gather finds its block in VMEM (the
    parent's first gather of a layer read it out of HBM, 465 us for 109)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    k, hidden = 16, 512
    cfg = train_gnn.GNNTrainConfig(hidden=hidden, embed_dim=hidden // 2, batch_size=2048)
    state = train_gnn.init_state(cfg, _run_inputs(_table("uniform", 8, k), cfg)[0], 0)  # weights do not depend on N
    # (rows, blocks, the compiler's temporaries at most: 2.31 / 2.88 GB, 2.82 / 3.41 at the parent)
    for n, blocks, temp_cap in [(32768, 16, 2.6e9), (40960, 20, 3.2e9)]:
        graph, pairs = _run_inputs(_table("uniform", n, k), cfg)
        graph = graph._replace(by_dst=pk.edges_by_destination(graph.neighbors, hidden, jnp.bfloat16))
        per_block = n * k // blocks
        assert graph.by_dst.perm.shape == (blocks, per_block), n
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x), sharding=chip),
                              (state, graph, pairs))
        compiled = jax.jit(train_gnn.make_train_step()).lower(*shapes).compile()
        text = compiled.as_text()
        entry = text[text.index("ENTRY "):]
        assert len(re.findall(r" custom-call\(.*tpu_custom_call", entry)) == 3, "a segmented sum a layer"
        written = re.findall(rf"^\s*\S+ = bf16\[{n},{k},{hidden}\]\{{([\d,]+)\S* fusion\(", entry, re.M)
        assert written and set(written) == {"2,0,1"}, set(written)  # K major-most wherever a fusion writes it
        copies = re.findall(rf"^\s*(\S+ = bf16\[{n},{k},{hidden}\]\S* copy\(.*?op_name=\S+)", entry, re.M)
        assert not copies, copies
        block = rf"bf16\[{per_block},{hidden}\]"
        together = re.findall(rf"^\s*(\S+) = \([^)]*{block}[^)]*{block}.*? fusion\(", entry, re.M)
        assert not together, together
        # nor a copy of the [N*K, H] view the reorder reads its blocks from (the forward's gathers write
        # that shape too: only the backward's ops, `transpose(jvp(...))`, count)
        viewed = [line.split(" = ")[0].strip() for line in entry.splitlines()
                  if re.match(rf"\s*\S+ = \(?[^=]*bf16\[{n * k},{hidden}\][^=]* (?:copy|fusion)\(", line)
                  and "transpose(jvp(" in line]
        assert not viewed, viewed
        defined =dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+)", entry, re.M))
        computations = _computations(text)
        tables = [defined[operand] for operand, body in re.findall(
            rf"= {block}\S* fusion\((%[^,)]+).*?, kind=kCustom, calls=(%[^,\s]+)", entry)
            if " gather(" in computations[body]]
        assert len(tables) == 3 * blocks, (n, len(tables))  # a reorder gather a block and layer
        assert all(t.endswith("S(1)}") for t in tables), (n, tables)  # each out of VMEM
        assert compiled.memory_analysis().temp_size_in_bytes < temp_cap, n
