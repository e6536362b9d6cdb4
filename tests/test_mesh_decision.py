"""Which mesh a GNN training run is placed on (parallel/mesh.mesh_for_run:
every device on `data`), and the row-sharded run that gives: the served scan
program on a `{data: 4}` mesh against the plain float32 reference and against
the one-device program, what its collectives move, a node count the devices
do not divide, and the decision in the run manifest. And the gather's VJP on
that mesh where the kernel runs (a sorted table per row shard,
ops.neighbor_agg_pallas.EdgesByShard): these CPU devices get no table from
the program, so the tests that want one say so (`PLATFORM`) and interpret the
kernel. (Fourteen tests: tests/test_gather_vjp.py's docstring says why the
count matters.)"""

from __future__ import annotations

import asyncio
import copy
import json
import re
import sys
from functools import partial
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dragonfly2_tpu.models.graphsage import TopoGraph, TopoScorer
from dragonfly2_tpu.ops import neighbor_agg_pallas as pk
from dragonfly2_tpu.ops.neighbor_agg import neighbor_gather
from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer import synthetic, train_gnn
from dragonfly2_tpu.trainer.synthetic import PairBatch

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402  (the benchmark's plain float32 reference: imports nothing of the program)
import telemetry_gen  # noqa: E402


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_every_device_of_a_run_goes_on_data(n_devices):
    mesh, decision = meshlib.mesh_for_run(jax.devices()[:n_devices])
    assert dict(mesh.shape) == {"data": n_devices}
    assert decision == {"rule": "one_device" if n_devices == 1 else "rows_over_data", "devices": n_devices}
    if n_devices == len(jax.devices()):  # no devices named: all there are
        assert meshlib.mesh_for_run()[0] == mesh


# ---- the row-sharded run at a tiny size ----

TINY = json.loads((BENCH / "tests" / "data" / "tiny" / "tiny.json").read_text())
STEPS = TINY["optimizer"]["gnn"]["steps_per_call"]


def _dataset(config: dict) -> dict:
    cl = config["cluster"]
    records = telemetry_gen.generate_for(cl, 2_147_483_777)
    return reference.build_dataset(*records, num_neighbors=config["model"]["num_neighbors"], uploads=1,
                                   chunk_rows=cl["chunk_rows"], pool_rows_cap=cl["pool_rows_cap"])


@pytest.fixture(scope="module")
def dataset() -> dict:
    return _dataset(TINY)


def _tiny_inputs(dataset: dict, config: dict = TINY) -> tuple:
    """(trainer configuration, graph, pair pool) of the tiny cell over the reference's dataset."""
    m = config["model"]
    cfg = train_gnn.GNNTrainConfig(hidden=m["hidden"], embed_dim=m["embed_dim"], num_layers=m["num_layers"],
                                   batch_size=m["pair_batch"])
    graph = TopoGraph(*(dataset[k] for k in ("node_feats", "neighbors", "mask", "edge_feats")))
    pairs = PairBatch(*(dataset["pairs"][k] for k in ("child", "parent", "feats", "label")))
    return cfg, graph, pairs


def _program(inputs: tuple, mesh, dtype, first_rows=None) -> tuple:
    """The served scan program over `inputs` (configuration, graph, pairs) on
    `mesh`, placed and compiled the way `train_async` does; `dtype` is the
    model's compute dtype (the trainer's is bfloat16). Returns (placed graph,
    compiled step's text, the first call's losses, gradient norms), and with
    `first_rows` (the pool rows of the first step's batch) the norm of every
    leaf of that step's gradient."""
    opt = TINY["optimizer"]["gnn"]
    cfg, graph, pairs = inputs
    state = train_gnn.init_state(cfg, graph, opt["init_seed"])
    model = TopoScorer(hidden=cfg.hidden, embed_dim=cfg.embed_dim, num_layers=cfg.num_layers, dtype=dtype)
    state = state.replace(apply_fn=model.apply)
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        state, graph, pairs, mesh, batch_size=cfg.batch_size, steps_per_call=STEPS)
    leaves = None
    if first_rows is not None:  # before the call: it donates the state
        grads = jax.jit(jax.grad(partial(train_gnn.loss_fn, state.apply_fn)))(
            state.params, g, PairBatch(*(a[first_rows] for a in pool)))
        leaves = _leaf_norms(grads)
    _, sub = jax.random.split(jax.random.PRNGKey(opt["sample_seed"]))
    text = multi_step.lower(state, g, pool, sub).compile().as_text()
    _, (losses, gnorms) = multi_step(state, g, pool, sub)
    return Run(g, text, np.asarray(losses, np.float64), np.asarray(gnorms, np.float64), leaves)


class Run(NamedTuple):
    g: TopoGraph | None
    text: str | None
    losses: np.ndarray
    gnorms: np.ndarray
    leaves: np.ndarray | None = None


def _leaf_norms(grads) -> np.ndarray:
    return np.asarray([jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree.leaves(grads)], np.float64)


@pytest.fixture(scope="module")
def runs(dataset) -> dict:
    data4 = meshlib.mesh_for_run(jax.devices()[:4])[0]
    one = meshlib.mesh_for_run(jax.devices()[:1])[0]
    ref = reference.follow_steps(TINY, dataset, STEPS)
    inputs = _tiny_inputs(dataset)
    return {
        "reference": Run(None, None, np.asarray(ref["loss"]), np.asarray(ref["grad_norm"])),
        "data4.f32": _program(inputs, data4, jnp.float32),
        "one.f32": _program(inputs, one, jnp.float32),
        "data4.bf16": _program(inputs, data4, jnp.bfloat16),
    }


def _gaps(got: Run, want: Run) -> tuple[float, float]:
    return (float(np.max(np.abs(got.losses - want.losses) / np.abs(want.losses))),
            float(np.max(np.abs(got.gnorms - want.gnorms) / np.abs(want.gnorms))))


# float32 on both sides: what is left is the order of summation (a row shard
# sums its own rows, the reduce-scatter adds the shards' partial sums) and
# flax's against the reference's formulation of the same equations, a few
# units in the last place carried through ten optimizer steps. The trainer's
# own bfloat16 reads two orders of magnitude over it (asserted below), so a
# program that computed in a lower precision than it says would not pass.
F32_TOLERANCE = 2e-5


def test_ten_steps_on_a_data4_mesh_follow_the_float32_reference(runs):
    loss_gap, gnorm_gap = _gaps(runs["data4.f32"], runs["reference"])
    assert loss_gap < F32_TOLERANCE and gnorm_gap < 10 * F32_TOLERANCE, (loss_gap, gnorm_gap)
    # the tolerance is tight enough that bfloat16 in float32's place fails it
    low_loss, low_gnorm = _gaps(runs["data4.bf16"], runs["reference"])
    assert low_loss > 5 * F32_TOLERANCE and low_gnorm > 50 * F32_TOLERANCE, (low_loss, low_gnorm)


def test_ten_steps_on_a_data4_mesh_follow_the_one_device_program(runs):
    loss_gap, gnorm_gap = _gaps(runs["data4.f32"], runs["one.f32"])
    assert loss_gap < F32_TOLERANCE and gnorm_gap < 10 * F32_TOLERANCE, (loss_gap, gnorm_gap)
    g = runs["data4.f32"][0]
    rows = pk.placed_rows(TINY["cluster"]["hosts"], 4) // 4  # a shard of the rung the 64 hosts are placed at
    assert {s.data.shape[0] for s in g.neighbors.addressable_shards} == {rows}
    assert g.by_dst is None  # no TPU here, so no table: the gather's VJP is the derived one


_COLLECTIVE = re.compile(
    r"= (\([^=]*\)|\S+) (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def test_no_collective_of_the_data4_step_moves_a_message_tensor(runs, kernel_runs):
    """The partitioner all-gathers `u[N, H]` forward and reduces the `[N, H]`
    cotangent backward; an exchange of `[N/dp, K, H]` (the gathered rows or
    their cotangent) would be K/dp times the bytes. With a table per row shard
    the program says so itself (`all_gather` under `shard_map`, the shards'
    `[N, H]` sums added over `data`): a layer still moves `[N, H]` out once
    and back once. (The CPU's compiler merges all-reduces into tuples and
    leaves a reduce-scatter as an all-reduce whose rows are sliced; the TPU's
    fuses the two.)"""
    for config, run in [(TINY, runs["data4.bf16"]), (KERNEL_TINY, kernel_runs["data4.tables"])]:
        m, n = config["model"], pk.placed_rows(config["cluster"]["hosts"], 4)  # the placed rows
        table, slots = n * m["hidden"], n // 4 * m["num_neighbors"]
        moved: dict[str, list[tuple]] = {}
        # (a long tuple's own `/*index=5*/` comments would end the match of its shapes)
        for result, kind in _COLLECTIVE.findall(re.sub(r"/\*index=\d+\*/", "", run.text)):
            moved.setdefault(kind, []).extend(
                tuple(int(d) for d in dims.split(",") if d) for dims in _SHAPE.findall(result))
        # a shard's [N/dp, K, H] however it is folded: rows of H, as many as the shard has slots
        assert not [dims for found in moved.values() for dims in found
                    if dims[-1:] == (m["hidden"],) and np.prod(dims[:-1]) >= slots], moved
        gathered = sum(int(np.prod(dims)) >= table for dims in moved["all-gather"])  # u, whole, on every device
        reduced = sum(int(np.prod(dims)) == table  # the cotangent's sums, among the gradients'
                      for dims in moved.get("all-reduce", []) + moved.get("reduce-scatter", []))
        assert gathered >= 1 and reduced >= 1, moved
        if config is KERNEL_TINY:  # the program's own exchange: [N, H] out once a layer and back once
            states = (n, m["hidden"])
            assert moved["all-gather"].count(states) == moved["all-reduce"].count(states) == m["num_layers"], moved


# ---- the same run where the kernel sums the gather's VJP: a sorted table per row shard ----

# the tiny cell at the smallest shapes the kernel takes: whole lanes, one tile of destinations
KERNEL_TINY = copy.deepcopy(TINY)
KERNEL_TINY["model"].update(hidden=128, embed_dim=64)
KERNEL_TINY["cluster"].update(hosts=256, probes=6144, downloads=1024)
# bfloat16 against float32 and against itself in another order of summation:
# 2^-8 a rounding; the runs read 1.5e-3 / 2.4e-3 (loss, gradient norm) and
# 2.7e-3 by the worst leaf against the reference, 1.6e-4 / 1.0e-3 / 1.0e-3
# against each other. A shard's sums left out, or added twice, is O(1) in
# every `msg_nbr` kernel's and every earlier leaf's norm.
BF16_TOLERANCE = 1e-2
SAME_DTYPE_TOLERANCE = 4e-3


def _kernel_as_xla_ops():
    """Pallas's HLO interpreter for the kernel (any truthy value that is not
    the TPU interpreter's parameters selects it): the grid as a loop of plain
    XLA ops, which the four virtual devices run under `shard_map` like any
    other. The TPU interpreter (tests/test_gather_vjp.py) simulates memories
    through host callbacks, and four devices' callbacks at once hung the CPU
    client here, most runs."""
    return pltpu.force_tpu_interpret_mode(True)


@pytest.fixture(scope="module")
def kernel_runs() -> dict:
    """The placed bfloat16 program at KERNEL_TINY on one device (no table: the
    CPU) and on `{data: 4}` with the table per row shard a TPU host would get
    (the test's word for it, `PLATFORM`; the kernel interpreted), and the
    float32 reference: ten steps, and the first step's gradient leaf by leaf."""
    dataset = _dataset(KERNEL_TINY)
    m, opt = KERNEL_TINY["model"], KERNEL_TINY["optimizer"]["gnn"]
    inputs = _tiny_inputs(dataset, KERNEL_TINY)
    assert inputs[1].neighbors.shape == (256, 16)
    rows = reference.batch_indices_of_step(opt["sample_seed"], STEPS, 1, m["pair_batch"], len(dataset["pairs"]["child"]))
    ref = reference.follow_steps(KERNEL_TINY, dataset, STEPS)
    ref_grads = jax.grad(reference.loss_fn)(
        jax.tree.map(jnp.asarray, reference.init_params(m, opt["init_seed"])),
        {k: jnp.asarray(dataset[k]) for k in ("node_feats", "neighbors", "mask", "edge_feats")},
        {k: jnp.asarray(v)[rows] for k, v in dataset["pairs"].items()}, num_layers=m["num_layers"])
    out = {
        "reference": Run(None, None, np.asarray(ref["loss"]), np.asarray(ref["grad_norm"]), _leaf_norms(ref_grads)),
        "one.derived": _program(inputs, meshlib.mesh_for_run(jax.devices()[:1])[0], jnp.bfloat16, rows),
    }
    with pytest.MonkeyPatch.context() as patch, _kernel_as_xla_ops():
        patch.setattr(pk, "PLATFORM", "cpu")
        out["data4.tables"] = _program(inputs, meshlib.mesh_for_run(jax.devices()[:4])[0], jnp.bfloat16, rows)
    return out


def test_ten_steps_with_a_table_per_row_shard_follow_the_reference_leaf_by_leaf(kernel_runs):
    """A backward that changed hands is judged by every leaf of the first
    step's gradient, not by the global norm alone (ROADMAP R10): the `{data:
    4}` program with the kernel's sums against the float32 reference and
    against the one-device program with `jnp.take`'s VJP."""
    tables, one, ref = kernel_runs["data4.tables"], kernel_runs["one.derived"], kernel_runs["reference"]
    assert one.g.by_dst is None and isinstance(tables.g.by_dst, pk.EdgesByShard)
    perm = tables.g.by_dst.tables.perm  # stacked by shard, a chip its own: 64 rows x 16 slots in one block
    assert perm.shape == (4, 1, 1024) and {s.data.shape for s in perm.addressable_shards} == {(1, 1, 1024)}
    assert len(ref.leaves) == len(one.leaves) == len(tables.leaves) == 34
    for got, want, tolerance in [(tables, ref, BF16_TOLERANCE), (one, ref, BF16_TOLERANCE),
                                 (tables, one, SAME_DTYPE_TOLERANCE)]:
        loss_gap, gnorm_gap = _gaps(got, want)
        leaf_gap = float(np.max(np.abs(got.leaves - want.leaves) / want.leaves))
        assert max(loss_gap, gnorm_gap, leaf_gap) < tolerance, (loss_gap, gnorm_gap, leaf_gap)
    # the tolerances are tight enough that a float32 program's readings differ from bfloat16's
    assert _gaps(tables, ref)[0] > 5 * F32_TOLERANCE


def test_the_per_shard_vjp_on_a_four_device_mesh_is_jnp_takes(monkeypatch):
    """`neighbor_gather` over a table per row shard against `jnp.take` on one
    device, for a graph with a hub (a quarter of all slots point at row 3),
    padded slots at row 0 and a tile of destinations nobody points at: the
    forward bit for bit, the VJP within bfloat16's order of summation (a
    chip's float32 sums rounded once, the four chips' added in bfloat16),
    rows and all sharded over `data` as they came."""
    from test_gather_vjp import _hub_table

    monkeypatch.setattr(pk, "PLATFORM", "cpu")  # the test's word: these CPU devices take the (interpreted) kernel
    mesh = meshlib.mesh_for_run(jax.devices()[:4])[0]
    rows = meshlib.batch_sharding(mesh)
    # a shard's [N/4, K, H] in blocks of 12 K slices, of 8, and of one and a half (straddling a slice's edge)
    for n, k, width, blocks in [(1024, 12, 128, 1), (512, 16, 256, 2), (1024, 6, 128, 4)]:
        case = (n, k, width, blocks)
        monkeypatch.setattr(pk, "BLOCK_BYTES", n // 4 * k * width * 2 // blocks)
        nbr = _hub_table(n, k, seed=4)
        rng = np.random.default_rng(5)
        h = jnp.asarray(rng.normal(size=(n, width)), jnp.bfloat16)
        g = jnp.asarray(rng.normal(size=(n, k, width)), jnp.bfloat16)
        tables, reason = pk.gather_vjp_tables(nbr, width, h.dtype, mesh)
        assert reason == "" and tables.tables.perm.shape == (4, blocks, n // 4 * k // blocks), case
        report = pk.gather_vjp_report(tables, nbr.shape, width, h.dtype, mesh)
        assert report["path"] == "sorted_kernel" and report["shards"] == 4 and report["blocks"] == blocks, case
        live = np.asarray(tables.tables.live).ravel()
        assert report["live_windows"] == {"least": live.min(), "most": live.max()}, case
        placed = jax.device_put((h, jnp.asarray(nbr), tables, g), (rows, rows, jax.tree.map(lambda _: rows, tables), rows))
        with _kernel_as_xla_ops():
            out, vjp = jax.vjp(lambda x: neighbor_gather(x, *placed[1:3]), placed[0])
            got = vjp(placed[3])[0]
        assert out.sharding.is_equivalent_to(rows, 3) and got.sharding.is_equivalent_to(rows, 2), case
        np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(jnp.take(h, nbr, axis=0), np.float32))
        want = np.asarray(jax.vjp(lambda x: jnp.take(x, nbr, axis=0), h.astype(jnp.float32))[1](g.astype(jnp.float32))[0])
        got = np.asarray(got.astype(jnp.float32))
        np.testing.assert_allclose(got, want, rtol=2.0 ** -6, atol=2.0 ** -6 * np.abs(want).max(), err_msg=str(case))
        assert not got[3 * n // 4:].any(), case  # nobody points there


def test_padding_rows_are_copies_of_node_zero():
    g = synthetic.make_cluster(num_nodes=10, num_neighbors=4, num_pairs=8, seed=1).graph
    assert train_gnn.pad_graph(g, 10) is g
    padded = train_gnn.pad_graph(g, 16)
    for got, want in zip(padded[:4], g[:4]):
        assert got.shape == (16,) + want.shape[1:] and got.dtype == want.dtype
        np.testing.assert_array_equal(got[:10], want)
        np.testing.assert_array_equal(got[10:], np.repeat(want[:1], 6, axis=0))
    assert padded.by_dst is None and int(padded.neighbors.max()) < 10  # no slot names a padding row


@pytest.mark.parametrize("hosts,n_devices", [(10, 8), (30, 4)])
def test_a_node_count_the_devices_do_not_divide_trains_as_on_one_device(hosts, n_devices):
    """Padding rows used to be rows of zeros: an all-zero embedding, whose L2
    norm has no gradient, and every parameter read NaN from the second step.
    As copies of node 0 they have the gradient zero, and the run is the
    one-device program's."""
    m = TINY["model"]
    cluster = synthetic.make_cluster(num_nodes=hosts, num_neighbors=m["num_neighbors"], num_pairs=512, seed=hosts)
    cfg = train_gnn.GNNTrainConfig(hidden=m["hidden"], embed_dim=m["embed_dim"], num_layers=m["num_layers"],
                                   batch_size=m["pair_batch"])
    inputs = (cfg, cluster.graph, cluster.pairs)
    sharded = _program(inputs, meshlib.mesh_for_run(jax.devices()[:n_devices])[0], jnp.float32)
    one = _program(inputs, meshlib.mesh_for_run(jax.devices()[:1])[0], jnp.float32)
    assert sharded[0].neighbors.shape[0] == one[0].neighbors.shape[0] == pk.placed_rows(hosts, n_devices) > hosts
    assert np.all(np.isfinite(sharded[2])) and np.all(np.isfinite(sharded[3]))
    loss_gap, gnorm_gap = _gaps(sharded, one)
    assert loss_gap < F32_TOLERANCE and gnorm_gap < 10 * F32_TOLERANCE, (loss_gap, gnorm_gap)


def test_a_run_is_placed_on_the_mesh_the_one_decision_gives(monkeypatch):
    """`train_async` asks `mesh_for_run` once a run, with no devices named,
    and places the run on what it returns: the manifest's `placement` is that
    mesh, that decision and what placement read back, with no other entry."""
    from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

    asked = []

    def decide(devices=None, run=meshlib.mesh_for_run):
        asked.append(devices)
        return run(jax.devices()[:2])

    monkeypatch.setattr(meshlib, "mesh_for_run", decide)
    m = TINY["model"]
    cluster = synthetic.make_cluster(num_nodes=30, num_neighbors=m["num_neighbors"], num_pairs=256, seed=30)
    cfg = train_gnn.GNNTrainConfig(hidden=m["hidden"], embed_dim=m["embed_dim"], num_layers=m["num_layers"],
                                   batch_size=m["pair_batch"])
    sink = TrainRunTelemetry("gnn")
    asyncio.run(train_gnn.train_async(cfg, cluster.graph, cluster.pairs, steps=2, steps_per_call=2, telemetry=sink))
    placement = sink.placement
    assert asked == [None]
    assert set(placement) == {"mesh", "decision", "graph", "batch_rows_per_device", "gather_vjp"}
    assert placement["mesh"] == {"data": 2}
    assert placement["decision"] == {"rule": "rows_over_data", "devices": 2, "hosts": 30, "rows": 256,
                                     "pad_pct": round(100 * (256 - 30) / 30, 2)}
    assert placement["graph"]["per_device_bytes"] == [placement["graph"]["bytes"] // 2] * 2
    assert placement["batch_rows_per_device"] * 2 == cfg.batch_size


def test_the_run_manifest_names_the_decision(tmp_path):
    """Through the service on the 8 virtual devices, with a host count they do
    not divide: the manifest's placement says who chose the mesh, node rows
    span every device, and the artifacts are whole and finite."""
    from dragonfly2_tpu.telemetry import TelemetryStorage
    from dragonfly2_tpu.telemetry.records import pack_records
    from dragonfly2_tpu.trainer import artifacts, train_mlp
    from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
    from test_trainer_service import _fill_telemetry

    svc = TrainerService(TrainerConfig(
        model_dir=str(tmp_path / "models"),
        mlp=train_mlp.MLPTrainConfig(hidden=(16, 16), steps=20, batch_size=64),
        gnn=train_gnn.GNNTrainConfig(hidden=16, embed_dim=8, num_layers=2, batch_size=64, warmup_steps=2),
        gnn_steps=8, gnn_steps_per_call=4,
    ))
    store = TelemetryStorage(tmp_path / "telemetry")
    _fill_telemetry(store, n_hosts=30, n_rows=600)

    async def body():
        token = (await svc.train_open({"hostname": "s"}))["token"]
        await svc.train_chunk({"token": token, "kind": "downloads", "data": pack_records(store.downloads.load_all())})
        await svc.train_chunk({"token": token, "kind": "probes", "data": pack_records(store.probes.load_all())})
        await svc.train_close({"token": token})
        await svc.wait_idle()

    asyncio.run(body())
    assert svc.trains_succeeded == 1, svc.last_result
    gnn = svc.run_history[-1]["models"]["gnn"]
    placement = gnn["placement"]
    n = len(jax.devices())
    assert placement["mesh"] == {"data": n}
    hosts = svc.run_history[-1]["dataset"]["nodes"]
    assert placement["decision"] == {"rule": "rows_over_data", "devices": n, "hosts": hosts, "rows": 256,
                                     "pad_pct": round(100 * (256 - hosts) / hosts, 2)}
    assert all(np.isfinite(gnn["evaluation"][k]) for k in ("final_loss",))
    graph = placement["graph"]
    assert len(graph["per_device_bytes"]) == n and max(graph["per_device_bytes"]) * n == graph["bytes"]
    assert placement["gather_vjp"]["path"] == "derived"  # what the one rule returned, and why
    assert placement["gather_vjp"]["reason"] == "cpu devices: the kernel compiles for tpu alone"
    # the export reads parameters that live on every device and a graph that was never placed
    artifact = Path(gnn["artifact"])
    assert {"params.msgpack", "graph.npz", "config.json"} <= {p.name for p in artifact.iterdir()}
    assert svc.last_result["gnn"]["native_export_error"] is None
    _model, params = artifacts.load_gnn(artifact)
    assert all(np.all(np.isfinite(leaf)) for leaf in jax.tree.leaves(params))
