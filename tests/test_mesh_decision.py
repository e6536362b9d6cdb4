"""Which mesh a GNN training run is placed on (parallel/mesh.mesh_for_run:
every device on `data`), and the row-sharded run that gives: the served scan
program on a `{data: 4}` mesh against the plain float32 reference and against
the one-device program, what its collectives move, a node count the devices
do not divide, and the decision in the run manifest."""

from __future__ import annotations

import asyncio
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models.graphsage import TopoGraph, TopoScorer
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
    assert dict(mesh.shape) == {"data": n_devices, "model": 1}
    assert decision == {"rule": "one_device" if n_devices == 1 else "rows_over_data", "devices": n_devices}
    if n_devices == len(jax.devices()):  # no devices named: all there are
        assert meshlib.mesh_for_run()[0] == mesh


# ---- the row-sharded run at a tiny size ----

TINY = json.loads((BENCH / "tests" / "data" / "tiny" / "tiny.json").read_text())
STEPS = TINY["optimizer"]["gnn"]["steps_per_call"]


@pytest.fixture(scope="module")
def dataset() -> dict:
    cl = TINY["cluster"]
    records = telemetry_gen.generate_for(cl, 2_147_483_777)
    return reference.build_dataset(*records, num_neighbors=TINY["model"]["num_neighbors"], uploads=1,
                                   chunk_rows=cl["chunk_rows"], pool_rows_cap=cl["pool_rows_cap"])


def _tiny_inputs(dataset: dict) -> tuple:
    """(trainer configuration, graph, pair pool) of the tiny cell over the reference's dataset."""
    m = TINY["model"]
    cfg = train_gnn.GNNTrainConfig(hidden=m["hidden"], embed_dim=m["embed_dim"], num_layers=m["num_layers"],
                                   batch_size=m["pair_batch"])
    graph = TopoGraph(*(dataset[k] for k in ("node_feats", "neighbors", "mask", "edge_feats")))
    pairs = PairBatch(*(dataset["pairs"][k] for k in ("child", "parent", "feats", "label")))
    return cfg, graph, pairs


def _program(inputs: tuple, mesh, dtype) -> tuple:
    """The served scan program over `inputs` (configuration, graph, pairs) on
    `mesh`, placed and compiled the way `train_async` does; `dtype` is the
    model's compute dtype (the trainer's is bfloat16). Returns (placed graph,
    compiled step's text, the first call's losses, gradient norms)."""
    opt = TINY["optimizer"]["gnn"]
    cfg, graph, pairs = inputs
    state = train_gnn.init_state(cfg, graph, opt["init_seed"])
    model = TopoScorer(hidden=cfg.hidden, embed_dim=cfg.embed_dim, num_layers=cfg.num_layers, dtype=dtype)
    state = state.replace(apply_fn=model.apply)
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        state, graph, pairs, mesh, batch_size=cfg.batch_size, steps_per_call=STEPS)
    _, sub = jax.random.split(jax.random.PRNGKey(opt["sample_seed"]))
    text = multi_step.lower(state, g, pool, sub).compile().as_text()
    _, (losses, gnorms) = multi_step(state, g, pool, sub)
    return g, text, np.asarray(losses, np.float64), np.asarray(gnorms, np.float64)


@pytest.fixture(scope="module")
def runs(dataset) -> dict:
    data4 = meshlib.make_mesh(jax.devices()[:4], model_parallel=1)
    one = meshlib.make_mesh(jax.devices()[:1], model_parallel=1)
    ref = reference.follow_steps(TINY, dataset, STEPS)
    inputs = _tiny_inputs(dataset)
    return {
        "reference": (np.asarray(ref["loss"]), np.asarray(ref["grad_norm"])),
        "data4.f32": _program(inputs, data4, jnp.float32),
        "one.f32": _program(inputs, one, jnp.float32),
        "data4.bf16": _program(inputs, data4, jnp.bfloat16),
    }


def _gaps(got: tuple, want: tuple) -> tuple[float, float]:
    return (float(np.max(np.abs(got[-2] - want[-2]) / np.abs(want[-2]))),
            float(np.max(np.abs(got[-1] - want[-1]) / np.abs(want[-1]))))


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
    rows = TINY["cluster"]["hosts"] // 4
    assert {s.data.shape[0] for s in g.neighbors.addressable_shards} == {rows}
    assert g.by_dst is None  # the gather's VJP stays the derived one on a mesh


_COLLECTIVE = re.compile(
    r"= (\([^=]*\)|\S+) (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def test_no_collective_of_the_data4_step_moves_a_message_tensor(runs):
    """The partitioner all-gathers `u[N, H]` forward and reduces the `[N, H]`
    cotangent backward; an exchange of `[N/dp, K, H]` (the gathered rows or
    their cotangent) would be K/dp times the bytes."""
    m, n = TINY["model"], TINY["cluster"]["hosts"]
    table, message = n * m["hidden"], n // 4 * m["num_neighbors"] * m["hidden"]
    assert message > table
    sizes: dict[str, int] = {}
    for result, kind in _COLLECTIVE.findall(runs["data4.bf16"][1]):
        for dims in _SHAPE.findall(result):
            elements = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            sizes[kind] = max(sizes.get(kind, 0), elements)
    assert sizes.get("all-gather", 0) >= table, sizes           # u, whole, on every device
    assert {"all-reduce", "reduce-scatter"} & set(sizes), sizes   # the cotangent's and the gradients' sums
    # padding to the partitioner's tiles aside, nothing larger than the [N, H] table moves
    assert max(sizes.values()) <= 1.1 * table < message, sizes


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
    assert sharded[0].neighbors.shape[0] == meshlib.pad_to_multiple(hosts, n_devices) > hosts
    assert np.all(np.isfinite(sharded[2])) and np.all(np.isfinite(sharded[3]))
    loss_gap, gnorm_gap = _gaps(sharded, one)
    assert loss_gap < F32_TOLERANCE and gnorm_gap < 10 * F32_TOLERANCE, (loss_gap, gnorm_gap)


def test_one_device_lowers_to_the_program_a_hand_built_mesh_gives(dataset):
    """On one device the decision is `{data: 1, model: 1}`, `make_mesh`'s
    answer before there was a decision: the lowered scan step is the same
    text."""
    cfg, graph, pairs = _tiny_inputs(dataset)
    decided, decision = meshlib.mesh_for_run(jax.devices()[:1])
    assert decision["rule"] == "one_device" and dict(decided.shape) == {"data": 1, "model": 1}
    texts = []
    for mesh in (decided, meshlib.make_mesh(jax.devices()[:1])):
        state, g, pool, multi_step = train_gnn.shard_for_training_scan(
            train_gnn.init_state(cfg, graph, 0), graph, pairs, mesh,
            batch_size=cfg.batch_size, steps_per_call=STEPS)
        texts.append(multi_step.lower(state, g, pool, jax.random.PRNGKey(0)).as_text())
    assert texts[0] == texts[1]


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
    assert placement["mesh"] == {"data": n, "model": 1}
    assert placement["decision"] == {"rule": "rows_over_data", "devices": n}
    assert all(np.isfinite(gnn["evaluation"][k]) for k in ("final_loss",))
    graph = placement["graph"]
    assert len(graph["per_device_bytes"]) == n and max(graph["per_device_bytes"]) * n == graph["bytes"]
    assert placement["gather_vjp"]["path"] == "derived"
    # the export reads parameters that live on every device and a graph that was never placed
    artifact = Path(gnn["artifact"])
    assert {"params.msgpack", "graph.npz", "config.json"} <= {p.name for p in artifact.iterdir()}
    assert svc.last_result["gnn"]["native_export_error"] is None
    _model, params = artifacts.load_gnn(artifact)
    assert all(np.all(np.isfinite(leaf)) for leaf in jax.tree.leaves(params))
