"""The ladder of row counts a host count is placed at
(ops.neighbor_agg_pallas.placed_rows): its properties over every host count a
trainer pool admits, that the one rule for the gather's VJP takes every rung,
that padding to a rung changes nothing a run computes or publishes (float32
against the same run unpadded: losses, every leaf's gradient and update, the
exported embeddings), and that a host count that moves inside a rung is served
the kept program."""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models.graphsage import TopoScorer
from dragonfly2_tpu.ops import neighbor_agg_pallas as pk
from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer import artifacts, synthetic, train_gnn
from dragonfly2_tpu.trainer.synthetic import PairBatch
from test_mesh_decision import F32_TOLERANCE
from test_trainer import KEPT_CFG, nothing_kept, one_device  # noqa: F401  (a fixture)

POOL_MAX_HOSTS = 65_536  # TrainerConfig.pool_max_hosts: no run is given more
SHARDS = [1, 4, 8]


@pytest.fixture(scope="module")
def rungs() -> dict[int, np.ndarray]:
    """placed_rows(n, shards) for n = 0 .. POOL_MAX_HOSTS, by shards."""
    return {s: np.array([pk.placed_rows(n, s) for n in range(POOL_MAX_HOSTS + 1)]) for s in SHARDS}


@pytest.mark.parametrize("shards", SHARDS)
def test_a_host_count_is_placed_at_or_above_itself_on_whole_tiles_and_shards(rungs, shards):
    rows, hosts = rungs[shards][8:], np.arange(8, POOL_MAX_HOSTS + 1)
    assert np.all(rows >= hosts) and not np.any(rows % pk.TILE_DST) and not np.any(rows % shards)
    assert np.all(np.diff(rows) >= 0)  # monotone: more hosts are never placed at fewer rows


@pytest.mark.parametrize("shards", SHARDS)
def test_padding_is_at_most_an_eighth_from_2048_hosts_up(rungs, shards):
    rows, hosts = rungs[shards][2048:], np.arange(2048, POOL_MAX_HOSTS + 1)
    assert np.max((rows - hosts) / hosts) <= 0.125
    # eight rungs an octave: a count that moves by less than 1/16 crosses at most one
    assert len(np.unique(rows[(hosts > 32_768) & (hosts <= 65_536)])) == 8


@pytest.mark.parametrize("shards", SHARDS)
def test_a_rung_is_placed_as_it_is(rungs, shards):
    for rung in np.unique(rungs[shards]):
        assert pk.placed_rows(int(rung), shards) == rung


@pytest.mark.parametrize("hosts,rows", [
    (32_768, 32_768), (65_536, 65_536),  # the accepted cells' clusters stay where they were
    (40_000, 40_960), (39_200, 40_960), (39_600, 40_960), (41_000, 45_056),  # gnn-40k-512, and a count that moves
    (8, 256), (256, 256), (257, 512), (2_049, 2_304), (32_769, 36_864),
])
def test_named_host_counts(hosts, rows):
    assert pk.placed_rows(hosts) == pk.placed_rows(hosts, 4) == pk.placed_rows(hosts, 8) == rows


@pytest.mark.parametrize("shards", [3, 6, 7])
def test_a_data_axis_that_is_no_power_of_two_gets_whole_shards_too(shards):
    for hosts in range(8, POOL_MAX_HOSTS, 97):
        rows = pk.placed_rows(hosts, shards)
        assert rows >= hosts and rows % shards == 0 and rows % pk.TILE_DST == 0 and pk.placed_rows(rows, shards) == rows


@pytest.mark.parametrize("width", [256, 512])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_one_rule_takes_every_rung_within_the_tables_reach(rungs, monkeypatch, shards, width):
    """`why_derived` is "" for every rung a pool can be placed at, at the
    widths the trainer ships, on one device and on `{data: 4}` (these CPU
    devices stand in for chips: the test's word, `PLATFORM`)."""
    monkeypatch.setattr(pk, "PLATFORM", "cpu")
    mesh = meshlib.mesh_for_run(jax.devices()[:shards])[0]
    reach = pk.MAX_BLOCKS * pk.BLOCK_BYTES // (16 * width * 2) * shards  # rows whose shard's slots fill MAX_BLOCKS blocks
    assert reach >= POOL_MAX_HOSTS
    for rung in np.unique(rungs[shards][8:]):
        assert pk.why_derived((int(rung), 16), width, jnp.bfloat16, mesh) == "", rung
        assert pk._table_blocks(int(rung) // shards * 16, int(rung), width, jnp.bfloat16)[0] > 0
    # and what placement handed it before: a host count as it came
    assert "not whole tiles" in pk.why_derived((40_000, 16), width, jnp.bfloat16, mesh)
    if width == 512 and shards == 1:  # from 32,768 rows on a rung is whole blocks of BLOCK_BYTES
        assert pk._table_blocks(40_960 * 16, 40_960, 512, jnp.bfloat16) == (20, "")


# ---- a run at a host count off the grid against the same run unpadded ----

HOSTS = 300  # placed at 512 rows; 300 = 4 x 75, so the unpadded run has whole row shards too
CFG = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2, batch_size=64, warmup_steps=2)
STEPS = 10


def _float32_run(cluster, mesh, monkeypatch, tmp_path) -> dict:
    """Ten steps of the served scan program in float32 over `cluster` on
    `mesh`, then the native export: losses, the first batch's gradient and
    the ten steps' update leaf by leaf, the placed rows and the embeddings
    `save_native` hands the exporter."""
    model = TopoScorer(hidden=CFG.hidden, embed_dim=CFG.embed_dim, num_layers=CFG.num_layers, dtype=jnp.float32)
    state = train_gnn.init_state(CFG, cluster.graph, 0).replace(apply_fn=model.apply)
    before = jax.tree.map(np.asarray, state.params)
    train_gnn._kept.clear()
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        state, cluster.graph, cluster.pairs, mesh, batch_size=CFG.batch_size, steps_per_call=STEPS)
    first = PairBatch(*(a[: CFG.batch_size] for a in pool))
    grads = jax.jit(jax.grad(lambda p: train_gnn.loss_fn(state.apply_fn, p, g, first)))(state.params)
    grads = jax.tree.map(np.asarray, grads)  # before the call: it donates the state
    state, (losses, _) = multi_step(state, g, pool, jax.random.PRNGKey(0))
    written = {}
    monkeypatch.setattr("dragonfly2_tpu.native.export_scorer_artifact", lambda params, z, path: written.update(z=z))
    artifacts.save_native(tmp_path, model, state.params, cluster.graph)
    return {
        "rows": g.node_feats.shape[0], "losses": np.asarray(losses, np.float64), "grads": grads,
        "update": jax.tree.map(lambda a, b: np.asarray(a) - b, state.params, before), "z": written["z"],
    }


@pytest.mark.parametrize("n_devices", [1, 4])
def test_padding_to_a_rung_changes_nothing_a_run_computes_or_publishes(monkeypatch, tmp_path, n_devices):
    cluster = synthetic.make_cluster(num_nodes=HOSTS, num_neighbors=8, num_pairs=1024, seed=HOSTS)
    mesh = meshlib.mesh_for_run(jax.devices()[:n_devices])[0]
    placed = _float32_run(cluster, mesh, monkeypatch, tmp_path)
    with monkeypatch.context() as unpadded:
        unpadded.setattr(pk, "placed_rows", lambda hosts, shards=1: hosts)  # the host count as it came
        plain = _float32_run(cluster, mesh, monkeypatch, tmp_path)
    assert (placed["rows"], plain["rows"]) == (512, HOSTS)
    assert np.max(np.abs(placed["losses"] - plain["losses"]) / np.abs(plain["losses"])) < F32_TOLERANCE
    for what in ("grads", "update"):
        got, want = jax.tree.leaves(placed[what]), jax.tree.leaves(plain[what])
        assert len(got) == len(want) > 20
        worst = max(float(np.linalg.norm(a - b) / np.linalg.norm(b)) for a, b in zip(got, want))
        assert worst < 10 * F32_TOLERANCE, (what, worst)  # the gradient norm's tolerance there
    # the cluster's own rows are written, none of the padding's
    assert placed["z"].shape == plain["z"].shape == (HOSTS, CFG.embed_dim)
    assert float(np.max(np.abs(placed["z"] - plain["z"]))) < F32_TOLERANCE  # unit-norm rows


def test_a_host_count_that_moves_inside_a_rung_keeps_the_program(nothing_kept):  # noqa: F811
    """Three runs in one process on the program's own mesh: 300 and 420 hosts
    share the rung 512 and the second is served the first's program; 600
    hosts are placed at 768 and build their own. The manifest says what was
    given and what was placed."""
    from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

    traced, decisions = [], []
    for hosts in (300, 420, 600):
        cluster = synthetic.make_cluster(num_nodes=hosts, num_neighbors=4, num_pairs=512, seed=hosts)
        sink = TrainRunTelemetry("gnn")
        asyncio.run(train_gnn.train_async(
            train_gnn.GNNTrainConfig(**KEPT_CFG), cluster.graph, cluster.pairs, steps=6, steps_per_call=3, telemetry=sink))
        manifest = sink.summary()
        traced.append(manifest["calls"]["traced"])
        decisions.append(manifest["placement"]["decision"])
        assert manifest["placement"]["gather_vjp"]["slots"] == decisions[-1]["rows"] * 4  # the table's: the placed rows'
    assert traced == [1, 0, 1]
    n = len(jax.devices())
    assert decisions == [
        {"rule": "rows_over_data", "devices": n, "hosts": 300, "rows": 512, "pad_pct": 70.67},
        {"rule": "rows_over_data", "devices": n, "hosts": 420, "rows": 512, "pad_pct": 21.9},
        {"rule": "rows_over_data", "devices": n, "hosts": 600, "rows": 768, "pad_pct": 28.0},
    ]


def test_two_placements_that_alternate_are_both_kept_and_a_third_evicts_the_least_recently_used(nothing_kept, monkeypatch):  # noqa: F811
    """A pool that rotates every second upload alternates between two rungs:
    one upload's hosts (300, 420: 512 rows) and two uploads' (600, 700: 768).
    Each builds its program once, and from then on both are served; a third
    rung (900: 1,024 rows) lets the least recently used go (nothing holds it),
    and that rung's next run builds again. The manifest's `kept` says so."""
    import gc
    import weakref

    from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

    one_device(monkeypatch)
    runs, programs = [], {}
    for hosts in (300, 600, 420, 700, 900, 600, 300):
        cluster = synthetic.make_cluster(num_nodes=hosts, num_neighbors=4, num_pairs=512, seed=hosts)
        sink = TrainRunTelemetry("gnn")
        asyncio.run(train_gnn.train_async(
            train_gnn.GNNTrainConfig(**KEPT_CFG), cluster.graph, cluster.pairs, steps=6, steps_per_call=3,
            telemetry=sink))
        manifest = sink.summary()
        rows = manifest["placement"]["decision"]["rows"]
        programs.setdefault(rows, weakref.ref(list(train_gnn._kept.values())[-1]))
        runs.append((rows, manifest["calls"]["traced"], manifest["kept"]))
        if hosts == 900:
            gc.collect()
            assert programs[512]() is None and programs[768]() is not None  # 512's was the least recently used
    assert [r for r, _, _ in runs] == [512, 768, 512, 768, 1024, 768, 512]
    assert [t for _, t, _ in runs] == [1, 1, 0, 0, 1, 0, 1]
    assert [k for _, _, k in runs] == [
        {"programs": 1, "served": False}, {"programs": 2, "served": False}, {"programs": 2, "served": True},
        {"programs": 2, "served": True}, {"programs": 2, "served": False}, {"programs": 2, "served": True},
        {"programs": 2, "served": False},
    ]
    assert len(train_gnn._kept) == train_gnn.KEPT_PROGRAMS
