"""Trainer tests: the served scan step over the virtual 8-device mesh,
convergence on the synthetic cluster, GNN beating the linear baseline, and
one program whoever asks for it."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models.scorer import GNNScorer, LinearScorer
from dragonfly2_tpu.parallel import mesh as meshlib
from dragonfly2_tpu.trainer import synthetic, train_gnn
from dragonfly2_tpu.trainer.synthetic import PairBatch


def test_make_mesh_axes():
    """A run's mesh has one axis, `data`, and every device is on it."""
    mesh, decision = meshlib.mesh_for_run()
    assert mesh.axis_names == ("data",)
    assert dict(mesh.shape) == {"data": len(jax.devices())} == {"data": 8}
    assert decision == {"rule": "rows_over_data", "devices": 8}


def test_param_sharding_rule(monkeypatch):
    """Placement replicates every leaf of the state, params and optimizer
    moments alike, on one device and on a `data` mesh of four, while the
    graph's node rows and the sorted table split over `data` (the test, not
    the program, says these CPU devices get tables)."""
    from dragonfly2_tpu.ops import neighbor_agg_pallas as pk

    monkeypatch.setattr(pk, "PLATFORM", "cpu")
    cluster = synthetic.make_cluster(num_nodes=1024, num_neighbors=16, num_pairs=256, seed=3)
    cfg = train_gnn.GNNTrainConfig(hidden=128, embed_dim=64, num_layers=2, batch_size=64)
    for n_devices in (1, 4):
        mesh = meshlib.mesh_for_run(jax.devices()[:n_devices])[0]
        state, _, g, _ = train_gnn._place_sharded(train_gnn.init_state(cfg, cluster.graph), cluster.graph, mesh)
        leaves = jax.tree.leaves((state.params, state.opt_state, state.step))
        assert len(leaves) > 2 * len(jax.tree.leaves(state.params))  # the moments are there too
        assert all(leaf.sharding.is_fully_replicated and leaf.sharding.mesh == mesh for leaf in leaves)
        table = jax.tree.leaves(g.by_dst)
        assert table, "no sorted table placed"
        for leaf in [*g[:4], *table]:  # a row shard (or a shard's table) a device
            assert leaf.sharding.shard_shape(leaf.shape)[0] * n_devices == leaf.shape[0], leaf.sharding


class TestShardedTraining:
    @pytest.fixture(scope="class")
    def cluster(self):
        # 10240 pairs: first 8192 for training, last 2048 held out for eval.
        return synthetic.make_cluster(num_nodes=128, num_neighbors=8, num_pairs=10240, seed=3)

    def test_one_sharded_step_runs_on_mesh(self, cluster):
        mesh = meshlib.mesh_for_run()[0]
        cfg = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2, batch_size=64, warmup_steps=2)
        state = train_gnn.init_state(cfg, cluster.graph)
        state, g, pool, multi_step = train_gnn.shard_for_training_scan(
            state, cluster.graph, cluster.pairs, mesh, batch_size=64, steps_per_call=1)
        # the params whole on every device
        assert all(p.sharding.is_fully_replicated for p in jax.tree.leaves(state.params))
        # graph rows actually sharded over the data axis, the pool on every device
        assert "data" in str(g.node_feats.sharding.spec)
        assert pool.child.sharding.is_fully_replicated
        state, (losses, gnorms) = multi_step(state, g, pool, jax.random.PRNGKey(0))
        assert losses.shape == gnorms.shape == (1,)
        assert np.isfinite(float(losses[0])) and float(gnorms[0]) > 0

    def test_convergence_beats_linear_baseline(self, cluster):
        train_pairs = PairBatch(*(a[:8192] for a in cluster.pairs))
        held_out = PairBatch(*(a[8192:] for a in cluster.pairs))
        cfg = train_gnn.GNNTrainConfig(
            hidden=64, embed_dim=32, num_layers=2, batch_size=512, warmup_steps=10, learning_rate=3e-3
        )
        state, g, pool, multi_step = train_gnn.shard_for_training_scan(
            train_gnn.init_state(cfg, cluster.graph), cluster.graph, train_pairs, meshlib.mesh_for_run()[0],
            batch_size=cfg.batch_size, steps_per_call=120)
        state, (losses, _) = multi_step(state, g, pool, jax.random.PRNGKey(0))
        losses = np.asarray(losses).tolist()
        assert losses[-1] < losses[0] * 0.5, f"no convergence: {losses}"

        # Held-out pairs (same graph, never trained on): GNN must beat the
        # reference's linear evaluator at ranking parents by true bandwidth.
        model = train_gnn.make_model(cfg)
        scorer = GNNScorer(model, state.params)
        scorer.refresh(cluster.graph)
        rng = np.random.default_rng(42)
        pairs = synthetic.sample_batch(held_out, 1024, rng)
        gnn_scores = scorer.score(pairs.feats, child=pairs.child, parent=pairs.parent)
        lin_scores = LinearScorer().score(pairs.feats)

        def rank_corr(a, b):
            ra, rb = np.argsort(np.argsort(a)), np.argsort(np.argsort(b))
            ra = ra - ra.mean()
            rb = rb - rb.mean()
            return float((ra * rb).sum() / np.sqrt((ra**2).sum() * (rb**2).sum()))

        gnn_corr = rank_corr(gnn_scores, pairs.label)
        lin_corr = rank_corr(lin_scores, pairs.label)
        assert gnn_corr > lin_corr + 0.1, f"GNN {gnn_corr:.3f} vs linear {lin_corr:.3f}"
        assert gnn_corr > 0.6, f"weak ranking: {gnn_corr:.3f}"


def test_scan_training_converges_and_matches_semantics():
    """Device-resident scan path (shard_for_training_scan): sampling inside
    lax.scan over the on-device pool must converge over several calls and
    keep the params replicated on the run's mesh."""
    cluster = synthetic.make_cluster(num_nodes=128, num_neighbors=8, num_pairs=8192, seed=3)
    cfg = train_gnn.GNNTrainConfig(hidden=64, embed_dim=32, num_layers=2, warmup_steps=5)
    mesh = meshlib.mesh_for_run()[0]
    state = train_gnn.init_state(cfg, cluster.graph, rng_seed=3)
    state, g, pool, multi = train_gnn.shard_for_training_scan(
        state, cluster.graph, cluster.pairs, mesh, batch_size=512, steps_per_call=10
    )
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(8):  # 80 steps in 8 dispatches
        key, sub = jax.random.split(key)
        state, (batch_losses, _) = multi(state, g, pool, sub)
        losses.extend(np.asarray(batch_losses).tolist())
    assert all(p.sharding.is_fully_replicated for p in jax.tree.leaves(state.params))
    assert len(losses) == 80 and all(np.isfinite(v) for v in losses)
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.5, losses[:3] + losses[-3:]


def one_device(patch):
    """Runs placed while `patch` holds get the mesh the program gives one device."""
    patch.setattr(meshlib, "mesh_for_run", lambda run=meshlib.mesh_for_run: run(jax.devices()[:1]))


@pytest.fixture(scope="module")
def served_runs():
    """Two short `train_async` runs, one with a telemetry sink and one
    without: {with sink?: (losses, lowered text of the scan step)}, and the sink."""
    from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

    cluster = synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=512, seed=1)
    cfg = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2, batch_size=64, warmup_steps=2)
    built = []
    build = train_gnn.shard_for_training_scan

    def spy(*args, **kwargs):
        built.append(build(*args, **kwargs))
        return built[-1]

    sink = TrainRunTelemetry("gnn")
    runs = {}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(train_gnn, "shard_for_training_scan", spy)
        one_device(m)  # a quick compile
        for telemetry in (sink, None):
            _, losses = asyncio.run(train_gnn.train_async(
                cfg, cluster.graph, cluster.pairs, steps=6, steps_per_call=3, telemetry=telemetry))
            state, g, pool, multi_step = built.pop()
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), (state, g, pool))
            runs[telemetry is not None] = losses, multi_step.lower(*shapes, jax.random.PRNGKey(0)).as_text()
    return runs, sink


def test_the_scan_step_is_one_program_with_and_without_telemetry(served_runs):
    """Telemetry is a reader of what every call pulls, not a switch of the
    compiled program: the served run lowers to the same text either way."""
    runs, _ = served_runs
    assert runs[True][1] == runs[False][1]


def test_train_async_without_telemetry_returns_the_same_losses(served_runs):
    runs, sink = served_runs
    (with_sink, _), (without, _) = runs[True], runs[False]
    assert len(without) == 6 and without == with_sink
    assert sink.steps == 6 and sink.last_loss == with_sink[-1] and sink.last_grad_norm > 0


@pytest.fixture
def nothing_kept():
    """A process that has kept no scan program, before the test and after it."""
    train_gnn._kept.clear()
    yield
    train_gnn._kept.clear()


KEPT_CFG = dict(hidden=32, embed_dim=16, num_layers=2, batch_size=64, warmup_steps=2)


def newest_kept():
    """The scan program the last run built or was served (kept most recently used last)."""
    return list(train_gnn._kept.values())[-1]


def _served(cluster, *, steps_per_call=3, **cfg):
    """One `train_async` run of 6 steps: (losses, the run manifest's `calls`)."""
    from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

    sink = TrainRunTelemetry("gnn")
    _, losses = asyncio.run(train_gnn.train_async(
        train_gnn.GNNTrainConfig(**{**KEPT_CFG, **cfg}), cluster.graph, cluster.pairs,
        steps=6, steps_per_call=steps_per_call, telemetry=sink))
    return losses, sink.summary()["calls"]


def test_a_run_of_the_kept_shapes_is_served_the_kept_program(nothing_kept):
    """The second run of one configuration and shapes (another graph, fresh
    weights) traces nothing: its first call hits the kept function's one
    cache entry. What it computes is what a process that kept nothing does.
    On the mesh the program gives one device and every device (`mesh_for_run`:
    a `data` mesh, made anew for every run)."""
    first = synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=512, seed=1)
    second = synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=512, seed=2)
    assert not np.array_equal(first.graph.neighbors, second.graph.neighbors)
    for placed in (one_device, lambda patch: None):
        with pytest.MonkeyPatch.context() as patch:
            placed(patch)
            _, calls = _served(first)
            assert calls["traced"] == 1 and calls["count"] == 2 and calls["first_ms"] > 0
            program = newest_kept()
            kept_losses, calls = _served(second)
            assert calls["traced"] == 0 and newest_kept() is program and program._cache_size() == 1
            train_gnn._kept.clear()
            fresh_losses, calls = _served(second)
            assert calls["traced"] == 1 and newest_kept() is not program
            assert kept_losses == fresh_losses and len(kept_losses) == 6


# two runs a case, each other than the one before it in one thing the program is built from
OTHER_RUNS = {
    "nodes": [dict(num_nodes=300), dict(num_nodes=300, num_pairs=256)],  # node rows of another rung; the pool's rows
    "steps_per_call": [dict(steps_per_call=2), dict(steps_per_call=2, batch_size=32)],
    "hidden": [dict(hidden=64), dict(hidden=64, learning_rate=1e-3)],  # the model, the transform: the state's tree
}


@pytest.mark.parametrize("others", OTHER_RUNS)
def test_a_run_of_other_shapes_builds_its_own_and_one_program_is_kept(nothing_kept, monkeypatch, others):
    """Whatever the program was built from is part of what it is kept under:
    a run that differs in any of it traces its own; of the programs built, the
    newest `KEPT_PROGRAMS` are kept, and the one a new build replaces (the
    least recently used) is let go (nothing holds it: the weak reference dies)."""
    import gc
    import weakref

    one_device(monkeypatch)
    sizes = dict(num_nodes=64, num_neighbors=4, num_pairs=512, seed=1)
    built = []
    for other in [{}, *OTHER_RUNS[others]]:
        cluster = synthetic.make_cluster(**{**sizes, **{k: v for k, v in other.items() if k in sizes}})
        _, calls = _served(cluster, **{k: v for k, v in other.items() if k not in sizes})
        assert calls["traced"] == 1 and newest_kept()._cache_size() == 1, other
        built.append(weakref.ref(newest_kept()))
        gc.collect()
        assert len(train_gnn._kept) == min(len(built), train_gnn.KEPT_PROGRAMS), other
        assert all(ref() is None for ref in built[: -train_gnn.KEPT_PROGRAMS]), other
        assert all(ref() in train_gnn._kept.values() for ref in built[-train_gnn.KEPT_PROGRAMS:]), other


def test_two_states_of_one_configuration_have_one_tree_structure():
    """Model and transform are made once per distinct values: `apply_fn` and
    `tx` are static fields of the state's tree, and a jitted function kept
    for one state traces again for a state whose structure is another."""
    cluster = synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=64, seed=2)
    one, same = (train_gnn.init_state(train_gnn.GNNTrainConfig(**KEPT_CFG), cluster.graph, seed) for seed in (0, 1))
    assert one.apply_fn == same.apply_fn and one.tx is same.tx
    assert jax.tree.structure(one) == jax.tree.structure(same)
    assert train_gnn.make_model(train_gnn.GNNTrainConfig(**KEPT_CFG)) is one.apply_fn.__self__
    for other in (dict(hidden=64), dict(weight_decay=0.0), dict(warmup_steps=3)):
        state = train_gnn.init_state(train_gnn.GNNTrainConfig(**{**KEPT_CFG, **other}), cluster.graph)
        assert jax.tree.structure(state) != jax.tree.structure(one), other


def test_the_body_and_the_scan_agree():
    """`make_train_step()` is what `multi_step` scans: jitted alone on the
    batch a one-step scan samples, it gives that scan's loss and gradient
    norm (tests/test_gather_vjp.py lowers the body alone)."""
    cluster = synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=64, seed=2)
    cfg = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2, batch_size=64, warmup_steps=2)
    unplaced = train_gnn.init_state(cfg, cluster.graph)
    state, g, pool, multi_step = train_gnn.shard_for_training_scan(
        unplaced, cluster.graph, cluster.pairs, meshlib.mesh_for_run(jax.devices()[:1])[0],
        batch_size=cfg.batch_size, steps_per_call=1)
    key = jax.random.PRNGKey(5)
    # the rows the scan's one step samples: its key is split off `key` as multi_step does
    idx = np.asarray(jax.random.randint(jax.random.split(key, 1)[0], (cfg.batch_size,), 0, 64))
    batch = PairBatch(*(jnp.asarray(np.asarray(a)[idx]) for a in cluster.pairs))
    _, (loss, gnorm) = jax.jit(train_gnn.make_train_step())(unplaced, jax.tree.map(jnp.asarray, cluster.graph), batch)
    _, (losses, gnorms) = multi_step(state, g, pool, key)
    np.testing.assert_allclose(float(losses[0]), float(loss), rtol=1e-5)
    np.testing.assert_allclose(float(gnorms[0]), float(gnorm), rtol=1e-5)


def test_mlp_training_learns_bandwidth():
    """North-star config 1: MLP bandwidth predictor on download records."""
    import optax
    from flax.training import train_state as ts

    from dragonfly2_tpu.models import BandwidthMLP

    cluster = synthetic.make_cluster(num_nodes=128, num_neighbors=8, num_pairs=8192, seed=5)
    model = BandwidthMLP(hidden=(64, 32))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, cluster.pairs.feats.shape[1])))
    state = ts.TrainState.create(apply_fn=model.apply, params=params, tx=optax.adam(1e-2))

    @jax.jit
    def step(state, x, y):
        def loss_fn(p):
            return jnp.mean((state.apply_fn(p, x) - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    rng = np.random.default_rng(0)
    first = last = None
    for i in range(150):
        b = synthetic.sample_batch(cluster.pairs, 256, rng)
        state, loss = step(state, jnp.asarray(b.feats), jnp.asarray(b.label))
        if i == 0:
            first = float(loss)
        last = float(loss)
    assert last < first * 0.4, f"MLP no convergence: {first} -> {last}"
