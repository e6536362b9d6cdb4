"""Model + ops + scorer tests (CPU backend, 8 virtual devices via conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models import BandwidthMLP, GraphSAGE, TopoScorer
from dragonfly2_tpu.models.features import FEATURE_DIM, BASE_WEIGHTS
from dragonfly2_tpu.models.scorer import GNNScorer, LinearScorer
from dragonfly2_tpu.ops.neighbor_agg import masked_mean, neighbor_gather
from dragonfly2_tpu.trainer import synthetic


@pytest.fixture(scope="module")
def tiny_cluster():
    return synthetic.make_cluster(num_nodes=64, num_neighbors=4, num_pairs=256, seed=1)


class TestOps:
    def test_neighbor_gather_shapes(self):
        h = jnp.arange(12.0).reshape(6, 2)
        nbrs = jnp.array([[1, 2], [0, 0], [5, 4], [3, 3], [0, 1], [2, 2]], jnp.int32)
        out = neighbor_gather(h, nbrs)
        assert out.shape == (6, 2, 2)
        np.testing.assert_allclose(out[0, 0], h[1])

    def test_masked_mean_ignores_padding(self):
        x = jnp.stack([jnp.ones((3, 4)), 5 * jnp.ones((3, 4))], axis=0)  # [2,3,4]
        mask = jnp.array([[1, 1, 0], [1, 0, 0]], jnp.float32)
        out = masked_mean(x, mask)
        np.testing.assert_allclose(out[0], np.ones(4), rtol=1e-5)
        np.testing.assert_allclose(out[1], 5 * np.ones(4), rtol=1e-5)

    def test_aggregate_matches_manual(self):
        rng = np.random.default_rng(0)
        h = rng.standard_normal((10, 8)).astype(np.float32)
        nbrs = rng.integers(0, 10, (10, 3)).astype(np.int32)
        mask = (rng.random((10, 3)) > 0.3).astype(np.float32)
        out = np.asarray(masked_mean(neighbor_gather(jnp.asarray(h), jnp.asarray(nbrs)), jnp.asarray(mask)))
        for i in range(10):
            sel = h[nbrs[i]][mask[i] > 0]
            want = sel.mean(0) if len(sel) else np.zeros(8)
            np.testing.assert_allclose(out[i], want, rtol=1e-4, atol=1e-5)


class TestModels:
    def test_mlp_forward(self):
        model = BandwidthMLP(hidden=(32, 16))
        x = jnp.ones((5, FEATURE_DIM))
        params = model.init(jax.random.PRNGKey(0), x)
        out = model.apply(params, x)
        assert out.shape == (5,)
        assert np.all((np.asarray(out) >= 0) & (np.asarray(out) <= 1))

    def test_graphsage_embeddings_normalized(self, tiny_cluster):
        g = jax.tree.map(jnp.asarray, tiny_cluster.graph)
        model = GraphSAGE(hidden=32, embed_dim=16, num_layers=2)
        params = model.init(jax.random.PRNGKey(0), g)
        z = model.apply(params, g)
        assert z.shape == (64, 16)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(z), axis=-1), 1.0, atol=1e-3)

    def test_toposcorer_jits(self, tiny_cluster):
        g = jax.tree.map(jnp.asarray, tiny_cluster.graph)
        model = TopoScorer(hidden=32, embed_dim=16, num_layers=2)
        idx = jnp.arange(8, dtype=jnp.int32)
        feats = jnp.zeros((8, FEATURE_DIM))
        params = model.init(jax.random.PRNGKey(0), g, idx, idx, feats)
        scores = jax.jit(model.apply)(params, g, idx, idx, feats)
        assert scores.shape == (8,)
        assert np.all(np.isfinite(np.asarray(scores)))


class TestScorers:
    def test_linear_matches_reference_weights(self):
        feats = np.zeros((3, FEATURE_DIM), np.float32)
        feats[0, :6] = 1.0  # perfect parent
        feats[1, 0] = 1.0  # only piece ratio
        scores = LinearScorer().score(feats)
        np.testing.assert_allclose(scores[0], BASE_WEIGHTS.sum(), rtol=1e-6)
        np.testing.assert_allclose(scores[1], 0.2, rtol=1e-6)
        assert scores[2] == 0.0

    def test_gnn_scorer_update_params_resets_caches(self, tiny_cluster):
        """In-place param swap must invalidate BOTH the embedding table and
        the precomputed head partials — serving resumes only after the next
        refresh, and with scores from the new params."""
        from dragonfly2_tpu.trainer import train_gnn

        cfg = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2)
        model = train_gnn.make_model(cfg)
        s1 = train_gnn.init_state(cfg, tiny_cluster.graph, rng_seed=1)
        s2 = train_gnn.init_state(cfg, tiny_cluster.graph, rng_seed=2)
        scorer = GNNScorer(model, s1.params)
        scorer.refresh(tiny_cluster.graph)
        child = tiny_cluster.pairs.child[:8]
        parent = tiny_cluster.pairs.parent[:8]
        feats = tiny_cluster.pairs.feats[:8]
        old = scorer.score(feats, child=child, parent=parent)

        scorer.update_params(s2.params)
        assert not scorer.ready  # caches dropped, must refresh first
        with pytest.raises(RuntimeError):
            scorer.score(feats, child=child, parent=parent)
        scorer.refresh(tiny_cluster.graph)
        new = scorer.score(feats, child=child, parent=parent)
        assert not np.allclose(old, new)  # genuinely the new model's scores

    def test_gnn_scorer_roundtrip(self, tiny_cluster):
        from dragonfly2_tpu.trainer import train_gnn

        cfg = train_gnn.GNNTrainConfig(hidden=32, embed_dim=16, num_layers=2)
        model = train_gnn.make_model(cfg)
        state = train_gnn.init_state(cfg, tiny_cluster.graph)
        scorer = GNNScorer(model, state.params)
        with pytest.raises(RuntimeError):
            scorer.score(np.zeros((4, FEATURE_DIM), np.float32), child=np.zeros(4, np.int32), parent=np.zeros(4, np.int32))
        scorer.refresh(tiny_cluster.graph)
        child = tiny_cluster.pairs.child[:40]
        parent = tiny_cluster.pairs.parent[:40]
        scores = scorer.score(tiny_cluster.pairs.feats[:40], child=child, parent=parent)
        assert scores.shape == (40,)
        assert np.all((scores > 0) & (scores < 1))
        # scorer head must agree with full-model forward
        g = jax.tree.map(jnp.asarray, tiny_cluster.graph)
        full = model.apply(
            state.params, g, jnp.asarray(child), jnp.asarray(parent), jnp.asarray(tiny_cluster.pairs.feats[:40])
        )
        np.testing.assert_allclose(scores, np.asarray(full), rtol=2e-2, atol=2e-2)
        # multi-round entry (micro-batcher shape) == stacked single rounds
        m_child = np.stack([child[:8], parent[:8]])
        m_parent = np.stack([parent[:8], child[:8]])
        m_feats = np.stack(
            [tiny_cluster.pairs.feats[:8], tiny_cluster.pairs.feats[8:16]]
        )
        multi = scorer.score_rounds(m_feats, child=m_child, parent=m_parent)
        assert multi.shape == (2, 8)
        for m in range(2):
            single = scorer.score(m_feats[m], child=m_child[m], parent=m_parent[m])
            np.testing.assert_allclose(multi[m], single, rtol=1e-5, atol=1e-6)
        # micro-batcher duck interface
        assert scorer.num_nodes == tiny_cluster.graph.node_feats.shape[0]
        assert scorer.feature_dim == FEATURE_DIM
        assert scorer.engine == "jax"
