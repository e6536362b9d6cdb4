"""Native C++ scorer: build, artifact round-trip, parity with the JAX scorer
(ref: the TF-Serving Predict hop this replaces, tfserving/client_v1.go:82-102)."""

import shutil
import time

import numpy as np
import pytest

from dragonfly2_tpu.native import NativeScorer, build_native_lib, export_scorer_artifact

pytestmark = pytest.mark.skipif(shutil.which("g++") is None, reason="g++ not available")


@pytest.fixture(scope="module")
def trained():
    import jax

    from dragonfly2_tpu.models.scorer import GNNScorer
    from dragonfly2_tpu.trainer import synthetic, train_gnn

    cluster = synthetic.make_cluster(num_nodes=128, num_neighbors=8, num_pairs=512, seed=3)
    cfg = train_gnn.GNNTrainConfig(hidden=64, embed_dim=32, num_layers=2)
    model = train_gnn.make_model(cfg)
    state = train_gnn.init_state(cfg, cluster.graph, rng_seed=3)
    import jax.numpy as jnp

    g = jax.tree.map(jnp.asarray, cluster.graph)
    z = np.asarray(jax.jit(lambda p, gg: model.apply(p, gg, method=model.embed))(state.params, g))
    jax_scorer = GNNScorer(model, state.params)
    jax_scorer.refresh(g)
    return cluster, state.params, z, jax_scorer


def test_build_lib_is_cached(tmp_path, monkeypatch):
    monkeypatch.setenv("DRAGONFLY_NATIVE_CACHE", str(tmp_path))
    lib = build_native_lib()
    assert lib.parent == tmp_path
    mtime = lib.stat().st_mtime
    lib2 = build_native_lib()
    assert lib2 == lib and lib.stat().st_mtime == mtime  # no rebuild


def test_export_and_score_parity(tmp_path, trained):
    cluster, params, z, jax_scorer = trained
    artifact = export_scorer_artifact(params, z, tmp_path / "scorer.dfsc")
    ns = NativeScorer(artifact)
    assert ns.num_nodes == 128 and ns.embed_dim == 32

    rng = np.random.default_rng(0)
    child = rng.integers(0, 128, size=40).astype(np.int32)
    parent = rng.integers(0, 128, size=40).astype(np.int32)
    feats = cluster.pairs.feats[:40].astype(np.float32)

    native = ns.score(feats, child=child, parent=parent)
    jaxed = jax_scorer.score(feats, child=child, parent=parent)
    assert native.shape == (40,)
    assert np.all((native > 0) & (native < 1))
    # bfloat16 JAX head vs float32 C++: scores agree to bf16 tolerance
    np.testing.assert_allclose(native, jaxed, atol=3e-2)
    # the *ranking* is what the scheduler consumes: top-4 must broadly agree
    top_native = set(np.argsort(-native)[:8])
    top_jax = set(np.argsort(-jaxed)[:4])
    assert top_jax <= top_native
    ns.close()


def test_bad_index_rejected(tmp_path, trained):
    cluster, params, z, _ = trained
    artifact = export_scorer_artifact(params, z, tmp_path / "scorer.dfsc")
    ns = NativeScorer(artifact)
    feats = np.zeros((2, ns.feature_dim), np.float32)
    with pytest.raises(ValueError):
        ns.score(feats, child=np.array([0, 999], np.int32), parent=np.array([0, 1], np.int32))
    ns.close()


def test_corrupt_artifact_rejected(tmp_path):
    bad = tmp_path / "bad.dfsc"
    bad.write_bytes(b"not a scorer artifact")
    with pytest.raises(IOError):
        NativeScorer(bad)


def test_artifact_loader_roundtrip(tmp_path, trained):
    from dragonfly2_tpu.trainer import artifacts, train_gnn

    cluster, params, z, _ = trained
    cfg = train_gnn.GNNTrainConfig(hidden=64, embed_dim=32, num_layers=2)
    model = train_gnn.make_model(cfg)
    assert artifacts.load_native(tmp_path) is None  # no artifact yet
    artifacts.save_native(tmp_path, model, params, cluster.graph)
    ns = artifacts.load_native(tmp_path)
    assert ns is not None and ns.num_nodes == 128
    ns.close()


def test_score_rounds_matches_single_calls(tmp_path, trained):
    """The amortized multi-round FFI entry must be bit-identical to M separate
    single-round calls (it is the same flat batch through the same GEMMs)."""
    cluster, params, z, _ = trained
    ns = NativeScorer(export_scorer_artifact(params, z, tmp_path / "s.dfsc"))
    rng = np.random.default_rng(5)
    M, B = 7, 40
    child = rng.integers(0, 128, size=(M, B)).astype(np.int32)
    parent = rng.integers(0, 128, size=(M, B)).astype(np.int32)
    feats = np.tile(cluster.pairs.feats[:B].astype(np.float32), (M, 1, 1))
    multi = ns.score_rounds(feats, child=child, parent=parent)
    assert multi.shape == (M, B)
    for m in range(M):
        single = ns.score(feats[m], child=child[m], parent=parent[m])
        np.testing.assert_array_equal(multi[m], single)
    # bad index anywhere in the queue rejects the whole call
    bad_child = child.copy()
    bad_child[3, 17] = 999
    with pytest.raises(ValueError):
        ns.score_rounds(feats, child=bad_child, parent=parent)
    ns.close()


def test_microbatch_scorer_coalesces(tmp_path, trained):
    """N concurrent async rounds scheduled in one tick must land in one
    multi-round native flush and return per-round results identical to
    direct single-round calls (including mixed round widths via padding)."""
    import asyncio

    from dragonfly2_tpu.native import MicroBatchScorer

    cluster, params, z, _ = trained
    ns = NativeScorer(export_scorer_artifact(params, z, tmp_path / "s.dfsc"))
    mb = MicroBatchScorer(ns)
    rng = np.random.default_rng(9)
    widths = [40, 40, 17, 40, 8]
    rounds = []
    for w in widths:
        rounds.append(
            (
                cluster.pairs.feats[:w].astype(np.float32),
                rng.integers(0, 128, size=w).astype(np.int32),
                rng.integers(0, 128, size=w).astype(np.int32),
            )
        )

    async def go():
        return await asyncio.gather(
            *(mb.score(f, child=c, parent=p) for f, c, p in rounds)
        )

    outs = asyncio.run(go())
    assert mb.flushes == 1 and mb.rounds == len(widths)
    for (f, c, p), out in zip(rounds, outs):
        np.testing.assert_array_equal(out, ns.score(f, child=c, parent=p))
    ns.close()


def test_microbatch_bad_round_fails_alone(tmp_path, trained):
    """One round carrying an out-of-range node id (a stale id from a
    pre-refresh graph) must fail with ValueError while the concurrent healthy
    rounds in the SAME flush still score — the optimistic-dispatch path: the
    native call rejects the flat batch, per-round validation then isolates
    the culprit and the survivors are re-scored."""
    import asyncio

    from dragonfly2_tpu.native import MicroBatchScorer

    cluster, params, z, _ = trained
    ns = NativeScorer(export_scorer_artifact(params, z, tmp_path / "s.dfsc"))
    mb = MicroBatchScorer(ns)
    rng = np.random.default_rng(9)
    f = cluster.pairs.feats[:8].astype(np.float32)
    good_c = rng.integers(0, 128, size=8).astype(np.int32)
    good_p = rng.integers(0, 128, size=8).astype(np.int32)
    bad_c = good_c.copy()
    bad_c[3] = 10_000_000  # far past num_nodes

    async def go():
        return await asyncio.gather(
            mb.score(f, child=good_c, parent=good_p),
            mb.score(f, child=bad_c, parent=good_p),
            mb.score(f, child=good_c, parent=good_p),
            return_exceptions=True,
        )

    r0, r1, r2 = asyncio.run(go())
    assert isinstance(r1, ValueError), r1
    expected = ns.score(f, child=good_c, parent=good_p)
    np.testing.assert_array_equal(r0, expected)
    np.testing.assert_array_equal(r2, expected)
    # the healthy rounds were still served by ONE coalesced re-score
    assert mb.rounds == 2
    ns.close()


def test_microbatch_validates_up_front_for_non_native_scorer():
    """A non-native scorer (the JAX fallback) CLAMPS out-of-bounds gather
    indices under jit instead of raising — so the micro-batcher must bounds-
    check its rounds BEFORE dispatch: a stale node id must surface as
    ValueError, never as a silently wrong score from a clamped embedding."""
    import asyncio

    from dragonfly2_tpu.native import MicroBatchScorer

    class _ClampingJaxLike:
        """score_rounds never raises on bad indices — like jnp.take."""

        ready = True
        engine = "jax"
        feature_dim = 16
        num_nodes = 128

        def score_rounds(self, feats, *, child, parent):
            return np.zeros(child.shape, np.float32)

    mb = MicroBatchScorer(_ClampingJaxLike())
    f = np.zeros((4, 16), np.float32)
    ok = np.arange(4, dtype=np.int32)
    bad = ok.copy()
    bad[1] = 999  # >= num_nodes; the fake would happily "score" it

    async def go():
        return await asyncio.gather(
            mb.score(f, child=ok, parent=ok),
            mb.score(f, child=bad, parent=ok),
            return_exceptions=True,
        )

    r_ok, r_bad = asyncio.run(go())
    assert isinstance(r_bad, ValueError), r_bad
    np.testing.assert_array_equal(r_ok, np.zeros(4, np.float32))


def test_microbatch_offload_path_matches_inline(tmp_path, trained):
    """offload=True runs multi-round flushes in a worker thread (the
    multicore serving pipeline); results, error isolation, and counters must
    match the inline path — this is the path the multicore bench host takes,
    which single-core CI never selects on its own."""
    import asyncio

    from dragonfly2_tpu.native import MicroBatchScorer

    cluster, params, z, _ = trained
    ns = NativeScorer(export_scorer_artifact(params, z, tmp_path / "s.dfsc"))
    mb = MicroBatchScorer(ns, offload=True)
    rng = np.random.default_rng(11)
    rounds = [
        (
            cluster.pairs.feats[:40].astype(np.float32),
            rng.integers(0, 128, size=40).astype(np.int32),
            rng.integers(0, 128, size=40).astype(np.int32),
        )
        for _ in range(6)
    ]

    async def go():
        good = [mb.score(f, child=c, parent=p) for f, c, p in rounds]
        # one round with an out-of-range index fails ALONE, off-thread or not
        bad = mb.score(
            rounds[0][0],
            child=np.full(40, 10_000, np.int32),
            parent=rounds[0][2],
        )
        results = await asyncio.gather(*good, bad, return_exceptions=True)
        return results[:-1], results[-1]

    outs, bad_out = asyncio.run(go())
    assert isinstance(bad_out, ValueError)
    for (f, c, p), out in zip(rounds, outs):
        np.testing.assert_array_equal(out, ns.score(f, child=c, parent=p))
    assert mb.rounds == len(rounds)
    ns.close()


def test_native_throughput_sanity(tmp_path, trained):
    """North-star config 5 shape: batched rounds of 40 candidates. On any
    hardware the native path must beat 1k rounds/s by a wide margin; the real
    number lands in bench.py."""
    cluster, params, z, _ = trained
    ns = NativeScorer(export_scorer_artifact(params, z, tmp_path / "s.dfsc"))
    rng = np.random.default_rng(1)
    child = rng.integers(0, 128, size=40).astype(np.int32)
    parent = rng.integers(0, 128, size=40).astype(np.int32)
    feats = cluster.pairs.feats[:40].astype(np.float32)
    ns.score(feats, child=child, parent=parent)  # warm
    t0 = time.perf_counter()
    n = 200
    for _ in range(n):
        ns.score(feats, child=child, parent=parent)
    rate = n / (time.perf_counter() - t0)
    assert rate > 1000, f"native scorer too slow: {rate:.0f} rounds/s"
    ns.close()
