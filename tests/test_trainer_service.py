"""ML loop end-to-end: telemetry → announcer upload → trainer → registry →
scheduler ml-evaluator hot swap (the loop the reference stubbed, SURVEY §3.4)."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from dragonfly2_tpu.manager.server import ManagerServer
from dragonfly2_tpu.rpc.core import RpcServer
from dragonfly2_tpu.rpc.manager import RemoteManagerClient
from dragonfly2_tpu.rpc.trainer import RemoteTrainerClient, register_trainer
from dragonfly2_tpu.scheduler.announcer import TrainerAnnouncer
from dragonfly2_tpu.telemetry import TelemetryStorage
from dragonfly2_tpu.telemetry.records import pack_records, unpack_records
from dragonfly2_tpu.trainer import artifacts, dataset as datasetlib, train_gnn, train_mlp
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
from dragonfly2_tpu.trainer.synthetic import PairBatch


def _fill_telemetry(store: TelemetryStorage, n_hosts: int = 12, n_rows: int = 200, seed: int = 3):
    """Synthesize plausible telemetry: fast hosts serve high bandwidth."""
    rng = np.random.default_rng(seed)
    hosts = [f"host-{i}".encode() for i in range(n_hosts)]
    capacity = rng.random(n_hosts) * 0.9 + 0.1
    for _ in range(n_rows):
        c, p = rng.integers(0, n_hosts, 2)
        feats = rng.random(16).astype(np.float32)
        feats[1] = capacity[p]  # upload_success correlates with capacity
        bw = capacity[p] * (1 << 30) * (0.8 + 0.4 * rng.random())
        store.downloads.append(
            task_id=b"t1", child_peer_id=b"c", parent_peer_id=b"p",
            child_host_id=hosts[c], parent_host_id=hosts[p],
            piece_count=10, piece_size=4 << 20, content_length=40 << 20,
            bandwidth_bps=bw, piece_cost_ms_mean=50.0,
            success=True, back_to_source=False, pair_features=feats,
        )
    for s in range(n_hosts):
        for d in rng.choice(n_hosts, size=4, replace=False):
            if d == s:
                continue
            store.probes.append(
                src_host_id=hosts[s], dst_host_id=hosts[int(d)],
                rtt_mean_ms=rng.random() * 50, rtt_std_ms=rng.random() * 5,
                rtt_min_ms=rng.random() * 20, probe_count=10,
            )
    return hosts


def test_pack_roundtrip(tmp_path):
    store = TelemetryStorage(tmp_path)
    _fill_telemetry(store, n_rows=10)
    arr = store.downloads.load_all()
    back = unpack_records(pack_records(arr))
    assert back.dtype == arr.dtype and len(back) == len(arr)
    assert bytes(back[0]["parent_host_id"]) == bytes(arr[0]["parent_host_id"])


def test_build_dataset_from_telemetry(tmp_path):
    store = TelemetryStorage(tmp_path)
    _fill_telemetry(store, n_hosts=10, n_rows=150)
    ds = datasetlib.build_dataset(store.downloads.load_all(), store.probes.load_all())
    assert ds.num_nodes >= 10
    assert ds.num_pairs > 100
    assert ds.graph.mask.sum() > 0  # probe edges landed
    # labels normalized to [0,1]
    assert 0 <= ds.pairs.label.min() and ds.pairs.label.max() <= 1.0
    # node upload-success aggregated for serving hosts
    assert (ds.graph.node_feats[:, 1] > 0).any()
    tr, ev = datasetlib.split_pairs(ds.pairs)
    assert len(tr.child) + len(ev.child) == ds.num_pairs


def test_mlp_training_learns(tmp_path):
    store = TelemetryStorage(tmp_path)
    _fill_telemetry(store, n_rows=400)
    ds = datasetlib.build_dataset(store.downloads.load_all(), store.probes.load_all())
    tr, ev = datasetlib.split_pairs(ds.pairs)
    cfg = train_mlp.MLPTrainConfig(hidden=(64, 64), steps=200, batch_size=256)
    params, evaluation = train_mlp.train(cfg, tr, eval_pairs=ev)
    # upload_success (feat 1) directly encodes capacity -> model must beat
    # the variance of the labels by a wide margin
    assert evaluation["eval_mse"] < float(np.var(ds.pairs.label)) * 0.8


def test_artifact_roundtrip(tmp_path):
    cfg = train_mlp.MLPTrainConfig(hidden=(32,), steps=5, batch_size=32)
    pairs = PairBatch(
        np.zeros(64, np.int32), np.zeros(64, np.int32),
        np.random.default_rng(0).random((64, 16)).astype(np.float32),
        np.random.default_rng(1).random(64).astype(np.float32),
    )
    params, _ = train_mlp.train(cfg, pairs)
    d = artifacts.save_artifact(
        tmp_path / "mlp-v1", model_type="mlp", version="v1",
        params=params, config={"hidden": [32]},
    )
    model, loaded = artifacts.load_mlp(d)
    import jax.numpy as jnp

    x = jnp.asarray(pairs.feats[:4])
    np.testing.assert_allclose(
        np.asarray(model.apply(params, x)), np.asarray(model.apply(loaded, x)), rtol=1e-6
    )


def test_trainer_service_full_loop(run, tmp_path):
    """Upload → train (MLP+GNN) → registry rows → evaluator hot-swap."""

    async def body():
        manager = ManagerServer(db_path=str(tmp_path / "m.db"))
        await manager.start()
        mc = RemoteManagerClient(manager.address)

        svc = TrainerService(
            TrainerConfig(
                model_dir=str(tmp_path / "models"),
                mlp=train_mlp.MLPTrainConfig(hidden=(32, 32), steps=60, batch_size=128),
                gnn=train_gnn.GNNTrainConfig(
                    hidden=32, embed_dim=16, num_layers=2, batch_size=128, warmup_steps=5
                ),
                gnn_steps=20,
            ),
            manager=mc,
        )
        server = RpcServer(host="127.0.0.1", port=0)
        register_trainer(server, svc)
        await server.start()

        # scheduler side: telemetry + announcer (interval irrelevant; upload once)
        store = TelemetryStorage(tmp_path / "telemetry")
        _fill_telemetry(store, n_hosts=10, n_rows=250)
        ann = TrainerAnnouncer(store, server.address, hostname="sch1", scheduler_id=0)
        try:
            out = await ann.upload_once()
            assert out["downloads"] == 250
            await svc.wait_idle()
            assert svc.trains_succeeded == 1, svc.last_result
            res = svc.last_result
            assert "mlp" in res and "gnn" in res, res

            # registry has both, active
            gnn_row = await mc.active_model("gnn", 0)
            mlp_row = await mc.active_model("mlp", 0)
            assert gnn_row["version"] == res["version"] == mlp_row["version"]
            assert gnn_row["evaluation"]["steps"] == 20

            # telemetry cleared after handoff
            assert len(store.downloads.load_all()) == 0

            # evaluator hot-swap path: load artifact like ManagerLink does
            from dragonfly2_tpu.scheduler.manager_link import ManagerLink

            scorer, node_index = ManagerLink._load_scorer(gnn_row["artifact_path"])
            assert scorer.ready and len(node_index) >= 10
            feats = np.random.default_rng(0).random((5, 16)).astype(np.float32)
            scores = scorer.score(feats, child=np.zeros(5, np.int32), parent=np.arange(5, dtype=np.int32))
            assert scores.shape == (5,) and np.isfinite(scores).all()

            # second upload produces a NEW active version
            _fill_telemetry(store, n_hosts=10, n_rows=100, seed=9)
            await asyncio.sleep(1.1)  # version key has second granularity
            await ann.upload_once()
            await svc.wait_idle()
            assert svc.trains_succeeded == 2
            gnn2 = await mc.active_model("gnn", 0)
            assert gnn2["version"] != gnn_row["version"]
            models = await mc.list_models(type="gnn")
            assert sum(m["state"] == "active" for m in models) == 1
        finally:
            await ann.stop()
            await server.stop()
            await mc.close()
            await manager.stop()

    run(body())


def test_trainer_skips_on_thin_data(run, tmp_path):
    async def body():
        svc = TrainerService(TrainerConfig(model_dir=str(tmp_path / "models"), min_pairs=16))
        token = (await svc.train_open({"hostname": "s"}))["token"]
        store = TelemetryStorage(tmp_path / "t")
        _fill_telemetry(store, n_rows=3)
        await svc.train_chunk(
            {"token": token, "kind": "downloads", "data": pack_records(store.downloads.load_all())}
        )
        await svc.train_close({"token": token})
        await svc.wait_idle()
        assert svc.last_result is not None
        assert "mlp" not in svc.last_result and "gnn" not in svc.last_result

        with pytest.raises(KeyError):
            await svc.train_chunk({"token": "bogus", "kind": "downloads", "data": b""})

    run(body())


def test_failed_run_and_device_are_visible_to_a_remote_caller(run, tmp_path):
    """The service survives a training exception, but the caller can see it:
    what raised is in last_result and the manifest, and status names the
    platform/device the trainer process holds (PR 21)."""
    from dragonfly2_tpu.trainer.service import TrainSession

    async def body():
        svc = TrainerService(TrainerConfig(model_dir=str(tmp_path)))

        async def boom(sess):
            raise ValueError("bad graph")

        svc._run_training = boom
        await svc._train(TrainSession("tok"))
        status = await svc.status()
        assert status["last_result"] == {"error": "ValueError: bad graph"}
        assert (status["platform"], status["device_count"]) == ("cpu", 8) and status["device_kind"]
        manifest = (await svc.train_history({"limit": 1}))["runs"][0]
        assert manifest["status"] == "error" and manifest["error"] == "ValueError: bad graph"
        assert manifest["platform"] == "cpu"

    run(body())
