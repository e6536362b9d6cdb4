"""ML-plane observability (ISSUE 15): feature sketches + PSI drift,
decision records + dfml replay, and training-run telemetry.

Clock discipline: every time-sensitive assertion drives an explicit
VirtualClock / now= — no sleeps (the ROADMAP tier-1 wall-clock note), and
the sketch/drift paths are exercised under the same injected clock the
swarm simulator uses, so DF029's virtual-clock contract holds by test.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from dragonfly2_tpu.models.features import FEATURE_DIM, FEATURE_NAMES
from dragonfly2_tpu.observability.sketches import (
    PSI_MAJOR,
    DriftDetector,
    FeatureSketch,
    classify_psi,
    psi,
)
from dragonfly2_tpu.utils.clock import VirtualClock


def _mk_service(**kw):
    from dragonfly2_tpu.scheduler.resource import HostType
    from dragonfly2_tpu.scheduler.service import SchedulerService

    svc = SchedulerService(**kw)
    task = svc.pool.load_or_create_task("t-mlobs", "http://origin/f.bin")
    task.set_metadata(1 << 28, 4 << 20)
    children = []
    for i in range(24):
        h = svc.pool.load_or_create_host(
            f"h{i}", f"10.0.0.{i}", f"host{i}", download_port=8000,
            host_type=HostType.NORMAL,
        )
        h.upload_limit = 100
        p = svc.pool.create_peer(f"p{i}", task, h)
        p.fsm.fire("register")
        p.fsm.fire("download")
        if i < 2:
            children.append(p)
        else:
            for k in range(4):
                p.finished_pieces.set(k)
            p.bump_feat()
    return svc, task, children


# ---------------------------------------------------------------------------
# FeatureSketch


class TestFeatureSketch:
    def test_binning_underflow_overflow_nan(self):
        sk = FeatureSketch(2, names=("a", "b"), bins=4)
        sk.update(np.array([
            [-0.5, 0.0],     # a: underflow,          b: first interior bin
            [0.99, 1.5],     # a: last interior bin,  b: overflow
            [np.nan, 0.5],   # a: NaN -> overflow,    b: interior
        ], np.float32))
        a, b = sk.counts
        assert a[0] == 1            # underflow (< lo)
        assert a[4] == 1            # 0.99 -> last interior bin
        assert a[-1] == 1           # NaN forced into overflow, not underflow
        assert b[1] == 1 and b[-1] == 1 and b[3] == 1
        assert sk.rows == 3

    def test_huge_finite_values_land_in_the_right_tail(self):
        # int64 cast of a huge float wraps to INT64_MIN; the float-space
        # clip must run FIRST so a leaked epoch-ns timestamp reads as
        # OVERFLOW (schema violation, high tail), never underflow
        sk = FeatureSketch(2, names=("a", "b"), bins=4)
        sk.update(np.array([
            [1.7e18, -1.7e18],
            [float("inf"), float("-inf")],
        ], np.float64))
        a, b = sk.counts
        assert a[-1] == 2 and a[0] == 0   # huge positive + inf -> overflow
        assert b[0] == 1                  # huge negative -> underflow
        assert b[-1] == 1                 # -inf is non-finite -> overflow

    def test_memory_bounded_and_vectorized_counts_exact(self):
        sk = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        shape_before = sk.counts.shape
        rng = np.random.default_rng(0)
        total = 0
        for _ in range(10):
            m = rng.random((1000, FEATURE_DIM)).astype(np.float32)
            total += sk.update(m)
        assert sk.counts.shape == shape_before  # bounded by construction
        assert sk.rows == total == 10_000
        # every feature column accounts for every row
        assert (sk.counts.sum(axis=1) == total).all()

    def test_serialization_roundtrip_and_merge(self):
        rng = np.random.default_rng(1)
        sk = FeatureSketch(4, names=("a", "b", "c", "d"))
        sk.update(rng.random((500, 4)))
        back = FeatureSketch.from_dict(json.loads(json.dumps(sk.to_dict())))
        assert back.names == sk.names and back.rows == sk.rows
        assert (back.counts == sk.counts).all()
        other = FeatureSketch(4, names=("a", "b", "c", "d"))
        other.update(rng.random((300, 4)))
        merged_rows = sk.rows + other.rows
        sk.merge(other)
        assert sk.rows == merged_rows
        with pytest.raises(ValueError):
            sk.merge(FeatureSketch(4, bins=7))

    def test_distribution_normalizes(self):
        sk = FeatureSketch(3)
        sk.update(np.random.default_rng(2).random((100, 3)))
        d = sk.distribution()
        assert np.allclose(d.sum(axis=1), 1.0)
        # empty sketch answers uniform, not NaN
        empty = FeatureSketch(3).distribution()
        assert np.allclose(empty.sum(axis=1), 1.0)

    def test_clock_injected_stamps(self):
        clk = VirtualClock(start=5.0, epoch=1_000.0)
        sk = FeatureSketch(2, clock=clk)
        assert sk.created_at == clk.time()
        clk.advance(30.0)
        sk.update(np.zeros((1, 2), np.float32))
        assert sk.updated_at == clk.time()


class TestPsi:
    def test_identical_is_zero_and_shift_is_major(self):
        rng = np.random.default_rng(3)
        ref = FeatureSketch(4)
        ref.update(rng.random((4000, 4)))
        assert (psi(ref, ref) == 0.0).all()
        shifted = FeatureSketch(4)
        shifted.update(rng.random((4000, 4)) * 0.3)  # squashed distribution
        scores = psi(ref, shifted)
        assert (scores > PSI_MAJOR).all()
        with pytest.raises(ValueError):
            psi(ref, FeatureSketch(5))

    def test_single_feature_shift_isolated(self):
        # drift in ONE column must not bleed into the others' scores
        rng = np.random.default_rng(4)
        base = rng.random((5000, 4))
        ref = FeatureSketch(4)
        ref.update(base)
        live_rows = rng.random((5000, 4))
        live_rows[:, 2] = 0.9 + 0.05 * rng.random(5000)  # column 2 shifts
        live = FeatureSketch(4)
        live.update(live_rows)
        scores = psi(ref, live)
        assert scores[2] > PSI_MAJOR
        assert (scores[[0, 1, 3]] < 0.1).all()

    def test_classify(self):
        assert classify_psi(0.01) == "stable"
        assert classify_psi(0.15) == "moderate"
        assert classify_psi(0.5) == "major"
        assert classify_psi(float("nan")) == "invalid"


# ---------------------------------------------------------------------------
# DriftDetector


class TestDriftDetector:
    def _ref(self, rng, n=3000, f=4):
        sk = FeatureSketch(f, names=tuple(f"f{i}" for i in range(f)))
        sk.update(rng.random((n, f)))
        return sk

    def test_dormant_without_reference(self):
        d = DriftDetector(sample_stride=1, export=False)
        for _ in range(10):
            d.observe(np.random.default_rng(0).random((8, 4)))
        assert d.updates == 0 and d.scores() is None

    def test_stride_sampling_exact(self):
        rng = np.random.default_rng(5)
        d = DriftDetector(sample_stride=4, compute_every=1000, export=False)
        d.set_reference(self._ref(rng), version="v1")
        for _ in range(64):
            d.observe(rng.random((8, 4)))
        assert d.updates == 16  # ratio-exact, no rng

    def test_periodic_compute_exports_gauges_virtual_clock(self):
        from dragonfly2_tpu.observability.sketches import (
            FEATURE_DRIFT,
            FEATURE_DRIFT_MAX,
        )

        clk = VirtualClock(start=0.0, epoch=2_000.0)
        rng = np.random.default_rng(6)
        d = DriftDetector(
            sample_stride=1, compute_every=4, clock=clk, export=True
        )
        d.set_reference(self._ref(rng), version="v1")
        clk.advance(100.0)
        for _ in range(4):
            d.observe(rng.random((64, 4)) * 0.25)  # decisively shifted
        assert d.computes == 1
        assert d.computed_at == clk.time()  # virtual stamp, no wall read
        scores = d.scores()
        assert scores is not None and max(scores.values()) > PSI_MAJOR
        assert d.max_score() == pytest.approx(max(scores.values()))
        assert float(FEATURE_DRIFT_MAX.value) >= d.max_score() - 1e-9
        assert float(FEATURE_DRIFT.labels(feature="f0").value) > PSI_MAJOR
        snap = d.snapshot()
        assert snap["reference_version"] == "v1"
        assert snap["psi_max"] > PSI_MAJOR and snap["drifted"]

    def test_reference_swap_resets_live(self):
        rng = np.random.default_rng(7)
        d = DriftDetector(sample_stride=1, compute_every=2, export=False)
        d.set_reference(self._ref(rng), version="v1")
        for _ in range(4):
            d.observe(rng.random((16, 4)))
        assert d.snapshot()["live_rows"] == 64
        d.set_reference(self._ref(rng), version="v2")
        snap = d.snapshot()
        assert snap["live_rows"] == 0 and snap["reference_version"] == "v2"
        assert d.scores() is None  # stale scores cleared with the reference

    def test_live_cap_bounds_rows(self):
        rng = np.random.default_rng(8)
        d = DriftDetector(
            sample_stride=1, compute_every=10_000, live_cap=500, export=False
        )
        d.set_reference(self._ref(rng), version="v1")
        for _ in range(20):
            d.observe(rng.random((100, 4)))
        assert d.snapshot()["live_rows"] <= 600  # halved past the cap

    def test_observe_never_raises(self):
        d = DriftDetector(sample_stride=1, export=False)
        rng = np.random.default_rng(9)
        d.set_reference(self._ref(rng), version="v1")
        d.observe(np.zeros((2, 9)))  # wrong width: swallowed, logged
        assert d.updates == 0 or True  # reaching here IS the assertion


# ---------------------------------------------------------------------------
# DecisionRecorder + service wiring


class TestDecisionRecorder:
    def test_stride_and_ring_bounds(self):
        from dragonfly2_tpu.scheduler.evaluator import DecisionRecorder

        svc, task, children = _mk_service()
        cands = [p for p in task.peers() if p is not children[0]][:8]
        feats = np.random.default_rng(0).random((8, FEATURE_DIM)).astype(np.float32)
        scores = np.random.default_rng(1).random(8).astype(np.float32)
        rec = DecisionRecorder(sample_rate=0.25, capacity=16)
        for _ in range(100):
            rec.maybe_record(children[0], cands, feats, scores)
        st = rec.stats()
        assert st["rounds_seen"] == 100 and st["recorded"] == 25
        assert st["records"] == 16  # bounded ring
        svc.close()

    def test_round_records_match_committed_parents_bit_exact(self, run):
        # the replay contract the mlobs-smoke leg gates on: the recorded
        # chosen top-k IS the round's committed parent list, and the stored
        # scores reproduce it through dfml's replay_topk
        from dragonfly2_tpu.cli.dfml import replay_topk

        svc, task, children = _mk_service(decision_sample_rate=1.0)

        async def go():
            return await svc.reschedule(children[0].id)

        outcome = run(go())
        assert outcome.parents
        doc = svc.decision_records(task_id=task.id, child=children[0].id)
        assert doc["records"], doc["recorder"]
        r = doc["records"][0]
        committed = [p.peer_id for p in outcome.parents]
        assert r["chosen"][: len(committed)] == committed
        replayed = [
            r["parents"][i]["peer"] for i in replay_topk(r["scores"], r["topk"])
        ]
        assert replayed == r["chosen"]
        # the feature matrix rides the record row-for-row with the parents
        assert len(r["feats"]) == len(r["parents"]) == len(r["scores"])
        assert len(r["feats"][0]) == FEATURE_DIM
        assert r["serving_mode"] == "base" and r["model_version"] == ""
        svc.close()

    def test_virtual_clock_stamps_and_filters(self, run):
        clk = VirtualClock(start=0.0, epoch=3_000.0)
        svc, task, children = _mk_service(
            decision_sample_rate=1.0, clock=clk
        )
        clk.advance(42.0)

        async def go():
            await svc.reschedule(children[0].id)
            await svc.reschedule(children[1].id)

        run(go())
        recs = svc.decision_records(child=children[1].id)["records"]
        assert len(recs) >= 1
        assert all(r["child_peer"] == children[1].id for r in recs)
        assert recs[0]["ts"] == clk.time()  # virtual, not wall
        none = svc.decision_records(task_id="no-such-task")["records"]
        assert none == []
        svc.close()

    def test_decision_records_rpc_over_the_wire(self, run):
        from dragonfly2_tpu.rpc.scheduler import (
            RemoteSchedulerClient,
            serve_scheduler,
        )

        svc, task, children = _mk_service(decision_sample_rate=1.0)

        async def go():
            server = serve_scheduler(svc, port=0)
            await server.start()
            client = RemoteSchedulerClient(f"127.0.0.1:{server.port}")
            try:
                await svc.reschedule(children[0].id)
                doc = await client.decision_records(task_id=task.id)
                slim = await client.decision_records(with_features=False)
            finally:
                await client.close()
                await server.stop()
            return doc, slim

        doc, slim = run(go())
        assert doc["records"] and doc["records"][0]["chosen"]
        assert "feats" in doc["records"][0]
        assert slim["records"] and "feats" not in slim["records"][0]
        assert "drift" in doc and "recorder" in doc
        svc.close()

    def test_evaluate_many_paths_record(self):
        # the dispatcher's batch entry records per round too (ml evaluator
        # in base-fallback: every batch round degrades through evaluate())
        from dragonfly2_tpu.scheduler.evaluator import new_evaluator

        svc, task, children = _mk_service(
            evaluator=new_evaluator("ml"), decision_sample_rate=1.0
        )
        cands = [p for p in task.peers() if p not in children][:8]
        outs = svc.evaluator.evaluate_many(
            [(children[0], cands), (children[1], cands)]
        )
        assert len(outs) == 2
        assert svc.decisions.stats()["recorded"] == 2
        svc.close()


# ---------------------------------------------------------------------------
# evaluator drift feed + alert propagation (clock-driven)


class TestDriftThroughEvaluator:
    def test_prepare_feeds_live_sketch_and_alert_fires(self, run):
        from dragonfly2_tpu.observability.alerts import AlertEngine, default_rules
        from dragonfly2_tpu.observability.timeseries import (
            MetricsRecorder,
            build_stats_frame,
            default_registry,
        )
        from dragonfly2_tpu.scheduler.evaluator import new_evaluator

        svc, task, children = _mk_service(evaluator=new_evaluator("ml"))
        cands = [p for p in task.peers() if p not in children][:16]
        svc.drift.sample_stride = 1
        svc.drift.compute_every = 8

        async def serve(n):
            for _ in range(n):
                await svc.reschedule(children[0].id)  # dflint: disable=DF025 each call IS one scheduling round under test, not a batchable fan-out
                await svc.reschedule(children[1].id)  # dflint: disable=DF025 each call IS one scheduling round under test, not a batchable fan-out

        # Warm-up to a STATIONARY serving regime first: retry_norm ramps
        # with schedule_rounds until it saturates at 10 rounds per child, so
        # a reference captured cold would read "drift" on the ramp alone.
        # The detector is dormant (no reference) through the ramp — which
        # also pins the dormancy contract on the real serving path.
        run(serve(12))
        assert svc.drift.updates == 0  # dormant: no reference, no folds
        # Bootstrap the reference FROM the live feed itself (a placeholder
        # reference makes observe() fold) — exactly what a model trained on
        # this regime's telemetry would ship in its artifact sketch.
        svc.drift.set_reference(
            FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES), version="boot"
        )
        run(serve(6))
        assert svc.drift.updates > 0  # _prepare/fallback fed the live sketch
        ref = svc.drift._live
        assert ref is not None and ref.rows > 0
        svc.drift.set_reference(ref, version="vtest")

        run(serve(8))
        stable = svc.drift.compute()
        assert stable is not None and max(stable.values()) < PSI_MAJOR

        # inject the shift: every probe RTT re-centers high — rtt_norm's
        # live distribution departs from the training reference
        rtt_col = FEATURE_NAMES.index("rtt_norm")
        for c in children:
            for p in cands:
                for _ in range(12):
                    svc.topology.enqueue(c.host.id, p.host.id, 900.0)
        run(serve(8))
        shifted = svc.drift.compute()
        assert shifted[FEATURE_NAMES[rtt_col]] > PSI_MAJOR

        # recorder → rules → frame, all at explicit clock times (no sleeps)
        rec = MetricsRecorder(default_registry(), interval=2.0)
        rec.sample_once(now=1000.0)
        rec.sample_once(now=1002.0)
        eng = AlertEngine(rec, rules=default_rules(), export=False)
        firing = eng.evaluate_once(now=1003.0)
        assert "feature_drift" in firing
        frame = build_stats_frame(
            rec, service="scheduler", hostname="t", alerts=eng
        )
        assert frame["rates"]["feature_drift_max"] > PSI_MAJOR
        assert "feature_drift" in frame["alerts"]
        svc.close()


# ---------------------------------------------------------------------------
# training-run telemetry + manifests + artifact sketch


class TestTrainTelemetry:
    def test_hook_counts_and_curve_bounded(self):
        from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

        clk = VirtualClock()
        tel = TrainRunTelemetry("mlp", batch_size=32, clock=clk)
        for i in range(1000):
            clk.advance(0.01)
            tel.on_step(1.0 / (i + 1), 0.5)
        s = tel.summary()
        assert s["steps"] == 1000 and s["examples"] == 32_000
        assert len(s["curve"]) <= 160  # bounded decimation
        assert s["final_loss"] == pytest.approx(1.0 / 1000)
        assert s["steps_per_sec"] == pytest.approx(100.0, rel=0.05)

    def test_steps_per_sec_excludes_setup_and_compile(self):
        # the gap between construction and the FIRST report is XLA setup +
        # compile; folding it in understated short runs 10x+ (review find)
        from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

        clk = VirtualClock()
        tel = TrainRunTelemetry("gnn", batch_size=1, clock=clk)
        clk.advance(30.0)               # "compile" — must not count
        tel.on_step(1.0, steps=10)      # first report (includes compile)
        assert tel.steps_per_sec() is None  # one report = no interval yet
        clk.advance(1.0)
        tel.on_step(0.5, steps=10)      # 10 post-compile steps in 1 s
        assert tel.steps_per_sec() == pytest.approx(10.0)

    def test_mlp_train_reports_steps_and_grad_norm(self):
        from dragonfly2_tpu.trainer import train_mlp
        from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry
        from dragonfly2_tpu.trainer.synthetic import PairBatch

        rng = np.random.default_rng(0)
        n = 256
        pairs = PairBatch(
            np.zeros(n, np.int32), np.ones(n, np.int32),
            rng.random((n, FEATURE_DIM)).astype(np.float32),
            rng.random(n).astype(np.float32),
        )
        cfg = train_mlp.MLPTrainConfig(hidden=(8,), steps=12, batch_size=64)
        tel = TrainRunTelemetry("mlp", batch_size=64)
        _params, ev = train_mlp.train(cfg, pairs, telemetry=tel)
        s = tel.summary()
        assert s["steps"] == 12
        assert s["grad_norm"] is not None and s["grad_norm"] > 0
        assert np.isfinite(s["final_loss"])
        assert np.isfinite(ev["train_mse"])

    def test_run_manifest_and_history(self, run):
        from dragonfly2_tpu.trainer.service import TrainerService, TrainSession

        svc = TrainerService()
        sess = TrainSession("tok", scheduler_hostname="sch-a")
        svc.trains_started = 3
        result = {
            "version": "v77-3", "num_pairs": 120, "num_nodes": 30,
            "build_seconds": 0.01,
            "gnn": {
                "artifact": "/tmp/x", "digest": "d" * 32,
                "evaluation": {"final_loss": 0.05, "steps": 6},
                "telemetry": {
                    "steps": 6, "final_loss": 0.05, "grad_norm": 0.2,
                    "steps_per_sec": 1.5, "curve": [(1, 0.2), (6, 0.05)],
                    "examples": 600,
                },
            },
        }
        svc._note_run(sess, result, 1_000.0, 2.5)
        empty = {"version": "v78-4", "num_pairs": 2, "num_nodes": 4,
                 "build_seconds": 0.01}
        svc._note_run(sess, empty, 1_010.0, 0.1)
        hist = run(svc.train_history({}))
        assert hist["total"] == 2
        newest, oldest = hist["runs"]
        assert newest["status"] == "skipped"  # below-min run is visible
        assert oldest["run_id"] == "v77-3" and oldest["status"] == "ok"
        assert oldest["models"]["gnn"]["final_loss"] == 0.05
        assert oldest["models"]["gnn"]["curve"]
        slim = run(svc.train_history({"with_curves": False}))
        assert "curve" not in slim["runs"][1]["models"]["gnn"]
        # error manifests ride the SAME append path/shape as ok/skipped
        svc._note_run(sess, {"version": "v79-5"}, 1_020.0, 0.2, status="error")
        err = run(svc.train_history({"limit": 1}))["runs"][0]
        assert err["status"] == "error" and err["run_id"] == "v79-5"
        assert "dataset" in err and err["models"] == {}
        # history is bounded
        from dragonfly2_tpu.trainer.service import RUN_HISTORY_CAP

        for i in range(RUN_HISTORY_CAP + 10):
            svc._note_run(sess, empty, 1_020.0 + i, 0.1)
        assert len(svc.run_history) == RUN_HISTORY_CAP

    def test_stats_frame_gains_trainer_keys(self):
        from dragonfly2_tpu.observability.timeseries import (
            MetricsRecorder,
            build_stats_frame,
            default_registry,
        )
        from dragonfly2_tpu.trainer.metrics import (
            TRAIN_LAST_RUN_LOSS,
            TrainRunTelemetry,
        )

        import time as _time

        tel = TrainRunTelemetry("gnn", batch_size=10)
        rec = MetricsRecorder(default_registry(), interval=2.0)
        # explicit now= (no sleeps); anchored near the wall clock because
        # build_stats_frame windows its rates against time.time()
        t1 = _time.time()
        tel.on_step(0.5, 0.1, steps=5)
        TRAIN_LAST_RUN_LOSS.set(0.5)
        rec.sample_once(now=t1 - 10.0)
        tel.on_step(0.25, 0.1, steps=45)
        rec.sample_once(now=t1)
        frame = build_stats_frame(rec, service="trainer", hostname="tr")
        rates = frame["rates"]
        assert rates["train_steps_per_s"] == pytest.approx(4.5, rel=0.01)
        assert rates["train_examples_per_s"] == pytest.approx(45.0, rel=0.01)
        assert rates["train_last_loss"] == 0.5
        assert rates["train_runs_total"] >= 0

    def test_dataset_finalize_freezes_sketch(self):
        from dragonfly2_tpu.trainer.dataset import build_dataset
        from dragonfly2_tpu.trainer.synthetic import synth_telemetry_records

        d, p = synth_telemetry_records(300, 100, 16, seed=2)
        ds = build_dataset(d, p)
        sk = ds.feature_sketch
        assert sk is not None
        assert sk.names == FEATURE_NAMES
        assert sk.rows == ds.num_pairs  # exactly the rows the model fits

    def test_artifact_sketch_digest_covered(self, tmp_path):
        from dragonfly2_tpu.trainer import artifacts

        sk = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        sk.update(np.random.default_rng(3).random((64, FEATURE_DIM)))
        d = tmp_path / "art"
        d.mkdir()
        (d / "params.msgpack").write_bytes(b"fake-params")
        artifacts.save_sketch(d, sk)
        digest = artifacts.artifact_digest(d)
        back = artifacts.load_sketch(d)
        assert back is not None and (back.counts == sk.counts).all()
        artifacts.verify_artifact(d, digest)
        # tamper with ONLY the sketch: the digest must refuse the artifact
        p = d / "sketch.json"
        p.write_text(p.read_text().replace(":", ": ", 1))
        with pytest.raises(artifacts.ArtifactIntegrityError):
            artifacts.verify_artifact(d, digest)
        assert artifacts.load_sketch(tmp_path / "nope") is None

    def test_manager_link_installs_and_clears_reference(self, tmp_path):
        from dragonfly2_tpu.scheduler.manager_link import ManagerLink
        from dragonfly2_tpu.trainer import artifacts

        sk = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        sk.update(np.random.default_rng(4).random((32, FEATURE_DIM)))
        d = tmp_path / "art2"
        d.mkdir()
        artifacts.save_sketch(d, sk)

        class Ev:
            drift = DriftDetector(export=False)

        ev = Ev()
        ManagerLink._install_drift_reference(
            ev, {"artifact_path": str(d), "version": "v9"}
        )
        assert ev.drift.reference_version == "v9"
        assert ev.drift.reference.rows == 32
        # a pre-sketch artifact CLEARS the baseline (never compare live
        # traffic against a previous model's training distribution)
        empty = tmp_path / "art3"
        empty.mkdir()
        ManagerLink._install_drift_reference(
            ev, {"artifact_path": str(empty), "version": "v10"}
        )
        assert ev.drift.reference is None


# ---------------------------------------------------------------------------
# dfml CLI


class TestDfml:
    def test_replay_and_explain_record(self, capsys):
        from dragonfly2_tpu.cli import dfml

        scores = [0.2, 0.9, 0.9, 0.1]
        assert dfml.replay_topk(scores, 2) == [1, 2]  # stable tie-break
        record = {
            "seq": 7, "ts": 123.0, "task_id": "t", "child_peer": "c",
            "child_host": "hc", "topk": 2,
            "parents": [{"peer": f"p{i}", "host": f"h{i}"} for i in range(4)],
            "scores": scores,
            "feats": np.random.default_rng(0)
                       .random((4, FEATURE_DIM)).round(3).tolist(),
            "chosen": ["p1", "p2"],
            "model_version": "", "serving_mode": "base", "trace_id": "",
        }
        assert dfml.explain_record(record) is True
        out = capsys.readouterr().out
        assert "bit-exact" in out and "p1" in out
        # a tampered record (chosen no longer reproduces) must fail replay
        bad = dict(record, chosen=["p3", "p0"])
        assert dfml.explain_record(bad) is False

    def test_sparkline(self):
        from dragonfly2_tpu.cli.dfml import sparkline

        s = sparkline([1.0, 0.5, 0.25, 0.1])
        assert len(s) == 4 and s[0] == "█" and s[-1] == "▁"
        assert sparkline([]) == ""
        assert "!" in sparkline([float("nan"), 1.0, 2.0])
        # the LAST point always renders (stride-and-truncate dropped the
        # tail — an end-of-run divergence was invisible in dfml train)
        curve = [0.5] * 159 + [9.9]
        assert sparkline(curve, width=48)[-1] == "█"

    def test_explain_cli_against_wire_scheduler(self, run, capsys):
        from dragonfly2_tpu.cli import dfml
        from dragonfly2_tpu.rpc.scheduler import serve_scheduler

        svc, task, children = _mk_service(decision_sample_rate=1.0)

        async def go():
            server = serve_scheduler(svc, port=0)
            await server.start()
            outcome = await svc.reschedule(children[0].id)
            import asyncio

            # the CLI owns its own loop: run it on a worker thread against
            # the live server (the dfmodel-test idiom)
            rc = await asyncio.to_thread(
                dfml.main,
                ["explain", "--scheduler", f"127.0.0.1:{server.port}",
                 task.id, children[0].id],
            )
            await server.stop()
            return rc, outcome

        rc, outcome = run(go())
        assert rc == 0
        out = capsys.readouterr().out
        assert "bit-exact" in out
        for p in outcome.parents:
            assert p.peer_id in out
        svc.close()

    def test_train_cli_against_wire_trainer(self, run, capsys):
        from dragonfly2_tpu.cli import dfml
        from dragonfly2_tpu.rpc.core import RpcServer
        from dragonfly2_tpu.rpc.trainer import register_trainer
        from dragonfly2_tpu.observability.gcwatch import GcWindow
        from dragonfly2_tpu.trainer.service import IngestCounts, TrainerService, TrainSession

        svc = TrainerService()
        collected = GcWindow()
        collected.collections, collected.ms, collected.max_ms = [310, 28, 2], 912.5, 870.25
        svc._note_run(
            TrainSession("t", gc=collected, ingest=IngestCounts(
                chunks=266, bytes=315_000_000, rows=1_081_344, decode_s=0.21, fold_s=1.75, merge_s=0.67,
                wait_s=0.52, open_to_close_s=3.18)), {
                "version": "v5-1", "num_pairs": 64, "num_nodes": 12,
                "build_seconds": 0.01,
                "mlp": {
                    "artifact": "/tmp/a", "digest": "e" * 32,
                    "evaluation": {"train_mse": 0.1},
                    "telemetry": {"steps": 10, "final_loss": 0.1,
                                  "grad_norm": 0.3, "steps_per_sec": 5.0,
                                  "curve": [(1, 0.9), (10, 0.1)],
                                  "examples": 100},
                },
                "gnn": {
                    "artifact": "/tmp/g", "digest": "f" * 32,
                    "evaluation": {"final_loss": 0.2},
                    "telemetry": {"steps": 20, "final_loss": 0.2, "grad_norm": 0.1, "steps_per_sec": 17.7,
                                  "curve": [(10, 0.3), (20, 0.2)], "examples": 100,
                                  "calls": {"count": 2, "traced": 0, "first_ms": 550.0, "dispatch_ms_max": 0.8,
                                            "pull_ms_max": 2310.5, "gc_ms": 870.25},
                                  "placement": {"decision": {"rule": "one_device", "devices": 1, "hosts": 40000,
                                                             "rows": 40960, "pad_pct": 2.4}}},
                },
            }, 1_000.0, 1.0,
        )

        async def go():
            server = RpcServer(port=0)
            register_trainer(server, svc)
            await server.start()
            import asyncio

            rc = await asyncio.to_thread(
                dfml.main, ["train", "--trainer", f"127.0.0.1:{server.port}"]
            )
            await server.stop()
            return rc

        assert run(go()) == 0
        out = capsys.readouterr().out
        assert "v5-1" in out and "mlp" in out and "steps=10" in out
        # the GNN's line says whether the run traced its program and what placement placed
        assert "traced=0 first=550.0ms" in out and "hosts=40000 rows=40960 pad=2.4%" in out
        # the upload's cost on the server, the collector's over the cycle and inside the GNN's calls
        assert ("ingest: sessions=1 chunks=266 MB=315.0 rows=1081344 decode=0.21s fold=1.75s merge=0.67s "
                "wait=0.52s open_to_close=3.18s") in out
        assert "gc=912.5ms (max 870.25ms, by generation [310, 28, 2])" in out
        assert "dispatch_max=0.8ms pull_max=2310.5ms gc=870.25ms" in out
