"""check.sh mlobs-smoke leg (ISSUE 15): the ML-plane observability loop
against real seams, end to end.

Boots the in-process cluster (manager RPC server + trainer service + ml
scheduler), runs a REAL train → publish → attach cycle (the artifact ships
the digest-covered training-reference sketch), serves live scheduling
rounds through the attached model, then:

  1. injects a shifted live feature distribution (every probe RTT
     re-centers to 900 ms) and asserts the `feature_drift` alert propagates
     recorder → rule engine → stats frame → manager → `dftop --once
     --json` — the full page path an operator would see;
  2. asserts `dfml explain` (the real CLI subprocess over the scheduler
     RPC) replays a real round's chosen parents EXACTLY — the recorded
     decision reproduces the committed top-k bit-for-bit.

Deterministic: ticks are driven explicitly (no polling loops), sampling
rates are pinned to 1.0, and the drift injection is a decisive re-centering
rather than a threshold-straddling nudge.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

N_HOSTS = 20
N_CHILDREN = 2


def build_pool(svc):
    """Live scheduler pool: 2 children + parent peers over h0..hN, with
    probe/bandwidth telemetry so every feature column is populated."""
    from dragonfly2_tpu.scheduler.resource import HostType

    task = svc.pool.load_or_create_task("t-mlobs", "http://origin/mlobs.bin")
    task.set_metadata(256 << 20, 4 << 20)
    children, parents = [], []
    for i in range(N_HOSTS):
        host = svc.pool.load_or_create_host(
            f"h{i}", f"10.0.0.{i}", f"host{i}", download_port=8000 + i,
            host_type=HostType.NORMAL,
        )
        host.upload_limit = 1000
        p = svc.pool.create_peer(f"peer-{i}", task, host)
        p.fsm.fire("register")
        p.fsm.fire("download")
        if i < N_CHILDREN:
            # saturate retry_norm up front: schedule_rounds ramps to its cap
            # over the first 10 rounds, and the training reference must
            # describe the STEADY regime, not the ramp
            p.schedule_rounds = 12
            children.append(p)
        else:
            for k in range(4):
                p.finished_pieces.set(k)
            p.bump_feat()
            parents.append(p)
    rng = np.random.default_rng(7)
    for c in children:
        for p in parents:
            for _ in range(8):
                svc.topology.enqueue(
                    c.host.id, p.host.id, float(rng.uniform(2.0, 20.0))
                )
            svc.bandwidth.observe(
                p.host.id, c.host.id, float(rng.uniform(2e8, 9e8))
            )
    return task, children, parents


async def warmup_and_harvest(svc, task, children, rounds=16) -> np.ndarray:
    """Serve REAL rounds (base-served; no model yet) and harvest the
    feature rows the rounds actually assembled, straight from the decision
    records — production telemetry's pair_features are stamped from live
    rounds the same way, so the artifact's reference sketch ends up
    describing exactly the serving-time distribution."""
    for _ in range(rounds):
        for c in children:
            await svc.reschedule(c.id)  # dflint: disable=DF025 each call IS one scheduling round under test, not a batchable fan-out
    rows = [
        np.asarray(r["feats"], np.float32)
        for r in svc.decision_records(task_id=task.id, limit=256)["records"]
    ]
    assert rows, "warm-up rounds recorded no decisions"
    return np.concatenate(rows)


def make_telemetry(svc, children, parents, feat_rows: np.ndarray, n_rows=400):
    """Training telemetry over this pool's hosts, pair_features drawn from
    the harvested live rows (warmup_and_harvest)."""
    from dragonfly2_tpu.telemetry.records import DOWNLOAD_DTYPE, PROBE_DTYPE

    rng = np.random.default_rng(11)
    d = np.zeros(n_rows, DOWNLOAD_DTYPE)
    for i in range(n_rows):
        c = children[i % len(children)]
        pi = int(rng.integers(0, len(parents)))
        d[i]["child_host_id"] = c.host.id.encode()
        d[i]["parent_host_id"] = parents[pi].host.id.encode()
        d[i]["success"] = True
        d[i]["bandwidth_bps"] = float(rng.uniform(2e8, 9e8))
        d[i]["pair_features"] = feat_rows[i % len(feat_rows)]
    probes = []
    for c in children:
        for p in parents:
            probes.append((c.host.id.encode(), p.host.id.encode(),
                           float(rng.uniform(2.0, 20.0))))
    pr = np.zeros(len(probes), PROBE_DTYPE)
    for i, (s, dst, rtt) in enumerate(probes):
        pr[i]["src_host_id"] = s
        pr[i]["dst_host_id"] = dst
        pr[i]["rtt_mean_ms"] = rtt
        pr[i]["rtt_std_ms"] = rtt * 0.1
        pr[i]["rtt_min_ms"] = rtt * 0.8
        pr[i]["probe_count"] = 10
    return d, pr


async def run_cli(*argv: str) -> subprocess.CompletedProcess:
    # off-loop: the RPC servers answering these CLIs live on OUR loop
    return await asyncio.to_thread(
        subprocess.run,
        [sys.executable, "-m", *argv],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )


async def main() -> int:
    from dragonfly2_tpu.manager.server import ManagerServer
    from dragonfly2_tpu.observability.alerts import AlertEngine, default_rules
    from dragonfly2_tpu.observability.timeseries import (
        MetricsRecorder,
        build_stats_frame,
        default_registry,
    )
    from dragonfly2_tpu.rpc.manager import RemoteManagerClient
    from dragonfly2_tpu.rpc.scheduler import serve_scheduler
    from dragonfly2_tpu.scheduler.evaluator import new_evaluator
    from dragonfly2_tpu.scheduler.manager_link import ManagerLink
    from dragonfly2_tpu.scheduler.service import SchedulerService
    from dragonfly2_tpu.telemetry.records import pack_records
    from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService

    tmp = Path(tempfile.mkdtemp(prefix="df-mlobs-smoke-"))
    manager = ManagerServer(db_path=str(tmp / "m.db"))
    await manager.start()
    mc = RemoteManagerClient(manager.address)
    svc = SchedulerService(
        evaluator=new_evaluator("ml"), decision_sample_rate=1.0
    )
    svc.drift.sample_stride = 1
    svc.drift.compute_every = 4
    link = ManagerLink(svc, manager.address, hostname="mlobs-sch", port=1)
    sched_server = serve_scheduler(svc, port=0)
    await sched_server.start()
    try:
        task, children, parents = build_pool(svc)
        # warm up to the steady serving regime and harvest ITS feature rows
        # as the training distribution (see warmup_and_harvest)
        feat_rows = await warmup_and_harvest(svc, task, children)

        # ---- train → publish: a REAL run over this pool's telemetry ----
        tcfg = TrainerConfig(
            model_dir=str(tmp / "models"), gnn_steps=6, gnn_steps_per_call=3,
            min_pairs=16, min_probe_rows=8,
        )
        tcfg.mlp = dataclasses.replace(tcfg.mlp, steps=20, hidden=(16,))
        tcfg.gnn = dataclasses.replace(
            tcfg.gnn, hidden=16, embed_dim=8, num_layers=2, batch_size=128
        )
        trainer = TrainerService(tcfg, manager=mc)
        tok = (await trainer.train_open({"hostname": "mlobs-sch"}))["token"]
        d, pr = make_telemetry(svc, children, parents, feat_rows)
        await trainer.train_chunk(
            {"token": tok, "kind": "downloads", "data": pack_records(d)}
        )
        await trainer.train_chunk(
            {"token": tok, "kind": "probes", "data": pack_records(pr)}
        )
        await trainer.train_close({"token": tok})
        await trainer.wait_idle()
        assert trainer.last_result and "gnn" in trainer.last_result, (
            f"train run produced no gnn model: {trainer.last_result}"
        )
        version = trainer.last_result["version"]
        hist = await trainer.train_history({})
        assert hist["runs"] and hist["runs"][0]["status"] == "ok", hist

        # ---- attach (digest-verified; the reference sketch installs) ----
        await link._check_model()
        assert svc.evaluator.serving_version == version, (
            svc.evaluator.serving_version, version,
        )
        assert svc.drift.reference_version == version, (
            "artifact reference sketch did not install"
        )

        # ---- serve: live rounds through the model, quiet drift ----
        for _ in range(12):
            for c in children:
                await c_round(svc, c)
        stable = svc.drift.compute()
        assert stable is not None, "live sketch never fed"
        psi_max_pre = max(stable.values())

        recorder = MetricsRecorder(default_registry(), interval=2.0)
        engine = AlertEngine(recorder, rules=default_rules(), export=False)
        now = time.time()
        recorder.sample_once(now=now - 2.0)
        recorder.sample_once(now=now)
        pre_firing = engine.evaluate_once(now=now)
        assert "feature_drift" not in pre_firing, (
            f"drift alert fired BEFORE the shift (psi_max={psi_max_pre}): "
            f"{pre_firing}"
        )

        # ---- inject the shift: every probe RTT re-centers to 900 ms ----
        for c in children:
            for p in parents:
                for _ in range(16):
                    svc.topology.enqueue(c.host.id, p.host.id, 900.0)
        for _ in range(12):
            for c in children:
                await c_round(svc, c)
        shifted = svc.drift.compute()
        assert shifted["rtt_norm"] > 0.25, (
            f"rtt_norm PSI {shifted['rtt_norm']} did not cross 0.25"
        )

        # ---- recorder → rules → frame → manager → dftop --once --json ----
        now = time.time()
        recorder.sample_once(now=now)
        firing = engine.evaluate_once(now=now + 0.1)
        assert "feature_drift" in firing, firing
        frame = build_stats_frame(
            recorder, service="scheduler", hostname="mlobs-sch",
            alerts=engine,
        )
        assert "feature_drift" in frame["alerts"], frame
        assert frame["rates"]["feature_drift_max"] > 0.25, frame["rates"]
        await mc.keepalive("scheduler", "mlobs-sch", stats=frame)
        top = await run_cli(
            "dragonfly2_tpu.cli.dftop",
            "--manager", manager.address, "--once", "--json",
        )
        assert top.returncode == 0, top.stderr
        doc = json.loads(top.stdout)
        member = next(
            m for m in doc["members"] if m["hostname"] == "mlobs-sch"
        )
        assert "feature_drift" in (member["frame"].get("alerts") or []), member
        assert member["frame"]["rates"]["feature_drift_max"] > 0.25

        # ---- dfml explain replays a real round's chosen parents ----
        outcome = await svc.reschedule(children[0].id)
        assert outcome.parents, "round committed no parents"
        committed = [p.peer_id for p in outcome.parents]
        rec = svc.decision_records(
            task_id=task.id, child=children[0].id, limit=1
        )["records"][0]
        assert rec["chosen"][: len(committed)] == committed, (
            f"recorded chosen {rec['chosen']} != committed {committed}"
        )
        explain = await run_cli(
            "dragonfly2_tpu.cli.dfml", "explain",
            "--scheduler", f"127.0.0.1:{sched_server.port}",
            task.id, children[0].id,
        )
        assert explain.returncode == 0, (explain.stdout, explain.stderr)
        assert "bit-exact" in explain.stdout, explain.stdout
        for pid in committed:
            assert pid in explain.stdout, (pid, explain.stdout)

        print(
            "mlobs smoke ok:",
            {
                "model": version,
                "serving": svc.evaluator.serving_version,
                "psi_max_pre": round(psi_max_pre, 4),
                "rtt_norm_psi_post": round(shifted["rtt_norm"], 3),
                "alert_path": "recorder->rules->frame->manager->dftop",
                "replayed_parents": committed,
            },
        )
        return 0
    finally:
        await sched_server.stop()
        await link.manager.close()
        await mc.close()
        await manager.stop()
        svc.close()


async def c_round(svc, child):
    await svc.reschedule(child.id)


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
