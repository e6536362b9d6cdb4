"""Three schedulers feeding one trainer (`benchmarks/generators/replicas.py`
deals one cluster's records to them): their sessions open side by side and
their chunks interleave, the first close starts a run, the other two fold in
and close while it trains and the drainer coalesces them into one run. What a
run trains on is its pool as it stood at its close, whatever merges land
after (during `finalize` on its worker thread, during the run), entry for
entry against the plain reference's build of the commits up to that close;
the coalesced run's GNN and MLP follow the reference inside the tiny cells'
limits; the manifest says how much of the ingest ran inside a run and whose
uploads the model holds, each by its trace id.

    JAX_PLATFORMS=cpu python -m pytest tests/test_scheduler_replicas.py -q
"""

from __future__ import annotations

import asyncio
import itertools
import json
import sys
import threading
import weakref
from pathlib import Path

import numpy as np
import pytest

from dragonfly2_tpu.observability import tracing
from dragonfly2_tpu.telemetry.records import pack_records, unpack_records
from dragonfly2_tpu.trainer import dataset as datasetlib, metrics as train_metrics, train_gnn, train_mlp
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import post_child  # noqa: E402  (the comparison's own gaps; imports no jax at the top)
import reference  # noqa: E402  (the benchmark's plain reference: imports nothing of the program)
import traffic_driver  # noqa: E402

DEPLOYMENT = json.loads((BENCH / "tests" / "data" / "replicas" / "replicas.json").read_text())
LIMITS = json.loads((BENCH / "tests" / "data" / "tiny" / "limits" / "tiny.retrain.json").read_text())["numbers"]
CLUSTER = DEPLOYMENT["cluster"]
SEED = 2_147_483_711
GNN_STEPS = DEPLOYMENT["optimizer"]["gnn"]["steps_per_call"]  # one scan call: what the comparison follows
MLP_STEPS = DEPLOYMENT["steps"]["mlp_steps"]


def _trips(feeder: dict) -> list[tuple[str, bytes]]:
    rows = CLUSTER["chunk_rows"]
    return [(kind, pack_records(feeder[kind][s : s + rows]))
            for kind in ("downloads", "probes") for s in range(0, len(feeder[kind]), rows)]


def _service(tmp_path) -> TrainerService:
    """The trainer as `--gnn-hidden <the deployment's>` makes it, two scan calls of GNN steps (a gap between)."""
    m = DEPLOYMENT["model"]
    return TrainerService(TrainerConfig(
        model_dir=str(tmp_path / "models"), gnn_steps=2 * GNN_STEPS,
        mlp=train_mlp.MLPTrainConfig(steps=MLP_STEPS),
        gnn=train_gnn.GNNTrainConfig(hidden=m["hidden"], embed_dim=m["embed_dim"], batch_size=m["pair_batch"]),
    ))


@pytest.fixture(scope="module")
def cycle(tmp_path_factory):
    """One cycle through TrainerService: three sessions open, the first
    chunk of each, then feeder 0's rest and its close (run A); the others'
    rest interleaved and feeder 1's close while run A's `finalize` is held on
    its worker thread, feeder 2's close while run A's GNN is held; the
    drainer coalesces both into run B. Returns what the cycle saw."""
    tmp_path = tmp_path_factory.mktemp("replicas")
    feeders = traffic_driver.load_file(BENCH / "generators" / "replicas.py").generate(CLUSTER, SEED)
    trips = [_trips(f) for f in feeders]
    svc = _service(tmp_path)
    # one number a telemetry sink (a model's run), kept past the sink's life, as trainer_child.py numbers them
    datasets, steps, sinks, numbers = [], [], weakref.WeakKeyDictionary(), itertools.count()
    in_finalize, finalize_go = threading.Event(), threading.Event()
    finalize, on_step, train_async = datasetlib.FrozenIngest.finalize, train_metrics.TrainRunTelemetry.on_step, train_gnn.train_async

    def held_finalize(self, **kw):
        if not datasets:
            in_finalize.set()
            assert finalize_go.wait(60)
        datasets.append(finalize(self, **kw))
        return datasets[-1]

    def recording(sink, loss, grad_norm=None, **kw):
        on_step(sink, loss, grad_norm, **kw)
        if sink not in sinks:
            sinks[sink] = next(numbers)
        run = sinks[sink]
        steps.append((0.0, run, sink.model, sink.steps, float(loss), float(grad_norm)))

    async def body(in_gnn: asyncio.Event, gnn_go: asyncio.Event) -> dict:
        tokens = [(await svc.train_open({"hostname": f["hostname"], "scheduler_id": f["scheduler_id"]}))["token"]
                  for f in feeders]
        seen = {"opened": await svc.status()}
        traces = [svc._sessions[t].span.trace_id for t in tokens]

        async def chunk(k: int, trip: tuple[str, bytes]) -> None:
            await svc.train_chunk({"token": tokens[k], "kind": trip[0], "data": trip[1]})

        for k in range(3):
            await chunk(k, trips[k][0])
        for trip in trips[0][1:]:
            await chunk(0, trip)
        await svc.train_close({"token": tokens[0]})
        assert await asyncio.to_thread(in_finalize.wait, 60)
        for turn in itertools.zip_longest(trips[1][1:], trips[2][1:]):
            await asyncio.gather(*(chunk(k, trip) for k, trip in enumerate(turn, 1) if trip))
        seen["late_close_1"] = await svc.status()
        await svc.train_close({"token": tokens[1]})  # merges while run A's finalize runs
        finalize_go.set()
        await asyncio.wait_for(in_gnn.wait(), 60)
        seen["late_close_2"] = await svc.status()
        await svc.train_close({"token": tokens[2]})  # merges while run A trains
        gnn_go.set()
        await svc.wait_idle()
        seen["done"] = await svc.status()
        return {"seen": seen, "traces": traces}

    def run() -> dict:
        in_gnn, gnn_go = asyncio.Event(), asyncio.Event()

        async def held_gnn(*a, **kw):
            if not in_gnn.is_set():
                in_gnn.set()
                await gnn_go.wait()
            return await train_async(*a, **kw)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(datasetlib.FrozenIngest, "finalize", held_finalize)
            mp.setattr(train_metrics.TrainRunTelemetry, "on_step", recording)
            mp.setattr(train_gnn, "train_async", held_gnn)
            mp.setattr(tracing.default_tracer(), "sample_rate", 1.0)
            return asyncio.run(body(in_gnn, gnn_go))

    out = run()
    return {**out, "svc": svc, "feeders": feeders, "trips": trips, "datasets": datasets,
            "manifests": list(svc.run_history), "program": {"step_events": steps},
            "spans": tracing.default_tracer().finished()}


def _reference(feeders: list, commits: list[int]) -> dict:
    return reference.build_dataset(commits=[(feeders[i]["downloads"], feeders[i]["probes"]) for i in commits],
                                   num_neighbors=DEPLOYMENT["model"]["num_neighbors"],
                                   chunk_rows=CLUSTER["chunk_rows"], pool_rows_cap=CLUSTER["pool_rows_cap"])


def _mismatch(got: dict, want: dict) -> dict:
    """Entries that differ, as post_child.py counts them: exact but for the
    float features, which count past two units in their last place."""
    out = {}
    for key in ("neighbors", "mask", "node_feats", "edge_feats"):
        g, w = np.asarray(got[key]), want[key]  # dflint: disable=DF033 one array a table, four tables
        if g.shape != w.shape:
            out[key] = f"shape {g.shape} != {w.shape}"
        elif key in ("neighbors", "mask"):
            out[key] = int(np.count_nonzero(g != w))
        else:
            ulps = 2.0 * np.spacing(np.abs(w).astype(np.float32)).astype(np.float64)
            out[key] = int(np.count_nonzero(np.abs(g.astype(np.float64) - w) > ulps))
    out["hosts"] = int(got["hosts"] != {h.decode(): i for i, h in enumerate(want["hosts"].tolist())})
    return out


def test_three_sessions_side_by_side_train_twice_the_late_closes_coalesced(cycle):
    seen = cycle["seen"]
    assert seen["opened"]["open_sessions"] == 3 and not seen["opened"]["training"]
    # both late closes landed while run A trained: it had started, run B had not
    for key in ("late_close_1", "late_close_2"):
        assert seen[key]["training"] and seen[key]["trains_started"] == 1
    done = seen["done"]
    assert (done["trains_started"], done["trains_succeeded"], done["trains_coalesced"]) == (2, 2, 1)
    assert (done["open_sessions"], done["queue_depth"], done["pool_rotations"]) == (0, 0, 0)
    a, b = cycle["manifests"]
    assert (a["ingest"]["sessions"], b["ingest"]["sessions"]) == (1, 2)
    assert (a["scheduler"], b["scheduler"]) == ("scheduler-0", "scheduler-2")
    assert b["ingest"]["chunks"] == sum(len(t) for t in cycle["trips"][1:])


@pytest.mark.parametrize("run, commits", [(0, [0]), (1, [0, 1, 2])], ids=["run_a", "coalesced_run_b"])
def test_a_runs_dataset_is_its_pool_as_it_stood_at_its_close(cycle, run, commits):
    """Run A froze its pool at feeder 0's close; feeder 1's merge landed while
    its finalize ran and feeder 2's while it trained. Its dataset, and the
    graph it published after both, are the reference's build of feeder 0's
    commit alone; run B's of the three commits in their order."""
    ds, want = cycle["datasets"][run], _reference(cycle["feeders"], commits)
    got = {"neighbors": ds.graph.neighbors, "mask": ds.graph.mask, "node_feats": ds.graph.node_feats,
           "edge_feats": ds.graph.edge_feats, "hosts": {h.decode(): i for h, i in ds.host_index.items()}}
    assert _mismatch(got, want) == dict.fromkeys(("neighbors", "mask", "node_feats", "edge_feats", "hosts"), 0)
    for key, column in zip(("child", "parent", "feats", "label"), ds.pairs):
        np.testing.assert_array_equal(column, want["pairs"][key])
    artifact = Path(cycle["manifests"][run]["models"]["gnn"]["artifact"])
    published = reference.read_graph(artifact)
    assert _mismatch(published, want) == dict.fromkeys(("neighbors", "mask", "node_feats", "edge_feats", "hosts"), 0)
    assert cycle["manifests"][run]["dataset"]["pairs"] == len(want["pairs"]["child"]) == sum(
        CLUSTER["pair_rows_per_scheduler"][i] for i in commits)


def test_a_frozen_pool_keeps_what_it_froze_through_later_merges_and_evictions():
    """The accumulator alone: merges of new hosts and edges and a pair cap
    that evicts the frozen chunks from the live pool leave a snapshot's
    finalize what it was at the freeze."""
    feeders = traffic_driver.load_file(BENCH / "generators" / "replicas.py").generate(CLUSTER, SEED + 1)

    def session(f: dict) -> datasetlib.DatasetAccumulator:
        acc = datasetlib.DatasetAccumulator()
        for kind, data in _trips(f):
            getattr(acc, f"add_{kind}")(unpack_records(data))
        return acc

    pool = datasetlib.DatasetAccumulator(max_pair_rows=CLUSTER["pair_rows_per_scheduler"][0])
    pool.merge_from(session(feeders[0]))
    frozen = pool.freeze()
    before = frozen.finalize()
    for f in feeders[1:]:
        pool.merge_from(session(f))  # new edge keys, hosts, stats; feeder 0's chunks evicted
    assert pool.pair_rows < CLUSTER["pair_rows_per_scheduler"][0] + sum(CLUSTER["pair_rows_per_scheduler"][1:])
    after = frozen.finalize()
    for x, y in zip((before.graph, before.pairs), (after.graph, after.pairs)):
        for u, v in zip(x, y):
            np.testing.assert_array_equal(u, v)
    assert before.host_index == after.host_index
    want = _reference(feeders, [0])
    np.testing.assert_array_equal(after.graph.neighbors, want["neighbors"])
    np.testing.assert_array_equal(after.pairs.child, want["pairs"]["child"])


def test_the_coalesced_run_follows_the_reference_inside_the_tiny_limits(cycle):
    """Run B (the pool of the three commits) on the program's seeded initial
    weights: its first scan call's losses and gradient norms and its MLP loop
    against the plain reference's, and its published MLP weights' change."""
    want = _reference(cycle["feeders"], [0, 1, 2])
    ref = reference.follow_steps(DEPLOYMENT, want, GNN_STEPS)
    reports = post_child.step_reports(cycle["program"], "gnn", GNN_STEPS)
    assert len(reports) == 2 and [n for n, _, _ in reports[1]] == list(range(1, GNN_STEPS + 1))
    gaps = post_child.trajectory_gaps([r[1] for r in reports[1]], [r[2] for r in reports[1]], ref)
    mlp_ref = reference.follow_mlp(DEPLOYMENT, want, MLP_STEPS)
    mlp_reports = post_child.step_reports(cycle["program"], "mlp", MLP_STEPS)[1]
    published = reference.read_params(Path(cycle["manifests"][1]["models"]["mlp"]["artifact"]))
    gaps.update(post_child.mlp_gaps(mlp_reports, published, mlp_ref, reference))
    assert {k: v <= LIMITS[k]["limit"] for k, v in gaps.items()} == dict.fromkeys(gaps, True), gaps


def test_the_manifest_counts_the_ingest_that_ran_inside_a_run(cycle):
    a, b = (m["ingest"] for m in cycle["manifests"])
    # feeder 0 folded and closed before any run: nothing inside one
    assert (a["in_run_s"], a["chunks_in_run"]) == (0, 0)
    # feeders 1 and 2: every chunk after their first, and both closes, while run A trained
    assert b["chunks_in_run"] == sum(len(t) - 1 for t in cycle["trips"][1:])
    assert 0 < b["in_run_s"] <= b["decode_s"] + b["fold_s"] + b["merge_s"]
    # and run A's GNN calls, which began after both closes, shared the loop with none
    calls = cycle["manifests"][0]["models"]["gnn"]["calls"]
    assert (calls["in_ingest"], calls["gap_ms_in_ingest"]) == ([], None) and calls["gap_ms_clear"] > 0


def test_the_coalesced_run_names_every_upload_it_holds_by_its_trace(cycle):
    """Run B's pool holds the three commits: its manifest names the three
    schedulers and the trace of each one's upload; its `trainer.train_run`
    span (in feeder 2's trace) carries the other two."""
    traces = cycle["traces"]
    assert len(set(traces)) == 3 and "0" * 32 not in traces
    a, b = (m["ingest"] for m in cycle["manifests"])
    assert b["schedulers"] == ["scheduler-0", "scheduler-1", "scheduler-2"] and b["traces"] == traces
    assert a["schedulers"] == ["scheduler-0"] and a["traces"] == traces[:1]
    (run_b,) = (s.to_dict() for s in cycle["spans"] if s.name == "trainer.train_run" and s.trace_id == traces[2])
    assert run_b["attrs"]["upload_traces"].split(",") == traces[:2]
    (run_a,) = (s.to_dict() for s in cycle["spans"] if s.name == "trainer.train_run" and s.trace_id == traces[0])
    assert "upload_traces" not in run_a["attrs"]


def test_a_scan_call_that_shared_the_loop_with_ingest_is_marked_and_its_gap_counted_apart():
    """`TrainRunTelemetry.on_calls` with the loop's ingest handlers: a call is
    marked where one ran from its start to the next call's enqueue, and the
    gaps after marked and unmarked calls have their own medians."""
    tel = train_metrics.TrainRunTelemetry("gnn")
    tel.loop_ingest = [(0.0095, 0.0105), (0.0305, 0.0306)]
    calls = [(0.0, 0.001, 0.010), (0.011, 0.014, 0.020), (0.021, 0.022, 0.030), (0.031, 0.0325, 0.040)]
    tel.on_calls(calls, traced=0, first_steps=1)
    assert tel.calls["in_ingest"] == [0, 2]
    # gaps: after call 0, 14 - 10 = 4 ms (marked); after 1, 2 ms; after 2, 2.5 ms (marked)
    assert (tel.calls["gap_ms_in_ingest"], tel.calls["gap_ms_clear"]) == (3.25, 2.0)
    plain = train_metrics.TrainRunTelemetry("mlp")
    plain.on_calls(calls, traced=0, first_steps=1)
    assert "in_ingest" not in plain.calls


def test_a_run_decides_whether_to_train_the_gnn_by_its_own_snapshot(tmp_path):
    """Feeder 0 sends downloads alone and closes; feeder 1's probes merge into
    the pool while run A's finalize runs. Run A's snapshot holds no probe row,
    so it trains the MLP and no GNN, though the live pool has probes by then;
    the coalesced run B, whose snapshot holds them, trains both."""
    feeders = traffic_driver.load_file(BENCH / "generators" / "replicas.py").generate(CLUSTER, SEED)
    svc = TrainerService(TrainerConfig(
        model_dir=str(tmp_path / "models"), gnn_steps=2, gnn_steps_per_call=2, min_probe_rows=1,
        mlp=train_mlp.MLPTrainConfig(steps=2), gnn=train_gnn.GNNTrainConfig(hidden=16, embed_dim=8, batch_size=32),
    ))
    in_finalize, go = threading.Event(), threading.Event()
    finalize = datasetlib.FrozenIngest.finalize

    def held(self, **kw):
        if not in_finalize.is_set():
            in_finalize.set()
            assert go.wait(60)
        return finalize(self, **kw)

    async def body():
        tokens = [(await svc.train_open({"hostname": f["hostname"]}))["token"] for f in feeders[:2]]
        for kind, data in _trips({**feeders[0], "probes": feeders[0]["probes"][:0]}):
            await svc.train_chunk({"token": tokens[0], "kind": kind, "data": data})  # dflint: disable=DF025 an upload's chunks, as the announcer sends them
        for kind, data in _trips(feeders[1]):
            await svc.train_chunk({"token": tokens[1], "kind": kind, "data": data})  # dflint: disable=DF025 an upload's chunks, as the announcer sends them
        await svc.train_close({"token": tokens[0]})
        assert await asyncio.to_thread(in_finalize.wait, 60)
        await svc.train_close({"token": tokens[1]})
        go.set()
        await svc.wait_idle()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(datasetlib.FrozenIngest, "finalize", held)
        asyncio.run(body())
    a, b = svc.run_history
    assert (sorted(a["models"]), sorted(b["models"])) == (["mlp"], ["gnn", "mlp"])
