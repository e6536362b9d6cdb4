"""The trainer's spans and always-on counts: the span tree of one upload and
the training run it queued, the bridge onto a profiler's clock
(`Tracer.annotate`), the monotonic duration, the upload's cost and the
collector's pauses, and the scan loop's call summary in the run manifest."""

from __future__ import annotations

import gc
import time
from collections import Counter

import jax
import numpy as np
import pytest

from dragonfly2_tpu.observability import tracing
from dragonfly2_tpu.observability.gcwatch import default_watch
from dragonfly2_tpu.telemetry import TelemetryStorage
from dragonfly2_tpu.telemetry.records import pack_records, unpack_records
from dragonfly2_tpu.trainer import metrics as train_metrics, train_gnn, train_mlp
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
from test_trainer_service import _fill_telemetry

GNN_STEPS, STEPS_PER_CALL = 12, 4
# the upload's chunks: downloads in three, probes in two
SPLITS = {"downloads": 3, "probes": 2}


def _chunks(store):
    return [(kind, pack_records(part)) for kind, n in SPLITS.items()
            for part in np.array_split(getattr(store, kind).load_all(), n)]


async def _upload(svc, store, *, wait=True):
    """One upload of the store's records through the service, and the run it starts."""
    token = (await svc.train_open({"hostname": "s"}))["token"]
    for kind, data in _chunks(store):
        await svc.train_chunk({"token": token, "kind": kind, "data": data})  # dflint: disable=DF025 the upload's chunks, counted
    await svc.train_close({"token": token})
    if wait:
        await svc.wait_idle()


def _service(tmp_path, **kw):
    return TrainerService(TrainerConfig(
        model_dir=str(tmp_path / "models"),
        mlp=train_mlp.MLPTrainConfig(hidden=(16, 16), steps=20, batch_size=64),
        gnn=train_gnn.GNNTrainConfig(hidden=16, embed_dim=8, num_layers=2, batch_size=64, warmup_steps=2),
        gnn_steps=GNN_STEPS, gnn_steps_per_call=STEPS_PER_CALL, **kw,
    ))


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One tiny upload and run through TrainerService (MLP + GNN, no
    manager), with the collector watched and one full collection made while
    the GNN's calls run: the run's manifest, the spans of its trace, and the
    upload's chunks."""
    import asyncio

    tmp_path = tmp_path_factory.mktemp("spans")
    # whatever ran in this process before: the run builds its scan programs
    train_gnn._kept.clear()
    train_mlp._scan_steps.clear_cache()
    svc = _service(tmp_path)
    store = TelemetryStorage(tmp_path / "telemetry")
    _fill_telemetry(store, n_hosts=10, n_rows=200)
    on_step = train_metrics.TrainRunTelemetry.on_step

    def collecting(self, *a, **kw):
        # one generation-2 collection between the GNN's second and third call
        if self.model == "gnn" and self.steps == 2 * STEPS_PER_CALL - 1:
            gc.collect()
        on_step(self, *a, **kw)

    watch = default_watch()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train_metrics.TrainRunTelemetry, "on_step", collecting)
        watch.install(tracing.default_tracer())
        try:
            asyncio.run(_upload(svc, store))
        finally:
            watch.uninstall()
    assert svc.trains_succeeded == 1, svc.last_result
    spans = [s.to_dict() for s in tracing.default_tracer().finished()]
    root = [s for s in spans if s["name"] == "trainer.train_run"][-1]
    return svc.run_history[-1], [s for s in spans if s["trace_id"] == root["trace_id"]], _chunks(store)


def test_a_training_run_leaves_the_span_tree(trained):
    _, spans, _ = trained
    by_id = {s["span_id"]: s for s in spans}

    def path(span):
        names = [span["name"]]
        while span["parent_id"] in by_id:
            span = by_id[span["parent_id"]]
            names.append(span["name"])
        return " < ".join(names)

    calls = GNN_STEPS // STEPS_PER_CALL
    # the upload and the run it queued are one trace: `trainer.ingest` is its root
    run = "trainer.train_run < trainer.ingest"
    run_gnn = f"trainer.train_gnn < {run}"
    assert Counter(path(s) for s in spans if s["name"].startswith(("trainer.gnn", "trainer.export", "trainer.ingest"))) == {
        "trainer.ingest": 1,
        "trainer.ingest.chunk < trainer.ingest": sum(SPLITS.values()),
        "trainer.ingest.merge < trainer.ingest": 1,
        f"trainer.gnn.setup < {run_gnn}": 1,
        f"trainer.gnn.place < trainer.gnn.setup < {run_gnn}": 1,
        f"trainer.gnn.call < {run_gnn}": calls,
        f"trainer.gnn.dispatch < trainer.gnn.call < {run_gnn}": calls,
        f"trainer.gnn.pull < trainer.gnn.call < {run_gnn}": calls,
        f"trainer.export < {run}": 2,
        f"trainer.export.native < trainer.export < {run}": 1,
    }
    call_spans = sorted((s for s in spans if s["name"] == "trainer.gnn.call"), key=lambda s: s["attrs"]["index"])
    assert [s["attrs"]["index"] for s in call_spans] == list(range(calls))
    assert {s["attrs"]["steps"] for s in call_spans} == {STEPS_PER_CALL}
    # placement says the hosts it was given and the rows it placed them at (a rung of the ladder)
    (place,) = (s["attrs"] for s in spans if s["name"] == "trainer.gnn.place")
    nodes = next(s["attrs"]["nodes"] for s in spans if s["name"] == "trainer.train_gnn")
    assert (place["hosts"], place["rows"]) == (nodes, 256) and nodes < 256
    assert place["data"] == len(jax.devices()) and "model" not in place  # the one axis, every device on it
    assert sorted(s["attrs"]["model"] for s in spans if s["name"] == "trainer.export") == ["gnn", "mlp"]


def test_the_manifest_counts_the_calls_and_times_the_export(trained):
    manifest, spans, _ = trained
    calls = manifest["models"]["gnn"]["calls"]
    assert set(calls) == {
        "count", "traced", "first_ms", "period_ms_p50", "period_ms_max", "turn_ms_p50", "turn_ms_max", "stall_ms",
        "dispatch_ms_max", "pull_ms_max", "gc_ms", "in_ingest", "gap_ms_in_ingest", "gap_ms_clear"}
    # from the second call on, each on one side of the enqueue: neither longer than the longest period
    assert 0 < calls["dispatch_ms_max"] < calls["period_ms_max"] and 0 < calls["pull_ms_max"] < calls["period_ms_max"]
    assert calls["count"] == GNN_STEPS // STEPS_PER_CALL
    # the run built its scan program: one trace, and a first call (trace, compile, steps) longer than any period
    assert calls["traced"] == 1 and calls["first_ms"] > calls["period_ms_max"]
    assert calls["stall_ms"] >= 0 and 0 < calls["turn_ms_p50"] <= calls["turn_ms_max"] < calls["period_ms_max"]
    # the upload had closed before the run began: no call shared the loop with ingest
    assert (calls["in_ingest"], calls["gap_ms_in_ingest"]) == ([], None) and calls["gap_ms_clear"] > 0
    # the MLP loop's 20 steps are one scan call, which this run traced: nothing to pace, no loop to share
    mlp_calls = manifest["models"]["mlp"]["calls"]
    assert set(mlp_calls) == set(calls) - {"in_ingest", "gap_ms_in_ingest", "gap_ms_clear"}
    assert (mlp_calls["count"], mlp_calls["traced"]) == (1, 1)
    assert mlp_calls["first_ms"] > 0 and mlp_calls["period_ms_p50"] is None and mlp_calls["stall_ms"] == 0
    assert mlp_calls["dispatch_ms_max"] is None and mlp_calls["pull_ms_max"] is None
    for model in ("mlp", "gnn"):
        seconds = manifest["models"][model]["evaluation"]["export_seconds"]
        span = next(s for s in spans if s["name"] == "trainer.export" and s["attrs"]["model"] == model)
        assert seconds == pytest.approx(span["duration_ms"] / 1e3, abs=0.05) and seconds > 0


def test_a_warm_service_retrains_on_the_scan_program_it_kept(tmp_path):
    """Two uploads of the same records into one service whose pair pool is at
    its cap (as the benchmark's retrain cell sends them): the second run's
    manifest says it traced nothing, and its first call is no longer the
    long one."""
    import asyncio

    train_gnn._kept.clear()
    train_mlp._scan_steps.clear_cache()
    svc = TrainerService(TrainerConfig(
        model_dir=str(tmp_path / "models"), pool_rows=64,
        mlp=train_mlp.MLPTrainConfig(hidden=(16, 16), steps=4, batch_size=64),
        gnn=train_gnn.GNNTrainConfig(hidden=16, embed_dim=8, num_layers=2, batch_size=64, warmup_steps=2),
        gnn_steps=GNN_STEPS, gnn_steps_per_call=STEPS_PER_CALL,
    ))
    store = TelemetryStorage(tmp_path / "telemetry")
    _fill_telemetry(store, n_hosts=10, n_rows=200)

    async def body():
        await _upload(svc, store)
        await _upload(svc, store)

    asyncio.run(body())
    assert svc.trains_succeeded == 2, svc.last_result
    cold, warm = (m["models"]["gnn"] for m in list(svc.run_history)[-2:])
    assert cold["placement"]["graph"] == warm["placement"]["graph"] and cold["steps"] == warm["steps"] == GNN_STEPS
    assert cold["calls"]["traced"] == 1 and warm["calls"]["traced"] == 0
    assert warm["calls"]["first_ms"] < cold["calls"]["first_ms"] / 2
    # and so does the MLP's loop: the pool's rows stayed, `jax.jit`'s own cache has the scan
    cold, warm = (m["models"]["mlp"]["calls"] for m in list(svc.run_history)[-2:])
    assert (cold["count"], cold["traced"]) == (1, 1) and (warm["count"], warm["traced"]) == (1, 0)
    assert warm["first_ms"] < cold["first_ms"] / 2


@pytest.mark.parametrize("calls,traced,gc_ms,expected,rate", [
    # the first call of a run that builds its program compiles: periods,
    # dispatches and pulls count from the second call, the first call's start
    # to end is first_ms; the rate is the 40 steps after the first call over
    # the second call's start to the last one's end
    ([(0.0, 0.9, 1.0), (1.1, 1.2, 2.1), (2.2, 2.3, 3.2), (5.2, 5.9, 6.2), (6.3, 6.4, 7.3)], 0, 12.5,
     {"count": 5, "traced": 0, "first_ms": 1000.0, "period_ms_p50": 1100.0, "period_ms_max": 3000.0,
      "turn_ms_p50": 100.0, "turn_ms_max": 2000.0, "stall_ms": 1350.0,
      "dispatch_ms_max": 700.0, "pull_ms_max": 900.0, "gc_ms": 12.5}, 40 / 6.2),
    ([(0.0, 8.0, 9.0), (9.5, 9.6, 10.0)], 1, None,
     {"count": 2, "traced": 1, "first_ms": 9000.0, "period_ms_p50": None, "period_ms_max": None,
      "turn_ms_p50": 500.0, "turn_ms_max": 500.0, "stall_ms": 0.0,
      "dispatch_ms_max": 100.0, "pull_ms_max": 400.0, "gc_ms": None}, 10 / 0.5),
    ([(2.0, 2.2, 2.25)], 1, 0.0,
     {"count": 1, "traced": 1, "first_ms": 250.0, "period_ms_p50": None, "period_ms_max": None,
      "turn_ms_p50": None, "turn_ms_max": None, "stall_ms": 0.0,
      "dispatch_ms_max": None, "pull_ms_max": None, "gc_ms": 0.0}, None),
    ([], 0, None, {"count": 0, "traced": 0, "first_ms": None, "period_ms_p50": None, "period_ms_max": None,
                   "turn_ms_p50": None, "turn_ms_max": None, "stall_ms": 0.0,
                   "dispatch_ms_max": None, "pull_ms_max": None, "gc_ms": None}, None),
])
def test_call_summary_arithmetic(calls, traced, gc_ms, expected, rate):
    sink = train_metrics.TrainRunTelemetry("gnn")
    for _ in calls:
        sink.on_step(0.5, steps=10)
    sink.on_calls(calls, traced=traced, first_steps=10, gc_ms=gc_ms)
    summary = sink.summary()
    assert summary["calls"] == pytest.approx(expected)
    # once the calls are in, the rate is theirs (none where one call leaves no interval)
    assert summary["steps_per_sec"] == (None if rate is None else pytest.approx(round(rate, 2)))


class _Recorder:
    def __init__(self):
        self.events = []

    def __call__(self, name):
        events = self.events

        class _Annotation:
            def __enter__(self):
                events.append(("enter", name))

            def __exit__(self, *exc):
                events.append(("exit", name))

        return _Annotation()


@pytest.mark.parametrize("sample_rate,expected", [
    (1.0, [("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]),
    (0.0, []),
])
def test_annotate_hook_brackets_sampled_spans_only(sample_rate, expected):
    recorder = _Recorder()
    tracer = tracing.Tracer(sample_rate=sample_rate, annotate=recorder)
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
    assert recorder.events == expected
    assert len(tracer.finished()) == len(expected) // 2


def test_annotation_is_left_when_the_span_raises():
    recorder = _Recorder()
    tracer = tracing.Tracer(annotate=recorder)
    with pytest.raises(ValueError):
        with tracer.span("failing"):
            raise ValueError("boom")
    assert recorder.events == [("enter", "failing"), ("exit", "failing")]
    assert tracer.finished()[0].status == "error"


def test_span_duration_survives_a_step_of_the_wall_clock(monkeypatch):
    tracer = tracing.Tracer()
    wall = iter([1_000.0] + [5_000.0] * 50)   # the wall clock jumps 4,000 s mid-span
    monkeypatch.setattr(time, "time", lambda: next(wall))
    with tracer.span("stepped") as span:
        pass
    assert span.start == 1_000.0                 # the export's unix time
    assert 0 <= span.duration_ms < 1_000          # from the monotonic clock
    assert span.end == pytest.approx(span.start + span.duration_ms / 1e3, abs=1e-3)
    assert span.to_dict()["duration_ms"] == span.duration_ms


def test_the_manifest_counts_the_upload(trained):
    manifest, spans, chunks = trained
    ingest = manifest["ingest"]
    assert set(ingest) == {"sessions", "chunks", "bytes", "rows", "decode_s", "fold_s", "merge_s", "wait_s",
                           "open_to_close_s", "in_run_s", "chunks_in_run", "schedulers", "traces",
                           "keys_looked_up", "keys_admitted", "collisions"}
    # no run was training while the upload came in; the pool holds this one upload, whose trace this is
    assert (ingest["in_run_s"], ingest["chunks_in_run"]) == (0, 0)
    (root,) = (s for s in spans if s["name"] == "trainer.ingest")
    assert (ingest["schedulers"], ingest["traces"]) == (["s"], [root["trace_id"]])
    assert (ingest["sessions"], ingest["chunks"]) == (1, len(chunks))
    assert ingest["bytes"] == sum(len(data) for _, data in chunks)
    assert ingest["rows"] == sum(len(unpack_records(data)) for _, data in chunks)
    stages = ingest["decode_s"], ingest["fold_s"], ingest["merge_s"]
    assert min(stages) > 0 and ingest["wait_s"] >= 0
    assert sum(stages) + ingest["wait_s"] <= ingest["open_to_close_s"] + 1e-3
    # the chunk spans say what each chunk carried
    chunk_spans = [s["attrs"] for s in spans if s["name"] == "trainer.ingest.chunk"]
    assert sorted((a["kind"], a["rows"], a["bytes"]) for a in chunk_spans) == sorted(
        (kind, len(unpack_records(data)), len(data)) for kind, data in chunks)


def test_the_collector_shows_in_the_manifest_and_the_trace(trained):
    manifest, spans, _ = trained
    # the full collection made between the GNN's calls: in the cycle's count and in the calls' window
    collected = manifest["gc"]
    assert collected["collections"][2] >= 1 and 0 < collected["max_ms"] <= collected["ms"]
    assert 0 < manifest["models"]["gnn"]["calls"]["gc_ms"] <= collected["ms"]
    # and a `python.gc` span in the upload's trace, under the span it paused
    by_id = {s["span_id"]: s["name"] for s in spans}
    paused = [by_id.get(s["parent_id"]) for s in spans if s["name"] == "python.gc"]
    assert "trainer.train_gnn" in paused
    assert {s["attrs"]["generation"] for s in spans if s["name"] == "python.gc"} == {2}


def test_an_unsampled_upload_records_no_span_and_counts_all_the_same(tmp_path, monkeypatch):
    """At sample rate 0 the session's root is drawn unsampled: not one span
    of the upload or its run is recorded, and its counters are. (A run below
    `min_pairs`: it builds the dataset and trains nothing.)"""
    import asyncio

    tracer = tracing.default_tracer()
    monkeypatch.setattr(tracer, "sample_rate", 0.0)
    before = len(tracer.finished())
    svc = _service(tmp_path, min_pairs=10**9)
    store = TelemetryStorage(tmp_path / "telemetry")
    _fill_telemetry(store, n_hosts=10, n_rows=200)
    asyncio.run(_upload(svc, store))
    assert len(tracer.finished()) == before
    manifest = svc.run_history[-1]
    assert manifest["status"] == "skipped" and manifest["ingest"]["chunks"] == sum(SPLITS.values())
    assert manifest["ingest"]["rows"] == sum(len(unpack_records(data)) for _, data in _chunks(store))
    assert min(manifest["ingest"][k] for k in ("decode_s", "fold_s", "merge_s")) > 0
    # no collector watch installed here: no count claims there was no pause
    assert manifest["gc"] is None


def test_closes_the_drainer_coalesces_are_summed(tmp_path):
    """Two uploads closed before the drainer runs train once, on the pool
    both merged into: the run's `ingest` is their sum."""
    import asyncio

    svc = _service(tmp_path, min_pairs=10**9)
    store = TelemetryStorage(tmp_path / "telemetry")
    _fill_telemetry(store, n_hosts=10, n_rows=200)

    async def body():
        await _upload(svc, store, wait=False)
        await _upload(svc, store, wait=False)
        await svc.wait_idle()

    asyncio.run(body())
    assert (svc.trains_started, svc.trains_coalesced, len(svc.run_history)) == (1, 1, 1)
    ingest = svc.run_history[-1]["ingest"]
    chunks = _chunks(store)
    assert (ingest["sessions"], ingest["chunks"]) == (2, 2 * len(chunks))
    assert ingest["bytes"] == 2 * sum(len(data) for _, data in chunks)
