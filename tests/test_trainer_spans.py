"""The trainer's spans and always-on call counts: the span tree of one
training run, the bridge onto a profiler's clock (`Tracer.annotate`), the
monotonic duration, and the scan loop's call summary in the run manifest."""

from __future__ import annotations

import time
from collections import Counter

import pytest

from dragonfly2_tpu.observability import tracing
from dragonfly2_tpu.telemetry import TelemetryStorage
from dragonfly2_tpu.telemetry.records import pack_records
from dragonfly2_tpu.trainer import metrics as train_metrics, train_gnn, train_mlp
from dragonfly2_tpu.trainer.service import TrainerConfig, TrainerService
from test_trainer_service import _fill_telemetry

GNN_STEPS, STEPS_PER_CALL = 12, 4


async def _upload(svc, store):
    """One upload of the store's records through the service, and the run it starts."""
    token = (await svc.train_open({"hostname": "s"}))["token"]
    await svc.train_chunk({"token": token, "kind": "downloads", "data": pack_records(store.downloads.load_all())})
    await svc.train_chunk({"token": token, "kind": "probes", "data": pack_records(store.probes.load_all())})
    await svc.train_close({"token": token})
    await svc.wait_idle()


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One tiny run through TrainerService (MLP + GNN, no manager): its
    manifest and the spans of its trace."""
    import asyncio

    tmp_path = tmp_path_factory.mktemp("spans")
    # whatever ran in this process before: the run builds its scan programs
    train_gnn._kept = None
    train_mlp._scan_steps.clear_cache()
    svc = TrainerService(TrainerConfig(
        model_dir=str(tmp_path / "models"),
        mlp=train_mlp.MLPTrainConfig(hidden=(16, 16), steps=20, batch_size=64),
        gnn=train_gnn.GNNTrainConfig(hidden=16, embed_dim=8, num_layers=2, batch_size=64, warmup_steps=2),
        gnn_steps=GNN_STEPS, gnn_steps_per_call=STEPS_PER_CALL,
    ))
    store = TelemetryStorage(tmp_path / "telemetry")
    _fill_telemetry(store, n_hosts=10, n_rows=200)

    asyncio.run(_upload(svc, store))
    assert svc.trains_succeeded == 1, svc.last_result
    spans = [s.to_dict() for s in tracing.default_tracer().finished()]
    root = [s for s in spans if s["name"] == "trainer.train_run"][-1]
    return svc.run_history[-1], [s for s in spans if s["trace_id"] == root["trace_id"]]


def test_a_training_run_leaves_the_span_tree(trained):
    _, spans = trained
    by_id = {s["span_id"]: s for s in spans}

    def path(span):
        names = [span["name"]]
        while span["parent_id"] in by_id:
            span = by_id[span["parent_id"]]
            names.append(span["name"])
        return " < ".join(names)

    calls = GNN_STEPS // STEPS_PER_CALL
    run_gnn = "trainer.train_gnn < trainer.train_run"
    assert Counter(path(s) for s in spans if s["name"].startswith(("trainer.gnn", "trainer.export"))) == {
        f"trainer.gnn.setup < {run_gnn}": 1,
        f"trainer.gnn.place < trainer.gnn.setup < {run_gnn}": 1,
        f"trainer.gnn.call < {run_gnn}": calls,
        f"trainer.gnn.dispatch < trainer.gnn.call < {run_gnn}": calls,
        f"trainer.gnn.pull < trainer.gnn.call < {run_gnn}": calls,
        "trainer.export < trainer.train_run": 2,
        "trainer.export.native < trainer.export < trainer.train_run": 1,
    }
    call_spans = sorted((s for s in spans if s["name"] == "trainer.gnn.call"), key=lambda s: s["attrs"]["index"])
    assert [s["attrs"]["index"] for s in call_spans] == list(range(calls))
    assert {s["attrs"]["steps"] for s in call_spans} == {STEPS_PER_CALL}
    # placement says the hosts it was given and the rows it placed them at (a rung of the ladder)
    (place,) = (s["attrs"] for s in spans if s["name"] == "trainer.gnn.place")
    nodes = next(s["attrs"]["nodes"] for s in spans if s["name"] == "trainer.train_gnn")
    assert (place["hosts"], place["rows"]) == (nodes, 256) and nodes < 256
    assert sorted(s["attrs"]["model"] for s in spans if s["name"] == "trainer.export") == ["gnn", "mlp"]


def test_the_manifest_counts_the_calls_and_times_the_export(trained):
    manifest, spans = trained
    calls = manifest["models"]["gnn"]["calls"]
    assert set(calls) == {
        "count", "traced", "first_ms", "period_ms_p50", "period_ms_max", "turn_ms_p50", "turn_ms_max", "stall_ms"}
    assert calls["count"] == GNN_STEPS // STEPS_PER_CALL
    # the run built its scan program: one trace, and a first call (trace, compile, steps) longer than any period
    assert calls["traced"] == 1 and calls["first_ms"] > calls["period_ms_max"]
    assert calls["stall_ms"] >= 0 and 0 < calls["turn_ms_p50"] <= calls["turn_ms_max"] < calls["period_ms_max"]
    # the MLP loop's 20 steps are one scan call, which this run traced: nothing to pace
    mlp_calls = manifest["models"]["mlp"]["calls"]
    assert set(mlp_calls) == set(calls) and (mlp_calls["count"], mlp_calls["traced"]) == (1, 1)
    assert mlp_calls["first_ms"] > 0 and mlp_calls["period_ms_p50"] is None and mlp_calls["stall_ms"] == 0
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

    train_gnn._kept = None
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


@pytest.mark.parametrize("calls,traced,expected", [
    # the first call of a run that builds its program compiles: periods count
    # from the second call's start, the first call's start to end is first_ms
    ([(0.0, 1.0), (1.1, 2.1), (2.2, 3.2), (5.2, 6.2), (6.3, 7.3)], 0,
     {"count": 5, "traced": 0, "first_ms": 1000.0, "period_ms_p50": 1100.0, "period_ms_max": 3000.0,
      "turn_ms_p50": 100.0, "turn_ms_max": 2000.0, "stall_ms": 1350.0}),
    ([(0.0, 9.0), (9.5, 10.0)], 1,
     {"count": 2, "traced": 1, "first_ms": 9000.0, "period_ms_p50": None, "period_ms_max": None,
      "turn_ms_p50": 500.0, "turn_ms_max": 500.0, "stall_ms": 0.0}),
    ([(2.0, 2.25)], 1,
     {"count": 1, "traced": 1, "first_ms": 250.0, "period_ms_p50": None, "period_ms_max": None,
      "turn_ms_p50": None, "turn_ms_max": None, "stall_ms": 0.0}),
    ([], 0, {"count": 0, "traced": 0, "first_ms": None, "period_ms_p50": None, "period_ms_max": None,
             "turn_ms_p50": None, "turn_ms_max": None, "stall_ms": 0.0}),
])
def test_call_summary_arithmetic(calls, traced, expected):
    sink = train_metrics.TrainRunTelemetry("gnn")
    sink.on_calls(calls, traced=traced)
    assert sink.summary()["calls"] == pytest.approx(expected)


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
