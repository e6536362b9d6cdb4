"""The retrain cell's window and its end-to-end reading: a "runs" window
holds at least `min_runs` whole cycles and at least the run's seconds, and
`retrain_s` is the median cycle, so that one cycle the machine paused is not
the window's reading (the run end to end: test_rehearsal's `tiny.retrain`)."""

import asyncio
import statistics
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run as harness  # noqa: E402
import traffic_driver  # noqa: E402

TRAFFIC = {"window": "runs", "runs_in_setup": 1, "min_runs": 3, "trace_runs": 1}
RUNS = traffic_driver.load_file(BENCH / "windows" / "runs.py")


def window_of(cycles_s, ingest_s=1.0, gap_s=0.0):
    """A "runs" window whose cycles took `cycles_s`, back to back."""
    uploads, t = [], 100.0
    for c in cycles_s:
        uploads.append({"t_open": t, "t_closed": t + ingest_s, "t_done": t + c, "error": None})
        t += c + gap_s
    return {"kind": "runs", "window_start": 100.0, "window_stop": uploads[-1]["t_done"],
            "window_s": uploads[-1]["t_done"] - 100.0, "uploads": uploads, "step_events": []}


def manifest(stall_ms=0.0):
    return {"status": "ok", "wall_s": 19.0, "dataset": {"build_seconds": 1.0}, "models": {
        "mlp": {"evaluation": {"train_seconds": 2.0, "export_seconds": 0.1}},
        "gnn": {"evaluation": {"train_seconds": 15.0, "export_seconds": 1.2}, "calls": {"stall_ms": stall_ms}}}}


@pytest.mark.parametrize("cycles_s,slow", [
    ([22.0, 22.2 * 1.15, 22.4], 1),   # one of three 15% slow: the two sound ones' reading
    ([22.0, 22.4, 22.2], 0),
    ([22.0, 22.1, 26.0, 22.3, 27.0], 2),  # a median of five leaves two out
])
def test_retrain_s_is_the_median_cycle_and_detail_keeps_every_cycle(cycles_s, slow):
    window = window_of(cycles_s, gap_s=0.5)
    runs = [manifest() for _ in range(len(cycles_s) + 1)]
    e2e, detail, attempted, failed = harness.end_to_end(RUNS, window, TRAFFIC, runs)
    sound = sorted(cycles_s)[: len(cycles_s) - slow]
    assert sound[0] - 1e-9 <= e2e["retrain_s"] <= sound[-1] + 1e-9
    assert e2e["retrain_s"] == pytest.approx(statistics.median(cycles_s))
    assert detail["cycles_s"] == pytest.approx(cycles_s) and detail["cycles_over_5pct"] == slow
    # the mean over the window (what the reading was up to PR 24) stays beside it
    assert detail["mean_cycle_s"] == pytest.approx(window["window_s"] / len(cycles_s))
    assert (attempted, failed) == (len(cycles_s) + 1, 0)
    assert detail["run_stages"][0]["gnn_export_s"] == 1.2 and detail["run_stages"][0]["gnn_stall_ms"] == 0.0


def test_the_stage_readers_take_the_median_over_the_same_cycles():
    sys.path.insert(0, str(BENCH / "layer_metrics"))
    window = window_of([22.0, 25.5, 22.4], ingest_s=3.0)
    window["uploads"][1]["t_closed"] += 2.0  # the slow cycle lost its time in ingest
    runs = [manifest(), manifest(), manifest(stall_ms=1500.0), manifest()]
    runs[2]["models"]["gnn"]["evaluation"]["export_seconds"] = 3.0
    ctx = {"window": window, "runs": runs, "compiles": [], "device": {"platform": "tpu"}}

    def read(name):
        return harness.read_layer_metric(BENCH / "layer_metrics", name, ctx)

    assert read("service.ingest_s") == pytest.approx(3.0)
    assert read("artifacts.export_s") == pytest.approx(1.3)  # export_seconds, not the wall's remainder
    assert read("dataset.build_s") == 1.0 and read("train_mlp.run_s") == 2.0
    assert read("compile.request_s") == 0
    # the paused cycle has a name all the same, and only on a chip
    assert read("host.stall_ms.retrain") == 1500.0 and read("host.stall_ms") is None
    ctx["device"]["platform"] = "cpu"
    assert read("host.stall_ms.retrain") is None


class FakeClient:
    """A trainer that publishes a model `cycle_s` after every `train_close`."""

    def __init__(self, cycle_s):
        self.cycle_s, self.closed, self.done_at = cycle_s, 0, 0.0

    async def train_open(self, *_):
        return "token"

    async def train_chunk(self, *_):
        pass

    async def train_close(self, _):
        self.closed += 1
        self.done_at = asyncio.get_running_loop().time() + self.cycle_s

    async def status(self):
        training = asyncio.get_running_loop().time() < self.done_at
        return {"trains_started": self.closed, "training": training, "last_result": {}}


class FakeTrainer:
    def ctl(self, cmd, **kw):
        return {"events": []}


@pytest.mark.parametrize("seconds,min_runs,cycles", [
    (0.0, 3, 3),    # the seconds have long passed: the window still holds min_runs cycles
    (0.0, 1, 1),
    (0.2, 2, None),  # cycles of 30 ms: the seconds decide, as before
])
def test_a_runs_window_does_not_close_before_min_runs_nor_before_its_seconds(seconds, min_runs, cycles):
    client = FakeClient(cycle_s=0.03)
    config = {"cluster": {"chunk_rows": 4}}
    feeder = {"hostname": "feeder", "scheduler_id": 0, "downloads": list(range(8)), "probes": list(range(8))}
    driver = traffic_driver.Driver(client, FakeTrainer(), config, {**TRAFFIC, "runs_in_setup": 0, "min_runs": min_runs},
                                   [feeder], seconds=seconds, trace_dir=None, deadline=time.monotonic() + 60.0)
    window = asyncio.run(driver.run(RUNS))
    assert window["kind"] == "runs" and len(window["uploads"]) >= min_runs
    assert window["window_s"] >= seconds
    if cycles is not None:
        assert len(window["uploads"]) == cycles
    else:
        # closes on the first cycle that ends after the seconds
        assert window["uploads"][-2]["t_done"] - window["window_start"] < seconds
