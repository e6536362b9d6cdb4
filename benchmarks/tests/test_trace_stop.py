"""A traced run's `trace_stop`: the reply limit comes from what is left of the
run's budget (with a floor), every other question keeps `ctl`'s short
default, the traced child alone gets one malloc arena, and the time the reply
took reaches the result line (the run end to end: test_mesh_metrics's
rehearsal of a traced run)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run as harness  # noqa: E402
import traffic_driver  # noqa: E402


@pytest.mark.parametrize("left_s,limit_s", [
    # a run 70 s old of 1,100: what is left less the reserve for the run's end, the export and the reference
    (1030.0, 1030.0 - traffic_driver.TRACE_STOP_RESERVE_S),
    (traffic_driver.TRACE_STOP_RESERVE_S + traffic_driver.TRACE_STOP_FLOOR_S + 1.0, traffic_driver.TRACE_STOP_FLOOR_S + 1.0),
    # little left, or the deadline passed: the floor, never less
    (200.0, traffic_driver.TRACE_STOP_FLOOR_S),
    (-5.0, traffic_driver.TRACE_STOP_FLOOR_S),
])
def test_the_limit_is_what_is_left_of_the_budget_with_a_floor(left_s, limit_s):
    assert traffic_driver.trace_stop_limit_s(5000.0 + left_s, 5000.0) == pytest.approx(limit_s)


class FakeTrainer:
    def __init__(self):
        self.asked = []

    def ctl(self, cmd, **kw):
        self.asked.append((cmd, kw))
        return {"marker_unix_ns": 1, "marker_monotonic": 2.0} if cmd == "trace_start" else {"stopped_monotonic": 3.0}


def test_trace_stop_alone_is_given_the_budgets_limit_and_its_time_is_kept():
    import time

    trainer = FakeTrainer()
    deadline = time.monotonic() + 900.0
    driver = traffic_driver.Driver(None, trainer, {}, {}, [], seconds=1.0,
                                   trace_dir=Path("/nowhere"), deadline=deadline)
    driver.trace_start()
    driver.trace_stop()
    (start, start_kw), (stop, stop_kw) = trainer.asked
    assert (start, stop) == ("trace_start", "trace_stop")
    assert "timeout" not in start_kw            # `ctl`'s own default: a question that is answered at once
    assert stop_kw["timeout"] == pytest.approx(900.0 - traffic_driver.TRACE_STOP_RESERVE_S, abs=1.0)
    assert driver.trace["stop_limit_s"] == stop_kw["timeout"] and 0 <= driver.trace["stop_s"] < 1.0
    assert driver.trace["stopped_monotonic"] == 3.0 and driver.trace["marker_monotonic"] == 2.0


def test_only_a_traced_childs_environment_differs(monkeypatch, tmp_path):
    """An untraced run starts its trainer with no variable of the harness's;
    a traced one adds the program's span file and one malloc arena."""
    seen = []

    class Stop(Exception):
        pass

    class Recorder:
        def __init__(self, repo, flags, log_path, *, launcher=None, env=None):
            seen.append(env)
            raise Stop

    import serverproc
    import telemetry_gen

    monkeypatch.setattr(serverproc, "TrainerProcess", Recorder)
    monkeypatch.setattr(telemetry_gen, "generate_for", lambda cluster, seed: (None, None))
    cell = harness.load_cell(harness.REPO / "BENCHMARK.json", "gnn-32k-512.steady")
    for trace in (0, 1):
        args = harness.argparse.Namespace(seed=1, seconds=30.0, trace=trace, launcher=None)
        with pytest.raises(Stop):
            harness.measure(args, cell, tmp_path)
    assert seen[0] == {}
    assert seen[1]["MALLOC_ARENA_MAX"] == "1" and set(seen[1]) == {
        "DRAGONFLY_TRACE_FILE", "DRAGONFLY_TRACE_SAMPLE", "MALLOC_ARENA_MAX"}
