"""`gnn-40k-512-churn.retrain-weekly`, the cell whose hosts churn between
weekly uploads: its files are found by name and are `gnn-40k-512`'s but for
the generator, each interval names its window of host ids, the window sends
interval k in cycle k and works out from the records which commits the
checked run's pool held after the rotations, the new readers read a recorded
run manifest (and nothing from a program that does not say), and a tiny churn
deployment (data/churn) is rehearsed on the CPU: correct against the
reference, and not correct where the window's reckoning leaves out the
rotation.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_churn_cell.py -q
"""

import asyncio
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH / "layer_metrics"))  # the readers import _common

import run as harness  # noqa: E402
import traffic_driver  # noqa: E402

CELL = "gnn-40k-512-churn.retrain-weekly"
CHURN = TESTS / "data" / "churn"
WINDOW = traffic_driver.load_file(BENCH / "windows" / "intervals.py")
READERS = ("compile.step_builds", "pool.stale_hosts_pct")


def test_the_cells_files_are_found_by_name_and_each_interval_names_its_window_of_hosts():
    cell = harness.load_cell(REPO / "BENCHMARK.json", CELL)
    config, like = cell["config"], json.loads((BENCH / "configs" / "gnn-40k-512.json").read_text())
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == ("gnn-40k-512-churn", "retrain-weekly", 1)
    assert cell["traffic"]["window"] == "intervals" and config["generator"] == "intervals"
    # gnn-40k-512's sizes and model: only who sends which hosts differs
    assert {k for k in config if config[k] != like.get(k)} == {"name", "source", "what", "generator", "cluster", "assumed"}
    assert {k: v for k, v in config["cluster"].items() if like["cluster"].get(k) != v} == {
        "hosts_per_interval": [40_000, 39_600, 40_400, 39_200], "hosts_replaced": 4_000, "intervals": 8}
    retrain = json.loads((BENCH / "limits" / "gnn-32k-512.retrain.json").read_text())
    assert set(cell["limits"]["numbers"]) == set(retrain["numbers"])
    feeders = cell["generator"].generate(config["cluster"], 2_147_495_001)
    assert len(feeders) == 8
    for i in (0, 1, 3):
        f = feeders[i]
        assert (len(f["downloads"]), len(f["probes"])) == (557_056, 640_000)
        hosts = np.unique(f["probes"]["src_host_id"])  # every host a probe source: the interval's hosts, all of them
        n = config["cluster"]["hosts_per_interval"][i]
        assert len(hosts) == n and (hosts[0], hosts[-1]) == (b"host-%06d" % (4_000 * i), b"host-%06d" % (4_000 * i + n - 1))
    assert WINDOW.pools_of(feeders[:3], config["cluster"]) == [[0], [0, 1], [2]]


class _Driver:
    """What the window asks of a Driver, with runs that end at once."""

    def __init__(self, feeders, traffic):
        self.feeders, self.traffic, self.uploads, self.trace, self.trace_dir = feeders, traffic, 0, None, None
        self.config = {"name": "churn", "cluster": json.loads((CHURN / "churn.json").read_text())["cluster"]}
        self.seconds = 0.0
        self.trainer = type("T", (), {"ctl": lambda self, *a, **kw: {"events": []}})()

    async def upload(self, feeder):
        self.uploads += 1
        return {"t_open": 0.0, "t_closed": 0.0}

    async def wait_run_done(self, n):
        return {"last_result": {}, "pool_rotations": 0}


def test_a_window_longer_than_the_intervals_stops_with_an_error_that_names_the_configuration():
    feeders = [{"downloads": None, "probes": None}] * 3
    driver = _Driver(feeders, {"runs_in_setup": 1, "min_runs": 5, "trace_runs": 1})
    driver.uploads = 1  # set-up sent feeder 0
    with pytest.raises(RuntimeError, match=r"churn: the window's cycle 3 needs feeder 3, the configuration has 3"):
        asyncio.run(WINDOW.drive(driver))


def _rehearse(workload: str, trace: int) -> dict:
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", workload, "--seed", "2147483659", "--seconds", "1",
         "--trace", str(trace), "--cpu-rehearsal", "--benchmark-json", str(CHURN / "BENCHMARK.json")],
        cwd=REPO, capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_rehearsal_of_a_churning_cluster_is_correct_on_the_pool_the_window_worked_out():
    """run.py end to end, traced, over four intervals of about 2,000 hosts:
    set-up sends interval 0, the window's cycles intervals 1-3; the trainer
    rotated its pool twice, the checked run trained on commits [2, 3], and
    the reference's rebuild of that pool is the program's graph entry for
    entry."""
    result = _rehearse("churn.weekly", 1)
    assert result["correct"] is True and (result["attempted"], result["failed"]) == (4, 0)
    detail = result["detail"]
    assert detail["feeders"] == [1, 2, 3] and detail["pool_commits"] == [[0, 1], [2], [2, 3]]
    assert detail["pool_rotations"] == 2 and result["compared"]["dataset_mismatch"] == [0, 0]
    read = result["rehearsal"]["read"]
    # interval 0 = hosts 0..2047, 1 = 256..2255: the double pool's 2,256 hosts, 256 of them stale
    assert read["pool.stale_hosts_pct"]["value"] == pytest.approx(100 * 256 / 2256)
    assert read["compile.step_builds"]["value"] == 2 and "ingest.merge_s" in read


def test_a_checked_run_whose_commits_leave_out_the_rotation_is_not_correct():
    result = _rehearse("churn.weekly-unrotated", 0)
    assert result["correct"] is False and result["compared"]["dataset_mismatch"][0] > 0


def _ctx(recorded: dict) -> dict:
    window = {"kind": "runs", "uploads": recorded["uploads"], "step_events": []}
    return {"window": window, "runs": recorded["runs"], "device": {"platform": "cpu"}}


def test_the_readers_read_a_recorded_manifest_and_nothing_from_a_program_that_does_not_say():
    recorded = json.loads((CHURN / "recorded_manifests.json").read_text())
    layer_dir = BENCH / "layer_metrics"
    cycles = recorded["runs"][-len(recorded["uploads"]):]
    assert [m["models"]["gnn"]["kept"]["served"] for m in cycles] == [False, False, True]
    assert [m["pool"]["rotated"] for m in cycles] == [True, False, True]
    assert harness.read_layer_metric(layer_dir, "compile.step_builds", _ctx(recorded)) == 2
    stale = harness.read_layer_metric(layer_dir, "pool.stale_hosts_pct", _ctx(recorded))
    assert stale == pytest.approx(np.median([100 * m["pool"]["hosts_stale"] / m["pool"]["hosts"] for m in cycles]))
    # the parent's manifests: no `pool`, no `kept`; the builds still read from `calls.traced`
    for m in recorded["runs"]:
        del m["pool"], m["models"]["gnn"]["kept"]
    assert harness.read_layer_metric(layer_dir, "pool.stale_hosts_pct", _ctx(recorded)) is None
    assert harness.read_layer_metric(layer_dir, "compile.step_builds", _ctx(recorded)) == 2
    for m in recorded["runs"]:
        del m["models"]["gnn"]["calls"]
    assert harness.read_layer_metric(layer_dir, "compile.step_builds", _ctx(recorded)) is None


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    retrain = {m["name"] for m in bench["per_layer"] if "gnn-32k-512.retrain" in m.get("workloads", [])}
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == retrain | set(READERS)
    assert all((BENCH / "layer_metrics" / f"{name}.py").is_file() for name in listed)
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert bench["per_layer"][-2:] == new and all(m["workloads"] == [CELL] and m["moves"] == "retrain_s" for m in new)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == "gnn-40k-512-churn"
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "retrain_s")["workloads"]
