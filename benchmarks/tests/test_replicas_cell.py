"""`gnn-32k-512-3sched.retrain-replicas`, three scheduler replicas feeding one
trainer: its files are found by name and are `gnn-32k-512`'s but for who sends
which records; the generator's three feeders together are `uniform`'s records,
every download's task id hashing onto the scheduler that sent it and every
probe sent by its source's scheduler; the window's reckoning of which runs
trained on the checked run's pool; the two new readers on recorded manifests
(and nothing from a program that does not count); a window whose late close
would miss the first run stops with an error that says so; and a tiny
deployment (data/replicas) rehearsed on the CPU: correct against the
reference, two runs and one coalescing a cycle.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_replicas_cell.py -q
"""

import asyncio
import json
import os
import subprocess
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH / "layer_metrics"))  # the readers import _common

import run as harness  # noqa: E402
import telemetry_gen  # noqa: E402
import traffic_driver  # noqa: E402

CELL = "gnn-32k-512-3sched.retrain-replicas"
REPLICAS = TESTS / "data" / "replicas"
WINDOW = traffic_driver.load_file(BENCH / "windows" / "replicas.py")
SEED = 2_147_496_001


@pytest.fixture(scope="module")
def cell():
    cell = harness.load_cell(REPO / "BENCHMARK.json", CELL)
    return cell, cell["generator"].generate(cell["config"]["cluster"], SEED)


def test_the_cells_files_are_found_by_name_and_are_gnn_32k_512s_but_for_who_sends_what(cell):
    cell, _ = cell
    config, like = cell["config"], json.loads((BENCH / "configs" / "gnn-32k-512.json").read_text())
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == ("gnn-32k-512-3sched", "retrain-replicas", 1)
    assert cell["traffic"]["window"] == "replicas" and config["generator"] == "replicas"
    assert (cell["traffic"]["runs_in_setup"], cell["traffic"]["cycles_in_setup"], cell["traffic"]["min_runs"]) == (0, 1, 5)
    assert {k for k in config if config[k] != like.get(k)} == {"name", "source", "what", "generator", "cluster", "assumed"}
    assert {k: v for k, v in config["cluster"].items() if like["cluster"].get(k) != v} == {
        "schedulers": 3, "tasks": 12_288, "download_chunks_per_scheduler": [46, 45, 45],
        "download_rows_per_scheduler": [188_416, 184_320, 184_320], "pair_rows_per_scheduler": [169_556, 165_870, 165_870],
        "hosts_per_scheduler": [10_923, 10_923, 10_922]}
    assert len(config["source"]) <= 200
    retrain = json.loads((BENCH / "limits" / "gnn-32k-512.retrain.json").read_text())
    assert set(cell["limits"]["numbers"]) == set(retrain["numbers"])


def test_the_three_feeders_are_uniforms_records_dealt_by_task_and_by_probing_host(cell):
    cell, feeders = cell
    cluster = cell["config"]["cluster"]
    downloads, probes = telemetry_gen.generate_for(cluster, SEED)
    assert [(f["hostname"], f["scheduler_id"]) for f in feeders] == [(f"scheduler-{i}", i) for i in range(3)]
    dealt = np.concatenate([f["downloads"] for f in feeders])
    assert (downloads["task_id"] == b"").all() and (dealt["task_id"] != b"").all()
    for name in downloads.dtype.names:
        if name != "task_id":
            np.testing.assert_array_equal(dealt[name], downloads[name])
    pair_rows = telemetry_gen.pair_rows_per_chunk(cluster["chunk_rows"], cluster["frac_failed"], cluster["frac_no_parent"])
    for i, f in enumerate(feeders):
        d = f["downloads"]
        owners = {zlib.crc32(t) % 3 for t in np.unique(d["task_id"]).tolist()}
        assert owners == {i}
        ok = d["success"] & (d["parent_host_id"] != b"")
        assert (len(d), int(ok.sum())) == (cluster["download_rows_per_scheduler"][i], cluster["pair_rows_per_scheduler"][i])
        assert WINDOW.chunk_pairs([f], cluster["chunk_rows"]) == [[pair_rows] * cluster["download_chunks_per_scheduler"][i]]
        src = cell["generator"].host_index(f["probes"]["src_host_id"], cluster["hosts"])
        assert (src % 3 == i).all() and len(np.unique(src)) == cluster["hosts_per_scheduler"][i]
    # each probe once, in uniform's order within a feeder
    assert sum(len(f["probes"]) for f in feeders) == len(probes)
    src = cell["generator"].host_index(probes["src_host_id"], cluster["hosts"])
    for i, f in enumerate(feeders):
        np.testing.assert_array_equal(f["probes"], probes[src % 3 == i])


def test_every_coalesced_run_trains_on_one_pool_once_a_week_has_filled_it(cell):
    """At the cell's size the pool holds 136 chunks (501,296 pairs) after
    every close from the first whole week on: every run B of the window trains
    on the set-up's run B's pool, no run A does."""
    cell, feeders = cell
    cluster = cell["config"]["cluster"]
    pairs = WINDOW.chunk_pairs(feeders, cluster["chunk_rows"])
    window = {"setup_cycles": 1, "cycles": [{}] * 5, "closes": [0, 1, 2] * 6, "chunk_pairs": pairs,
              "pool_rows_cap": cluster["pool_rows_cap"]}
    runs = [{"models": {"gnn": {"steps": 300}}}] * 12
    checked = WINDOW.checked(window, runs)
    assert checked == {"run": 11, "commits": [0, 1, 2] * 6, "same_pool": [1, 3, 5, 7, 9, 11]}
    held = [sum(n for _, _, n in WINDOW.pool_of([0, 1, 2] * k + [0], pairs, cluster["pool_rows_cap"])[2]) for k in range(1, 6)]
    assert held == [501_296] * 5
    # a close order that changes from one cycle to the next is another pool
    window["closes"] = [0, 1, 2] * 5 + [0, 2, 1]
    assert WINDOW.checked(window, runs)["same_pool"] == [11]


def _ctx(runs: list[tuple[dict, dict]]) -> dict:
    window = {"kind": "runs", "uploads": [u for u, _ in runs], "step_events": []}
    return {"window": window, "runs": [{"models": {}}] + [m for _, m in runs], "device": {"platform": "tpu"}}


def _manifest(in_run_s=None, gap=None, clear=None) -> dict:
    ingest = {} if in_run_s is None else {"in_run_s": in_run_s}
    calls = {} if clear is None else {"in_ingest": [], "gap_ms_in_ingest": gap, "gap_ms_clear": clear}
    return {"ingest": ingest, "models": {"gnn": {"calls": calls}}}


def test_the_new_readers_read_a_cycle_of_two_runs_and_nothing_from_a_program_that_does_not_count():
    fold = traffic_driver.load_file(BENCH / "layer_metrics" / "ingest.fold_in_run_s.py")
    gap = traffic_driver.load_file(BENCH / "layer_metrics" / "gnn.gap_ms_in_ingest.py")
    runs = []
    for k, (b_in_run, a_gap) in enumerate([(1.5, 30.0), (1.7, None), (1.2, 50.0)]):
        runs += [({"cycle": k, "run": "A"}, _manifest(0.0, a_gap, 4.0)),
                 ({"cycle": k, "run": "B"}, _manifest(b_in_run, None, 4.5))]
    ctx = _ctx(runs)
    assert fold.read(ctx) == 1.5 and gap.read(ctx) == 40.0
    parent = _ctx([(u, {"ingest": {}, "models": {"gnn": {"calls": {}}}}) for u, _ in runs])
    assert (fold.read(parent), gap.read(parent)) == (None, None)
    assert gap.read({**ctx, "window": {**ctx["window"], "kind": "scan_calls"}}) is None


class _Client:
    """A trainer whose first run ends before the late closes come."""

    def __init__(self):
        self.started = 0

    async def status(self):
        return {"trains_started": self.started, "trains_coalesced": 0, "training": False, "open_sessions": 3,
                "pool_rotations": 0, "last_result": {}}

    async def train_open(self, hostname, scheduler_id):
        return hostname

    async def train_chunk(self, token, kind, records):
        return len(records)

    async def train_close(self, token):
        self.started += 1


def test_a_late_close_that_would_miss_the_first_run_stops_the_window_with_an_error_that_says_so():
    feeder = {"hostname": "s", "scheduler_id": 0, "downloads": np.zeros(0), "probes": np.zeros(0)}

    class Driver:
        client, feeders, config = _Client(), [feeder] * 3, {"cluster": {"chunk_rows": 256}}
        deadline = float("inf")

        async def send(self, token, f):
            pass

    with pytest.raises(RuntimeError, match=r"feeder 1's close would come after run A ended, not while it trains"):
        asyncio.run(WINDOW.cycle(Driver()))


def test_rehearsal_of_three_schedulers_is_correct_with_two_runs_and_one_coalescing_a_cycle():
    """run.py end to end, traced, at tiny's 64 hosts: one set-up cycle and
    two in the window; the checked run B's pool rebuilt from the six closes is
    the program's graph entry for entry."""
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "replicas.cycles", "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--cpu-rehearsal", "--benchmark-json", str(REPLICAS / "BENCHMARK.json")],
        cwd=REPO, capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.splitlines()[-1])
    assert result["correct"] is True and (result["attempted"], result["failed"]) == (6, 0)
    assert result["compared"]["dataset_mismatch"] == [0, 0]
    detail = result["detail"]
    assert (detail["runs_per_cycle"], detail["coalesced_per_cycle"], detail["open_together"]) == ([2, 2], [1, 1], [3, 3])
    assert detail["close_order"] == [[0, 1, 2]] * 2 and detail["late_closes_in_run"] == [2, 2]
    notes = detail["runs"]
    assert [n["sessions"] for n in notes] == [1, 2, 1, 2]
    assert [n["chunks_in_run"] > 0 for n in notes] == [False, True, False, True]
    assert notes[1]["schedulers"] == ["scheduler-0", "scheduler-1", "scheduler-2"]
    read = result["rehearsal"]["read"]
    assert read["ingest.fold_in_run_s"]["value"] > 0 and {"dataset.build_s", "compile.step_builds"} <= set(read)
