"""`gnn-40k-512.steady`, the cell whose host count is off every grid: its
files are found by name and are `gnn-32k-512.steady`'s but for the cluster's
size, its records are pinned byte for byte, `placement.pad_pct` reads a
recorded run manifest (and nothing from a program that does not say what it
placed), and the cell is rehearsed on the CPU at a tiny host count off the grid.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_off_grid_cell.py -q
"""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import run as harness  # noqa: E402

CELL = "gnn-40k-512.steady"
OFF_GRID = TESTS / "data" / "off_grid"


def test_the_cells_files_are_found_by_name_and_its_records_are_pinned():
    cell = harness.load_cell(REPO / "BENCHMARK.json", CELL)
    config, like = cell["config"], json.loads((BENCH / "configs" / "gnn-32k-512.json").read_text())
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == ("gnn-40k-512", "steady", 1)
    assert cell["traffic"]["window"] == "scan_calls" and config["generator"] == "uniform"
    # the accepted one-chip configuration at width 512 but for the cluster's size: only the placed row count differs
    assert {k for k in config if config[k] != like.get(k)} == {"name", "source", "what", "cluster", "assumed"}
    assert {k: v for k, v in config["cluster"].items() if like["cluster"][k] != v} == {"hosts": 40_000, "probes": 640_000}
    assert config["cluster"]["hosts"] % 256 and config["cluster"]["probes"] < config["cluster"]["pool_max_edges"]
    assert set(cell["limits"]["numbers"]) == set(json.loads((BENCH / "limits" / "gnn-32k-512.steady.json").read_text())["numbers"])
    assert all("lower" in n and "limit" in n for n in cell["limits"]["numbers"].values())
    (feeder,) = cell["generator"].generate(config["cluster"], 2_147_483_659)
    downloads, probes = feeder["downloads"], feeder["probes"]
    assert (len(downloads), len(probes)) == (557_056, 640_000)
    assert len(set(probes["src_host_id"].tolist())) == 40_000  # every host a probe source: the graph has 40,000 rows
    digest = hashlib.sha256(downloads.tobytes())
    digest.update(probes.tobytes())
    assert digest.hexdigest() == RECORDS_AT_PR_37


# sha256 over downloads.tobytes() then probes.tobytes() at seed 2,147,483,659, as this PR's chip runs were fed
RECORDS_AT_PR_37 = "0bf1e8dc20a281f613d84fcbfd923762378c2014fd3bfb936b4629520edbca33"


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    like = {m["name"] for m in bench["per_layer"] if "gnn-32k-512.steady" in m.get("workloads", [])}
    # `msg_roofline` matches ops by the shape [hosts, K, H], which placed rows of 40,960 never have
    assert listed == (like - {"msg_roofline"}) | {"placement.pad_pct"}
    assert all((BENCH / "layer_metrics" / f"{name}.py").is_file() for name in listed)
    pad = next(m for m in bench["per_layer"] if m["name"] == "placement.pad_pct")
    assert pad["workloads"] == [CELL] and (pad["layer"], pad["moves"], pad["better"]) == ("placement", "train_steps_per_s", "lower")
    assert bench["per_layer"][-1] is pad and bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == "gnn-40k-512"
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_steps_per_s")["workloads"]


def test_pad_pct_reads_a_recorded_manifest_and_nothing_from_a_program_that_does_not_say():
    manifest = json.loads((OFF_GRID / "recorded_manifest.json").read_text())
    layer_dir = BENCH / "layer_metrics"
    assert manifest["models"]["gnn"]["placement"]["decision"] == {
        "rule": "one_device", "devices": 1, "hosts": 300, "rows": 512, "pad_pct": 70.67}
    assert harness.read_layer_metric(layer_dir, "placement.pad_pct", {"runs": [manifest]}) == 70.67
    # the parent's manifest: a decision without the counts, a run without a GNN, no run
    del manifest["models"]["gnn"]["placement"]["decision"]["pad_pct"]
    assert harness.read_layer_metric(layer_dir, "placement.pad_pct", {"runs": [manifest]}) is None
    manifest["models"]["gnn"] = None
    assert harness.read_layer_metric(layer_dir, "placement.pad_pct", {"runs": [manifest]}) is None
    assert harness.read_layer_metric(layer_dir, "placement.pad_pct", {"runs": []}) is None


def test_rehearsal_of_the_cell_at_a_tiny_host_count_off_the_grid(tmp_path):
    """run.py end to end, traced, with the cell's own entry (chips 1, the
    steady mix, every metric it lists) over 300 hosts at hidden 32: the
    program places them at 512 rows, the reference never pads, and the run is
    correct inside the tiny steady cell's limits; the manifest's count comes through
    the new reader."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "off_grid", "source": "benchmarks/tests", "reduced": ["gnn_steps", "mlp_steps"],
                         "file": str((OFF_GRID / "off_grid.json").relative_to(REPO)), "why": "CPU rehearsal"}]
    bench["workloads"] = [{"name": "off_grid.steady", "config": "off_grid", "traffic": "steady", "chips": 1, "why": "rehearsal"}]
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if "workloads" in m:
                m["workloads"] = ["off_grid.steady"] if CELL in m["workloads"] else []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "limits").symlink_to(OFF_GRID / "limits")  # the tiny steady cell's, number for number
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "off_grid.steady", "--seed", "2147483783",
         "--seconds", "1", "--trace", "1", "--cpu-rehearsal", "--benchmark-json", str(tmp_path / "BENCHMARK.json")],
        cwd=REPO, capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["metrics"] == {}
    assert result["compared"]["dataset_mismatch"] == [0, 0] and result["compared"]["export_mismatch"] == [0, 0]
    read = result["rehearsal"]["read"]
    assert read["placement.pad_pct"] == {"value": 70.67, "unit": "%"}
    assert "compile.in_window" in read and "msg_roofline" not in read
