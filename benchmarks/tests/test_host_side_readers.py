"""The readers of what the trainer counts on its host (PR 39): the upload's
decode, fold and merge (`ingest.*_s`) and the collector's pauses
(`host.gc_ms`, `host.gc_ms.retrain`), against two cycles' manifests recorded
from a tiny TrainerService with the collector watched
(data/host_side/recorded_manifests.json), and nothing from a manifest of the
parent's shape, which counts neither.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_host_side_readers.py -q
"""

import copy
import json
import statistics
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
LAYER_DIR = BENCH / "layer_metrics"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(LAYER_DIR))

import run as harness  # noqa: E402

RECORDED = json.loads((TESTS / "data" / "host_side" / "recorded_manifests.json").read_text())
INGEST = ("ingest.decode_s", "ingest.fold_s", "ingest.merge_s")
RETRAIN = (*INGEST, "host.gc_ms.retrain")


def retrain_ctx(runs):
    """A "runs" window over the recorded cycles (the feeder's times made up:
    each upload 3 s, a little over what the server counted)."""
    uploads = [{"t_open": 10.0 * i, "t_closed": 10.0 * i + 3.0} for i in range(len(runs))]
    return {"window": {"kind": "runs", "uploads": uploads}, "runs": runs, "device": {"platform": "tpu"}}


def steady_ctx(runs):
    return {"window": {"kind": "scan_calls"}, "runs": runs, "device": {"platform": "tpu"}}


def read(name, ctx):
    return harness.read_layer_metric(LAYER_DIR, name, ctx)


def parent_shaped(manifest):
    """What the parent's program writes: no `ingest`, no `gc`, no `calls.gc_ms`."""
    m = copy.deepcopy(manifest)
    del m["ingest"], m["gc"]
    del m["models"]["gnn"]["calls"]["gc_ms"]
    return m


@pytest.mark.parametrize("name", INGEST)
def test_an_ingest_reader_is_the_median_of_the_cycles(name):
    key = name.split(".", 1)[1]
    assert read(name, retrain_ctx(RECORDED)) == statistics.median(m["ingest"][key] for m in RECORDED)
    assert read(name, retrain_ctx(RECORDED)) > 0


def test_the_ingest_split_is_inside_the_feeders_clock():
    ctx = retrain_ctx(RECORDED)
    split = sum(read(name, ctx) for name in INGEST)
    assert 0 < split <= read("service.ingest_s", ctx)
    for m in RECORDED:
        i = m["ingest"]
        assert i["decode_s"] + i["fold_s"] + i["merge_s"] + i["wait_s"] <= i["open_to_close_s"] + 1e-3


def test_the_collectors_time_is_summed_over_the_cycles():
    assert read("host.gc_ms.retrain", retrain_ctx(RECORDED)) == pytest.approx(sum(m["gc"]["ms"] for m in RECORDED))
    # the cold cycle built its programs and collected in full, inside the GNN's calls too
    cold = RECORDED[0]
    assert cold["gc"]["collections"][2] >= 1 and cold["models"]["gnn"]["calls"]["gc_ms"] > 0


def test_host_gc_ms_reads_the_window_runs_calls():
    assert read("host.gc_ms", steady_ctx(RECORDED)) == RECORDED[-1]["models"]["gnn"]["calls"]["gc_ms"]
    assert read("host.gc_ms", steady_ctx(RECORDED[:1])) == RECORDED[0]["models"]["gnn"]["calls"]["gc_ms"] > 0


@pytest.mark.parametrize("name", RETRAIN)
def test_a_retrain_reader_finds_nothing_in_the_parents_manifests_or_another_window(name):
    assert read(name, retrain_ctx([parent_shaped(m) for m in RECORDED])) is None
    # one cycle of the parent's shape among the program's: nothing, rather than a reading of part of the window
    assert read(name, retrain_ctx([RECORDED[0], parent_shaped(RECORDED[1])])) is None
    assert read(name, steady_ctx(RECORDED)) is None


def test_host_gc_ms_finds_nothing_in_the_parents_manifest_or_another_window():
    assert read("host.gc_ms", steady_ctx([parent_shaped(RECORDED[-1])])) is None
    no_gnn = copy.deepcopy(RECORDED[-1])
    no_gnn["models"]["gnn"] = None
    assert read("host.gc_ms", steady_ctx([no_gnn])) is None
    assert read("host.gc_ms", steady_ctx([])) is None
    assert read("host.gc_ms", retrain_ctx(RECORDED)) is None


def test_benchmark_json_lists_the_readers_where_they_read():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"][-5:]}
    steady = [w["name"] for w in bench["workloads"] if w["traffic"] == "steady"]
    for name in INGEST:
        assert entries[name] == {"name": name, "unit": "s", "better": "lower", "source": "program_counter",
                                 "layer": "ingest RPC", "moves": "retrain_s", "workloads": ["gnn-32k-512.retrain"]}
    assert entries["host.gc_ms.retrain"] == {
        "name": "host.gc_ms.retrain", "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "host loop", "moves": "retrain_s", "workloads": ["gnn-32k-512.retrain"]}
    assert entries["host.gc_ms"] == {
        "name": "host.gc_ms", "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "host loop", "moves": "train_steps_per_s", "workloads": steady}
    assert all((LAYER_DIR / f"{name}.py").is_file() for name in entries)
    # a layer BENCHMARK.json already names, letter for letter
    layers = {m["layer"] for m in bench["per_layer"][:-5]}
    assert {"ingest RPC", "host loop"} <= layers
