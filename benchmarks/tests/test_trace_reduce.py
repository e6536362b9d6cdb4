"""The trace reduction on a trace small enough to work out by hand, and on
one recorded on the chip (data/recorded_trace.json.gz, cut from a traced run
of gnn-32k-512.steady)."""

import gzip
import json
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "layer_metrics"))

import run as harness  # noqa: E402
import trace_reduce  # noqa: E402

SCATTER = "%fusion.1 = bf16[64,32]{1,0} fusion(s32[1024]{0} %i, bf16[1024,32]{1,0} %c), kind=kInput"
GATHER = "%fusion.2 = bf16[1024,32]{1,0} fusion(bf16[64,32]{1,0} %h, s32[1024]{0} %i), kind=kLoop"
DENSE = "%convolution.3 = bf16[64,32]{1,0} convolution(bf16[64,32]{1,0} %h, bf16[32,32]{1,0} %w)"


def hand_trace():
    """Three executions of `jit_multi_step`, 100 us each, 20 us apart; in each
    a dense op [0, 30), a gather [30, 60) and a scatter [50, 100) (overlapping
    the gather by 10 us on another core of the same chip)."""
    ops, modules = [], []
    for call in range(3):
        t = 1_000 + call * 120_000
        modules.append(["jit_multi_step(123)", "", t, 100_000])
        ops += [["convolution.3", DENSE, t, 30_000], ["fusion.2", GATHER, t + 30_000, 30_000],
                ["fusion.1", SCATTER, t + 50_000, 50_000]]
    host = [["python", "PjitFunction(multi_step)", 0, 400_000], ["python", "np.asarray", 101_000, 19_000]]
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops, "modules": modules}],
            "host": host, "marker_ns": 500}


TINY_CONFIG = {
    "scan_program": "multi_step", "cluster": {"hosts": 64},
    "optimizer": {"gnn": {"steps_per_call": 10}},
    "model": {"num_neighbors": 16, "hidden": 32, "embed_dim": 16, "node_features": 12, "edge_features": 4,
              "pair_features": 16, "num_layers": 1, "pair_batch": 128, "head_hidden": [256, 128, 1]},
}


def test_shapes_of_reads_result_then_operands():
    assert trace_reduce.shapes_of(SCATTER) == [("bf16", (64, 32)), ("s32", (1024,)), ("bf16", (1024, 32))]


def test_window_busy_gaps_and_classes_by_hand():
    compact = hand_trace()
    a, b = trace_reduce.window_of(compact, {}, "multi_step", None)
    # whole call periods: from the second execution's start to the last one's
    assert (a, b) == (121_000, 241_000)
    view = trace_reduce.TraceView(compact, a, b)
    assert view.window_s == pytest.approx(120e-6)
    assert view.busy_s() == pytest.approx(100e-6)          # union: the overlap counts once
    assert view.idle_gaps() == [(221_000, 241_000)]
    assert [m[2] for m in view.module_runs("multi_step")] == [121_000, 241_000]
    import flops

    scatter = view.op_seconds(lambda name, shapes: flops.is_scatter(TINY_CONFIG, shapes))
    messages = view.op_seconds(lambda name, shapes: flops.touches_messages(TINY_CONFIG, shapes)
                               and not flops.is_scatter(TINY_CONFIG, shapes))
    assert scatter == pytest.approx(50e-6) and messages == pytest.approx(30e-6)
    assert view.top_ops(1)[0][0].startswith("fusion.1 bf16[64,32] s32[1024]")
    assert view.top_gaps(1) == [["PjitFunction(multi_step)", pytest.approx(20e-6)]]


def test_an_op_of_no_duration_makes_no_container_of_the_op_it_is_stamped_with():
    """The profiler stamps a buffer-assembling `custom-call` of no duration
    with the start of the op that follows it; that op holds nothing. A `while`
    holds the ops of its body, the first of which starts with it."""
    kernel = ["sum_by_destination", "%sum_by_destination = bf16[64,32] custom-call()", 5_000, 2_000]
    stamp = ["custom-call.285", "%custom-call.285 = bf16[64,32] custom-call()", 5_000, 0]
    after = ["copy-start.24", "%copy-start.24 = () copy-start(%sum_by_destination)", 7_000, 0]
    loop = ["while.8", "%while.8 = () while()", 1_000, 10_000]
    first = ["fusion.2", GATHER, 1_000, 4_000]
    leaves = trace_reduce.leaf_ops([loop, first, stamp, kernel, after])
    assert leaves == [first, kernel, stamp, after]
    view = trace_reduce.TraceView({"devices": [{"plane": "/device:TPU:0", "modules": [],
                                                "ops": [loop, first, stamp, kernel, after]}], "host": []}, 0, 12_000)
    assert view.busy_s() == pytest.approx(6e-6)     # the kernel's two microseconds are busy time, not a gap


def test_readers_on_the_hand_trace():
    compact = hand_trace()
    a, b = trace_reduce.window_of(compact, {}, "multi_step", None)
    ctx = {"config": TINY_CONFIG, "view": trace_reduce.TraceView(compact, a, b),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "device": {"platform": "tpu", "count": 1, "memory_peak_bytes": 7_000_000_000},
           "window": {"window_start": 10.0, "window_stop": 20.0, "kind": "scan_calls"},
           "compiles": [[5.0, "/jax/core/compile/backend_compile_duration", 1.0],
                        [12.0, "/jax/core/compile/backend_compile_duration", 0.5],
                        [13.0, "/jax/compilation_cache/cache_hits", None]]}
    layer_dir = BENCH / "layer_metrics"

    def read(name):
        return harness.read_layer_metric(layer_dir, name, ctx)

    assert read("compile.in_window") == 1
    assert read("host.gap_ms_per_call") == pytest.approx(0.020)
    assert read("step.device_ms") == pytest.approx(0.010)     # one call of 100 us, ten steps
    assert read("device.idle_pct.steady") == pytest.approx(100 * 20 / 120)
    assert read("device.peak_hbm_gb") == pytest.approx(7.0)
    import flops

    rate = 10 / 120e-6
    assert read("step_mfu") == pytest.approx(100 * flops.step_flops(TINY_CONFIG)["total"] * rate / 197e12)
    # the gather VJP's roofline reads the program's `gather` scope, not a shape: this trace has the shapes
    # and no op names, so there is nothing to read (with names: test_scope_reduce's hand trace)
    assert view_scatter_seconds(ctx) == pytest.approx(50e-6) and read("scatter_roofline") is None
    # a reader that finds nothing to read returns nothing
    ctx["view"] = None
    assert read("scatter_roofline") is None and read("step_mfu") is None


def view_scatter_seconds(ctx):
    import flops

    return ctx["view"].op_seconds(lambda name, shapes: flops.is_scatter(ctx["config"], shapes))


RECORDED = TESTS / "data" / "recorded_trace.json.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace_reduces():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    config = json.loads((BENCH / "configs" / "gnn-32k-512.json").read_text())
    a, b = trace_reduce.window_of(recorded, {}, config["scan_program"], None)
    view = trace_reduce.TraceView(recorded, a, b)
    import flops

    scatter = view.op_seconds(lambda name, shapes: flops.is_scatter(config, shapes))
    assert 0 < scatter < view.busy_s() <= view.window_s
    assert scatter / view.busy_s() > 0.3      # the finding this benchmark starts from
