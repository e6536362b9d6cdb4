"""The four-chip cell's own numbers: the exchange's byte count and the mesh's
share of its peak against counts worked by hand, the `mesh.*` readers on a
trace of four device planes built by hand, and a rehearsal of the cell at a
tiny size on four virtual CPU devices.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mesh_metrics.py -q
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "layer_metrics"))

import exchange  # noqa: E402
import flops  # noqa: E402
import run as harness  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "gnn-64k-512.steady"
CONFIG = json.loads((BENCH / "configs" / "gnn-64k-512.json").read_text())
ICI = json.loads((BENCH / "peaks_ici.json").read_text())["TPU v5 lite"]
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def test_exchange_bytes_by_hand():
    # u[N, H] in bf16: 65,536 x 512 x 2 = 67,108,864 bytes; a chip of four lacks (all-gather) or owes
    # (reduce-scatter) three quarters of it, 50,331,648; three layers, both directions: 6 x that
    got = exchange.exchange_floor(CONFIG, 4, ICI)
    assert got["bytes"] == 6 * 50_331_648 == 301_989_888
    assert (got["all_gathers"], got["reduce_scatters"]) == (3, 3)
    # 1,600 Gbit/s = 200e9 bytes/s a chip
    assert got["seconds"] == pytest.approx(301_989_888 / 200e9) == pytest.approx(1.50994944e-3)
    assert exchange.exchange_floor(CONFIG, 1, ICI)["bytes"] == 0
    assert exchange.exchange_floor(CONFIG, 2, ICI)["bytes"] == 6 * 33_554_432


def test_step_flops_of_the_new_configuration_by_hand():
    # forward: 2*N*12*H + 3*(3*2*N*H*H + 2*N*16*4*H) + 2*N*H*D + head(B = 2,048; 3D+16 = 784 -> 256 -> 128 -> 1)
    # 805,306,368 + 3*(103,079,215,104 + 4,294,967,296) + 17,179,869,184 + (822,083,584 + 134,217,728 + 524,288)
    forward = 341_064_548_352
    # backward: every product's weight gradient, and the input gradient of all but the first Dense and the edge projections
    backward = forward + (forward - 805_306_368 - 3 * 4_294_967_296)
    got = flops.step_flops(CONFIG)
    assert (got["forward"], got["backward"], got["total"]) == (forward, backward, forward + backward)


# ---- the readers on four device planes ----

AG_START = "%async-collective-start = (bf16[16,32]{1,0}, bf16[64,32]{1,0}, s32[2]{0}) fusion(bf16[16,32]{1,0} %u), kind=kCustom, calls=%async_collective_start"
AG_DONE = "%async-collective-done = bf16[64,32]{1,0} fusion(bf16[16,32]{1,0} %a, bf16[64,32]{1,0} %b, s32[2]{0} %c), kind=kCustom, calls=%async_collective_done"
CARRIES_A_START = ("%fusion.829 = (bf16[16,32]{1,0}, bf16[64,32]{1,0}) fusion(bf16[16,32]{1,0} %h), kind=kOutput, "
                   "calls=%async_collective_fusion.829")
REDUCE_SCATTER = "%fusion.776 = bf16[16,32]{1,0} fusion(bf16[64,32]{1,0} %fusion.775), kind=kCustom, calls=%all-reduce-scatter.2.clone"
READS_A_COLLECTIVE = "%fusion.777 = bf16[16,16,32]{2,0,1} fusion(bf16[64,32]{1,0} %all-gather-done.3, s32[16,16]{1,0} %n), kind=kLoop, calls=%fused_computation.5"
SHARD_SCATTER = ("%fusion.775 = bf16[64,32]{1,0} fusion(s32[256]{0} %rows, bf16[256,32]{1,0} %cotangent, s32[256]{0} %order), "
                 "kind=kCustom, calls=%scatter")
GRADS = "%all-reduce.9 = f32[32,32]{1,0} all-reduce(f32[32,32]{1,0} %g), replica_groups={{0,1,2,3}}, to_apply=%add"

TINY_CONFIG = {
    "scan_program": "multi_step", "cluster": {"hosts": 64},
    "optimizer": {"gnn": {"steps_per_call": 10}},
    "model": {"num_neighbors": 16, "hidden": 32, "embed_dim": 16, "node_features": 12, "edge_features": 4,
              "pair_features": 16, "num_layers": 1, "pair_batch": 128, "head_hidden": [256, 128, 1],
              "compute_dtype": "bfloat16"},
}


BODY = "jit(multi_step)/while/body/closed_call/"
FORWARD = BODY + "jvp(TopoScorer)/encoder/SAGELayer_0/"
BACKWARD = BODY + "transpose(jvp(TopoScorer))/encoder/SAGELayer_0/"
# the op_name of each of a call's seven ops, in the order `four_plane_trace` lists them: on a `data` mesh the
# all-gather of `u` and the reduce-scatter of its cotangent carry the `gather` scope (PERF.md, PR 27)
OP_NAMES = [FORWARD + "gather/jit(_take)/gather", FORWARD + "dense/msg_self/dot_general", FORWARD + "gather/jit(_take)/gather",
            FORWARD + "gather/jit(_take)/gather", BACKWARD + "gather/jit(_take)/scatter-add",
            BACKWARD + "gather/jit(_take)/scatter-add", BODY + "optimizer/psum"]
# the per-shard kernel of PR 31's program in place of the scatter-add (the shapes of its traced four-chip run,
# shrunk): a reorder gather of a cotangent slice, the wait on a table's copy into fast memory, the kernel
REORDER = "%fusion.772 = bf16[128,32]{1,0} fusion(bf16[128,32]{1,0} %bitcast.438, s32[128]{0:S(1)} %copy-done.75), kind=kCustom, calls=%gather"
TABLE_DONE = "%copy-done.187 = s32[24]{0:S(1)} copy-done((s32[24]{0:S(1)}, s32[24]{0}, u32[]{:S(2)}) %copy-start.187)"
SHARD_KERNEL = ("%sum_by_destination.1 = bf16[64,32]{1,0} custom-call(s32[]{:T(128)} %bitcast.396, s32[24]{0:S(1)} %copy-done.187, "
                "s32[24]{0:S(1)} %copy-done.188, bf16[256,32]{1,0} %fusion.772), custom_call_target=\"tpu_custom_call\"")


def four_plane_trace():
    """Three executions of `jit_multi_step`, 100 us each, 20 us apart, on four
    planes. In each: an asynchronous all-gather, its start [0, 1) and its done
    [9, 10) around a Dense that carries the start along [1, 9); a gather that
    reads its result into the shard's [N/4, K, H] [10, 50); the shard's
    scatter-add into [N, H] [54, 60); a reduce-scatter [60, 80) and the
    gradients' all-reduce [80, 85). Plane k runs its gather k microseconds
    longer (so it idles k us less before the scatter, which starts together
    everywhere)."""
    devices = []
    for k in range(4):
        ops, modules = [], []
        for call in range(3):
            t = 1_000 + call * 120_000
            modules.append(["jit_multi_step(123)", "", t, 100_000])
            ops += [["async-collective-start", AG_START, t, 1_000], ["fusion.829", CARRIES_A_START, t + 1_000, 8_000],
                    ["async-collective-done", AG_DONE, t + 9_000, 1_000],
                    ["fusion.777", READS_A_COLLECTIVE, t + 10_000, 40_000 + 1_000 * k],
                    ["fusion.775", SHARD_SCATTER, t + 54_000, 6_000],
                    ["fusion.776", REDUCE_SCATTER, t + 60_000, 20_000],
                    ["all-reduce.9", GRADS, t + 80_000, 5_000]]
        devices.append({"plane": f"/device:TPU:{k}", "ops": ops, "modules": modules})
    return {"devices": devices, "host": [], "marker_ns": 500}


def test_mesh_readers_on_a_hand_built_four_plane_trace():
    compact = four_plane_trace()
    a, b = trace_reduce.window_of(compact, {}, "multi_step", None)
    assert (a, b) == (121_000, 241_000)      # one whole call period: ten steps
    placement = {"graph": {"bytes": 4_000, "per_device_bytes": [1_000, 1_000, 1_000, 1_000]}}
    ctx = {"config": TINY_CONFIG, "view": trace_reduce.TraceView(compact, a, b), "peaks": PEAKS,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
           "device_ops": named(compact, OP_NAMES),
           "runs": [{"models": {"gnn": {"placement": placement}}}]}
    layer_dir = BENCH / "layer_metrics"

    def read(name):
        return harness.read_layer_metric(layer_dir, name, ctx)

    # in collective ops: the all-gather's start and done (1 + 1 us: what is exposed of it), the reduce-scatter
    # and the all-reduce, 27 us a call over ten steps; neither the Dense that carries the start along nor the
    # gather that only READS a collective's result is one
    assert read("mesh.collective_ms") == pytest.approx(27e-6 * 1e3 / 10)
    # an exchange in flight: [0, 10) from the start's begin to the done's end, and [60, 85): 35 us a call
    floor = exchange.exchange_floor(TINY_CONFIG, 4, ICI)
    assert floor["bytes"] == 2 * (64 * 32 * 2) * 3 // 4
    assert read("mesh.collective_roofline") == pytest.approx(100 * floor["seconds"] / 3.5e-6)
    rate = 10 / 120e-6
    assert read("mesh.step_mfu") == pytest.approx(
        100 * flops.step_flops(TINY_CONFIG)["total"] * rate / (4 * 197e12))
    assert read("mesh.step_mfu") == pytest.approx(read("step_mfu") / 4)
    assert read("mesh.graph_shard_pct") == pytest.approx(25.0)
    # busy: 81 us on plane 0 (1 + 8 + 1 + 40 + 6 + 20 + 5), 84 us on plane 3
    assert read("mesh.plane_skew_pct") == pytest.approx(100 * (84 / 81 - 1))
    # a row shard's kernels, first plane, against a chip's quarter of the whole graph's floor. The gather's VJP
    # by the program's scope: the scatter-add (result [N, H], the shard's 256 row numbers and [256, H]
    # cotangent) 6 us a call and the reduce-scatter of the partial sums 20 us. The ops on the shard's
    # [16, 16, 32] that are not the scatter-add's shape (the forward gather) 40 us a call. One chip's readers
    # read nothing on four: their floors are one chip's, and `msg_roofline` matches no whole-N shape
    scatter = flops.scatter_floor(TINY_CONFIG, PEAKS)
    assert scatter["bytes"] == 1024 * 32 * 2 + 1024 * 4 + 64 * 32 * 2
    assert read("mesh.scatter_roofline") == pytest.approx(100 * scatter["seconds"] / 4 * 10 / 26e-6)
    assert read("mesh.msg_roofline") == pytest.approx(
        100 * flops.message_floor(TINY_CONFIG, PEAKS)["seconds"] / 4 * 10 / 40e-6)
    assert read("scatter_roofline") is None and read("msg_roofline") is None
    # the accepted readers the cell also lists read the first plane, or the mean over planes
    assert read("step.device_ms") == pytest.approx(0.010)
    assert read("device.idle_pct.steady") == pytest.approx(100 * (1 - 82.5 / 120))

    # the graph whole on each device, as the default mesh on four chips leaves it
    placement["graph"]["per_device_bytes"] = [4_000] * 4
    assert read("mesh.graph_shard_pct") == pytest.approx(100.0)
    # a program that says nothing of its placement, one chip, or no trace: nothing to read, and no error
    ctx["runs"] = [{"models": {"gnn": {}}}]
    assert read("mesh.graph_shard_pct") is None
    one = {**ctx, "device": {**ctx["device"], "count": 1},
           "view": trace_reduce.TraceView({**compact, "devices": compact["devices"][:1]}, a, b)}
    assert harness.read_layer_metric(layer_dir, "mesh.plane_skew_pct", one) is None
    assert harness.read_layer_metric(layer_dir, "mesh.collective_roofline", one) is None
    # kernels over `model` (the parent's default mesh on four chips) leave [N, K, H/4]: no shard of rows for
    # `mesh.msg_roofline` to read by shape; the VJP's scope is there whatever the shapes
    for op in (op for d in compact["devices"] for op in d["ops"]):
        op[1] = op[1].replace(",32]", ",8]")
    assert read("mesh.msg_roofline") is None
    assert read("mesh.scatter_roofline") == pytest.approx(100 * scatter["seconds"] / 4 * 10 / 26e-6)
    # a program that names nothing (no scopes in its op names): nothing to read
    ctx["device_ops"] = [[op[0], "", op[2], op[3]] for op in ctx["device_ops"]]
    ctx.pop("step_ops")
    assert read("mesh.scatter_roofline") is None
    ctx["view"] = None
    assert all(read(name) is None for name in
               ("mesh.collective_ms", "mesh.collective_roofline", "mesh.step_mfu", "mesh.plane_skew_pct",
                "mesh.scatter_roofline", "mesh.msg_roofline"))


def named(compact, op_names):
    """`scope_reduce.device_ops` of the first plane: each op with its op_name."""
    ops = compact["devices"][0]["ops"]
    return [[op[0], op_names[i % len(op_names)], op[2], op[3]] for i, op in enumerate(ops)]


def test_mesh_scatter_roofline_follows_the_vjp_through_a_per_shard_kernel():
    """The four-plane trace with the derived scatter-add [54, 60) replaced by
    what PR 31's program ran on a shard: a reorder gather [50, 52), the wait
    on a block table's unnamed copy into fast memory [52, 54), the kernel
    [54, 60); the reduce-scatter [60, 80) stays. No op has the scatter-add's
    shapes (the reader by shape found nothing in that program's traced run);
    by the scope the VJP is 30 us a call, the wait on the copy with it."""
    import _mesh

    compact = four_plane_trace()
    for d in compact["devices"]:
        ops = []
        for op in d["ops"]:
            if op[0] != "fusion.775":
                ops.append(op)
                continue
            t = op[2] - 4_000
            ops += [["fusion.772", REORDER, t, 2_000], ["copy-done.187", TABLE_DONE, t + 2_000, 2_000],
                    ["sum_by_destination.1", SHARD_KERNEL, t + 4_000, 6_000]]
        d["ops"] = ops
    # plane 0 ran its forward gather [10, 50): the reorder gather follows it at once
    a, b = trace_reduce.window_of(compact, {}, "multi_step", None)
    view = trace_reduce.TraceView(compact, a, b)
    assert view.op_seconds(lambda name, shapes: _mesh.is_shard_scatter(TINY_CONFIG, 4, shapes)) == 0
    kernel = BACKWARD + "gather/jit(sum_by_destination)/pallas_call"
    op_names = OP_NAMES[:4] + [BACKWARD + "gather/jit(_take)/gather", "", kernel] + OP_NAMES[5:]
    ctx = {"config": TINY_CONFIG, "view": view, "peaks": PEAKS, "device_ops": named(compact, op_names),
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, "runs": []}
    layer_dir = BENCH / "layer_metrics"
    floor = flops.scatter_floor(TINY_CONFIG, PEAKS)["seconds"]
    assert harness.read_layer_metric(layer_dir, "mesh.scatter_roofline", ctx) == pytest.approx(100 * floor / 4 * 10 / 30e-6)
    assert harness.read_layer_metric(layer_dir, "scope.gather_bwd_ms", ctx) == pytest.approx(28e-6 * 1e3 / 10)
    # the same wait on a copy that a `message` op reads is none of the VJP's
    for op in compact["devices"][0]["ops"]:
        if op[0] == "sum_by_destination.1":
            op[1] = op[1].replace("%copy-done.187, ", "")
        if op[0] == "fusion.829":
            op[1] = op[1].replace("%h)", "%h, s32[24]{0:S(1)} %copy-done.187)")
    ctx = {**ctx, "view": trace_reduce.TraceView(compact, a, b)}
    ctx.pop("step_ops")
    assert harness.read_layer_metric(layer_dir, "mesh.scatter_roofline", ctx) == pytest.approx(100 * floor / 4 * 10 / 28e-6)


def test_benchmark_json_lists_the_new_cell_where_its_readers_read():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("gnn-64k-512", "steady", 4)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert {m for m in listed if m.startswith("mesh.")} == {
        "mesh.step_mfu", "mesh.collective_ms", "mesh.collective_roofline", "mesh.graph_shard_pct", "mesh.plane_skew_pct",
        "mesh.scatter_roofline", "mesh.msg_roofline"}
    # one chip's peak, floors and shapes: not for a cell on four
    assert not listed & {"step_mfu", "msg_roofline", "scatter_roofline"}
    assert all((BENCH / "layer_metrics" / f"{name}.py").is_file() for name in listed)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_steps_per_s")["workloads"]


def test_rehearsal_of_the_cell_on_four_virtual_devices(tmp_path):
    """run.py end to end, traced, with the cell's own entry (chips 4, the
    steady mix, every metric it lists) over the tiny configuration, on four
    virtual CPU devices: correct, no metric, and no reader raised."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    tiny = TESTS / "data" / "tiny"
    bench["configs"] = [{"name": "tiny", "source": "benchmarks/tests", "reduced": ["gnn_steps", "mlp_steps"],
                         "file": str((tiny / "tiny.json").relative_to(REPO)), "why": "CPU rehearsal"}]
    bench["workloads"] = [{"name": "tiny.steady", "config": "tiny", "traffic": "steady", "chips": 4, "why": "rehearsal"}]
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if "workloads" in m:
                m["workloads"] = ["tiny.steady"] if CELL in m["workloads"] else []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "limits").symlink_to(tiny / "limits")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "tiny.steady", "--seed", "2147483783",
         "--seconds", "1", "--trace", "1", "--cpu-rehearsal", "--benchmark-json", str(tmp_path / "BENCHMARK.json")],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["device"] == {
        "platform": "cpu", "kind": result["device"]["kind"], "count": 4, "memory_peak_bytes": None}
    # on the CPU no device plane is traced and no device metric is read; what the host's counters give is there
    assert "compile.in_window" in result["rehearsal"]["read"]
    assert not any(name.startswith("mesh.") for name in result["rehearsal"]["read"])
    # a traced run's line says what the trainer's child took to answer `trace_stop`, beside the limit it was given
    after = result["after_window"]
    assert 0 < after["trace_stop_s"] < after["trace_stop_limit_s"] and after["trace_stop_limit_s"] >= 120.0
    assert "trace_reduce_s" in after
