"""The readers of the program's own names (the step's scopes, the trainer's
`trainer.gnn.call` events): the pass over an xplane built by hand, a trace
small enough to work out by hand, the traces recorded on the chip with the
scopes in them (data/recorded_scopes.json.gz, PR 25: the gather's VJP a
scatter-add fusion; data/recorded_sorted_vjp.json.gz, PR 32: reorder gathers
and the kernel `sum_by_destination`; both cut from traced runs of
gnn-32k-512.steady), and the older recorded trace, which has neither. The
gather VJP's roofline (`scatter_roofline`) reads the `gather` backward scope
with the unnamed copies that feed it, so it reads in both programs."""

import gzip
import json
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "layer_metrics"))

import flops  # noqa: E402
import run as harness  # noqa: E402
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402
from test_trace_reduce import DENSE, GATHER, SCATTER, TINY_CONFIG  # noqa: E402

LAYER_DIR = BENCH / "layer_metrics"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SCOPE_METRICS = ["scope.gather_bwd_ms", "scope.message_ms", "scope.dense_ms", "scope.optimizer_ms"]
HOST_PARTS = ["host.dispatch_ms", "host.pull_tail_ms", "host.loop_turn_ms"]
NEW_METRICS = SCOPE_METRICS + ["scope.unattributed_pct"] + HOST_PARTS + ["host.stall_ms"]

BODY = "jit(multi_step)/while/body/closed_call/"
SCATTER_NAME = BODY + "transpose(jvp(TopoScorer))/encoder/SAGELayer_0/gather/jit(_take)/scatter-add"
GATHER_NAME = BODY + "jvp(TopoScorer)/encoder/SAGELayer_0/gather/jit(_take)/gather"
DENSE_NAME = BODY + "jvp(TopoScorer)/encoder/SAGELayer_0/dense/msg_nbr/dot_general"


@pytest.mark.parametrize("op_name,expected", [
    (SCATTER_NAME, ("gather", True)),
    (GATHER_NAME, ("gather", False)),
    (DENSE_NAME, ("dense", False)),
    (BODY + "transpose(jvp(TopoScorer))/encoder/SAGELayer_2/message/msg_edge/dot_general", ("message", True)),
    (BODY + "jvp(TopoScorer))/encoder/SAGELayer_1/reduce/reduce_sum", ("reduce", False)),
    (BODY + "jvp(TopoScorer)/head/head/layers_0/dot_general", ("head", False)),
    (BODY + "transpose(jvp(loss))/mul", ("loss", True)),
    (BODY + "optimizer/jit(clip)/max", ("optimizer", False)),
    # the primitive's own name is no scope: a pool gather belongs to `sample`
    (BODY + "sample/gather", ("sample", False)),
    ("jit(multi_step)/sample/jit(_threefry_split)/slice", ("sample", False)),
    ("jit(multi_step)/while/body/gather", (None, False)),
    ("jit(multi_step)/while/body/dynamic_update_slice", (None, False)),
    ("", (None, False)),
    (None, (None, False)),
])
def test_classify(op_name, expected):
    assert scope_reduce.classify(op_name) == expected


XSPACE = """
planes { name: "/host:CPU" lines { name: "python" events { metadata_id: 1 offset_ps: 1000 duration_ps: 5 } }
         event_metadata { key: 1 value { id: 1 name: "trainer.gnn.call" } } }
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000 events { metadata_id: 9 offset_ps: 4000000 duration_ps: 9000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 5000000 duration_ps: 2000750
                   stats { metadata_id: 3 uint64_value: 5000000 } }
          events { metadata_id: 2 offset_ps: 8000999 duration_ps: 1000000 }
          events { metadata_id: 1 offset_ps: 300000000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[64,32]{1,0} fusion(s32[1024]{0} %i), kind=kInput"
                                  stats { metadata_id: 4 str_value: "scatter fusion" }
                                  stats { metadata_id: 7 str_value: "SCATTER_NAME:" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy-done.4 = bf16[64,32]{1,0} copy-done(%copy-start.4)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_multi_step(123)" } }
  stat_metadata { key: 3 value { id: 3 name: "device_offset_ps" } }
  stat_metadata { key: 4 value { id: 4 name: "hlo_category" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
planes { name: "/device:TPU:1" lines { name: "XLA Ops" events { metadata_id: 1 offset_ps: 1 duration_ps: 1 } } }
""".replace("SCATTER_NAME", SCATTER_NAME)


def test_the_pass_over_an_xplane_reads_names_from_the_event_metadata(tmp_path):
    from jaxlib._profile_data import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops = scope_reduce.device_ops(path)
    assert ops == [["fusion.1", SCATTER_NAME, 6000, 2000], ["copy-done.4", "", 9000, 1000],
                   ["fusion.1", SCATTER_NAME, 300_001_000, 2000]]
    # the same events, at the same times, as the reduction the other readers go through
    compact = trace_reduce.compact_xplane(path)
    assert [[op[0], op[2], op[3]] for op in compact["devices"][0]["ops"]] == [[op[0], op[2], op[3]] for op in ops]
    assert scope_reduce.scoped_ops(ops, 7000, 9500) == [(7000, 1000, "gather", True, "fusion.1"),
                                                        (9000, 500, None, False, "copy-done.4")]
    (tmp_path / "host_only.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "/host:CPU" }'))
    assert scope_reduce.device_ops(tmp_path / "host_only.pb") == []


def hand_trace():
    """test_trace_reduce's hand trace (three executions of 100 us, 20 us apart;
    in each a dense op [0, 30), a gather [30, 60), a scatter [50, 100)) inside a
    `while`, with each op's op_name and the host's call events: a call begins
    8 us before its execution does and ends 5 us after it."""
    ops, names, modules, host = [], [], [], []
    for call in range(3):
        t = 1_000 + call * 120_000
        modules.append(["jit_multi_step(123)", "", t, 100_000])
        ops += [["while.1", "%while.1 = () while()", t, 100_000], ["convolution.3", DENSE, t, 30_000],
                ["fusion.2", GATHER, t + 30_000, 30_000], ["fusion.1", SCATTER, t + 50_000, 50_000]]
        names += ["jit(multi_step)/while", DENSE_NAME, GATHER_NAME, SCATTER_NAME]
        host.append(["python", "trainer.gnn.call", t - 8_000, 113_000])
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops, "modules": modules}], "host": host,
            "marker_ns": 500}, names


def context(compact, names, config, **extra):
    a, b = trace_reduce.window_of(compact, {}, config["scan_program"], None)
    ops = compact["devices"][0]["ops"]
    return {"config": config, "view": trace_reduce.TraceView(compact, a, b),
            "device_ops": names and [[op[0], name, op[2], op[3]] for op, name in zip(ops, names)],
            "peaks": PEAKS,
            "device": {"platform": "tpu", "count": 1, "memory_peak_bytes": 7_000_000_000},
            "window": {"window_start": 10.0, "window_stop": 20.0, "kind": "scan_calls"},
            "runs": [], "compiles": [], **extra}


def read_all(ctx, names):
    return {name: harness.read_layer_metric(LAYER_DIR, name, ctx) for name in names}


def test_readers_on_the_hand_trace():
    compact, names = hand_trace()
    manifest = {"models": {"gnn": {"calls": {"count": 3, "stall_ms": 12.5}}, "mlp": {"calls": None}}}
    got = read_all(context(compact, names, TINY_CONFIG, runs=[manifest]), NEW_METRICS + ["step.device_ms"])
    # the window holds one call (ten steps): 50 us of scatter, 30 of gather, 30 of dense; the
    # gather and the scatter overlap by 10 us, so the named ops exceed the 100 us by those
    assert got["scope.gather_bwd_ms"] == pytest.approx(0.005)
    assert got["scope.message_ms"] == pytest.approx(0.003)
    assert got["scope.dense_ms"] == pytest.approx(0.003)
    assert got["scope.optimizer_ms"] == 0
    assert got["scope.unattributed_pct"] == pytest.approx(-10.0)
    assert sum(got[m] for m in SCOPE_METRICS) + got["scope.unattributed_pct"] / 100 * got["step.device_ms"] \
        == pytest.approx(got["step.device_ms"])
    assert (got["host.dispatch_ms"], got["host.pull_tail_ms"], got["host.loop_turn_ms"]) \
        == pytest.approx((0.008, 0.005, 0.007))
    assert got["host.stall_ms"] == 12.5
    # the gather's VJP (here a scatter-add fusion, found by its scope) against its floor: 50 us a call of ten steps
    floor = flops.scatter_floor(TINY_CONFIG, PEAKS)["seconds"]
    ctx = context(compact, names, TINY_CONFIG)
    assert read_all(ctx, ["scatter_roofline"])["scatter_roofline"] == pytest.approx(100 * floor * 10 / 50e-6)
    # one chip's floor: nothing to read on a mesh, where `mesh.scatter_roofline` takes a chip's share of it
    ctx["device"]["count"] = 4
    got = read_all(ctx, ["scatter_roofline", "mesh.scatter_roofline"])
    assert got["scatter_roofline"] is None
    assert got["mesh.scatter_roofline"] == pytest.approx(100 * floor / 4 * 10 / 50e-6)


# ---- the unnamed copies that feed a scope's ops ----

COPY_START = "%copy-start.7 = (bf16[1024,32]{1,0:S(1)}, bf16[1024,32]{1,0}, u32[]{:S(2)}) copy-start(bf16[1024,32]{1,0} %c)"
COPY_DONE = "%copy-done.7 = bf16[1024,32]{1,0:S(1)} copy-done((bf16[1024,32]{1,0:S(1)}, bf16[1024,32]{1,0}, u32[]{:S(2)}) %copy-start.7)"
SLICE_DONE = "%slice-done.8 = bf16[64,32]{1,0:S(1)} async-done(((bf16[64,32]{1,0}), bf16[64,32]{1,0:S(1)}, s32[]{:S(2)}) %slice-start.8)"
KERNEL = ("%sum_by_destination = bf16[64,32]{1,0} custom-call(s32[]{:T(128)} %bitcast.3, bf16[1024,32]{1,0:S(1)} %copy-done.7), "
          "custom_call_target=\"tpu_custom_call\"")
READS_SLICE = "%fusion.2 = bf16[1024,32]{1,0} fusion(bf16[64,32]{1,0:S(1)} %slice-done.8, s32[1024]{0} %i), kind=kLoop, calls=%fused_computation.2"
KERNEL_NAME = BODY + "transpose(jvp(TopoScorer))/encoder/SAGELayer_0/gather/jit(sum_by_destination)/pallas_call"


def test_operands_are_the_instructions_read_not_the_computations_called():
    assert scope_reduce.operands_of(KERNEL) == ["bitcast.3", "copy-done.7"]
    assert scope_reduce.operands_of(READS_SLICE) == ["slice-done.8", "i"]
    assert scope_reduce.operands_of(COPY_DONE) == ["copy-start.7"]
    assert scope_reduce.operands_of("fusion.1") == []


def test_an_unnamed_op_takes_the_scope_its_result_is_read_under():
    texts = {"copy-start.7": COPY_START, "copy-done.7": COPY_DONE, "slice-done.8": SLICE_DONE,
             "sum_by_destination": KERNEL, "fusion.2": READS_SLICE,
             "copy-done.9": "%copy-done.9 = bf16[64,32]{1,0} copy-done(%copy-start.9)",
             "fusion.5": "%fusion.5 = bf16[64,32]{1,0} fusion(bf16[64,32]{1,0} %copy-done.9, bf16[64,32]{1,0} %copy-done.7)",
             "copy-done.11": "%copy-done.11 = bf16[64,32]{1,0} copy-done(%copy-start.11)"}
    scoped = {"sum_by_destination": ("gather", True), "fusion.2": ("gather", False), "fusion.5": ("dense", True)}
    found = scope_reduce.scopes_by_consumer({k: v for k, v in texts.items() if k != "fusion.5"}, scoped)
    # the start through its done to the kernel; the slice to the forward gather; one that nothing reads is left out
    assert found == {"copy-start.7": ("gather", True), "copy-done.7": ("gather", True), "slice-done.8": ("gather", False)}
    # read under two scopes: left with none (and so is its start); what `dense` alone reads goes to `dense`
    found = scope_reduce.scopes_by_consumer(texts, scoped)
    assert found == {"slice-done.8": ("gather", False), "copy-done.9": ("dense", True)}


def fed_trace():
    """The hand trace with the VJP a kernel that waits for a copy into fast
    memory: in each execution a dense op [0, 30), the forward gather's wait on
    a slice [30, 36) and the gather [36, 60), the copy's start [60, 60), the
    wait on it [60, 64), the kernel [64, 100)."""
    ops, names, modules = [], [], []
    for call in range(3):
        t = 1_000 + call * 120_000
        modules.append(["jit_multi_step(123)", "", t, 100_000])
        ops += [["while.1", "%while.1 = () while()", t, 100_000], ["convolution.3", DENSE, t, 30_000],
                ["slice-done.8", SLICE_DONE, t + 30_000, 6_000], ["fusion.2", READS_SLICE, t + 36_000, 24_000],
                ["copy-start.7", COPY_START, t + 60_000, 0], ["copy-done.7", COPY_DONE, t + 60_000, 4_000],
                ["sum_by_destination", KERNEL, t + 64_000, 36_000]]
        names += ["jit(multi_step)/while", DENSE_NAME, "", GATHER_NAME, "", "", KERNEL_NAME]
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops, "modules": modules}], "host": [], "marker_ns": 500}, names


def test_a_copy_that_feeds_the_gathers_backward_is_counted_and_one_that_feeds_another_scope_is_not():
    compact, names = fed_trace()
    ctx = context(compact, names, TINY_CONFIG)
    got = read_all(ctx, SCOPE_METRICS + ["scope.unattributed_pct", "scatter_roofline"])
    # the scopes keep their definition: named ops only, the two waits (10 us of 100) under no name
    assert got["scope.gather_bwd_ms"] == pytest.approx(0.0036) and got["scope.message_ms"] == pytest.approx(0.0024)
    assert got["scope.unattributed_pct"] == pytest.approx(10.0)
    # the VJP's roofline counts the kernel and the wait on the copy it reads, 40 us a call; not the slice's
    floor = flops.scatter_floor(TINY_CONFIG, PEAKS)["seconds"]
    assert got["scatter_roofline"] == pytest.approx(100 * floor * 10 / 40e-6)
    import _scopes

    assert _scopes.fed_ms(ctx, lambda scope, backward: scope == "gather" and not backward) == pytest.approx(0.0006)
    assert _scopes.fed_ms(ctx, lambda scope, backward: scope == "message") == 0
    # the same kernel reading its input in place: the scope alone
    compact, names = fed_trace()
    for op in compact["devices"][0]["ops"]:
        op[1] = op[1].replace("%copy-done.7)", "%cotangent)")
    got = read_all(context(compact, names, TINY_CONFIG), ["scatter_roofline"])
    assert got["scatter_roofline"] == pytest.approx(100 * floor * 10 / 36e-6)


def test_readers_find_nothing_without_names_or_a_trace():
    compact, names = hand_trace()
    # a program without scopes: op names as flax and JAX alone give them
    plain = [n.replace("/gather/", "/").replace("/dense/", "/") for n in names]
    compact["host"] = [h for h in compact["host"] if h[1] != "trainer.gnn.call"]
    assert set(read_all(context(compact, plain, TINY_CONFIG), NEW_METRICS).values()) == {None}
    # no xplane of this run to be found (sys.argv names no workload), no trace at all, a rehearsal on the CPU
    assert set(read_all(context(compact, None, TINY_CONFIG), SCOPE_METRICS).values()) == {None}
    ctx = context(compact, names, TINY_CONFIG, runs=[{"models": {"gnn": {"calls": {"stall_ms": 0.0}}}}])
    ctx["view"] = None
    ctx["device"] = {"platform": "cpu"}
    assert set(read_all(ctx, NEW_METRICS).values()) == {None}


RECORDED = TESTS / "data" / "recorded_scopes.json.gz"
CONFIG_32K = json.loads((BENCH / "configs" / "gnn-32k-512.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        trace = json.load(f)
    return context(trace, trace["op_names"], CONFIG_32K)


def test_recorded_scatter_fusion_is_the_gathers_backward(recorded):
    ops = recorded["view"].devices[0]["ops"]
    names = {(op[0], op[2]): op[1] for op in recorded["device_ops"]}
    scatters = [op for op in ops if flops.is_scatter(CONFIG_32K, trace_reduce.shapes_of(op[1]))]
    assert len(scatters) == 2 * 10 * 3       # two calls in the window, ten steps, three SAGE layers
    assert {scope_reduce.classify(names[op[0], op[2]]) for op in scatters} == {("gather", True)}
    assert {names[op[0], op[2]].split("/")[-4] for op in scatters} == {"SAGELayer_0", "SAGELayer_1", "SAGELayer_2"}


def test_recorded_scopes_cover_the_step(recorded):
    got = read_all(recorded, SCOPE_METRICS + ["scope.unattributed_pct", "step.device_ms"])
    steps, view = 20, recorded["view"]
    # every leaf op of the window: the step's ops, named or not, and two tiny programs between the calls
    leaf_ms = view.op_seconds(lambda name, shapes: True) * 1e3 / steps
    unnamed_ms = sum(op[1] for op in recorded["step_ops"] if op[2] is None) / steps / 1e6
    assert sum(got[m] for m in SCOPE_METRICS) + unnamed_ms == pytest.approx(leaf_ms, rel=1e-4)
    assert unnamed_ms < 0.005 * leaf_ms
    # what is in no named leaf op is the unattributed share, so that the five add up to the step
    assert sum(got[m] for m in SCOPE_METRICS) + got["scope.unattributed_pct"] / 100 * got["step.device_ms"] \
        == pytest.approx(got["step.device_ms"])
    assert 0 < got["scope.unattributed_pct"] < 5
    # the same ops found two ways: by the program's name and by their shapes
    by_shape_ms = view.op_seconds(lambda name, shapes: flops.is_scatter(CONFIG_32K, shapes)) * 1e3 / steps
    assert got["scope.gather_bwd_ms"] == pytest.approx(by_shape_ms, rel=0.03)
    assert got["scope.gather_bwd_ms"] > got["scope.message_ms"] > got["scope.dense_ms"] > got["scope.optimizer_ms"] > 0
    # and the VJP's roofline, by the scope: the floor over that time (no unnamed copy feeds a scatter-add)
    floor_ms = flops.scatter_floor(CONFIG_32K, PEAKS)["seconds"] * 1e3
    assert floor_ms == pytest.approx(2.0970, rel=1e-3)
    roofline = read_all(recorded, ["scatter_roofline"])["scatter_roofline"]
    assert roofline == pytest.approx(100 * floor_ms / got["scope.gather_bwd_ms"]) == pytest.approx(6.0, abs=0.1)


@pytest.fixture(scope="module")
def recorded_sorted():
    with gzip.open(TESTS / "data" / "recorded_sorted_vjp.json.gz", "rt") as f:
        trace = json.load(f)
    return context(trace, trace["op_names"], CONFIG_32K)


def test_recorded_sorted_vjp_reads_by_its_scope_where_no_shape_matches(recorded_sorted):
    """The program since PR 26: the VJP is reorder gathers and the kernel. No
    op has the scatter-add's shapes, so a reader by shape is silent; by the
    scope the roofline reads, and the five parts still add up to the step."""
    view = recorded_sorted["view"]
    assert view.op_seconds(lambda name, shapes: flops.is_scatter(CONFIG_32K, shapes)) == 0
    names = SCOPE_METRICS + ["scope.unattributed_pct", "step.device_ms", "scatter_roofline", "device.idle_pct.steady"]
    got = read_all(recorded_sorted, names)
    assert got["step.device_ms"] == pytest.approx(43.04, abs=0.01)
    assert sum(got[m] for m in SCOPE_METRICS) + got["scope.unattributed_pct"] / 100 * got["step.device_ms"] \
        == pytest.approx(got["step.device_ms"])
    kernels = [op for op in recorded_sorted["step_ops"] if op[4].startswith("sum_by_destination")]
    assert len(kernels) == 2 * 10 * 3 and {(op[2], op[3]) for op in kernels} == {("gather", True)}
    import _scopes

    fed = _scopes.fed_ms(recorded_sorted, lambda scope, backward: scope == "gather" and backward)
    floor_ms = flops.scatter_floor(CONFIG_32K, PEAKS)["seconds"] * 1e3
    assert got["scatter_roofline"] == pytest.approx(100 * floor_ms / (got["scope.gather_bwd_ms"] + fed))
    assert got["scatter_roofline"] == pytest.approx(12.43, abs=0.05) and 0 <= fed < 0.05
    # the unnamed copies this program waits on feed `dense`'s backward (the kernel's sums copied out of fast memory)
    assert _scopes.fed_ms(recorded_sorted, lambda scope, backward: scope == "dense" and backward) == pytest.approx(0.358, abs=0.01)


def test_recorded_ops_of_no_duration_hide_no_kernel(recorded_sorted):
    """Until PR 32 `leaf_ops` took an op for a container when the profiler had
    stamped an op of no duration with its start: in this trace 3.3% of the
    step (kernels and gathers of the `gather` scope, mostly) read as time in
    no op, and as idle time of the device."""
    ops = recorded_sorted["view"].compact["devices"][0]["ops"]
    leaves = {id(op) for op in trace_reduce.leaf_ops(ops)}
    assert {op[0] for op in ops if id(op) not in leaves} == {"while.8"}
    stamped = {op[2] for op in ops if op[3] == 0}
    hidden = [op for op in ops if op[3] > 0 and op[2] in stamped and not op[0].startswith("while")]
    assert len(hidden) > 150 and sum(op[3] for op in hidden) > 50e6
    got = read_all(recorded_sorted, ["scope.unattributed_pct", "device.idle_pct.steady", "scope.gather_bwd_ms"])
    assert got["scope.unattributed_pct"] == pytest.approx(1.29, abs=0.02)
    assert got["device.idle_pct.steady"] == pytest.approx(1.42, abs=0.02)
    assert got["scope.gather_bwd_ms"] == pytest.approx(16.87, abs=0.01)


def test_recorded_host_parts_sum_to_the_gap(recorded):
    got = read_all(recorded, HOST_PARTS + ["host.gap_ms_per_call"])
    assert all(got[m] > 0 for m in HOST_PARTS)
    assert sum(got[m] for m in HOST_PARTS) == pytest.approx(got["host.gap_ms_per_call"], rel=1e-9)
    calls = [h for h in recorded["view"].compact["host"] if h[1] == "trainer.gnn.call"]
    assert len(calls) >= len(recorded["view"].module_runs("multi_step"))


def test_the_older_recorded_trace_gives_the_new_readers_nothing():
    with gzip.open(TESTS / "data" / "recorded_trace.json.gz", "rt") as f:
        old = json.load(f)
    ctx = context(old, None, CONFIG_32K)
    assert harness.read_layer_metric(LAYER_DIR, "step.device_ms", ctx) > 0
    assert set(read_all(ctx, NEW_METRICS).values()) == {None}
    # ... nor with op names as the parent's program gives them: flax's module names alone, among
    # them the pairwise head's, which is a name of the vocabulary too
    body = "jit(multi_step)/while/body/closed_call/jvp(TopoScorer)/"
    names = [body + ("head/layers_0/dot_general" if i % 50 == 0 else "encoder/SAGELayer_0/mul")
             for i in range(len(old["devices"][0]["ops"]))]
    ctx = context(old, names, CONFIG_32K)
    assert set(read_all(ctx, NEW_METRICS).values()) == {None}
