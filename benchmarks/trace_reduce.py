"""From the profiler's trace to numbers: the reduction every per-layer metric
reads through, kept with the benchmark so that every PR computes the same
number in the same way.

Two halves. `compact_xplane` (needs jax, runs in post_child.py) turns the
profiler's `.xplane.pb` into plain lists: per device plane the executed ops
(line "XLA Ops": name, HLO text with shapes, start, duration) and the executed
programs (line "XLA Modules"), and the host's TraceMe events. An op that
holds other ops (the scan's `while`) is a container: its time is its parts'
and it is left out of every sum over ops. `TraceView`
(plain Python, used by the readers and testable on a recorded trace) cuts them
to the traced window and answers: device busy time as the union of op
intervals, idle gaps and what the host was doing in them, the executions of a
named program, and the time of the ops a predicate on their shapes selects.

All times are nanoseconds on the profiler's clock (0 = start of the trace);
`marker` ties that clock to the host's monotonic clock.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MARKER = "benchmarks.clock_marker"
# host events shorter than this are dropped from the compact trace (the
# marker always stays): they name no idle gap worth a line
HOST_MIN_NS = 20_000

_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|f8\w*)\[([\d,]*)\]")


def shapes_of(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every `dtype[d0,d1,...]` of an HLO instruction's text, in order: the
    result first, then the operands."""
    return [(d, tuple(int(x) for x in dims.split(",") if x)) for d, dims in _SHAPE.findall(text)]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def compact_xplane(path: Path) -> dict:
    """The profiler's file as plain lists (see the module's docstring)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(path))
    devices, host, marker_ns = [], [], None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            entry = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                into = entry["ops"] if line.name == OPS_LINE else entry["modules"]
                for ev in line.events:
                    # an op's event name is its whole HLO instruction,
                    # "%fusion.7 = bf16[..]{..} fusion(s32[..] %a, ...), kind=..."
                    short, _, _ = ev.name.partition(" = ")
                    text = ev.name if line.name == OPS_LINE else ""
                    into.append([short.lstrip("%"), text, int(ev.start_ns), int(ev.duration_ns)])
            devices.append(entry)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER and marker_ns is None:
                        marker_ns = int(ev.start_ns)
                    if ev.duration_ns >= HOST_MIN_NS:
                        host.append([line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return {"devices": devices, "host": host, "marker_ns": marker_ns}


def read_spans(path: Path | None) -> list[dict]:
    """The program's own spans (its tracer's JSON lines): name, start (unix
    seconds), duration_ms."""
    if path is None or not Path(path).is_file():
        return []
    out = []
    for line in Path(path).read_text().splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            continue
    return out


def leaf_ops(ops: list[list]) -> list[list]:
    """The ops that hold no other op: a `while` (the scan), a conditional or
    a call spans the ops of its body on the same line, and would count their
    time twice. An op of no duration is held by nothing and holds nothing: the
    TPU's profiler stamps a `custom-call` that only assembles buffers with the
    start of the op that follows it, often to the same nanosecond, and that op
    is no container for it: dropped, its time would read as time in no op."""
    ordered = sorted(ops, key=lambda op: (op[2], -op[3]))
    timed = [op for op in ordered if op[3] > 0]
    holders = {id(op) for op, nxt in zip(timed, timed[1:])
               if nxt[2] < op[2] + op[3] and nxt[2] + nxt[3] <= op[2] + op[3] and op[3] > nxt[3]}
    return [op for op in ordered if id(op) not in holders]


def _union_ns(intervals: list[tuple[int, int]]) -> tuple[int, list[tuple[int, int]]]:
    """(covered length, gaps between the merged intervals)."""
    covered, gaps, end = 0, [], None
    for a, b in sorted(intervals):
        if end is None:
            covered, end = b - a, b
        elif a > end:
            gaps.append((end, a))
            covered += b - a
            end = b
        elif b > end:
            covered += b - end
            end = b
    return covered, gaps


class TraceView:
    """A compact trace cut to one window [start_ns, stop_ns]."""

    def __init__(self, compact: dict, start_ns: int, stop_ns: int, *, spans: list | None = None,
                 clock: dict | None = None):
        self.compact, self.start_ns, self.stop_ns = compact, start_ns, stop_ns
        self.spans = spans or []
        self.clock = clock or {}
        self.devices = [
            {
                "plane": d["plane"],
                "ops": [op for op in leaf_ops(d["ops"]) if op[2] < stop_ns and op[2] + op[3] > start_ns],
                "modules": [m for m in d["modules"] if m[2] <= stop_ns and m[2] + m[3] > start_ns],
            }
            for d in compact["devices"]
        ]

    @property
    def window_s(self) -> float:
        return (self.stop_ns - self.start_ns) / 1e9

    def _clip(self, ev) -> tuple[int, int]:
        return max(ev[2], self.start_ns), min(ev[2] + ev[3], self.stop_ns)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the device planes."""
        if not self.devices:
            return 0.0
        total = sum(_union_ns([self._clip(op) for op in d["ops"]])[0] for d in self.devices)
        return total / len(self.devices) / 1e9

    def idle_gaps(self) -> list[tuple[int, int]]:
        """Idle intervals of the first device inside the window."""
        if not self.devices:
            return []
        intervals = [self._clip(op) for op in self.devices[0]["ops"]]
        _, gaps = _union_ns(intervals)
        if intervals:
            first, last = min(a for a, _ in intervals), max(b for _, b in intervals)
            gaps = [(self.start_ns, first)] * (first > self.start_ns) + gaps
            gaps += [(last, self.stop_ns)] * (last < self.stop_ns)
        else:
            gaps = [(self.start_ns, self.stop_ns)]
        return gaps

    def module_runs(self, name_part: str) -> list[list]:
        """Executions of the program whose name holds `name_part`, first device."""
        if not self.devices:
            return []
        return sorted((m for m in self.devices[0]["modules"] if name_part in m[0]), key=lambda m: m[2])

    def op_seconds(self, select) -> float:
        """Device seconds (first device) of the ops for which
        `select(name, shapes)` holds; shapes as `shapes_of` gives them."""
        if not self.devices:
            return 0.0
        total = 0
        for op in self.devices[0]["ops"]:
            if select(op[0], shapes_of(op[1])):
                a, b = self._clip(op)
                total += b - a
        return total / 1e9

    # ---- breakdown ----

    def top_ops(self, n: int = 10) -> list[list]:
        if not self.devices:
            return []
        by_name: dict[str, int] = {}
        for op in self.devices[0]["ops"]:
            a, b = self._clip(op)
            label = _label(op)
            by_name[label] = by_name.get(label, 0) + (b - a)
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def _host_label(self, a: int, b: int) -> str:
        """What the host was doing in [a, b]: the program's innermost span
        over the gap's middle, and the host event that overlaps it longest."""
        mid = (a + b) // 2
        parts = []
        offset = self.clock.get("unix_ns_minus_trace_ns")
        if offset is not None:
            inner = None
            for s in self.spans:
                s0 = int(s["start"] * 1e9) - offset
                s1 = s0 + int(s["duration_ms"] * 1e6)
                if s0 <= mid <= s1 and (inner is None or s1 - s0 < inner[0]):
                    inner = (s1 - s0, s["name"])
            if inner:
                parts.append(inner[1])
        best = None
        for _line, name, start, dur in self.compact["host"]:
            overlap = min(b, start + dur) - max(a, start)
            if overlap > 0 and name != MARKER and (best is None or overlap > best[0]):
                best = (overlap, name)
        if best:
            parts.append(best[1])
        return " / ".join(parts) or "(no host event)"

    def top_gaps(self, n: int = 10) -> list[list]:
        by_label: dict[str, int] = {}
        # name the 200 longest gaps; shorter ones share what is left of the idle time
        for a, b in sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:200]:
            label = self._host_label(a, b)
            by_label[label] = by_label.get(label, 0) + (b - a)
        top = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]


def _label(op: list) -> str:
    """An op's name with its shapes, as the breakdown prints it."""
    shapes = shapes_of(op[1])
    return op[0] if not shapes else op[0] + " " + " ".join(
        f"{d}[{','.join(map(str, dims))}]" for d, dims in shapes[:4])


def window_of(compact: dict, spec_trace: dict, program_name: str | None,
              host_window: tuple[float, float] | None) -> tuple[int, int]:
    """The traced window on the profiler's clock. With a program name: from
    the start of its first whole execution to the start of its last, so that
    it holds whole call periods (each call and the gap after it). Otherwise
    the host's [t0, t1] (monotonic seconds) moved onto the profiler's clock by
    the marker."""
    if program_name is not None:
        runs = sorted((m for d in compact["devices"][:1] for m in d["modules"] if program_name in m[0]),
                      key=lambda m: m[2])
        if len(runs) < 3:
            raise ValueError(f"{len(runs)} executions of {program_name!r} in the trace: no window")
        # the first execution may have begun before the trace did
        return runs[1][2], runs[-1][2]
    offset = spec_trace["marker_monotonic"] * 1e9 - compact["marker_ns"]
    return int(host_window[0] * 1e9 - offset), int(host_window[1] * 1e9 - offset)


def reduce_run(spec: dict) -> dict | None:
    """post_child.py's half: compact the xplane, write it where the parent's
    readers find it, and return the device's busy and window seconds with the
    breakdown."""
    compact = compact_xplane(find_xplane(Path(spec["trace_dir"])))
    if not compact["devices"]:
        return None  # a CPU rehearsal: no device plane, no device metric
    out_path = Path(spec["work"]) / "trace_compact.json"
    out_path.write_text(json.dumps(compact))
    return {"compact": str(out_path)}


def view_for(compact: dict, stretch: tuple, window: dict, spans: list) -> TraceView:
    """The TraceView of a run's traced window (parent's half); `stretch` is
    what the window's module says of it: `window_of`'s program name and host window."""
    trace = window["trace"]
    clock = {}
    if compact.get("marker_ns") is not None:
        clock["unix_ns_minus_trace_ns"] = trace["marker_unix_ns"] - compact["marker_ns"]
    # the feeder's uploads as spans of their own, so that a gap in which the
    # trainer only ingests has a name (host monotonic -> unix by the marker)
    to_unix = trace["marker_unix_ns"] / 1e9 - trace["marker_monotonic"]
    spans = list(spans) + [
        {"name": "feeder.upload (train_open..train_close)", "start": u["t_open"] + to_unix,
         "duration_ms": (u["t_closed"] - u["t_open"]) * 1e3}
        for u in window["uploads"]
    ]
    a, b = window_of(compact, trace, *stretch)
    return TraceView(compact, a, b, spans=spans, clock=clock)
