"""What the readers of the program's own names share: the device time of the
scan program's ops by scope (`scope.*`), the same with the unnamed copies
that feed a scope's ops (the gather VJP's two rooflines), and the idle gap
between two scan calls split by the host's `trainer.gnn.call` events
(`host.*_ms`)."""

from __future__ import annotations

import argparse
import bisect
import sys
from pathlib import Path

import scope_reduce
import trace_reduce
from _common import scan_calls, steps_in_window

_WORK = Path(scope_reduce.__file__).resolve().parents[1] / ".bench_work"
CALL_SPAN = "trainer.gnn.call"


def _xplane_of_this_run() -> Path | None:
    """run.py keeps the run's work directory, named by --workload, until the
    result line is printed, and the readers run in its process."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    workload = ap.parse_known_args(sys.argv[1:])[0].workload
    if not workload:
        return None
    try:
        return trace_reduce.find_xplane(_WORK / workload / "trace")
    except FileNotFoundError:
        return None


def step_ops(ctx: dict) -> list[tuple] | None:
    """(start_ns, duration_ns, scope, backward, name) of the leaf ops inside
    the scan program's executions that start in the traced window; None
    without a device trace, and for a program that does not speak the
    vocabulary: one with less than half of its device time under a name of
    it (flax's own module names put the parent's pairwise head under `head`,
    and nothing else). Reduced once a run (kept in `ctx`)."""
    if "step_ops" in ctx:
        return ctx["step_ops"]
    ctx["step_ops"] = None
    calls, view = scan_calls(ctx), ctx.get("view")
    if calls is None:
        return None
    ops = ctx.get("device_ops")      # a recorded trace brings its own
    if ops is None:
        xplane = _xplane_of_this_run()
        if xplane is None:
            return None
        ops = scope_reduce.device_ops(xplane)
    in_a_call = _within(calls)
    inside = [op for op in scope_reduce.scoped_ops(ops, view.start_ns, view.stop_ns) if in_a_call(op[0], op[0] + op[1])]
    named = sum(op[1] for op in inside if op[2] is not None)
    if 2 * named >= sum(m[3] for m in calls):
        ctx["step_ops"] = inside
    return ctx["step_ops"]


def _within(calls: list):
    """The test whether [start, stop] lies inside one of the executions `calls` (sorted)."""
    starts = [m[2] for m in calls]

    def inside(start: int, stop: int) -> bool:
        i = bisect.bisect_right(starts, start) - 1
        return i >= 0 and stop <= calls[i][2] + calls[i][3]

    return inside


def scope_ms(ctx: dict, select) -> float | None:
    """Device milliseconds a step of the ops for which `select(scope,
    backward)` holds."""
    ops = step_ops(ctx)
    if ops is None:
        return None
    return sum(op[1] for op in ops if select(op[2], op[3])) / steps_in_window(ctx) / 1e6


def fed_ms(ctx: dict, select) -> float | None:
    """Device milliseconds a step of the ops WITHOUT a name whose result only
    ops for which `select(scope, backward)` holds read
    (`scope_reduce.scopes_by_consumer`): the waits on the asynchronous copies
    that feed them. The HLO text that names the operands comes from the
    trace's first device plane, as the ops do."""
    ops = step_ops(ctx)
    if ops is None:
        return None
    in_a_call = _within(scan_calls(ctx))
    scoped = {op[4]: (op[2], op[3]) for op in ops if op[2] is not None}
    # every op of the scan program's executions, those of no duration too: a slice's `-done` is read by a
    # `custom-call` that assembles buffers in no time, and that by the kernel
    texts = {op[0]: op[1] for op in ctx["view"].devices[0]["ops"] if in_a_call(op[2], op[2] + op[3])}
    fed = {name for name, found in scope_reduce.scopes_by_consumer(texts, scoped).items() if select(*found)}
    return sum(op[1] for op in ops if op[4] in fed) / steps_in_window(ctx) / 1e6


def gather_vjp_ms(ctx: dict) -> float | None:
    """Device milliseconds a step that the gather's VJP takes, whatever
    implements it: the backward ops under the program's `gather` scope
    (`scope.gather_bwd_ms`) and the unnamed copies that only they read."""
    def select(scope, backward):
        return scope == "gather" and backward

    named = scope_ms(ctx, select)
    return None if not named else named + fed_ms(ctx, select)


def gap_parts(ctx: dict) -> dict | None:
    """The idle gap after every execution of the scan program that starts in
    the traced window, split by the host's `trainer.gnn.call` events (on the
    trace's own clock) into `pull_tail` (the execution's end to the end of
    its call), `turn` (to the start of the next call) and `dispatch` (to the
    start of the next execution): mean milliseconds of each, the three
    summing to `host.gap_ms_per_call`. None where the trace holds no such
    event."""
    calls = scan_calls(ctx)
    if calls is None:
        return None
    view = ctx["view"]
    spans = sorted((start, start + dur) for _line, name, start, dur in view.compact["host"]
                   if name == CALL_SPAN)
    executions = view.module_runs(ctx["config"]["scan_program"])

    def span_of(execution):
        i = bisect.bisect_right(spans, (execution[2], float("inf"))) - 1
        return spans[i] if i >= 0 and execution[2] <= spans[i][1] else None

    parts = {"pull_tail": [], "turn": [], "dispatch": []}
    for call in calls:
        nxt = next((m for m in executions if m[2] > call[2]), None)
        own, following = span_of(call), nxt and span_of(nxt)
        if own is None or following is None or following == own:
            continue
        parts["pull_tail"].append(own[1] - (call[2] + call[3]))
        parts["turn"].append(following[0] - own[1])
        parts["dispatch"].append(nxt[2] - following[0])
    if not parts["turn"]:
        return None
    return {k: sum(v) / len(v) / 1e6 for k, v in parts.items()}
