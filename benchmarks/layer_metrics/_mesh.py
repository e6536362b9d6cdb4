"""What the readers of a cell on several chips share: the chips the run had,
the device planes' busy time, the collective ops' device time, and the shapes
a row shard's gather VJP and message ops have."""

from __future__ import annotations

import bisect
import re

import flops
from _common import scan_calls
from trace_reduce import _union_ns

# A device op that moves data between chips, by its own name or by the
# computation it calls (XLA's TPU compiler runs a reduce-scatter as a fusion
# that calls `all-reduce-scatter`, and an all-gather as the pair
# `async-collective-start` / `async-collective-done`): never by an operand's
# name (a fusion that reads `%all-gather-done.3` computes), and not an
# `async_collective_fusion`, which is a compute fusion that carries a
# collective's start along: its time is the compute's, under the compute's scope.
_KINDS = "all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|async-collective"
_OWN_NAME = re.compile(rf"^(?:{_KINDS})")
_CALLS = re.compile(rf"calls=%(?:{_KINDS})")
_HALF = re.compile(r"^(.*)-(start|done)((?:\.\d+)?)$")


def chips(ctx: dict) -> int:
    return int(ctx["device"]["count"])


def is_shard_scatter(config: dict, n_chips: int, shapes: list) -> bool:
    """The gather's VJP on a row shard: an op whose result is the whole
    `[N, H]` (a chip's partial sums for every row, reduce-scattered after it)
    and that takes the shard's N*K/chips row numbers (s32) and its
    `[N*K/chips, H]` cotangent. `flops.is_scatter` on one chip."""
    m, n = config["model"], config["cluster"]["hosts"]
    k, h, rows = m["num_neighbors"], m["hidden"], n // n_chips
    if not shapes or shapes[0][1] != (n, h):
        return False
    operands = shapes[1:]
    return any(d == "s32" and dims in ((rows * k,), (rows * k, 1), (rows, k)) for d, dims in operands) and any(
        dims in ((rows * k, h), (rows, k, h)) for _, dims in operands)


def row_shard(config: dict, n_chips: int) -> dict:
    """The configuration with one row shard's hosts, for `flops.touches_messages`:
    an op with an `[N/chips, K, H]` or `[N*K/chips, H]` result or operand."""
    return {**config, "cluster": {**config["cluster"], "hosts": config["cluster"]["hosts"] // n_chips}}


def is_collective(name: str, text: str) -> bool:
    return bool(_OWN_NAME.match(name) or _CALLS.search(text))


def _collective_ops(ctx: dict) -> tuple[list, int] | None:
    """(collective ops of the first device plane inside the scan program's
    executions that start in the traced window, clipped to it; steps in the
    window); None without a device trace or without such an op (one chip)."""
    calls = scan_calls(ctx)
    if calls is None:
        return None
    view = ctx["view"]
    starts = [c[2] for c in calls]
    ops = []
    for name, text, start, duration in view.devices[0]["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < calls[i][2] + calls[i][3] and is_collective(name, text):
            a, b = max(start, view.start_ns), min(start + duration, view.stop_ns)
            if b > a:
                ops.append((name, a, b))
    if not ops:
        return None
    return ops, len(calls) * ctx["config"]["optimizer"]["gnn"]["steps_per_call"]


def collective_seconds_per_step(ctx: dict) -> float | None:
    """Device seconds a step IN collective ops: the synchronous ones whole,
    of an asynchronous one its start and its done (the wait that is left
    after what ran in between)."""
    found = _collective_ops(ctx)
    if found is None:
        return None
    ops, steps = found
    return sum(b - a for _, a, b in ops) / 1e9 / steps


def exchange_in_flight_seconds_per_step(ctx: dict) -> float | None:
    """Seconds a step during which some exchange was in flight: the union of
    the synchronous collectives' intervals and, for an asynchronous one, of
    the whole span from its start's begin to its done's end (the k-th
    `x-start.N` with the k-th `x-done.N`). Never shorter than the transfer
    itself, whatever ran beside it: the time a share of the interconnect's
    peak is taken over."""
    found = _collective_ops(ctx)
    if found is None:
        return None
    ops, steps = found
    intervals, halves = [], {}
    for name, a, b in sorted(ops, key=lambda op: op[1]):
        half = _HALF.match(name)
        if half is None:
            intervals.append((a, b))
        else:
            halves.setdefault((half.group(1), half.group(3)), {"start": [], "done": []})[half.group(2)].append((a, b))
    for pair in halves.values():
        if len(pair["start"]) == len(pair["done"]):
            intervals += [(s[0], max(s[1], d[1])) for s, d in zip(pair["start"], pair["done"])]
        else:  # a pair cut by the window's edge: what is left counts as it stands
            intervals += pair["start"] + pair["done"]
    return _union_ns(intervals)[0] / 1e9 / steps


def plane_busy_ns(ctx: dict) -> list[int] | None:
    """Busy nanoseconds of every device plane inside the traced window."""
    view = ctx.get("view")
    if view is None or not view.devices:
        return None
    return [_union_ns([view._clip(op) for op in d["ops"]])[0] for d in view.devices]
