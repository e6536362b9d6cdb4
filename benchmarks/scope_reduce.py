"""From the profiler's trace to device time by what an op is FOR.

`trace_reduce.compact_xplane` keeps an op's HLO text (shapes) and drops what
the profiler knows besides, among it the op's `op_name`: the path of
`jax.named_scope` names it was traced under, which is where the program says
what the op belongs to (scopes.json holds the benchmark's copy of the
program's vocabulary). This is the second, small reduction: one pass over the
`.xplane.pb` that gives, per leaf op of the first device's "XLA Ops" line
inside a window, (start, duration, scope, backward, name).

The profiler keeps the `op_name` as a stat of the event's METADATA (`tf_op`,
"<op_name>:<type>" with an empty type; checked on the v5e), which
`jax.profiler.ProfileData` does not show: it lists an event's own stats only.
So the pass reads the file's protobuf wire format itself, the few fields it
needs (tsl/profiler/protobuf/xplane.proto), with the standard library alone:
run.py's process may run it, and never imports jax. A trace of a program
without scopes gives ops whose scope is None, a trace without the stat gives
the same: the readers then find nothing to read.

Ops the compiler adds without metadata have no `op_name` and so no scope:
among them the asynchronous copies into and out of fast memory (`copy-start`
/ `copy-done`, `slice-start` / `slice-done`), whose `-done` lasts as long as
the device waits for the transfer. An op's HLO text names its operands, so
such an op can be given the scope its result is read under
(`scopes_by_consumer`); the two rooflines of the gather's VJP count it, the
`scope.*_ms` metrics do not.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

from trace_reduce import DEVICE_PLANE, OPS_LINE, leaf_ops

VOCABULARY = json.loads((Path(__file__).resolve().parent / "scopes.json").read_text())
# `transpose(jvp(loss))` -> `loss`: JAX wraps the outermost name of a
# differentiated function in the transformation's own name
_WRAPPED = re.compile(r"(?:[\w.-]+\()*([^()]*)\)*")
# an operand of an HLO instruction: `%name` after a space or a bracket, not
# the computation an attribute names (`calls=%fused_computation.5`)
_OPERAND = re.compile(r"(?<![=\w])%([\w.-]+)")


def classify(op_name: str | None) -> tuple[str | None, bool]:
    """(scope, backward) of an op: the innermost vocabulary name among the
    components of its `op_name`, the last one left out (it is the primitive's
    name, and `gather` is a primitive too), and whether the op belongs to the
    backward pass."""
    if not op_name:
        return None, False
    for part in reversed(op_name.split("/")[:-1]):
        inner = _WRAPPED.fullmatch(part)
        if inner and inner.group(1) in VOCABULARY["names"]:
            return inner.group(1), VOCABULARY["backward"] in op_name
    return None, False


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint's value, or a
    view of a length-delimited (or fixed-width) field's bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _first(buf, field: int, default=None):
    return next((v for f, v in _fields(buf) if f == field), default)


def _map_entries(plane, field: int):
    """(key, value message) of a map<int64, message> field of an XPlane."""
    for f, entry in _fields(plane):
        if f == field:
            yield _first(entry, 1, 0), _first(entry, 2, b"")


def device_ops(xplane: Path) -> list[list]:
    """[name, op_name, start_ns, duration_ns] of every event of the first
    device plane's "XLA Ops" line, as `trace_reduce.leaf_ops` takes them and
    with the times `trace_reduce.compact_xplane` gives the same events."""
    space = memoryview(Path(xplane).read_bytes())
    # XSpace.planes = 1; XPlane.name = 2, .lines = 3, .event_metadata = 4, .stat_metadata = 5
    plane = next((p for f, p in _fields(space)
                  if f == 1 and bytes(_first(p, 2, b"")).decode().startswith(DEVICE_PLANE)), None)
    if plane is None:
        return []
    # XStatMetadata.name = 2; XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value = 5
    stat_id = next((key for key, meta in _map_entries(plane, 5)
                    if bytes(_first(meta, 2, b"")).decode() == VOCABULARY["op_name_stat"]), None)
    named = {}
    for key, meta in _map_entries(plane, 4):
        op_name = ""
        for f, stat in _fields(meta) if stat_id is not None else ():
            if f == 5 and _first(stat, 1) == stat_id:
                # "<op_name>:<type>", the type empty for an XLA op
                value = bytes(_first(stat, 5, b"")).decode()
                op_name = value.rpartition(":")[0] or value
        short, _, _ = bytes(_first(meta, 2, b"")).decode().partition(" = ")
        named[key] = (short.lstrip("%"), op_name)
    ops = []
    for f, line in _fields(plane):
        # XLine.name = 2, .timestamp_ns = 3, .events = 4
        if f != 3 or bytes(_first(line, 2, b"")).decode() != OPS_LINE:
            continue
        t0 = _first(line, 3, 0)
        for g, event in _fields(line):
            if g != 4:
                continue
            # XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3
            e = dict(_fields(event))
            ops.append([*named.get(e.get(1), ("", "")), t0 + e.get(2, 0) // 1000, e.get(3, 0) // 1000])
    return ops


def scoped_ops(ops: list[list], start_ns: int, stop_ns: int) -> list[tuple]:
    """(start_ns, duration_ns, scope, backward, name) of every leaf op of
    `ops` (as `device_ops` gives them) that overlaps [start_ns, stop_ns],
    clipped to it."""
    out = []
    for name, op_name, start, duration in leaf_ops(ops):
        a, b = max(start, start_ns), min(start + duration, stop_ns)
        if b > a:
            out.append((a, b - a, *classify(op_name), name))
    return out


def operands_of(text: str) -> list[str]:
    """Names of the instructions that an HLO instruction's text reads."""
    return _OPERAND.findall(text.partition(" = ")[2])


def scopes_by_consumer(texts: dict[str, str], scoped: dict[str, tuple]) -> dict[str, tuple]:
    """(scope, backward) for the ops of `texts` (op name -> HLO text) that
    have none in `scoped` (op name -> (scope, backward)): the one that every
    named op reading the op's result has, through other unnamed ops (a
    `copy-start` is read by its `copy-done`, that by a fusion). An op whose
    result nothing named reads, or ops of several scopes do, is left out."""
    readers: dict[str, list[str]] = {}
    for name, text in texts.items():
        for operand in operands_of(text):
            readers.setdefault(operand, []).append(name)

    def named_readers(name: str, seen: set) -> set:
        found = set()
        for reader in readers.get(name, ()):
            if reader in seen:
                continue
            seen.add(reader)
            found |= {scoped[reader]} if reader in scoped else named_readers(reader, seen)
        return found

    out = {}
    for name in texts:
        if name not in scoped:
            found = named_readers(name, {name})
            if len(found) == 1:
                out[name] = found.pop()
    return out
