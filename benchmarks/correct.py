"""The comparison that decides `correct`: every reading beside the limit its
cell's limits file gives it (limits/<workload>.json). A reading that is
missing, not a number, or over its limit fails the run; a limit of 0 is an
exact comparison. No jax here."""

from __future__ import annotations

import math


def within(value, limit) -> bool:
    return isinstance(value, (int, float)) and math.isfinite(value) and value <= limit


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """({name: [reading, limit]}, all within). Only the numbers the limits
    file names are judged; each has to be there."""
    compared = {name: [readings.get(name), spec["limit"]] for name, spec in limits["numbers"].items()}
    return compared, all(within(v, limit) for v, limit in compared.values())


def judge_stand_ins(readings: dict, extra: dict, limits: dict) -> dict:
    """The lower-precision control and each planted fault, put in the
    program's place: the numbers a stand-in reaches (`control.<number>`,
    `fault.<name>.<number>` in `extra`) are laid over the program's readings
    and the whole goes through judge() against the cell's own limits.
    {stand-in: {"correct", "over": numbers past their limit, "known_to_pass"}};
    `known_to_pass` is the limits file's list of faults that no number of the
    cell can see yet."""
    names = set(limits["numbers"])
    stand_ins: dict[str, dict] = {}
    for key, value in extra.items():
        head, _, number = key.rpartition(".")
        if number in names and (head == "control" or head.startswith("fault.")):
            stand_ins.setdefault(head, {})[number] = value
    out = {}
    for head, numbers in sorted(stand_ins.items()):
        compared, ok = judge({**readings, **numbers}, limits)
        out[head] = {
            "correct": ok, "over": sorted(k for k, (v, limit) in compared.items() if not within(v, limit)),
            "known_to_pass": head in limits.get("known_to_pass", []),
        }
    return out
