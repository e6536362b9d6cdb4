"""correct.py alone: the judge, and the control and faults laid over the
program's readings (no jax, no subprocess)."""

import pytest

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import correct  # noqa: E402

LIMITS = {"numbers": {"exact": {"limit": 0}, "gap": {"limit": 0.01}, "other_gap": {"limit": 0.1}},
          "known_to_pass": ["fault.blind"]}
SOUND = {"exact": 0, "gap": 0.002, "other_gap": 0.03}


@pytest.mark.parametrize("readings,ok", [
    (SOUND, True),
    ({**SOUND, "gap": 0.01}, True),
    ({**SOUND, "gap": 0.011}, False),
    ({**SOUND, "exact": 1}, False),
    ({**SOUND, "gap": None}, False),
    ({**SOUND, "gap": float("nan")}, False),
    ({"exact": 0, "gap": 0.002}, False),
])
def test_judge(readings, ok):
    compared, verdict = correct.judge(readings, LIMITS)
    assert verdict is ok
    assert compared["gap"] == [readings.get("gap"), 0.01]


def test_stand_ins_go_through_the_same_judge():
    extra = {
        "control.gap": 0.2, "control.other_gap": 0.05,           # fails one number: not correct
        "fault.half.gap": 0.004, "fault.half.other_gap": 0.5,    # the other number catches it
        "fault.blind.gap": 0.003,                                 # no number sees it: listed
        "fault.unseen.gap": 0.003,                                # no number sees it: not listed
        "fault.crashed.gap": None,                                # gave no number: has failed
        "fault.half.not_a_number_of_the_cell": 9.0, "reference.loss": [1.0, 2.0],
    }
    out = correct.judge_stand_ins(SOUND, extra, LIMITS)
    assert {k: v["correct"] for k, v in out.items()} == {
        "control": False, "fault.half": False, "fault.blind": True, "fault.unseen": True, "fault.crashed": False}
    assert out["control"]["over"] == ["gap"] and out["fault.half"]["over"] == ["other_gap"]
    assert [k for k, v in out.items() if v["known_to_pass"]] == ["fault.blind"]


def test_a_failing_program_fails_every_stand_in():
    out = correct.judge_stand_ins({**SOUND, "exact": 3}, {"fault.blind.gap": 0.0}, LIMITS)
    assert out["fault.blind"]["correct"] is False
