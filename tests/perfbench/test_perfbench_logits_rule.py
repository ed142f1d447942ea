"""The rule that judges the logits check (perfbench/correct.py `_judge`)."""

import pytest

from perfbench import correct

LEVEL = [0.02] * 20
WIDE = [1.0] * 20   # every margin far above the threshold


@pytest.mark.parametrize("name, rel, margins, ok, compared", [
    ("a sound prompt", LEVEL, WIDE, True, 20),
    ("a swapped expert where the margin is small is not compared",
     LEVEL + [0.5], WIDE + [0.05], True, 20),
    ("a swapped expert where the margin is wide is a fault",
     LEVEL + [0.5], WIDE + [1.0], False, 21),
    ("one wrong position fails on the worst", LEVEL[:-1] + [0.2], WIDE, False, 20),
    ("a loss of precision everywhere fails on the median", [0.1] * 20, WIDE, False, 20),
    ("the worst level measured passes", [0.041] * 19 + [0.06], WIDE, True, 20),
    ("too few stable positions is a failure, not a pass",
     LEVEL, [1.0] * 3 + [0.1] * 17, False, 3),
    ("a dense model has no margins to fall under", LEVEL, [float("inf")] * 20, True, 20),
    ("a non-finite reading fails", LEVEL[:-1] + [float("inf")], WIDE, False, 20),
])
def test_judge(name, rel, margins, ok, compared):
    got = correct._judge(rel, margins)
    assert got["ok"] is ok, name
    assert got["compared"] == compared
    assert got["compared"] + got["skipped_for_routing"] == len(rel)
