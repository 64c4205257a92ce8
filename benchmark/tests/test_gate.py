"""The gate's verdict on hand-made per-position ratios and routing margins."""

import numpy as np
import pytest

from harness.build import judge_gate

GATE = {"min_positions_held": 0.8, "median_ratio_max": 0.5,
        "worst_ratio_max": 2.5, "excuse_margin_max": 0.02}
S = 6                                    # prefill positions; 4 decode follow


def _case(edits=(), margin_edits=()):
    ratio = np.full((2, 10), 0.3)
    margins = np.full((2, 10), 0.2)
    for (i, j), v in edits:
        ratio[i, j] = v
    for (i, j), v in margin_edits:
        margins[i, j] = v
    return judge_gate(ratio, margins, S, GATE)


def test_every_position_held_passes():
    out = _case()
    assert out["passed"] and out["held_share"] == {"prefill": 1.0,
                                                   "decode": 1.0}


@pytest.mark.parametrize("edits,margin_edits,passed,why", [
    # a flip at a near-tie, and one after it in the same sequence: excused
    ([((0, 3), 1.4), ((0, 8), 1.2)], [((0, 3), 0.004)], True, None),
    # the same errors with the near-tie in the OTHER sequence: not excused
    ([((0, 3), 1.4)], [((1, 3), 0.004)], False, "no near-tie"),
    # a near-tie AFTER the position does not excuse it
    ([((0, 3), 1.4)], [((0, 4), 0.004)], False, "no near-tie"),
    # excused, but wrong by more than a flipped expert can explain
    ([((0, 3), 9.0)], [((0, 3), 0.004)], False, "worst ratio"),
    # too many decode positions off (3 of 8), every one excused
    ([((0, 6), 1.2), ((0, 7), 1.2), ((1, 9), 1.2)],
     [((0, 0), 0.001), ((1, 0), 0.001)], False, "held shares"),
])
def test_positions_not_held(edits, margin_edits, passed, why):
    out = _case(edits, margin_edits)
    assert out["passed"] is passed
    assert out["n_not_held"] == len(edits)
    if why:
        assert any(why in w for w in out["why"])


def test_the_bulk_must_sit_at_half_the_bound():
    out = judge_gate(np.full((2, 10), 0.7), np.full((2, 10), 0.2), S, GATE)
    assert not out["passed"] and "median ratio" in out["why"][0]


def test_a_dense_model_has_no_excuse():
    ratio = np.full((1, 10), 0.3)
    ratio[0, 2] = 1.1
    out = judge_gate(ratio, np.full((1, 10), np.inf), S, GATE)
    assert not out["passed"]
