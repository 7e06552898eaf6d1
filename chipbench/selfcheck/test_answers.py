"""The comparison that decides ``correct``, on made-up answers."""

import datetime

import pytest

from chipbench import answers

ROWS = {"kind": "rows"}
TOPK = {"kind": "topk", "k": 3, "keys": ["k"], "by": "rev"}
RTOL = 1e-4


def test_rows_floats_within_rtol_everything_else_exact():
    ref = {"flag": ["A", "N"], "n": [10, 20], "s": [100.0, 200.0]}
    ok = {"flag": ["A", "N"], "n": [10, 20], "s": [100.001, 199.99]}
    assert answers.compare("t", ok, ref, ROWS, RTOL) == pytest.approx(5e-5)
    for bad in ({"flag": ["A", "N"], "n": [10, 21], "s": [100.0, 200.0]},
                {"flag": ["A", "R"], "n": [10, 20], "s": [100.0, 200.0]},
                {"flag": ["A", "N"], "n": [10, 20], "s": [100.02, 200.0]},
                {"flag": ["A"], "n": [10], "s": [100.0]},
                {"n": [10, 20], "flag": ["A", "N"], "s": [100.0, 200.0]},
                {"flag": ["A", "N"], "n": [10, 20],
                 "s": [float("nan"), 200.0]}):
        with pytest.raises(answers.Mismatch):
            answers.compare("t", bad, ref, ROWS, RTOL)


def _ref():
    d = datetime.date(1995, 1, 1)
    return {"k": [1, 2, 3, 4, 5], "rev": [900.0, 800.0, 700.0, 699.99, 10.0],
            "d": [d] * 5}


def test_topk_tolerates_a_tie_at_the_cut_only():
    ref = _ref()
    d = ref["d"][0]
    exact = {"k": [1, 2, 3], "rev": [900.0, 800.0, 700.0], "d": [d] * 3}
    tie = {"k": [1, 2, 4], "rev": [900.0, 800.0, 699.99], "d": [d] * 3}
    assert answers.compare("t", exact, ref, TOPK, RTOL) == 0.0
    assert answers.compare("t", tie, ref, TOPK, RTOL) == 0.0
    wrong_row = {"k": [1, 2, 5], "rev": [900.0, 800.0, 10.0], "d": [d] * 3}
    missing_top = {"k": [2, 3, 4], "rev": [800.0, 700.0, 699.99],
                   "d": [d] * 3}
    out_of_order = {"k": [2, 1, 3], "rev": [800.0, 900.0, 700.0],
                    "d": [d] * 3}
    unknown_key = {"k": [1, 2, 99], "rev": [900.0, 800.0, 700.0],
                   "d": [d] * 3}
    short = {"k": [1, 2], "rev": [900.0, 800.0], "d": [d] * 2}
    off = {"k": [1, 2, 3], "rev": [900.0, 800.0, 700.2], "d": [d] * 3}
    for bad in (wrong_row, missing_top, out_of_order, unknown_key, short,
                off):
        with pytest.raises(answers.Mismatch):
            answers.compare("t", bad, ref, TOPK, RTOL)


def test_cut_to_answer():
    assert answers.cut_to_answer(_ref(), TOPK)["k"] == [1, 2, 3]
    assert answers.cut_to_answer(_ref(), ROWS) == _ref()
