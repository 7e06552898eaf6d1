"""The whole-pass window's arithmetic, on synthetic pass walls."""

import pytest

from chipbench import window


def _simulate(walls, seconds):
    """Drive ``may_start`` over a stream of pass walls, as run.py does."""
    done, clock = [], 0.0
    stream = iter(walls)
    while window.may_start(clock, done, seconds):
        w = next(stream)
        done.append(w)
        clock += w
    return done, clock


def test_rate_is_the_same_for_14_and_15_equal_passes():
    # PR 24's artefact: 1.42 s passes in a 20 s clock; a window that ends
    # a hair earlier or later holds 14 or 15 passes
    a = window.queries_per_hour(14, 2, 100.0, 100.0 + 14 * 1.42)
    b = window.queries_per_hour(15, 2, 7.0, 7.0 + 15 * 1.42)
    assert a == pytest.approx(b, rel=1e-12)
    assert a == pytest.approx(2 * 3600 / 1.42)
    # what PR 24 divided by: the clock, not the passes' own time
    assert 14 * 2 * 3600 / 20.0 != pytest.approx(15 * 2 * 3600 / 20.0,
                                                 rel=0.05)


@pytest.mark.parametrize("wall", [1.39, 1.42, 1.45])
def test_window_of_whole_passes_never_overruns_by_a_pass(wall):
    done, clock = _simulate([wall] * 100, 20.0)
    assert len(done) == int(20.0 // wall)
    assert clock <= 20.0
    rate = window.queries_per_hour(len(done), 2, 0.0, clock)
    assert rate == pytest.approx(2 * 3600 / wall)


def test_gaps_between_passes_count_against_the_rate():
    # 10 passes of 1 s with 0.1 s between them took 10.9 s, not 10
    assert window.queries_per_hour(10, 3, 0.0, 10.9) == pytest.approx(
        3 * 10 * 3600 / 10.9)


def test_first_pass_always_starts_and_a_long_one_ends_the_window():
    done, _ = _simulate([30.0, 30.0], 20.0)
    assert done == [30.0]
    with pytest.raises(ValueError):
        window.queries_per_hour(0, 2, 0.0, 0.0)


def test_quantile_interpolates():
    walls = [float(i) for i in range(1, 102)]   # 1..101
    assert window.quantile(walls, 0.5) == 51.0
    assert window.quantile(walls, 0.9) == 91.0
    assert window.quantile([3.0], 0.9) == 3.0
    assert window.quantile([1.0, 2.0], 0.5) == 1.5
