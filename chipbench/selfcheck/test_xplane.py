"""The trace reduction against a small recorded trace.

``small.xplane.pb``: TPU v5 lite x1, PR 25's probe call; two traced passes
of Q1 then Q6 over 300 k rows of ``lineitem`` in two files. Its events were
printed raw by that call and the numbers below are sums made by hand from
that print, not by the code under test: four runs of ``jit_run_packed`` of
84755, 84537, 84553 and 84548 ns (both files of Q1, in each pass; Q6's
filtered batches were under the engine's 4096-row device floor and ran on
the host), ``pass:0`` from 48601516 ns for 146022077 ns."""

import os

import pytest

from chipbench import xplane

TRACE = os.path.join(os.path.dirname(__file__), "small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return xplane.reduce(TRACE)


def test_programs_and_their_device_time(summary):
    assert summary.chips == 1 and summary.passes == 2
    assert summary.module_runs == {"jit_run_packed": 4}
    assert summary.module_s["jit_run_packed"] == pytest.approx(
        (84755 + 84537 + 84553 + 84548) * 1e-9, abs=4e-9)
    assert summary.span_module_s == {
        "execute:q1": {"jit_run_packed": pytest.approx(338393e-9, abs=4e-9)}}
    assert summary.span_count["execute:q1"] == 2
    assert summary.span_count["execute:q6"] == 2


def test_busy_is_the_union_and_idle_is_the_rest(summary):
    # operations nest inside their program's run, so the union is the runs
    assert summary.busy_s == pytest.approx(338393e-9, rel=1e-3)
    assert summary.window_s > 0.146
    assert summary.idle_share == pytest.approx(
        1 - summary.busy_s / summary.window_s)
    gaps = dict(summary.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    assert max(gaps, key=gaps.get) == "execute:q1"
    assert len(summary.device_ops) == 10
    assert summary.device_ops[0][0] == "concatenate.2"


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [
        (0, 3), (5, 8)]
    assert xplane.total(xplane.union([(0, 2), (1, 3)])) == 3
    assert xplane.complement([(1, 2), (4, 5)], 0, 6) == [
        (0, 1), (2, 4), (5, 6)]
    assert xplane.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert xplane.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]
    assert xplane.op_name("%fusion.20 = f32[8]{0} fusion(%p)") == "fusion.20"
    assert xplane.module_name("jit_run_packed(109413)") == "jit_run_packed"
