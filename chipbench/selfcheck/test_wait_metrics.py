"""The seven readers of PR 43 (work apart from wait) on hand-made
summaries with known answers: two traced passes with different values (the
median of two is their mean), a query the warm-up left outside every pass,
a program whose summaries lack the new fields (the parent of PR 43: None,
but for ``uncovered_ms_per_pass``, which reads what was always there), a
pass that dispatched nothing (0, not None), and nothing to read at all."""

import os
import types

import pytest

from chipbench import program_spans, run, wait_spans
from chipbench.layer_metrics import (
    dispatch_launch_ms_per_pass, dispatch_offcpu_ms_per_pass,
    handoff_ms_per_pass, handoffs_per_pass, op_offcpu_ms_per_pass,
    uncovered_ms_per_pass, uncovered_named_pct)

NEW = {
    "dispatch_launch_ms_per_pass": ("ms", "lower", "program_span", "executor"),
    "dispatch_offcpu_ms_per_pass": ("ms", "lower", "program_span", "executor"),
    "op_offcpu_ms_per_pass": ("ms", "lower", "program_span", "host operators"),
    "uncovered_ms_per_pass": ("ms", "lower", "program_span", "executor"),
    "uncovered_named_pct": ("%", "higher", "program_span", "tracing"),
    "handoffs_per_pass": ("count", "lower", "program_counter", "executor"),
    "handoff_ms_per_pass": ("ms", "lower", "program_counter", "executor"),
}


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30)])


def _phase(sum_us, cpu_us=None, timed_us=None, count=1):
    p = {"count": count, "wall_us": sum_us, "sum_us": sum_us, "bytes": 0,
         "rows": 0}
    if cpu_us is not None:
        p["cpu_us"] = cpu_us
        p["timed_us"] = sum_us if timed_us is None else timed_us
    return p


def _summary(t0, wall=10_000, covered=7_000, phases=None, holes=None,
             handoffs=None):
    s = {"t0_perf_s": t0, "wall_us": wall, "covered_us": covered,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "phases": phases or {}}
    if holes is not None:
        s["holes"] = {"us": wall - covered, "by": {}, "unnamed_us": holes}
    if handoffs is not None:
        s["handoffs"] = {"count": handoffs[0], "us": handoffs[1],
                         "max_us": handoffs[2]}
    return s


def _q1(t0, k):
    """Q1 of a pass, every number scaled by ``k``."""
    return _summary(t0, wall=10_000 * k, covered=7_000 * k, phases={
        "device:dispatch": _phase(4_000 * k, cpu_us=1_000 * k),
        "dispatch:launch": _phase(3_000 * k, cpu_us=900 * k, count=16),
        "mem:size": _phase(600 * k, cpu_us=10 * k),
        "expr:eval": _phase(2_000 * k, cpu_us=1_500 * k),
        # an explicit-timestamp span of the name beside the live ones
        "agg:host": _phase(900 * k, cpu_us=300 * k, timed_us=400 * k),
        "device:fetch": _phase(5_000 * k, cpu_us=100 * k),   # no compute span
        "wait:channel": _phase(9_000 * k, cpu_us=0, timed_us=0)},
        holes=1_000 * k, handoffs=(40, 2_000 * k, 900 * k))


def _q6(t0, k):
    return _summary(t0, wall=4_000 * k, covered=3_000 * k, phases={
        "device:dispatch": _phase(1_000 * k, cpu_us=400 * k),
        "dispatch:launch": _phase(800 * k, cpu_us=350 * k, count=16),
        "expr:eval": _phase(500 * k, cpu_us=500 * k)},
        holes=500 * k, handoffs=(20, 1_000 * k, 300 * k))


# the warm-up's query lies before the first pass: nobody's
TWO_PASSES = [_q1(5.0, 100), _q1(11.0, 1), _q6(15.0, 1),
              _q1(21.0, 3), _q6(25.0, 3)]

#: per pass with k = 1; the median of the passes k = 1 and k = 3 is k = 2
AT_K1 = {
    "dispatch_launch_ms_per_pass": (3_000 + 800) / 1e3,
    "dispatch_offcpu_ms_per_pass": (3_000 + 600) / 1e3,
    # mem:size 590 + expr:eval 500 + agg:host (400 - 300) + Q6's 0
    "op_offcpu_ms_per_pass": (590 + 500 + 100) / 1e3,
    "uncovered_ms_per_pass": (3_000 + 1_000) / 1e3,
    "handoff_ms_per_pass": (2_000 + 1_000) / 1e3,
}
READERS = {
    "dispatch_launch_ms_per_pass": dispatch_launch_ms_per_pass,
    "dispatch_offcpu_ms_per_pass": dispatch_offcpu_ms_per_pass,
    "op_offcpu_ms_per_pass": op_offcpu_ms_per_pass,
    "uncovered_ms_per_pass": uncovered_ms_per_pass,
    "uncovered_named_pct": uncovered_named_pct,
    "handoffs_per_pass": handoffs_per_pass,
    "handoff_ms_per_pass": handoff_ms_per_pass,
}


@pytest.mark.parametrize("name", sorted(AT_K1))
def test_the_median_of_two_traced_passes(monkeypatch, name):
    monkeypatch.setattr(program_spans, "finished", lambda: TWO_PASSES)
    assert READERS[name].read(CTX) == pytest.approx(2 * AT_K1[name])


def test_what_the_cpu_clock_gives_is_a_mean_over_the_passes(monkeypatch):
    """Three passes, k = 1, 1, 10: the median pass reads k = 1, the mean
    k = 4. The machines' thread-CPU clock steps by 10 ms, so a pass's own
    off-CPU sum is the nearest step and only the total over the window
    estimates anything."""
    ctx = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30),
                                        _pass(30, 40)])
    three = TWO_PASSES[1:3] + [_q1(21.0, 1), _q6(25.0, 1),
                               _q1(31.0, 10), _q6(35.0, 10)]
    monkeypatch.setattr(program_spans, "finished", lambda: three)
    for name in ("dispatch_offcpu_ms_per_pass", "op_offcpu_ms_per_pass"):
        assert READERS[name].read(ctx) == pytest.approx(4 * AT_K1[name])
    for name in ("dispatch_launch_ms_per_pass", "uncovered_ms_per_pass",
                 "handoff_ms_per_pass"):
        assert READERS[name].read(ctx) == pytest.approx(AT_K1[name])


def test_counts_and_shares(monkeypatch):
    monkeypatch.setattr(program_spans, "finished", lambda: TWO_PASSES)
    assert handoffs_per_pass.read(CTX) == 60          # 40 + 20, both passes
    # holes 3000k + 1000k a pass, unnamed 1000k + 500k: over all passes
    assert uncovered_named_pct.read(CTX) == pytest.approx(
        100.0 * (4_000 - 1_500) / 4_000)


def _stripped(s):
    """A summary as the parent of PR 43 writes it."""
    out = {k: v for k, v in s.items() if k not in ("holes", "handoffs")}
    out["phases"] = {n: {k: v for k, v in p.items()
                         if k not in ("cpu_us", "timed_us")}
                     for n, p in s["phases"].items()
                     if n != "dispatch:launch"
                     and not n.startswith("wait:")}
    return out


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_parent_reads_none(monkeypatch, name):
    parent = [_stripped(s) for s in TWO_PASSES]
    monkeypatch.setattr(program_spans, "finished", lambda: parent)
    got = READERS[name].read(CTX)
    if name == "uncovered_ms_per_pass":
        # wall_us and covered_us were always there: the one reader of the
        # seven that gives the parent a number
        assert got == pytest.approx(2 * AT_K1[name])
    else:
        assert got is None


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("summaries", [None, [], [_q1(1.0, 1)]],
                         ids=["no-ring", "empty-ring", "outside-every-pass"])
def test_nothing_to_read_is_none(monkeypatch, name, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert READERS[name].read(CTX) is None


def test_a_pass_that_launched_nothing_reads_zero(monkeypatch):
    """Host-only queries of a program that does split work from wait: 0
    dispatch time, not None (the metric lists no cells, so every cell's
    traced run has to report it)."""
    host = [_summary(t, phases={"expr:eval": _phase(500, cpu_us=400)},
                     holes=100, handoffs=(3, 30, 20))
            for t in (11.0, 21.0)]
    monkeypatch.setattr(program_spans, "finished", lambda: host)
    assert dispatch_launch_ms_per_pass.read(CTX) == 0
    assert dispatch_offcpu_ms_per_pass.read(CTX) == 0
    assert op_offcpu_ms_per_pass.read(CTX) == pytest.approx(0.1)
    assert handoffs_per_pass.read(CTX) == 3


def test_no_hole_is_no_share(monkeypatch):
    whole = [_summary(11.0, wall=1_000, covered=1_000, holes=0,
                      handoffs=(0, 0, 0))]
    monkeypatch.setattr(program_spans, "finished", lambda: whole)
    assert uncovered_named_pct.read(CTX) is None
    assert uncovered_ms_per_pass.read(CTX) == 0


def test_the_compute_spans_are_the_programs():
    from daft_tpu import tracing
    assert set(wait_spans.COMPUTE_SPANS) == set(tracing.COMPUTE_SPANS)
    assert "device:dispatch" not in op_offcpu_ms_per_pass.NAMES
    assert set(op_offcpu_ms_per_pass.NAMES) | {"device:dispatch"} == \
        set(wait_spans.COMPUTE_SPANS)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_is_listed_with_a_reader_for_every_cell(name):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    unit, better, source, layer = NEW[name]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": source, "layer": layer, "moves": "pass_s"}
    assert layer in {x["layer"] for x in bench["per_layer"]
                     if x["name"] not in NEW}
    assert os.path.exists(os.path.join(
        run.ROOT, "chipbench", "layer_metrics", name + ".py"))


def test_they_are_appended_after_what_was_there():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("select_hbm_pct")
    assert names[at + 1:at + 8] == list(NEW)
