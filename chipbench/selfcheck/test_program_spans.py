"""``program_spans``: the program's trace summaries laid on the window's
passes, on hand-made summaries and passes (no program, no clock)."""

import importlib
import types

import pytest

from chipbench import program_spans


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, wall, covered, phases, tables=(0, 0, 0)):
    return {"t0_perf_s": t0, "wall_us": wall, "covered_us": covered,
            "tables": dict(zip(("from_cache", "encoded", "host"), tables)),
            "phases": {n: {"count": 1, "wall_us": w, "sum_us": w,
                           "bytes": b, "rows": 0}
                       for n, (w, b) in phases.items()}}


SUMMARIES = [
    # warm-up, before the window: belongs to no pass
    _summary(1.0, 900, 900, {"scan:load": (900, 0)}),
    # pass 0 (10..20): two queries
    _summary(10.5, 1000, 800, {"scan:load": (400, 0),
                               "device:put": (100, 5_000_000)}, (0, 4, 0)),
    _summary(15.0, 1000, 900, {"scan:load": (200, 0),
                               "join:build": (300, 0),
                               "join:probe": (100, 0)}, (0, 0, 1)),
    # pass 1 (20..30): one query, no join
    _summary(21.0, 2000, 1000, {"scan:load": (1000, 0),
                                "device:put": (300, 7_000_000)}, (3, 0, 0)),
    # pass 2 (30..40) holds no summary (run after the profiler stopped)
    # pass 3 (40..50)
    _summary(41.0, 1000, 1000, {"scan:load": (800, 0),
                                "device:put": (200, 9_000_000)}, (4, 0, 0)),
]
CTX = types.SimpleNamespace(
    passes=[_pass(10, 20), _pass(20, 30), _pass(30, 40), _pass(40, 50)])


def test_summaries_are_kept_by_the_pass_they_started_in():
    held = program_spans.by_pass(CTX.passes, SUMMARIES)
    assert [len(h) for h in held] == [2, 1, 1]


def test_per_pass_sums_within_a_pass_and_takes_the_median_over_passes():
    phases = program_spans.per_pass(CTX, SUMMARIES)
    # scan:load a pass: 600, 1000, 800 -> 800
    assert phases["scan:load"]["wall_us"] == 800
    assert phases["device:put"]["bytes"] == 7_000_000
    # entered in one pass of three: the median pass spent nothing there
    assert phases["join:build"]["wall_us"] == 0
    assert phases["scan:load"]["count"] == 1


@pytest.mark.parametrize("summaries", [None, [], SUMMARIES[:1]])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    # a --trace 0 run, a program with no ring, traces outside every pass
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert program_spans.per_pass(CTX) is None
    assert program_spans.phase_ms(CTX, "scan:load") is None
    assert program_spans.totals(CTX) is None
    for name in ("scan_decode_ms_per_pass", "put_MB_per_pass",
                 "device_tables_pct", "span_coverage_pct"):
        reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert reader.read(CTX) is None


def test_the_readers_on_the_hand_made_window(monkeypatch):
    monkeypatch.setattr(program_spans, "finished", lambda: SUMMARIES)

    def read(name):
        return importlib.import_module(
            f"chipbench.layer_metrics.{name}").read(CTX)

    assert read("scan_decode_ms_per_pass") == pytest.approx(0.8)
    assert read("put_MB_per_pass") == pytest.approx(7.0)
    assert read("put_ms_per_pass") == pytest.approx(0.2)
    assert read("host_join_ms_per_pass") == 0.0
    assert read("fetch_ms_per_pass") == 0.0
    # 3 + 4 from the cache, 4 encoded, 1 left to the host
    assert read("device_tables_pct") == pytest.approx(100 * 11 / 12)
    assert read("span_coverage_pct") == pytest.approx(100 * 3700 / 5000)
