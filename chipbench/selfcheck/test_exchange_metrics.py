"""The two readers of the hand-over between operators
(``exchange_ms_per_pass``, ``exchange_fanouts_per_pass``) on hand-made
summaries: a program that fans out per morsel, one that hands a small
input over unhashed, and one with nothing to read (no ring, an empty
one, a trace outside every pass)."""

import importlib
import types

import pytest

from chipbench import program_spans, run


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, phases):
    return {"t0_perf_s": t0, "wall_us": 10_000, "covered_us": 9_000,
            "tables": {"from_cache": 0, "encoded": 0, "host": 0},
            "phases": {n: {"count": c, "wall_us": w, "sum_us": w,
                           "bytes": 0, "rows": 0}
                       for n, (c, w) in phases.items()}}


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30),
                                    _pass(30, 40)])
# a grouped query and an ungrouped one a pass
PER_MORSEL = [_summary(t, {"exchange:partition": (16, 40_000 + t),
                           "expr:eval": (3, 5_000)})
              for t in (11.0, 21.0, 31.0)] \
    + [_summary(t, {"expr:eval": (2, 900)}) for t in (15.0, 25.0, 35.0)]
GATHERED = [_summary(t, {"exchange:gather": (1, 300),
                         "expr:eval": (3, 5_000)})
            for t in (11.0, 21.0, 31.0)]
MIXED = [_summary(11.0, {"exchange:partition": (4, 2_000),
                         "exchange:gather": (1, 500)}),
         _summary(21.0, {"exchange:partition": (6, 4_000),
                         "exchange:gather": (1, 300)}),
         _summary(31.0, {"exchange:partition": (5, 3_000),
                         "exchange:gather": (1, 400)})]


def _read(name):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(CTX)


@pytest.mark.parametrize("summaries,ms,fanouts", [
    (PER_MORSEL, 40.021, 16), (GATHERED, 0.3, 0), (MIXED, 3.4, 5)])
def test_the_exchange_readers(monkeypatch, summaries, ms, fanouts):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read("exchange_ms_per_pass") == pytest.approx(ms)
    assert _read("exchange_fanouts_per_pass") == fanouts


@pytest.mark.parametrize("summaries", [
    None, [], [_summary(1.0, {"exchange:partition": (16, 40_000)})]])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read("exchange_ms_per_pass") is None
    assert _read("exchange_fanouts_per_pass") is None


def test_both_are_listed_under_the_layer_they_split():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    other = by_name["host_other_ms_per_pass"]
    for name, unit in (("exchange_ms_per_pass", "ms"),
                       ("exchange_fanouts_per_pass", "count")):
        m = by_name[name]
        assert m["layer"] == other["layer"] and m["moves"] == "pass_s"
        assert (m["unit"], m["better"], m["source"]) == \
            (unit, "lower", "program_span")
        assert "workloads" not in m
