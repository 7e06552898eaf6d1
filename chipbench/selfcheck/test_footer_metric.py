"""The reader of the per-file footer store (``footer_store_hit_pct``) on
hand-made summaries: a warm store, a cold first pass, a file rewritten
between passes, a program that tallies no footers (the parent of PR 33),
a pass that planned no local Parquet file, and nothing to read at all."""

import types

import pytest

from chipbench import program_spans, run
from chipbench.layer_metrics import footer_store_hit_pct


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, footers=None):
    s = {"t0_perf_s": t0, "wall_us": 10_000, "covered_us": 9_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "phases": {}}
    if footers is not None:
        s["footers"] = {"from_store": footers[0], "read": footers[1]}
    return s


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30),
                                    _pass(30, 40)])
# Q1 then Q6 a pass, each over the same 160 files
WARM = [_summary(t, (160, 0)) for t in (11.0, 15.0, 21.0, 25.0, 31.0, 35.0)]
# the first traced query finds nothing held; before the first pass a
# trace that is nobody's (the warm-up's) read them too and is not counted
COLD = [_summary(5.0, (0, 160)), _summary(11.0, (0, 160))] \
    + [_summary(t, (160, 0)) for t in (15.0, 21.0, 25.0, 31.0, 35.0)]
ONE_REWRITTEN = [_summary(t, (160, 0)) for t in (11.0, 15.0, 21.0)] \
    + [_summary(25.0, (159, 1))] \
    + [_summary(t, (160, 0)) for t in (31.0, 35.0)]


@pytest.mark.parametrize("summaries,pct", [
    (WARM, 100.0), (COLD, 100.0 * 800 / 960),
    (ONE_REWRITTEN, 100.0 * 959 / 960)],
    ids=["warm", "cold-first-query", "one-file-rewritten"])
def test_the_share_of_footers_held(monkeypatch, summaries, pct):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert footer_store_hit_pct.read(CTX) == pytest.approx(pct)


@pytest.mark.parametrize("summaries", [
    None, [], [_summary(1.0, (160, 0))],
    [_summary(t) for t in (11.0, 21.0, 31.0)],
    [_summary(t, (0, 0)) for t in (11.0, 21.0, 31.0)]],
    ids=["no-ring", "empty-ring", "outside-every-pass", "no-footers-key",
         "no-local-parquet-planned"])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert footer_store_hit_pct.read(CTX) is None


def test_it_is_listed_under_the_plan_layer_for_every_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    m = next(m for m in bench["per_layer"]
             if m["name"] == "footer_store_hit_pct")
    assert m == {"name": "footer_store_hit_pct", "unit": "%",
                 "better": "higher", "source": "program_counter",
                 "layer": "API / plan", "moves": "pass_s"}
    assert m["layer"] == next(x for x in bench["per_layer"]
                              if x["name"] == "optimize_ms_per_pass")["layer"]
