"""The reader of ``file_stats_per_file`` on hand-made summaries: one stat a
file, the three a file of a program that stats in the planner and twice in
the executor, a pass with a catalog scan whose tasks carry no identity, a
program that tallies no files (the parent of PR 39), a pass that planned
no file, and nothing to read at all."""

import types

import pytest

from chipbench import program_spans, run
from chipbench.layer_metrics import file_stats_per_file


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, files=None):
    s = {"t0_perf_s": t0, "wall_us": 10_000, "covered_us": 9_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "footers": {"from_store": 160, "read": 0}, "phases": {}}
    if files is not None:
        s["files"] = {"planned": files[0], "stats": files[1]}
    return s


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30),
                                    _pass(30, 40)])
TIMES = (11.0, 15.0, 21.0, 25.0, 31.0, 35.0)
# Q1 then Q6 a pass, each over the same 160 files; a trace before the
# first pass (the warm-up's) is nobody's and is not counted
ONCE = [_summary(5.0, (160, 480))] + [_summary(t, (160, 160)) for t in TIMES]
THRICE = [_summary(t, (160, 480)) for t in TIMES]
# one query of six also fingerprints 16 tasks that carry no identity
FALLBACK = [_summary(t, (160, 160)) for t in TIMES[:-1]] \
    + [_summary(TIMES[-1], (160, 192))]


@pytest.mark.parametrize("summaries,per_file", [
    (ONCE, 1.0), (THRICE, 3.0), (FALLBACK, 992 / 960)],
    ids=["one-a-file", "three-a-file", "some-tasks-carry-none"])
def test_stats_over_files_planned(monkeypatch, summaries, per_file):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert file_stats_per_file.read(CTX) == pytest.approx(per_file)


@pytest.mark.parametrize("summaries", [
    None, [], [_summary(1.0, (160, 160))],
    [_summary(t) for t in (11.0, 21.0, 31.0)],
    [_summary(t, (0, 0)) for t in (11.0, 21.0, 31.0)]],
    ids=["no-ring", "empty-ring", "outside-every-pass", "no-files-key",
         "no-file-planned"])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert file_stats_per_file.read(CTX) is None


def test_it_is_listed_under_the_plan_layer_for_every_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    m = bench["per_layer"][-1]
    assert m == {"name": "file_stats_per_file", "unit": "stats/file",
                 "better": "lower", "source": "program_counter",
                 "layer": "API / plan", "moves": "pass_s"}
    assert m["layer"] == next(x for x in bench["per_layer"]
                              if x["name"] == "plan_ms")["layer"]
