"""The reader of the partial-decode tally (``decode_tables_per_batch``) on
hand-made summaries: SF100's windows of 54 + 54 + 52 tables a query, a
query answered on the host beside one on the device, a program that decodes
every table alone, a program that tallies nothing (the parent of PR 37),
passes that decoded no device result, and nothing to read at all."""

import types

import pytest

from chipbench import program_spans, run
from chipbench.layer_metrics import decode_tables_per_batch


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, decode=None):
    s = {"t0_perf_s": t0, "wall_us": 10_000, "covered_us": 9_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "phases": {}}
    if decode is not None:
        s["decode"] = {"tables": decode[0], "batches": decode[1]}
    return s


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30),
                                    _pass(30, 40)])
STARTS = (11.0, 15.0, 21.0, 25.0, 31.0, 35.0)   # Q1 then Q6 a pass
# before the first pass a trace that is nobody's (the warm-up's): not counted
WINDOWED = [_summary(5.0, (160, 160))] \
    + [_summary(t, (160, 3)) for t in STARTS]
# Q6 runs on the host (the hot cell): its traces decode nothing
Q1_ONLY = [_summary(t, (16, 3) if i % 2 == 0 else (0, 0))
           for i, t in enumerate(STARTS)]
A_TABLE_A_BATCH = [_summary(t, (160, 160)) for t in STARTS]


@pytest.mark.parametrize("summaries,ratio", [
    (WINDOWED, 160 / 3), (Q1_ONLY, 16 / 3), (A_TABLE_A_BATCH, 1.0)],
    ids=["a-batch-a-window", "one-query-on-the-host", "a-table-a-batch"])
def test_tables_over_batches(monkeypatch, summaries, ratio):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert decode_tables_per_batch.read(CTX) == pytest.approx(ratio)


@pytest.mark.parametrize("summaries", [
    None, [], [_summary(1.0, (160, 3))],
    [_summary(t) for t in STARTS],
    [_summary(t, (0, 0)) for t in STARTS]],
    ids=["no-ring", "empty-ring", "outside-every-pass", "no-decode-key",
         "nothing-decoded"])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert decode_tables_per_batch.read(CTX) is None


def test_it_is_listed_under_the_decode_layer_for_every_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    m = next(m for m in bench["per_layer"]
             if m["name"] == "decode_tables_per_batch")
    assert m == {"name": "decode_tables_per_batch", "unit": "tables/batch",
                 "better": "higher", "source": "program_counter",
                 "layer": "partial decode", "moves": "pass_s"}
    assert m["layer"] == next(x for x in bench["per_layer"]
                              if x["name"] == "decode_ms_per_pass")["layer"]
