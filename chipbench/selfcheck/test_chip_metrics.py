"""The four readers of the sharded HBM cache (``chips_with_tables``,
``chip_rows_imbalance_pct``, ``resident_GB_all_chips``,
``mesh_agg_hbm_pct``) on hand-made summaries: tables over four chips, a
chip left idle, one visible chip, a program that tallies no chips (the
parent of PR 28), and nothing to read at all."""

import importlib
import types

import pytest

from chipbench import peaks, program_spans, run
from chipbench.queries import q1


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, chips=None):
    s = {"t0_perf_s": t0, "wall_us": 10_000, "covered_us": 9_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "phases": {}}
    if chips is not None:
        s["chips"] = [{"chip": k, "tables": t, "rows": r,
                       "resident_bytes": b} for k, (t, r, b) in
                      enumerate(chips)]
    return s


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30),
                                    _pass(30, 40)])
GB = 10**9
# Q1 (all tables) then Q6 (smaller filtered tables) a pass, four chips
FOUR = [_summary(t, [(40, 150_000_000 + 1_000_000 * k, 5 * GB)
                     for k in range(4)]) for t in (11.0, 21.0, 31.0)] \
    + [_summary(t, [(40, 3_000_000, 5 * GB)] * 4)
       for t in (15.0, 25.0, 35.0)]
# the fourth chip visible, holding nothing
IDLE = [_summary(t, [(54, 200_000_000, 7 * GB), (53, 200_000_000, 7 * GB),
                     (53, 200_000_000, 7 * GB), (0, 0, 0)])
        for t in (11.0, 21.0, 31.0)]
ONE = [_summary(t, [(16, 59_970_000, 2 * GB)]) for t in (11.0, 21.0, 31.0)]
# every table left to the host: the chips are listed, none served any
HOST_ONLY = [_summary(t, [(0, 0, 0)]) for t in (11.0, 21.0, 31.0)]


def _read(name, ctx=CTX):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(ctx)


@pytest.mark.parametrize("summaries,chips,imbalance,resident", [
    (FOUR, 4, 100.0 * 3_000_000 / 154_500_000, 20.0),
    (IDLE, 3, 100.0 * 200 / 150, 21.0),
    (ONE, 1, 0.0, 2.0),
    (HOST_ONLY, 0, 0.0, 0.0)],
    ids=["four-chips", "one-idle", "one-chip", "host-only"])
def test_the_chip_readers(monkeypatch, summaries, chips, imbalance,
                          resident):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read("chips_with_tables") == chips
    assert _read("chip_rows_imbalance_pct") == pytest.approx(imbalance)
    assert _read("resident_GB_all_chips") == pytest.approx(resident)


@pytest.mark.parametrize("summaries", [
    None, [], [_summary(1.0, [(16, 1000, GB)])],
    [_summary(t) for t in (11.0, 21.0, 31.0)]],
    ids=["no-ring", "empty-ring", "outside-every-pass", "no-chips-key"])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read("chips_with_tables") is None
    assert _read("chip_rows_imbalance_pct") is None
    assert _read("resident_GB_all_chips") is None


def test_mesh_agg_hbm_pct_is_agg_hbm_pct_over_every_chips_seconds():
    rows = 600_000_000
    need = peaks.scan_agg_bytes(rows, list(q1.SCANS["lineitem"].values()))
    # 160 runs of 1.5 ms spread over four planes: the reduction has
    # already added them, so the share is per chip-second
    trace = types.SimpleNamespace(
        span_module_s={"execute:q1": {"jit_run_packed": 3 * 0.240}},
        span_count={"execute:q1": 3})
    ctx = types.SimpleNamespace(
        trace=trace, peaks=peaks.PEAKS["TPU v5 lite"],
        traffic={"agg_programs": ["jit_run_packed"]}, queries={"q1": q1},
        table_rows={"lineitem": rows})
    want = 100.0 * (3 * need / 819e9) / (3 * 0.240)
    assert _read("mesh_agg_hbm_pct", ctx) == pytest.approx(want)
    assert _read("mesh_agg_hbm_pct", ctx) == _read("agg_hbm_pct", ctx)
    ctx.trace = None   # a --trace 0 run
    assert _read("mesh_agg_hbm_pct", ctx) is None


def test_they_are_listed_under_layers_the_benchmark_has():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"][:27]}
    for name, unit, better, source in (
            ("chips_with_tables", "count", "higher", "program_counter"),
            ("chip_rows_imbalance_pct", "%", "lower", "program_counter"),
            ("resident_GB_all_chips", "GB", "higher", "program_counter"),
            ("mesh_agg_hbm_pct", "%", "higher", "device_trace")):
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"]) == \
            (unit, better, source)
        assert m["layer"] in layers and m["moves"] == "pass_s"
    assert by_name["mesh_agg_hbm_pct"]["workloads"] == \
        ["tpch-sf100.scan-agg-resident"]
    assert by_name["mesh_agg_hbm_pct"]["layer"] == \
        by_name["agg_hbm_pct"]["layer"]
    cell = next(c for c in bench["workloads"]
                if c["name"] == "tpch-sf100.scan-agg-resident")
    assert cell["chips"] == 4
    assert sum(c["chips"] == 4 for c in bench["workloads"]) \
        <= len(bench["workloads"]) // 2
