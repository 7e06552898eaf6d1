"""The three readers of the join tally (``join_device_pairs_pct``,
``join_max_pair_Mrows``, ``device_join_ms_per_pass``) on hand-made
summaries: SF10's pass with Q3's sixteen largest pairs on the device, a
pass whose every pair stayed on the host, a query that joins nothing beside
them, a program that tallies nothing (the parent of PR 38), and nothing to
read at all."""

import importlib
import types

import pytest

from chipbench import program_spans, run

NAMES = ("join_device_pairs_pct", "join_max_pair_Mrows",
         "device_join_ms_per_pass")


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, joins=None, device_us=0, host_us=0):
    """``joins``: (pairs_device, pairs_host, rows_device, rows_host,
    max_pair_rows), or None for a program that keeps no such tally."""
    s = {"t0_perf_s": t0, "wall_us": 10_000_000, "covered_us": 9_000_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "phases": {}}
    for name, us in (("join:device", device_us), ("join:build", host_us)):
        if us:
            s["phases"][name] = {"count": 1, "wall_us": us, "sum_us": us,
                                 "bytes": 0, "rows": 0}
    if joins is not None:
        s["joins"] = dict(zip(("pairs_device", "pairs_host", "rows_device",
                               "rows_host", "max_pair_rows"), joins))
    return s


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30)])
NO_JOIN = (0, 0, 0, 0, 0)                         # Q1
# Q3, Q10, Q1 a pass; before the first pass a warm-up's trace, not counted
SOME_ON_THE_DEVICE = [_summary(5.0, (32, 0, 9, 0, 9_000_000), 9_000_000)] + [
    s for t in (10, 20) for s in (
        _summary(t + 1.0, (16, 16, 33_500_000, 9_000_000, 2_093_000),
                 device_us=1_400_000 + t, host_us=300_000),
        _summary(t + 5.0, (0, 48, 0, 47_000_000, 976_000), host_us=900_000),
        _summary(t + 8.0, NO_JOIN))]
ALL_ON_THE_HOST = [
    s for t in (10, 20) for s in (
        _summary(t + 1.0, (0, 32, 0, 3_400_000, 209_400), host_us=300_000),
        _summary(t + 5.0, (0, 48, 0, 4_700_000, 97_700), host_us=400_000),
        _summary(t + 8.0, NO_JOIN))]
THE_PARENT = [_summary(t, None, host_us=300_000)
              for t in (11.0, 15.0, 21.0, 25.0)]


def _read(name):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(CTX)


@pytest.mark.parametrize("summaries,pct,mrows,ms", [
    (SOME_ON_THE_DEVICE, 20.0, 2.093, 1400.015),
    (ALL_ON_THE_HOST, 0.0, 0.2094, 0.0)],
    ids=["some-on-the-device", "all-on-the-host"])
def test_the_join_readers(monkeypatch, summaries, pct, mrows, ms):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read("join_device_pairs_pct") == pytest.approx(pct)
    assert _read("join_max_pair_Mrows") == pytest.approx(mrows)
    assert _read("device_join_ms_per_pass") == pytest.approx(ms)


@pytest.mark.parametrize("summaries,device_ms", [
    (None, None), ([], None), ([_summary(1.0, (16, 16, 5, 5, 5), 1000)], None),
    (THE_PARENT, None),
    # a program that has the span and matched no pair read 0 ms of it
    ([_summary(t, NO_JOIN) for t in (11.0, 21.0)], 0.0)],
    ids=["no-ring", "empty-ring", "outside-every-pass", "the-parent",
         "nothing-joined"])
def test_nothing_to_read_is_none(monkeypatch, summaries, device_ms):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read("join_device_pairs_pct") is None
    assert _read("join_max_pair_Mrows") is None
    assert _read("device_join_ms_per_pass") == device_ms


def test_they_are_listed_for_the_cells_that_join():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    layers = {"join_device_pairs_pct": ("dispatch gate", "program_counter"),
              "join_max_pair_Mrows": ("host operators", "program_counter"),
              "device_join_ms_per_pass": ("device programs", "program_span")}
    have = {m["layer"] for m in bench["per_layer"] if m["name"] not in layers}
    for name, (layer, source) in layers.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["layer"], m["source"], m["moves"]) == (layer, source,
                                                         "pass_s")
        assert layer in have
        assert m["workloads"] == ["tpch-sf1.join", "tpch-sf10.join"]
        for cell in m["workloads"]:
            traffic = next(c["traffic"] for c in bench["workloads"]
                           if c["name"] == cell)
            assert traffic == "join"
