"""The four readers ``tpch-sf10.part-lookup`` brings
(``host_scan_Mrows_per_pass``, ``join_in_Mrows_per_pass``,
``join_out_pct``, ``repeated_scans_per_pass``) on hand-made summaries:
SF10's pass of Q17 and Q6 as the engine runs it today, the same pass once a
key set filters the fact scan, a program that has the old tallies and not
the new ones (the parent of PR 48), a program that keeps no ring at all,
and nothing to read."""

import importlib
import types

import pytest

from chipbench import program_spans, run

NAMES = ("host_scan_Mrows_per_pass", "join_in_Mrows_per_pass",
         "join_out_pct", "repeated_scans_per_pass")


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, scan_rows=None, joins=None, repeated=None):
    """``joins``: (rows_device, rows_host, rows_out) or None for a program
    that keeps no join tally; ``rows_out`` None for one that keeps the old
    keys only. ``repeated`` None: no ``plan`` tally."""
    s = {"t0_perf_s": t0, "wall_us": 10_000_000, "covered_us": 9_000_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0},
         "phases": {}}
    if scan_rows is not None:
        s["phases"]["scan:load"] = {"count": 64, "wall_us": 4_000_000,
                                    "sum_us": 30_000_000, "bytes": 0,
                                    "rows": scan_rows}
    if joins is not None:
        device, host, out = joins
        s["joins"] = {"pairs_device": 0, "pairs_host": 48,
                      "rows_device": device, "rows_host": host,
                      "max_pair_rows": 3_800_000}
        if out is not None:
            s["joins"].update(rows_small=host // 1000, rows_out=out)
    if repeated is not None:
        s["plan"] = {"repeated_scans": repeated}
    return s


CTX = types.SimpleNamespace(passes=[_pass(10, 20), _pass(20, 30)])
NONE_CTX = types.SimpleNamespace(passes=[])


def _q17_q6(t, scan, joins, q6_scan=1_100_000, new=True):
    """A pass: Q17 then Q6 (which joins nothing and repeats no scan)."""
    return [_summary(t + 1.0, scan, joins, 2 if new else None),
            _summary(t + 9.0, q6_scan, (0, 0, 0 if new else None),
                     0 if new else None)]


# before the first pass a warm-up's trace, not counted
TODAY = [_summary(5.0, 9, (9, 9, 9), 9)] \
    + _q17_q6(10, 119_950_000, (0, 120_100_000, 183_000)) \
    + _q17_q6(20, 119_950_000, (0, 120_060_000, 183_000))
PUSHED_DOWN = _q17_q6(10, 4_000, (0, 310_000, 183_000), q6_scan=0) \
    + _q17_q6(20, 4_000, (0, 310_000, 183_000), q6_scan=0)
THE_PARENT = _q17_q6(10, 119_950_000, (0, 120_100_000, None), new=False) \
    + _q17_q6(20, 119_950_000, (0, 120_060_000, None), new=False)


def _read(name, ctx=CTX):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(ctx)


@pytest.mark.parametrize("summaries,want", [
    (TODAY, (121.05, 120.08, 100 * 366_000 / 240_160_000, 2.0)),
    (PUSHED_DOWN, (0.004, 0.31, 100 * 183_000 / 310_000, 2.0)),
    (THE_PARENT, (121.05, 120.08, None, None))],
    ids=["today", "pushed-down", "the-parent"])
def test_the_lookup_readers(monkeypatch, summaries, want):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    for name, value in zip(NAMES, want):
        got = _read(name)
        if value is None:
            assert got is None, name
        else:
            assert got == pytest.approx(value), name


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("summaries", [
    None, [], [_summary(1.0, 7, (1, 1, 1), 2)]],
    ids=["no-ring", "empty-ring", "outside-every-pass"])
def test_nothing_to_read_is_none(monkeypatch, summaries, name):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    assert _read(name) is None
    assert _read(name, NONE_CTX) is None


def test_a_program_that_is_not_there_reads_none(monkeypatch):
    """``program_spans.finished`` answers None where ``daft_tpu.tracing``
    cannot be imported or keeps no ring: every reader then leaves its
    metric out and none raises."""
    import builtins
    real = builtins.__import__

    def refuse(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "daft_tpu" and "tracing" in (fromlist or ()):
            raise ImportError("no program")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", refuse)
    assert program_spans.finished() is None
    for name in NAMES:
        assert _read(name) is None


def test_a_query_that_scans_and_joins_nothing_reads_zero(monkeypatch):
    # a resident pass: the cache serves every table, nothing is joined
    monkeypatch.setattr(program_spans, "finished", lambda: [
        _summary(t, None, (0, 0, 0), 0) for t in (11.0, 21.0)])
    assert _read("host_scan_Mrows_per_pass") == 0.0
    assert _read("join_in_Mrows_per_pass") == 0.0
    assert _read("join_out_pct") is None      # nothing in: no share
    assert _read("repeated_scans_per_pass") == 0.0


def test_they_are_listed_for_the_lookup_cell_alone():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    layers = {"host_scan_Mrows_per_pass": ("scan / decode", "program_span",
                                           "lower"),
              "join_in_Mrows_per_pass": ("host operators",
                                         "program_counter", "lower"),
              "join_out_pct": ("host operators", "program_counter",
                               "higher"),
              "repeated_scans_per_pass": ("API / plan", "program_counter",
                                          "lower")}
    have = {m["layer"] for m in bench["per_layer"] if m["name"] not in layers}
    for name, (layer, source, better) in layers.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["layer"], m["source"], m["better"], m["moves"]) == \
            (layer, source, better, "pass_s")
        assert layer in have
        assert m["workloads"] == ["tpch-sf10.part-lookup"]
    cell = next(c for c in bench["workloads"]
                if c["name"] == "tpch-sf10.part-lookup")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch-sf10-part-lookup", "part-lookup", 1)
    config = run.load_json(run.HERE, "configs", cell["config"] + ".json")
    listed = next(c for c in bench["configs"]
                  if c["name"] == cell["config"])
    assert config["source"] == listed["source"]
    assert config["reduced"] == listed["reduced"] == ["tables"]
    traffic = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    assert traffic["queries"] == ["q17", "q6"] and traffic["cache"] == "keep"
