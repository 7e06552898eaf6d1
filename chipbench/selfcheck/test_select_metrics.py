"""The three readers of the selection tally (``select_device_tables_pct``,
``select_survivors_pct``, ``select_hbm_pct``) on hand-made summaries and a
hand-made trace summary: a pass whose ``lineitem`` tables the device
selected while ``part`` stayed with the reader, a pass the gate kept on the
host, a program that tallies nothing (the parent of PR 41), and nothing to
read at all; then the new cell walked on the CPU with an answer altered
where it is produced."""

import importlib
import types

import pytest

from chipbench import peaks, program_spans, run
from chipbench.layer_metrics import select_hbm_pct
from chipbench.queries import q6, q14, q19

NAMES = ("select_device_tables_pct", "select_survivors_pct",
         "select_hbm_pct")
KEYS = ("tables_device", "tables_host", "rows_in", "rows_out",
        "rows_in_device", "rows_out_device", "overflows")
CELL = "tpch-sf10.star-revenue"
ROWS, PART = 59_970_000, 2_000_000


def _pass(start, end):
    return types.SimpleNamespace(start_s=start, end_s=end)


def _summary(t0, selects=None):
    s = {"t0_perf_s": t0, "wall_us": 1_000_000, "covered_us": 900_000,
         "tables": {"from_cache": 0, "encoded": 0, "host": 0}, "phases": {}}
    if selects is not None:
        s["selects"] = dict(zip(KEYS, selects))
    return s


NOTHING = (0, 0, 0, 0, 0, 0, 0)                    # Q6: ends in an aggregate
Q14_DEV = (16, 16, ROWS + PART, 780_000 + PART, ROWS, 780_000, 0)
Q19_DEV = (16, 16, ROWS + PART, 2_140_000 + PART, ROWS, 2_140_000, 0)
Q14_HOST = (0, 32, ROWS + PART, 780_000 + PART, 0, 0, 0)
Q19_HOST = (0, 32, ROWS + PART, 2_140_000 + PART, 0, 0, 0)
# Q14, Q19, Q6 a pass; before the first pass a warm-up's trace, not counted
ON_THE_DEVICE = [_summary(5.0, (16, 0, 9, 9, 9, 9, 3))] + [
    _summary(t + dt, sel) for t in (10, 20)
    for dt, sel in ((1.0, Q14_DEV), (4.0, Q19_DEV), (8.0, NOTHING))]
ON_THE_HOST = [
    _summary(t + dt, sel) for t in (10, 20)
    for dt, sel in ((1.0, Q14_HOST), (4.0, Q19_HOST), (8.0, NOTHING))]
THE_PARENT = [_summary(t + dt) for t in (10, 20) for dt in (1.0, 4.0, 8.0)]

SURVIVORS = 100.0 * (780_000 + 2_140_000 + 2 * PART) / (2 * (ROWS + PART))


def _ctx(trace=None):
    return types.SimpleNamespace(
        passes=[_pass(10, 20), _pass(20, 30)], trace=trace,
        peaks=peaks.PEAKS["TPU v5 lite"],
        traffic={"queries": ["q14", "q19", "q6"],
                 "select_programs": ["jit_run_select"],
                 "agg_programs": ["jit_run_packed"]},
        queries={"q14": q14, "q19": q19, "q6": q6})


def _least_bytes():
    # Q14 reads and writes key 8 + two floats 4 + a date 4; Q19 key 8 +
    # three floats 4 + two codes 1
    return 2 * ((ROWS * 20 + 780_000 * 20) + (ROWS * 22 + 2_140_000 * 22))


def _trace(select_s):
    """Two traced passes whose selection programs took ``select_s`` device
    seconds in all, half a query; Q6's aggregate beside them."""
    return types.SimpleNamespace(
        span_module_s={"execute:q14": {"jit_run_select": select_s / 2},
                       "execute:q19": {"jit_run_select": select_s / 2,
                                       "jit_other": 1.0},
                       "execute:q6": {"jit_run_packed": 0.004}},
        span_count={"execute:q14": 2, "execute:q19": 2, "execute:q6": 2})


def _read(name, ctx):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(ctx)


def test_the_byte_function_counts_reads_a_row_and_writes_a_survivor():
    assert select_hbm_pct.select_bytes(q14, 1000, 10) == 1000 * 20 + 10 * 20
    assert select_hbm_pct.select_bytes(q19, 1000, 10) == 1000 * 22 + 10 * 22


@pytest.mark.parametrize("summaries,tables_pct", [
    (ON_THE_DEVICE, 50.0), (ON_THE_HOST, 0.0)],
    ids=["lineitem-on-the-device", "all-on-the-host"])
def test_the_tally_readers(monkeypatch, summaries, tables_pct):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    ctx = _ctx()
    assert _read("select_device_tables_pct", ctx) == pytest.approx(tables_pct)
    assert _read("select_survivors_pct", ctx) == pytest.approx(SURVIVORS)


def test_the_roofline_share_of_a_program_that_reads_what_it_must(
        monkeypatch):
    monkeypatch.setattr(program_spans, "finished", lambda: ON_THE_DEVICE)
    at_peak = _least_bytes() / 819e9
    # a program that moved exactly the least bytes in twice the time
    assert _read("select_hbm_pct", _ctx(_trace(2 * at_peak))) \
        == pytest.approx(50.0)
    # and the chain program's kind of time: 16 ms a 4 M-row table
    slow = _read("select_hbm_pct", _ctx(_trace(64 * 0.016)))
    assert 0 < slow < 100


@pytest.mark.parametrize("summaries", [None, [], [_summary(1.0, Q14_DEV)],
                                       THE_PARENT],
                         ids=["no-ring", "empty-ring", "outside-every-pass",
                              "the-parent"])
def test_nothing_to_read_is_none(monkeypatch, summaries):
    monkeypatch.setattr(program_spans, "finished", lambda: summaries)
    for name in NAMES:
        assert _read(name, _ctx(_trace(1.0))) is None


def test_no_selection_program_in_the_trace_is_none(monkeypatch):
    # the gate kept every table on the host: the tallies read, the share
    # of the roofline has no program to time
    monkeypatch.setattr(program_spans, "finished", lambda: ON_THE_HOST)
    quiet = types.SimpleNamespace(
        span_module_s={"execute:q6": {"jit_run_packed": 0.004}},
        span_count={"execute:q6": 2})
    assert _read("select_hbm_pct", _ctx(quiet)) is None
    assert _read("select_hbm_pct", _ctx(None)) is None
    assert _read("select_device_tables_pct", _ctx(quiet)) == 0.0


def test_they_are_listed_for_the_cells_with_filtered_scans():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    joins = ["tpch-sf1.join", "tpch-sf10.join"]
    want = {"select_device_tables_pct": ("dispatch gate", "program_counter",
                                         [CELL] + joins),
            "select_survivors_pct": ("executor", "program_counter",
                                     [CELL] + joins),
            "select_hbm_pct": ("device programs", "device_trace", [CELL])}
    have = {m["layer"] for m in bench["per_layer"] if m["name"] not in want}
    for name, (layer, source, cells) in want.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["layer"], m["source"], m["moves"], m["workloads"]) == (
            layer, source, "pass_s", cells)
        assert layer in have
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch-sf10-star", "star-revenue", 1)
    traffic = run.load_json(run.HERE, "traffic", "star-revenue.json")
    assert traffic["queries"] == ["q14", "q19", "q6"]
    assert traffic["select_programs"] == ["jit_run_select"]


def _walk(trace):
    # (a seed whose SF0.01 data leaves Q19 a row: an empty sum is null)
    args = run.parse(["--workload", CELL, "--seed", "2147486501",
                      "--seconds", "3", "--trace", str(trace), "--rehearse"])
    return run.execute(args)


def test_the_cell_walks_and_reads_its_tallies():
    result = _walk(1)
    assert result["correct"] is True and result["failed"] == 0
    assert result["control_refused"] is True
    metrics = result["metrics"]
    # at SF0.01 the gate keeps every table on the host; the tallies read
    assert metrics["select_device_tables_pct"]["value"] == 0.0
    assert 0 < metrics["select_survivors_pct"]["value"] < 100
    assert "select_hbm_pct" not in metrics


def test_an_altered_answer_of_the_new_cell_is_not_correct(monkeypatch):
    import daft_tpu
    real = daft_tpu.DataFrame.to_pydict

    def off_by_a_thousandth(self, *a, **kw):
        out = real(self, *a, **kw)
        return {name: [v * 1.001 if isinstance(v, float) else v
                       for v in col] for name, col in out.items()}

    monkeypatch.setattr(daft_tpu.DataFrame, "to_pydict", off_by_a_thousandth)
    result = _walk(0)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
