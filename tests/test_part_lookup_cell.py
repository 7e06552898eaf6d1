"""What the deployment ``tpch-sf10-part-lookup`` (cell
``tpch-sf10.part-lookup``) leans on, small and on the CPU: TPC-H Q17 over
16-file tables through ``daft_tpu.read_parquet`` against the benchmark's
plain reference in every way the engine can run it (the gates left alone,
the device tier off, the ``join_agg`` region forced onto the device and
refused, and the specification's SQL text), a seed whose key set is empty,
the tallies a traced query keeps of what its joins were handed and gave
back and of the scans its plan repeats, and the bfloat16 control's number
beside the limit."""

import importlib
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import daft_tpu  # noqa: E402
from benchmarking.tpch import sql_queries  # noqa: E402
from chipbench import answers, datagen  # noqa: E402
from chipbench.reference import common, q17 as ref17  # noqa: E402
from daft_tpu import tracing  # noqa: E402
from daft_tpu.physical import plan as pp  # noqa: E402
from daft_tpu.physical.translate import repeated_scans  # noqa: E402

RTOL = 1e-4
TABLES = ("part", "lineitem")
#: how Q17 is run: environment to set (None: unset), and whether it is the
#: SQL text or the DataFrame builder
MODES = {"auto": ({}, False),
         "host": ({"DAFT_TPU_DEVICE": "0"}, False),
         "fusion-on": ({"DAFT_TPU_FUSION": "1"}, False),
         "fusion-off": ({"DAFT_TPU_FUSION": "0"}, False),
         "sql": ({}, True)}
KNOBS = ("DAFT_TPU_DEVICE", "DAFT_TPU_FUSION", "DAFT_TPU_DEVICE_JOIN")


#: two sizes, two plans: at SF0.02 ``lineitem`` (120 k rows) is small
#: enough to be the broadcast side, so ``part`` is the probe and the inner
#: join-aggregate becomes a ``join_agg`` region; at SF0.05 the ~10 parts
#: the filter keeps are broadcast against ``lineitem``'s morsels and no
#: region forms. (At SF10 both sides are hash-partitioned: PERF.md §5.)
SCALES = {"sf0.02": (0.02, 2**31 + 48), "sf0.05": (0.05, 2**31 + 48)}


@pytest.fixture(scope="module", params=list(SCALES))
def root(request, tmp_path_factory):
    sf, seed = SCALES[request.param]
    return datagen.ensure_dataset(
        str(tmp_path_factory.mktemp("tpch_" + request.param)), "t", sf, 16,
        list(TABLES), seed, 1)


def _get_df(root):
    return lambda t: daft_tpu.read_parquet(f"{root}/{t}/*.parquet")


def _run(root, q, mp, env=None, sql=False):
    """One traced query: (its answer, its trace's summary)."""
    for k in KNOBS:
        mp.delenv(k, raising=False)
    for k, v in (env or {}).items():
        mp.setenv(k, v)
    mp.setenv("DAFT_TPU_TRACE", "1")
    tracing.reset_for_tests()
    get_df = _get_df(root)
    if sql:
        df = daft_tpu.sql(sql_queries.Q17, **{t: get_df(t) for t in TABLES})
    else:
        df = importlib.import_module(f"chipbench.queries.{q}").build(get_df)
    got = df.to_pydict()
    (summary,) = tracing.finished()
    return got, summary


@pytest.fixture(scope="module")
def runs(root):
    mp = pytest.MonkeyPatch()
    try:
        return {mode: _run(root, "q17", mp, env, sql)
                for mode, (env, sql) in MODES.items()}
    finally:
        mp.undo()
        tracing.reset_for_tests()


@pytest.fixture(scope="module")
def merges(root):
    """The plan's three joins as pandas merges of the same inputs: rows of
    ``part`` the filter keeps, rows of ``lineitem``, and the rows each
    join gives."""
    import pyarrow.compute as pc
    f = pc.field
    part = common.frame(root, "part", ["p_partkey"],
                        filters=(f("p_brand") == ref17.BRAND)
                        & (f("p_container") == ref17.CONTAINER))
    li = common.frame(root, "lineitem", ["l_partkey", "l_quantity"])
    joined = part.merge(li, left_on="p_partkey", right_on="l_partkey")
    avg = joined.groupby("p_partkey", as_index=False).l_quantity.mean()
    again = joined.merge(avg, on="p_partkey")
    return {"part": len(part), "lineitem": len(li),
            "out": 2 * len(joined) + len(again)}


@pytest.mark.parametrize("mode", MODES)
def test_answer_is_the_references(root, runs, mode):
    err = answers.compare(f"q17 {mode}", runs[mode][0], ref17.answer(root),
                          ref17.COMPARE, RTOL)
    assert err <= RTOL
    (value,) = runs[mode][0]["avg_yearly"]
    assert value is not None and value > 0


def test_the_dataframe_and_the_sql_text_agree(runs):
    (df,), (sql,) = (list(runs[m][0].values()) for m in ("auto", "sql"))
    assert sql == pytest.approx(df, rel=1e-9)


def test_the_region_ran_on_the_device_where_forced_and_not_where_refused(
        root, runs):
    on, off, host = (runs[m][1]["joins"]
                     for m in ("fusion-on", "fusion-off", "host"))
    if "sf0.02" in root:
        # forced, the inner join-aggregate's probe morsels (the files of
        # part that hold a key) are matched inside the region's program:
        # device pairs that pass no match_indices
        assert 0 < on["pairs_device"] <= 16 and on["rows_device"] > 0
        assert "device:region" in runs["fusion-on"][1]["phases"]
        assert "device:region" not in runs["fusion-off"][1]["phases"]
    else:
        assert on["pairs_device"] == 0
    for j in (off, host):
        assert j["pairs_device"] == 0 and j["rows_device"] == 0


#: the ways of running the DataFrame form; the SQL text plans otherwise
DF_MODES = [m for m, (_, sql) in MODES.items() if not sql]


@pytest.mark.parametrize("mode", DF_MODES)
def test_the_join_tallies_are_the_merges(runs, merges, mode):
    j = runs[mode][1]["joins"]
    assert set(j) == set(tracing.JOIN_TALLIES)
    assert merges["part"] > 0
    # every way of running it matches the same pairs
    assert j["rows_out"] == merges["out"]
    rows_in = j["rows_host"] + j["rows_device"]
    # lineitem is handed to two joins whole (no predicate of its own), and
    # where it is the broadcast side once a pair
    assert rows_in >= 2 * merges["lineitem"]
    assert 0 < 2 * j["rows_small"] <= rows_in
    # a join here keeps about a thousandth of what it is handed
    assert j["rows_out"] / rows_in < 0.01


@pytest.mark.parametrize("mode", DF_MODES)
def test_the_plan_scans_both_tables_twice(runs, mode):
    summary = runs[mode][1]
    assert summary["plan"] == {"repeated_scans": 2}
    assert set(summary["plan"]) == set(tracing.PLAN_TALLIES)
    # 16 files a table, four scans
    assert summary["files"]["planned"] == 64


def test_the_sql_text_scans_lineitem_twice_and_part_once(runs, merges):
    """The decorrelated subquery aggregates all of ``lineitem`` by part
    before any join: one scan of ``part``, two of ``lineitem``."""
    summary = runs["sql"][1]
    assert summary["plan"] == {"repeated_scans": 1}
    assert summary["files"]["planned"] == 48
    j = summary["joins"]
    rows_in = j["rows_host"] + j["rows_device"]
    assert 0 < j["rows_out"] < 0.01 * rows_in
    assert 0 < 2 * j["rows_small"] <= rows_in


def test_q6_joins_nothing_and_repeats_no_scan(root):
    mp = pytest.MonkeyPatch()
    try:
        _, s = _run(root, "q6", mp)
    finally:
        mp.undo()
        tracing.reset_for_tests()
    assert s["plan"] == {"repeated_scans": 0}
    assert s["joins"]["rows_out"] == 0 and s["joins"]["rows_small"] == 0
    assert s["joins"]["rows_host"] + s["joins"]["rows_device"] == 0


def test_the_tallies_are_on_the_root_span(root):
    from daft_tpu import observability as obs
    mp = pytest.MonkeyPatch()
    try:
        _, s = _run(root, "q17", mp)
        spans = obs.last_query_stats().trace_ctx.recorder.spans()
    finally:
        mp.undo()
        tracing.reset_for_tests()
    attrs = next(sp for sp in spans if sp["name"] == "query")["attrs"]
    assert attrs["plan_repeated_scans"] == 2
    assert attrs["join_rows_out"] == s["joins"]["rows_out"]
    assert attrs["join_rows_small"] == s["joins"]["rows_small"]


def test_an_empty_key_set_answers_null(tmp_path):
    """SF0.002 holds 400 parts, so most seeds hold none of one brand and
    container: the joins match nothing and the sum is SQL's null."""
    import pyarrow.compute as pc
    root = datagen.ensure_dataset(str(tmp_path), "t", 0.002, 16,
                                  list(TABLES), 2**31 + 480, 1)
    f = pc.field
    assert common.frame(root, "part", ["p_partkey"],
                        filters=(f("p_brand") == ref17.BRAND)
                        & (f("p_container") == ref17.CONTAINER)).empty
    ref = ref17.answer(root)
    assert ref == {"avg_yearly": [None]}
    mp = pytest.MonkeyPatch()
    try:
        for mode in ("auto", "fusion-on", "sql"):
            env, sql = MODES[mode]
            got, summary = _run(root, "q17", mp, env, sql)
            assert answers.compare(f"empty {mode}", got, ref, ref17.COMPARE,
                                   RTOL) == 0.0
            assert summary["joins"]["rows_out"] == 0
            assert summary["plan"] == {"repeated_scans": 1 if sql else 2}
    finally:
        mp.undo()
        tracing.reset_for_tests()


def test_the_bfloat16_control_is_refused_for_q17(root):
    """The reference computed in bfloat16, in the engine's place: its last
    division rounds the answer itself, so it lands 1e-4 to 4e-3 away and
    the comparison refuses it at the cell's ``rtol``."""
    ref = ref17.answer(root)
    low = ref17.answer(root, common.bf16)
    err = answers.compare("control q17", low, ref, ref17.COMPARE, math.inf)
    assert RTOL < err < 1e-2
    with pytest.raises(answers.Mismatch):
        answers.compare("control q17", low, ref, ref17.COMPARE, RTOL)


def test_the_cells_rtol_is_this_files():
    import json
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tpch-sf10-part-lookup.json")) as f:
        config = json.load(f)
    assert config["rtol"] == RTOL
    assert config["tables"] == list(TABLES)


# ------------------------------------------- repeated_scans, by its plans

class _Task:
    def __init__(self, *paths):
        self.paths = list(paths)


def _scan(*paths):
    return pp.ScanSource([_Task(p) for p in paths], None)


def _over(*children):
    return pp.PhysicalPlan(list(children), None)


@pytest.mark.parametrize("plan,want", [
    (lambda: _scan("a", "b"), 0),
    (lambda: _over(_scan("a"), _scan("b")), 0),
    # two scans of the same files, and a third of one of them
    (lambda: _over(_scan("a", "b"), _scan("a", "b")), 1),
    (lambda: _over(_over(_scan("a", "b"), _scan("c")),
                   _over(_scan("b"), _scan("c"))), 2),
    # one scan under two consumers is one scan
    (lambda: (lambda s: _over(_over(s), _over(s)))(_scan("a")), 0),
    # a source with no files (in memory, a generator) repeats nothing
    (lambda: _over(pp.InMemorySource([], None), _scan(), _scan()), 0)],
    ids=["one-scan", "two-tables", "same-files-twice", "q17-shaped",
         "shared-node", "no-files"])
def test_repeated_scans_counts_scans_that_reread_files(plan, want):
    assert repeated_scans(plan()) == want
