"""The per-file Parquet footer store (``daft_tpu/io/footers.py``) and the
scan planning over its digest (``readers.make_scan_tasks`` /
``_prune_row_groups``).

Covers: parity of digest pruning with the answers the walk over pyarrow's
``FileMetaData`` gave (frozen below before that walk was deleted),
invalidation by ``(size, mtime_ns)``, that no plan or task list is kept
between queries, the ``footers`` tally on a query's trace, LRU eviction at
the cap, and eight threads planning the same files at once."""

import datetime
import os
import sys
import threading

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import col, lit, tracing
from daft_tpu.io import footers, readers
from daft_tpu.io.scan import GlobScanOperator, Pushdowns
from daft_tpu.logical import plan as lp


@pytest.fixture(autouse=True)
def _empty_store():
    footers.get_store().clear()
    yield
    footers.get_store().clear()


def day(i):
    return datetime.date(2020, 1, 1) + datetime.timedelta(days=i)


# ------------------------------------------------------------ (a) parity

@pytest.fixture(scope="module")
def four_groups(tmp_path_factory):
    """40 rows in 4 row groups of 10: ``i`` = 0..39, ``f`` = 1.5 i, ``d`` =
    2020-01-01 + i days, ``s`` = "k00".."k39", ``n`` nullable (g0 no nulls,
    g1 all nulls, g2 every other, g3 none), ``nostat`` without statistics."""
    n = 40
    t = pa.table({
        "i": pa.array(range(n), pa.int64()),
        "f": pa.array([1.5 * k for k in range(n)], pa.float64()),
        "d": pa.array([day(k) for k in range(n)], pa.date32()),
        "s": pa.array([f"k{k:02d}" for k in range(n)], pa.string()),
        "n": pa.array(list(range(10)) + [None] * 10 + [20, None] * 5
                      + list(range(30, 40)), pa.int64()),
        "nostat": pa.array(range(n), pa.int64()),
    })
    p = str(tmp_path_factory.mktemp("parity") / "t.parquet")
    pq.write_table(t, p, row_group_size=10,
                   write_statistics=["i", "f", "d", "s", "n"])
    return footers.Footer(pq.ParquetFile(p).metadata)


OPS = {
    "lt": lambda c, v: c < v, "le": lambda c, v: c <= v,
    "gt": lambda c, v: c > v, "ge": lambda c, v: c >= v,
    "eq": lambda c, v: c == v,
    "is_in": lambda c, v: c.is_in(list(v) if isinstance(v, tuple) else [v]),
    "is_null": lambda c, v: c.is_null(),
    "not_null": lambda c, v: c.not_null(),
}
ALL = [0, 1, 2, 3]
KEPT = {op: ALL for op in ("lt", "le", "gt", "ge", "eq", "is_in")}
#: (column, literal, {operator: row groups kept}) as ``_prune_row_groups``
#: answered on the ``FileMetaData`` at PR 28's commit
FROZEN = [
    ("i", 15, {"lt": [0, 1], "le": [0, 1], "gt": [1, 2, 3], "ge": [1, 2, 3],
               "eq": [1], "is_in": [1], "is_null": [], "not_null": ALL}),
    ("i", 10, {"lt": [0], "le": [0, 1], "gt": [1, 2, 3], "ge": [1, 2, 3],
               "eq": [1], "is_in": [1]}),
    ("i", 19, {"lt": [0, 1], "le": [0, 1], "gt": [2, 3], "ge": [1, 2, 3],
               "eq": [1], "is_in": [1]}),
    ("i", 40, {"lt": ALL, "le": ALL, "gt": [], "ge": [], "eq": [],
               "is_in": []}),
    ("i", -1, {"lt": [], "le": [], "gt": ALL, "ge": ALL, "eq": [],
               "is_in": []}),
    ("i", (5, 25), {"is_in": [0, 2]}),
    ("f", 22.5, {"lt": [0, 1], "le": [0, 1], "gt": [1, 2, 3],
                 "ge": [1, 2, 3], "eq": [1], "is_in": [1], "is_null": [],
                 "not_null": ALL}),
    ("f", 15.0, {"lt": [0], "le": [0, 1], "gt": [1, 2, 3], "ge": [1, 2, 3],
                 "eq": [1], "is_in": [1]}),
    ("f", 28.5, {"lt": [0, 1], "le": [0, 1], "gt": [2, 3], "ge": [1, 2, 3],
                 "eq": [1], "is_in": [1]}),
    ("f", 100.0, {"lt": ALL, "le": ALL, "gt": [], "ge": [], "eq": [],
                  "is_in": []}),
    ("f", -1.0, {"lt": [], "le": [], "gt": ALL, "ge": ALL, "eq": [],
                 "is_in": []}),
    ("f", (7.5, 37.5), {"is_in": [0, 2]}),
    ("d", day(15), {"lt": [0, 1], "le": [0, 1], "gt": [1, 2, 3],
                    "ge": [1, 2, 3], "eq": [1], "is_in": [1], "is_null": [],
                    "not_null": ALL}),
    ("d", day(10), {"lt": [0], "le": [0, 1], "gt": [1, 2, 3],
                    "ge": [1, 2, 3], "eq": [1], "is_in": [1]}),
    ("d", day(19), {"lt": [0, 1], "le": [0, 1], "gt": [2, 3],
                    "ge": [1, 2, 3], "eq": [1], "is_in": [1]}),
    ("d", day(40), {"lt": ALL, "le": ALL, "gt": [], "ge": [], "eq": [],
                    "is_in": []}),
    ("d", day(-1), {"lt": [], "le": [], "gt": ALL, "ge": ALL, "eq": [],
                    "is_in": []}),
    ("d", (day(5), day(25)), {"is_in": [0, 2]}),
    ("s", "k15", {"lt": [0, 1], "le": [0, 1], "gt": [1, 2, 3],
                  "ge": [1, 2, 3], "eq": [1], "is_in": [1], "is_null": [],
                  "not_null": ALL}),
    ("s", "k10", {"lt": [0], "le": [0, 1], "gt": [1, 2, 3], "ge": [1, 2, 3],
                  "eq": [1], "is_in": [1]}),
    ("s", "k19", {"lt": [0, 1], "le": [0, 1], "gt": [2, 3], "ge": [1, 2, 3],
                  "eq": [1], "is_in": [1]}),
    ("s", "k40", {"lt": ALL, "le": ALL, "gt": [], "ge": [], "eq": [],
                  "is_in": []}),
    ("s", "a", {"lt": [], "le": [], "gt": ALL, "ge": ALL, "eq": [],
                "is_in": []}),
    ("s", ("k05", "k25"), {"is_in": [0, 2]}),
    # null counts; g1 is all nulls and has no min / max, so it is kept
    ("n", 15, {"lt": [0, 1], "le": [0, 1], "gt": [1, 2, 3], "ge": [1, 2, 3],
               "eq": [1], "is_in": [1], "is_null": [1, 2],
               "not_null": [0, 2, 3]}),
    ("n", 20, {"lt": [0, 1], "le": [0, 1, 2], "gt": [1, 3], "ge": [1, 2, 3],
               "eq": [1, 2], "is_in": [1, 2]}),
    ("n", (5, 35), {"is_in": [0, 1, 3]}),
    # no statistics, and a column the file lacks: nothing is pruned
    ("nostat", 15, {**KEPT, "is_null": ALL, "not_null": ALL}),
    ("missing", 15, {**KEPT, "is_null": ALL, "not_null": ALL}),
    # a literal whose type does not compare: the TypeError keeps the group
    ("i", "k15", KEPT), ("s", 15, KEPT), ("d", 15, KEPT), ("f", "x", KEPT),
    ("i", day(4), KEPT),
]


def _frozen_cases():
    for cname, value, by_op in FROZEN:
        for op, kept in by_op.items():
            yield pytest.param(cname, op, value, kept,
                               id=f"{cname}-{op}-{value}".replace(" ", ""))


@pytest.mark.parametrize("cname,op,value,kept", _frozen_cases())
def test_digest_pruning_answers_as_the_metadata_walk_did(
        four_groups, cname, op, value, kept):
    assert readers._prune_row_groups(
        four_groups, OPS[op](col(cname), value)) == kept


@pytest.mark.parametrize("expr,kept", [
    ((col("i") >= 10) & (col("i") < 20), [1]),
    ((col("d") >= day(12)) & (col("f") < 30.0) & col("n").not_null(), []),
    (lit(25) < col("i"), [2, 3]),
    ((col("i") < 5) | (col("i") > 35), None),
    ((col("missing") > 3) & (col("i") > "z") & (col("s") >= "k30"), [3]),
    ((col("i") < 5) & (col("i") > 35), []),
    (None, None)],
    ids=["range", "three-columns", "literal-first", "or-bounds-nothing",
         "missing-and-wrong-type-skipped", "nothing-survives", "no-filter"])
def test_digest_pruning_of_conjunctions(four_groups, expr, kept):
    assert readers._prune_row_groups(four_groups, expr) == kept


def test_digest_holds_plain_values_and_the_totals(four_groups):
    f = four_groups
    assert f.group_rows == [10] * 4 and f.num_rows == 40
    assert f.total_bytes == sum(f.group_bytes) == sum(
        f.metadata.row_group(g).total_byte_size for g in range(4))
    assert f.columns["d"][1] == (True, day(10), day(19), 0)
    assert f.columns["n"][1] == (False, None, None, 10)
    assert f.columns["nostat"] == [None] * 4
    assert "missing" not in f.columns


# ------------------------------------------------------ (b) invalidation

def _write(path, values, **kw):
    pq.write_table(pa.table({"x": pa.array(values, pa.int64())}), path,
                   compression="none", use_dictionary=False, **kw)


def _over_100(pattern):
    return sorted(dt.read_parquet(pattern).where(col("x") > 100)
                  .to_pydict()["x"])


def test_a_file_rewritten_in_place_is_planned_from_its_new_footer(tmp_path):
    p = str(tmp_path / "a.parquet")
    _write(p, range(50))
    assert _over_100(p) == []          # pruned by max 49, and stored
    held = footers.footer(p)
    # another size
    _write(p, range(150))
    assert _over_100(p) == list(range(101, 150))
    assert footers.footer(p) is not held
    # the same size, other rows, another mtime_ns
    st = os.stat(p)
    held = footers.footer(p)
    _write(p, range(1000, 1150))
    assert os.stat(p).st_size == st.st_size
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
    assert _over_100(p) == list(range(1000, 1150))
    assert footers.footer(p) is not held
    assert len(footers.get_store()) == 1
    # unchanged: held
    assert footers.footer(p) is footers.footer(p)


def test_a_file_replaced_after_read_parquet_is_seen_at_collect(tmp_path):
    p = str(tmp_path / "a.parquet")
    _write(p, range(50))
    df = dt.read_parquet(p).where(col("x") > 100)
    _write(p, range(80, 130))
    assert sorted(df.to_pydict()["x"]) == list(range(101, 130))


def test_a_deleted_file_fails_as_before(tmp_path):
    for i in range(2):
        _write(str(tmp_path / f"p{i}.parquet"), range(i * 200, i * 200 + 10))
    pattern = str(tmp_path / "*.parquet")
    assert _over_100(pattern) == list(range(200, 210))
    df = dt.read_parquet(pattern).where(col("x") > 100)
    os.remove(str(tmp_path / "p1.parquet"))
    with pytest.raises(FileNotFoundError):
        df.to_pydict()


# ------------------------------------------------------ (c) no plan cache

def _sources(df):
    found = []

    def see(node):
        if isinstance(node, lp.Source):
            found.append(node)
        return node
    df._builder.optimize().plan.transform_up(see)
    return found


def test_each_query_builds_its_own_tasks_from_the_same_entries(tmp_path):
    for i in range(3):
        pq.write_table(pa.table({"x": list(range(i * 40, i * 40 + 40))}),
                       str(tmp_path / f"p{i}.parquet"), row_group_size=10)
    pattern = str(tmp_path / "*.parquet")
    low, = _sources(dt.read_parquet(pattern).where(col("x") < 15))
    entries = {p: footers.footer(p) for p in sorted(
        str(tmp_path / f"p{i}.parquet") for i in range(3))}
    high, = _sources(dt.read_parquet(pattern).where(col("x") >= 95))
    again, = _sources(dt.read_parquet(pattern).where(col("x") < 15))
    assert [t.row_groups for t in low.materialized_tasks] == \
        [[[0, 1]], [[]], [[]]]
    assert [t.row_groups for t in high.materialized_tasks] == \
        [[[]], [[]], [[1, 2, 3]]]
    assert [t._num_rows for t in high.materialized_tasks] == [0, 0, 30]
    # the same filter again: equal tasks, but built anew
    assert [t.row_groups for t in again.materialized_tasks] == \
        [t.row_groups for t in low.materialized_tasks]
    assert again.materialized_tasks is not low.materialized_tasks
    assert not {id(t) for t in again.materialized_tasks} \
        & {id(t) for t in low.materialized_tasks}
    # all three planned from the entries the first query stored
    for src in (low, high, again):
        for t in src.materialized_tasks:
            assert t.pq_metadata is entries[t.paths[0]].metadata
    assert len(footers.get_store()) == 3


# ------------------------------------------------------- (d) the counters

def _traced(monkeypatch, df):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    n = len(tracing.finished())
    out = df.to_pydict()
    done = tracing.finished()
    assert len(done) == n + 1
    return out, done[-1]


def test_the_trace_counts_footers_read_and_from_store(tmp_path, monkeypatch):
    for i in range(5):
        _write(str(tmp_path / f"p{i}.parquet"), range(i * 10, i * 10 + 10))
    pattern = str(tmp_path / "*.parquet")
    schema = dt.read_parquet(pattern).schema()
    footers.get_store().clear()
    # with the schema given nothing is read before the query is planned
    df = dt.read_parquet(pattern, schema=schema).where(col("x") >= 25)
    out, first = _traced(monkeypatch, df)
    assert sorted(out["x"]) == list(range(25, 50))
    assert first["footers"] == {"from_store": 0, "read": 5}
    # inferring the schema reads the first file's footer through the
    # store too, outside any trace
    df = dt.read_parquet(pattern).where(col("x") < 25)
    out, second = _traced(monkeypatch, df)
    assert sorted(out["x"]) == list(range(25))
    assert second["footers"] == {"from_store": 5, "read": 0}
    footers.get_store().clear()
    _, third = _traced(monkeypatch, dt.read_parquet(pattern))
    assert third["footers"] == {"from_store": 1, "read": 4}


def test_the_counts_are_on_the_optimize_span(tmp_path, monkeypatch):
    for i in range(3):
        _write(str(tmp_path / f"p{i}.parquet"), range(10))
    df = dt.read_parquet(str(tmp_path / "*.parquet"))
    seen = []
    add = tracing.SpanRecorder.add

    def spy(self, name, *a, **kw):
        if name == "plan:optimize":
            seen.append(kw.get("attrs"))
        return add(self, name, *a, **kw)
    monkeypatch.setattr(tracing.SpanRecorder, "add", spy)
    _traced(monkeypatch, df)
    assert seen == [{"footers_from_store": 1, "footers_read": 2,
                     "files_planned": 3, "file_stats": 3}]


def test_a_scan_of_no_file_tallies_nothing(monkeypatch):
    df = dt.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1)
    out, summary = _traced(monkeypatch, df)
    assert out["x"] == [2, 3]
    assert summary["footers"] == {"from_store": 0, "read": 0}
    assert len(footers.get_store()) == 0


# ------------------------------------------------------------ (e) the cap

def test_the_cap_evicts_the_least_recently_used(tmp_path, monkeypatch):
    paths = []
    for name in "abc":
        paths.append(str(tmp_path / f"{name}.parquet"))
        _write(paths[-1], range(10))
    a, b, c = paths
    reads = []
    real = pq.ParquetFile
    monkeypatch.setattr(footers.pq, "ParquetFile",
                        lambda p, *x, **kw: reads.append(p) or real(p, *x, **kw))
    store = footers.FooterStore(max_entries=2)
    store.get(a)
    store.get(b)
    store.get(a)                     # a is now the more recently used
    store.get(c)                     # b goes
    assert reads == [a, b, c] and len(store) == 2
    store.get(a)
    store.get(c)
    assert reads == [a, b, c]
    store.get(b)                     # read again; a goes (c was used last)
    assert reads == [a, b, c, b] and len(store) == 2
    store.get(a)
    assert reads == [a, b, c, b, a]
    assert footers.MAX_ENTRIES >= 1024


# ----------------------------------------------------- (f) eight threads

def test_eight_threads_plan_the_same_files_at_once(tmp_path):
    n_files, n_threads = 64, 8
    for i in range(n_files):
        pq.write_table(pa.table({"x": list(range(i * 20, i * 20 + 20))}),
                       str(tmp_path / f"p{i:02d}.parquet"), row_group_size=5)
    op = GlobScanOperator(str(tmp_path / "*.parquet"), "parquet")
    footers.get_store().clear()
    pushdowns = Pushdowns(filters=(col("x") >= 7) & (col("x") < 1000))
    barrier = threading.Barrier(n_threads)
    results, errors = [None] * n_threads, []

    def plan(k):
        try:
            barrier.wait(timeout=30)
            tasks = op.to_scan_tasks(pushdowns)
            results[k] = [(tuple(t.paths), t.row_groups, t._num_rows,
                           t.size_bytes()) for t in tasks]
        except BaseException as exc:  # reported below, on the main thread
            errors.append(exc)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=plan, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(footers.get_store()) == n_files
    assert all(r == results[0] for r in results) and results[0]
    assert sum(r[2] for r in results[0]) == 50 * 20 - 7 + 2  # whole groups
