"""One identity read a file a query (``io/footers.identities``): the
listing stats nothing, a scan's files are stat-ed once and together when
its tasks are made, the identity travels on the ``ScanTask`` and
``device/cache.task_fingerprint`` reads it.

Covers: (a) the count of stat-like calls on the data files of a query,
(b) ``glob_paths`` against the expression it replaced, (c) a file
rewritten between ``read_parquet`` and ``collect()`` or between two
queries derived from one ``read_parquet``, (d) the fingerprint's value and
a merged task's identities, (e) a path that vanishes before the batch."""

import collections
import datetime
import glob
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import col, lit, tracing
from daft_tpu.device import cache as dcache
from daft_tpu.io import footers, scan
from daft_tpu.io.scan import GlobScanOperator, Pushdowns, ScanTask


@pytest.fixture(autouse=True)
def _empty_stores():
    footers.get_store().clear()
    dcache.get_cache().clear()
    yield
    footers.get_store().clear()
    dcache.get_cache().clear()


# ------------------------------------------- (a) stat-like calls a query

N_FILES = 16


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    root = tmp_path_factory.mktemp("li")
    rng = np.random.default_rng(7)
    n = 5000
    for i in range(N_FILES):
        days = rng.integers(0, 1500, n)
        pq.write_table(pa.table({
            "l_returnflag": rng.choice(["A", "N", "R"], n),
            "l_linestatus": rng.choice(["F", "O"], n),
            "l_quantity": rng.uniform(1, 50, n),
            "l_extendedprice": rng.uniform(1, 1e4, n),
            "l_discount": rng.uniform(0, .1, n),
            "l_tax": rng.uniform(0, .08, n),
            "l_shipdate": pa.array([datetime.date(1995, 1, 1)
                                    + datetime.timedelta(days=int(d))
                                    for d in days]),
        }), str(root / f"p{i:02d}.parquet"))
    return str(root / "*.parquet")


def q1(pattern, schema=None):
    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    return (dt.read_parquet(pattern, schema=schema)
            .where(col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
            .groupby("l_returnflag", "l_linestatus")
            .agg(col("l_quantity").sum().alias("sum_qty"),
                 disc_price.sum().alias("sum_disc_price"),
                 (disc_price * (1 + col("l_tax"))).sum().alias("sum_charge"),
                 col("l_discount").mean().alias("avg_disc"),
                 col("l_quantity").count().alias("count_order"))
            .sort(["l_returnflag", "l_linestatus"]))


@pytest.fixture
def stat_calls(monkeypatch):
    """Counts ``os.stat``, ``os.path.isfile`` / ``exists`` / ``getsize``
    on ``*.parquet`` paths, by name."""
    counts = collections.Counter()

    def counted(mod, name):
        real = getattr(mod, name)

        def call(p, *a, **kw):
            if str(p).endswith(".parquet"):
                counts[name] += 1
            return real(p, *a, **kw)
        monkeypatch.setattr(mod, name, call)
    counted(os, "stat")
    for name in ("isfile", "exists", "getsize"):
        counted(os.path, name)
    return counts


@pytest.mark.parametrize("inflight", ["0", "2"])
@pytest.mark.parametrize("run", ["cold-first", "resident-second"])
def test_a_query_stats_each_data_file_once(lineitem, stat_calls, monkeypatch,
                                           run, inflight):
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", inflight)
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    schema = dt.read_parquet(lineitem).schema()
    if run == "resident-second":
        first = q1(lineitem, schema).to_pydict()
    else:
        footers.get_store().clear()
    stat_calls.clear()
    out = q1(lineitem, schema).to_pydict()      # build + collect()
    assert sum(stat_calls.values()) == N_FILES, dict(stat_calls)
    assert set(stat_calls) == {"stat"}
    summary = tracing.finished()[-1]
    assert summary["files"] == {"planned": N_FILES, "stats": N_FILES}
    if run == "resident-second":
        assert out == first
        assert summary["tables"]["from_cache"] == N_FILES
        assert summary["footers"] == {"from_store": N_FILES, "read": 0}
    else:
        assert summary["tables"]["from_cache"] == 0
        assert summary["footers"] == {"from_store": 0, "read": N_FILES}
    # inferring the schema costs the builder one more, on the first file
    stat_calls.clear()
    q1(lineitem)
    assert dict(stat_calls) == {"stat": 1}


# ----------------------------------------------------- (b) the listing

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("tree")
    for rel in ("a1.parquet", "a2.parquet", "b10.parquet", ".hid.parquet",
                "note.txt", "sub/c1.parquet", "sub/deep/d1.parquet",
                "sub2/e1.parquet", "dir.parquet/inner.parquet"):
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(b"x")
    os.symlink(str(root / "a1.parquet"), str(root / "link.parquet"))
    os.symlink(str(root / "nowhere"), str(root / "dangling.parquet"))
    os.symlink(str(root / "sub"), str(root / "dirlink.parquet"))
    return str(root)


def _as_before(pattern):
    return sorted(m for m in glob.glob(pattern, recursive=True)
                  if os.path.isfile(m))


@pytest.mark.parametrize("pattern,n", [
    ("{r}/*.parquet", 4),        # dir.parquet/, dirlink, dangling, .hid out
    ("{r}/.*.parquet", 1),
    ("{r}/link*", 1),
    ("{r}/dangling*", 0),
    ("{r}/**/*.parquet", None),   # glob follows the directory symlink
    ("{r}/**", None),
    ("{r}/a[0-9].parquet", 2),
    ("{r}/b[0-9][0-9].par?uet", 1),
    ("{r}/sub*/*.parquet", 2),
    ("{r}/s*/**/d*.parquet", 1),
    ("{r}/*/*.parquet", None),
    ("{r}/s*/c1.parquet", 1),
    ("{r}/a1.parquet/*.parquet", 0),
    ("file://{r}/*.parquet", 4)],
    ids=["subdir-matching", "dot-file", "symlink-to-file",
         "dangling-symlink", "recursive", "recursive-tail", "class",
         "class-and-mark", "magic-directory", "recursive-inside",
         "star-directory", "plain-tail",
         "file-as-directory", "file-scheme"])
def test_glob_paths_lists_what_glob_and_isfile_listed(tree, pattern, n,
                                                      monkeypatch):
    p = pattern.format(r=tree)
    expected = _as_before(p[7:] if p.startswith("file://") else p)
    assert n is None or len(expected) == n
    if not expected:
        with pytest.raises(FileNotFoundError):
            scan.glob_paths(p)
        return
    assert scan.glob_paths(p) == expected
    # relative to the working directory too
    monkeypatch.chdir(tree)
    rel = pattern.format(r=".")
    if not rel.startswith("file://"):
        assert scan.glob_paths(rel) == _as_before(rel)
        assert scan.glob_paths(rel[2:]) == _as_before(rel[2:])


def test_the_listing_stats_no_regular_file(tree, stat_calls):
    found = scan.glob_paths(tree + "/a*.parquet")
    assert len(found) == 2 and not stat_calls


# ------------------------------------------------- (c) a file rewritten

def _write(path, values):
    pq.write_table(pa.table({"x": pa.array(values, pa.int64())}), path,
                   compression="none", use_dictionary=False)


def _rewrite(path, how):
    st = os.stat(path)
    if how == "other-size":
        _write(path, range(1000, 5000 + 4096 + 500))
        assert os.stat(path).st_size != st.st_size
    else:
        _write(path, range(1000, 1000 + 5000))
        assert os.stat(path).st_size == st.st_size
        os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))


def _sum_query(df, pushed):
    """A fused scan aggregate: with ``pushed`` the optimizer rebuilds the
    ``Source`` (a filter goes down into it), without it the plan's
    ``Source`` is the ``DataFrame``'s own node."""
    if pushed:
        df = df.where(col("x") >= 0)
    return df.agg(col("x").sum().alias("s"), col("x").count().alias("n"))


def _traced_collect(df):
    n = len(tracing.finished())
    out = df.to_pydict()
    done = tracing.finished()
    assert len(done) == n + 1
    return (out["s"][0], out["n"][0]), done[-1]


@pytest.mark.parametrize("how", ["other-size", "same-size-later-mtime"])
@pytest.mark.parametrize("when", ["before-collect", "between-pushed",
                                  "between-own-source"])
def test_a_rewritten_file_is_read_anew(tmp_path, monkeypatch, when, how):
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    p = str(tmp_path / "a.parquet")
    _write(p, range(5000))
    old = (sum(range(5000)), 5000)
    base = dt.read_parquet(p)
    pushed = when != "between-own-source"
    if when != "before-collect":
        # an earlier query derived from the same read_parquet: it leaves
        # the footer in the store and the columns in the HBM cache
        got, _ = _traced_collect(_sum_query(base, pushed))
        assert got == old
        # which Source the query ran on: the DataFrame's own node keeps
        # the task list of a query whose rules did not rebuild it
        assert ("materialized_tasks" in base._builder._plan.__dict__) \
            == (not pushed)
        got, again = _traced_collect(_sum_query(base, pushed))
        assert got == old
        assert again["footers"] == {"from_store": 1, "read": 0}
        assert again["tables"]["from_cache"] == 1
    query = _sum_query(base, pushed)
    _rewrite(p, how)
    new = pq.read_table(p)["x"].to_pylist()
    got, summary = _traced_collect(query)
    assert got == (sum(new), len(new))
    assert summary["footers"] == {"from_store": 0, "read": 1}
    assert summary["tables"] == {"from_cache": 0, "encoded": 1, "host": 0}
    assert summary["files"] == {"planned": 1, "stats": 1}


def test_no_task_list_outlives_its_query(tmp_path):
    from daft_tpu.logical import plan as lp
    p = str(tmp_path / "a.parquet")
    _write(p, range(100))
    df = dt.read_parquet(p)
    src = df._builder._plan
    assert isinstance(src, lp.Source)
    assert _sum_query(df, False).to_pydict() == {"s": [4950], "n": [100]}
    first = src.materialized_tasks     # the DataFrame's own node kept it
    assert _sum_query(df, False).to_pydict() == {"s": [4950], "n": [100]}
    assert src.materialized_tasks is not first
    assert src.materialized_tasks[0] is not first[0]


# ------------------------------------------------ (d) the fingerprint

def _tasks(tmp_path, n=3):
    paths = []
    for i in range(n):
        paths.append(str(tmp_path / f"p{i}.parquet"))
        _write(paths[-1], range(i * 10, i * 10 + 10))
    op = GlobScanOperator(str(tmp_path / "*.parquet"), "parquet")
    return paths, op.to_scan_tasks(Pushdowns())


def _bare(task):
    t = ScanTask(task.paths, task.file_format, task.schema, task.pushdowns,
                 task._num_rows, task._size_bytes, task.row_groups)
    assert t.identities is None
    return t


def test_the_fingerprint_is_the_one_a_stat_gives(tmp_path, stat_calls):
    paths, (merged,) = _tasks(tmp_path)
    # three small files merge into one task, which keeps three identities
    assert merged.paths == paths
    assert merged.identities == [
        (os.stat(p).st_size, os.stat(p).st_mtime_ns) for p in paths]
    stat_calls.clear()
    carried = dcache.task_fingerprint(merged)
    assert not stat_calls
    assert carried == dcache.task_fingerprint(_bare(merged))
    # an exists and a stat a file (``os.path.exists`` is itself counted
    # once more, as the ``os.stat`` it makes)
    assert stat_calls == {"exists": 3, "stat": 6}
    assert [s[0] for s in carried[0]] == paths


def test_split_tasks_keep_their_file_s_identity(tmp_path):
    p = str(tmp_path / "big.parquet")
    pq.write_table(pa.table({"x": list(range(4000))}), p, row_group_size=1000)
    (whole,) = GlobScanOperator(p, "parquet").to_scan_tasks(Pushdowns())
    rg_bytes = whole.pq_metadata.row_group(0).total_byte_size
    parts = scan.split_scan_tasks([whole], rg_bytes * 2, 8)
    assert len(parts) == 2
    st = os.stat(p)
    for t in parts:
        assert t.identities == [(st.st_size, st.st_mtime_ns)]
        assert dcache.task_fingerprint(t) == \
            dcache.task_fingerprint(_bare(t))


def test_a_task_that_carries_none_is_stat_ed_and_tallied(tmp_path,
                                                         monkeypatch):
    paths, (merged,) = _tasks(tmp_path)
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    ctx = tracing.maybe_start_trace("query")
    assert ctx is not None
    try:
        with tracing.attach(ctx):
            assert dcache.task_fingerprint(merged) is not None
            assert tracing.file_counts() == {"planned": 0, "stats": 0}
            assert dcache.task_fingerprint(_bare(merged)) is not None
            assert tracing.file_counts() == {"planned": 0, "stats": 6}
    finally:
        tracing.abort_trace(ctx)


def test_csv_tasks_carry_the_identity_and_its_size(tmp_path):
    p = str(tmp_path / "t.csv")
    with open(p, "w") as f:
        f.write("x\n1\n2\n3\n")
    df = dt.read_csv(p)
    (task,) = GlobScanOperator(p, "csv").to_scan_tasks(Pushdowns())
    st = os.stat(p)
    assert task.identities == [(st.st_size, st.st_mtime_ns)]
    assert task.size_bytes() == st.st_size
    assert df.to_pydict() == {"x": [1, 2, 3]}


# ------------------------------------------------ (e) a vanished path

def test_identities_of_local_remote_and_vanished_paths(tmp_path):
    paths = []
    for i in range(20):
        paths.append(str(tmp_path / f"p{i:02d}.parquet"))
        _write(paths[-1], range(i + 1))
    gone = str(tmp_path / "gone.parquet")
    asked = paths[:7] + [gone, "s3://bucket/key.parquet"] + paths[7:]
    got = footers.identities(asked)
    assert got[7] is None and got[8] is None
    assert got[:7] + got[9:] == [
        (os.stat(p).st_size, os.stat(p).st_mtime_ns) for p in paths]
    assert footers.identities([]) == []
    assert footers.identities([gone]) == [None]


def test_a_path_that_vanishes_before_the_batch_raises_as_before(tmp_path):
    for i in range(2):
        _write(str(tmp_path / f"p{i}.parquet"), range(i * 200, i * 200 + 10))
    df = dt.read_parquet(str(tmp_path / "*.parquet")).where(col("x") > 100)
    assert sorted(df.to_pydict()["x"]) == list(range(200, 210))
    # built before the file goes, collected after (a DataFrame keeps a
    # result it has collected, so a new one over the same listing)
    df = dt.read_parquet(str(tmp_path / "*.parquet")).where(col("x") > 100)
    os.remove(str(tmp_path / "p1.parquet"))
    with pytest.raises(FileNotFoundError):
        df.to_pydict()
    op = GlobScanOperator(str(tmp_path / "p0.parquet"), "parquet")
    os.remove(str(tmp_path / "p0.parquet"))
    (task,) = op.to_scan_tasks(Pushdowns())
    assert task.identities is None
    assert dcache.task_fingerprint(task) is None
