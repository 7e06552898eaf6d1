"""A filtered Parquet scan that ends in rows, its filter run on the device
over the scan's encoded tables (``executor._scan_select``), small and on
the CPU: what the deployment ``tpch-sf10-star`` (cell
``tpch-sf10.star-revenue``) leans on.

Covers: (a) TPC-H Q14, Q19 and Q6 through ``read_parquet(...).to_pydict()``
against the benchmark's plain references with the selection forced on,
forced off and left to the gate; (b) the selection alone against pyarrow's
filtering of the same files; (c) an overflow of the ladder's first rung and
of its ceiling; (d) the HBM column cache across runs and a rewritten file;
(e) the fused aggregate over the resolver it now shares with the selection;
(f) the gate's answers and the footer's estimate of the survivors; (g) the
packed block's layout; (h) the compaction's search; (i) the output stage:
the survivors brought back by gathers of 128-lane rows against element
gathers and numpy, bit for bit, and which shapes take which."""

import datetime
import importlib
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import daft_tpu  # noqa: E402
from chipbench import answers, datagen  # noqa: E402
from daft_tpu import col, lit, tracing  # noqa: E402
from daft_tpu.device import cache as dcache, column as dcol  # noqa: E402
from daft_tpu.device import costmodel as cm, fragment, runtime  # noqa: E402
from daft_tpu.io import readers  # noqa: E402

#: ``DAFT_TPU_FUSION`` for each way a filtered scan's table can be answered
MODES = {"forced-on": "1", "forced-off": "0", "auto": None}
N_FILES = 16


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.delenv("DAFT_TPU_DEVICE", raising=False)
    monkeypatch.delenv("DAFT_TPU_DEVICE_FORCE", raising=False)
    dcache.get_cache().clear()
    tracing.reset_for_tests()
    yield
    dcache.get_cache().clear()


def set_mode(monkeypatch, mode):
    if MODES[mode] is None:
        monkeypatch.delenv("DAFT_TPU_FUSION", raising=False)
    else:
        monkeypatch.setenv("DAFT_TPU_FUSION", MODES[mode])


def failures():
    return sum(v["count"] for v in runtime.device_failures().values())


def last_summary():
    return tracing.finished()[-1]


# ------------------------------- (a) the cell's queries, every mode

@pytest.fixture(scope="module")
def star(tmp_path_factory):
    return datagen.ensure_dataset(
        str(tmp_path_factory.mktemp("tpch_star")), "t", 0.01, N_FILES,
        ["part", "lineitem"], 2**31 + 41, 1)


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("q", ["q14", "q19", "q6"])
def test_star_queries_against_reference(star, monkeypatch, q, mode):
    set_mode(monkeypatch, mode)
    before = failures()
    build = importlib.import_module(f"chipbench.queries.{q}").build
    ref = importlib.import_module(f"chipbench.reference.{q}")
    got = build(lambda t: daft_tpu.read_parquet(
        f"{star}/{t}/*.parquet")).to_pydict()
    # the CPU keeps float64: the selection's rows are the file's values
    err = answers.compare(f"{q} {mode}", got, ref.answer(star), ref.COMPARE,
                          1e-12)
    assert err <= 1e-12
    assert failures() == before
    sel = last_summary()["selects"]
    if q == "q6":       # ends in an aggregate: not a selection
        assert sel["tables_device"] == sel["tables_host"] == 0
    elif mode == "forced-on":
        # lineitem's 16 tables and part's 16 (``not_null(p_partkey)``)
        assert (sel["tables_device"], sel["tables_host"]) == (2 * N_FILES, 0)
        assert sel["rows_out_device"] == sel["rows_out"]
    elif mode == "forced-off":
        assert (sel["tables_device"], sel["tables_host"]) == (0, 2 * N_FILES)
    else:
        assert sel["tables_device"] + sel["tables_host"] == 2 * N_FILES
    if q != "q6":
        assert 0 < sel["rows_out"] < sel["rows_in"]
    # SF0.01's tables sit in the 4 096 bucket, which does not split into
    # blocks of 128 x 128 rows: element gathers, whoever filtered
    assert sel["tables_row_gather"] == 0


# ------------------------------------- (b) the selection alone

INSTRUCTS = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
MODES_OF_SHIPPING = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
DAY0 = datetime.date(1995, 1, 1)


def write_files(root, n_files=4, rows=3000, seed=11):
    """Files whose string dictionaries differ: file ``i`` lacks the
    ``i``-th instruction and the ``i``-th mode; ``qty`` holds nulls."""
    rng = np.random.default_rng(seed)
    for i in range(n_files):
        instr = [s for k, s in enumerate(INSTRUCTS) if k != i % 4]
        modes = [s for k, s in enumerate(MODES_OF_SHIPPING) if k != i % 7]
        qty = rng.integers(1, 51, rows).astype(np.float64)
        pq.write_table(pa.table({
            "key": pa.array(rng.integers(0, 10**12, rows), pa.int64()),
            "price": rng.uniform(900, 105000, rows),
            "qty": pa.array(qty, mask=rng.random(rows) < 0.1),
            "ship": pa.array([DAY0 + datetime.timedelta(days=int(d))
                              for d in rng.integers(0, 2000, rows)]),
            "instruct": rng.choice(instr, rows),
            "mode": rng.choice(modes, rows),
        }), os.path.join(root, f"p{i:02d}.parquet"))
    return os.path.join(root, "*.parquet")


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    return write_files(str(tmp_path_factory.mktemp("select")))


f = pc.field
#: name -> (the engine's predicate, pyarrow's)
PREDICATES = {
    "date-range": ((col("ship") >= lit(datetime.date(1996, 3, 1)))
                   & (col("ship") < lit(datetime.date(1996, 4, 1))),
                   (f("ship") >= datetime.date(1996, 3, 1))
                   & (f("ship") < datetime.date(1996, 4, 1))),
    "string-eq": (col("instruct") == "DELIVER IN PERSON",
                  f("instruct") == "DELIVER IN PERSON"),
    "string-is-in": (col("mode").is_in(["AIR", "AIR REG", "TRUCK"])
                     & (col("instruct") == "NONE"),
                     f("mode").isin(["AIR", "AIR REG", "TRUCK"])
                     & (f("instruct") == "NONE")),
    "nulls-in-predicate-column": (col("qty") < 10, f("qty") < 10),
    # (inside every file's min..max, so no row group is pruned away)
    "empty": (col("instruct") == "NO SUCH INSTRUCTION",
              f("instruct") == "NO SUCH INSTRUCTION"),
    "all-survive": (col("ship") >= lit(DAY0), f("ship") >= DAY0),
}


def arrow_answer(pattern, keep, columns=None):
    import glob
    t = pa.concat_tables([pq.read_table(p).filter(keep)
                          for p in sorted(glob.glob(pattern))])
    t = t.select(columns) if columns else t
    return {n: t.column(n).to_pylist() for n in t.column_names}


@pytest.mark.parametrize("name", list(PREDICATES))
def test_selection_alone_against_pyarrow(files, monkeypatch, name):
    set_mode(monkeypatch, "forced-on")
    pred, keep = PREDICATES[name]
    before = failures()
    got = daft_tpu.read_parquet(files).where(pred).to_pydict()
    want = arrow_answer(files, keep)
    assert list(got) == list(want)
    # keys, dates, strings and (on the CPU, which keeps float64) floats
    # exact, in source order
    assert got == want
    sel = last_summary()["selects"]
    assert (sel["tables_device"], sel["tables_host"]) == (4, 0)
    assert sel["rows_in"] == 4 * 3000
    assert sel["rows_out"] == len(want["key"])
    assert failures() == before


def test_projection_above_the_scan_rides_the_program(files, monkeypatch):
    set_mode(monkeypatch, "forced-on")
    pred, keep = PREDICATES["string-eq"]
    got = (daft_tpu.read_parquet(files).where(pred)
           .select(col("key"), (col("price") * 2).alias("twice"),
                   col("mode")).to_pydict())
    want = arrow_answer(files, keep, ["key", "price", "mode"])
    assert got["key"] == want["key"] and got["mode"] == want["mode"]
    assert got["twice"] == [2 * p for p in want["price"]]
    assert last_summary()["selects"]["tables_device"] == 4


def test_rows_leave_in_the_device_encoding_without_float64(
        files, monkeypatch):
    """A chip without float64: a float column comes back as the float32
    rounding of the file's values; keys, dates and strings exact."""
    monkeypatch.setattr(dcol, "supports_f64", lambda: False)
    set_mode(monkeypatch, "forced-on")
    pred = col("ship") < lit(datetime.date(1995, 7, 7))
    got = daft_tpu.read_parquet(files).where(pred).to_pydict()
    want = arrow_answer(files, f("ship") < datetime.date(1995, 7, 7))
    for name in ("key", "ship", "instruct", "mode"):
        assert got[name] == want[name]
    assert got["price"] == [float(np.float32(p)) for p in want["price"]]
    assert got["price"] != want["price"]
    assert last_summary()["selects"]["tables_device"] == 4


# --------------------------------------------- (c) the ladder's overflows

def test_first_rung_overflow_redispatches(files, monkeypatch):
    """No table of this predicate has run and the footer bounds no string:
    the first rung is a quarter of the bucket, 3/4 of the rows survive."""
    set_mode(monkeypatch, "forced-on")
    before = failures()
    got = daft_tpu.read_parquet(files).where(
        col("instruct") != "TAKE BACK RETURN").to_pydict()
    want = arrow_answer(files, f("instruct") != "TAKE BACK RETURN")
    assert got == want
    sel = last_summary()["selects"]
    # (the planner may merge small files into one task: a table a task)
    assert sel["overflows"] >= 1
    assert sel["tables_device"] >= 1 and sel["tables_host"] == 0
    # a quarter of the bucket and then all of it: both too wide for rows
    assert sel["tables_row_gather"] == 0
    assert failures() == before
    # the rung is learned: the next scan overflows nowhere
    daft_tpu.read_parquet(files).where(
        col("instruct") != "TAKE BACK RETURN").to_pydict()
    assert last_summary()["selects"]["overflows"] == 0


def test_row_gathers_engage_where_the_bucket_splits_into_blocks(
        tmp_path, monkeypatch):
    """A 12 000-row table (the 16 384 bucket, which splits into blocks of
    128 x 128 rows) under a date range that keeps ~1.5%: the footer's
    min / max bound the share, so the first rung is 256 slots and the
    program brings its survivors back by row gathers, as its dispatch
    span and the tally say; a filter that keeps half the rows at the same
    bucket (rung 8 192: too wide) keeps the element gathers."""
    pattern = write_files(str(tmp_path), n_files=2, rows=12000, seed=7)
    set_mode(monkeypatch, "forced-on")
    before = failures()
    kept = []
    real = tracing.SpanRecorder.finish
    monkeypatch.setattr(tracing.SpanRecorder, "finish", lambda self, *a: (
        real(self, *a), kept.append(self.spans()))[0])

    def gathers():
        return sorted((s["attrs"]["capacity"], s["attrs"]["gather"])
                      for s in kept[-1] if s["name"] == "device:dispatch"
                      and s["attrs"].get("program") == "region")

    pred, keep = PREDICATES["date-range"]
    want = arrow_answer(pattern, keep)
    assert 100 < len(want["key"]) < 2 * 256
    for _ in range(2):      # from the footer's estimate, then as learned
        assert daft_tpu.read_parquet(pattern).where(pred).to_pydict() == want
        sel = last_summary()["selects"]
        assert (sel["tables_device"], sel["tables_row_gather"]) == (2, 2)
        assert sel["overflows"] == 0
        assert gathers() == [(16384, "rows")] * 2
    half = col("ship") >= lit(DAY0 + datetime.timedelta(days=1000))
    got = daft_tpu.read_parquet(pattern).where(half).to_pydict()
    assert got == arrow_answer(
        pattern, f("ship") >= DAY0 + datetime.timedelta(days=1000))
    sel = last_summary()["selects"]
    assert (sel["tables_device"], sel["tables_row_gather"]) == (2, 0)
    assert set(gathers()) == {(16384, "elements")}
    assert failures() == before


def test_ceiling_overflow_rereads_on_the_host(files, monkeypatch):
    """The gate takes every table and prices no more than 128 survivors
    worth fetching: every table overflows the ceiling and is re-read from
    its pristine task by the reader."""
    set_mode(monkeypatch, "auto")
    monkeypatch.setattr(cm, "select_wins", lambda *a, **k: True)
    monkeypatch.setattr(cm, "select_max_rows", lambda *a, **k: 100)
    before = failures()
    pred = col("mode") != "SHIP"
    got = daft_tpu.read_parquet(files).where(pred).to_pydict()
    assert got == arrow_answer(files, f("mode") != "SHIP")
    s = last_summary()
    sel = s["selects"]
    assert sel["overflows"] == sel["tables_host"] >= 1
    assert sel["tables_device"] == 0
    assert s["tables"]["host"] == 0     # resolved for the device, all
    assert sel["rows_in"] == 4 * 3000
    assert sel["rows_out"] == len(got["key"])
    assert failures() == before


def test_auto_learns_the_share_from_the_readers_scan(files, monkeypatch):
    """Nothing bounds a string test, so the first scan is the reader's;
    what it finds is the gate's bet for the next one, with or without a
    projection above the scan."""
    set_mode(monkeypatch, "auto")
    # a host so slow that any known share wins the device the table
    monkeypatch.setattr(cm, "HOST_SELECT_VALUES_PER_S", 1e3)
    pred = col("mode") == "RAIL"
    want = arrow_answer(files, f("mode") == "RAIL")
    first = daft_tpu.read_parquet(files).where(pred).to_pydict()
    sel = last_summary()["selects"]
    assert sel["tables_device"] == 0 and sel["tables_host"] >= 1
    assert first == want
    second = daft_tpu.read_parquet(files).where(pred).to_pydict()
    sel = last_summary()["selects"]
    assert sel["tables_device"] >= 1 and sel["tables_host"] == 0
    assert sel["overflows"] == 0    # the first rung came from the share
    assert second == want
    third = daft_tpu.read_parquet(files).where(pred).select(
        "key", (col("qty") + 1).alias("more")).to_pydict()
    assert last_summary()["selects"]["tables_device"] >= 1
    assert third["key"] == want["key"]


# ------------------------------------------- (d) the HBM column cache

def test_second_run_is_resident_and_a_rewritten_file_is_read_anew(
        tmp_path, monkeypatch):
    pattern = write_files(str(tmp_path), n_files=N_FILES, rows=1500,
                          seed=5)
    set_mode(monkeypatch, "forced-on")
    pred, keep = PREDICATES["date-range"]
    cache = dcache.get_cache()
    first = daft_tpu.read_parquet(pattern).where(pred).to_pydict()
    assert last_summary()["tables"] == {"from_cache": 0,
                                        "encoded": N_FILES, "host": 0}
    put = cache.stats()["put_bytes"]
    assert put > 0
    second = daft_tpu.read_parquet(pattern).where(pred).to_pydict()
    assert second == first == arrow_answer(pattern, keep)
    assert last_summary()["tables"] == {"from_cache": N_FILES,
                                        "encoded": 0, "host": 0}
    assert cache.stats()["put_bytes"] == put
    # another filter over the same files shares the unfiltered columns
    other, okeep = PREDICATES["all-survive"]
    assert daft_tpu.read_parquet(pattern).where(other).select(
        "key", "ship").to_pydict() == arrow_answer(pattern, okeep,
                                                   ["key", "ship"])
    assert last_summary()["tables"]["from_cache"] == N_FILES
    # a file rewritten in place (another size and mtime) is read anew
    path = os.path.join(str(tmp_path), "p03.parquet")
    t = pq.read_table(path)
    pq.write_table(t.slice(0, 700), path)
    third = daft_tpu.read_parquet(pattern).where(pred).to_pydict()
    assert third == arrow_answer(pattern, keep)
    assert third != first
    assert last_summary()["tables"] == {"from_cache": N_FILES - 1,
                                        "encoded": 1, "host": 0}


# --------------------- (e) the fused aggregate over the shared resolver

#: Q6's answer over ``star`` from the parent commit's resolver
#: (``git archive 7b3fc95``, same seed, ``DAFT_TPU_DEVICE_FORCE=1``, CPU)
Q6_PARENT_REVENUE = 23694.638000000003
#: and its ``global_agg`` dispatches a query: one a table and the merge's
Q6_PARENT_DISPATCHES = N_FILES + 1


def test_q6_answer_and_dispatches_unchanged_by_the_shared_resolver(
        star, monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    build = importlib.import_module("chipbench.queries.q6").build
    get_df = lambda t: daft_tpu.read_parquet(  # noqa: E731
        f"{star}/{t}/*.parquet")
    led0 = cm.ledger_snapshot(raw=True).get("global_agg", {})
    first = build(get_df).to_pydict()
    assert last_summary()["tables"] == {"from_cache": 0,
                                        "encoded": N_FILES, "host": 0}
    second = build(get_df).to_pydict()
    assert last_summary()["tables"] == {"from_cache": N_FILES,
                                        "encoded": 0, "host": 0}
    led1 = cm.ledger_snapshot(raw=True)["global_agg"]
    assert led1["dispatches"] - led0.get("dispatches", 0) \
        == 2 * Q6_PARENT_DISPATCHES
    assert first == second
    assert first["revenue"] == [Q6_PARENT_REVENUE]


# ------------------------------------------------ (f) the gate's answers

def test_gate_prices_a_resident_table_by_its_survivors():
    rows, cap = 3_750_000, 4_194_304
    q14, q19 = (4, 4), (6, 5)       # (columns read, packed words a slot)
    # Q14 (1.3%: the 65 536 bucket) and Q19 (3.6%: 262 144) win
    assert cm.select_wins(rows, q14[0], 65_536, q14[1], True)
    assert cm.select_wins(rows, q19[0], 262_144, q19[1], True)
    # Q3's ``l_shipdate > 1995-03-15`` keeps 54% (the 2 097 152 bucket),
    # Q10's ``l_returnflag = 'R'`` 25% (1 048 576): the reader's
    assert not cm.select_wins(rows, 4, 2_097_152, 4, True)
    assert not cm.select_wins(rows, 4, 1_048_576, 4, True)
    # a filter that keeps everything; Q3's half of ``orders`` (0.94 M-row
    # tables); ``part``'s ``not_null`` over 125 k rows
    assert not cm.select_wins(rows, 4, cap, 4, True)
    assert not cm.select_wins(937_500, 4, 524_288, 4, True)
    assert not cm.select_wins(125_000, 2, 131_072, 3, True)
    # Q10's three months of ``orders`` (3.8%: the 65 536 bucket, two keys
    # and a date out) sit on the break-even: the device's since the slot's
    # price was read from row gathers (16.8 ms against the reader's 18.75)
    assert cm.select_wins(937_500, 3, 65_536, 4, True)
    # nothing known of the predicate: the host takes the table
    assert not cm.select_wins(rows, 4, None, 4, True)
    # a resident table's price does not read the link; it is tallied
    before = dict(cm.decision_counts.get("select", {}))
    cm.select_wins(rows, q14[0], 65_536, q14[1], True)
    after = cm.decision_counts["select"]
    assert after["device"] == before.get("device", 0) + 1
    # the ceiling sits where the two prices meet, and holds both rungs
    for n_cols, words, rung in ((4, 4, 65_536), (6, 5, 262_144)):
        most = cm.select_max_rows(rows, n_cols, words)
        assert most >= rung
        assert cm.select_wins(rows, n_cols, int(most * 0.9), words, True)
        assert not cm.select_wins(rows, n_cols, int(most * 1.1), words, True)
    # a miss is an investment: Q14's upload of 5 planes of 4 M rows
    assert cm.select_wins(rows, 4, 65_536, 4, False, bytes_up=25.0 * cap,
                          cacheable=True)
    assert not cm.select_wins(rows, 4, 65_536, 4, False,
                              bytes_up=25.0 * cap, cacheable=False)


def test_footer_selectivity_reads_a_range_and_nothing_of_a_string(star):
    scan = daft_tpu.read_parquet(f"{star}/lineitem/*.parquet")
    month = scan.where((col("l_shipdate") >= lit(datetime.date(1995, 9, 1)))
                       & (col("l_shipdate")
                          < lit(datetime.date(1995, 10, 1))))
    half = scan.where(col("l_shipdate") > lit(datetime.date(1995, 3, 15)))
    text = scan.where(col("l_shipinstruct") == "DELIVER IN PERSON")

    def estimate(df):
        from daft_tpu.logical import plan as lp
        plan = df._builder.optimize().plan
        while not isinstance(plan, lp.Source):
            plan = plan.children[0]
        tasks = plan.scan_op.to_scan_tasks(plan.pushdowns)
        return readers.footer_selectivity(tasks[0])

    assert 0.008 < estimate(month) < 0.02        # 30 days of ~2 500
    assert 0.45 < estimate(half) < 0.62
    assert estimate(text) is None


# ------------------------------------------- (g) the packed block's layout

ROW_DTYPES = {
    "key-floats-date": (np.int64, np.float32, np.float32, np.int32),
    "odd-narrow": (np.float32, np.int64, np.int32, np.bool_, np.float64),
    "narrow-only": (np.int32, np.float32),
    "one-wide": (np.float64,),
    "small-ints": (np.int8, np.int16, np.uint8, np.uint32, np.bool_),
}


@pytest.mark.parametrize("name", list(ROW_DTYPES))
def test_packed_rows_round_trip(name):
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    w, live = 64, 41
    vals, valids = [], []
    for dt in ROW_DTYPES[name]:
        dt = np.dtype(dt)
        if dt == np.bool_:
            v = rng.random(w) < 0.5
        elif dt.kind == "f":
            v = rng.normal(0, 1e6, w).astype(dt)
        else:
            info = np.iinfo(dt)
            v = rng.integers(info.min, info.max, w, dtype=dt,
                             endpoint=True)
        vals.append(v)
        valids.append(rng.random(w) < 0.8)
    block = np.asarray(fragment._pack_rows(
        [jnp.asarray(v) for v in vals], [jnp.asarray(m) for m in valids],
        jnp.asarray(live, jnp.int32)))
    dtypes = [v.dtype for v in vals]
    wide = sum(np.dtype(d).itemsize == 8 for d in dtypes)
    assert block.shape == (1 + wide + (len(dtypes) - wide + 1) // 2, w)
    assert fragment._packed_live(block) == live
    for (got, ok), v, m in zip(fragment._unpack_rows(block, dtypes), vals,
                               valids):
        assert got.dtype == v.dtype
        np.testing.assert_array_equal(got, v)
        np.testing.assert_array_equal(ok, m)


# ------------------------------------------- (h) the compaction's search

@pytest.mark.parametrize("capacity,w", [(32768, 2048), (32768, 4096),
                                        (32768, 8192), (4096, 512),
                                        (65536, 128)],
                         ids=["blocks", "blocks-widest", "search-wide-bucket",
                              "search-small-table", "blocks-of-4"])
@pytest.mark.parametrize("share", [0.0, 0.03, 0.5, 1.0])
def test_survivor_rows_against_numpy(capacity, w, share):
    """Both branches of ``fragment._survivor_rows`` (the three-level block
    search and the binary search over the flat running count) give the
    first ``w`` live rows in source order."""
    import jax.numpy as jnp
    rng = np.random.default_rng(int(share * 100) + capacity)
    mask = rng.random(capacity) < share
    mask[capacity - capacity // 10:] = False    # the bucket's padding
    want = np.nonzero(mask)[0][:w]
    got = np.asarray(fragment._survivor_rows(jnp.asarray(mask), w))
    assert got.shape == (w,) and got.dtype == np.int32
    np.testing.assert_array_equal(got[:len(want)], want)
    assert ((got >= 0) & (got < capacity)).all()


# --------------------------------------------------- (i) the output stage

MIXES = {
    "one-f32": (np.float32,),
    "q14": (np.int64, np.float32, np.float32, np.int32),
    "q19": (np.int64, np.float32, np.float32, np.float32, np.int32,
            np.int32),
    "31": (np.float32, np.int32, np.int64, np.bool_, np.int8, np.int16,
           np.uint8, np.uint32, np.float64, np.uint16) * 3 + (np.float32,),
}

#: id -> (capacity, rung, survivors' share, the outputs, row gathers?)
OUTPUT_STAGES = {
    "16k-rung128-1pct": (16384, 128, 0.005, "q19", True),
    "16k-widest-rows-4pct": (16384, 2048, 0.04, "q14", True),
    "16k-too-wide": (16384, 4096, 0.2, "one-f32", False),
    "16k-unfiltered-all": (16384, 16384, 1.0, "q19", False),
    "128k-rung128-none": (131072, 128, 0.0, "q19", True),
    "128k-31-outputs-4pct": (131072, 8192, 0.04, "31", True),
    "128k-overflowing-rung": (131072, 2048, 0.5, "q14", True),
    "128k-unfiltered-all": (131072, 131072, 1.0, "one-f32", False),
    "4m-rung128-too-narrow": (4194304, 128, 0.00001, "q19", False),
    "4m-q14-65536-1pct": (4194304, 65536, 0.0128, "q14", True),
    "4m-q19-262144-4pct": (4194304, 262144, 0.0357, "q19", True),
    "4m-one-262144-1pct": (4194304, 262144, 0.01, "one-f32", True),
    "no-128-row-blocks": (5000, 128, 0.01, "q19", False),
    "no-128x128-blocks": (4096, 128, 0.01, "q19", False),
}


def _planes(rng, dtypes, capacity):
    """Values over the whole bucket (negative zeros, NaNs, the integer
    types' extremes among them) and their validity; every third column
    holds nulls, the rest none."""
    vals, valids = [], []
    for k, dt in enumerate(dtypes):
        dt = np.dtype(dt)
        if dt == np.bool_:
            v = rng.random(capacity) < 0.5
        elif dt.kind == "f":
            v = rng.normal(0, 1e6, capacity).astype(dt)
            v[::7] = -0.0
            v[3::1001] = np.nan
        else:
            info = np.iinfo(dt)
            v = rng.integers(info.min, info.max, capacity, dtype=dt,
                             endpoint=True)
        vals.append(v)
        valids.append(rng.random(capacity) < 0.8 if k % 3 == 0
                      else np.ones(capacity, np.bool_))
    return vals, valids


@pytest.mark.parametrize("case", list(OUTPUT_STAGES))
def test_output_stage_rows_against_elements_and_numpy(case):
    """``fragment._take_rows`` (row gathers and a dense lane pick) and the
    element gathers it stands in for give the same packed block, bit for
    bit, dead slots included; its live rows are numpy's; and
    ``fragment.gathers_rows`` sends each shape where it belongs."""
    import jax
    import jax.numpy as jnp
    capacity, w, share, mix, rows = OUTPUT_STAGES[case]
    assert fragment.gathers_rows(capacity, w) is rows
    rng = np.random.default_rng(capacity + w)
    mask = rng.random(capacity) < share
    if share < 1.0:
        mask[capacity - capacity // 10:] = False    # the bucket's padding
    want = np.nonzero(mask)[0]
    vals, valids = _planes(rng, MIXES[mix], capacity)

    def by_elements(outs, idx, sel):
        return ([jnp.take(v, idx) for v, _ in outs],
                [jnp.take(m, idx) & sel for _, m in outs])

    def block(take):
        def run(vals, valids, mask):
            idx = fragment._survivor_rows(mask, w)
            live = jnp.sum(mask).astype(jnp.int32)
            sel = jnp.arange(w, dtype=jnp.int32) < live
            return fragment._pack_rows(
                *take(list(zip(vals, valids)), idx, sel), live)
        return np.asarray(jax.jit(run)(
            [jnp.asarray(v) for v in vals],
            [jnp.asarray(m) for m in valids], jnp.asarray(mask)))

    elements = block(by_elements)
    assert fragment._packed_live(elements) == len(want)
    if capacity % 128 == 0:     # the row path's one need of a shape
        got = block(fragment._take_rows)
        assert got.dtype == elements.dtype and got.shape == elements.shape
        np.testing.assert_array_equal(got, elements)
    live = min(len(want), w)
    for (v, ok), src, m in zip(
            fragment._unpack_rows(elements[:, :live],
                                  [x.dtype for x in vals]), vals, valids):
        assert v.dtype == src.dtype
        np.testing.assert_array_equal(v.view(np.uint8),
                                      src[want[:live]].view(np.uint8))
        np.testing.assert_array_equal(ok, m[want[:live]])


@pytest.mark.parametrize("capacity,w,rows", [(16384, 1024, True),
                                             (131072, 8192, True),
                                             (16384, 4096, False),
                                             (131072, 64, False)])
def test_the_program_takes_the_path_its_shape_asks_for(monkeypatch, capacity,
                                                       w, rows):
    """The chain program as ``executor._scan_select`` builds it, run at a
    shape ``gathers_rows`` sends to the rows and at one it does not,
    against the same program traced with element gathers only: the block
    that leaves the device is the same to the bit."""
    import jax.numpy as jnp
    from daft_tpu import DataType
    from daft_tpu.schema import Field, Schema
    schema = Schema([Field("k", DataType.int64()),
                     Field("x", DataType.float32()),
                     Field("d", DataType.int32())])
    rng = np.random.default_rng(w)
    arrays = {"k": rng.integers(-2**62, 2**62, capacity),
              "x": rng.normal(0, 1e4, capacity).astype(np.float32),
              "d": rng.integers(0, 100000, capacity).astype(np.int32)}
    valids = {n: rng.random(capacity) < 0.9 for n in arrays}
    mask = np.arange(capacity) < capacity - capacity // 8
    cut = 50000 * w // capacity     # the survivors half fill the bucket

    def run():
        fragment._region_cache.clear()
        prog = fragment.get_fused_region(
            [col(c) for c in schema.column_names], col("d") < lit(cut),
            schema)
        return np.asarray(prog.packed_fn(
            {n: jnp.asarray(a) for n, a in arrays.items()},
            {n: jnp.asarray(m) for n, m in valids.items()},
            jnp.asarray(mask), (), out_w=w))

    assert fragment.gathers_rows(capacity, w) is rows
    calls = []
    real = fragment._take_rows
    monkeypatch.setattr(fragment, "_take_rows",
                        lambda *a: calls.append(1) or real(*a))
    got = run()
    assert bool(calls) is rows
    monkeypatch.setattr(fragment, "gathers_rows", lambda C, w: False)
    del calls[:]
    elements = run()
    assert not calls
    fragment._region_cache.clear()
    np.testing.assert_array_equal(got, elements)
    keep = mask & valids["d"] & (arrays["d"] < cut)
    assert 0 < fragment._packed_live(got) == keep.sum() <= w
    (k, _), (x, _), (d, _) = fragment._unpack_rows(
        got[:, :keep.sum()], [np.int64, np.float32, np.int32])
    np.testing.assert_array_equal(k, arrays["k"][keep])
    np.testing.assert_array_equal(x, arrays["x"][keep])
    np.testing.assert_array_equal(d, arrays["d"][keep])
