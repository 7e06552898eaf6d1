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
(f) the gate's answers and the footer's estimate of the survivors."""

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
    assert failures() == before
    # the rung is learned: the next scan overflows nowhere
    daft_tpu.read_parquet(files).where(
        col("instruct") != "TAKE BACK RETURN").to_pydict()
    assert last_summary()["selects"]["overflows"] == 0


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
    # a filter that keeps everything; Q10's three months of ``orders``
    # (3.8% of 0.94 M-row tables); ``part``'s ``not_null`` over 125 k rows
    assert not cm.select_wins(rows, 4, cap, 4, True)
    assert not cm.select_wins(937_500, 3, 65_536, 3, True)
    assert not cm.select_wins(125_000, 4, 131_072, 4, True)
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
