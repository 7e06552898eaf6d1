"""Push-pipeline machinery: failure propagation, cancellation, order
preservation, the partitioned-agg dispatcher, and interp-executor parity.

Reference seam: Swordfish's pipeline/dispatcher
(``src/daft-local-execution/src/pipeline.rs:100-830``,
``dispatcher.rs:24-60``, ``sinks/grouped_aggregate.rs:54-151``); here
``daft_tpu/execution/pipeline.py``. These paths only fail as rare hangs or
silent truncations in production queries, so they get dedicated tests."""

import threading
import time

import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.datatype import DataType

_STAGE_PREFIXES = ("drv-", "dsp-", "wrk-", "col-", "red-")


@pytest.fixture(autouse=True)
def small_morsels():
    """8k-row fixtures re-chunk into ~16 real morsels (the default 128k
    morsel would swallow them whole and the stages under test would see a
    single-morsel stream)."""
    with dt.execution_config_ctx(default_morsel_size=500):
        yield


def _stage_threads():
    return [t for t in threading.enumerate()
            if any(t.name.startswith(p) for p in _STAGE_PREFIXES)]


def _wait_stages_exit(timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        alive = [t for t in _stage_threads() if t.is_alive()]
        if not alive:
            return True
        time.sleep(0.05)
    return False


@pytest.fixture
def many_files(tmp_path):
    """16 parquet files → a genuinely multi-morsel streaming source."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    root = tmp_path / "many"
    root.mkdir()
    n = 0
    for i in range(16):
        rows = 500
        pq.write_table(
            pa.table({"id": pa.array(range(n, n + rows), pa.int64()),
                      "g": pa.array([(n + j) % 7 for j in range(rows)],
                                    pa.int64()),
                      "v": pa.array([float(j) for j in range(rows)])}),
            root / f"part-{i:02d}.parquet")
        n += rows
    return str(root / "*.parquet"), n


def test_midstream_failure_surfaces_not_truncates(many_files):
    """A kernel failure deep into the stream must raise at the consumer —
    the fail-before-close ordering in pipeline.py exists so a failing
    query can never end as a clean truncated result."""
    glob, n = many_files

    @dt.udf(return_dtype=DataType.int64())
    def boom(ids):
        vals = ids.to_pylist()
        if any(v == 6500 for v in vals):  # lives in file 13 of 16
            raise RuntimeError("injected mid-stream kernel failure")
        return vals

    df = dt.read_parquet(glob).with_column("x", boom(col("id")))
    with pytest.raises(Exception, match="injected mid-stream"):
        df.to_pydict()
    assert _wait_stages_exit(), \
        f"stage threads leaked: {[t.name for t in _stage_threads()]}"


def test_consumer_drop_cancels_all_stage_threads(many_files):
    """Dropping the output iterator mid-stream must unwind every stage
    thread (dispatcher, workers, collector, drivers) within the poll
    bound — a leak here is a deadlocked query in a server."""
    glob, n = many_files

    @dt.udf(return_dtype=DataType.int64())
    def slow(ids):
        time.sleep(0.3)  # 16 morsels × 0.3 s ≫ time-to-first-output
        return ids.to_pylist()

    df = dt.read_parquet(glob).with_column("x", slow(col("id")))
    it = df.iter_partitions()
    next(it)
    assert len([t for t in _stage_threads() if t.is_alive()]) > 0, \
        "pipeline finished before the drop — slow() not slow enough"
    it.close()  # consumer walks away
    del it
    assert _wait_stages_exit(), \
        f"stage threads leaked: {[t.name for t in _stage_threads()]}"


def test_map_stage_preserves_order(many_files):
    """RoundRobin dispatch + in-order collection: output order equals
    input order even when per-morsel compute time is adversarial."""
    glob, n = many_files

    @dt.udf(return_dtype=DataType.int64())
    def jitter(ids):
        vals = ids.to_pylist()
        # earlier morsels sleep longer: a racy collector would emit
        # later morsels first
        time.sleep(0.05 if vals and vals[0] < 2000 else 0.001)
        return vals

    out = dt.read_parquet(glob).select(jitter(col("id")).alias("id")) \
        .to_pydict()
    assert out["id"] == list(range(n))


def test_error_after_some_output_still_raises(many_files):
    """Consume a few morsels THEN hit the failure: the iterator must
    raise, not stop cleanly (the truncation failure mode)."""
    glob, n = many_files

    @dt.udf(return_dtype=DataType.int64())
    def late_boom(ids):
        vals = ids.to_pylist()
        if any(v >= 7000 for v in vals):
            raise RuntimeError("late failure")
        return vals

    df = dt.read_parquet(glob).with_column("x", late_boom(col("id")))
    it = df.iter_partitions()
    got = 0
    with pytest.raises(Exception, match="late failure"):
        for _ in it:
            got += 1
    assert _wait_stages_exit()


# ------------------------------------------------- partitioned dispatcher

def test_partitioned_agg_matches_interp(many_files, monkeypatch):
    # host tier: with the 8-device CPU mesh up, the grouped agg would
    # otherwise lower onto DeviceExchangeAgg and bypass the dispatcher
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    glob, n = many_files
    df = dt.read_parquet(glob)
    agg = (df.groupby("g").agg(
        col("v").sum().alias("sv"), col("v").mean().alias("mv"),
        col("id").count().alias("c"), col("v").max().alias("hi"))
        .sort("g"))
    push = agg.to_pydict()
    with dt.execution_config_ctx(local_executor="interp"):
        interp = agg.to_pydict()
    assert push == interp
    # the fused stage really ran with >1 reducer
    from daft_tpu import observability as obs
    stats = obs.last_query_stats()
    # note: last stats are from the interp run; re-run under push
    push2 = agg.to_pydict()
    stats = obs.last_query_stats()
    workers = [s.workers for s in stats._ops.values()
               if s.workers and "Aggregate" in s.name]
    assert workers and max(workers) > 1, \
        f"grouped agg did not partition-parallelize: " \
        f"{[(s.name, s.workers) for s in stats._ops.values()]}"
    assert push2 == interp


def test_partitioned_agg_incremental_merge(many_files, monkeypatch):
    """Force the re-agg threshold low so every reducer exercises the
    state-merge path, and check exactness."""
    from daft_tpu.execution import pipeline
    monkeypatch.setattr(pipeline, "_REAGG_ROWS", 256)
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    glob, n = many_files
    out = (dt.read_parquet(glob).groupby("g")
           .agg(col("v").sum().alias("sv"), col("id").count().alias("c"))
           .sort("g").to_pydict())
    assert sum(out["c"]) == n
    expected_sv = {}
    for i in range(n):
        expected_sv[i % 7] = expected_sv.get(i % 7, 0.0) + float(i % 500)
    assert out["sv"] == pytest.approx([expected_sv[g] for g in out["g"]])


def test_partitioned_agg_declines_on_huge_footer_ndv(many_files, monkeypatch):
    """Footer stats predicting more groups than _FUSE_MAX_GROUPS route the
    final agg to the SPILL-PARTITIONED fused reducer (round 19: the state
    streams through a rotated-radix store, merged per bucket on read) —
    and DAFT_TPU_SPILL_AGG=0 restores the legacy decline onto the
    spill-bounded exchange path. Keys without footer evidence (or small
    ranges) keep the in-memory fused default."""
    from daft_tpu.execution import pipeline
    from daft_tpu.physical.translate import translate
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    glob, n = many_files

    def final_agg_node(df):
        phys = translate(df._builder.optimize().plan)
        found = []

        def walk(node):
            if type(node).__name__ == "Aggregate" and node.mode == "final":
                found.append(node)
            for c in node.children:
                walk(c)
        walk(phys)
        assert found, "no final Aggregate in plan"
        return found[0]

    df_wide = dt.read_parquet(glob).groupby("id").agg(
        col("v").sum().alias("s"))
    node = final_agg_node(df_wide)
    assert node.group_ndv == pytest.approx(n)  # dense ids: range == rows
    # n (8000) distinct ids > a forced-low threshold → the fusion now
    # keeps the boundary elided but switches to the spilling reducer
    monkeypatch.setattr(pipeline, "_FUSE_MAX_GROUPS", n // 2)
    info = pipeline._partitioned_agg_info(node)
    assert info is not None and info[3] is True  # spill=True
    # legacy escape hatch: DAFT_TPU_SPILL_AGG=0 declines the fusion
    monkeypatch.setenv("DAFT_TPU_SPILL_AGG", "0")
    assert pipeline._partitioned_agg_info(node) is None
    monkeypatch.delenv("DAFT_TPU_SPILL_AGG")
    # the small-range key keeps the in-memory fused path under the same
    # threshold
    df_small = dt.read_parquet(glob).groupby("g").agg(
        col("v").sum().alias("s"))
    small = final_agg_node(df_small)
    assert small.group_ndv == pytest.approx(7)
    small_info = pipeline._partitioned_agg_info(small)
    assert small_info is not None and small_info[3] is False
    # and both paths still answer correctly end-to-end: the declined
    # (exchange) path must produce every group with the right sums
    out = df_wide.sort("id").to_pydict()
    assert out["id"] == list(range(n))
    assert out["s"] == pytest.approx([float(i % 500) for i in range(n)])
    out_small = df_small.sort("g").to_pydict()
    assert out_small["g"] == list(range(7))
    expected = {}
    for i in range(n):
        expected[i % 7] = expected.get(i % 7, 0.0) + float(i % 500)
    assert out_small["s"] == pytest.approx([expected[g] for g in range(7)])


# ------------------------------------- small morsels ahead of a hash fan-out

def _traced_spans(monkeypatch, build):
    """Run ``build()`` traced; its answer and the spans it left."""
    from daft_tpu import observability as obs
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    out = build().to_pydict()
    spans = obs.last_query_stats().trace_ctx.recorder.spans()
    monkeypatch.delenv("DAFT_TPU_TRACE")
    return out, spans


def _named(spans, name):
    return [s["attrs"] for s in spans if s["name"] == name]


def _interp(build):
    with dt.execution_config_ctx(local_executor="interp"):
        return build().to_pydict()


def _coalesce_property(seed):
    """Per bucket the same rows in the same order as the per-morsel
    fan-out, every row once, large morsels untouched."""
    import numpy as np
    from daft_tpu.execution import out_of_core as ooc
    from daft_tpu.micropartition import MicroPartition
    rng = np.random.default_rng(seed)
    t = ooc.FANOUT_COALESCE_ROWS
    sizes = rng.choice([0, 1, 4, t // 3, t - 1, t, t + 1, 3 * t],
                       size=int(rng.integers(1, 14)))
    morsels, at = [], 0
    for n in map(int, sizes):
        ids = np.arange(at, at + n)
        morsels.append(MicroPartition.from_pydict(
            {"id": ids, "k": ids * 7919 % 101, "s": [f"s{i % 13}" for i in ids]}))
        at += n
    by, parts = [col("k"), col("s")], 5

    def buckets(pieces_of_each_unit):
        out = [[] for _ in range(parts)]
        for pieces in pieces_of_each_unit:
            for j, piece in enumerate(pieces):
                out[j].extend(piece.to_pydict()["id"])
        return out

    units = list(ooc.coalesce_small(iter(morsels)))
    assert buckets(mp.partition_by_hash(by, parts) for mp, _ in units) \
        == buckets(mp.partition_by_hash(by, parts) for mp in morsels)
    assert sum(len(mp) for mp, _ in units) == at
    assert sum(m for _, m in units) == sum(1 for n in sizes if n)
    large = [mp for mp in morsels if len(mp) >= t]
    passed = [mp for mp, m in units if m == 1 and len(mp) >= t]
    assert len(large) == len(passed) \
        and all(a is b for a, b in zip(large, passed))
    # a unit under the threshold is the last, or has a large one behind it
    for (mp, _), (after, m) in zip(units, units[1:]):
        assert len(mp) >= t or (m == 1 and len(after) >= t)


def _small_input_gathers(many_files, monkeypatch):
    """16 files, 7 groups, host tier: the 16 partial morsels reach one
    reducer unhashed."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    glob, n = many_files

    def build():
        return (dt.read_parquet(glob).groupby("g")
                .agg(col("v").sum().alias("sv"), col("id").count().alias("c"))
                .sort("g"))
    out, spans = _traced_spans(monkeypatch, build)
    assert _named(spans, "exchange:partition") == []
    (gather,) = _named(spans, "exchange:gather")
    assert gather["morsels"] > 1 and gather["rows"] == 7 * gather["morsels"]
    assert out == _interp(build) and sum(out["c"]) == n


def _crossing_threshold_hashes(tmp_path, monkeypatch):
    """small, small, large, small: once one unit went out by hash the
    rest do, and no group comes out of two reducers."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from daft_tpu.execution import out_of_core as ooc
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setattr(ooc, "FANOUT_COALESCE_ROWS", 50)
    tmp_path.mkdir()
    for i, groups in enumerate((3, 3, 200, 3)):
        pq.write_table(pa.table({"g": [j % groups for j in range(400)],
                                 "v": [float(j) for j in range(400)]}),
                       tmp_path / f"part-{i}.parquet")

    def build():
        return (dt.read_parquet(str(tmp_path / "*.parquet")).groupby("g")
                .agg(col("v").sum().alias("sv"), col("v").count().alias("c"))
                .sort("g"))
    out, spans = _traced_spans(monkeypatch, build)
    assert _named(spans, "exchange:gather") == []
    fans = _named(spans, "exchange:partition")
    assert [(f["morsels"], f["rows"]) for f in fans] \
        == [(2, 6), (1, 200), (1, 3)]
    assert out["g"] == list(range(200)) and sum(out["c"]) == 1600
    assert out == _interp(build)


def _copartitioned_join_sanitized(many_files, monkeypatch):
    """One side of a co-partitioned join arrives in 500-row morsels and
    is partitioned in one call; the bucket index still pairs the sides
    (the sanitizer re-hashes every bucket it sees)."""
    from daft_tpu.analysis import plan_sanitizer as ps
    glob, n = many_files
    right = dt.from_pydict({"rk": list(range(0, n, 2)),
                            "w": list(range(n // 2))})

    def build():
        return (dt.read_parquet(glob).join(right.into_partitions(4),
                                           left_on="id", right_on="rk")
                .sort("id"))
    was_enabled = ps.is_enabled()
    ps.enable()
    try:
        before = ps.counters_snapshot()
        with dt.execution_config_ctx(broadcast_join_size_bytes_threshold=1):
            out, spans = _traced_spans(monkeypatch, build)
            interp = _interp(build)
        delta = ps.counters_delta(before, ps.counters_snapshot())
    finally:
        if not was_enabled:
            ps.disable()
    assert delta["membership_parts"] > 0 and delta["violations"] == 0
    fans = _named(spans, "exchange:partition")
    assert {"rows": n, "morsels": 16} in \
        [{k: f[k] for k in ("rows", "morsels")} for f in fans]
    assert out == interp and out["id"] == list(range(0, n, 2))


def _spill_reducer_sums(many_files, monkeypatch, threshold):
    """The spill-partitioned reducer behind the hand-over (the default
    threshold: 8 000 partial rows fit the buffer) and behind coalesced
    hash fan-outs (a threshold of 1 024 rows)."""
    from daft_tpu.execution import out_of_core as ooc, pipeline
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setattr(pipeline, "_REAGG_ROWS", 256)
    monkeypatch.setattr(pipeline, "_FUSE_MAX_GROUPS", 100)
    if threshold:
        monkeypatch.setattr(ooc, "FANOUT_COALESCE_ROWS", threshold)
    glob, n = many_files

    def build():
        return (dt.read_parquet(glob).groupby("id")
                .agg(col("v").sum().alias("s")).sort("id"))
    out, spans = _traced_spans(monkeypatch, build)
    assert out["id"] == list(range(n))
    assert out["s"] == [float(i % 500) for i in range(n)]
    # (the reducers' own radix splits are ``exchange:partition`` too)
    gathers = _named(spans, "exchange:gather")
    if threshold:
        assert gathers == []
        assert any(f["morsels"] > 1 and f["rows"] >= threshold
                   for f in _named(spans, "exchange:partition"))
    else:
        assert gathers == [{"rows": n, "morsels": 16}]


@pytest.mark.parametrize("case", [
    "property_seed0", "property_seed1", "property_seed2", "property_seed3",
    "property_seed4", "small_input_gathers", "crossing_threshold_hashes",
    "copartitioned_join_sanitized", "spill_reducer_behind_gather",
    "spill_reducer_behind_hash",
])
def test_small_morsels_are_fanned_out_together(case, many_files, tmp_path,
                                               monkeypatch):
    """``out_of_core.coalesce_small`` at the hash fan-outs: the fused
    dispatcher, the Exchange operator and the spill reducer behind it."""
    if case.startswith("property_seed"):
        _coalesce_property(int(case[-1]))
    elif case == "small_input_gathers":
        _small_input_gathers(many_files, monkeypatch)
    elif case == "crossing_threshold_hashes":
        _crossing_threshold_hashes(tmp_path / "cross", monkeypatch)
    elif case == "copartitioned_join_sanitized":
        _copartitioned_join_sanitized(many_files, monkeypatch)
    else:
        _spill_reducer_sums(many_files, monkeypatch,
                            1024 if case.endswith("hash") else None)


# --------------------------------------------------- interp executor tier

@pytest.fixture(scope="module")
def shapes_df():
    return dt.from_pydict({
        "k": ["a", "b", "a", "c", "b", "a", "c", "b"],
        "i": [3, 1, 4, 1, 5, 9, 2, 6],
        "f": [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8],
        "lst": [[1], [2, 3], [], [4], [5, 6], [7], [8], [9]],
    })


def _interp_and_push(build):
    push = build().to_pydict()
    with dt.execution_config_ctx(local_executor="interp"):
        interp = build().to_pydict()
    assert push == interp
    return push


@pytest.mark.parametrize("case", [
    "filter_project", "groupby", "global_agg", "sort", "join", "window",
    "explode", "distinct", "limit", "concat", "sql_subquery", "rollup",
])
def test_interp_executor_parity(case, shapes_df):
    """The interp (pull-generator) executor is reachable config
    (``local_executor="interp"``): every representative plan shape must
    agree with the push default."""
    df = shapes_df
    other = dt.from_pydict({"k": ["a", "b", "z"], "w": [10, 20, 30]})
    builds = {
        "filter_project": lambda: df.where(col("i") > 2)
            .select(col("k"), (col("i") * 2).alias("d")).sort("d"),
        "groupby": lambda: df.groupby("k").agg(
            col("i").sum().alias("s"), col("f").mean().alias("m")).sort("k"),
        "global_agg": lambda: df.agg(col("i").sum().alias("s"),
                                     col("i").count_distinct().alias("nd")),
        "sort": lambda: df.sort(["k", "i"], desc=[False, True]),
        "join": lambda: df.join(other, on="k").sort(["k", "i"]),
        "window": lambda: df.select(
            col("k"), col("i"),
            col("i").sum().over(dt.Window().partition_by("k")
                                .order_by("i")).alias("r")).sort(["k", "i"]),
        "explode": lambda: df.explode(col("lst")).sort(["k", "i"]),
        "distinct": lambda: df.select("k").distinct().sort("k"),
        "limit": lambda: df.sort("i").limit(3),
        "concat": lambda: df.select("k").concat(other.select("k")).sort("k"),
        "sql_subquery": lambda: dt.sql(
            "SELECT k, i FROM t WHERE i > (SELECT avg(i) FROM t) "
            "ORDER BY i", t=df),
        "rollup": lambda: dt.sql(
            "SELECT k, sum(i) AS s FROM t GROUP BY ROLLUP(k) "
            "ORDER BY s", t=df),
    }
    _interp_and_push(builds[case])


# ------------------------------------------ waits on a channel (PR 43)

def _traced_channel(capacity=4):
    from daft_tpu import tracing
    from daft_tpu.execution.pipeline import Channel, PipelineContext
    rec = tracing.SpanRecorder("c" * 32)
    return (rec, tracing.SpanContext(rec, rec.root_id),
            Channel(PipelineContext(), capacity=capacity))


def test_a_consumer_that_waits_for_its_item_tallies_a_handoff():
    from daft_tpu import tracing
    rec, ctx, ch = _traced_channel()
    item = object()

    def produce():
        with tracing.attach(ctx):
            time.sleep(0.05)      # the taker is waiting by now: 30 ms at least
            ch.put(item)
            ch.close()

    t = threading.Thread(target=produce)
    with tracing.attach(ctx):
        t.start()
        got = list(ch)
    t.join(5)
    assert got == [item] and got[0] is item     # unwrapped again
    waits = [s for s in rec.spans() if s["name"] == "wait:channel"]
    assert waits[0]["attrs"]["side"] == "get"
    assert waits[0]["dur_us"] >= 30_000 and "cpu_us" not in waits[0]
    # the item came after the taker began to wait: the hand-off is the
    # sliver from the put to the taker running, not the 30 ms before it
    assert 0 <= waits[0]["attrs"]["tail_us"] <= waits[0]["dur_us"] - 29_000
    rec.finish()
    h = rec.summary()["handoffs"]
    assert h["count"] == 2                      # the item and the end marker
    assert waits[0]["attrs"]["tail_us"] <= h["max_us"] <= h["us"]


def test_a_put_on_a_full_channel_is_a_wait_span():
    from daft_tpu import tracing
    rec, ctx, ch = _traced_channel(capacity=1)
    got = []

    def consume():
        with tracing.attach(ctx):
            time.sleep(0.03)
            got.extend(ch)

    t = threading.Thread(target=consume)
    with tracing.attach(ctx):
        ch.put("a")             # fits: no wait
        t.start()
        ch.put("b")             # the queue is full until the consumer comes
        ch.close()
    t.join(5)
    assert got == ["a", "b"]
    puts = [s for s in rec.spans() if s["name"] == "wait:channel"
            and s["attrs"]["side"] == "put"]
    assert len(puts) >= 1 and puts[0]["dur_us"] >= 25_000
    assert "tail_us" not in puts[0]["attrs"]
    rec.finish()
    s = rec.summary()
    # "a" lay in the queue when its taker came: handed over at once
    assert s["handoffs"]["count"] == 3
    assert s["holes"]["by"]["wait:channel"] > 0


def test_untraced_a_channel_hands_over_the_very_object(monkeypatch):
    from daft_tpu import tracing
    from daft_tpu.execution.pipeline import Channel, PipelineContext
    monkeypatch.setattr(time, "thread_time_ns", lambda: 1 / 0)
    monkeypatch.setattr(time, "perf_counter_ns", lambda: 1 / 0)
    assert tracing.current() is None
    ch = Channel(PipelineContext(), capacity=2)
    item = object()
    ch.put(item)
    assert ch._q.queue[0] is item               # no stamp, no wrapper
    ch.close()
    assert [x is item for x in ch] == [True]


@pytest.mark.parametrize("query", ["agg", "join"])
def test_a_traced_query_names_its_holes(many_files, monkeypatch, query):
    pattern, n = many_files

    def build():
        df = dt.read_parquet(pattern)
        if query == "agg":
            return df.where(col("v") > 10).groupby("g").agg(
                col("v").sum().alias("s"))
        small = dt.from_pydict({"g": list(range(7)),
                                "w": [float(i) for i in range(7)]})
        return df.join(small, on="g").groupby("g").agg(
            (col("v") * col("w")).sum().alias("s"))

    out, spans = _traced_spans(monkeypatch, build)
    assert len(out["g"]) == 7
    from daft_tpu import tracing
    s = tracing.finished()[-1]
    assert s["dropped"] == 0
    assert s["handoffs"]["count"] > 0
    assert s["handoffs"]["us"] >= s["handoffs"]["max_us"]
    holes = s["holes"]
    assert holes["us"] == s["wall_us"] - s["covered_us"]
    assert 0 <= holes["unnamed_us"] <= holes["us"]
    assert all(0 < v <= holes["us"] for v in holes["by"].values())
    for sp in spans:
        if "cpu_us" in sp:
            assert sp["cpu_us"] <= sp["dur_us"] + 50, sp
        if sp["name"].startswith("wait:"):
            assert sp["dur_us"] >= tracing.WAIT_FLOOR_US
    # every live span of the host operators says how much of it was work
    assert any("cpu_us" in sp for sp in spans if sp["name"] == "scan:load")
