"""Scan fast path: byte-range read planner, parallel coalesced fetch,
prefetch-pipelined scans (reference: ``daft-parquet/read_planner`` +
``src/daft-io``).

Covers: planner range math (coalesce gap, request floor,
projection/pruning interaction), ``get_ranges`` parity across
Local/HTTP/S3-stub sources, prefetch ordering + memory admission +
chaos-serialize degradation, the per-query ``io`` stats block, 4xx
no-retry, hive key union, null_count/is_in pruning, head-range schema
inference, and parity of a pruned+projected remote read vs the naive
path."""

import http.server
import os
import threading
import urllib.parse

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.context import execution_config_ctx
from daft_tpu.io import read_planner as rp
from daft_tpu.io.object_io import (HTTPConfig, HTTPSource, LocalSource,
                                   retry_backoff_s)


# --------------------------------------------------------------- fixtures

class _RangeStore(http.server.BaseHTTPRequestHandler):
    """In-memory object store speaking Range/HEAD/404 + scripted failures;
    every request lands in ``log`` so tests count GETs per path."""

    store = {}
    log = []
    fail_next = []  # status codes consumed one per request

    def log_message(self, *a):
        pass

    def _key(self):
        return urllib.parse.urlparse(self.path).path.lstrip("/")

    def _scripted(self):
        if _RangeStore.fail_next:
            code = _RangeStore.fail_next.pop(0)
            self.send_response(code)
            self.end_headers()
            return True
        return False

    def do_HEAD(self):
        _RangeStore.log.append(("HEAD", self._key()))
        data = self.store.get(self._key())
        if data is None:
            self.send_response(404)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()

    def do_GET(self):
        _RangeStore.log.append(("GET", self._key()))
        if self._scripted():
            return
        data = self.store.get(self._key())
        if data is None:
            self.send_response(404)
            self.end_headers()
            return
        rng = self.headers.get("Range")
        if rng:
            spec = rng.split("=")[1]
            a, b = spec.split("-")
            start, end = int(a), min(int(b), len(data) - 1)
            chunk = data[start:end + 1]
            self.send_response(206)
        else:
            chunk = data
            self.send_response(200)
        self.send_header("Content-Length", str(len(chunk)))
        self.end_headers()
        self.wfile.write(chunk)


@pytest.fixture(scope="module")
def store():
    _RangeStore.store = {}
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _RangeStore)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()


def _parquet_bytes(table, **kw) -> bytes:
    import io as _io
    buf = _io.BytesIO()
    pq.write_table(table, buf, **kw)
    return buf.getvalue()


@pytest.fixture
def remote_dataset(store):
    """4 parquet files × 4 row groups × 4 columns on the HTTP store."""
    urls = []
    for i in range(4):
        n = 400
        t = pa.table({
            "seq": pa.array(range(i * n, (i + 1) * n)),
            "v": pa.array([float(j) for j in range(n)]),
            "pad": pa.array([f"pad-{j % 13}" for j in range(n)]),
            "w": pa.array([j * 2 for j in range(n)]),
        })
        key = f"ds/part-{i}.parquet"
        _RangeStore.store[key] = _parquet_bytes(t, row_group_size=100)
        urls.append(f"{store}/{key}")
    return urls


# --------------------------------------------------------- planner: math

def test_coalesce_gap_merges_within_tolerance():
    ranges = [(0, 10), (15, 30), (200, 210), (205, 260)]
    out = rp.coalesce_ranges(ranges, gap=10, floor=0)
    assert out == [(0, 30), (200, 260)]  # overlap + small hole merge
    # a hole wider than the tolerance stays split (floor off)
    assert rp.coalesce_ranges([(0, 10), (100, 110)], gap=10, floor=0) \
        == [(0, 10), (100, 110)]
    assert rp.coalesce_ranges([], gap=10, floor=0) == []


def test_coalesce_request_floor_batches_small_requests():
    # sub-floor requests absorb neighbors across holes smaller than the
    # floor — scattered small chunks become one RTT-amortizing request
    ranges = [(0, 10), (50, 60), (100, 110)]
    assert rp.coalesce_ranges(ranges, gap=5, floor=1000) == [(0, 110)]
    # two already-large requests split by a hole > gap stay separate
    big = [(0, 2000), (3500, 6000)]
    assert rp.coalesce_ranges(big, gap=5, floor=1000) == big
    # hole >= floor is never absorbed, however small the requests
    assert rp.coalesce_ranges([(0, 10), (5000, 5010)], gap=5, floor=1000) \
        == [(0, 10), (5000, 5010)]


def test_plan_parquet_ranges_projection_and_pruning(tmp_path):
    p = str(tmp_path / "t.parquet")
    t = pa.table({"a": list(range(1000)),
                  "b": [float(i) for i in range(1000)],
                  "c": [f"s{i}" for i in range(1000)]})
    pq.write_table(t, p, row_group_size=250)  # 4 row groups
    md = pq.ParquetFile(p).metadata

    def chunk_span(g, name):
        rg = md.row_group(g)
        for ci in range(rg.num_columns):
            cc = rg.column(ci)
            if cc.path_in_schema == name:
                start = cc.data_page_offset
                if cc.dictionary_page_offset is not None:
                    start = min(start, cc.dictionary_page_offset)
                return (start, start + cc.total_compressed_size)
        raise KeyError(name)

    # projection × pruning: exactly the selected groups' selected chunks
    got = rp.plan_parquet_ranges(md, row_groups=[1, 3], columns=["a"])
    assert got == sorted([chunk_span(1, "a"), chunk_span(3, "a")])
    # all groups, two columns — 8 ranges before normalization
    got = rp.plan_parquet_ranges(md, None, ["a", "b"])
    total = sum(e - s for s, e in got)
    expect = sum(chunk_span(g, c)[1] - chunk_span(g, c)[0]
                 for g in range(4) for c in ("a", "b"))
    assert total == expect  # overlap-merge never loses or double-counts
    assert rp.plan_parquet_ranges(md, [], ["a"]) == []
    # unknown column projects to nothing
    assert rp.plan_parquet_ranges(md, [0], []) == []


def test_range_cache_reads_across_segments():
    cache = rp.RangeCache([((0, 10), bytes(range(10))),
                           ((20, 30), bytes(range(20, 30)))])
    assert cache.read(2, 8) == bytes(range(2, 8))
    assert cache.read(20, 30) == bytes(range(20, 30))
    with pytest.raises(KeyError):
        cache.read(5, 25)  # hole between segments
    with pytest.raises(KeyError):
        cache.read(28, 35)  # runs past a segment


# ------------------------------------------------- get_ranges: parity

def test_get_ranges_parity_across_sources(tmp_path, store, monkeypatch):
    from daft_tpu.io.object_io import S3Config
    from daft_tpu.io.s3 import S3Source

    data = bytes(range(256)) * 40
    ranges = [(0, 100), (5000, 5500), (137, 139), (10000, 10240)]
    expected = [data[s:e] for s, e in ranges]

    lp = tmp_path / "blob.bin"
    lp.write_bytes(data)
    assert LocalSource().get_ranges(str(lp), ranges) == expected

    _RangeStore.store["parity/blob.bin"] = data
    http_src = HTTPSource(HTTPConfig())
    assert http_src.get_ranges(f"{store}/parity/blob.bin", ranges,
                               parallelism=3) == expected

    s3 = S3Source(S3Config(endpoint_url=store, key_id="k", access_key="s",
                           region_name="us-east-1"))
    _RangeStore.store["bkt/blob.bin"] = data
    assert s3.get_ranges("s3://bkt/blob.bin", ranges,
                         parallelism=4) == expected

    # stats thread through: one record per request
    from daft_tpu.io.object_io import IOStatsContext
    st = IOStatsContext("t")
    LocalSource().get_ranges(str(lp), ranges, st)
    assert st.num_gets == len(ranges)
    assert st.bytes_read == sum(len(b) for b in expected)


# ------------------------------------- planned remote reads: end-to-end

def test_planned_remote_read_parity_and_coalescing(remote_dataset,
                                                   monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")

    def q():
        with execution_config_ctx(scan_tasks_min_size_bytes=1):
            return (dt.read_parquet(remote_dataset)
                    .where(col("seq") < 800)
                    .select("seq", "v").to_pydict())

    monkeypatch.setenv("DAFT_TPU_IO_PLANNED_READS", "0")
    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "0")
    before = rp.scan_counters_snapshot()
    naive = q()
    naive_c = rp.scan_counters_delta(before)

    monkeypatch.setenv("DAFT_TPU_IO_PLANNED_READS", "1")
    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "2")
    before = rp.scan_counters_snapshot()
    fast = q()
    fast_c = rp.scan_counters_delta(before)

    assert sorted(naive["seq"]) == sorted(fast["seq"]) == list(range(800))
    assert naive["v"] and sorted(naive["v"]) == sorted(fast["v"])
    # the whole point: far fewer object GETs for the same read
    assert fast_c.get("gets", 0) < naive_c.get("gets", 0)
    assert fast_c.get("range_requests", 0) < fast_c.get("ranges_planned", 0)
    assert fast_c.get("bytes_used", 0) > 0
    assert not fast_c.get("planned_read_fallbacks")
    assert fast_c.get("prefetch_tasks", 0) > 0


def test_planned_read_row_group_pruning_fetches_less(remote_dataset,
                                                     monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_IO_PLANNED_READS", "1")

    def run(pred):
        with execution_config_ctx(scan_tasks_min_size_bytes=1):
            df = dt.read_parquet(remote_dataset).select("seq", "v")
            if pred is not None:
                df = df.where(pred)
            before = rp.scan_counters_snapshot()
            out = df.to_pydict()
            return out, rp.scan_counters_delta(before)

    full, full_c = run(None)
    pruned, pruned_c = run(col("seq") < 100)  # 1 of 16 row groups
    assert len(full["seq"]) == 1600 and sorted(pruned["seq"]) == \
        list(range(100))
    assert pruned_c.get("bytes_used", 0) < full_c.get("bytes_used", 1)
    assert pruned_c.get("ranges_planned", 0) < full_c.get(
        "ranges_planned", 1)


# ------------------------------------------------ prefetch pipeline

def test_prefetch_preserves_task_order(tmp_path, monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "3")
    for i in range(6):
        pq.write_table(pa.table({"x": list(range(i * 10, (i + 1) * 10))}),
                       tmp_path / f"p{i}.parquet")
    with execution_config_ctx(scan_tasks_min_size_bytes=1,
                              max_sources_per_scan_task=1):
        out = dt.read_parquet(str(tmp_path) + "/*.parquet").to_pydict()
    # no sort anywhere: order is the glob (task) order
    assert out["x"] == list(range(60))


def test_prefetch_early_limit_abandons_cleanly(tmp_path, monkeypatch):
    """A satisfied limit abandons the scan stream mid-task: the window's
    producers must unblock (dead-stream signal), not wedge the pool."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "3")
    for i in range(6):
        pq.write_table(pa.table({"x": list(range(i * 1000, (i + 1) * 1000))}),
                       tmp_path / f"p{i}.parquet")
    with execution_config_ctx(scan_tasks_min_size_bytes=1,
                              max_sources_per_scan_task=1,
                              default_morsel_size=100):
        out = dt.read_parquet(str(tmp_path) + "/*.parquet").limit(150) \
            .to_pydict()
    assert out["x"] == list(range(150))


def test_prefetch_memory_admission(tmp_path, monkeypatch):
    """Prefetched bytes stay under the memory budget: with a budget that
    fits ~one task, the window's producers serialize on admission."""
    from daft_tpu.execution import memory
    from daft_tpu.execution.executor import LocalExecutor
    from daft_tpu.io.scan import GlobScanOperator, Pushdowns
    from daft_tpu.physical import plan as pp

    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "3")
    for i in range(5):
        pq.write_table(
            pa.table({"x": list(range(2000)),
                      "y": [float(j) for j in range(2000)]}),
            tmp_path / f"p{i}.parquet")

    with execution_config_ctx(scan_tasks_min_size_bytes=1,
                              max_sources_per_scan_task=1):
        op = GlobScanOperator(str(tmp_path) + "/*.parquet", "parquet")
        tasks = op.to_scan_tasks(Pushdowns())
        assert len(tasks) == 5
        sizes = [t.size_bytes() for t in tasks]
        assert all(sizes)
        budget = int(max(sizes) * 1.5)  # roughly one task at a time

        class Tracking(memory.MemoryManager):
            max_held = 0

            def acquire(self, n):
                super().acquire(n)
                with self._cond:
                    Tracking.max_held = max(Tracking.max_held, self._held)

        ex = LocalExecutor()
        ex.mem = Tracking(budget)
        node = pp.ScanSource(tasks, op.schema())
        out = list(ex._exec_ScanSource(node))
        assert sum(len(p) for p in out) == 5 * 2000
        assert 0 < Tracking.max_held <= budget


def test_prefetch_admission_no_deadlock(tmp_path, monkeypatch):
    """Regression: with a budget admitting only ONE task and multi-file
    tasks producing more batches than any queue bound, an out-of-order
    admission must not deadlock the FIFO consumer (review finding: a
    later producer holding admission while blocked on a bounded queue
    starved the head task forever)."""
    from daft_tpu.execution import memory
    from daft_tpu.execution.executor import LocalExecutor
    from daft_tpu.io.scan import Pushdowns, ScanTask
    from daft_tpu.physical import plan as pp
    from daft_tpu.schema import Schema

    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "2")
    paths = []
    for i in range(12):
        p = str(tmp_path / f"f{i}.parquet")
        pq.write_table(pa.table({"x": list(range(i * 50, (i + 1) * 50))}), p)
        paths.append(p)
    schema = Schema.from_arrow(pq.read_schema(paths[0]))
    # two 6-file tasks (>4 batches each), est sized so only one admits
    tasks = [ScanTask(paths[:6], "parquet", schema, Pushdowns(),
                      size_bytes_hint=800_000),
             ScanTask(paths[6:], "parquet", schema, Pushdowns(),
                      size_bytes_hint=800_000)]
    ex = LocalExecutor()
    ex.mem = memory.MemoryManager(1_000_000)
    node = pp.ScanSource(tasks, schema)
    result = {}

    def drain():
        result["rows"] = sum(len(p)
                             for p in ex._exec_ScanSource(node))

    t = threading.Thread(target=drain, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "prefetch scan deadlocked under admission"
    assert result["rows"] == 600


def test_prefetch_degrades_under_chaos(remote_dataset, monkeypatch):
    """PR 2 contract: an active fault plan or DAFT_TPU_CHAOS_SERIALIZE=1
    forces the pre-fast-path sequential scan loop (prefetch_tasks counter
    stays flat), while the answer is unchanged."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "4")

    def q():
        with execution_config_ctx(scan_tasks_min_size_bytes=1):
            return dt.read_parquet(remote_dataset).select("seq") \
                .to_pydict()

    assert rp.scan_sequential_fallback() is False
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    assert rp.scan_sequential_fallback() is True
    before = rp.scan_counters_snapshot()
    out = q()
    delta = rp.scan_counters_delta(before)
    assert sorted(out["seq"]) == list(range(1600))
    assert delta.get("prefetch_tasks", 0) == 0

    monkeypatch.delenv("DAFT_TPU_CHAOS_SERIALIZE")
    monkeypatch.setenv("DAFT_TPU_FAULT_SPEC", "task:0")
    from daft_tpu.distributed import resilience as rz
    rz.reset_for_tests()
    assert rp.scan_sequential_fallback() is True
    before = rp.scan_counters_snapshot()
    q()
    assert rp.scan_counters_delta(before).get("prefetch_tasks", 0) == 0
    monkeypatch.delenv("DAFT_TPU_FAULT_SPEC")
    rz.reset_for_tests()


# ------------------------------------------------------- io stats block

def test_io_stats_block_in_explain_analyze(remote_dataset, monkeypatch,
                                           capsys):
    import daft_tpu.observability as obs
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_IO_PLANNED_READS", "1")
    monkeypatch.setenv("DAFT_TPU_SCAN_PREFETCH", "2")
    with execution_config_ctx(scan_tasks_min_size_bytes=1):
        df = dt.read_parquet(remote_dataset).where(col("seq") < 800) \
            .select("seq", "v")
        df.explain(analyze=True)
    printed = capsys.readouterr().out
    assert "io (scan plane):" in printed
    assert "range requests" in printed
    st = obs.last_query_stats()
    assert st is not None and st.io.get("gets", 0) > 0
    assert st.io.get("bytes_fetched", 0) > 0
    lines = obs.render_io_block(st.io)
    assert any("prefetch" in ln for ln in lines)


# ------------------------------------------------------ retry satellite

def test_http_4xx_not_retried_5xx_retried(store):
    src = HTTPSource(HTTPConfig(num_tries=4))
    _RangeStore.store["r/x.bin"] = b"payload"

    _RangeStore.log = []
    with pytest.raises(Exception):
        src.get(f"{store}/r/missing.bin")
    # 404 is deterministic: exactly ONE request, not num_tries
    assert len([e for e in _RangeStore.log
                if e[1] == "r/missing.bin"]) == 1

    _RangeStore.fail_next = [500, 503]
    assert src.get(f"{store}/r/x.bin") == b"payload"  # 2 failures + 1 ok


def test_retry_backoff_deterministic_and_bounded():
    a = [retry_backoff_s("s3://b/k", i) for i in range(6)]
    b = [retry_backoff_s("s3://b/k", i) for i in range(6)]
    assert a == b  # deterministic jitter
    assert all(0 < x <= 2.0 for x in a)  # hard cap, jitter included
    assert retry_backoff_s("other", 0) != a[0]  # keyed jitter


# ------------------------------------------------------- hive satellite

def test_hive_union_across_mixed_key_paths(tmp_path):
    (tmp_path / "g=a").mkdir()
    (tmp_path / "g=b" / "h=1").mkdir(parents=True)
    pq.write_table(pa.table({"v": [1, 2]}), tmp_path / "g=a" / "x.parquet")
    pq.write_table(pa.table({"v": [3]}),
                   tmp_path / "g=b" / "h=1" / "y.parquet")
    df = dt.read_parquet(str(tmp_path) + "/**/*.parquet",
                         hive_partitioning=True)
    assert set(df.schema().column_names) == {"v", "g", "h"}
    out = df.sort("v").to_pydict()
    assert out["v"] == [1, 2, 3]
    assert out["g"] == ["a", "a", "b"]
    # missing-key → null fill on the path without h=
    assert out["h"] == [None, None, "1"]


# ---------------------------------------------------- pruning satellite

def test_prune_null_count_and_is_in(tmp_path):
    from daft_tpu.io.footers import Footer
    from daft_tpu.io.readers import _prune_row_groups

    p = str(tmp_path / "t.parquet")
    t = pa.table({
        # g0: 0..99 no nulls; g1: all nulls; g2: 200..299 some nulls
        "a": pa.array(list(range(100)) + [None] * 100
                      + list(range(200, 290)) + [None] * 10),
    })
    pq.write_table(t, p, row_group_size=100)
    md = Footer(pq.ParquetFile(p).metadata)

    # is_null: zero-null groups prune
    assert _prune_row_groups(md, col("a").is_null()) == [1, 2]
    # not_null: the all-null group prunes
    assert _prune_row_groups(md, col("a").not_null()) == [0, 2]
    # is_in: min/max containment (g1 has no min/max → kept conservatively)
    assert _prune_row_groups(md, col("a").is_in([250, 270])) \
        == [1, 2]
    assert _prune_row_groups(md, col("a").is_in([50])) == [0, 1]
    # conjunct composes with the existing comparison bounds
    assert _prune_row_groups(
        md, col("a").is_in([250]) & (col("a") > 240)) == [1, 2]
    # end-to-end answers agree with the pruned plan
    out = dt.read_parquet(p).where(col("a").is_in([50, 250])) \
        .to_pydict()
    assert sorted(out["a"]) == [50, 250]
    out = dt.read_parquet(p).where(col("a").is_null()).to_pydict()
    assert len(out["a"]) == 110


def test_remote_parquet_is_planned_without_the_footer_store(
        remote_dataset, monkeypatch):
    """A remote file has no ``(size, mtime_ns)`` to be trusted by: its
    footer is fetched for every plan, kept by nobody and tallied nowhere."""
    from daft_tpu import tracing
    from daft_tpu.io import footers
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    footers.get_store().clear()
    for _ in range(2):
        out = dt.read_parquet(remote_dataset).where(col("seq") < 150) \
            .select("seq").to_pydict()
        assert sorted(out["seq"]) == list(range(150))
        assert tracing.finished()[-1]["footers"] == \
            {"from_store": 0, "read": 0}
    assert len(footers.get_store()) == 0


# -------------------------------------------------- inference satellite

def test_remote_csv_schema_from_head_range(store, monkeypatch):
    body = ("x,y\n" + "\n".join(f"{i},{i * 0.5}" for i in range(20000))) \
        .encode()
    _RangeStore.store["csv/big.csv"] = body
    monkeypatch.setenv("DAFT_TPU_IO_INFER_BYTES", "4096")
    before = rp.scan_counters_snapshot()
    df = dt.read_csv(f"{store}/csv/big.csv")
    assert df.schema().column_names == ["x", "y"]
    delta = rp.scan_counters_delta(before)
    # inference fetched a bounded head, not the whole object
    assert 0 < delta.get("bytes_fetched", 0) < len(body)
    out = df.to_pydict()
    assert len(out["x"]) == 20000 and out["x"][:3] == [0, 1, 2]


def test_remote_json_head_inference_falls_back_whole(store, monkeypatch):
    # ONE record larger than the head budget: the truncated head can't
    # parse → whole-object fallback still infers correctly
    rec = '{"a": 1, "blob": "%s"}\n' % ("z" * 9000)
    _RangeStore.store["js/one.json"] = rec.encode()
    monkeypatch.setenv("DAFT_TPU_IO_INFER_BYTES", "1024")
    before = rp.scan_counters_snapshot()
    df = dt.read_json(f"{store}/js/one.json")
    assert set(df.schema().column_names) == {"a", "blob"}
    assert rp.scan_counters_delta(before).get("infer_head_fallbacks", 0) \
        >= 0  # truncation without newline skips the parse attempt
    assert df.to_pydict()["a"] == [1]


def test_chunked_stream_reader_exact_bytes(tmp_path):
    data = os.urandom(50_000)
    p = tmp_path / "blob.bin"
    p.write_bytes(data)
    r = rp.ChunkedObjectReader(LocalSource(), str(p), chunk=7_000)
    got = b""
    while True:
        piece = r.read(4_096)
        if not piece:
            break
        got += piece
    assert got == data
