"""Serving plane: scheduler fairness/admission/cancellation, plan+result
caches with fingerprint invalidation, concurrent-stats isolation, and the
Spark Connect operation-retention sweep."""

import http.server
import os
import threading
import time
import urllib.parse

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import DataType, col, serving, udf
from daft_tpu.execution.cancellation import (CancelToken, QueryCancelled,
                                             cancel_scope, current_token)
from daft_tpu.execution.memory import MemoryManager
from daft_tpu.logical.fingerprint import fingerprint
from daft_tpu.serving import AdmissionRejected, QueryScheduler


def mkdf(d):
    return dt.from_pydict(d)


@pytest.fixture
def sched():
    s = QueryScheduler(concurrency=2, queue_timeout_s=20.0)
    yield s
    s.shutdown()


@pytest.fixture
def parquet_table(tmp_path):
    """A small parquet table on local disk (stat-able → cacheable)."""
    root = tmp_path / "t"
    mkdf({"k": list(range(2000)),
          "g": [i % 7 for i in range(2000)],
          "v": [float(i % 31) for i in range(2000)]}) \
        .write_parquet(str(root))
    return str(root / "*.parquet")


def _agg_query(glob):
    return dt.read_parquet(glob).groupby("g") \
        .agg(col("v").sum().alias("s")).sort("g")


# ------------------------------------------------------------- scheduler

def test_submit_returns_correct_results(sched, parquet_table):
    expected = _agg_query(parquet_table).to_pydict()
    hs = [sched.submit(_agg_query(parquet_table), session=f"s{i % 3}")
          for i in range(6)]
    for h in hs:
        assert h.result(60).to_recordbatch().to_pydict() == expected
        assert h.state == "done"
    assert sched.admission.outstanding == 0


def test_concurrent_stress_mixed_sessions(parquet_table):
    """≥8 mixed queries across ≥3 sessions concurrently: correct results,
    no admission leak, zero lock-order cycles when the sanitizer is armed.
    (The CI sanitizer job runs this whole suite under DAFT_TPU_SANITIZE=1.)
    """
    sched = QueryScheduler(concurrency=4)
    try:
        shapes = {
            "agg": lambda: _agg_query(parquet_table),
            "topk": lambda: dt.read_parquet(parquet_table)
            .sort("v", desc=True).limit(5).select("k", "v"),
            "lookup": lambda: dt.read_parquet(parquet_table)
            .where(col("k") == 123).select("k", "g"),
            "mem_join": lambda: mkdf({"a": [1, 2, 3], "b": [10, 20, 30]})
            .join(mkdf({"a": [2, 3, 4], "c": [5, 6, 7]}), on="a"),
        }
        expected = {name: fac().to_pydict() for name, fac in shapes.items()}
        names = ["agg", "topk", "lookup", "mem_join"] * 3  # 12 queries
        hs = [(n, sched.submit(shapes[n](), session=f"sess-{i % 3}"))
              for i, n in enumerate(names)]
        for n, h in hs:
            got = h.result(120).to_recordbatch().to_pydict()
            assert got == expected[n], f"{n} diverged under concurrency"
        assert sched.admission.outstanding == 0
        from daft_tpu.analysis import lock_sanitizer
        if lock_sanitizer.is_enabled():
            assert int(lock_sanitizer.counters_snapshot()
                       .get("graph_cycles", 0)) == 0
    finally:
        sched.shutdown()


def _gated_query(gate: threading.Event, started: threading.Event = None,
                 tag=None, order=None):
    """An in-memory query whose single morsel blocks on ``gate`` (and
    optionally records ``tag`` into ``order`` when it runs)."""

    @udf(return_dtype=DataType.int64())
    def block(s):
        if started is not None:
            started.set()
        if order is not None:
            order.append(tag)
        gate.wait(30)
        return s.to_pylist()

    return mkdf({"a": [1]}).select(block(col("a")))


def test_weighted_fair_share_ordering():
    """concurrency=1: queued sessions drain by stride — weight 2 gets two
    dispatches for every one of weight 1; FIFO within a session."""
    sched = QueryScheduler(concurrency=1, queue_timeout_s=60.0)
    try:
        gate0 = threading.Event()
        started = threading.Event()
        blocker = sched.submit(_gated_query(gate0, started), session="z")
        assert started.wait(20)  # worker is now pinned; queue builds below
        order = []
        done_gate = threading.Event()
        done_gate.set()  # queued queries don't block, only record
        hs = []
        for i in range(6):
            hs.append(sched.submit(
                _gated_query(done_gate, tag="A", order=order),
                session="A", weight=2.0))
        for i in range(3):
            hs.append(sched.submit(
                _gated_query(done_gate, tag="B", order=order),
                session="B", weight=1.0))
        gate0.set()
        blocker.result(60)
        for h in hs:
            h.result(60)
        # stride with weights 2:1 → in any prefix of 3k dispatches, A has
        # ~2k; check the first 6 recorded dispatches carry 4 A / 2 B
        first6 = order[:6]
        assert first6.count("A") == 4 and first6.count("B") == 2, order
        # FIFO within a session is positional: all hs per session resolve
        assert all(h.state == "done" for h in hs)
    finally:
        sched.shutdown()


def test_priority_dispatches_first():
    sched = QueryScheduler(concurrency=1, queue_timeout_s=60.0)
    try:
        gate0 = threading.Event()
        started = threading.Event()
        blocker = sched.submit(_gated_query(gate0, started), session="z")
        assert started.wait(20)
        order = []
        open_gate = threading.Event()
        open_gate.set()
        lo = sched.submit(_gated_query(open_gate, tag="lo", order=order),
                          session="s", priority=0)
        hi = sched.submit(_gated_query(open_gate, tag="hi", order=order),
                          session="s2", priority=5)
        gate0.set()
        blocker.result(60)
        lo.result(60)
        hi.result(60)
        assert order == ["hi", "lo"]
    finally:
        sched.shutdown()


def test_cancel_running_query_releases_admission():
    sched = QueryScheduler(concurrency=1, queue_timeout_s=60.0)
    try:
        gate = threading.Event()
        started = threading.Event()
        h = sched.submit(_gated_query(gate, started), session="s")
        assert started.wait(20)
        assert sched.admission.outstanding > 0  # admitted while running
        h.cancel("test cancel")
        gate.set()  # morsel finishes; executor sees the token next
        with pytest.raises(QueryCancelled):
            h.result(60)
        assert h.state == "cancelled"
        deadline = time.time() + 10
        while sched.admission.outstanding and time.time() < deadline:
            time.sleep(0.02)
        assert sched.admission.outstanding == 0  # admission released
    finally:
        sched.shutdown()


def test_cancel_queued_query_is_immediate():
    sched = QueryScheduler(concurrency=1, queue_timeout_s=60.0)
    try:
        gate = threading.Event()
        started = threading.Event()
        blocker = sched.submit(_gated_query(gate, started), session="s")
        assert started.wait(20)
        queued = sched.submit(mkdf({"a": [1]}).select(col("a")),
                              session="s")
        queued.cancel()
        with pytest.raises(QueryCancelled):
            queued.result(5)
        assert queued.state == "cancelled"
        gate.set()
        blocker.result(60)
        assert sched.admission.outstanding == 0
    finally:
        sched.shutdown()


def test_queue_timeout_rejects_without_admission():
    sched = QueryScheduler(concurrency=1, queue_timeout_s=60.0)
    try:
        gate = threading.Event()
        started = threading.Event()
        blocker = sched.submit(_gated_query(gate, started), session="s")
        assert started.wait(20)
        held = sched.admission.outstanding
        late = sched.submit(mkdf({"a": [1]}).select(col("a")),
                            session="s", timeout_s=0.3)
        with pytest.raises(AdmissionRejected) as ei:
            late.result(30)
        assert ei.value.kind == "queue_timeout"
        assert late.state == "rejected"
        assert sched.admission.outstanding == held  # never admitted
        gate.set()
        blocker.result(60)
        assert sched.admission.outstanding == 0
    finally:
        sched.shutdown()


def test_queue_full_rejection():
    sched = QueryScheduler(concurrency=1, queue_depth=1,
                           queue_timeout_s=60.0)
    try:
        gate = threading.Event()
        started = threading.Event()
        blocker = sched.submit(_gated_query(gate, started), session="s")
        assert started.wait(20)
        q1 = sched.submit(mkdf({"a": [1]}).select(col("a")), session="s")
        q2 = sched.submit(mkdf({"a": [1]}).select(col("a")), session="s")
        with pytest.raises(AdmissionRejected) as ei:
            q2.result(5)
        assert ei.value.kind == "queue_full"
        gate.set()
        blocker.result(60)
        q1.result(60)
    finally:
        sched.shutdown()


def test_memory_rejection_is_structured():
    sched = QueryScheduler(concurrency=1, memory_budget=1 << 20)
    try:
        h = sched.submit(mkdf({"a": [1]}).select(col("a")),
                         est_bytes=10 << 20)
        with pytest.raises(AdmissionRejected) as ei:
            h.result(30)
        assert ei.value.kind == "memory"
        assert ei.value.est_bytes == 10 << 20
        assert ei.value.budget == 1 << 20
        assert sched.admission.outstanding == 0
    finally:
        sched.shutdown()


def test_memory_manager_try_acquire_deadline_and_cancel():
    m = MemoryManager(budget=100)
    m.acquire(80)
    t0 = time.monotonic()
    assert m.try_acquire(50, deadline=time.monotonic() + 0.3) is False
    assert time.monotonic() - t0 < 5
    tok = CancelToken()
    tok.set()
    assert m.try_acquire(50, cancel=tok) is False
    m.release(80)
    assert m.try_acquire(50, deadline=time.monotonic() + 0.3) is True
    assert m.outstanding == 50
    m.release(50)
    assert m.outstanding == 0


def test_cancel_scope_threads_token_into_executor():
    tok = CancelToken()
    with cancel_scope(tok):
        assert current_token() is tok
        from daft_tpu.execution.pipeline import PushExecutor
        ex = PushExecutor()
        assert ex.cancel_token is tok
    assert current_token() is None


# ---------------------------------------------------------------- caches

def test_result_cache_hit_and_source_invalidation(tmp_path):
    root = tmp_path / "t"
    mkdf({"g": [1, 1, 2], "v": [1.0, 2.0, 3.0]}).write_parquet(str(root))
    glob = str(root / "*.parquet")
    sched = QueryScheduler(concurrency=1)
    try:
        h1 = sched.submit(_agg_query(glob))
        r1 = h1.result(60).to_recordbatch().to_pydict()
        assert h1.stats.serving["result_cache"] == "miss"
        h2 = sched.submit(_agg_query(glob))
        r2 = h2.result(60).to_recordbatch().to_pydict()
        assert h2.stats.serving["result_cache"] == "hit"
        assert r1 == r2
        # rewrite the source (content AND stat change) → both caches bust
        time.sleep(0.02)  # ensure a distinct mtime_ns even on coarse fs
        mkdf({"g": [1, 1, 2], "v": [10.0, 20.0, 30.0]}) \
            .write_parquet(str(root), write_mode="overwrite")
        h3 = sched.submit(_agg_query(glob))
        r3 = h3.result(60).to_recordbatch().to_pydict()
        assert h3.stats.serving["result_cache"] == "miss"
        assert r3["s"] == [30.0, 30.0]
    finally:
        sched.shutdown()


def test_plan_cache_hit_when_result_cache_disabled(parquet_table):
    sched = QueryScheduler(concurrency=1, result_cache_bytes=0)
    try:
        h1 = sched.submit(_agg_query(parquet_table))
        h1.result(60)
        assert h1.stats.serving["plan_cache"] == "miss"
        h2 = sched.submit(_agg_query(parquet_table))
        h2.result(60)
        assert h2.stats.serving["plan_cache"] == "hit"
        assert h2.stats.serving["result_cache"] == "bypass"
        snap = sched.counters_snapshot()
        assert snap["plan_cache_hits"] >= 1
    finally:
        sched.shutdown()


def test_config_change_busts_plan_cache(parquet_table):
    from daft_tpu.context import execution_config_ctx
    sched = QueryScheduler(concurrency=1, result_cache_bytes=0)
    try:
        sched.submit(_agg_query(parquet_table)).result(60)
        with execution_config_ctx(default_morsel_size=999):
            h = sched.submit(_agg_query(parquet_table))
            h.result(60)
            assert h.stats.serving["plan_cache"] == "miss"
    finally:
        sched.shutdown()


def test_fingerprint_literal_stripping_and_volatility(tmp_path,
                                                      parquet_table):
    from daft_tpu.context import get_context
    cfg = get_context().execution_config
    b1 = dt.read_parquet(parquet_table).where(col("v") > 5)._builder.plan
    b2 = dt.read_parquet(parquet_table).where(col("v") > 9)._builder.plan
    f1, f2 = fingerprint(b1, cfg), fingerprint(b2, cfg)
    assert f1 is not None and f2 is not None
    assert f1.structure == f2.structure       # literal-stripped shape
    assert f1.params != f2.params             # bound-parameter vector
    assert f1.key != f2.key
    # identical text → identical key
    b3 = dt.read_parquet(parquet_table).where(col("v") > 5)._builder.plan
    assert fingerprint(b3, cfg).key == f1.key
    # in-memory sources are uncacheable (pinning + id-reuse hazards)
    assert fingerprint(mkdf({"a": [1]}).select(col("a"))._builder.plan,
                       cfg) is None
    # UDF callables are uncacheable (repr address reuse)
    @udf(return_dtype=DataType.int64())
    def f(s):
        return s.to_pylist()
    assert fingerprint(
        dt.read_parquet(parquet_table).select(f(col("k")))._builder.plan,
        cfg) is None


def test_lru_byte_budget_evicts():
    from daft_tpu.serving.caches import _LRUCache
    c = _LRUCache(100)
    c.put(("a",), 1, 40)
    c.put(("b",), 2, 40)
    c.put(("c",), 3, 40)           # evicts ("a",)
    assert c.get(("a",)) is None
    assert c.get(("b",)) == 2
    assert c.stats()["evictions"] == 1
    c.put(("huge",), 4, 200)       # over budget → not stored
    assert c.get(("huge",)) is None


def test_serving_block_rendered_in_explain(parquet_table):
    sched = QueryScheduler(concurrency=1)
    try:
        h = sched.submit(_agg_query(parquet_table), session="render-s",
                         priority=2)
        h.result(60)
        text = h.stats.render()
        assert "serving (query scheduler):" in text
        assert "session=render-s" in text
        assert "priority=2" in text
        # a result-cache hit still renders a serving block
        h2 = sched.submit(_agg_query(parquet_table), session="render-s")
        h2.result(60)
        assert "result cache: hit" in h2.stats.render()
    finally:
        sched.shutdown()


# ------------------------------------------- concurrent stats isolation

class _Store(http.server.BaseHTTPRequestHandler):
    store = {}

    def log_message(self, *a):
        pass

    def _key(self):
        return urllib.parse.urlparse(self.path).path.lstrip("/")

    def do_HEAD(self):
        data = self.store.get(self._key())
        if data is None:
            self.send_response(404)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()

    def do_GET(self):
        data = self.store.get(self._key())
        if data is None:
            self.send_response(404)
            self.end_headers()
            return
        rng = self.headers.get("Range")
        if rng:
            a, b = rng.split("=")[1].split("-")
            start, end = int(a), min(int(b), len(data) - 1)
            chunk = data[start:end + 1]
            self.send_response(206)
        else:
            chunk = data
            self.send_response(200)
        self.send_header("Content-Length", str(len(chunk)))
        self.end_headers()
        self.wfile.write(chunk)


@pytest.fixture
def http_parquet():
    import io as _io
    buf = _io.BytesIO()
    pq.write_table(pa.table({
        "g": pa.array([i % 5 for i in range(4000)]),
        "v": pa.array([float(i) for i in range(4000)]),
    }), buf, row_group_size=500)
    _Store.store = {"ds/p.parquet": buf.getvalue()}
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Store)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_port}/ds/p.parquet"
    srv.shutdown()


def test_two_concurrent_queries_have_isolated_io_stats(http_parquet):
    """The r11 bugfix: per-query io/shuffle/recovery stats were diffed
    from process-wide counters, so two overlapping queries read each
    other's traffic. With context attribution, a pure in-memory query
    must show ZERO io no matter what scans run concurrently."""
    sched = QueryScheduler(concurrency=4)
    stop = threading.Event()
    scan_handles, mem_handles = [], []
    try:
        def scanner():
            while not stop.is_set() and len(scan_handles) < 6:
                h = sched.submit(
                    dt.read_parquet(http_parquet).groupby("g")
                    .agg(col("v").sum()), session="scan-sess")
                h.result(60)
                scan_handles.append(h)

        t = threading.Thread(target=scanner, daemon=True)
        t.start()
        for _ in range(6):
            h = sched.submit(
                mkdf({"x": [1, 2, 3, 4]}).agg(col("x").sum()),
                session="mem-sess")
            h.result(60)
            mem_handles.append(h)
        stop.set()
        t.join(timeout=90)
        assert scan_handles, "scanner never completed a query"
        # the scanning queries observed real io traffic…
        assert any(h.stats.io.get("gets", 0) > 0 for h in scan_handles)
        # …and the in-memory queries observed NONE of it
        for h in mem_handles:
            assert h.stats.io.get("gets", 0) == 0, h.stats.io
            assert h.stats.io.get("bytes_fetched", 0) == 0
    finally:
        stop.set()
        sched.shutdown()


# ------------------------------------------------- connect op retention

def test_operation_retention_ttl_and_byte_sweep(monkeypatch):
    grpc = pytest.importorskip("grpc")  # noqa: F841 — server needs it
    from daft_tpu.connect.server import SparkConnectServer, _Operation

    srv = SparkConnectServer()
    try:
        st = srv._session("sweep-sess")

        class _Resp:
            def __init__(self, n):
                self._n = n
                self.response_id = f"r{n}"

            def ByteSize(self):
                return self._n

        def finished_op(op_id, nbytes, age_s):
            op = _Operation(op_id, (), reattachable=True)
            op.record(_Resp(nbytes))
            op.finish()
            op.finished_at = time.monotonic() - age_s
            st.operations[op_id] = op
            return op

        # TTL sweep: an old finished op is dropped, a fresh one kept
        monkeypatch.setenv("DAFT_TPU_SERVE_OP_TTL", "100")
        finished_op("old", 10, age_s=1000)
        finished_op("fresh", 10, age_s=1)
        srv._session("sweep-sess")
        assert "old" not in st.operations
        assert "fresh" in st.operations

        # byte-budget sweep: newest kept first, the rest dropped
        st.operations.pop("fresh")  # would otherwise occupy the budget
        monkeypatch.setenv("DAFT_TPU_SERVE_OP_RETAIN_BYTES", "25")
        finished_op("b1", 20, age_s=30)
        finished_op("b2", 20, age_s=20)
        finished_op("b3", 20, age_s=10)
        srv._session("sweep-sess")
        kept = set(st.operations)
        assert "b3" in kept and "b1" not in kept and "b2" not in kept

        # a RUNNING operation is never swept, regardless of budget
        running = _Operation("running", (), reattachable=True)
        running.record(_Resp(1000))
        st.operations["running"] = running
        srv._session("sweep-sess")
        assert "running" in st.operations
    finally:
        srv.stop()


def test_operation_cancel_callbacks_fire():
    from daft_tpu.connect.server import _Operation
    op = _Operation("x", (), reattachable=False)
    fired = []
    op.bind_cancel(lambda: fired.append(1))
    op.request_cancel()
    assert fired == [1]
    # late binding on an already-cancelled op fires immediately
    op.bind_cancel(lambda: fired.append(2))
    assert fired == [1, 2]


def test_projection_compile_is_single_flight(monkeypatch):
    """N concurrent cold queries tracing the SAME projection must compile
    once: the losers wait on the winner's event instead of burning
    duplicate (multi-second on TPU) trace+lowering work."""
    from daft_tpu.device import runtime as drt
    from daft_tpu.schema import Field, Schema

    calls = []
    call_lock = threading.Lock()

    class _FakeCompiled:
        needs_cols = ()

    def slow_compile(exprs, schema):
        with call_lock:
            calls.append(1)
        time.sleep(0.2)
        return _FakeCompiled()

    monkeypatch.setattr(drt.compiler, "compile_projection", slow_compile)
    schema = Schema([Field("serve_sf_test", DataType.int64())])
    exprs = [(col("serve_sf_test") + 1).alias("out")]
    results = []

    def run():
        results.append(drt._get_compiled(exprs, schema))

    threads = [threading.Thread(target=run) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    assert len(calls) == 1, f"{len(calls)} duplicate compiles"
    assert len(results) == 8
    assert all(r is results[0] for r in results)  # one shared program


def test_live_view_shape(sched):
    view = sched.live_view()
    assert view["concurrency"] == 2
    assert "admitted_bytes" in view and "counters" in view
    assert isinstance(view["sessions"], dict)


# ----------------------------------------------- review-hardening fixes

def test_serve_memory_zero_disables_admission(monkeypatch):
    """DAFT_TPU_SERVE_MEMORY=0 must disable admission outright, not fall
    back to the engine memory limit inside MemoryManager."""
    monkeypatch.setenv("DAFT_TPU_SERVE_MEMORY", "0")
    monkeypatch.setenv("DAFT_TPU_MEMORY_LIMIT", "1GiB")
    s = QueryScheduler(concurrency=1)
    try:
        assert s.admission.budget is None
        assert s.admission.try_acquire(1 << 50)  # nothing gates
        assert s.admission.outstanding == 0
    finally:
        s.shutdown()


def test_estimate_runs_outside_scheduler_lock(sched, monkeypatch):
    """The cost-model footprint estimate can do real IO (remote footer
    reads); submit() must not hold the scheduler condition across it."""
    in_estimate = threading.Event()
    release = threading.Event()

    def slow_estimate(self, builder):
        in_estimate.set()
        assert release.wait(10), "estimator never released"
        return 1 << 20

    monkeypatch.setattr(QueryScheduler, "_estimate_bytes", slow_estimate)
    hs = []
    t = threading.Thread(
        target=lambda: hs.append(sched.submit(mkdf({"a": [1]}))),
        daemon=True)
    t.start()
    assert in_estimate.wait(10)
    # while the submitter sits in the estimator, the scheduler lock must
    # be free for workers / the sweep / the dashboard
    acquired = sched._cond.acquire(timeout=2.0)
    try:
        assert acquired, "submit held the scheduler lock across the " \
            "footprint estimate"
    finally:
        if acquired:
            sched._cond.release()
    release.set()
    t.join(20)
    assert hs and hs[0].result(30).to_recordbatch().to_pydict() == \
        {"a": [1]}


def test_idle_sessions_are_swept(monkeypatch):
    """Session queues are client-keyed (Connect mints one UUID per
    session); drained sessions must not accumulate forever."""
    from daft_tpu.serving import scheduler as sched_mod
    s = QueryScheduler(concurrency=2)
    try:
        hs = [s.submit(mkdf({"a": [i]}), session=f"uuid-{i}")
              for i in range(6)]
        for h in hs:
            h.result(60)
        monkeypatch.setattr(sched_mod, "_SESSION_IDLE_TTL_S", 0.0)
        with s._cond:
            s._sweep_expired_locked()   # marks empties idle
        time.sleep(0.01)
        with s._cond:
            s._sweep_expired_locked()   # TTL elapsed → dropped
            assert s._sessions == {}
        # a returning session is simply re-created
        h = s.submit(mkdf({"a": [9]}), session="uuid-0")
        assert h.result(60).to_recordbatch().to_pydict() == {"a": [9]}
    finally:
        s.shutdown()


def test_unstable_literal_is_uncacheable():
    """Literals key the result cache, so only faithful-repr types may
    fingerprint; a truncated/recycled repr (numpy-style) must bypass."""
    import datetime
    import decimal

    from daft_tpu.logical.fingerprint import _Uncacheable, _canon_lit

    class Truncates:  # reprs like a numpy array: plausible, lossy
        def __repr__(self):
            return "[0, 1, ..., 1999]"

    assert _canon_lit(7) == "7"
    assert _canon_lit([1, "x", None]) == "[1,'x',None]"
    assert _canon_lit({"b": 2, "a": 1}) == "{'a':1,'b':2}"
    assert "2026" in _canon_lit(datetime.date(2026, 8, 3))
    assert "3.14" in _canon_lit(decimal.Decimal("3.14"))
    for bad in (Truncates(), [1, Truncates()], {"k": Truncates()},
                object(), lambda: 1):
        with pytest.raises(_Uncacheable):
            _canon_lit(bad)


def test_attributed_device_kernels_isolated():
    """Two attributed contexts must each see only their own dispatches,
    not a diff of the shared ledger spanning both."""
    from daft_tpu import observability as obs
    from daft_tpu.device import costmodel

    c1, c2 = obs.RuntimeStatsContext(), obs.RuntimeStatsContext()
    with obs.attributed(c1):
        costmodel.ledger_record("serve_test_argsort", rows=10,
                                nbytes=1e6, seconds=0.01)
    with obs.attributed(c2):
        costmodel.ledger_record("serve_test_join", rows=5,
                                nbytes=2e6, flops=1e6, seconds=0.02)
    c1.finish()
    c2.finish()
    assert set(c1.device_kernels) == {"serve_test_argsort"}
    assert set(c2.device_kernels) == {"serve_test_join"}
    assert c1.device_kernels["serve_test_argsort"]["rows"] == 10
    assert c2.device_kernels["serve_test_join"]["dispatches"] == 1
    assert "achieved_tflops" in c2.device_kernels["serve_test_join"]


def test_cancel_unwinds_noncacheable_runner_drain(monkeypatch):
    """Distributed/AQE runners bypass the caches and don't thread the
    CancelToken into their workers; the scheduler's drain loop must
    check it per partition so INTERRUPT releases admission mid-query."""
    import daft_tpu.context as ctx_mod
    from daft_tpu.micropartition import MicroPartition

    first_part = threading.Event()
    proceed = threading.Event()

    class _FakeRunner:  # not a NativeRunner → non-cacheable path
        def run_iter(self, builder):
            yield MicroPartition.from_pydict({"a": [1]})
            first_part.set()
            proceed.wait(20)
            yield MicroPartition.from_pydict({"a": [2]})

    monkeypatch.setattr(ctx_mod.get_context(), "get_or_create_runner",
                        lambda: _FakeRunner())
    s = QueryScheduler(concurrency=1, memory_budget=1 << 30)
    try:
        h = s.submit(mkdf({"a": [0]}), est_bytes=1 << 20)
        assert first_part.wait(20)
        h.cancel()
        proceed.set()
        with pytest.raises(QueryCancelled):
            h.result(20)
        assert h.state == "cancelled"
        deadline = time.monotonic() + 10
        while s.admission.outstanding and time.monotonic() < deadline:
            time.sleep(0.01)
        assert s.admission.outstanding == 0
    finally:
        s.shutdown()


# ----------------------- r14 lifecycle regressions (daft-lint flow pass)

def test_admission_released_when_prerun_bookkeeping_raises(
        parquet_table, monkeypatch):
    """r14 regression (found by daft-lint memory-admission-leak): an
    exception between a successful try_acquire and the run-worker's
    try-block — here the handle's running transition — used to leak the
    admitted bytes AND the worker's running slot for the process
    lifetime (the worker thread died, so the handle never completed)."""
    from daft_tpu.serving import scheduler as sched_mod
    sched = QueryScheduler(concurrency=1, memory_budget=1 << 30,
                           queue_timeout_s=30.0)
    try:
        orig = sched_mod.QueryHandle._mark_running

        def boom(self):
            raise RuntimeError("bookkeeping exploded")

        monkeypatch.setattr(sched_mod.QueryHandle, "_mark_running", boom)
        h = sched.submit(_agg_query(parquet_table))
        with pytest.raises(RuntimeError, match="bookkeeping exploded"):
            h.result(30)
        assert h.state == "failed"
        assert sched.admission.outstanding == 0
        # the worker slot survived: a healthy query still runs on it
        monkeypatch.setattr(sched_mod.QueryHandle, "_mark_running", orig)
        h2 = sched.submit(_agg_query(parquet_table))
        assert h2.result(30).to_recordbatch().to_pydict() \
            == _agg_query(parquet_table).to_pydict()
        assert sched.admission.outstanding == 0
    finally:
        sched.shutdown()


def test_breaker_drain_polls_cancellation():
    """r14 regression (daft-lint uncancellable-loop): a pipeline
    breaker's consume loop (sort sampling, bucket stores) drains its
    whole child before yielding — without the in-loop poll, INTERRUPT
    ran the drain to completion while holding admission."""
    from daft_tpu.execution.executor import LocalExecutor
    from daft_tpu.micropartition import MicroPartition

    tok = CancelToken()
    with cancel_scope(tok):
        ex = LocalExecutor()  # captures the scope's token
    mp = MicroPartition.from_pydict({"x": [1.0, 2.0, 3.0]})
    seen = {"n": 0}

    def stream():
        for _ in range(100):
            seen["n"] += 1
            if seen["n"] == 3:
                tok.set("client interrupt")
            yield mp

    with pytest.raises(QueryCancelled):
        ex._consume_sampling(stream(), [col("x")])
    assert seen["n"] <= 4, "drain kept running after the token fired"

    # the bucket-store drain polls too
    tok2 = CancelToken()
    with cancel_scope(tok2):
        ex2 = LocalExecutor()
    seen["n"] = 0

    def stream2():
        for _ in range(100):
            seen["n"] += 1
            if seen["n"] == 3:
                tok2.set("client interrupt")
            yield mp

    with pytest.raises(QueryCancelled):
        ex2._key_bucket_store(stream2(), [col("x")], 4)
    assert seen["n"] <= 4
