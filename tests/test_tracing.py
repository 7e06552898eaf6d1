"""Query-wide tracing plane tests: span propagation, deterministic ids
under chaos, Chrome/OTLP export, /metrics scrape, flight recorder."""

import glob
import json
import os
import threading
import time

import pytest

import daft_tpu as daft
from daft_tpu import col, tracing
from daft_tpu import observability as obs


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


def _run_distributed(monkeypatch, n_workers=2, fault_spec=None, seed="7"):
    """One distributed grouped-agg query; returns (answer, recorder)."""
    import daft_tpu.context as dctx
    from daft_tpu.distributed import resilience as rz
    from daft_tpu.runners.distributed_runner import DistributedRunner

    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    monkeypatch.setenv("DAFT_TPU_RETRY_BACKOFF", "0.01")
    if fault_spec:
        monkeypatch.setenv("DAFT_TPU_FAULT_SPEC", fault_spec)
        monkeypatch.setenv("DAFT_TPU_FAULT_SEED", seed)
    rz.reset_for_tests()
    runner = DistributedRunner(num_workers=n_workers)
    old = dctx.get_context()._runner
    dctx.get_context().set_runner(runner)
    try:
        df = (daft.from_pydict({"k": [i % 7 for i in range(4000)],
                                "v": [float(i) for i in range(4000)]})
              .into_partitions(3)
              .groupby("k").agg(col("v").sum().alias("s")))
        out = df.to_pydict()
    finally:
        dctx.get_context().set_runner(old)
        if runner._manager is not None:
            runner._manager.shutdown()
        rz.reset_for_tests()
    stats = obs.last_query_stats()
    assert stats is not None and stats.trace_ctx is not None
    rows = sorted(zip(out["k"], [round(s, 6) for s in out["s"]]))
    return rows, stats.trace_ctx.recorder


# ------------------------------------------------------------ gating

def test_tracing_off_by_default():
    df = daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1)
    df.collect()
    stats = obs.last_query_stats()
    assert stats.trace_ctx is None
    assert stats.trace_summary == {}
    # span sites are no-ops on untraced threads
    assert tracing.current() is None
    sp = tracing.span("anything")
    assert sp is tracing._NOOP


def test_sampling_zero_traces_nothing(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_TRACE_SAMPLE", "0.0")
    daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1).collect()
    assert obs.last_query_stats().trace_ctx is None


def test_span_ids_are_pure_functions_of_keys():
    assert tracing.span_id_from("task:s0.t1") == \
        tracing.span_id_from("task:s0.t1")
    assert tracing.span_id_from("task:s0.t1") != \
        tracing.span_id_from("task:s0.t2")
    assert len(tracing.span_id_from("x")) == 16


def test_recorder_bounded(monkeypatch):
    rec = tracing.SpanRecorder("t" * 32, max_spans=5)
    for i in range(10):
        rec.add("s", tracing.span_id_from(f"k{i}"), None, i, 1)
    assert len(rec.spans()) == 5
    assert rec.dropped == 5
    assert rec.summary()["dropped"] == 5


# ----------------------------------------------------- local tracing

def test_local_query_trace_exports_chrome(tmp_path, monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_TRACE_DIR", str(tmp_path))
    df = (daft.from_pydict({"x": list(range(500)),
                            "g": [i % 5 for i in range(500)]})
          .where(col("x") > 10).groupby("g").agg(col("x").sum().alias("s")))
    df.collect()
    stats = obs.last_query_stats()
    assert stats.trace_ctx is not None
    assert stats.trace_summary.get("spans", 0) > 0
    files = glob.glob(str(tmp_path / "trace_*.json"))
    assert files, "no chrome trace exported"
    doc = json.load(open(files[0]))
    assert tracing.validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "query" in names
    assert "plan:optimize" in names and "plan:translate" in names
    assert any(n.startswith("op:") for n in names)
    # explain(analyze=True) renders the trace line
    assert "trace: id=" in stats.render()


def test_trace_registry_unregisters_after_export(tmp_path, monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1).collect()
    rec = obs.last_query_stats().trace_ctx.recorder
    assert rec.exported
    assert tracing.recorder_for(rec.trace_id) is None


# ------------------------------------------------- distributed chaos

def test_chaos_trace_deterministic_and_complete(monkeypatch):
    """The satellite contract: a seeded chaotic distributed query yields
    a merged trace where every retry/lineage-recompute is a child of its
    task span, span ids replay bit-identically across two runs, and no
    span is orphaned."""
    spec = "task:0.1,fetch:0.1,crash:0.1"
    rows1, rec1 = _run_distributed(monkeypatch, fault_spec=spec)
    rows2, rec2 = _run_distributed(monkeypatch, fault_spec=spec)
    assert rows1 == rows2

    # bit-identical span ids across runs
    assert sorted(rec1.span_ids()) == sorted(rec2.span_ids())

    # no orphans: every parent id resolves
    assert tracing.orphan_spans(rec1) == []

    spans = rec1.spans()
    kinds = {s["name"] for s in spans}
    # the merged trace covers driver, stage, worker-task and fetch tiers
    for want in ("query", "stage", "task", "task:run", "shuffle:fetch"):
        assert want in kinds, (want, sorted(kinds))
    # chaos actually fired: retries and/or recomputes present…
    assert "task:retry" in kinds
    # …and every retry / recompute hangs off a task span
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        if s["name"] in ("task:retry", "lineage:recompute"):
            parent = by_id.get(s["parent_id"])
            assert parent is not None and parent["name"] == "task", s
        if s["name"] == "task:run":
            parent = by_id.get(s["parent_id"])
            assert parent is not None and parent["name"] == "task", s
    # chrome export of the merged trace validates
    assert tracing.validate_chrome_trace(
        tracing.chrome_trace_json(rec1)) == []


def test_faultfree_distributed_trace(monkeypatch):
    rows, rec = _run_distributed(monkeypatch)
    kinds = {s["name"] for s in rec.spans()}
    assert "task:run" in kinds and "stage" in kinds
    assert tracing.orphan_spans(rec) == []


def test_remote_worker_ships_spans_cross_process(monkeypatch):
    """A worker in ANOTHER process buffers its spans and ships them back
    with the task result; the driver merges them with clock-offset
    correction into the one query trace."""
    import subprocess
    import sys

    from daft_tpu.distributed import (LeastLoadedScheduler, StagePlan,
                                      StageRunner, WorkerManager)
    from daft_tpu.distributed.remote_worker import RemoteWorker
    from daft_tpu.physical.translate import translate

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DAFT_TPU_TRACE", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "daft_tpu.distributed.remote_worker",
         "--port", "0", "--host", "127.0.0.1"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=repo)
    try:
        line = proc.stdout.readline()  # "daft-tpu worker on http://…"
        addr = line.strip().split()[-1]
        assert addr.startswith("http://"), line
        monkeypatch.setenv("DAFT_TPU_TRACE", "1")
        tctx = tracing.maybe_start_trace("xproc")
        assert tctx is not None
        df = (daft.from_pydict({"k": [i % 5 for i in range(300)],
                                "v": [float(i) for i in range(300)]})
              .into_partitions(2)
              .groupby("k").agg(col("v").sum().alias("s")))
        with tracing.attach(tctx):
            sp = StagePlan.from_physical(
                translate(df._builder.optimize().plan))
            mgr = WorkerManager([RemoteWorker("remote-0", addr)])
            runner = StageRunner(mgr, LeastLoadedScheduler())
            parts = list(runner.run(sp))
        got = {}
        for p in parts:
            d = p.to_pydict()
            for k, s in zip(d.get("k", []), d.get("s", [])):
                got[k] = s
        assert set(got) == {0, 1, 2, 3, 4}
        rec = tctx.recorder
        kinds = {s["name"] for s in rec.spans()}
        assert "rpc:post" in kinds
        assert "task:run" in kinds, sorted(kinds)
        # the worker's spans really crossed the wire: worker-lane spans
        # exist and a clock offset was measured for the worker address
        assert any(s["lane"].startswith("worker:")
                   for s in rec.spans() if s["name"] == "task:run")
        assert addr in rec.summary().get("clock_offsets_us", {})
        assert tracing.orphan_spans(rec) == []
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# ------------------------------------------------------ wire context

def test_wire_headers_roundtrip():
    rec = tracing.SpanRecorder("ab" * 16)
    tracing.register_recorder(rec)
    ctx = tracing.SpanContext(rec, rec.root_id)
    hdrs = tracing.wire_headers(ctx)
    assert hdrs["X-Daft-Trace-Id"] == rec.trace_id
    back = tracing.context_from_headers(hdrs)
    assert back is not None
    assert back.recorder is rec and back.span_id == rec.root_id
    # unknown trace (other process) → None
    tracing.unregister_recorder(rec.trace_id)
    assert tracing.context_from_headers(hdrs) is None
    assert tracing.context_from_headers({}) is None


def test_remote_span_merge_applies_clock_offset():
    rec = tracing.SpanRecorder("cd" * 16)
    remote = [{"name": "task:run", "span_id": tracing.span_id_from("r"),
               "parent_id": rec.root_id, "ts_us": 1_000_000,
               "dur_us": 5, "lane": "worker:w9"}]
    rec.add_remote(remote, offset_us=250, worker="http://w9:1")
    s = rec.spans()[0]
    assert s["ts_us"] == 1_000_250
    assert rec.summary()["clock_offsets_us"] == {"http://w9:1": 250}
    # malformed remote spans are counted, not raised
    rec.add_remote([{"nope": 1}], 0, "w")
    assert rec.dropped == 1


# ------------------------------------------------------ chrome schema

def test_chrome_validator_catches_bad_traces():
    assert tracing.validate_chrome_trace({}) == \
        ["traceEvents is not a list"]
    bad_phase = {"traceEvents": [
        {"name": "x", "ph": "Q", "pid": 1, "tid": 1}]}
    assert tracing.validate_chrome_trace(bad_phase)
    neg_ts = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": -5, "dur": 1}]}
    assert tracing.validate_chrome_trace(neg_ts)
    non_monotonic = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 10, "dur": 1},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5, "dur": 1}]}
    assert any("non-monotonic" in p
               for p in tracing.validate_chrome_trace(non_monotonic))
    unmatched = {"traceEvents": [
        {"name": "a", "ph": "B", "pid": 1, "tid": 1, "ts": 1}]}
    assert any("unmatched B" in p
               for p in tracing.validate_chrome_trace(unmatched))
    ok = {"traceEvents": [
        {"name": "a", "ph": "B", "pid": 1, "tid": 1, "ts": 1},
        {"name": "a", "ph": "E", "pid": 1, "tid": 1, "ts": 2}]}
    assert tracing.validate_chrome_trace(ok) == []


# ---------------------------------------------------------- /metrics

def test_prometheus_text_parses_strictly():
    text = tracing.prometheus_text()
    metrics = tracing.parse_prometheus_text(text)
    assert "daft_tpu_flight_recorder_queries_total" in metrics
    assert "daft_tpu_traces_active" in metrics
    for bad in ("no value\n", "0badname 1\n", "m 1 2 3\n", "m notanum\n",
                "# TYPE m sometype\n"):
        with pytest.raises(ValueError):
            tracing.parse_prometheus_text(bad)


def test_metrics_endpoint_and_serving_gauges(monkeypatch):
    import urllib.request

    from daft_tpu import dashboard, serving

    sched = serving.QueryScheduler(concurrency=1)
    monkeypatch.setattr(serving, "_shared", sched)
    port = dashboard.launch(0)
    try:
        df = daft.from_pydict({"x": list(range(100)),
                               "g": [i % 4 for i in range(100)]}) \
            .groupby("g").agg(col("x").sum().alias("s"))
        sched.submit(df).result(timeout=60)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        metrics = tracing.parse_prometheus_text(text)
        assert metrics.get("daft_tpu_serving_completed_total", 0) >= 1
        assert "daft_tpu_serving_queue_depth" in metrics
        assert "daft_tpu_serving_running" in metrics
    finally:
        dashboard.shutdown()
        monkeypatch.setattr(serving, "_shared", None)
        sched.shutdown()


# ----------------------------------------------------- flight recorder

def test_flight_recorder_records_and_rotates(tmp_path, monkeypatch):
    path = str(tmp_path / "queries.jsonl")
    monkeypatch.setenv("DAFT_TPU_QUERY_LOG", path)
    monkeypatch.setenv("DAFT_TPU_QUERY_LOG_BYTES", "4000")
    monkeypatch.setenv("DAFT_TPU_SLOW_QUERY_MS", "0.000001")
    daft.from_pydict({"x": list(range(50))}).where(col("x") > 5).collect()
    entries = tracing.flight_history()
    assert entries, "no flight-recorder entry for the query"
    e = entries[0]
    assert e["wall_us"] > 0 and "operators" in e
    assert e["slow"] is True  # any query beats a 1ns threshold
    # rotation: write entries past the byte cap
    for i in range(100):
        tracing.flight_record({"i": i, "pad": "x" * 128})
    assert os.path.exists(path + ".1"), "no rotated generation"
    assert os.path.getsize(path) <= 4000
    # history reads across generations, newest first
    hist = tracing.flight_history(limit=10)
    assert len(hist) == 10 and hist[0]["i"] == 99


def test_flight_recorder_history_endpoint(tmp_path, monkeypatch):
    import urllib.request

    from daft_tpu import dashboard

    monkeypatch.setenv("DAFT_TPU_QUERY_LOG",
                       str(tmp_path / "queries.jsonl"))
    daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1).collect()
    port = dashboard.launch(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/history", timeout=10) as r:
            hist = json.loads(r.read())
        assert hist and "wall_us" in hist[0]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10) as r:
            page = r.read().decode()
        assert "flight recorder" in page
    finally:
        dashboard.shutdown()


# ------------------------------------------------- dashboard history cap

def test_dashboard_history_bounded_by_count_and_bytes(monkeypatch):
    from daft_tpu import dashboard

    monkeypatch.setattr(dashboard, "_history", [])
    monkeypatch.setattr(dashboard, "_history_bytes", [])
    monkeypatch.setattr(dashboard, "_MAX_HISTORY", 10)
    monkeypatch.setattr(dashboard, "_MAX_HISTORY_BYTES", 3000)

    class FakeStats:
        def as_dict(self):
            return {"Op": {"rows_out": 1}}

        def render(self, plan=None):
            return "explain " + "y" * 400  # ~420B entries

    for _ in range(50):
        dashboard.broadcast_query(FakeStats())
    assert len(dashboard._history) <= 10
    assert sum(dashboard._history_bytes) <= 3000
    # byte cap binds before the count cap with these sizes
    assert len(dashboard._history) < 10
    # the newest entry always survives
    assert dashboard._history[-1]["explain"].startswith("explain")


# -------------------------------------------------- otlp hardening

class _StubCollector:
    """OTLP collector stub: mode 'ok' | 'hang' | '500'."""

    def __init__(self, mode):
        import http.server

        stub = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                if stub.mode == "hang":
                    stub.hung.wait(20)
                    return
                stub.received.append((self.path, json.loads(body)))
                code = 500 if stub.mode == "500" else 200
                self.send_response(code)
                self.end_headers()
                self.wfile.write(b"{}")
                stub.got.set()

            def log_message(self, *a):
                pass

        import http.server as hs
        self.mode = mode
        self.received = []
        self.got = threading.Event()
        self.hung = threading.Event()
        self.srv = hs.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.srv.serve_forever,
                         daemon=True).start()

    @property
    def endpoint(self):
        return f"http://127.0.0.1:{self.srv.server_port}"

    def shutdown(self):
        self.hung.set()
        self.srv.shutdown()


def test_otlp_hung_collector_never_stalls_query(monkeypatch):
    stub = _StubCollector("hang")
    try:
        monkeypatch.setenv("DAFT_TPU_OTLP_ENDPOINT", stub.endpoint)
        monkeypatch.setenv("DAFT_TPU_OTLP_TIMEOUT", "0.3")
        before = obs.obs_counters_snapshot().get("otlp_export_errors", 0)
        t0 = time.monotonic()
        out = daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1) \
            .count_rows()
        elapsed = time.monotonic() - t0
        assert out == 2
        # the query path never blocks on the hung POST
        assert elapsed < 10
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if obs.obs_counters_snapshot().get(
                    "otlp_export_errors", 0) > before:
                break
            time.sleep(0.05)
        assert obs.obs_counters_snapshot().get(
            "otlp_export_errors", 0) > before
    finally:
        stub.shutdown()


def test_otlp_500_counted_not_raised(monkeypatch):
    stub = _StubCollector("500")
    try:
        monkeypatch.setenv("DAFT_TPU_OTLP_ENDPOINT", stub.endpoint)
        before = obs.obs_counters_snapshot().get("otlp_export_errors", 0)
        daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1).collect()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if obs.obs_counters_snapshot().get(
                    "otlp_export_errors", 0) > before:
                break
            time.sleep(0.05)
        assert obs.obs_counters_snapshot().get(
            "otlp_export_errors", 0) > before
    finally:
        stub.shutdown()


def test_otlp_spans_posted_for_traced_query(monkeypatch):
    stub = _StubCollector("ok")
    try:
        monkeypatch.setenv("DAFT_TPU_OTLP_ENDPOINT", stub.endpoint)
        monkeypatch.setenv("DAFT_TPU_TRACE", "1")
        daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1).collect()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(p == "/v1/traces" for p, _ in stub.received):
                break
            time.sleep(0.05)
        traces = [b for p, b in stub.received if p == "/v1/traces"]
        assert traces, [p for p, _ in stub.received]
        scope = traces[0]["resourceSpans"][0]["scopeSpans"][0]
        names = {s["name"] for s in scope["spans"]}
        assert "query" in names
        # metrics still export beside spans
        assert any(p == "/v1/metrics" for p, _ in stub.received)
    finally:
        stub.shutdown()


# ------------------------------------------------------- serving plane

def test_serving_trace_has_queue_and_run_spans(monkeypatch):
    from daft_tpu import serving

    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    sched = serving.QueryScheduler(concurrency=1)
    try:
        df = daft.from_pydict({"x": list(range(200)),
                               "g": [i % 3 for i in range(200)]}) \
            .groupby("g").agg(col("x").sum().alias("s"))
        h = sched.submit(df, session="traced")
        h.result(timeout=60)
        assert h.trace_ctx is not None
        rec = h.trace_ctx.recorder
        assert rec.exported  # finalized by the scheduler, once
        kinds = {s["name"] for s in rec.spans()}
        assert "serve:queue" in kinds and "serve:run" in kinds
        assert "plan:fingerprint" in kinds
        q = next(s for s in rec.spans() if s["name"] == "serve:queue")
        assert q["attrs"]["session"] == "traced"
        assert tracing.orphan_spans(rec) == []
        # the handle's stats carry the summary for explain/history
        assert h.stats.trace_summary.get("trace_id") == rec.trace_id
    finally:
        sched.shutdown()


def test_serving_failed_query_still_exported(tmp_path, monkeypatch):
    """A FAILED serving query is the one an operator most needs: it must
    still land in the flight recorder (with the error) and export its
    trace with error status — only rejected/cancelled queries skip."""
    from daft_tpu import DataType, serving, udf

    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_QUERY_LOG", str(tmp_path / "q.jsonl"))
    monkeypatch.setenv("DAFT_TPU_TRACE_DIR", str(tmp_path))

    @udf(return_dtype=DataType.int64())
    def boom(x):
        raise RuntimeError("intentional test failure")

    sched = serving.QueryScheduler(concurrency=1)
    try:
        df = daft.from_pydict({"x": [1, 2, 3]}).select(boom(col("x")))
        h = sched.submit(df)
        with pytest.raises(Exception):
            h.result(timeout=60)
        assert h.state == "failed"
        entries = [e for e in tracing.flight_history()
                   if (e.get("serving") or {}).get("state") == "failed"]
        assert entries, tracing.flight_history()
        assert "intentional test failure" in entries[0]["serving"]["error"]
        if h.trace_ctx is not None:
            rec = h.trace_ctx.recorder
            assert rec.exported
            root = next(s for s in rec.spans() if s["name"] == "query")
            assert root.get("status") == "error"
            assert glob.glob(str(tmp_path / "trace_*.json"))
    finally:
        sched.shutdown()


def test_worker_concurrent_tasks_one_trace_no_span_loss(monkeypatch):
    """Two tasks of ONE trace running concurrently on the same
    cross-process worker: the per-trace ship-back buffer is refcounted
    and drained, so neither task's run span is lost (the regression was
    the loser of the check-then-register race vanishing into an
    unregistered recorder)."""
    import subprocess
    import sys

    from daft_tpu.distributed.remote_worker import RemoteWorker
    from daft_tpu.distributed.worker import StageTask
    from daft_tpu.micropartition import MicroPartition
    from daft_tpu.physical import plan as pp
    from daft_tpu.recordbatch import RecordBatch

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "daft_tpu.distributed.remote_worker",
         "--port", "0", "--host", "127.0.0.1", "--slots", "2"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=repo)
    rw = None
    try:
        addr = proc.stdout.readline().strip().split()[-1]
        rec = tracing.SpanRecorder("ee" * 16)
        tracing.register_recorder(rec)
        rw = RemoteWorker("r0", addr, num_slots=2)
        mp = MicroPartition.from_recordbatch(
            RecordBatch.from_pydict({"x": list(range(50))}))
        schema = mp.schema

        def mk_task(i):
            return StageTask(
                0, pp.InMemorySource([mp], schema), {}, task_idx=i,
                fault_key=f"s0.t{i}",
                trace_ctx=(rec.trace_id,
                           tracing.span_id_from(f"run:s0.t{i}"),
                           rec.root_id))

        futs = [rw.submit(mk_task(i)) for i in range(2)]
        for f in futs:
            assert f.result(timeout=120)
        runs = {s["span_id"] for s in rec.spans()
                if s["name"] == "task:run"}
        assert tracing.span_id_from("run:s0.t0") in runs
        assert tracing.span_id_from("run:s0.t1") in runs
        tracing.unregister_recorder(rec.trace_id)
    finally:
        if rw is not None:
            rw.shutdown()
        proc.terminate()
        proc.wait(timeout=10)


def test_serving_cancel_event_and_trace_close(monkeypatch):
    from daft_tpu import serving

    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    sched = serving.QueryScheduler(concurrency=1)
    try:
        blocker = threading.Event()

        class SlowStats:
            pass

        # a queued query cancelled before it runs
        df = daft.from_pydict({"x": [1]}).where(col("x") > 0)
        h1 = sched.submit(df)       # will run
        h2 = sched.submit(df)       # may queue behind h1
        h2.cancel("test cancel")
        try:
            h2.result(timeout=30)
        except Exception:
            pass
        blocker.set()
        if h2.state == "cancelled" and h2.trace_ctx is not None:
            rec = h2.trace_ctx.recorder
            assert rec.exported  # closed, not leaked
            assert tracing.recorder_for(rec.trace_id) is None
    finally:
        sched.shutdown()


def test_planner_failure_aborts_and_unregisters_trace(monkeypatch):
    """r14 regression (found by daft-lint trace-recorder-leak): a
    translate/optimize failure between maybe_start_trace and the
    executor's stats-context adoption left the recorder registered for
    the process lifetime, with the trace silently lost."""
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")

    def boom(plan):
        raise RuntimeError("translate exploded")

    monkeypatch.setattr("daft_tpu.runners.native_runner.translate", boom)
    df = daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1)
    with pytest.raises(RuntimeError, match="translate exploded"):
        df.to_pydict()
    # the aborted trace closed and left the registry
    with tracing._reg_lock:
        assert dict(tracing._recorders) == {}


def test_abort_trace_is_idempotent_and_none_safe():
    tracing.abort_trace(None)  # no-op
    rec = tracing.SpanRecorder("t" * 32)
    tracing.register_recorder(rec)
    ctx = tracing.SpanContext(rec, rec.root_id)
    tracing.abort_trace(ctx)
    tracing.abort_trace(ctx)  # second call: already exported, no-op
    assert rec.exported and rec.status == "error"
    with tracing._reg_lock:
        assert rec.trace_id not in tracing._recorders


# ----------------------------------------- spans inside the query path
#
# One profiler session for the module: a small device-tier aggregate over
# Parquet and a small host join run under it, and every case below reads
# what they left behind (the autouse reset empties the ring between
# tests, so the fixture keeps its own copy).

_AGG_SPANS = ("plan:optimize", "plan:translate", "scan:load",
              "device:encode", "device:put", "device:dispatch",
              "device:fetch", "device:decode", "result:collect")
_JOIN_SPANS = ("plan:optimize", "plan:translate", "join:build",
               "join:probe", "agg:host", "sort:topn", "result:collect")
_HARNESS_PREFIXES = ("pass:", "plan:", "execute:", "clear-cache")


def _write_lineitem(root, files=4, n=800):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(11)
    for i in range(files):
        pq.write_table(
            pa.table({"flag": rng.integers(0, 4, n),
                      "qty": rng.random(n) * 50,
                      "price": rng.random(n) * 1000}),
            str(root / f"part{i}.parquet"))
    return str(root)


def _scan_agg(root):
    return (daft.read_parquet(f"{root}/*.parquet")
            .groupby("flag")
            .agg(col("qty").sum().alias("sum_qty"),
                 col("qty").count().alias("cnt")))


def _small_join():
    left = daft.from_pydict({"k": [i % 50 for i in range(600)],
                             "v": [float(i) for i in range(600)]})
    right = daft.from_pydict({"k": list(range(50)),
                              "g": [i % 5 for i in range(50)]})
    return (left.join(right, on="k").groupby("g")
            .agg(col("v").sum().alias("s")).sort(col("s"), desc=True)
            .limit(3))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    import jax
    from jax.profiler import ProfileData
    root = _write_lineitem(tmp_path_factory.mktemp("spans_pq"))
    prof = str(tmp_path_factory.mktemp("spans_prof"))
    tracing.reset_for_tests()
    mp = pytest.MonkeyPatch()
    mp.delenv("DAFT_TPU_TRACE", raising=False)
    jax.profiler.start_trace(prof)
    try:
        mp.setenv("DAFT_TPU_DEVICE_FORCE", "1")
        agg = _scan_agg(root).to_pydict()
        mp.delenv("DAFT_TPU_DEVICE_FORCE")
        join = _small_join().to_pydict()
    finally:
        jax.profiler.stop_trace()
        mp.undo()
    summaries = tracing.finished()
    tracing.reset_for_tests()
    (pb,) = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                      recursive=True)
    host_events = [e.name for plane in ProfileData.from_file(pb).planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events]
    assert sorted(agg["cnt"]) and len(join["g"]) == 3
    assert len(summaries) == 2
    return {"agg": summaries[0], "join": summaries[1],
            "host_events": host_events}


@pytest.mark.parametrize(
    "query,name",
    [("agg", n) for n in _AGG_SPANS] + [("join", n) for n in _JOIN_SPANS])
def test_profiled_query_leaves_its_leaf_spans(profiled, query, name):
    s = profiled[query]
    phase = s["phases"].get(name)
    assert phase and phase["count"] >= 1, sorted(s["phases"])
    assert 0 <= phase["wall_us"] <= s["wall_us"]
    assert phase["sum_us"] >= phase["wall_us"]
    # ... and the same span lies on a host line of the profile
    assert f"daft:{name}" in set(profiled["host_events"])


def test_profiled_summaries_place_and_cover_the_query(profiled):
    now = time.perf_counter()
    for s in (profiled["agg"], profiled["join"]):
        assert 0 < s["t0_perf_s"] < now and s["t0_unix_us"] > 0
        assert 0 < s["covered_us"] <= s["wall_us"]
    assert profiled["agg"]["t0_perf_s"] < profiled["join"]["t0_perf_s"]
    put = profiled["agg"]["phases"]["device:put"]
    enc = profiled["agg"]["phases"]["device:encode"]
    assert put["bytes"] == enc["bytes"] > 0 and enc["rows"] > 0
    tables = profiled["agg"]["tables"]  # small files share a scan task
    assert tables["encoded"] >= 1
    assert tables["from_cache"] == tables["host"] == 0


@pytest.mark.parametrize("prefix", _HARNESS_PREFIXES)
def test_program_writes_no_event_under_a_harness_prefix(profiled, prefix):
    # chipbench/xplane.py attributes device time to the latest-started
    # host span whose name starts with one of these: they are the
    # benchmark's, and this module wrote none into the profile
    assert any(n.startswith("daft:") for n in profiled["host_events"])
    assert not [n for n in profiled["host_events"] if n.startswith(prefix)]


def test_no_profile_and_no_flag_leaves_nothing_behind(monkeypatch):
    monkeypatch.delenv("DAFT_TPU_TRACE", raising=False)
    monkeypatch.delenv("DAFT_TPU_XPLANE_DIR", raising=False)
    assert not tracing.profile_requested()
    assert _small_join().to_pydict()["g"]
    assert tracing.finished() == []
    assert tracing.span("scan:load") is tracing._NOOP


def test_xplane_dir_is_a_request_for_spans(tmp_path, monkeypatch):
    monkeypatch.delenv("DAFT_TPU_TRACE", raising=False)
    monkeypatch.setenv("DAFT_TPU_XPLANE_DIR", str(tmp_path))
    # sampled away only when DAFT_TPU_TRACE alone asked
    monkeypatch.setenv("DAFT_TPU_TRACE_SAMPLE", "0.0")
    assert tracing.profile_requested()
    _small_join().to_pydict()
    (s,) = tracing.finished()
    assert "join:probe" in s["phases"]
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)


@pytest.mark.parametrize("threads", [1, 4])
def test_phase_wall_is_a_union_over_threads(threads):
    rec = tracing.SpanRecorder("t" * 32)
    ctx = tracing.SpanContext(rec, rec.root_id)
    go = threading.Barrier(threads)

    def work(i):
        with tracing.attach(ctx):
            go.wait()
            with tracing.span("scan:load", key=f"w{i}",
                              attrs={"rows": 10, "bytes": 100}):
                time.sleep(0.05)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    rec.finish()
    (s,) = tracing.finished()
    p = s["phases"]["scan:load"]
    assert p["count"] == threads and p["rows"] == 10 * threads
    assert p["bytes"] == 100 * threads
    assert p["wall_us"] <= s["wall_us"] and s["covered_us"] <= s["wall_us"]
    assert p["sum_us"] >= p["wall_us"] >= 45_000
    if threads > 1:  # overlapping intervals count once in the wall
        assert p["sum_us"] >= threads * 45_000
        assert p["wall_us"] < p["sum_us"]
    assert s["covered_us"] == p["wall_us"]


@pytest.mark.parametrize("name", ["exchange:partition", "exchange:gather"])
def test_exchange_spans_are_leaves_and_count_as_covered(name):
    assert name in tracing.LEAF_SPANS
    rec = tracing.SpanRecorder("e" * 32)
    with tracing.attach(tracing.SpanContext(rec, rec.root_id)):
        with tracing.span(name, attrs={"rows": 4, "morsels": 2}):
            time.sleep(0.01)
    rec.finish()
    (s,) = tracing.finished()
    p = s["phases"][name]
    assert p["count"] == 1 and p["rows"] == 4
    assert s["covered_us"] == p["wall_us"] >= 9_000


def test_fanouts_say_how_many_morsels_they_fold(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    left = daft.from_pydict({"k": [i % 50 for i in range(600)],
                             "v": [float(i) for i in range(600)]})
    right = daft.from_pydict({"k": list(range(50)),
                              "g": [i % 5 for i in range(50)]})
    with daft.execution_config_ctx(broadcast_join_size_bytes_threshold=1):
        out = (left.into_partitions(3).join(right.into_partitions(2), on="k")
               .groupby("g").agg(col("v").sum().alias("s")).to_pydict())
    assert sorted(out["g"]) == [0, 1, 2, 3, 4]
    spans = obs.last_query_stats().trace_ctx.recorder.spans()
    fans = [s["attrs"] for s in spans if s["name"] == "exchange:partition"]
    # each join side's morsels are under the threshold: one call a side
    assert sorted((f["rows"], f["morsels"]) for f in fans) \
        == [(50, 2), (600, 3)]
    # ... and the final aggregate's few partial rows are hashed by no one
    (gather,) = [s["attrs"] for s in spans if s["name"] == "exchange:gather"]
    assert gather["morsels"] >= 1 and gather["rows"] >= 5
    (s,) = tracing.finished()
    assert s["phases"]["exchange:gather"]["count"] == 1


def test_finished_ring_is_bounded_and_newest_last():
    for i in range(260):
        tracing.SpanRecorder(f"{i:032d}").finish()
    ring = tracing.finished()
    assert len(ring) == 256
    assert ring[-1]["trace_id"] == f"{259:032d}"
    assert [s["trace_id"] for s in tracing.finished(2)] == \
        [f"{258:032d}", f"{259:032d}"]
    tracing.reset_for_tests()
    assert tracing.finished() == []


def test_operator_spans_are_real_intervals(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    daft.from_pydict({"x": list(range(2000))}).where(
        col("x") % 2 == 0).collect()
    rec = obs.last_query_stats().trace_ctx.recorder
    spans = rec.spans()
    root = next(s for s in spans if s["span_id"] == rec.root_id)
    op = next(s for s in spans if s["name"] == "op:Filter")
    assert root["ts_us"] <= op["ts_us"]
    assert op["ts_us"] + op["dur_us"] <= root["ts_us"] + root["dur_us"]
    assert 0 < op["attrs"]["busy_us"] and op["attrs"]["rows_out"] == 1000
    assert op["attrs"]["self_us"] <= op["attrs"]["busy_us"]


@pytest.fixture(scope="module")
def cold_then_warm(tmp_path_factory):
    from daft_tpu.device import cache as dcache, costmodel
    root = _write_lineitem(tmp_path_factory.mktemp("spans_cache_pq"))
    mp = pytest.MonkeyPatch()
    mp.setenv("DAFT_TPU_TRACE", "1")
    mp.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    tracing.reset_for_tests()
    dcache.get_cache().clear()
    out = {}
    try:
        for phase in ("cold", "warm"):
            c0 = dcache.get_cache().stats()
            t0 = dict(costmodel.scan_table_counts)
            _scan_agg(root).to_pydict()
            c1 = dcache.get_cache().stats()
            out[phase] = {
                "cache": {k: c1[k] - c0[k] for k in c1 if k != "chips"},
                "chips": c1["chips"],
                "process": {k: v - t0[k] for k, v in
                            costmodel.scan_table_counts.items()},
                "summary": tracing.finished()[-1]}
    finally:
        mp.undo()
        dcache.get_cache().clear()
        tracing.reset_for_tests()
    return out


@pytest.mark.parametrize("phase,source", [("cold", "encoded"),
                                          ("warm", "from_cache")])
def test_cache_counts_and_table_tally(cold_then_warm, phase, source):
    got = cold_then_warm[phase]
    tables = got["summary"]["tables"]
    n = tables[source]  # scan tasks (small files share one)
    assert n >= 1 and sum(tables.values()) == n
    assert got["process"] == tables
    assert n == cold_then_warm["cold"]["summary"]["tables"]["encoded"]
    put = got["summary"]["phases"].get("device:put", {}).get("bytes", 0)
    if phase == "cold":
        assert (got["cache"]["hits"], got["cache"]["misses"]) == (0, n)
        # what the cache was given is what it now holds; the spans also
        # see the few bytes that were put and not cached (the partials'
        # merge runs on the device too when forced)
        assert got["cache"]["put_bytes"] == got["cache"]["bytes"] > 0
        assert got["cache"]["put_bytes"] <= put < 2 * got["cache"]["bytes"]
    else:
        assert (got["cache"]["hits"], got["cache"]["misses"]) == (n, 0)
        assert got["cache"]["put_bytes"] == 0 == got["cache"]["bytes"]
        assert put < cold_then_warm["cold"]["cache"]["put_bytes"] / 4
    assert got["cache"]["evicted_bytes"] == 0
    # the same tally per chip: every device table under the chip that
    # holds it, and the cache's bytes there as the query left them
    chips = got["summary"]["chips"]
    assert sum(c["tables"] for c in chips) == n
    assert sum(c["rows"] for c in chips) > 0
    assert {c["chip"]: c["resident_bytes"] for c in chips
            if c["resident_bytes"]} == \
        {k: v["bytes"] for k, v in got["chips"].items() if v["bytes"]}


# ------------------------------------------ work apart from wait (PR 43)

def _recorder(**kw):
    rec = tracing.SpanRecorder("w" * 32, **kw)
    return rec, tracing.SpanContext(rec, rec.root_id)


def _busy(seconds):
    end = time.perf_counter() + seconds
    x = 0
    while time.perf_counter() < end:
        x += 1
    return x


@pytest.mark.parametrize("body,lo,hi", [
    (lambda: _busy(0.08), 0.8, 1.0),
    (lambda: time.sleep(0.08), 0.0, 0.1),
], ids=["busy-loop", "sleep"])
def test_live_span_reads_thread_cpu_time(body, lo, hi):
    # a busy loop on a loaded host may lose its core: the best of a few
    for _ in range(5):
        rec, ctx = _recorder()
        with tracing.attach(ctx):
            with tracing.span("expr:eval"):
                body()
        (s,) = rec.spans()
        assert s["dur_us"] >= 80_000
        assert s["cpu_us"] <= s["dur_us"] + 50
        if lo * s["dur_us"] <= s["cpu_us"] <= hi * s["dur_us"] + 50:
            return
    raise AssertionError(f"cpu_us {s['cpu_us']} of dur_us {s['dur_us']}")


def test_explicit_and_remote_spans_carry_no_cpu_time():
    rec, ctx = _recorder()
    with tracing.attach(ctx):
        with tracing.span("agg:host"):
            _busy(0.01)
        tracing.note_wait("wait:channel", 0, 5_000_000, {"side": "get"})
    rec.add("agg:host", "e" * 16, None, rec.now_us(), 700)
    rec.add_remote([{"name": "agg:host", "span_id": "r" * 16,
                     "ts_us": rec.now_us(), "dur_us": 900, "cpu_us": 900}],
                   offset_us=0, worker="w0")
    spans = {s["span_id"]: s for s in rec.spans()}
    assert "cpu_us" not in spans["e" * 16]
    assert "cpu_us" not in spans["r" * 16]          # absent, not 0
    live = [s for s in spans.values() if "cpu_us" in s]
    assert [s["name"] for s in live] == ["agg:host"]
    rec.finish()
    phase = rec.summary()["phases"]["agg:host"]
    # timed_us is the duration of the spans that carry a CPU time only
    assert phase["count"] == 3
    assert phase["timed_us"] == live[0]["dur_us"]
    assert phase["cpu_us"] == live[0]["cpu_us"]
    assert phase["sum_us"] == live[0]["dur_us"] + 700 + 900
    wait = rec.summary()["phases"]["wait:channel"]
    assert (wait["timed_us"], wait["cpu_us"], wait["sum_us"]) == (0, 0, 5000)


def test_only_the_leaves_and_the_launch_read_the_cpu_clock(monkeypatch):
    """A read of the thread-CPU clock costs ~6 us on the machines with
    the chips, so the stage, submit, drain and wait spans, whose CPU time
    no metric reads, do not pay it (``tracing.CPU_SPANS``)."""
    reads = []
    real = time.thread_time_ns
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: reads.append(1) or real())
    rec, ctx = _recorder()
    with tracing.attach(ctx):
        for name in ("pipeline:stage", "device:submit", "device:drain",
                     "serve:run"):
            with tracing.span(name):
                pass
        with tracing.wait("wait:window"):
            time.sleep(0.001)
        assert reads == []
        with tracing.span("device:dispatch"):
            with tracing.launch("fragment.packed"):
                pass
    assert len(reads) == 4
    assert tracing.CPU_SPANS == tracing.LEAF_SPANS | {"dispatch:launch"}
    assert {s["name"] for s in rec.spans() if "cpu_us" in s} == \
        {"device:dispatch", "dispatch:launch"}


def test_durations_come_from_the_monotonic_clock(monkeypatch):
    """A host that steps its wall clock mid-span moves no duration."""
    rec, ctx = _recorder()
    real = time.time
    with tracing.attach(ctx):
        with tracing.span("expr:eval"):
            monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
            time.sleep(0.01)
    monkeypatch.setattr(time, "time", real)
    rec.finish()
    (s, root) = rec.spans()
    assert 10_000 <= s["dur_us"] < 1_000_000
    assert 0 <= s["ts_us"] - root["ts_us"] < 1_000_000
    assert root["dur_us"] < 1_000_000


def _fixed_recorder():
    """Spans at hand-picked microseconds after the root's start."""
    rec, _ = _recorder()
    t0 = rec._root_t0
    for i, (name, at, dur, attrs) in enumerate([
            ("plan:optimize", 0, 100, None),
            ("device:dispatch", 300, 100, None),
            ("device:fetch", 600, 200, None),
            ("device:submit", 250, 200, None),         # hole 250-300, 400-450
            ("wait:pool", 120, 100, {"pool": "devpipe"}),   # hole 120-220
            ("wait:result", 100, 480, {"tail_us": 30}),  # its tail: 550-580
            ("wait:channel", 0, 1000, {"side": "get", "tail_us": 50}),
            ("pipeline:stage", 0, 1000, None),
            ("op:Sort", 0, 900, None)]):
        rec.add(name, f"{i:016x}", None, t0 + at, dur, attrs=attrs)
    return rec


def _finish_at(rec, wall_us):
    rec._root_perf_ns = time.perf_counter_ns() - wall_us * 1000
    rec.finish()
    return rec.summary()


def test_holes_are_named_by_what_lay_over_them():
    s = _finish_at(_fixed_recorder(), 1000)
    assert 1000 <= s["wall_us"] < 1100
    extra = s["wall_us"] - 1000          # the root closed a little later
    # leaves cover 0-100, 300-400, 600-800
    assert s["covered_us"] == 400
    holes = s["holes"]
    assert holes["us"] == 600 + extra == s["wall_us"] - s["covered_us"]
    by = holes["by"]
    assert by["device:submit"] == 100
    assert by["wait:pool"] == 100
    assert by["wait:result"] == 480 - 100    # 100-300, 400-580
    assert by["wait:channel"] == 600         # all of them, and says nothing
    assert by["handoff"] == 30 + 50          # 550-580 and 950-1000
    assert by["op:Sort"] == 500
    assert "pipeline:stage" not in by and "query" not in by
    assert "plan:optimize" not in by
    # named: 120-220 (pool), 250-300 + 400-450 (submit), the two tails
    assert holes["unnamed_us"] == holes["us"] - (100 + 100 + 30 + 50)


def test_launch_is_no_leaf():
    """``covered_us`` of a fixed recorder is the same to the microsecond
    with ``dispatch:launch`` spans laid into and beside its leaves."""
    before = _finish_at(_fixed_recorder(), 1000)
    rec = _fixed_recorder()
    t0 = rec._root_t0
    rec.add("dispatch:launch", "a" * 16, None, t0 + 320, 60)   # nested
    rec.add("dispatch:launch", "b" * 16, None, t0 + 820, 100)  # alone
    after = _finish_at(rec, 1000)
    assert after["covered_us"] == before["covered_us"] == 400
    assert "dispatch:launch" not in tracing.LEAF_SPANS
    assert not [n for n in tracing.LEAF_SPANS if n.startswith("wait:")]
    assert after["holes"]["by"]["dispatch:launch"] == 100
    assert after["holes"]["unnamed_us"] == \
        before["holes"]["unnamed_us"] - 100 + \
        (after["wall_us"] - before["wall_us"])
    assert tracing.COMPUTE_SPANS < tracing.LEAF_SPANS


def _launches(run):
    run()
    spans = obs.last_query_stats().trace_ctx.recorder.spans()
    by_id = {s["span_id"]: s for s in spans}
    found = set()
    for s in spans:
        if s["name"] == "dispatch:launch":
            parent = by_id[s["parent_id"]]
            assert "cpu_us" in s and s["lane"] == "device"
            if parent["name"] != "query":
                assert parent["ts_us"] <= s["ts_us"]
                assert s["ts_us"] + s["dur_us"] <= \
                    parent["ts_us"] + parent["dur_us"]
            found.add((s["attrs"]["program"], parent["name"]))
    return found


def _fusion_queries():
    import numpy as np
    rng = np.random.default_rng(7)
    n = 4000
    df = daft.from_pydict({
        "a": rng.integers(0, 100, n).astype(np.int64),
        "b": rng.normal(size=n),
        "k": rng.integers(0, 50, n).astype(np.int64)})
    build = daft.from_pydict({
        "k2": np.arange(0, 40, dtype=np.int64), "w": rng.normal(size=40),
        "g": (np.arange(40, dtype=np.int64) % 5)})
    return {
        "fragment.packed": lambda: df.groupby("k").agg(
            col("b").sum().alias("s")).to_pydict(),
        "region.chain": lambda: df.where(col("a") > 30).select(
            (col("b") * 2.0).alias("b2"), col("a")).to_pydict(),
        "region.topk": lambda: df.where(col("a") > 10).select(
            col("a"), col("b")).sort(col("b"), desc=True).limit(9)
        .to_pydict(),
        "region.join_agg": lambda: df.where(col("a") > 20).join(
            build, left_on=col("k"), right_on=col("k2"), how="inner")
        .groupby(col("g")).agg((col("b") * col("w")).sum().alias("rev"))
        .to_pydict(),
        "kernels.argsort": lambda: df.sort(col("b")).to_pydict(),
    }


@pytest.mark.parametrize("site,parent,fusion", [
    ("fragment.packed", "device:dispatch", "0"),
    ("region.chain", "device:dispatch", "1"),
    ("region.topk", "device:dispatch", "1"),
    ("region.join_agg", "device:dispatch", "1"),
    # device/runtime.py's four sites open no dispatch leaf of their own:
    # the launch stands under whatever called it
    ("compiler.projection", "expr:eval", "0"),
    ("kernels.argsort", "sort:topn", "0"),
    ("kernels.grouped_agg", "pipeline:stage", "0"),
])
def test_launch_span_sits_where_the_jitted_call_is(monkeypatch, site,
                                                   parent, fusion):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_FUSION", fusion)
    queries = _fusion_queries()
    run = queries.get(site) or queries["fragment.packed"]
    assert (site, parent) in _launches(run)


def test_launch_span_nests_in_the_device_join(monkeypatch):
    import numpy as np
    from daft_tpu.joins import _device_match_indices
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    rng = np.random.default_rng(3)
    rec, ctx = _recorder()
    with tracing.attach(ctx):
        out = _device_match_indices(
            rng.integers(0, 50, 400), rng.integers(0, 50, 150),
            np.ones(400, bool), np.ones(150, bool))
    assert out is not None
    spans = {s["name"]: s for s in rec.spans()}
    launch, join = spans["dispatch:launch"], spans["join:device"]
    assert launch["parent_id"] == join["span_id"]
    assert launch["attrs"]["program"] == "kernels.join_fused"
    assert join["ts_us"] <= launch["ts_us"] and \
        launch["dur_us"] <= join["dur_us"]


def test_short_waits_are_counted_not_stored():
    rec, ctx = _recorder(max_spans=64)
    t = time.perf_counter_ns()
    with tracing.attach(ctx):
        for i in range(10_000):
            tracing.note_wait("wait:channel", t, t + 20_000)   # 20 us
        for _ in range(3):
            with tracing.wait("wait:result"):
                pass
        tracing.note_wait("wait:channel", t,
                          t + tracing.WAIT_FLOOR_US * 1000, {"side": "put"})
    rec.finish()
    s = rec.summary()
    assert s["dropped"] == 0 == rec.dropped
    assert s["waits_short"]["count"] == 10_003
    assert s["waits_short"]["us"] >= 10_000 * 20
    assert s["phases"]["wait:channel"]["count"] == 1   # the one at the floor
    assert "wait:result" not in s["phases"]
    assert s["spans"] == 2


def test_a_chaos_replay_counts_its_waits_and_stores_none(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    rec, ctx = _recorder()
    with tracing.attach(ctx):
        tracing.note_wait("wait:pool", 0, 50_000_000)
    assert rec.spans() == []
    assert rec.summary()["spans"] == 0
    assert rec._tallies["waits_short"] == 1


def test_wait_pool_from_a_pool_of_one_busy_worker():
    import concurrent.futures as cf
    rec, ctx = _recorder()
    release = threading.Event()
    with cf.ThreadPoolExecutor(max_workers=1) as pool:
        with tracing.attach(ctx):
            first = pool.submit(tracing.run_attached,
                                tracing.submitted(ctx, "one"),
                                release.wait, 5)
            second = pool.submit(tracing.run_attached,
                                 tracing.submitted(ctx, "one"),
                                 tracing.current)
        time.sleep(0.06)
        release.set()
        assert first.result() is True
        # the worker ran under the submitter's context, unwrapped
        assert second.result().recorder is rec
    # the second submit stood in the queue behind the first (whose own
    # wait, a thread's start, is stored only if it passed the floor)
    waits = sorted((s for s in rec.spans() if s["name"] == "wait:pool"),
                   key=lambda s: s["dur_us"])
    assert 1 <= len(waits) <= 2 and waits[-1]["dur_us"] >= 60_000
    assert waits[-1]["attrs"] == {"pool": "one"}
    assert "cpu_us" not in waits[-1]
    rec.finish()
    h = rec.summary()["handoffs"]
    assert h["count"] == 2 and h["max_us"] >= 60_000 and h["us"] >= h["max_us"]
    # the same through observability's shape, untraced: the context goes
    # through as it is
    assert obs.submit_attribution("one") is obs.current_attribution()
    assert tracing.submitted(None, "one") is None


def test_exports_carry_cpu_time():
    rec, ctx = _recorder()
    with tracing.attach(ctx):
        with tracing.span("expr:eval", attrs={"rows": 3}):
            pass
    rec.add("device:inflight", "f" * 16, None, rec.now_us(), 5)
    events = {e["name"]: e for e in tracing.chrome_trace_events(rec)
              if e["ph"] == "X"}
    assert events["expr:eval"]["args"]["rows"] == 3
    assert "cpu_us" in events["expr:eval"]["args"]
    assert "cpu_us" not in events["device:inflight"]["args"]
    otlp = tracing.otlp_spans_payload(rec)
    spans = {s["name"]: {a["key"] for a in s["attributes"]}
             for s in otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]}
    assert "cpu_us" in spans["expr:eval"]
    assert "cpu_us" not in spans["device:inflight"]


def test_untraced_nothing_reads_the_cpu_clock(monkeypatch):
    """Off means off: no thread-CPU read, no stamp, no wrapper."""
    def boom():
        raise AssertionError("thread_time_ns read on an untraced thread")
    monkeypatch.setattr(time, "thread_time_ns", boom)
    assert tracing.current() is None
    assert tracing.span("expr:eval") is tracing._NOOP
    assert tracing.launch("fragment.packed") is tracing._NOOP
    assert tracing.wait("wait:window") is tracing._NOOP
    tracing.note_wait("wait:channel", 0, 10 ** 9)
    marker = object()
    assert tracing.submitted(marker, "p") is marker
    assert tracing.started(marker) is marker
    assert obs.run_attributed(None, lambda x: x, marker) is marker
    assert tracing.run_attached(None, lambda x: x, marker) is marker
    out = (daft.from_pydict({"x": list(range(100)),
                             "g": [i % 3 for i in range(100)]})
           .where(col("x") > 10).groupby("g").agg(col("x").sum())
           .to_pydict())
    assert len(out["g"]) == 3
    assert tracing.finished() == []
