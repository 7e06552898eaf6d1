"""Runtime stats / chrome trace / explain-analyze tests
(reference model: runtime_stats.rs, common/tracing, tests/observability/)."""

import json
import os

import pytest

import daft_tpu as daft
from daft_tpu import col
from daft_tpu import observability as obs


def test_runtime_stats_collected():
    df = (daft.from_pydict({"x": list(range(1000)), "g": [i % 10 for i in range(1000)]})
          .where(col("x") > 99)
          .groupby("g").agg(col("x").sum().alias("s")))
    df.collect()
    stats = obs.last_query_stats()
    assert stats is not None
    d = stats.as_dict()
    assert stats.wall_us is not None and stats.wall_us > 0
    # source emits all 1000 rows; final agg emits 10 groups
    src = [v for k, v in d.items() if "Source" in k]
    assert src and src[0]["rows_out"] == 1000
    root = [v for k, v in d.items() if "Agg" in k]
    assert any(v["rows_out"] == 10 for v in root)


def test_runtime_stats_unfused_filter():
    df = daft.from_pydict({"x": list(range(1000))}).where(col("x") > 99)
    df.collect()
    d = obs.last_query_stats().as_dict()
    filters = [v for k, v in d.items() if k.startswith("Filter")]
    assert filters and filters[0]["rows_out"] == 900


def test_explain_analyze_renders(capsys):
    df = daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1)
    df.explain(analyze=True)
    out = capsys.readouterr().out
    assert "rows_out=2" in out
    assert "query wall time" in out


def test_chrome_trace_written(tmp_path, monkeypatch):
    # the one chrome-trace writer: DAFT_TPU_TRACE=1 + DAFT_TPU_TRACE_DIR
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_TRACE_DIR", str(tmp_path))
    df = daft.from_pydict({"x": list(range(100))}).where(col("x") % 2 == 0)
    df.collect()
    (path,) = tmp_path.glob("trace_*.json")
    with open(path) as f:
        trace = json.load(f)
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert "op:Filter" in {e["name"] for e in spans}
    for e in spans:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)


def test_stats_exclusive_time_nonneg():
    df = daft.from_pydict({"x": list(range(500))}).with_column(
        "y", col("x") * 2).where(col("y") > 10)
    df.collect()
    stats = obs.last_query_stats()
    for v in stats.as_dict().values():
        assert v["exclusive_us"] >= 0
        assert v["inclusive_us"] >= v["exclusive_us"]


def test_explain_analyze_not_stale(capsys):
    df1 = daft.from_pydict({"x": [1, 2, 3]}).where(col("x") > 1)
    df1.collect()
    # another query runs afterwards…
    daft.from_pydict({"y": list(range(50))}).where(col("y") > 10).collect()
    # …but df1's analysis must show df1's stats (2 rows), not the later query's
    df1.explain(analyze=True)
    out = capsys.readouterr().out
    assert "rows_out=2" in out and "rows_out=39" not in out


def test_aqe_coalesces_small_shuffles(monkeypatch):
    """With AQE on, an engine-inserted shuffle over tiny data coalesces to
    fewer partitions, sized by actual materialized bytes (reference:
    AdaptivePlanner next_stage/update_stats)."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.context import execution_config_ctx
    from daft_tpu.physical import adaptive

    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")  # host exchange path
    df = daft_tpu.from_pydict({"k": [i % 5 for i in range(100)],
                               "v": [float(i) for i in range(100)]})
    df = df.into_partitions(8)
    # count_distinct is non-decomposable → single-stage agg over a real
    # engine-inserted hash exchange (the fused partitioned-agg dispatcher
    # handles mergeable finals without materializing a shuffle at all)
    with execution_config_ctx(enable_aqe=True,
                              target_partition_size_bytes=1 << 30):
        out = df.groupby("k").agg(col("v").count_distinct().alias("s")) \
            .sort("k").to_pydict()
    assert out["k"] == [0, 1, 2, 3, 4]
    planner = adaptive.last_planner()
    assert planner is not None and planner.history
    # tiny data against a 1GB target → coalesced to 1 partition
    assert planner.history[-1].partitions == 1
    assert "→1 parts" in planner.history[-1].decision
    # user-visible explain
    assert "Adaptive execution" in planner.explain_analyze()


def test_aqe_records_fused_partitioned_agg(monkeypatch):
    """Mergeable grouped aggs skip the shuffle entirely via the fused
    partitioned-agg dispatcher; with AQE on, that elision is recorded in
    the adaptive history so explain_analyze shows why no exchange ran."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.context import execution_config_ctx
    from daft_tpu.physical import adaptive

    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    df = daft_tpu.from_pydict({"k": [i % 5 for i in range(100)],
                               "v": [float(i) for i in range(100)]})
    df = df.into_partitions(8)
    with execution_config_ctx(enable_aqe=True,
                              target_partition_size_bytes=1 << 30):
        out = df.groupby("k").agg(col("v").sum().alias("s")) \
            .sort("k").to_pydict()
    assert out["k"] == [0, 1, 2, 3, 4]
    assert out["s"] == [sum(float(i) for i in range(100) if i % 5 == k)
                        for k in range(5)]
    planner = adaptive.last_planner()
    assert planner is not None and planner.history
    assert any("fused partitioned agg" in s.decision
               for s in planner.history)


def test_aqe_demotes_hash_join_to_broadcast(monkeypatch):
    """With AQE on, a planned hash-hash join whose measured build side fits
    the broadcast threshold skips both shuffles and broadcasts it
    (reference: AdaptivePlanner re-planning joins from materialized
    stats)."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.context import execution_config_ctx
    from daft_tpu.physical import adaptive

    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    big = daft_tpu.from_pydict(
        {"k": [i % 10 for i in range(20_000)],
         "v": list(range(20_000))}).into_partitions(4)
    # a highly selective filter: the static planner's 20%-of-input size
    # heuristic (~tens of KB) exceeds the threshold so it plans hash-hash,
    # but the MEASURED bytes (a handful of rows) fit — exactly the
    # mis-estimate AQE corrects by demoting to broadcast
    small = daft_tpu.from_pydict(
        {"k": [i % 1000 for i in range(10_000)],
         "w": [f"n{i % 1000}" for i in range(10_000)]}) \
        .into_partitions(4).where(col("k") == 0)
    with execution_config_ctx(enable_aqe=True,
                              broadcast_join_size_bytes_threshold=4096):
        out = big.join(small, on="k").groupby("w") \
            .agg(col("v").sum().alias("s")).sort("w").to_pydict()
    # k==0 survives the filter 10 times; each match contributes big's v
    # sum over k==0
    assert out["w"] == ["n0"]
    assert out["s"] == [sum(range(0, 20_000, 10)) * 10]
    def final_strategies(planner):
        from daft_tpu.physical import plan as pp
        out = []

        def walk(n):
            if isinstance(n, pp.HashJoin):
                out.append(n.strategy)
            for c in n.children:
                walk(c)
        walk(planner.final_plan)
        return out

    planner = adaptive.last_planner()
    assert planner is not None
    # the adaptive runner materialized the join input and re-planned with
    # ACTUAL bytes: the tiny measured side now broadcasts
    decisions = [h.decision for h in planner.history
                 if "join input" in h.decision]
    assert decisions, planner.explain_analyze()
    assert any(s in ("broadcast_right", "broadcast_left")
               for s in final_strategies(planner)), planner.final_plan

    # same query with a zero threshold keeps the hash-hash plan
    with execution_config_ctx(enable_aqe=True,
                              broadcast_join_size_bytes_threshold=0):
        out2 = big.join(small, on="k").groupby("w") \
            .agg(col("v").sum().alias("s")).sort("w").to_pydict()
    assert out2 == out
    planner = adaptive.last_planner()
    assert all(s == "hash" for s in final_strategies(planner)), \
        final_strategies(planner)


def test_user_repartition_not_adapted():
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.context import execution_config_ctx

    df = daft_tpu.from_pydict({"k": list(range(50))})
    with execution_config_ctx(enable_aqe=True,
                              target_partition_size_bytes=1 << 30):
        out = df.repartition(6, col("k"))
        assert out.num_partitions() == 6
        got = out.to_pydict()
    assert sorted(got["k"]) == list(range(50))


def test_dashboard_serves_query_history():
    import urllib.request
    import daft_tpu
    from daft_tpu import col, dashboard

    port = dashboard.launch(0)
    try:
        df = daft_tpu.from_pydict({"x": [1, 2, 3]})
        df.select((col("x") * 2).alias("y")).to_pydict()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/") as r:
            page = r.read().decode()
        assert "daft-tpu queries" in page
        assert "query 1" in page
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/queries") as r:
            import json
            data = json.loads(r.read())
        assert data and "operators" in data[0]
    finally:
        dashboard.shutdown()


def test_cli_version_and_dashboard_entry(capsys):
    from daft_tpu.cli import main
    assert main(["version"]) == 0
    out = capsys.readouterr().out.strip()
    assert out == daft_tpu_version()


def daft_tpu_version():
    import daft_tpu
    return daft_tpu.__version__


def test_xplane_trace_captures_per_query(tmp_path, monkeypatch):
    """DAFT_TPU_XPLANE_DIR captures a jax profiler trace around query
    execution (the TPU-native analogue of the reference's chrome-trace
    layer) without disturbing results."""
    import os
    import daft_tpu
    from daft_tpu import col

    monkeypatch.setenv("DAFT_TPU_XPLANE_DIR", str(tmp_path))
    out = daft_tpu.from_pydict({"x": list(range(100))}) \
        .where(col("x") % 2 == 0).count_rows()
    assert out == 50
    # a profile directory materialized with at least one artifact
    found = []
    for root, _, files in os.walk(tmp_path):
        found.extend(files)
    assert found, "no xplane trace artifacts written"


def test_otlp_export_posts_operator_counters(monkeypatch):
    """Per-op counters export as OTLP/HTTP JSON metrics when
    DAFT_TPU_OTLP_ENDPOINT is set (reference: common/tracing OTLP export,
    runtime_stats.rs)."""
    import http.server
    import json
    import threading

    import daft_tpu
    from daft_tpu import col

    received = []
    done = threading.Event()

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            received.append((self.path, json.loads(body)))
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")
            done.set()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        monkeypatch.setenv("DAFT_TPU_OTLP_ENDPOINT",
                           f"http://127.0.0.1:{srv.server_port}")
        out = (daft_tpu.from_pydict({"k": [1, 1, 2], "v": [1.0, 2.0, 3.0]})
               .groupby("k").agg(col("v").sum().alias("s"))
               .sort("k").to_pydict())
        assert out["k"] == [1, 2]
        assert done.wait(10), "no OTLP POST arrived"
    finally:
        srv.shutdown()
    path, payload = received[0]
    assert path == "/v1/metrics"
    scope = payload["resourceMetrics"][0]["scopeMetrics"][0]
    names = {m["name"] for m in scope["metrics"]}
    assert names == {"daft_tpu.operator.rows_out",
                     "daft_tpu.operator.batches_out",
                     "daft_tpu.operator.cpu_us"}
    rows = next(m for m in scope["metrics"]
                if m["name"] == "daft_tpu.operator.rows_out")
    ops = {a["value"]["stringValue"]
           for p in rows["sum"]["dataPoints"]
           for a in p["attributes"] if a["key"] == "operator"}
    assert any("Aggregate" in o or "Agg" in o for o in ops), ops
