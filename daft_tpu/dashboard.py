"""Embedded query dashboard.

Reference: ``src/daft-dashboard`` — a localhost HTTP server receiving
broadcast query plans + timings (``lib.rs:28-60``, launched via
``daft.dashboard.launch()`` / DAFT_DASHBOARD). Here the server renders the
engine's own runtime stats: recent queries with per-operator rows/timings
(observability.RuntimeStatsContext) and HBM/IO counters, as plain HTML —
no bundled frontend, same surface.
"""

from __future__ import annotations

import html
import http.server
import json
import threading
import time
from typing import List, Optional

DEFAULT_PORT = 3238

_history_lock = threading.Lock()
_history: List[dict] = []
_history_bytes: List[int] = []  # parallel to _history: entry JSON sizes
#: broadcast-history bounds — BOTH apply: a count cap and a byte cap
#: (one query with a giant explain must not let 49 more like it pin
#: hundreds of MB in a long-lived --serve process)
_MAX_HISTORY = 50
_MAX_HISTORY_BYTES = 4 << 20
_server: Optional[http.server.ThreadingHTTPServer] = None


def broadcast_query(stats) -> None:
    """Record a finished query's stats for the dashboard (called by the
    runner; reference hook: ``DataFrame._broadcast_query_plan``)."""
    try:
        entry = {
            "ts": time.strftime("%H:%M:%S"),
            "operators": stats.as_dict(),
            "explain": stats.render(getattr(stats, "plan", None)),
            # resilience plane: recovery events (retries, quarantines,
            # recomputed map tasks, speculative wins…) for this query
            "recovery": dict(getattr(stats, "recovery", {}) or {}),
            # shuffle data plane: bytes written/fetched, compression
            # ratio inputs, combine reduction, fetch overlap
            "shuffle": dict(getattr(stats, "shuffle", {}) or {}),
            # scan-side IO plane: GETs vs planned ranges (coalescing),
            # bytes fetched vs used, prefetch overlap
            "io": dict(getattr(stats, "io", {}) or {}),
            # device kernels: per-family dispatch/byte/MFU ledger delta,
            # incl. the dense/sort strategy that ran
            "device_kernels": dict(
                getattr(stats, "device_kernels", {}) or {}),
            # self-tuning feedback plane (r20): calibration observations
            # + runtime re-plan decisions this query made
            "adaptive": dict(getattr(stats, "adaptive", {}) or {}),
            # lock-order sanitizer (DAFT_TPU_SANITIZE=1): graph size,
            # cycles, per-query contention/blocking events
            "sanitizer": dict(getattr(stats, "sanitizer", {}) or {}),
            # serving plane: session/priority/queue-wait/admission and
            # plan/result cache outcomes for scheduler-run queries
            "serving": dict(getattr(stats, "serving", {}) or {}),
            # tracing plane: merged-trace summary (id, span count)
            "trace": dict(getattr(stats, "trace_summary", {}) or {}),
        }
        size = len(json.dumps(entry, default=str))
    except Exception:
        return
    with _history_lock:
        _history.append(entry)
        _history_bytes.append(size)
        # count cap, then byte cap: evict oldest-first until both hold
        while len(_history) > _MAX_HISTORY \
                or (sum(_history_bytes) > _MAX_HISTORY_BYTES
                    and len(_history) > 1):
            _history.pop(0)
            _history_bytes.pop(0)


def _serving_view() -> dict:
    """Live scheduler state for the dashboard (never boots a scheduler,
    never raises — an idle process just shows an empty view)."""
    try:
        from . import serving
        sched = serving.shared_scheduler_if_running()
        if sched is None:
            return {}
        return sched.live_view()
    except Exception:
        return {}


def _fleet_view() -> dict:
    """Live fleet state when this process hosts the router: per-replica
    gauges, the aggregate, the autoscaling signal and the gossiped
    state-store generations (empty when no router is installed)."""
    try:
        from . import fleet
        router = fleet.installed_router()
        if router is None:
            return {}
        out = router.gauges()
        out["scale_signal"] = router.scale_signal()
        out["assignments"] = len(router.assignments())
        from .fleet import state_sync
        out["counters"] = state_sync.counters_snapshot()
        st = state_sync.installed()
        if st is not None:
            out["state"] = st.view()
        return out
    except Exception:
        return {}


class _Handler(http.server.BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def _reply(self, body: bytes, ctype: str) -> None:
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path.startswith("/metrics"):
            # Prometheus text-format scrape: process-wide serving /
            # shuffle / io / recovery / kernel counters + queue-depth
            # and cache-hit-rate gauges
            from . import tracing
            self._reply(tracing.prometheus_text().encode(),
                        "text/plain; version=0.0.4")
            return
        if self.path.startswith("/api/history"):
            # flight-recorder history (DAFT_TPU_QUERY_LOG JSONL)
            from . import tracing
            self._reply(json.dumps(tracing.flight_history()).encode(),
                        "application/json")
            return
        if self.path.startswith("/api/serving"):
            body = json.dumps(_serving_view()).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if self.path.startswith("/api/fleet"):
            self._reply(json.dumps(_fleet_view()).encode(),
                        "application/json")
            return
        if self.path.startswith("/api/queries"):
            with _history_lock:
                body = json.dumps(_history).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        live = _serving_view()
        live_html = ""
        if live:
            sess = live.get("sessions") or {}
            sess_rows = "".join(
                f"<tr><td>{html.escape(str(n))}</td>"
                f"<td>{s.get('queued')}</td><td>{s.get('weight')}</td></tr>"
                for n, s in sorted(sess.items()))
            live_html = (
                "<h2>serving queue (live)</h2>"
                f"<p>running {live.get('running', 0)}/"
                f"{live.get('concurrency', 0)} · queued "
                f"{live.get('queued', 0)} · admitted "
                f"{live.get('admitted_bytes', 0)} / "
                f"{live.get('admission_budget')} bytes</p>"
                + ("<table border=1><tr><th>session</th><th>queued</th>"
                   "<th>weight</th></tr>" + sess_rows + "</table>"
                   if sess_rows else ""))
        rows = []
        with _history_lock:
            for i, q in enumerate(reversed(_history)):
                srv = q.get("serving") or {}
                srv_html = ("<p><b>serving:</b> "
                            + html.escape(json.dumps(srv, default=str))
                            + "</p>" if srv else "")
                rec = q.get("recovery") or {}
                rec_html = ("<p><b>recovery events:</b> "
                            + html.escape(json.dumps(rec)) + "</p>"
                            if rec else "")
                shf = q.get("shuffle") or {}
                shf_html = ("<p><b>shuffle:</b> "
                            + html.escape(json.dumps(
                                {k: round(v, 1) for k, v in shf.items()}))
                            + "</p>" if shf else "")
                sio = q.get("io") or {}
                io_html = ("<p><b>io:</b> "
                           + html.escape(json.dumps(
                               {k: round(v, 1) for k, v in sio.items()}))
                           + "</p>" if sio else "")
                san = q.get("sanitizer") or {}
                san_html = ("<p><b>lock sanitizer:</b> "
                            + html.escape(json.dumps(
                                {k: round(v, 1) for k, v in san.items()}))
                            + "</p>" if san else "")
                rows.append(
                    f"<h3>query {len(_history) - i} — {q['ts']}</h3>"
                    f"{srv_html}{rec_html}{shf_html}{io_html}{san_html}"
                    f"<pre>{html.escape(q['explain'])}</pre>")
        # flight-recorder history view (persisted across restarts, unlike
        # the in-memory broadcast list above)
        hist_html = ""
        try:
            from . import tracing
            entries = tracing.flight_history(limit=20)
        except Exception:
            entries = []
        if entries:
            hist_rows = "".join(
                f"<tr><td>{html.escape(str(e.get('ts')))}</td>"
                f"<td>{float(e.get('wall_us', 0)) / 1e3:.1f}ms</td>"
                f"<td>{'SLOW' if e.get('slow') else ''}</td>"
                f"<td>{html.escape(str((e.get('trace') or {}).get('trace_id', '')))}</td>"
                f"</tr>" for e in entries)
            hist_html = ("<h2>query history (flight recorder)</h2>"
                         "<table border=1><tr><th>ts</th><th>wall</th>"
                         "<th>slow</th><th>trace</th></tr>"
                         + hist_rows + "</table>")
        body = ("<html><head><title>daft-tpu dashboard</title></head><body>"
                "<h1>daft-tpu queries</h1>" + live_html + hist_html
                + ("".join(rows) or "<p>no queries yet</p>")
                + "</body></html>").encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/html")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


_server_lock = threading.Lock()


def launch(port: int = DEFAULT_PORT, block: bool = False) -> int:
    """Start the dashboard server; returns the bound port."""
    global _server
    with _server_lock:
        if _server is None:
            _server = http.server.ThreadingHTTPServer(("127.0.0.1", port),
                                                      _Handler)
            t = threading.Thread(target=_server.serve_forever, daemon=True,
                                 name="daft-tpu-dashboard")
            t.start()
        srv = _server
    if block:
        try:
            while _server is srv:
                time.sleep(1)
        except KeyboardInterrupt:
            pass
    return srv.server_port


def shutdown() -> None:
    global _server
    with _server_lock:
        if _server is not None:
            _server.shutdown()
            _server.server_close()  # release the listening socket
            _server = None
