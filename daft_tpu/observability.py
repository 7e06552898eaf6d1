"""Per-operator runtime stats and progress reporting.

Capability mirror of the reference's observability stack:
- per-operator rows/cpu counters (``daft-local-execution/src/runtime_stats.rs:23-75``)
- progress bars (``progress_bar.rs`` / ``daft/runners/progress_bar.py``)
- ``explain_analyze`` plan annotation
  (``physical_planner/planner.rs:451-640``)

The reference's chrome-trace layer (``src/common/tracing/src/lib.rs``) is
the tracing plane's (``tracing.py``): ``DAFT_TPU_TRACE=1`` +
``DAFT_TPU_TRACE_DIR`` writes one Chrome trace per query, operators
included (``op:<Operator>`` spans, emitted from here at ``finish()``).

Env flags (same spirit as the reference's ``DAFT_DEV_*``):
- ``DAFT_TPU_PROGRESS`` — ``1`` enables a tqdm partition-progress bar
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional


# ------------------------------------------------------------ attribution
#
# Process-wide planes (shuffle / scan-io / recovery) are shared counters;
# diffing them per query breaks the moment two queries overlap (both diffs
# see the union). The serving plane needs per-query numbers, so counter
# chokepoints ALSO bump the thread's *attributed* RuntimeStatsContext:
# executors install their stats context on every thread that does work for
# the query (driver generators, pool workers, pipeline stages, IO fan-out),
# and `finish()` prefers the context-local tally over the process diff
# whenever the context was attributed at all.

_attr_tl = threading.local()


def current_attribution() -> Optional["RuntimeStatsContext"]:
    return getattr(_attr_tl, "ctx", None)


@contextlib.contextmanager
def attributed(ctx: Optional["RuntimeStatsContext"]):
    """Install ``ctx`` as this thread's stats-attribution target (and,
    when the context belongs to a traced query, its span context — the
    tracing plane rides the same propagation: pool workers, pipeline
    stage threads and prefetch producers all come through here)."""
    from . import tracing  # hot path: resolved from sys.modules
    prev = getattr(_attr_tl, "ctx", None)
    _attr_tl.ctx = ctx
    tctx = ctx.trace_ctx if ctx is not None else None
    tprev = tracing._set_current(tctx) if tctx is not None else None
    if ctx is not None:
        ctx._attributed = True
    try:
        yield
    finally:
        if tctx is not None:
            tracing._set_current(tprev)
        _attr_tl.ctx = prev


# nested-execution marker: worker-side stage fragments and
# coordinator-deferred executions run their own executors (each with its
# own RuntimeStatsContext + set_last_stats); per-query EXPORTS (otlp,
# trace files, flight recorder) must fire once per top-level query, so
# nested scopes suppress them and the outermost owner finalizes.

_nested_tl = threading.local()


@contextlib.contextmanager
def nested_scope():
    prev = getattr(_nested_tl, "n", 0)
    _nested_tl.n = prev + 1
    try:
        yield
    finally:
        _nested_tl.n = prev


def in_nested_scope() -> bool:
    return getattr(_nested_tl, "n", 0) > 0


@contextlib.contextmanager
def query_scope():
    """A top-level call that still has work to do once its executor is
    drained (``DataFrame.to_pydict`` turning the result into Python
    lists): the per-query exports wait for the end of the block, so that
    work lies inside the query's trace. Yields a callable giving the span
    context of the query that ran inside the block (None: none ran, or it
    is untraced). Inside an outer scope nothing changes: the outermost
    owner finalizes."""
    if in_nested_scope():
        yield lambda: None
        return
    before = last_query_stats_local()

    def ran() -> Optional["RuntimeStatsContext"]:
        ctx = last_query_stats_local()
        return ctx if ctx is not before else None

    try:
        with nested_scope():
            yield lambda: getattr(ran(), "trace_ctx", None)
    finally:
        ctx = ran()
        if ctx is not None:
            finalize_query(ctx)


def submit_attribution(pool: str) -> Optional["RuntimeStatsContext"]:
    """:func:`current_attribution` as a pool-submit site passes it to
    :func:`run_attributed`: when the thread is traced, stamped with the
    pool's name and the instant (``tracing.submitted``), so the worker
    records the submit's time in the pool's queue as ``wait:pool``."""
    from . import tracing
    return tracing.submitted(getattr(_attr_tl, "ctx", None), pool)


def run_attributed(ctx, fn, *args, **kwargs):
    """Run ``fn`` with ``ctx`` attributed — the shape pool-submit sites
    use to carry the submitting thread's attribution onto the worker
    (``ctx`` from :func:`current_attribution` or
    :func:`submit_attribution`)."""
    from . import tracing
    with attributed(tracing.started(ctx)):
        try:
            return fn(*args, **kwargs)
        finally:
            tracing.done(ctx)


def bump_plane(plane: str, key: str, n: float = 1) -> None:
    """Credit ``n`` to the attributed context's plane tally (no-op when
    the thread is unattributed — the process-wide counter the caller
    already bumped remains the only record, as before)."""
    ctx = current_attribution()
    if ctx is not None:
        ctx._bump(plane, key, n)


def _ledger_raw() -> Dict[str, dict]:
    """Raw snapshot of the device-kernel dispatch ledger (never raises —
    observability must not take a query down over a device import)."""
    try:
        from .device import costmodel
        return costmodel.ledger_snapshot(raw=True)
    except Exception:
        return {}


def _device_failures_raw() -> Dict[str, dict]:
    """Snapshot of device work that failed and ran on the host instead
    (``runtime.device_failed``): ``{site: {"count", "first_error"}}``."""
    try:
        from .device import costmodel
        return costmodel.failures_snapshot()
    except Exception:
        return {}


def _recovery_raw() -> Dict[str, int]:
    """Raw snapshot of the distributed resilience counters (retries,
    quarantines, recomputed map tasks, speculative wins/losses …) —
    never raises, like the device ledger."""
    try:
        from .distributed import resilience
        return resilience.counters_snapshot()
    except Exception:
        return {}


def _shuffle_raw() -> Dict[str, float]:
    """Raw snapshot of the shuffle data-plane counters (bytes written/
    fetched, compression ratio inputs, combine row reduction, fetch wall
    vs serial-equivalent time) — never raises, like the device ledger."""
    try:
        from .distributed import shuffle_service
        return shuffle_service.shuffle_counters_snapshot()
    except Exception:
        return {}


def _spill_raw() -> Dict[str, float]:
    """Raw snapshot of the out-of-core spill-tier counters (bytes
    written/read, partitions spilled, grace-join/agg recursions, store
    peak residency) — never raises, like the device ledger."""
    try:
        from .execution import memory
        return memory.spill_counters_snapshot()
    except Exception:
        return {}


def _scan_io_raw() -> Dict[str, float]:
    """Raw snapshot of the scan-plane IO counters (object GETs, planned
    ranges vs coalesced requests, bytes fetched vs used, prefetch wall vs
    serial-equivalent) — never raises, like the device ledger."""
    try:
        from .io import read_planner
        return read_planner.scan_counters_snapshot()
    except Exception:
        return {}


def _exchange_raw() -> Dict[str, float]:
    """Raw snapshot of the collective-exchange program-cache counters
    (hit/miss/uncacheable traces of the memoized mesh programs,
    ``parallel/exchange.py``) — never raises, like the device ledger."""
    try:
        from .parallel import exchange
        c = exchange.exchange_cache_counters()
        return {k: float(v) for k, v in c.items() if k != "entries"}
    except Exception:
        return {}


def _adaptive_raw() -> Dict[str, float]:
    """Raw snapshot of the self-tuning counters (calibration
    observations, re-plan decisions: combine flips, broadcast
    demotions, exchange re-picks, estimate rewrites) — never raises,
    like the device ledger."""
    try:
        from .physical import adaptive
        return adaptive.counters_snapshot()
    except Exception:
        return {}


def _governor_raw() -> Dict[str, float]:
    """Raw snapshot of the memory-governor action counters (pressure
    episodes, throttle waits, budget/prefetch shrinks, gc collections)
    — never raises, like the device ledger."""
    try:
        from .execution import governor
        return governor.counters_snapshot()
    except Exception:
        return {}


def _sanitizer_raw() -> Dict[str, float]:
    """Raw snapshot of the lock-order sanitizer counters (acquisitions,
    contended acquisitions, blocking-while-held events) — empty unless
    DAFT_TPU_SANITIZE=1; never raises, like the device ledger."""
    try:
        from .analysis import lock_sanitizer
        return lock_sanitizer.counters_snapshot()
    except Exception:
        return {}


def _retrace_raw() -> Dict[str, float]:
    """Raw snapshot of the retrace-sanitizer counters (trace events, XLA
    compiles + seconds, budget violations) — empty unless the retrace
    sanitizer is armed; never raises, like the device ledger."""
    try:
        from .analysis import retrace_sanitizer
        return retrace_sanitizer.counters_snapshot()
    except Exception:
        return {}


def _plansan_raw() -> Dict[str, float]:
    """Raw snapshot of the plan-sanitizer counters (rule checks,
    membership/order samples, conservation checks, violations) — empty
    unless the plan sanitizer is armed; never raises."""
    try:
        from .analysis import plan_sanitizer
        return plan_sanitizer.counters_snapshot()
    except Exception:
        return {}


def device_kernel_ledger() -> Dict[str, dict]:
    """Process-wide per-dispatch achieved-bytes/flops ledger with derived
    roofline/MFU percentages (``costmodel.ledger_record`` feeds it at
    every real argsort / join / grouped-agg / projection dispatch)."""
    try:
        from .device import costmodel
        return costmodel.ledger_snapshot()
    except Exception:
        return {}


class OperatorStats:
    """Counters for one physical operator (reference:
    ``RuntimeStatsContext`` counters)."""

    __slots__ = ("name", "rows_out", "batches_out", "inclusive_us",
                 "first_pull_s", "last_pull_s",
                 "morsel_rows_min", "morsel_rows_max", "workers", "lock")

    def __init__(self, name: str):
        self.name = name
        self.rows_out = 0
        self.batches_out = 0
        self.inclusive_us = 0
        # ``time.perf_counter()`` at the first pull's start and the last
        # pull's end: the interval the operator lived in (its ``op:``
        # span), where ``inclusive_us`` is only the time inside pulls
        self.first_pull_s = None
        self.last_pull_s = None
        # observed morsel sizes: shows the re-chunking buffer honoring
        # execution_config.default_morsel_size in explain_analyze/traces
        self.morsel_rows_min = None
        self.morsel_rows_max = None
        # worker-thread count of this operator's pipeline stage (push
        # executor map stages; None = single driver thread)
        self.workers = None
        self.lock = threading.Lock()

    def record(self, nrows: int, dur_us: int):
        with self.lock:
            self.rows_out += nrows
            self.batches_out += 1
            self.inclusive_us += dur_us
            if self.morsel_rows_min is None or nrows < self.morsel_rows_min:
                self.morsel_rows_min = nrows
            if self.morsel_rows_max is None or nrows > self.morsel_rows_max:
                self.morsel_rows_max = nrows

    def record_time(self, dur_us: int):
        with self.lock:
            self.inclusive_us += dur_us


class RuntimeStatsContext:
    """Per-query stats: one ``OperatorStats`` per physical-plan node.

    Timing semantics: ``inclusive_us`` is wall time spent producing each
    batch at that operator's output boundary (includes upstream pull in this
    pull-based pipeline); ``exclusive_us`` subtracts the children's inclusive
    time at render. With pipelined thread-pool ops this is an approximation —
    the reference's push model has the same per-operator granularity.
    """

    def __init__(self):
        from . import tracing
        self._ops: Dict[int, OperatorStats] = {}
        self._children: Dict[int, List[int]] = {}
        self._lock = threading.Lock()
        self.wall_us: Optional[int] = None
        self.plan = None  # physical plan root, set by the executor
        self._t0 = time.perf_counter()
        self._t0_unix_us = int(time.time() * 1e6)
        # tracing plane: adopt the thread's current span context (the
        # runner / serving scheduler started the trace before building
        # this context); None = this query is untraced — every span
        # site guard-checks that and stays allocation-free
        self.trace_ctx = tracing.current()
        self.trace_summary: Dict[str, object] = {}
        # per-dispatch device-kernel MFU/roofline accounting: snapshot the
        # process-wide ledger now, diff at finish() → this query's share
        self._ledger0 = _ledger_raw()
        self.device_kernels: Dict[str, dict] = {}
        # …device work that failed and was replaced by a host run
        self._device_failures0 = _device_failures_raw()
        self.device_failures: Dict[str, dict] = {}
        # same pattern for the resilience plane's recovery events
        self._recovery0 = _recovery_raw()
        self.recovery: Dict[str, int] = {}
        # …and for the shuffle data plane (bytes written/fetched,
        # compression, combine reduction, fetch overlap)
        self._shuffle0 = _shuffle_raw()
        self.shuffle: Dict[str, float] = {}
        # …and for the scan-side IO plane (requests vs planned ranges,
        # bytes fetched vs used, prefetch overlap)
        self._io0 = _scan_io_raw()
        self.io: Dict[str, float] = {}
        # …and the out-of-core spill tier (bytes written/read, grace
        # recursions, per-store peak residency)
        self._spill0 = _spill_raw()
        self.spill: Dict[str, float] = {}
        # …and the collective-exchange program cache (hit/miss/
        # uncacheable): the evidence that same-shape mesh exchanges
        # re-enter one trace instead of re-tracing per call
        self._exchange0 = _exchange_raw()
        self.exchange: Dict[str, float] = {}
        # …and the self-tuning feedback plane (round 20): calibration
        # observations + runtime re-plan decisions this query made
        self._adaptive0 = _adaptive_raw()
        self.adaptive: Dict[str, float] = {}
        # …and the memory governor (round 23): pressure actions taken
        # while this query ran, plus the process peak RSS at finish —
        # the bounded-RSS evidence the scale bench commits per query
        self._governor0 = _governor_raw()
        self.governor: Dict[str, float] = {}
        # …and for the lock-order sanitizer (DAFT_TPU_SANITIZE=1):
        # per-query acquisition/contention deltas + current graph size
        self._sanitizer0 = _sanitizer_raw()
        self.sanitizer: Dict[str, float] = {}
        # …and the retrace sanitizer (DAFT_TPU_SANITIZE_RETRACE): this
        # query's trace/recompile events — the per-query recompile tax
        self._retrace0 = _retrace_raw()
        self.retrace: Dict[str, float] = {}
        # …and the plan sanitizer (DAFT_TPU_SANITIZE_PLAN): this query's
        # plan-contract checks — rule schema equality, re-hashed
        # membership samples, sort-order and row-conservation proofs
        self._plansan0 = _plansan_raw()
        self.plansan: Dict[str, float] = {}
        # context-local plane tallies (shuffle/io/recovery): counter
        # chokepoints bump these through the thread attribution installed
        # by the executors; finish() prefers them over the process diffs
        # so two overlapping queries don't read each other's counters
        self._plane_lock = threading.Lock()
        self._planes: Dict[str, Dict[str, float]] = {}
        self._attributed = False
        # serving-plane block (queue wait, admission, cache hits) — set
        # by the query scheduler for queries it ran; empty otherwise
        self.serving: Dict[str, object] = {}

    def _bump(self, plane: str, key: str, n: float) -> None:
        with self._plane_lock:
            d = self._planes.setdefault(plane, {})
            d[key] = d.get(key, 0) + n

    def _plane(self, plane: str) -> Dict[str, float]:
        with self._plane_lock:
            return dict(self._planes.get(plane, {}))

    def register(self, node) -> OperatorStats:
        key = id(node)
        with self._lock:
            st = self._ops.get(key)
            if st is None:
                st = OperatorStats(type(node).__name__)
                self._ops[key] = st
                self._children[key] = [id(c) for c in node.children]
            return st

    def instrument(self, node, it):
        """Wrap a node's output iterator with rows/time accounting."""
        st = self.register(node)

        def gen():
            while True:
                t0 = time.perf_counter()
                if st.first_pull_s is None:
                    st.first_pull_s = t0
                try:
                    item = next(it)
                except StopIteration:
                    st.last_pull_s = time.perf_counter()
                    return
                t1 = st.last_pull_s = time.perf_counter()
                st.record(len(item), int((t1 - t0) * 1_000_000))
                yield item
        return gen()

    def finish(self):
        self.wall_us = int((time.perf_counter() - self._t0) * 1_000_000)
        # scoped attribution beats the process-wide diff: an attributed
        # context's tallies contain exactly this query's events even when
        # other queries ran concurrently. Unattributed contexts (e.g. the
        # distributed runner's driver-level context, whose counters come
        # from worker/fetch threads) keep the legacy diff semantics.
        try:
            from .device import costmodel
            if self._attributed:
                self.device_kernels = costmodel.ledger_from_tallies(
                    self._plane("device_kernels"))
            else:
                self.device_kernels = costmodel.ledger_delta(
                    self._ledger0, _ledger_raw())
        except Exception:
            self.device_kernels = {}
        fails = _device_failures_raw()
        counts = self._plane("device_failures") if self._attributed else {
            site: d["count"]
            - self._device_failures0.get(site, {}).get("count", 0)
            for site, d in fails.items()}
        self.device_failures = {
            site: {"count": int(n),
                   "first_error": fails.get(site, {}).get("first_error", "")}
            for site, n in counts.items() if n}
        if self._attributed:
            self.recovery = {k: int(v)
                             for k, v in self._plane("recovery").items()}
            self.shuffle = self._plane("shuffle")
            self.io = self._plane("io")
            self.spill = self._plane("spill")
            self.adaptive = self._plane("adaptive")
            self.governor = self._plane("governor")
        else:
            try:
                from .distributed import resilience
                self.recovery = resilience.counters_delta(
                    self._recovery0, _recovery_raw())
            except Exception:
                self.recovery = {}
            try:
                from .distributed import shuffle_service
                self.shuffle = shuffle_service.shuffle_counters_delta(
                    self._shuffle0, _shuffle_raw())
            except Exception:
                self.shuffle = {}
            try:
                from .io import read_planner
                self.io = read_planner.scan_counters_delta(
                    self._io0, _scan_io_raw())
            except Exception:
                self.io = {}
            try:
                from .execution import memory
                self.spill = memory.spill_counters_delta(
                    self._spill0, _spill_raw())
            except Exception:
                self.spill = {}
            try:
                from .physical import adaptive
                self.adaptive = adaptive.counters_delta(
                    self._adaptive0, _adaptive_raw())
            except Exception:
                self.adaptive = {}
            try:
                from .execution import governor
                self.governor = governor.counters_delta(
                    self._governor0, _governor_raw())
            except Exception:
                self.governor = {}
        # RSS gauges ride the governor block regardless of attribution:
        # peak RSS is process state (like the sanitizers), not traffic —
        # the scale bench's bounded-RSS gate reads it per query
        try:
            from .execution import governor
            self.governor["rss_peak_bytes"] = float(
                governor.peak_rss_bytes())
            lim = governor.limit_bytes()
            if lim:
                self.governor["rss_limit_bytes"] = float(lim)
        except Exception:
            pass
        # process-wide diff regardless of attribution: the program cache
        # is shared engine state (like the sanitizers), not per-thread
        # traffic — concurrent queries legitimately share its hits
        after_ex = _exchange_raw()
        self.exchange = {k: v - self._exchange0.get(k, 0)
                         for k, v in after_ex.items()
                         if v - self._exchange0.get(k, 0)}
        try:
            from .analysis import lock_sanitizer
            self.sanitizer = lock_sanitizer.counters_delta(
                self._sanitizer0, _sanitizer_raw())
        except Exception:
            self.sanitizer = {}
        try:
            from .analysis import retrace_sanitizer
            self.retrace = retrace_sanitizer.counters_delta(
                self._retrace0, _retrace_raw())
        except Exception:
            self.retrace = {}
        try:
            from .analysis import plan_sanitizer
            self.plansan = plan_sanitizer.counters_delta(
                self._plansan0, _plansan_raw())
        except Exception:
            self.plansan = {}
        self._emit_trace_spans()

    def _emit_trace_spans(self) -> None:
        """Fold this executor's per-operator timings into the query
        trace as one span per physical operator (children of the span
        context this executor ran under — the task:run span for worker
        fragments, the query root locally). The span is the interval the
        operator lived in, first pull's start to last pull's end;
        ``busy_us`` is the time inside its pulls (upstream included),
        ``self_us`` that less its children's."""
        ctx = self.trace_ctx
        if ctx is None:
            return
        rec = ctx.recorder
        try:
            for key, st in list(self._ops.items()):
                first = st.first_pull_s
                if first is None:  # registered, never pulled
                    first = last = self._t0
                else:
                    last = max(st.last_pull_s or first, first)
                rec.add(f"op:{st.name}",
                        rec.unique_span_id(f"op:{st.name}"),
                        ctx.span_id,
                        self._t0_unix_us + int((first - self._t0) * 1e6),
                        int((last - first) * 1e6),
                        attrs={"rows_out": st.rows_out,
                               "batches": st.batches_out,
                               "busy_us": st.inclusive_us,
                               "self_us": self.exclusive_us(key)},
                        lane="pipeline")
            self.trace_summary = rec.summary()
        except Exception:
            pass  # observability must never take the query down

    # ---- reporting ---------------------------------------------------
    def exclusive_us(self, key: int) -> int:
        st = self._ops[key]
        child_incl = sum(self._ops[c].inclusive_us
                         for c in self._children.get(key, [])
                         if c in self._ops)
        return max(st.inclusive_us - child_incl, 0)

    def render(self, plan=None) -> str:
        """ASCII explain-analyze tree (annotated like the reference's
        ``explain_analyze``)."""
        if plan is None:
            plan = self.plan
        lines = []
        if self.wall_us is not None:
            lines.append(f"query wall time: {self.wall_us / 1e6:.3f}s")

        def walk(node, depth):
            key = id(node)
            st = self._ops.get(key)
            pad = "  " * depth
            if st is None:
                lines.append(f"{pad}{type(node).__name__}")
            else:
                wk = f" workers={st.workers}" if st.workers else ""
                lines.append(
                    f"{pad}{st.name}: rows_out={st.rows_out} "
                    f"batches={st.batches_out} "
                    f"total={st.inclusive_us / 1e6:.3f}s "
                    f"self={self.exclusive_us(key) / 1e6:.3f}s{wk}")
            for c in node.children:
                walk(c, depth + 1)

        if plan is not None:
            walk(plan, 0)
        else:
            for st in self._ops.values():
                lines.append(f"{st.name}: rows_out={st.rows_out} "
                             f"batches={st.batches_out} "
                             f"total={st.inclusive_us / 1e6:.3f}s")
        if self.device_kernels:
            lines.append("device kernels (per-dispatch ledger, "
                         "end-to-end incl. link):")
            for kind, d in sorted(self.device_kernels.items()):
                extra = ""
                if "achieved_gbps" in d:
                    extra = f" {d['achieved_gbps']} GB/s"
                    if "roofline_pct" in d:  # a known chip is attached
                        extra += f" ({d['roofline_pct']}% roofline)"
                if "mfu_pct" in d:
                    extra += f" {d['mfu_pct']}% MFU"
                if "strategy" in d:
                    extra += f" strategy={d['strategy']}"
                if "overlap_x" in d:
                    # r17 async pipeline: serial-equivalent stage seconds
                    # vs pipelined wall (>1 = overlap really hid work)
                    extra += f" overlap={d['overlap_x']}x"
                if "fused_ops" in d:
                    # r21 whole-query compilation: operators fused into
                    # region programs + host round-trips that eliminated
                    extra += (f" fused_ops={d['fused_ops']}"
                              f" rt_saved={d.get('round_trips_saved', 0)}")
                if "fusion_x" in d:
                    extra += f" fusion={d['fusion_x']}x"
                lines.append(
                    f"  {kind}: dispatches={d['dispatches']} "
                    f"rows={d['rows']} time={d['seconds']:.3f}s{extra}")
        if self.device_failures:
            lines.append("device failures (ran on the HOST instead):")
            for site, d in sorted(self.device_failures.items()):
                lines.append(f"  {site}: {d['count']} — first error: "
                             f"{d['first_error']}")
        if self.recovery:
            lines.append("resilience (recovery events):")
            for k, v in sorted(self.recovery.items()):
                lines.append(f"  {k}: {v}")
        lines.extend(render_shuffle_block(self.shuffle))
        lines.extend(render_exchange_block(self.exchange))
        lines.extend(render_adaptive_block(self.adaptive))
        lines.extend(render_io_block(self.io))
        lines.extend(render_spill_block(self.spill))
        lines.extend(render_governor_block(self.governor))
        lines.extend(render_sanitizer_block(self.sanitizer))
        lines.extend(render_retrace_block(self.retrace))
        lines.extend(render_plansan_block(self.plansan))
        lines.extend(render_serving_block(self.serving))
        if self.trace_summary:
            t = self.trace_summary
            lines.append(f"trace: id={t.get('trace_id')} "
                         f"spans={t.get('spans')} "
                         f"dropped={t.get('dropped', 0)}")
        return "\n".join(lines)

    def as_dict(self) -> Dict[str, dict]:
        out = {}
        for key, st in self._ops.items():
            name = st.name
            i = 2
            while name in out:
                name = f"{st.name}#{i}"
                i += 1
            out[name] = {"rows_out": st.rows_out,
                         "morsel_rows_min": st.morsel_rows_min,
                         "morsel_rows_max": st.morsel_rows_max,
                         "workers": st.workers,
                         "batches_out": st.batches_out,
                         "inclusive_us": st.inclusive_us,
                         "exclusive_us": self.exclusive_us(key)}
        return out


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} GB"


def render_shuffle_block(sh: Dict[str, float]) -> List[str]:
    """Human lines for one query's shuffle data-plane delta (shared by
    ``explain(analyze=True)`` and the dashboard). Shows each fast-path
    layer's evidence: wire-vs-raw bytes (compression ratio), combine row
    reduction, and parallel-fetch wall vs the serial-equivalent sum."""
    if not sh:
        return []
    lines = ["shuffle (data plane):"]
    written = sh.get("bytes_written", 0)
    raw = sh.get("bytes_pushed_raw", 0)
    if written or raw:
        ratio = f", {raw / written:.2f}x compression" if written else ""
        lines.append(f"  written: {_fmt_bytes(written)} wire "
                     f"({_fmt_bytes(raw)} raw{ratio}), "
                     f"rows={int(sh.get('rows_pushed', 0))}")
    cin, cout = sh.get("combine_rows_in", 0), sh.get("combine_rows_out", 0)
    if cin:
        red = f" ({cin / cout:.1f}x reduction)" if cout else ""
        lines.append(f"  combine: {int(cin)} -> {int(cout)} rows{red}")
    fetched = sh.get("bytes_fetched", 0)
    if fetched or sh.get("fetches"):
        wall = sh.get("fetch_span_us", 0) / 1e6
        serial = sh.get("fetch_wall_us", 0) / 1e6
        overlap = f", wall {wall:.3f}s vs serial-equivalent " \
                  f"{serial:.3f}s" if wall else \
                  f", serial {serial:.3f}s"
        lines.append(f"  fetched: {_fmt_bytes(fetched)} in "
                     f"{int(sh.get('fetches', 0))} fetches{overlap}")
    paths = {p: int(sh.get(f"exchange_path_{p}", 0))
             for p in ("collective", "hierarchical", "flight")}
    if any(paths.values()):
        lines.append("  exchange paths: " + ", ".join(
            f"{p}={n}" for p, n in paths.items() if n))
    if sh.get("ici_exchanges"):
        lines.append(
            f"  ici: {_fmt_bytes(sh.get('ici_bytes', 0))} in "
            f"{int(sh.get('ici_exchanges', 0))} collective exchanges "
            f"({int(sh.get('ici_rows', 0))} rows over the mesh, "
            f"not the wire)")
    if sh.get("hierarchical_streams"):
        lines.append(f"  hierarchical: "
                     f"{int(sh.get('hierarchical_streams', 0))} "
                     f"per-mesh stream(s)")
    return lines


def render_exchange_block(ex: Dict[str, float]) -> List[str]:
    """Human lines for one query's collective-exchange program-cache
    delta (shared by ``explain(analyze=True)`` and the dashboard): the
    evidence that repeated same-shape mesh exchanges re-entered one
    memoized trace instead of re-tracing per call."""
    if not ex:
        return []
    lines = ["exchange programs (collective cache):"]
    lines.append("  " + ", ".join(
        f"{k}={int(v)}" for k, v in sorted(ex.items())))
    return lines


def render_adaptive_block(d: Dict[str, float]) -> List[str]:
    """Human lines for one query's self-tuning delta (shared by
    ``explain(analyze=True)`` and the dashboard): the re-plan decisions
    it made, the calibration observations it fed, plus the live
    calibrated-vs-default state of the cost-model constants (which
    learned values are overriding the hard-coded defaults right now)."""
    cal_names: List[str] = []
    try:
        from .device import calibration
        if calibration.enabled():
            cal_names = calibration.calibrated_names()
    except Exception:
        pass
    if not d and not cal_names:
        return []
    lines = ["adaptive (self-tuning):"]
    decisions = {k: int(v) for k, v in sorted(d.items())
                 if k != "calibration_observations" and v}
    if decisions:
        lines.append("  re-plan: " + ", ".join(
            f"{k}={v}" for k, v in decisions.items()))
    obs_n = int(d.get("calibration_observations", 0))
    if obs_n:
        lines.append(f"  calibration: {obs_n} observations fed")
    if cal_names:
        lines.append("  calibrated constants (overriding defaults): "
                     + ", ".join(cal_names))
    return lines


def render_spill_block(d: Dict[str, float]) -> List[str]:
    """Human lines for one query's out-of-core spill delta (shared by
    ``explain(analyze=True)`` and the dashboard): disk bytes the spill
    tier wrote/read, partitions that left RAM, grace-join/agg recursion
    evidence (deepest rotated-radix level reached, depth-bound
    exhaustions on unsplittable keys), and the summed per-store peak
    residency of the stores that spilled (an upper bound on what the
    spill tier held resident)."""
    if not d:
        return []
    lines = ["spill (out-of-core tier):"]
    written = d.get("bytes_written", 0)
    read = d.get("bytes_read", 0)
    if written or read:
        lines.append(f"  disk: {_fmt_bytes(written)} written / "
                     f"{_fmt_bytes(read)} read, "
                     f"{int(d.get('partitions_spilled', 0))} partitions "
                     f"spilled")
    jp, jg = int(d.get("joins_partitioned", 0)), \
        int(d.get("joins_gathered", 0))
    if jp or jg:
        lines.append(f"  grace join: {jp} partitioned, {jg} gathered")
    rec = int(d.get("recursions", 0))
    if rec or d.get("depth_exhausted"):
        deepest = max((int(k.rsplit("_d", 1)[1]) for k in d
                       if k.startswith("recursions_d")), default=0)
        lines.append(
            f"  recursion: {rec} re-partitions (deepest level {deepest}),"
            f" {int(d.get('depth_exhausted', 0))} depth-bound exhaustions")
    if d.get("agg_buckets_merged"):
        lines.append(f"  agg: {int(d.get('agg_buckets_merged', 0))} "
                     f"state buckets merged on read")
    if d.get("stores"):
        ns = int(d.get("stores", 0))
        # summed per-store peaks: an upper bound on what the spilling
        # stores held resident (stores are often sequential, so the true
        # instantaneous peak is usually far lower)
        lines.append(
            f"  resident: ≤{_fmt_bytes(d.get('store_peak_bytes', 0))} "
            f"summed peak across {ns} spilling store(s)")
    disk_w = d.get("disk_bytes_written", 0)
    if disk_w and written:
        # post-codec file bytes vs logical bytes: the spill codec's
        # measured on-disk win (r23 fast path)
        lines.append(
            f"  codec: {_fmt_bytes(disk_w)} on disk "
            f"({written / disk_w:.2f}x compression)")
    return lines


def render_governor_block(d: Dict[str, float]) -> List[str]:
    """Human lines for one query's memory-governor delta (shared by
    ``explain(analyze=True)`` and the dashboard): the backpressure
    actions taken while the query ran (pressure episodes, bounded
    throttle waits, budget/prefetch shrinks, gc passes) and the process
    peak RSS against the configured limit — the bounded-RSS evidence
    the scale bench commits per query."""
    peak = d.get("rss_peak_bytes", 0)
    lim = d.get("rss_limit_bytes", 0)
    actions = {k: v for k, v in d.items()
               if k not in ("rss_peak_bytes", "rss_limit_bytes") and v}
    if not actions and not (peak and lim):
        return []
    lines = ["memory governor:"]
    if peak:
        vs = f" vs limit {_fmt_bytes(lim)}" if lim else ""
        lines.append(f"  rss: peak {_fmt_bytes(peak)}{vs}")
    if actions:
        waits = int(actions.pop("throttle_waits", 0))
        wait_us = actions.pop("throttle_wait_us", 0)
        if waits:
            lines.append(f"  throttle: {waits} bounded wait(s), "
                         f"{wait_us / 1e6:.2f}s total")
        rest = {k: int(v) for k, v in sorted(actions.items())
                if not k.startswith("throttle_")}
        if rest:
            lines.append("  actions: " + ", ".join(
                f"{k}={v}" for k, v in rest.items()))
    return lines


def render_io_block(d: Dict[str, float]) -> List[str]:
    """Human lines for one query's scan-plane IO delta (shared by
    ``explain(analyze=True)`` and the dashboard). Each fast-path layer's
    evidence: requests issued vs byte ranges needed pre-coalesce, bytes
    fetched vs bytes actually decoded, and prefetch-pipelined wall vs the
    serial-equivalent sum of per-task load times."""
    if not d:
        return []
    lines = ["io (scan plane):"]
    gets = int(d.get("gets", 0))
    planned = int(d.get("ranges_planned", 0))
    reqs = int(d.get("range_requests", 0))
    if gets or planned:
        coal = f", {planned / reqs:.1f}x coalesced" if reqs else ""
        lines.append(f"  requests: {gets} GETs "
                     f"({planned} ranges needed -> {reqs} range "
                     f"requests{coal})")
    fetched = d.get("bytes_fetched", 0)
    used = d.get("bytes_used", 0)
    if fetched:
        eff = f" ({100.0 * used / fetched:.1f}% used)" if used else ""
        lines.append(f"  bytes: {_fmt_bytes(fetched)} fetched / "
                     f"{_fmt_bytes(used)} decoded{eff}")
    span = d.get("scan_span_us", 0) / 1e6
    serial = d.get("scan_task_us", 0) / 1e6
    if span or serial:
        tasks = int(d.get("prefetch_tasks", 0))
        overlap = f" ({serial / span:.1f}x overlap)" if span else ""
        lines.append(f"  prefetch: {tasks} tasks, wall {span:.3f}s vs "
                     f"serial-equivalent {serial:.3f}s{overlap}")
    misses = int(d.get("planner_miss_gets", 0))
    falls = int(d.get("planned_read_fallbacks", 0))
    if misses or falls:
        lines.append(f"  planner: {misses} miss GETs, "
                     f"{falls} whole-file fallbacks")
    return lines


def render_serving_block(s: Dict[str, object]) -> List[str]:
    """Human lines for one query's serving-plane record (shared by
    ``explain(analyze=True)`` and the dashboard; set only for queries run
    through the query scheduler): which session/priority it ran as, how
    long it queued, what the admission controller charged it, and whether
    the plan/result caches served it."""
    if not s:
        return []
    lines = ["serving (query scheduler):"]
    lines.append(
        f"  session={s.get('session')} priority={s.get('priority', 0)} "
        f"queue_wait={float(s.get('queue_wait_us', 0)) / 1e3:.1f}ms "
        f"admitted={_fmt_bytes(float(s.get('admitted_bytes', 0)))} "
        f"(running={int(s.get('running_at_admit', 0))} at admit)")
    lines.append(
        f"  plan cache: {s.get('plan_cache', 'off')}, "
        f"result cache: {s.get('result_cache', 'off')}")
    return lines


def render_sanitizer_block(s: Dict[str, float]) -> List[str]:
    """Human lines for one query's lock-sanitizer delta (shared by
    ``explain(analyze=True)`` and the dashboard; empty unless
    ``DAFT_TPU_SANITIZE=1``): current lock-order graph size + cycle
    count, and this query's acquisition/contention/blocking events."""
    if not s:
        return []
    cycles = int(s.get("graph_cycles", 0))
    lines = ["concurrency (lock sanitizer):"]
    lines.append(f"  graph: {int(s.get('graph_locks', 0))} lock sites, "
                 f"{int(s.get('graph_edges', 0))} order edges, "
                 f"{cycles} cycle{'s' if cycles != 1 else ''}"
                 + (" (POTENTIAL DEADLOCK)" if cycles else ""))
    lines.append(f"  this query: {int(s.get('acquisitions', 0))} "
                 f"acquisitions, {int(s.get('contended', 0))} contended, "
                 f"{int(s.get('blocking_while_held', 0))} "
                 f"blocking-while-held")
    return lines


def render_retrace_block(s: Dict[str, float]) -> List[str]:
    """Human lines for one query's retrace-sanitizer delta (shared by
    ``explain(analyze=True)`` and the dashboard; empty unless the
    retrace sanitizer is armed): trace events + XLA compiles this query
    paid — a hot query's line should read all zeros."""
    if not s:
        return []
    viol = int(s.get("violations", 0))
    lines = ["shape discipline (retrace sanitizer):"]
    lines.append(
        f"  this query: {int(s.get('traces', 0))} trace events, "
        f"{int(s.get('compiles', 0))} XLA compiles "
        f"({float(s.get('compile_seconds', 0.0)):.3f}s compiling), "
        f"{int(s.get('unscoped_traces', 0))} unscoped")
    lines.append(
        f"  budget violations: {viol} this query, "
        f"{int(s.get('total_violations', 0))} total"
        + (" (RETRACE TAX — see retrace_sanitizer.report())"
           if viol else ""))
    return lines


def render_plansan_block(s: Dict[str, float]) -> List[str]:
    """Human lines for one query's plan-sanitizer delta (shared by
    ``explain(analyze=True)`` and the dashboard; empty unless the plan
    sanitizer is armed): contract checks this query paid and whether
    any plan invariant broke — a healthy query reads violations 0."""
    if not s:
        return []
    viol = int(s.get("violations", 0))
    lines = ["plan discipline (plan sanitizer):"]
    lines.append(
        f"  this query: {int(s.get('rule_checks', 0))} rule schema "
        f"checks, {int(s.get('membership_parts', 0))} partitions "
        f"({int(s.get('membership_rows', 0))} rows) membership-sampled, "
        f"{int(s.get('order_parts', 0))} order-checked, "
        f"{int(s.get('conservation_checks', 0))} conservation proofs")
    lines.append(
        f"  contract violations: {viol} this query, "
        f"{int(s.get('total_violations', 0))} total"
        + (" (PLAN CONTRACT BROKEN — see plan_sanitizer.report())"
           if viol else ""))
    return lines


# ---------------------------------------------------------------------------
# per-process "last query" registry


_last_stats: Optional[RuntimeStatsContext] = None
_last_lock = threading.Lock()


def xplane_trace_dir() -> Optional[str]:
    """``DAFT_TPU_XPLANE_DIR=<dir>`` captures a jax profiler (xplane/
    TensorBoard) trace per query — the TPU-native analogue of the
    reference's chrome-trace layer (``src/common/tracing``): device kernel
    timelines, HBM transfers and XLA compilation spans land in
    ``<dir>/plugins/profile``, and the query's own spans lie on the host
    lines of the same profile as ``daft:<span>`` (a query profiled this
    way is traced: ``tracing.profile_requested``)."""
    from .analysis import knobs
    return knobs.env_str("DAFT_TPU_XPLANE_DIR") or None


_xplane_lock = threading.Lock()
_xplane_owner: Optional[object] = None


class _XplaneTrace:
    """Per-query jax profiler session. The jax profiler is process-global,
    so only the OUTERMOST executor owns the capture — nested/concurrent
    executors (exchanges, worker tasks) no-op instead of truncating the
    query-level trace. Never takes the query down on failure."""

    def __init__(self, out_dir: str):
        global _xplane_owner
        self._active = False
        with _xplane_lock:
            if _xplane_owner is not None:
                return  # someone else is tracing this process
            _xplane_owner = self
        try:
            import jax
            jax.profiler.start_trace(out_dir)
            self._active = True
        except Exception:
            with _xplane_lock:
                _xplane_owner = None

    def stop(self) -> None:
        global _xplane_owner
        if not self._active:
            return
        self._active = False
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        with _xplane_lock:
            if _xplane_owner is self:
                _xplane_owner = None


def progress_enabled() -> bool:
    from .analysis import knobs
    return bool(knobs.env_bool("DAFT_TPU_PROGRESS"))


def new_query_stats() -> RuntimeStatsContext:
    from . import tracing
    ctx = RuntimeStatsContext()
    # fallback trace start for executors driven without a runner (the
    # runners/serving scheduler normally start the trace earlier, so the
    # planner spans land too); nested scopes never start traces — the
    # query-wide sampling decision was the top level's to make
    if ctx.trace_ctx is None and not in_nested_scope():
        ctx.trace_ctx = tracing.maybe_start_trace("query")
    return ctx


_tl_last = threading.local()


def set_last_stats(ctx: RuntimeStatsContext):
    global _last_stats
    with _last_lock:
        _last_stats = ctx
    # per-thread record too: under the serving plane N queries finish
    # concurrently and the GLOBAL last-stats slot is whichever finished
    # last — each scheduler worker reads its own query's context back via
    # last_query_stats_local() (the executor's finish runs on the thread
    # that drained it)
    _tl_last.stats = ctx
    # feed the dashboard when it's up (reference: broadcast_query_plan hook)
    from . import dashboard
    if dashboard._server is not None:
        dashboard.broadcast_query(ctx)
    # per-query exports fire once per TOP-LEVEL query: nested scopes
    # (worker stage fragments, scheduler-deferred executions) suppress
    # them and the outermost coordinator calls finalize_query itself
    if not in_nested_scope():
        finalize_query(ctx)


# ------------------------------------------------- observability counters
# Export-plane accounting (otlp_export_errors & co): process-wide like
# the shuffle/recovery counters, surfaced through the /metrics scrape.

_obs_counters_lock = threading.Lock()
_obs_counters: Dict[str, float] = {}


def obs_count(name: str, n: float = 1) -> None:
    with _obs_counters_lock:
        _obs_counters[name] = _obs_counters.get(name, 0) + n


def obs_counters_snapshot() -> Dict[str, float]:
    with _obs_counters_lock:
        return dict(_obs_counters)


def finalize_query(ctx: RuntimeStatsContext) -> None:
    """One top-level query's export hooks: OTLP metrics (+spans for
    traced queries), the merged Chrome trace file, and the flight
    recorder. Idempotent per trace; never raises into the query path."""
    from . import tracing
    from .analysis import knobs
    endpoint = knobs.env_str("DAFT_TPU_OTLP_ENDPOINT")
    if endpoint:
        export_otlp(ctx, endpoint)
    try:
        tctx = ctx.trace_ctx
        rec = tctx.recorder if tctx is not None else None
        if rec is not None and not rec.exported:
            rec.exported = True
            rec.finish()
            ctx.trace_summary = rec.summary()
            tracing.unregister_recorder(rec.trace_id)
            out_dir = knobs.env_str("DAFT_TPU_TRACE_DIR")
            if out_dir:
                try:
                    os.makedirs(out_dir, exist_ok=True)
                    path = os.path.join(out_dir,
                                        f"trace_{rec.trace_id}.json")
                    with open(path, "w") as f:
                        json.dump(tracing.chrome_trace_json(rec), f)
                except Exception:
                    obs_count("trace_export_errors")
            if endpoint:
                _post_otlp_async(endpoint, "/v1/traces",
                                 tracing.otlp_spans_payload(rec))
        if tracing._flight_path():  # don't build entries nobody records
            tracing.flight_record(flight_entry(ctx))
    except Exception:
        obs_count("finalize_errors")


def flight_entry(ctx: RuntimeStatsContext) -> dict:
    """One flight-recorder record: the query's stat blocks, trace
    summary and slow-query flag."""
    from . import tracing
    wall_us = ctx.wall_us or 0
    slow_ms = tracing.slow_query_ms()
    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "wall_us": wall_us,
        "slow": bool(slow_ms and slow_ms > 0
                     and wall_us / 1e3 > slow_ms),
        "operators": ctx.as_dict(),
    }
    for block in ("recovery", "shuffle", "exchange", "io", "spill",
                  "governor", "adaptive", "device_kernels",
                  "device_failures", "serving",
                  "sanitizer", "retrace", "plansan"):
        v = getattr(ctx, block, None)
        if v:
            entry[block] = dict(v)
    if ctx.trace_summary:
        entry["trace"] = dict(ctx.trace_summary)
    return entry


# ------------------------------------------------------------------ OTLP

def otlp_payload(ctx: RuntimeStatsContext) -> dict:
    """Per-operator counters as an OTLP/HTTP JSON ExportMetricsServiceRequest
    (the reference exports the same counters over OTLP:
    ``src/common/tracing/src/lib.rs:29-90``, ``runtime_stats.rs:23-66``).
    DELTA temporality: each export carries one query's contribution, keyed
    only by operator name — bounded series cardinality, and collectors sum
    deltas across queries without reset semantics."""
    now_ns = int(time.time() * 1e9)
    start_ns = now_ns - (ctx.wall_us or 0) * 1000

    def sum_metric(name: str, unit: str, points):
        return {"name": name, "unit": unit, "sum": {
            "aggregationTemporality": 1,  # DELTA
            "isMonotonic": True,
            "dataPoints": points}}

    def point(value: int, op_name: str):
        return {"asInt": str(int(value)),
                "startTimeUnixNano": str(start_ns),
                "timeUnixNano": str(now_ns),
                "attributes": [
                    {"key": "operator",
                     "value": {"stringValue": op_name}}]}

    per_op = ctx.as_dict()
    metrics = [
        sum_metric("daft_tpu.operator.rows_out", "{row}",
                   [point(st["rows_out"], nm)
                    for nm, st in per_op.items()]),
        sum_metric("daft_tpu.operator.batches_out", "{batch}",
                   [point(st["batches_out"], nm)
                    for nm, st in per_op.items()]),
        sum_metric("daft_tpu.operator.cpu_us", "us",
                   [point(st["exclusive_us"], nm)
                    for nm, st in per_op.items()]),
    ]
    return {"resourceMetrics": [{
        "resource": {"attributes": [
            {"key": "service.name",
             "value": {"stringValue": "daft_tpu"}}]},
        "scopeMetrics": [{
            "scope": {"name": "daft_tpu.observability"},
            "metrics": metrics}]}]}


def _post_otlp_async(endpoint: str, route: str, payload_obj: dict) -> None:
    """Fire-and-forget OTLP/HTTP POST on a daemon thread with a bounded
    timeout (``DAFT_TPU_OTLP_TIMEOUT``). A hung or erroring collector
    can neither stall nor fail the query — every failure (including a
    non-2xx status, a read that outlives the timeout, or a thread spawn
    at interpreter shutdown) is swallowed and counted in
    ``otlp_export_errors``."""
    import urllib.request

    try:
        from .analysis import knobs
        timeout = knobs.env_float("DAFT_TPU_OTLP_TIMEOUT")
        payload = json.dumps(payload_obj).encode()
        url = endpoint.rstrip("/") + route

        def post():
            try:
                req = urllib.request.Request(
                    url, data=payload,
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=timeout).read()
            except Exception:
                obs_count("otlp_export_errors")

        threading.Thread(target=post, name="daft-tpu-otlp",
                         daemon=True).start()
    except Exception:
        obs_count("otlp_export_errors")


def export_otlp(ctx: RuntimeStatsContext, endpoint: str) -> None:
    """Fire-and-forget POST of the query's operator counters to an
    OTLP/HTTP collector (``<endpoint>/v1/metrics``); traced queries
    additionally export their span tree to ``/v1/traces`` (see
    ``finalize_query``). Never fails or blocks the query."""
    try:
        _post_otlp_async(endpoint, "/v1/metrics", otlp_payload(ctx))
    except Exception:
        obs_count("otlp_export_errors")


def last_query_stats() -> Optional[RuntimeStatsContext]:
    """Stats of the most recent execution in this process."""
    with _last_lock:
        return _last_stats


def last_query_stats_local() -> Optional[RuntimeStatsContext]:
    """Stats of the most recent execution drained on THIS thread (nested
    executions overwrite it in completion order, so after a top-level
    drain this is the outermost query's context)."""
    return getattr(_tl_last, "stats", None)


def wrap_progress(it, desc: str = "partitions"):
    """tqdm progress over a partition stream when DAFT_TPU_PROGRESS=1."""
    if not progress_enabled():
        return it
    try:
        from tqdm import tqdm
    except ImportError:
        return it

    def gen():
        rows = 0
        with tqdm(desc=desc, unit="part") as bar:
            for p in it:
                rows += len(p)
                bar.set_postfix_str(f"{rows} rows")
                bar.update(1)
                yield p
    return gen()
