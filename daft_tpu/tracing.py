"""Query-wide tracing plane: distributed span propagation + exports.

One query = one trace. Explicit span context (trace id, span id, parent
id) threads through every layer that already has stats hooks:

- the serving scheduler (queue-wait + run spans, cancellation events),
- the planner (optimize / translate / fingerprint-cache outcome),
- the device runtime (one span per dispatch, annotated with the MFU
  ledger's strategy/bytes/flops — the roofline story on the timeline),
- pipeline stages and scan-prefetch producers (riding the existing
  thread-attribution machinery in ``observability``),
- the distributed tier: span context travels over the HTTP/Flight
  shuffle wire as headers and over the remote-worker RPC; workers emit
  child spans for task run / fetch / retry / lineage-recompute /
  speculation and ship them back with task results; the driver merges
  them — with per-worker clock-offset correction — into ONE query trace.

Exports: Chrome trace JSON (perfetto-loadable) per query
(``DAFT_TPU_TRACE_DIR``), OTLP spans (``DAFT_TPU_OTLP_ENDPOINT``,
``/v1/traces`` beside the metrics export), a Prometheus text-format
``/metrics`` scrape on the dashboard, and a bounded flight recorder
(``DAFT_TPU_QUERY_LOG`` JSONL with size-capped rotation) served at
``/api/history``.

Design contracts:

- **near-free when off** — span creation guards on the thread's current
  span context (one ``getattr``); no dicts, no ids, no timestamps are
  built for untraced queries. The per-query enable decision
  (``DAFT_TPU_TRACE`` × ``DAFT_TPU_TRACE_SAMPLE``, or a profile being
  taken: see :func:`maybe_start_trace`) happens once at trace creation.
- **one clock** — while a profile is being taken every live span is
  also a ``jax.profiler.TraceAnnotation`` named ``daft:<span>`` on the
  same thread, so the profile holds the program's spans on its host
  lines, beside the device lines they explain. A span's ``ts_us`` is
  wall-clock time (the trace's start on ``time.time()`` plus what
  ``time.perf_counter_ns()`` has counted since); its ``dur_us`` comes
  from ``perf_counter_ns`` alone, so a host that steps its clock moves
  no duration.
- **work apart from wait** — a live leaf span (and the launch:
  :data:`CPU_SPANS`) also reads its thread's CPU clock
  (``time.thread_time_ns()``) and carries ``cpu_us``:
  ``dur_us - cpu_us`` is how long the thread stood still inside it, and
  :data:`COMPUTE_SPANS` names the spans for which that is a wait for the
  GIL or a lock (``phases[name]["timed_us"] - ["cpu_us"]``). The jitted
  call is a span of its own (``dispatch:launch``, :func:`launch`). The
  places where work waits in a queue are ``wait:*`` spans
  (``wait:window``, ``wait:result``, ``wait:pool``, ``wait:channel``;
  :func:`wait`, :func:`note_wait`), a taker's latency after an item was
  handed to it is tallied (``summary()["handoffs"]``), and
  ``summary()["holes"]`` lays those spans over the part of the query's
  wall that no leaf span covers.
- **deterministic under chaos** — span ids are minted by hashing the
  planner's stable identities (``Stage.task_key`` fault keys, operator
  names, attempt numbers), never RNG, so a seeded
  ``DAFT_TPU_CHAOS_SERIALIZE=1`` run replays bit-identical span ids.
- **bounded** — ``DAFT_TPU_TRACE_MAX_SPANS`` caps the per-query buffer
  (drops counted), the recorder registry is size-capped, and the flight
  recorder rotates at ``DAFT_TPU_QUERY_LOG_BYTES``.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------- ids

#: spans whose trace buffer is full are counted, never stored; the
#: registry holds at most this many ACTIVE (unexported) recorders —
#: an abandoned trace must not leak its spans forever
_MAX_ACTIVE_RECORDERS = 64

_WIRE_TRACE_HEADER = "X-Daft-Trace-Id"
_WIRE_PARENT_HEADER = "X-Daft-Parent-Span"


def span_id_from(key: str) -> str:
    """16-hex span id from a stable key. Pure function of the key — the
    same planner-minted identity yields the same id run after run, which
    is the chaos-replay contract for traces."""
    return hashlib.sha256(b"daft-span\x1f"
                          + key.encode()).hexdigest()[:16]


def _hash01(key: str) -> float:
    h = hashlib.sha256(b"daft-trace\x1f" + key.encode()).digest()
    return int.from_bytes(h[:8], "big") / 2 ** 64


def _now_us() -> int:
    return int(time.time() * 1e6)


#: a wait shorter than this is counted (``summary()["waits_short"]``),
#: not stored: a join pass hands thousands of morsels from stage to stage
#: and most takers wait a few microseconds for theirs, while a query's
#: buffer holds ``DAFT_TPU_TRACE_MAX_SPANS`` spans
WAIT_FLOOR_US = 200


def _replayed() -> bool:
    """A chaos replay (``DAFT_TPU_CHAOS_SERIALIZE=1`` or an active fault
    plan: ``device.pipeline.sequential_fallback``'s two conditions): its
    span ids have to come out bit-identical, and which waits pass the
    floor hangs on the clock, so every wait is counted and none stored."""
    from .analysis import knobs
    if knobs.env_bool("DAFT_TPU_CHAOS_SERIALIZE"):
        return True
    try:
        from .distributed.resilience import active_fault_plan
        return active_fault_plan() is not None
    except Exception:
        return False


# ----------------------------------------------------------- recorder


class SpanRecorder:
    """One query's span buffer. Bounded; thread-safe; ids deterministic."""

    def __init__(self, trace_id: str, max_spans: Optional[int] = None,
                 bridge: bool = False):
        if max_spans is None:
            from .analysis import knobs
            max_spans = knobs.env_int("DAFT_TPU_TRACE_MAX_SPANS")
        self.trace_id = trace_id
        #: a profile is being taken: every live span is also a
        #: ``jax.profiler.TraceAnnotation`` (decided once, at the start)
        self.bridge = bridge
        self.max_spans = max(int(max_spans), 1)
        #: decided once, at the start, as ``bridge`` is
        self.wait_floor_us = sys.maxsize if _replayed() else WAIT_FLOOR_US
        self._lock = threading.Lock()
        self._spans: List[dict] = []
        self.dropped = 0
        self._key_seq: Dict[str, int] = {}
        self.clock_offsets_us: Dict[str, int] = {}
        self.root_id = span_id_from("query")
        self._root_t0 = _now_us()
        #: the same instant on ``time.perf_counter_ns()``, the clock every
        #: duration of this trace is taken from (:meth:`wall_us`); as
        #: ``time.perf_counter()`` seconds a reader in this process
        #: places the trace among its own timings with no offset
        self._root_perf_ns = time.perf_counter_ns()
        self._root_perf_s = self._root_perf_ns / 1e9
        self._root_dur = 0
        #: counts kept on the root span (``tally``): where each scan
        #: task's table came from
        self._tallies: Dict[str, int] = {}
        #: per chip (``tally_chip``): the tables it ran, their rows, and
        #: the HBM column cache's bytes on it as last noted
        self._chips: Dict[int, Dict[str, int]] = {}
        self._summary: Optional[dict] = None
        self._finished = False
        self.exported = False
        self.status = "ok"

    # -- id minting ---------------------------------------------------
    def unique_key(self, key: str) -> str:
        """``key``, suffixed ``~N`` on repeats — a recomputed map task
        reuses its stable fault key; its spans must still be distinct.
        The counter is deterministic whenever execution order is
        (which ``DAFT_TPU_CHAOS_SERIALIZE=1`` guarantees)."""
        with self._lock:
            n = self._key_seq.get(key, 0)
            self._key_seq[key] = n + 1
        return key if n == 0 else f"{key}~{n}"

    def unique_span_id(self, key: str) -> str:
        return span_id_from(self.unique_key(key))

    # -- clock ---------------------------------------------------------
    def wall_us(self, perf_ns: int) -> int:
        """The wall-clock microsecond of a ``time.perf_counter_ns()``
        reading: the trace's start plus what the monotonic clock counted
        since, so spans of one trace keep their order and their lengths
        whatever the host does to its clock meanwhile."""
        return self._root_t0 + (perf_ns - self._root_perf_ns) // 1000

    def now_us(self) -> int:
        return self.wall_us(time.perf_counter_ns())

    # -- recording ----------------------------------------------------
    def add(self, name: str, span_id: str, parent_id: Optional[str],
            ts_us: int, dur_us: int, attrs: Optional[dict] = None,
            lane: str = "driver", status: str = "ok",
            cpu_us: Optional[int] = None) -> None:
        """``cpu_us``: the CPU time of the thread that lived the span
        (live spans of :data:`CPU_SPANS` only; any other, a span added
        with explicit timestamps, or one shipped back from another
        process, has none: absent, not 0)."""
        span = {"name": name, "span_id": span_id,
                "parent_id": parent_id or self.root_id,
                "ts_us": int(ts_us), "dur_us": max(int(dur_us), 0),
                "lane": lane}
        if cpu_us is not None:
            span["cpu_us"] = max(int(cpu_us), 0)
        if attrs:
            span["attrs"] = attrs
        if status != "ok":
            span["status"] = status
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return
            self._spans.append(span)

    def add_remote(self, spans: List[dict], offset_us: int,
                   worker: str) -> None:
        """Merge spans shipped back from another process, correcting
        their wall clock by the measured offset."""
        with self._lock:
            self.clock_offsets_us[worker] = int(offset_us)
        for s in spans:
            try:
                self.add(s["name"], s["span_id"], s.get("parent_id"),
                         int(s["ts_us"]) + int(offset_us), s["dur_us"],
                         attrs=s.get("attrs"),
                         lane=s.get("lane") or f"worker:{worker}",
                         status=s.get("status", "ok"))
            except (KeyError, TypeError, ValueError):
                self.dropped += 1

    def tally(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._tallies[key] = self._tallies.get(key, 0) + n

    def tally_max(self, key: str, n: int) -> None:
        with self._lock:
            self._tallies[key] = max(self._tallies.get(key, 0), n)

    def add_wait(self, name: str, parent_id: Optional[str], t0_ns: int,
                 t1_ns: int, attrs: Optional[dict] = None) -> None:
        """A wait whose length is known only afterwards, between two
        ``time.perf_counter_ns()`` readings: a ``wait:*`` span with
        explicit timestamps (so not in a profile), or, under
        :data:`WAIT_FLOOR_US`, a count."""
        dur_us = max(t1_ns - t0_ns, 0) // 1000
        if dur_us < self.wait_floor_us:
            self.wait_short(dur_us)
            return
        self.add(name, self.unique_span_id(name), parent_id,
                 self.wall_us(t0_ns), dur_us, attrs=attrs, lane="wait")

    def wait_short(self, dur_us: int) -> None:
        with self._lock:
            t = self._tallies
            t["waits_short"] = t.get("waits_short", 0) + 1
            t["waits_short_us"] = t.get("waits_short_us", 0) + dur_us

    def handoff(self, latency_us: int) -> None:
        """One item taken from a channel, or one submit started on a
        pool: how long after it was ready (and its taker waiting) the
        taker ran."""
        latency_us = max(int(latency_us), 0)
        with self._lock:
            t = self._tallies
            t["handoffs"] = t.get("handoffs", 0) + 1
            t["handoff_us"] = t.get("handoff_us", 0) + latency_us
            t["handoff_max_us"] = max(t.get("handoff_max_us", 0),
                                      latency_us)

    def tally_chip(self, chip: int, tables: int = 0, rows: int = 0,
                   resident_bytes: Optional[int] = None) -> None:
        with self._lock:
            c = self._chips.setdefault(
                chip, {"tables": 0, "rows": 0, "resident_bytes": 0})
            c["tables"] += tables
            c["rows"] += rows
            if resident_bytes is not None:
                c["resident_bytes"] = resident_bytes

    def finish(self, status: Optional[str] = None) -> None:
        """Close the root span (idempotent) and keep the trace's summary
        in the process's ring (:func:`finished`). ``None`` keeps whatever
        status was pre-set on the recorder (a failed query marks it
        ``error`` before the export path finishes the root)."""
        with self._lock:
            if self._finished:
                return
            self._finished = True
            tallies = dict(self._tallies)
        if status is not None:
            self.status = status
        self._root_dur = max(self.now_us() - self._root_t0, 0)
        self.add("query", self.root_id, None, self._root_t0,
                 self._root_dur, attrs=tallies or None, lane="driver",
                 status=self.status)
        self._summary = self._summarize()
        _finished_ring.append(self._summary)

    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._spans)

    def drain(self) -> List[dict]:
        """Remove and return the buffered spans (ship-back path: each
        remote task response carries the spans recorded so far, so
        concurrent tasks of one trace never double-ship)."""
        with self._lock:
            out = self._spans
            self._spans = []
            return out

    def span_ids(self) -> set:
        with self._lock:
            return {s["span_id"] for s in self._spans}

    def summary(self) -> dict:
        """Counts of the buffer; for a finished trace also where its wall
        went: ``phases`` (per span name), ``covered_us`` (the union of
        the :data:`LEAF_SPANS`), ``tables`` (the scan-task tally),
        ``footers`` (Parquet footers planned ``from_store`` or ``read``),
        ``files`` (the files its scans ``planned`` and the ``stats``, the
        stat-like system calls, it made on them),
        ``plan`` (what the physical plan asks for, counted once where it
        is built: :data:`PLAN_TALLIES`),
        ``decode`` (the packed results of how many device ``tables`` were
        decoded into how many record ``batches``), ``joins`` (the bucket
        pairs the query's joins matched, their rows in, the rows of their
        smaller sides and their rows out: :data:`JOIN_TALLIES`),
        ``selects`` (the filtered scans' tables that ended in rows:
        :data:`SELECT_TALLIES`), ``agg_launches`` (the fused aggregate's
        window tables by the launch that answered them:
        :data:`AGG_LAUNCH_TALLIES`),
        ``chips`` (the same tally per chip: ``chip``, ``tables``, ``rows``
        and ``resident_bytes``, one entry a chip, in chip order),
        ``handoffs`` (``count`` items taken from a channel or submits
        started on a pool, the ``us`` their takers took to run once the
        item was there and they were waiting, and the longest,
        ``max_us``), ``waits_short`` (the ``count`` and ``us`` of waits
        under :data:`WAIT_FLOOR_US`, which no span holds) and ``holes``
        (:func:`_holes`: the wall no leaf span covers, by the name of
        what lay over it), computed once, when the root closed."""
        return self._summary or self._summarize()

    def _summarize(self) -> dict:
        with self._lock:
            spans = list(self._spans)
            offsets = dict(self.clock_offsets_us)
            done = self._finished
            tallies = dict(self._tallies)
            chips = [{"chip": k, **c}
                     for k, c in sorted(self._chips.items())]
        out = {"trace_id": self.trace_id, "spans": len(spans),
               "dropped": self.dropped}
        if offsets:
            out["clock_offsets_us"] = offsets
        if done:
            lo, hi = self._root_t0, self._root_t0 + self._root_dur
            out["t0_unix_us"] = lo
            out["t0_perf_s"] = self._root_perf_s
            out["wall_us"] = self._root_dur
            out["phases"] = _phases(spans, self.root_id)
            leaves = _merged([_interval(s) for s in spans
                              if s["name"] in LEAF_SPANS], lo, hi)
            out["covered_us"] = _length(leaves)
            out["holes"] = _holes(spans, leaves, lo, hi)
            out["handoffs"] = {"count": tallies.get("handoffs", 0),
                               "us": tallies.get("handoff_us", 0),
                               "max_us": tallies.get("handoff_max_us", 0)}
            out["waits_short"] = {"count": tallies.get("waits_short", 0),
                                  "us": tallies.get("waits_short_us", 0)}
            out["tables"] = {k: tallies.get(k, 0) for k in TABLE_SOURCES}
            out["footers"] = _footer_counts(tallies)
            out["files"] = _file_counts(tallies)
            out["plan"] = {k: tallies.get("plan_" + k, 0)
                           for k in PLAN_TALLIES}
            out["decode"] = {"tables": tallies.get("decode_tables", 0),
                             "batches": tallies.get("decode_batches", 0)}
            out["joins"] = {k: tallies.get("join_" + k, 0)
                            for k in JOIN_TALLIES}
            out["selects"] = {k: tallies.get("select_" + k, 0)
                              for k in SELECT_TALLIES}
            out["agg_launches"] = {k: tallies.get("agg_" + k, 0)
                                   for k in AGG_LAUNCH_TALLIES}
            out["chips"] = chips
        return out


#: the spans that hold the work itself and do not nest in each other: a
#: query's wall is covered by their union (``summary()["covered_us"]``).
#: ``device:put`` and ``device:dispatch`` carry the ``chip`` their planes
#: or program went to (an index of ``parallel.mesh.scan_devices()``; 0
#: when one chip is visible; a round launch of the fused aggregate, one
#: program over a table on every chip, carries ``tables`` and ``chips``
#: in its place), ``device:fetch`` the number of ``chips``
#: its results lay on (and ``chip`` when that is one). ``join:device``
#: is one bucket pair matched by the fused device join
#: (``joins._device_match_indices``: pad, put, dispatch, fetch and unpack,
#: with ``rows_left``, ``rows_right``, ``capacity``, ``pairs``, ``bytes``
#: fetched); a pair matched on the host is ``join:build`` + ``join:probe``
LEAF_SPANS = frozenset((
    "plan:optimize", "plan:translate", "scan:load", "device:encode",
    "device:put", "device:dispatch", "device:fetch", "device:decode",
    "agg:host", "join:build", "join:probe", "join:device", "sort:topn",
    "expr:eval",
    "exchange:partition", "exchange:gather", "mem:size",
    "result:collect"))

#: the spans whose body is computation in the calling thread, so that a
#: thread off the CPU inside one (``dur_us - cpu_us``) is a thread that
#: waits for the GIL or for a lock. (Where Arrow hands part of a kernel to
#: its own pool the caller waits too: read a span's share with nothing
#: beside it before the number under load is believed.) The other live
#: leaves wait for something else: ``device:fetch`` for the chip and the
#: link, ``scan:load`` for the file system and Arrow's pool,
#: ``plan:optimize`` for the IO pool's batch of ``stat``s, ``device:put``
#: for the copy to the chip, ``join:device`` for its own fetch
COMPUTE_SPANS = frozenset((
    "expr:eval", "exchange:partition", "exchange:gather", "mem:size",
    "join:build", "join:probe", "agg:host", "sort:topn", "device:decode",
    "device:encode", "plan:translate", "device:dispatch"))

#: the live spans that read their thread's CPU clock: the leaves and the
#: launch, which are what a metric reads. Not every live span: on the
#: machines with the chips one ``time.thread_time_ns()`` costs 6.2 us (a
#: trap into the sandbox's kernel; 0.6 us elsewhere) and steps by 10 ms,
#: and a resident pass holds ~170 stage, submit, drain and wait spans whose
#: CPU time nobody asks for (``chip_proof/clock_cost.py``; PERF.md §6,
#: PR 43)
CPU_SPANS = LEAF_SPANS | {"dispatch:launch"}

#: the spans that say what was going on while no leaf span ran
#: (``summary()["holes"]["unnamed_us"]`` is what lies under none of them).
#: A taker that waits for its producer (``wait:channel``, ``wait:result``)
#: says nothing of what the producer is about, and some thread waits so
#: all through a query: such a span names a hole only over its tail, the
#: hand-off (``tail_us``: the item was there, the taker waiting, and
#: not yet running). ``pipeline:stage`` and the root span a thread's whole
#: life and name nothing
HOLE_SPANS = frozenset((
    "device:submit", "device:drain", "device:inflight", "scan:prefetch",
    "dispatch:launch", "wait:pool", "wait:window"))
_LIFELONG_SPANS = frozenset(("pipeline:stage", "query"))

#: ``summary()["joins"]``: the bucket pairs ``joins.match_indices`` matched
#: with the fused device program (``join:device``) or on the host
#: (``join:build`` + ``join:probe``), and the probe morsels a ``join_agg``
#: region matched against its build side inside its own program (device
#: pairs too: ``fragment.drain_join_agg``); the rows (left + right) of
#: each tier's pairs; the rows of the largest pair: the size the join
#: gate's break-even (``costmodel.join_wins``) is compared with; and, over
#: both tiers, ``rows_small`` (the rows of each pair's smaller side: a
#: side matched against many morsels, a broadcast build side, counts once
#: a pair) and ``rows_out`` (the index pairs matched: an inner join's
#: output rows; the unmatched rows an outer join adds are not in it).
#: ``rows_out`` over rows in is what a join kept of what it was handed.
#: Tallied as ``join_<key>`` on the query's root span (``joins.tally_pair``)
JOIN_TALLIES = ("pairs_device", "pairs_host", "rows_device", "rows_host",
                "max_pair_rows", "rows_small", "rows_out")

#: ``summary()["plan"]``: ``repeated_scans``, the scans of the physical
#: plan that read a file an earlier scan of the same plan reads
#: (``physical.translate.repeated_scans``: Q17's two joins of ``part`` to
#: ``lineitem`` need other columns, so ``translate`` cannot share them and
#: both tables are read twice: 2). Tallied as ``plan_<key>`` on the
#: query's root span, once a query, where the physical plan is built
#: (``runners/native_runner.py``)
PLAN_TALLIES = ("repeated_scans",)

#: ``summary()["selects"]``: the tables of filtered scans that ended in
#: rows (a scan under a join, a sort, a projection: not under a fused
#: aggregate), by where the filter ran: the chain program over the
#: table's encoded columns (``fragment.drain_select_tables``) or the
#: reader on the host; the rows those tables held and the rows that
#: survived, on both tiers alike (and the device's share of both apart:
#: what its programs read and what its fetches carried); the tables
#: whose survivors outgrew a rung of the device's ladder; and, of the
#: device's tables, those whose survivors the program brought back by
#: gathers of 128-lane rows (``fragment.gathers_rows``: static a
#: capacity and rung; the rest took element gathers). Tallied as
#: ``select_<key>`` on the query's root span
SELECT_TALLIES = ("tables_device", "tables_host", "rows_in", "rows_out",
                  "rows_in_device", "rows_out_device", "overflows",
                  "tables_row_gather")

#: ``summary()["agg_launches"]``: the tables of the fused aggregate's
#: windows (``fragment.submit_fused_agg_tables``) by the launch that
#: answered them: a round launch, ONE call of the SPMD program over a
#: table on every chip, or a launch of their own (one chip visible, a
#: ragged round, a round whose launch failed). Tallied as ``agg_<key>`` on
#: the query's root span
AGG_LAUNCH_TALLIES = ("tables_round", "tables_single")

#: where a scan task's table came from, as the device tier's scan path
#: tallies it (``SpanRecorder.tally`` / :func:`tally`)
TABLE_SOURCES = ("from_cache", "encoded", "host")


def _footer_counts(tallies: Dict[str, int]) -> Dict[str, int]:
    """The local Parquet footers a trace's scans were planned from: held
    by ``io.footers``' store, or read from the file (and stored)."""
    return {"from_store": tallies.get("footers_from_store", 0),
            "read": tallies.get("footers_read", 0)}


def _file_counts(tallies: Dict[str, int]) -> Dict[str, int]:
    """The files a trace's glob scans made tasks for (one count a
    ``to_scan_tasks``) and the stat-like system calls the program made on
    scan files inside the query: ``io.footers.identities``' batch, and
    the fallbacks of a task or a footer lookup that was handed no
    identity (``FooterStore.get``, ``readers.make_scan_tasks``,
    ``device/cache.task_fingerprint``). One a file is the floor: every
    query stats every local file it reads."""
    return {"planned": tallies.get("files_planned", 0),
            "stats": tallies.get("file_stats", 0)}


def _interval(span: dict) -> Tuple[int, int]:
    return span["ts_us"], span["ts_us"] + span["dur_us"]


def _merged(intervals, lo: Optional[int] = None,
            hi: Optional[int] = None) -> List[Tuple[int, int]]:
    """The union of ``(start, end)`` intervals, clipped to ``[lo, hi]``
    when given, as disjoint intervals in order."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(merged: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def _union_us(intervals, lo: Optional[int] = None,
              hi: Optional[int] = None) -> int:
    """Length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]`` when given: spans of one name on several threads
    overlap, and a wall must not count the overlap twice."""
    return _length(_merged(intervals, lo, hi))


def _overlap_us(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two lists of disjoint intervals in
    order (:func:`_merged`)."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _holes(spans: List[dict], leaves: List[Tuple[int, int]], lo: int,
           hi: int) -> dict:
    """The query's wall that no leaf span covers (``us``: ``wall_us -
    covered_us``), with every other span laid over it: ``by`` gives, per
    span name, how much of the holes the union of that name's spans
    overlaps, and under ``handoff`` the same for the hand-off tails of
    the waits that carry one (names may add to more than ``us``: threads
    run abreast); ``unnamed_us`` is the part under none of
    :data:`HOLE_SPANS` and no hand-off, each microsecond counted once."""
    holes: List[Tuple[int, int]] = []
    at = lo
    for s, e in leaves:
        if s > at:
            holes.append((at, s))
        at = e
    if hi > at:
        holes.append((at, hi))
    by_name: Dict[str, list] = {}
    for s in spans:
        name = s["name"]
        if name not in LEAF_SPANS and name not in _LIFELONG_SPANS:
            by_name.setdefault(name, []).append(_interval(s))
            tail = (s.get("attrs") or {}).get("tail_us")
            if tail:
                end = s["ts_us"] + s["dur_us"]
                by_name.setdefault("handoff", []).append(
                    (end - min(tail, s["dur_us"]), end))
    by = {}
    naming = []
    for name, group in by_name.items():
        us = _overlap_us(holes, _merged(group, lo, hi))
        if us:
            by[name] = us
        if name in HOLE_SPANS or name == "handoff":
            naming.extend(group)
    total = _length(holes)
    return {"us": total, "by": by,
            "unnamed_us": total - _overlap_us(holes,
                                              _merged(naming, lo, hi))}


def _phases(spans: List[dict], root_id: str) -> Dict[str, dict]:
    """Per span name: how many, the union of their intervals
    (``wall_us``, never more than the query's wall), their plain sum
    (``sum_us``), the ``bytes`` and ``rows`` attributes summed, and the
    thread-CPU time of the spans that carry one (``cpu_us``) beside the
    ``dur_us`` of those same spans (``timed_us``): ``timed_us - cpu_us``
    is how long the threads stood still inside spans of that name."""
    by_name: Dict[str, list] = {}
    for s in spans:
        if s["span_id"] != root_id:
            by_name.setdefault(s["name"], []).append(s)
    out = {}
    for name, group in by_name.items():
        attrs = [s.get("attrs") or {} for s in group]
        timed = [s for s in group if "cpu_us" in s]
        out[name] = {
            "count": len(group),
            "wall_us": _union_us([_interval(s) for s in group]),
            "sum_us": sum(s["dur_us"] for s in group),
            "cpu_us": sum(s["cpu_us"] for s in timed),
            "timed_us": sum(s["dur_us"] for s in timed),
            "bytes": sum(int(a.get("bytes") or 0) for a in attrs),
            "rows": sum(int(a.get("rows", a.get("rows_in")) or 0)
                        for a in attrs)}
    return out


#: summaries of the last finished traces, oldest first (dicts, not spans)
_finished_ring: "collections.deque" = collections.deque(maxlen=256)


def finished(limit: Optional[int] = None) -> List[dict]:
    """``summary()`` of the most recent finished traces of this process,
    oldest first (``limit`` keeps the newest)."""
    out = list(_finished_ring)
    if limit is None:
        return out
    return out[-limit:] if limit > 0 else []


class SpanContext:
    """(recorder, current span id) — the unit that travels across
    threads and the wire."""

    __slots__ = ("recorder", "span_id")

    def __init__(self, recorder: SpanRecorder, span_id: str):
        self.recorder = recorder
        self.span_id = span_id

    def wire(self) -> Tuple[str, str]:
        """(trace_id, span_id) for header / RPC propagation."""
        return self.recorder.trace_id, self.span_id


# -------------------------------------------------- thread propagation

_tl = threading.local()


def current() -> Optional[SpanContext]:
    return getattr(_tl, "ctx", None)


def _set_current(ctx: Optional[SpanContext]) -> Optional[SpanContext]:
    """Raw swap for hot paths (``observability.attributed``); returns
    the previous context so the caller can restore it."""
    prev = getattr(_tl, "ctx", None)
    _tl.ctx = ctx
    return prev


@contextlib.contextmanager
def attach(ctx: Optional[SpanContext]):
    """Install ``ctx`` as this thread's span context. ``None`` is a
    no-op (the current context, if any, stays installed)."""
    if ctx is None:
        yield None
        return
    prev = _set_current(ctx)
    try:
        yield ctx
    finally:
        _set_current(prev)


def run_attached(ctx: Optional[SpanContext], fn, *args, **kwargs):
    """Run ``fn`` under ``ctx`` — the shape pool-submit sites use to
    carry the submitting thread's span context onto a worker thread
    (``ctx`` as is, or stamped by :func:`submitted`)."""
    with attach(started(ctx)):
        try:
            return fn(*args, **kwargs)
        finally:
            done(ctx)


class _Submitted:
    """A context on its way to a pool worker, stamped where the traced
    submitting thread captured it; ``done_ns``: when the worker was done
    with the submit (0 until then), for whoever waits for its result."""

    __slots__ = ("ctx", "trace", "pool", "t_ns", "done_ns")

    def __init__(self, ctx, trace: SpanContext, pool: str):
        self.ctx = ctx
        self.trace = trace
        self.pool = pool
        self.done_ns = 0
        self.t_ns = time.perf_counter_ns()


def submitted(ctx, pool: str):
    """``ctx`` (a span context, or ``observability``'s attribution) as a
    pool-submit site hands it to ``run_attached`` / ``run_attributed``:
    itself when the submitting thread is untraced, else stamped with the
    pool's name and the instant, so that the worker can say how long the
    submit stood in the pool's queue (:func:`started`)."""
    trace = current()
    if trace is None:
        return ctx
    return _Submitted(ctx, trace, pool)


def started(ctx):
    """On the worker, before anything else: the context a submit site
    passed, unwrapped; for a stamped one the time from submit to now is
    a ``wait:pool`` span (``pool``: its name) and one hand-off."""
    if type(ctx) is not _Submitted:
        return ctx
    now = time.perf_counter_ns()
    rec = ctx.trace.recorder
    rec.add_wait("wait:pool", ctx.trace.span_id, ctx.t_ns, now,
                 {"pool": ctx.pool})
    rec.handoff((now - ctx.t_ns) // 1000)
    return ctx.ctx


def done(ctx) -> None:
    """On the worker, last of all: a stamped context learns when."""
    if type(ctx) is _Submitted:
        ctx.done_ns = time.perf_counter_ns()


# ---------------------------------------------------------- live spans


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass

    def handed(self, ready_ns):
        pass


_NOOP = _NoopSpan()


#: ``jax.profiler.TraceAnnotation`` once JAX is imported (never imported
#: from here: a host-only process stays free of it); False if it cannot
#: be had
_annotation = None


def _annotation_cls():
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation as found
        except Exception:
            found = False
        # daft-lint: allow(unguarded-global-mutation) -- benign
        # last-wins memo of an import
        _annotation = found
    return _annotation or None


def _annotate(name: str):
    """The profiler's twin of a span, entered: ``daft:<name>`` on this
    thread's host line of the profile being taken. The prefix keeps the
    program's spans apart from those a harness writes into the same
    profile under its own names."""
    cls = _annotation_cls()
    if cls is None:
        return None
    try:
        ann = cls("daft:" + name)
        ann.__enter__()
        return ann
    except Exception:
        return None


def _close_annotation(ann, attrs: Optional[dict], exc=(None, None, None)):
    """Leave the twin, with the span's attributes as its metadata (one
    call, at the end: some are known only then)."""
    try:
        if attrs:
            ann.set_metadata(**attrs)
        ann.__exit__(*exc)
    except Exception:
        pass


class _LiveSpan:
    __slots__ = ("_ctx", "_name", "_key", "_attrs", "_lane", "_t0",
                 "_cpu0", "_id", "_prev", "_ann")

    def __init__(self, ctx: SpanContext, name: str, key: Optional[str],
                 attrs: Optional[dict], lane: str):
        self._ctx = ctx
        self._name = name
        self._key = key or name
        self._attrs = dict(attrs) if attrs else None
        self._lane = lane

    def set(self, key, value) -> None:
        if self._attrs is None:
            self._attrs = {}
        self._attrs[key] = value

    def __enter__(self):
        rec = self._ctx.recorder
        self._id = rec.unique_span_id(self._key)
        self._ann = _annotate(self._name) if rec.bridge else None
        self._prev = _set_current(SpanContext(rec, self._id))
        # the CPU clock (:data:`CPU_SPANS` only) is read inside the wall
        # clock's interval, at both ends: cpu_us <= dur_us but for the
        # clocks' grain
        self._t0 = time.perf_counter_ns()
        self._cpu0 = time.thread_time_ns() \
            if self._name in CPU_SPANS else None
        return self

    def __exit__(self, exc_type, exc, tb):
        cpu = None if self._cpu0 is None else \
            (time.thread_time_ns() - self._cpu0) // 1000
        dur = time.perf_counter_ns() - self._t0
        _set_current(self._prev)
        if self._ann is not None:
            _close_annotation(self._ann, self._attrs, (exc_type, exc, tb))
        self._record(dur // 1000, cpu,
                     "error" if exc_type is not None else "ok")
        return False

    def _record(self, dur_us: int, cpu_us: Optional[int],
                status: str) -> None:
        rec = self._ctx.recorder
        rec.add(self._name, self._id, self._ctx.span_id,
                rec.wall_us(self._t0), dur_us, attrs=self._attrs,
                lane=self._lane, status=status, cpu_us=cpu_us)


class _LiveWait(_LiveSpan):
    """A wait that is lived through (so it rides the ``daft:`` bridge
    into a profile); under :data:`WAIT_FLOOR_US` it is counted, not
    stored, as :meth:`SpanRecorder.add_wait` does."""

    __slots__ = ()

    def handed(self, ready_ns: int) -> None:
        """What was waited for is here, and was ready at ``ready_ns``
        (``time.perf_counter_ns()``; 0: not known, nothing is counted):
        one hand-off, of the time since the later of that and the wait's
        start, which is also the span's ``tail_us``."""
        if not ready_ns:
            return
        us = (time.perf_counter_ns() - max(ready_ns, self._t0)) // 1000
        self._ctx.recorder.handoff(us)
        self.set("tail_us", us)

    def _record(self, dur_us: int, cpu_us: Optional[int],
                status: str) -> None:
        rec = self._ctx.recorder
        if dur_us < rec.wait_floor_us and status == "ok":
            rec.wait_short(dur_us)
        else:
            super()._record(dur_us, cpu_us, status)


def span(name: str, key: Optional[str] = None,
         attrs: Optional[dict] = None, lane: str = "driver"):
    """Context manager recording one span under the thread's current
    context; a cheap no-op singleton when the thread is untraced (the
    sampling gate: no ids, no dicts, no clock reads)."""
    ctx = current()
    if ctx is None:
        return _NOOP
    return _LiveSpan(ctx, name, key, attrs, lane)


def launch(program: str, chip: Optional[int] = 0, **attrs):
    """``dispatch:launch``: the call of a jitted function and nothing
    else, opened exactly where ``retrace_sanitizer.dispatch_scope``
    brackets it (``program`` is the sanitizer's site id; a round launch,
    ``fragment.round``, carries its ``tables`` and ``chips`` and no
    ``chip``). No leaf: it
    nests in its ``device:dispatch`` / ``join:device``, or stands alone
    at ``device/runtime.py``'s sites. Its ``cpu_us`` is the client's
    launch work in the calling thread; the rest of its wall the thread
    spent off the CPU inside the call."""
    ctx = current()
    if ctx is None:
        return _NOOP
    if chip is not None:
        attrs["chip"] = chip
    attrs["program"] = program
    return _LiveSpan(ctx, "dispatch:launch", None, attrs, "device")


def wait(name: str):
    """A live ``wait:*`` span around a blocking call (``wait:window``,
    ``wait:result``); the no-op singleton when the thread is untraced."""
    ctx = current()
    if ctx is None:
        return _NOOP
    return _LiveWait(ctx, name, None, None, "wait")


def note_wait(name: str, t0_ns: int, t1_ns: int,
              attrs: Optional[dict] = None) -> None:
    """A ``wait:*`` span between two ``time.perf_counter_ns()`` readings,
    recorded once the wait is over (no-op when untraced)."""
    ctx = current()
    if ctx is not None:
        ctx.recorder.add_wait(name, ctx.span_id, t0_ns, t1_ns, attrs)


def event(name: str, key: Optional[str] = None,
          attrs: Optional[dict] = None, lane: str = "driver",
          ctx: Optional[SpanContext] = None,
          parent_id: Optional[str] = None) -> None:
    """Zero-duration span (cancellations, retries, speculation marks)."""
    ctx = ctx if ctx is not None else current()
    if ctx is None:
        return
    rec = ctx.recorder
    ann = _annotate(name) if rec.bridge else None
    if ann is not None:
        _close_annotation(ann, attrs)
    rec.add(name, rec.unique_span_id(key or name),
            parent_id or ctx.span_id, rec.now_us(), 0, attrs=attrs,
            lane=lane)


def tally(key: str, n: int = 1) -> None:
    """Count on the current trace's root span (no-op when untraced)."""
    ctx = current()
    if ctx is not None:
        ctx.recorder.tally(key, n)


def tally_max(key: str, n: int) -> None:
    """Keep the largest ``n`` under ``key`` on the current trace's root
    span (no-op when untraced)."""
    ctx = current()
    if ctx is not None:
        ctx.recorder.tally_max(key, n)


def footer_counts() -> Dict[str, int]:
    """``{"from_store", "read"}`` of the current trace so far (empty when
    untraced)."""
    ctx = current()
    if ctx is None:
        return {}
    with ctx.recorder._lock:
        return _footer_counts(ctx.recorder._tallies)


def file_counts() -> Dict[str, int]:
    """``{"planned", "stats"}`` of the current trace so far (empty when
    untraced)."""
    ctx = current()
    if ctx is None:
        return {}
    with ctx.recorder._lock:
        return _file_counts(ctx.recorder._tallies)


def tally_chip(chip: int, tables: int = 0, rows: int = 0,
               resident_bytes: Optional[int] = None) -> None:
    """Count a device table (and its rows) under the chip that ran it,
    or note the HBM column cache's bytes on that chip, on the current
    trace (no-op when untraced)."""
    ctx = current()
    if ctx is not None:
        ctx.recorder.tally_chip(chip, tables, rows, resident_bytes)


# ------------------------------------------------------ trace registry

_reg_lock = threading.Lock()
_recorders: "Dict[str, SpanRecorder]" = {}
_trace_seq = itertools.count(1)


def trace_enabled() -> bool:
    from .analysis import knobs
    return bool(knobs.env_bool("DAFT_TPU_TRACE"))


def recorder_for(trace_id: str) -> Optional[SpanRecorder]:
    with _reg_lock:
        return _recorders.get(trace_id)


def register_recorder(rec: SpanRecorder) -> None:
    with _reg_lock:
        while len(_recorders) >= _MAX_ACTIVE_RECORDERS:
            _recorders.pop(next(iter(_recorders)))
        _recorders[rec.trace_id] = rec


def unregister_recorder(trace_id: str) -> None:
    with _reg_lock:
        _recorders.pop(trace_id, None)


def profile_requested() -> bool:
    """A profile is a request for spans: a ``jax.profiler`` session is
    live in this process (whoever started it), or ``DAFT_TPU_XPLANE_DIR``
    will start one for this query (its capture starts after the trace
    decision). One env read and, once JAX is imported, one C call."""
    from .analysis import knobs
    if knobs.env_str("DAFT_TPU_XPLANE_DIR"):
        return True
    cls = _annotation_cls()
    try:
        return cls is not None and bool(cls.is_enabled())
    except Exception:
        return False


def maybe_start_trace(kind: str = "query") -> Optional[SpanContext]:
    """Start (and register) a trace for a new top-level query — or
    return ``None`` when nobody asked for one, the query loses the
    sampling draw, or the thread is already inside a trace (the query
    joins it). Asked for by ``DAFT_TPU_TRACE=1`` (sampled by
    ``DAFT_TPU_TRACE_SAMPLE``; the draw hashes the deterministic
    per-process trace key, never RNG) or by a profile being taken
    (:func:`profile_requested`; never sampled away)."""
    if current() is not None:
        return None
    profiled = profile_requested()
    if not profiled and not trace_enabled():
        return None
    from .analysis import knobs
    seq = next(_trace_seq)
    trace_key = f"{kind}:{seq}"
    rate = 1.0 if profiled else knobs.env_float("DAFT_TPU_TRACE_SAMPLE")
    if rate < 1.0 and _hash01(trace_key) >= max(rate, 0.0):
        return None
    trace_id = hashlib.sha256(
        f"daft-trace\x1f{os.getpid()}\x1f{trace_key}".encode()
    ).hexdigest()[:32]
    rec = SpanRecorder(trace_id, bridge=profiled)
    register_recorder(rec)
    return SpanContext(rec, rec.root_id)


def abort_trace(ctx: Optional[SpanContext],
                status: str = "error") -> None:
    """Close and unregister a trace whose query died before anything
    could adopt it (a planner failure between :func:`maybe_start_trace`
    and the executor's stats context taking ownership). Idempotent and
    no-op for None / already-exported contexts — safe to call from any
    error path. Without this, every failed optimize/translate left its
    recorder registered for the process lifetime (the registry cap made
    it a rotation of leaks rather than growth, but the trace itself was
    silently lost)."""
    if ctx is None:
        return
    rec = ctx.recorder
    if rec is None or getattr(rec, "exported", False):
        return
    rec.exported = True
    rec.finish(status)
    unregister_recorder(rec.trace_id)


def remote_context(trace_id: str, span_id: str,
                   parent_id: Optional[str] = None
                   ) -> Optional[SpanContext]:
    """Rebuild a span context from wire identifiers. In-process workers
    find the driver's live recorder in the registry; a foreign process
    (remote worker) gets ``None`` from here and must buffer its own
    spans for ship-back (``WorkerServer`` does)."""
    rec = recorder_for(trace_id)
    if rec is None:
        return None
    return SpanContext(rec, span_id)


def wire_headers(ctx: Optional[SpanContext] = None) -> Dict[str, str]:
    """Span-context HTTP headers for the shuffle wire (empty when
    untraced)."""
    ctx = ctx if ctx is not None else current()
    if ctx is None:
        return {}
    trace_id, span_id = ctx.wire()
    return {_WIRE_TRACE_HEADER: trace_id, _WIRE_PARENT_HEADER: span_id}


def context_from_headers(headers) -> Optional[SpanContext]:
    """Span context from incoming shuffle-wire headers (None when the
    request is untraced or the trace lives in another process)."""
    try:
        trace_id = headers.get(_WIRE_TRACE_HEADER)
        span_id = headers.get(_WIRE_PARENT_HEADER)
    except Exception:
        return None
    if not trace_id or not span_id:
        return None
    return remote_context(trace_id, span_id)


# ------------------------------------------------------- chrome export

#: lane order for the chrome export's tid assignment: driver layers
#: first, then device, then workers in first-seen order
_LANE_PRIORITY = ("driver", "serving", "planner", "pipeline", "scan",
                  "device", "dev:upload", "dev:compute", "dev:download")


def _export_attrs(span: dict) -> dict:
    """A span's attributes as the exports carry them: its own, and
    ``cpu_us`` where it has one."""
    attrs = dict(span.get("attrs") or {})
    if "cpu_us" in span:
        attrs["cpu_us"] = span["cpu_us"]
    return attrs


def chrome_trace_events(rec: SpanRecorder) -> List[dict]:
    """Perfetto-loadable event list: one ``X`` (complete) event per
    span on a per-lane tid, plus ``M`` thread-name metadata events.
    Timestamps are rebased to the earliest span and sorted monotonic."""
    spans = sorted(rec.spans(), key=lambda s: (s["ts_us"], s["span_id"]))
    if not spans:
        return []
    base = min(s["ts_us"] for s in spans)
    lanes: Dict[str, int] = {}
    for lane in _LANE_PRIORITY:
        lanes[lane] = len(lanes)
    for s in spans:
        lanes.setdefault(s["lane"], len(lanes))
    pid = os.getpid()
    events: List[dict] = [
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
         "args": {"name": lane}}
        for lane, tid in sorted(lanes.items(), key=lambda kv: kv[1])]
    for s in spans:
        args = {"span_id": s["span_id"], "parent_id": s["parent_id"]}
        args.update(_export_attrs(s))
        if s.get("status", "ok") != "ok":
            args["status"] = s["status"]
        events.append({"name": s["name"], "ph": "X",
                       "ts": s["ts_us"] - base, "dur": s["dur_us"],
                       "pid": pid, "tid": lanes[s["lane"]],
                       "args": args})
    return events


def chrome_trace_json(rec: SpanRecorder) -> dict:
    return {"traceEvents": chrome_trace_events(rec),
            "displayTimeUnit": "ms",
            "otherData": {"trace_id": rec.trace_id,
                          "dropped_spans": rec.dropped}}


def validate_chrome_trace(doc: dict) -> List[str]:
    """Schema check for an exported Chrome trace (the ``obs-smoke``
    gate): required event fields, non-negative monotonic timestamps,
    only ``X``/``M``/``B``/``E`` phases with ``B``/``E`` matched per
    (pid, tid). Returns human-readable problems (empty = valid)."""
    problems: List[str] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents is not a list"]
    last_ts: Dict[tuple, float] = {}
    open_b: Dict[tuple, int] = {}
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            problems.append(f"event {i} is not an object")
            continue
        ph = e.get("ph")
        if ph not in ("X", "M", "B", "E"):
            problems.append(f"event {i}: unsupported phase {ph!r}")
            continue
        for field in ("name", "pid", "tid"):
            if field not in e:
                problems.append(f"event {i}: missing {field!r}")
        if ph == "M":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"event {i}: bad ts {ts!r}")
            continue
        key = (e.get("pid"), e.get("tid"))
        if ts < last_ts.get(key, 0):
            problems.append(
                f"event {i}: non-monotonic ts on lane {key}")
        last_ts[key] = ts
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i}: bad dur {dur!r}")
        elif ph == "B":
            open_b[key] = open_b.get(key, 0) + 1
        elif ph == "E":
            if open_b.get(key, 0) <= 0:
                problems.append(f"event {i}: E without matching B")
            else:
                open_b[key] -= 1
    for key, n in open_b.items():
        if n:
            problems.append(f"lane {key}: {n} unmatched B event(s)")
    return problems


def orphan_spans(rec: SpanRecorder) -> List[dict]:
    """Spans whose parent id resolves to no recorded span (and is not
    the root). The chaos-correctness contract: always empty."""
    ids = rec.span_ids() | {rec.root_id}
    return [s for s in rec.spans()
            if s["parent_id"] not in ids]


# --------------------------------------------------------- OTLP export


def otlp_spans_payload(rec: SpanRecorder) -> dict:
    """The trace as an OTLP/HTTP JSON ExportTraceServiceRequest
    (``/v1/traces``), extending the metrics-only export in
    ``observability.export_otlp``."""
    def _span(s: dict) -> dict:
        out = {
            "traceId": rec.trace_id,
            "spanId": s["span_id"],
            "name": s["name"],
            "kind": 1,  # INTERNAL
            "startTimeUnixNano": str(s["ts_us"] * 1000),
            "endTimeUnixNano": str((s["ts_us"] + s["dur_us"]) * 1000),
            "attributes": [
                {"key": "lane", "value": {"stringValue": s["lane"]}}],
        }
        if s["parent_id"] != s["span_id"]:
            out["parentSpanId"] = s["parent_id"]
        for k, v in _export_attrs(s).items():
            if isinstance(v, bool):
                val = {"boolValue": v}
            elif isinstance(v, int):
                val = {"intValue": str(v)}
            elif isinstance(v, float):
                val = {"doubleValue": v}
            else:
                val = {"stringValue": str(v)}
            out["attributes"].append({"key": str(k), "value": val})
        if s.get("status", "ok") != "ok":
            out["status"] = {"code": 2}  # ERROR
        return out

    return {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name",
             "value": {"stringValue": "daft_tpu"}}]},
        "scopeSpans": [{
            "scope": {"name": "daft_tpu.tracing"},
            "spans": [_span(s) for s in rec.spans()]}]}]}


# ------------------------------------------------- prometheus /metrics


def _prom_escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n").replace('"', '\\"')


def _prom_name(prefix: str, raw: str) -> str:
    out = []
    for ch in raw:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    name = "".join(out).strip("_").lower()
    return f"daft_tpu_{prefix}_{name}"


def prometheus_text() -> str:
    """Process-wide counters/gauges in Prometheus text exposition
    format: the serving / shuffle / scan-io / recovery / device-kernel
    planes plus queue-depth and cache-hit-rate gauges. Never raises —
    a plane that fails to import simply contributes nothing."""
    lines: List[str] = []

    def emit(name: str, value, kind: str = "counter",
             help_: str = "") -> None:
        if not isinstance(value, (int, float)):
            return
        lines.append(f"# HELP {name} {help_ or name}")
        lines.append(f"# TYPE {name} {kind}")
        if isinstance(value, float) and value == int(value):
            value = int(value)
        lines.append(f"{name} {value}")

    def plane(prefix: str, counters: Dict[str, float],
              help_: str) -> None:
        for k in sorted(counters):
            emit(_prom_name(prefix, k) + "_total", counters[k],
                 "counter", f"{help_} ({k})")

    try:
        from .distributed import shuffle_service
        plane("shuffle", shuffle_service.shuffle_counters_snapshot(),
              "shuffle data-plane counter")
    except Exception:
        pass
    try:
        from .io import read_planner
        plane("io", read_planner.scan_counters_snapshot(),
              "scan-plane io counter")
    except Exception:
        pass
    try:
        from .execution import memory
        plane("spill", memory.spill_counters_snapshot(),
              "out-of-core spill-tier counter")
    except Exception:
        pass
    try:
        from .execution import governor
        plane("governor", governor.counters_snapshot(),
              "memory-governor backpressure action counter")
        snap = governor.snapshot()
        emit("daft_tpu_rss_bytes", snap["rss_bytes"], "gauge",
             "current process resident set size")
        emit("daft_tpu_rss_peak_bytes", snap["rss_peak_bytes"], "gauge",
             "peak process resident set size since start/reset")
        if snap["limit_bytes"]:
            emit("daft_tpu_memory_limit_bytes", snap["limit_bytes"],
                 "gauge", "configured DAFT_TPU_MEMORY_LIMIT budget")
        emit("daft_tpu_governor_pressured", snap["pressured"], "gauge",
             "1 while RSS sits inside the governor's hysteresis band")
    except Exception:
        pass
    try:
        from .distributed import resilience
        plane("recovery", resilience.counters_snapshot(),
              "resilience recovery counter")
    except Exception:
        pass
    try:
        from .physical import adaptive as _adaptive
        plane("adaptive", _adaptive.counters_snapshot(),
              "self-tuning feedback counter")
    except Exception:
        pass
    try:
        from .device import calibration
        if calibration.enabled():
            emit("daft_tpu_calibration_constants_active",
                 len(calibration.calibrated_names()), "gauge",
                 "cost-model constants currently overridden by the "
                 "calibrated profile")
    except Exception:
        pass
    try:
        from .parallel import exchange
        ex = exchange.exchange_cache_counters()
        emit("daft_tpu_exchange_programs", ex.pop("entries", 0), "gauge",
             "memoized collective exchange programs resident")
        plane("exchange", ex,
              "collective exchange program-cache counter")
    except Exception:
        pass
    try:
        from .distributed import topology
        emit("daft_tpu_exchange_collective_inflight",
             topology.collective_inflight(), "gauge",
             "collective exchange groups currently in flight")
    except Exception:
        pass
    try:
        from . import observability as obs
        plane("obs", obs.obs_counters_snapshot(),
              "observability export counter")
    except Exception:
        pass
    try:
        from .fleet import state_sync
        fleet_counters = state_sync.counters_snapshot()
        if fleet_counters:
            plane("fleet", fleet_counters,
                  "serving-fleet counter (routing, gossip, cache tier)")
    except Exception:
        pass
    try:
        from .analysis import retrace_sanitizer
        plane("retrace", retrace_sanitizer.counters_snapshot(),
              "retrace sanitizer counter")
    except Exception:
        pass
    try:
        from .analysis import plan_sanitizer
        plane("plansan", plan_sanitizer.counters_snapshot(),
              "plan sanitizer contract-check counter")
    except Exception:
        pass
    try:
        from .device import costmodel
        for kind, d in sorted(costmodel.ledger_snapshot(raw=True).items()):
            emit(_prom_name("kernel", f"{kind}_dispatches") + "_total",
                 d.get("dispatches", 0), "counter",
                 f"device dispatches ({kind})")
            emit(_prom_name("kernel", f"{kind}_seconds") + "_total",
                 round(d.get("seconds", 0.0), 6), "counter",
                 f"device kernel seconds ({kind})")
    except Exception:
        pass
    try:
        from . import serving
        sched = serving.shared_scheduler_if_running()
        if sched is not None:
            view = sched.live_view()
            emit("daft_tpu_serving_queue_depth", view.get("queued", 0),
                 "gauge", "queries queued in the serving scheduler")
            emit("daft_tpu_serving_running", view.get("running", 0),
                 "gauge", "queries currently running")
            emit("daft_tpu_serving_admitted_bytes",
                 view.get("admitted_bytes", 0), "gauge",
                 "admission-controller outstanding bytes")
            counters = view.get("counters", {})
            for k in sorted(counters):
                if k.startswith(("plan_cache_", "result_cache_")) \
                        or k in ("submitted", "completed", "failed",
                                 "cancelled") \
                        or k.startswith("rejected_"):
                    emit(_prom_name("serving", k) + "_total",
                         counters[k], "counter",
                         f"serving scheduler counter ({k})")
            for cache in ("plan_cache", "result_cache"):
                hits = counters.get(f"{cache}_hits", 0)
                misses = counters.get(f"{cache}_misses", 0)
                if hits + misses:
                    emit(f"daft_tpu_serving_{cache}_hit_rate",
                         round(hits / (hits + misses), 6), "gauge",
                         f"{cache} hit rate since process start")
    except Exception:
        pass
    emit("daft_tpu_traces_active", len(_recorders), "gauge",
         "span recorders currently registered")
    with _flight_lock:
        emit("daft_tpu_flight_recorder_queries_total", _flight_written,
             "counter", "queries persisted to the flight recorder")
    return "\n".join(lines) + "\n"


def parse_prometheus_text(text: str) -> Dict[str, float]:
    """Strict parser for the text exposition format (the scrape gate in
    ``obs-smoke``): every line must be a comment, blank, or
    ``name[{labels}] value [timestamp]`` with a valid metric name and a
    float value. Raises ``ValueError`` on any malformed line."""
    out: Dict[str, float] = {}
    typed: Dict[str, str] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] in ("HELP", "TYPE"):
                if parts[1] == "TYPE":
                    if len(parts) < 4 or parts[3] not in (
                            "counter", "gauge", "histogram", "summary",
                            "untyped"):
                        raise ValueError(
                            f"line {lineno}: bad TYPE line {line!r}")
                    typed[parts[2]] = parts[3]
                continue
            raise ValueError(f"line {lineno}: bad comment {line!r}")
        name = line.split("{")[0].split()[0]
        if not name or not (name[0].isalpha() or name[0] in "_:"):
            raise ValueError(f"line {lineno}: bad metric name {line!r}")
        if not all(c.isalnum() or c in "_:" for c in name):
            raise ValueError(f"line {lineno}: bad metric name {line!r}")
        rest = line[len(name):].strip()
        if rest.startswith("{"):
            close = rest.find("}")
            if close < 0:
                raise ValueError(f"line {lineno}: unclosed labels")
            rest = rest[close + 1:].strip()
        fields = rest.split()
        if not fields:
            raise ValueError(f"line {lineno}: missing value")
        try:
            value = float(fields[0])
        except ValueError:
            raise ValueError(
                f"line {lineno}: non-numeric value {fields[0]!r}")
        if len(fields) > 2:
            raise ValueError(f"line {lineno}: trailing garbage")
        out[name] = value
    return out


# ------------------------------------------------------ flight recorder

_flight_lock = threading.Lock()
_flight_written = 0


def _flight_path() -> Optional[str]:
    from .analysis import knobs
    return knobs.env_str("DAFT_TPU_QUERY_LOG") or None


def flight_record(entry: dict) -> None:
    """Append one query record to the flight-recorder JSONL
    (``DAFT_TPU_QUERY_LOG``); rotates the file to ``<path>.1`` when it
    exceeds ``DAFT_TPU_QUERY_LOG_BYTES``. Never raises into the query
    path."""
    global _flight_written
    path = _flight_path()
    if not path:
        return
    from .analysis import knobs
    cap = knobs.env_bytes("DAFT_TPU_QUERY_LOG_BYTES")
    try:
        line = json.dumps(entry, default=str) + "\n"
    except Exception:
        return
    with _flight_lock:
        try:
            if cap and cap > 0:
                try:
                    if os.path.getsize(path) + len(line) > cap:
                        os.replace(path, path + ".1")
                except OSError:
                    pass  # no current file yet
            # daft-lint: allow(blocking-under-lock) -- the size check,
            # rotation and append must be one atomic unit vs concurrent
            # query-finish writers; local log file, one line per query
            with open(path, "a") as f:
                f.write(line)
            _flight_written += 1
        except Exception:
            pass


#: bytes read from the END of each flight-recorder generation per
#: history call — the wanted entries are by construction at the tail;
#: reading whole 16MiB logs per dashboard poll is the alternative
_FLIGHT_TAIL_BYTES = 512 << 10


def flight_history(limit: int = 200) -> List[dict]:
    """Most-recent-first flight-recorder entries (current file, then
    the rotated generation), read from a bounded tail window of each.
    Tolerates torn/partial head lines."""
    path = _flight_path()
    if not path:
        return []
    out: List[dict] = []
    for p in (path, path + ".1"):
        try:
            with open(p, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                start = max(size - _FLIGHT_TAIL_BYTES, 0)
                f.seek(start)
                data = f.read()
        except OSError:
            continue
        lines = data.splitlines()
        if start > 0 and lines:
            lines = lines[1:]  # first line is mid-record: drop it
        for line in reversed(lines):
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
            if len(out) >= limit:
                return out
    return out


def slow_query_ms() -> float:
    from .analysis import knobs
    return knobs.env_float("DAFT_TPU_SLOW_QUERY_MS")


def reset_for_tests() -> None:
    global _flight_written
    with _reg_lock:
        _recorders.clear()
    _finished_ring.clear()
    with _flight_lock:
        _flight_written = 0
