"""Push-based morsel pipeline: per-operator workers over bounded channels.

The reference's local engine ("Swordfish",
``src/daft-local-execution/src/pipeline.rs:100-830``) runs every operator
as concurrent tasks connected by bounded channels: a dispatcher task
distributes input morsels to N worker tasks
(``dispatcher.rs:24-60`` — RoundRobin preserves order, Unordered doesn't,
Partitioned fans by key), and blocking sinks consume their whole input
through the same channel machinery before emitting
(``sinks/blocking_sink.rs:32-55``).

This module is that dataflow for the TPU engine, built on Python threads
(Arrow C++ and XLA release the GIL, so operator workers genuinely overlap;
the reference reaches the same place with tokio tasks):

- :class:`Channel` — bounded MPMC queue with producer-refcounted close and
  cooperative cancellation.
- :class:`PipelineContext` — per-query thread registry, first-error
  capture, cancellation fan-out.
- :class:`PushExecutor` — a :class:`LocalExecutor` whose ``_exec`` returns
  an iterator over an ACTIVELY-PUSHED output channel instead of a lazy
  generator:

  * map-shaped operators (Project/Filter/Explode/…) become real worker
    stages: one RoundRobin dispatcher thread, N kernel workers, one
    collector thread that restores order — per-operator worker counts and
    observed morsel sizes land in ``explain_analyze``/traces.
  * everything else (sources, sorts, joins, exchanges, device tiers,
    limits) runs its inherited handler inside a dedicated driver thread;
    the handler's child pulls transparently become channel reads, so every
    operator in the plan is an always-running concurrent component with
    backpressure — the push topology — while the TPU-specialized handlers
    stay single-sourced in ``executor.py``.

Cancellation: dropping the output iterator (or an operator error) cancels
the context; blocked producers wake within ``_POLL_S`` and unwind. The
first error wins and re-raises at the consumer.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Callable, Iterator, List, Optional

from .. import tracing
from ..micropartition import MicroPartition
from ..physical import plan as pp
from .executor import LocalExecutor

_POLL_S = 0.05  # cancellation latency bound for blocked channel ops
_REAGG_ROWS = 1 << 17  # partitioned-agg reducer: merge state every N rows


class PipelineCancelled(Exception):
    """Internal unwind signal — never escapes to the user."""


class PipelineContext:
    """Per-query registry of stage threads + first-error capture."""

    def __init__(self):
        self.cancelled = threading.Event()
        self.error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self.threads: List[threading.Thread] = []
        # the owning query's RuntimeStatsContext: installed on every
        # stage thread so shared-plane counters attribute to this query
        self.stats_ctx = None

    def fail(self, exc: BaseException):
        with self._lock:
            if self.error is None:
                self.error = exc
        self.cancelled.set()

    def cancel(self):
        self.cancelled.set()

    def spawn(self, fn: Callable[[], None], name: str) -> threading.Thread:
        t = threading.Thread(target=self._guard, args=(fn,), name=name,
                             daemon=True)
        with self._lock:
            self.threads.append(t)
        t.start()
        return t

    def _guard(self, fn):
        from .. import observability as obs
        from .. import tracing
        try:
            with obs.attributed(self.stats_ctx):
                # one span per stage-thread lifetime; the thread name is
                # deterministic (plan-derived), so span ids replay
                name = threading.current_thread().name
                with tracing.span("pipeline:stage", key=f"stage:{name}",
                                  attrs={"thread": name},
                                  lane="pipeline"):
                    fn()
        except PipelineCancelled:
            pass
        except BaseException as exc:  # noqa: BLE001 — first error wins
            self.fail(exc)

    def join(self, timeout: float = 5.0):
        for t in self.threads:
            t.join(timeout=timeout)


_DONE = object()


class _Stamped:
    """An item put by a traced thread, with the instant of the put
    (``time.perf_counter_ns()``): what the taker's hand-off latency is
    counted from."""

    __slots__ = ("item", "t_ns")

    def __init__(self, item):
        self.item = item
        self.t_ns = time.perf_counter_ns()


class Channel:
    """Bounded channel with producer-refcounted close.

    ``producers`` producers must each call :meth:`close`; when the last
    one does, ``consumers`` DONE markers are enqueued so every consumer's
    iteration terminates. Blocked puts/gets poll the context's cancel
    event (there is no way to interrupt a raw ``queue`` wait).

    Traced, a put on a full queue and a take from an empty one are
    ``wait:channel`` spans (``side``: ``put`` / ``get``), and every take
    tallies one hand-off: the time from the later of (item put, taker
    began to wait) to the taker running. Untraced the queue holds the
    very objects it was given."""

    def __init__(self, ctx: PipelineContext, capacity: int = 4,
                 producers: int = 1, consumers: int = 1):
        self.ctx = ctx
        self._q: queue.Queue = queue.Queue(maxsize=max(capacity, 1))
        self._producers = producers
        self._consumers = consumers
        self._lock = threading.Lock()

    def put(self, item) -> None:
        if tracing.current() is None:
            self._put(item)
            return
        item = _Stamped(item)
        try:
            self._q.put_nowait(item)
        except queue.Full:
            self._put(item)
            tracing.note_wait("wait:channel", item.t_ns,
                              time.perf_counter_ns(), {"side": "put"})

    def _put(self, item) -> None:
        while True:
            if self.ctx.cancelled.is_set():
                raise PipelineCancelled()
            try:
                self._q.put(item, timeout=_POLL_S)
                return
            except queue.Full:
                continue

    def close(self) -> None:
        with self._lock:
            self._producers -= 1
            if self._producers > 0:
                return
        for _ in range(self._consumers):
            try:
                self.put(_DONE)
            except PipelineCancelled:
                return

    def _get(self):
        while True:
            if self.ctx.cancelled.is_set():
                raise PipelineCancelled()
            try:
                return self._q.get(timeout=_POLL_S)
            except queue.Empty:
                continue

    def _get_traced(self, tctx):
        """A take by a traced thread: the wait on an empty queue as a
        span, the hand-off tallied, the item unwrapped."""
        rec = tctx.recorder
        try:
            item = self._q.get_nowait()
            t_wait = 0
        except queue.Empty:
            t_wait = time.perf_counter_ns()
            item = self._get()
        if type(item) is not _Stamped:      # put by an untraced thread
            return item
        if not t_wait:
            # it lay in the queue when its taker came: handed over at once
            rec.handoff(0)
            return item.item
        now = time.perf_counter_ns()
        tail_us = (now - max(item.t_ns, t_wait)) // 1000
        rec.handoff(tail_us)
        rec.add_wait("wait:channel", tctx.span_id, t_wait, now,
                     {"side": "get", "tail_us": tail_us})
        return item.item

    def __iter__(self) -> Iterator:
        while True:
            tctx = tracing.current()
            if tctx is not None:
                item = self._get_traced(tctx)
            else:
                item = self._get()
                if type(item) is _Stamped:  # a traced put, an untraced take
                    item = item.item
            if item is _DONE:
                return
            yield item


def _default_workers() -> int:
    return max(min((os.cpu_count() or 4), 8), 2)


# map-shaped operators: (node type name) -> kernel factory. Each returns a
# per-morsel function; the stage machinery provides dispatcher / workers /
# in-order collection. Per-partition semantics match executor.py's
# _ordered_parallel bodies (single-sourced there for the interpreter).
def _map_kernel(node) -> Optional[Callable[[MicroPartition], MicroPartition]]:
    name = type(node).__name__
    if name == "Project":
        return lambda p: p.eval_expression_list(node.exprs)
    if name == "UDFProject":
        return lambda p: p.eval_expression_list(node.exprs)
    if name == "Filter":
        return lambda p: p.filter(node.predicate)
    if name == "Explode":
        return lambda p: p.explode(node.exprs)
    if name == "Unpivot":
        return lambda p: p.unpivot(node.ids, node.values,
                                   node.variable_name, node.value_name)
    if name == "Dedup":
        return lambda p: p.distinct(node.on)
    if name == "Sample":
        if node.fraction is not None:
            return lambda p: p.sample(fraction=node.fraction, size=None,
                                      with_replacement=node.with_replacement,
                                      seed=node.seed)
        return lambda p: p.head(node.size)
    if name == "Window":
        from ..window_exec import run_window
        return lambda p: MicroPartition.from_recordbatch(
            run_window(p.combined(), node))
    if name == "Pivot":
        return lambda p: p.pivot(node.group_by, node.pivot_col,
                                 node.value_col,
                                 node.names).cast_to_schema(node.schema())
    if name == "Aggregate":
        # per-partition agg (partial stage, or final over hash buckets) is
        # map-shaped; the fused device tier (DeviceFragmentAgg) stays a
        # driver stage
        return lambda p: p.agg(node.aggs, node.group_by) \
            .cast_to_schema(node.schema())
    return None


def _map_workers(node) -> int:
    if type(node).__name__ == "UDFProject" and node.concurrency:
        return max(int(node.concurrency), 1)
    return _default_workers()


# The final-stage agg ops the fused reducer can merge are the associative
# self-merges single-sourced in ``aggs.AGG_DECOMPOSITION``: re-applying the
# op over its own output column merges two partial states correctly, which
# is what makes the reference's Partitioned dispatcher + grouped_aggregate
# sink sound (``dispatcher.rs:24-60``, ``sinks/grouped_aggregate.rs:54-151``).
# The merge expressions come from ``aggs.merge_exprs_for`` (shared with the
# distributed map-side shuffle combine and the streaming reduce-side merge
# agg).

#: decline the fused dispatcher when the evidence predicts more groups
#: than this: the spill-bounded exchange path aggregates each bucket
#: exactly once, while the fused reducer's LSM merges cost O(log n) passes
#: over a state it must also hold in RAM. Measured crossover on TPC-H:
#: 15M groups (SF10 Q18) fused wins 34.5s vs 46.5s; 150M groups (SF100
#: Q18) fused loses 528s vs 207s. Evidence, best-first: parquet-footer
#: NDV; else the planner's row estimate (an upper bound on groups — a
#: near-unique-key groupby on a huge in-memory source must not default
#: into the fused reducer's unbounded group state, the r5 OOM hole);
#: either way a configured DAFT_TPU_MEMORY_LIMIT additionally declines
#: predicted group state that cannot fit the budget.
_FUSE_MAX_GROUPS = 32_000_000

#: resident bytes one group row costs the fused reducer (key + agg state
#: columns at ~8B each plus Arrow overhead), times the ~2× LSM headroom —
#: deliberately coarse; only the order of magnitude gates anything
_FUSE_BYTES_PER_GROUP = 16


def _fused_groups_admissible(node) -> bool:
    """Decline-if-huge gate for the fused partitioned-agg dispatcher."""
    ndv = getattr(node, "group_ndv", None)
    if ndv is None:
        ndv = getattr(node, "group_rows_est", None)
    if ndv is None:
        return True
    if ndv > _FUSE_MAX_GROUPS:
        return False
    from .memory import memory_limit_bytes
    budget = memory_limit_bytes()
    if budget is not None:
        est = _est_state_bytes(node)
        if est is not None and est > budget:
            return False
    return True


def _est_state_bytes(node):
    """Predicted resident group-state bytes for this final agg (the
    fused reducer's working set): NDV evidence × row width × the coarse
    per-group cost — None without evidence."""
    ndv = getattr(node, "group_ndv", None)
    if ndv is None:
        ndv = getattr(node, "group_rows_est", None)
    if ndv is None:
        return None
    width = max(1 + len(getattr(node, "group_by", ())
                        ) + len(getattr(node, "aggs", ())), 2)
    return float(ndv) * width * _FUSE_BYTES_PER_GROUP


def _partitioned_agg_info(node, cfg=None):
    """When ``node`` is a final grouped Aggregate over an engine-inserted
    hash Exchange whose final aggs are associative self-merges, return
    (exchange_child, key_exprs, merge_aggs, spill, est_state_bytes) for
    the fused partitioned-agg stage; else None. ``merge_aggs`` re-merge
    two batches of FINAL-schema state: for a final agg
    ``op(col(p)).alias(out)``, the merge is ``op(col(out)).alias(out)``.

    ``spill`` selects the spill-partitioned reducer (round 19): a group
    state the budget can't hold streams through a rotated-radix spill
    store and merges per bucket on read (``AGG_DECOMPOSITION`` self-merge
    semantics) — peak RSS ≈ budget + one bucket — instead of declining
    the fusion (``DAFT_TPU_SPILL_AGG=0`` restores the decline)."""
    from ..aggs import merge_exprs_for
    from . import out_of_core as ooc
    if not (isinstance(node, pp.Aggregate) and node.mode == "final"
            and node.group_by):
        return None
    ch = node.children[0]
    if not (isinstance(ch, pp.Exchange) and ch.kind == "hash"
            and ch.engine_inserted):
        return None
    mode = ooc.spill_agg_mode(cfg)
    est_state = _est_state_bytes(node)
    if _fused_groups_admissible(node):
        spill = mode == "1"
    elif mode == "0":
        return None  # legacy decline → the spill-bounded exchange plan
    else:
        # the in-memory reducer's state would not fit (or NDV evidence
        # is past the fuse ceiling): spill-partitioned reducer
        spill = True
    # shared subplans stream through the executor's shared buffer — the
    # fusion would bypass it
    if getattr(ch, "shared_consumers", 1) > 1 \
            or getattr(node, "shared_consumers", 1) > 1:
        return None
    merge = merge_exprs_for(node.aggs, alias_to="out")
    if merge is None:
        return None
    return ch.children[0], list(ch.by), merge, spill, est_state


class PushExecutor(LocalExecutor):
    """Push-dataflow executor: every plan node is an always-running stage.

    Inherits every operator implementation from :class:`LocalExecutor`;
    only the wiring changes — ``_exec`` spawns the node's stage threads and
    returns an iterator over its bounded output channel, so a handler's
    ``self._exec(child)`` transparently becomes a channel subscription and
    the whole plan runs concurrently with backpressure."""

    #: channel capacity between stages, in morsels. Small: backpressure is
    #: the point; each buffered morsel is ~default_morsel_size rows.
    CHANNEL_CAPACITY = 4

    def __init__(self):
        super().__init__()
        self.pipe = PipelineContext()

    # ------------------------------------------------------------- entry
    def run(self, plan: pp.PhysicalPlan,
            stage_inputs=None) -> Iterator[MicroPartition]:
        if stage_inputs:
            self.stage_inputs = stage_inputs
        from .. import observability as obs
        from . import cancellation as _cxl
        self.stats = obs.new_query_stats()
        self.stats.plan = plan
        self.pipe.stats_ctx = self.stats
        xdir = obs.xplane_trace_dir()
        tok = self.cancel_token
        if tok is not None:
            # a fired token must unblock EVERY stage (channels poll the
            # pipeline's cancel event), not just the driver loop
            tok.add_callback(self.pipe.cancel)

        def gen():
            xtrace = obs._XplaneTrace(xdir) if xdir else None
            try:
                out = self._exec(plan)
                while True:
                    try:
                        with obs.attributed(self.stats):
                            mp = next(out)
                    except StopIteration:
                        break
                    except PipelineCancelled:
                        break
                    yield mp
                if tok is not None and tok.is_set():
                    raise _cxl.QueryCancelled(
                        tok.reason or "query cancelled")
                if self.pipe.error is not None:
                    raise self.pipe.error
            finally:
                self.pipe.cancel()
                if xtrace is not None:
                    xtrace.stop()
                self.stats.finish()
                obs.set_last_stats(self.stats)
        return gen()

    # ------------------------------------------------------------ stages
    # _exec (inherited) routes multi-consumer nodes through the shared
    # buffer; everything else lands here and becomes a stage
    def _exec_node(self, node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        pagg = _partitioned_agg_info(node, self.cfg)
        if pagg is not None:
            out = self._partitioned_agg_stage(node, *pagg)
        elif isinstance(node, pp.Aggregate) \
                and self._streamed_agg_input(node):
            # a streaming parallel-fetch stage input yields one morsel per
            # map SOURCE (not hash-disjoint) — the per-morsel map kernel
            # would duplicate groups; run the inherited streaming
            # merge-agg handler on a driver stage instead
            out = self._driver_stage(node)
        else:
            kernel = _map_kernel(node)
            if kernel is not None:
                out = self._map_stage(node, kernel)
            else:
                out = self._driver_stage(node)
        from ..analysis import plan_sanitizer
        wrapped = plan_sanitizer.wrap_node(node, iter(out))
        if self.stats is not None:
            return self.stats.instrument(node, wrapped)
        return wrapped

    def _driver_stage(self, node) -> Channel:
        """One dedicated thread runs the inherited handler generator and
        pushes its output — sources, sinks, joins, exchanges, device tiers
        and limits keep their single-sourced implementations while still
        living inside the push topology."""
        h = getattr(LocalExecutor, "_exec_" + type(node).__name__, None)
        if h is None:
            raise NotImplementedError(f"executor for {type(node).__name__}")
        out = Channel(self.pipe, self.CHANNEL_CAPACITY)

        def drive():
            # fail() BEFORE close(): close enqueues the DONE marker, and a
            # consumer that drains it must already see ctx.error — the
            # reverse order can end a failing query as a clean truncated
            # stream
            try:
                for mp in h(self, node):
                    out.put(mp)
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)
            finally:
                out.close()
        self.pipe.spawn(drive, name=f"drv-{type(node).__name__}")
        return out

    def _partitioned_agg_stage(self, node, exchange_child, by,
                               merge_aggs, spill: bool = False,
                               est_state=None) -> Channel:
        """Partitioned-by-hash dispatcher fused with the final grouped
        aggregation (reference ``dispatcher.rs:24-60`` Partitioned +
        ``sinks/grouped_aggregate.rs:54-151``): the dispatcher hashes the
        incoming partial-agg morsels into k slices (small ones together:
        ``out_of_core.coalesce_small``; an input that fits that buffer
        whole goes unhashed to reducer 0), worker i streams
        partition i, incrementally merging its state every
        ``_REAGG_ROWS`` buffered rows, and emits its final state at
        close. Replaces Exchange(hash) + per-bucket map agg: no
        materialization barrier, k concurrent reducers, and the final agg
        starts before the child finishes.

        Memory: the un-merged buffer is bounded by
        ``max(_REAGG_ROWS, len(state))`` — the LSM-style amortization lets
        it grow to the current state size, so peak residency is ~2× the
        worker's group cardinality (proportional to the output this
        reducer must materialize anyway). With ``spill`` (round 19) the
        reducer never holds its state at all: every ``_REAGG_ROWS`` the
        buffer collapses to FINAL-schema partial states that radix-fan
        (rotated — the dispatcher already consumed ``h % k``) into a
        per-reducer spill store, and each bucket self-merges ON READ via
        the ``AGG_DECOMPOSITION`` merge expressions — an unbounded-NDV
        group-by streams in one pass at peak RSS ≈ budget + one bucket,
        recursing (bounded) on a bucket skew redominates."""
        from .. import tracing
        from . import out_of_core as ooc
        k = _default_workers()
        if self.stats is not None:
            self.stats.register(node).workers = k
        if self.cfg.enable_aqe:
            self._aqe().record_replan(
                f"fused partitioned agg: hash shuffle elided → {k} reducers"
                + (" (spill-partitioned)" if spill else ""))
        child = self._exec(exchange_child)
        in_q = [Channel(self.pipe, 2) for _ in range(k)]
        out = Channel(self.pipe, self.CHANNEL_CAPACITY, producers=k)
        name = type(node).__name__

        def fan(mp, morsels):
            for i, part in enumerate(mp.partition_by_hash(by, k, morsels)):
                if len(part):
                    in_q[i].put(part)

        def dispatch():
            try:
                units = ooc.coalesce_small(child)
                head = next(units, None)
                if head is None:
                    return
                mp, morsels = head
                if len(mp) < ooc.FANOUT_COALESCE_ROWS:
                    # a small head left the buffer because the stream
                    # ended or a large morsel is already behind it: the
                    # look-ahead waits for nothing
                    behind = next(units, None)
                    if behind is None:
                        # the whole input fitted the buffer. Reducers
                        # need only be key-disjoint, which one is: hash
                        # nothing, and the other k - 1 see a closed
                        # channel, as an empty partition does
                        with tracing.span("exchange:gather",
                                          lane="pipeline",
                                          attrs={"rows": len(mp),
                                                 "morsels": morsels}):
                            in_q[0].put(MicroPartition.from_recordbatch(
                                mp.combined()))
                        return
                    fan(mp, morsels)
                    mp, morsels = behind
                # once a morsel went out by hash, all that follow do too
                fan(mp, morsels)
                for mp, morsels in units:
                    fan(mp, morsels)
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)  # before close — see _driver_stage
            finally:
                for q in in_q:
                    q.close()

        def reducer(i):
            state: Optional[MicroPartition] = None
            buf: List[MicroPartition] = []
            rows = 0

            def merge():
                nonlocal state, buf, rows
                if not buf:
                    return
                fresh = buf[0].concat(buf[1:]) if len(buf) > 1 else buf[0]
                fresh = fresh.agg(node.aggs, node.group_by) \
                    .cast_to_schema(node.schema())
                state = fresh if state is None else \
                    state.concat([fresh]).agg(merge_aggs, node.group_by) \
                    .cast_to_schema(node.schema())
                buf, rows = [], 0

            try:
                for mp in in_q[i]:
                    buf.append(mp)
                    rows += len(mp)
                    # merge only once the buffer rivals the state (LSM-style
                    # amortization): every row then joins O(log n) merges.
                    # A fixed threshold is quadratic on near-unique keys —
                    # SF100 Q18 (groups ≈ rows) spent 5.6× host time
                    # re-merging a 100M-row state every 128k rows
                    if rows >= max(_REAGG_ROWS,
                                   0 if state is None else len(state)):
                        merge()
                merge()
                if state is not None and len(state):
                    out.put(state)
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)
            finally:
                out.close()

        def spill_reducer(i):
            from ..expressions import col as _col
            from . import memory, spill_io
            skeys = [_col(g.name()) for g in node.group_by]
            m = ooc.agg_state_fanout(est_state, k, self.cfg)
            depth_max = ooc.spill_max_depth(self.cfg)
            bucket_budget = max(ooc.pair_budget_bytes() // k, 16 << 10)
            store = memory.PartitionedSpillStore(
                m, budget=max(memory.breaker_budget_bytes() // k,
                              16 << 10))
            buf: List[MicroPartition] = []
            rows = 0

            def flush():
                nonlocal buf, rows
                if not buf:
                    return
                fresh = buf[0].concat(buf[1:]) if len(buf) > 1 else buf[0]
                fresh = fresh.agg(node.aggs, node.group_by) \
                    .cast_to_schema(node.schema())
                for j, piece in enumerate(ooc.radix_split(
                        fresh.combined(), skeys, m, 1)):
                    if len(piece):
                        store.push(j, piece)
                buf, rows = [], 0

            try:
                for mp in in_q[i]:
                    buf.append(mp)
                    rows += len(mp)
                    if rows >= _REAGG_ROWS:
                        flush()
                flush()
                store.finalize()
                # bucket reads prefetch-pipelined like the grace join's
                # pair reads: bucket j+1 decodes while j merges
                for batches in spill_io.prefetch_ordered(
                        (lambda j=j: store.bucket_batches(j)
                         for j in range(m)),
                        spill_io.read_prefetch_window(self.cfg)):
                    if not batches:
                        continue
                    for state in ooc.merge_spilled_agg_bucket(
                            batches, merge_aggs, node.group_by,
                            node.schema(), skeys, 1, depth_max,
                            bucket_budget):
                        if len(state):
                            out.put(state)
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)
            finally:
                store.close()
                out.close()

        self.pipe.spawn(dispatch, name=f"dsp-{name}")
        body = spill_reducer if spill else reducer
        for i in range(k):
            self.pipe.spawn(lambda i=i: body(i), name=f"red-{name}-{i}")
        return out

    def _map_stage(self, node, kernel) -> Channel:
        """RoundRobin dispatcher → N kernel workers → in-order collector
        (``dispatcher.rs:38-131``: RR to per-worker channels preserves
        global order when read back round-robin)."""
        k = _map_workers(node)
        if self.stats is not None:
            self.stats.register(node).workers = k
        child = self._exec(node.children[0])
        in_q = [Channel(self.pipe, 2) for _ in range(k)]
        out_q = [Channel(self.pipe, 2) for _ in range(k)]
        out = Channel(self.pipe, self.CHANNEL_CAPACITY)
        name = type(node).__name__

        def dispatch():
            try:
                i = 0
                for mp in child:
                    in_q[i % k].put(mp)
                    i += 1
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)  # before close — see _driver_stage
            finally:
                for q in in_q:
                    q.close()

        def worker(i):
            try:
                for mp in in_q[i]:
                    out_q[i].put(kernel(mp))
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)
            finally:
                out_q[i].close()

        def collect():
            try:
                iters = [iter(q) for q in out_q]
                alive = list(range(k))
                while alive:
                    nxt = []
                    for i in alive:
                        try:
                            out.put(next(iters[i]))
                            nxt.append(i)
                        except StopIteration:
                            pass
                    alive = nxt
            except PipelineCancelled:
                pass
            except BaseException as exc:  # noqa: BLE001
                self.pipe.fail(exc)
            finally:
                out.close()

        self.pipe.spawn(dispatch, name=f"dsp-{name}")
        for i in range(k):
            self.pipe.spawn(lambda i=i: worker(i), name=f"wrk-{name}-{i}")
        self.pipe.spawn(collect, name=f"col-{name}")
        return out
