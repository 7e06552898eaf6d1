"""Streaming partition-parallel local executor.

The single-node engine (reference: "Swordfish",
``src/daft-local-execution``): operators stream MicroPartitions, pipelined
ops run on a shared thread pool (Arrow C++ and XLA both release the GIL, so
threads scale), pipeline breakers (sort / final agg / join build) materialize.
Ordering is preserved via bounded in-order future windows
(the RoundRobin dispatcher of ``dispatcher.rs:24-60``).

Global sort follows the reference's sample→boundaries→range-partition→merge
pipeline (``daft/execution/physical_plan.py:1632``).
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import threading
from typing import Callable, Iterator, List, Optional

import numpy as np

from ..context import get_context
from ..expressions import Expression, col
from ..micropartition import MicroPartition
from ..physical import plan as pp
from ..recordbatch import RecordBatch
from ..series import Series

_POOL: Optional[cf.ThreadPoolExecutor] = None
_SCAN_POOL: Optional[cf.ThreadPoolExecutor] = None
# guards pool creation: two racing first callers used to each build a
# pool, leaking the loser's worker threads for the process lifetime
# (found by daft-lint's unguarded-global-mutation rule)
_pools_lock = threading.Lock()


def _pool() -> cf.ThreadPoolExecutor:
    global _POOL
    if _POOL is not None:   # hot path: no lock once built
        return _POOL
    with _pools_lock:
        if _POOL is None:
            _POOL = cf.ThreadPoolExecutor(
                max_workers=max(os.cpu_count() or 4, 4),
                thread_name_prefix="daft-tpu-exec")
        return _POOL


def _scan_pool() -> cf.ThreadPoolExecutor:
    """Dedicated pool for prefetch-pipelined scan producers. NOT the
    shared exec pool: a producer blocked on its bounded output queue
    would otherwise hold an exec slot that a downstream operator's future
    needs to drain that very queue (deadlock when window+1 ≥ pool size)."""
    global _SCAN_POOL
    if _SCAN_POOL is not None:
        return _SCAN_POOL
    with _pools_lock:
        if _SCAN_POOL is None:
            _SCAN_POOL = cf.ThreadPoolExecutor(
                max_workers=max((os.cpu_count() or 4) * 2, 8),
                thread_name_prefix="daft-tpu-scan")
        return _SCAN_POOL


def _ordered_parallel(inputs: Iterator, fn: Callable,
                      width: Optional[int] = None) -> Iterator:
    """Map fn over inputs on the pool, yielding results in order with a
    bounded in-flight window (backpressure)."""
    from .. import observability as obs
    width = width or max((os.cpu_count() or 4), 4) * 2
    pool = _pool()
    pending: List[cf.Future] = []
    it = iter(inputs)
    done = False
    while True:
        while not done and len(pending) < width:
            try:
                x = next(it)
            except StopIteration:
                done = True
                break
            # carry the submitting thread's stats attribution onto the
            # pool worker: shared-plane counters bumped inside fn must
            # credit the query this morsel belongs to
            pending.append(pool.submit(
                obs.run_attributed, obs.submit_attribution("exec"),
                fn, x))
        if not pending:
            return
        yield pending.pop(0).result()


class LocalExecutor:
    """Interprets a physical plan into a stream of MicroPartitions."""

    def __init__(self):
        from . import cancellation, memory
        self.cfg = get_context().execution_config
        self.stats = None
        # cooperative cancellation: the serving scheduler installs the
        # query's token on the submitting thread (cancel_scope); capture
        # it here so it rides the executor instance into stage threads
        self.cancel_token = cancellation.current_token()
        # bounds bytes of scan tasks materializing concurrently
        self.mem = memory.MemoryManager()
        # stage-input bindings for distributed stage fragments
        self.stage_inputs = {}
        self._aqe_planner = None
        # shared-subplan result buffers (multi-consumer physical nodes)
        import threading as _th
        self._shared = {}
        self._shared_lock = _th.Lock()

    def _aqe(self):
        if self._aqe_planner is None:
            from ..physical import adaptive
            self._aqe_planner = adaptive.new_planner(self.cfg)
        return self._aqe_planner

    def _poll_cancel(self) -> None:
        """Cancellation poll for blocking drain loops. The driver loop
        checks the token at every YIELD boundary, but a pipeline breaker
        (sort consume, exchange fanout, join bucket store) drains its
        whole child before yielding anything — without this poll,
        INTERRUPT on a breaker-heavy query ran it to completion while
        holding its admission (daft-lint: uncancellable-loop)."""
        tok = self.cancel_token
        if tok is not None:
            tok.check()

    def run(self, plan: pp.PhysicalPlan,
            stage_inputs=None) -> Iterator[MicroPartition]:
        if stage_inputs:
            self.stage_inputs = stage_inputs
        return self._run(plan)

    def _run(self, plan: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        from .. import observability as obs
        self.stats = obs.new_query_stats()
        self.stats.plan = plan  # for explain_analyze rendering
        xdir = obs.xplane_trace_dir()

        def gen():
            xtrace = obs._XplaneTrace(xdir) if xdir else None
            tok = self.cancel_token
            it = None
            try:
                # every pull at this boundary runs with this query's
                # stats context attributed on the consumer thread, so
                # shared-plane counters (scan io, shuffle, recovery)
                # credit THIS query even when others run concurrently;
                # the token check bounds cancel latency to one morsel
                with obs.attributed(self.stats):
                    it = obs.wrap_progress(self._exec(plan))
                while True:
                    if tok is not None:
                        tok.check()
                    with obs.attributed(self.stats):
                        try:
                            item = next(it)
                        except StopIteration:
                            break
                    yield item
            finally:
                if it is not None and hasattr(it, "close"):
                    with obs.attributed(self.stats):
                        it.close()  # producer cleanup counts here too
                if xtrace is not None:
                    xtrace.stop()
                self.stats.finish()
                obs.set_last_stats(self.stats)
        return gen()

    # ------------------------------------------------------------------
    def _exec(self, node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        if getattr(node, "shared_consumers", 1) > 1:
            return self._shared_stream(node)
        return self._exec_node(node)

    def _exec_node(self, node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        h = getattr(self, "_exec_" + type(node).__name__, None)
        if h is None:
            raise NotImplementedError(f"executor for {type(node).__name__}")
        it = h(node)
        from ..analysis import plan_sanitizer
        it = plan_sanitizer.wrap_node(node, it)
        if self.stats is not None:
            it = self.stats.instrument(node, it)
        return it

    def _shared_stream(self, node) -> Iterator[MicroPartition]:
        """A subplan with multiple consumers executes ONCE into a
        breaker-budget buffer; every consumer streams the buffered
        partitions (reference: common-subplan reuse in the physical
        planner). Thread-safe: the push executor's consumer stages race
        here — the first builds, the rest wait on its completion."""
        import threading
        from . import memory
        with self._shared_lock:
            ent = self._shared.get(id(node))
            build = ent is None
            if build:
                ent = {"done": threading.Event(), "buf": None, "err": None,
                       "remaining": getattr(node, "shared_consumers", 2)}
                self._shared[id(node)] = ent
        if build:
            try:
                ent["buf"] = memory.materialize(
                    self._exec_node(node), memory.breaker_budget_bytes())
            except BaseException as exc:  # noqa: BLE001
                ent["err"] = exc
                raise
            finally:
                ent["done"].set()
        else:
            ent["done"].wait()
            if ent["err"] is not None:
                raise ent["err"]

        def serve():
            # each consumer decrements on completion (or abandonment —
            # GeneratorExit lands in the finally); the LAST one frees the
            # buffer's memory and spill files mid-query instead of at GC
            try:
                yield from iter(ent["buf"])
            finally:
                with self._shared_lock:
                    ent["remaining"] -= 1
                    last = ent["remaining"] <= 0
                    if last:
                        self._shared.pop(id(node), None)
                if last:
                    ent["buf"].close()
        return serve()

    # sources ----------------------------------------------------------
    def _morselize(self, stream: Iterator) -> Iterator:
        """Re-chunk a partition stream to ``default_morsel_size`` rows
        (the reference's dispatcher-side morsel re-chunking,
        ``src/daft-local-execution/src/buffer.rs``): oversized source
        partitions split so downstream operators pipeline at morsel
        granularity. Observed sizes land in the per-op trace stats."""
        morsel = int(self.cfg.default_morsel_size or 0)
        if morsel <= 0:
            yield from stream
            return
        for p in stream:
            n = len(p)
            if n <= morsel + morsel // 2:
                yield p
                continue
            rb = p.combined()
            for start in range(0, n, morsel):
                yield MicroPartition.from_recordbatch(
                    rb.slice(start, min(start + morsel, n)))

    def _exec_ScanSource(self, node: pp.ScanSource):
        from ..io import read_planner as rp
        if not node.tasks:
            yield MicroPartition.empty(node.schema())
            return
        selected, prog = self._scan_select(node)
        if selected is not None:
            yield from self._morselize(selected)
            return
        if node.tasks[0].pushdowns.filters is not None:
            yield from _tally_host_select(node.tasks,
                                          self._scan_host(node, rp), prog)
            return
        yield from self._scan_host(node, rp)

    def _scan_host(self, node: pp.ScanSource, rp):
        prefetch = rp.scan_prefetch_tasks()
        if prefetch <= 0 or rp.scan_sequential_fallback():
            # pre-fast-path behavior: whole-task loads on the pool (kept
            # verbatim as the DAFT_TPU_CHAOS_SERIALIZE / active-fault-plan
            # degradation so PR 2's replay contract stays bit-identical)
            def run(t):
                est = t.size_bytes() or 0
                self.mem.acquire(est)
                try:
                    return _load_with_retry(t)
                finally:
                    self.mem.release(est)
            yield from self._morselize(_ordered_parallel(iter(node.tasks),
                                                         run))
            return
        yield from self._morselize(self._prefetch_scan(node.tasks, prefetch))

    def _prefetch_scan(self, tasks, window: int):
        """Prefetch-pipelined scan source: up to ``window`` upcoming
        ScanTasks resolve on the IO pool AHEAD of the one the consumer is
        draining, each admission-gated by the memory manager (prefetched
        bytes can't blow DAFT_TPU_MEMORY_LIMIT), and each task's batches
        stream out as its files decode — the first morsel lands at
        first-file completion, not task completion. Output stays in task
        order. Wall vs serial-equivalent time feeds the ``io`` stats
        block."""
        import collections
        import queue as _queue
        import threading
        import time as _time

        from ..io import read_planner as rp

        pool = _scan_pool()
        t_span0 = _time.perf_counter()

        class _Stream:
            """Per-task batch queue; ``dead`` makes an abandoned consumer
            (early limit, error upstream) stop the producer. UNBOUNDED on
            purpose: memory admission is the loading gate (as in the
            pre-PR path, which also released admission on load
            completion). A bounded queue would let a producer block on
            put() while HOLDING admission that the FIFO-head task's
            producer is waiting for — a deadlock the consumer, stuck on
            the head task's queue, could never break."""

            def __init__(self):
                self.q = _queue.Queue()
                self.dead = threading.Event()

            def put(self, item):
                if self.dead.is_set():
                    raise _ScanAbandoned()
                self.q.put(item)

        class _ScanAbandoned(Exception):
            pass

        def produce(task, st: _Stream, task_idx: int):
            if st.dead.is_set():  # consumer gone before we even started
                return
            from .. import tracing
            from . import governor
            t0 = _time.perf_counter()
            est = task.size_bytes() or 0
            # governor backpressure BEFORE admission: a bounded throttle
            # (never a gate — it times out) that slows the producers down
            # while process RSS sits above the high watermark, so
            # prefetched bytes stop arriving before the OS OOMs
            governor.throttle("scan_prefetch")
            # producer span keyed by the deterministic task index; the
            # producer thread carries the query's span context through
            # the same attribution the io counters ride
            sp = tracing.span("scan:prefetch", key=f"scan.t{task_idx}",
                              attrs={"est_bytes": est}, lane="scan")
            self.mem.acquire(est)
            try:
                with sp:
                    if st.dead.is_set():
                        return
                    schema = task.materialized_schema()
                    produced = False
                    try:
                        for rb in _loaded_batches(task):
                            st.put(("batch",
                                    MicroPartition.from_recordbatch(
                                        rb.cast_to_schema(schema))))
                            produced = True
                    except OSError:
                        if produced:
                            raise  # can't re-stream mid-task: dup rows
                        _time.sleep(0.2)  # transient IO: one clean retry
                        for rb in _loaded_batches(task):
                            st.put(("batch",
                                    MicroPartition.from_recordbatch(
                                        rb.cast_to_schema(schema))))
                            produced = True
                    if not produced:
                        st.put(("batch", MicroPartition.empty(schema)))
                    st.put(("done", None))
            except _ScanAbandoned:
                pass
            except BaseException as exc:  # noqa: BLE001
                try:
                    st.put(("err", exc))
                except _ScanAbandoned:
                    pass
            finally:
                self.mem.release(est)
                rp.scan_count("scan_task_us",
                              (_time.perf_counter() - t0) * 1e6)

        inflight = collections.deque()
        it = iter(tasks)
        submitted = [0]

        def submit() -> bool:
            try:
                t = next(it)
            except StopIteration:
                return False
            st = _Stream()
            from .. import observability as obs
            pool.submit(obs.run_attributed, obs.submit_attribution("scan"),
                        produce, t, st, submitted[0])
            submitted[0] += 1
            inflight.append(st)
            rp.scan_count("prefetch_tasks")
            return True

        from . import governor

        def refill():
            # fill to the governor's CURRENT window (≤ the configured
            # one): under memory pressure in-flight prefetch narrows to
            # one task ahead, and widens back out once RSS recovers
            while len(inflight) < governor.prefetch_window(window) + 1:
                if not submit():
                    return

        refill()
        current = None
        try:
            while inflight:
                current = inflight.popleft()
                while True:
                    kind, val = current.q.get()
                    if kind == "batch":
                        yield val
                    elif kind == "err":
                        raise val
                    else:
                        break
                current = None
                refill()
        finally:
            # an abandoned consumer (early limit, downstream error) must
            # unblock every producer — including the one being drained
            if current is not None:
                current.dead.set()
            for st in inflight:
                st.dead.set()
            rp.scan_count("scan_span_us",
                          (_time.perf_counter() - t_span0) * 1e6)

    def _exec_InMemorySource(self, node: pp.InMemorySource):
        if not node.partitions:
            yield MicroPartition.empty(node.schema())
            return
        yield from iter(node.partitions)

    def _exec_StageInput(self, node: pp.StageInput):
        # binding: a materialized partition list OR a lazy _ParallelFetch
        # (distributed reduce input — per-source tables stream in as the
        # bounded fetch pool completes them; emptiness is only known after
        # draining it)
        parts = self.stage_inputs.get(node.stage_id)
        if parts is None:
            yield MicroPartition.empty(node.schema())
            return
        got = False
        for p in parts:
            got = True
            yield p
        if not got:
            yield MicroPartition.empty(node.schema())

    # pipelined maps ---------------------------------------------------
    def _exec_Project(self, node: pp.Project):
        src = node.children[0]
        if isinstance(src, pp.ScanSource) and src.tasks \
                and getattr(src, "shared_consumers", 1) <= 1:
            # a projection directly over a filtered scan rides the scan's
            # selection program
            selected, _ = self._scan_select(src, node.exprs, node.schema())
            if selected is not None:
                yield from self._morselize(selected)
                return
        child = self._exec(node.children[0])
        yield from _ordered_parallel(
            child, lambda p: p.eval_expression_list(node.exprs))

    def _exec_UDFProject(self, node: pp.UDFProject):
        child = self._exec(node.children[0])
        width = node.concurrency or None
        yield from _ordered_parallel(
            child, lambda p: p.eval_expression_list(node.exprs), width=width)

    def _exec_Filter(self, node: pp.Filter):
        child = self._exec(node.children[0])
        yield from _ordered_parallel(child, lambda p: p.filter(node.predicate))

    def _exec_Explode(self, node: pp.Explode):
        child = self._exec(node.children[0])
        yield from _ordered_parallel(child, lambda p: p.explode(node.exprs))

    def _exec_Unpivot(self, node: pp.Unpivot):
        child = self._exec(node.children[0])
        yield from _ordered_parallel(
            child, lambda p: p.unpivot(node.ids, node.values,
                                       node.variable_name, node.value_name))

    def _exec_Sample(self, node: pp.Sample):
        child = self._exec(node.children[0])
        yield from _ordered_parallel(
            child, lambda p: p.sample(fraction=node.fraction, size=None,
                                      with_replacement=node.with_replacement,
                                      seed=node.seed)
            if node.fraction is not None else p.head(node.size))

    def _exec_MonotonicallyIncreasingId(self, node):
        child = self._exec(node.children[0])
        for i, p in enumerate(child):
            yield p.add_monotonically_increasing_id(i, node.column_name)

    def _exec_Limit(self, node: pp.Limit):
        remaining = node.limit
        to_skip = node.offset
        for p in self._exec(node.children[0]):
            n = len(p)
            if to_skip:
                if n <= to_skip:
                    to_skip -= n
                    continue
                p = MicroPartition.from_recordbatch(
                    p.combined().slice(to_skip, n))
                to_skip = 0
            if remaining <= 0:
                break
            if len(p) > remaining:
                p = p.head(remaining)
            remaining -= len(p)
            yield p
            if remaining <= 0:
                break

    def _exec_Concat(self, node: pp.Concat):
        yield from self._exec(node.children[0])
        yield from self._exec(node.children[1])

    # aggregation ------------------------------------------------------
    def _streamed_agg_input(self, node) -> bool:
        """True when this Aggregate's child is a StageInput bound to a
        STREAMING parallel fetch: the binding yields one morsel per map
        source (not hash-disjoint!), so per-morsel aggregation would
        duplicate groups — the streaming merge-agg below re-merges
        instead. ``worker._stream_safe`` only enables streaming when the
        aggs are self-merges, so the merge table always exists here."""
        ch = node.children[0] if node.children else None
        if not isinstance(ch, pp.StageInput):
            return False
        return getattr(self.stage_inputs.get(ch.stage_id),
                       "streaming", False)

    def _exec_Aggregate(self, node: pp.Aggregate):
        if self._streamed_agg_input(node):
            yield from self._merge_agg_stream(node,
                                              self._exec(node.children[0]))
            return
        child = self._exec(node.children[0])
        yield from _ordered_parallel(
            child, lambda p: p.agg(node.aggs, node.group_by)
            .cast_to_schema(node.schema()))

    _MERGE_AGG_REAGG_ROWS = 1 << 17

    def _merge_agg_stream(self, node: pp.Aggregate, stream):
        """Streaming merge over a multi-morsel pipelined-fetch input:
        aggregate each arriving source morsel and LSM-merge the states
        with the self-merge table (``aggs.merge_exprs_for``) — reduce
        compute overlaps the remaining fetches instead of waiting on the
        full concat barrier, and emits ONE state morsel like the barrier
        path did."""
        from ..aggs import merge_exprs_for
        merge_aggs = merge_exprs_for(node.aggs, alias_to="out")
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

        for mp in stream:
            self._poll_cancel()
            buf.append(mp)
            rows += len(mp)
            if rows >= max(self._MERGE_AGG_REAGG_ROWS,
                           0 if state is None else len(state)):
                merge()
        merge()
        if state is not None:
            yield state
        else:
            yield MicroPartition.empty(node.schema())

    def _exec_DeviceFragmentAgg(self, node: pp.DeviceFragmentAgg):
        from ..aggs import split_agg_expr
        from ..device import fragment, runtime as drt
        specs = [split_agg_expr(a) for a in node.aggs]
        child_exprs = [(c if c is not None else _lit_true()).alias(f"__v{i}__")
                       for i, (op, c, nm, pr) in enumerate(specs)]
        ops = tuple(s[0] for s in specs)
        agg_names = [s[2] for s in specs]
        agg_cols = [col(nm) for nm in agg_names]

        def host_agg(rb: RecordBatch) -> MicroPartition:
            if node.predicate is not None:
                rb = rb.filter(node.predicate)
            return MicroPartition.from_recordbatch(
                rb.agg(node.aggs, node.group_by).cast_to_schema(node.schema()))

        def morsel_gate(rb: RecordBatch, window: int = 0):
            """Cost-gate one morsel: the fused program when the device
            should take it, None → host. No device work happens here.
            ``window`` ≥ 2 prices the transfer at the pipeline's
            steady-state overlap instead of the full serial chain."""
            from ..device import costmodel
            if not (drt.device_enabled()
                    and len(rb) >= max(drt._min_rows(), 1)):
                return None
            prog = fragment.get_fused_agg(node.group_by, child_exprs, ops,
                                          node.predicate, rb.schema)
            if prog is None:
                return None
            # in-memory batch: the upload is one-shot, it must beat the
            # host outright (no HBM-cache identity to invest in)
            from ..device import column as dcol
            packed_out = fragment.packed_bytes_per_group(
                len(node.group_by), len(ops)) * fragment._OUT_CAP0
            if not costmodel.agg_upload_wins(
                    dcol.encoded_nbytes(rb, prog.compiled.needs_cols),
                    packed_out, cacheable=False,
                    host_bytes=drt._batch_cols_nbytes(
                        rb, prog.compiled.needs_cols),
                    window=window):
                return None
            return prog

        def submit_morsel(prog, rb: RecordBatch):
            """Encode + async dispatch (no blocking fetch); None → the
            device declined at submit (pyobject / lowering failure)."""
            try:
                return fragment.submit_fused_agg(
                    prog, rb, node.group_by, agg_cols, node.schema())
            except Exception as exc:
                # lowering/compile errors propagate; only device resource
                # exhaustion degrades to the host, counted
                drt.device_failed("executor.fused_agg.submit", exc)
                return None

        def drain_device_agg(tok) -> Optional[MicroPartition]:
            try:
                out = fragment.drain_fused_agg_table(tok)
            except Exception as exc:
                drt.device_failed("executor.fused_agg.drain", exc)
                return None
            if out is None:
                return None
            return MicroPartition.from_recordbatch(
                out.cast_to_schema(node.schema()))

        def device_agg(rb: RecordBatch) -> Optional[MicroPartition]:
            prog = morsel_gate(rb)
            if prog is None:
                return None
            tok = submit_morsel(prog, rb)
            return None if tok is None else drain_device_agg(tok)

        src = node.children[0]
        if isinstance(src, pp.ScanSource) and src.tasks \
                and drt.device_enabled() \
                and _fragment_groups_affordable(node, src):
            # task-level path: consult the HBM column cache per scan task —
            # a hit runs the fused program on device-resident columns with
            # zero file IO and zero host→device transfer. All tasks' packed
            # results come back in ONE device→host transfer (the link is
            # RTT-bound, so per-task gets would serialize ~40 ms each).
            prog = fragment.get_fused_agg(node.group_by, child_exprs, ops,
                                          node.predicate, src.schema())
            if prog is not None:
                yield from self._fragment_scan_tasks(
                    node, prog, src, agg_cols, host_agg)
                return

        child = self._exec(node.children[0])
        from ..device import pipeline as dpipe
        window = dpipe.inflight_window()
        if window > 0 and drt.device_enabled():
            # round 17: async pipeline — morsel N+1's encode+upload runs
            # on the submit pool while morsel N computes on device and
            # morsel N−1 downloads/decodes here
            yield from self._pipelined_fragment_morsels(
                child, morsel_gate, submit_morsel, drain_device_agg,
                host_agg, window)
            return

        # synchronous per-morsel chain, kept verbatim as the
        # DAFT_TPU_CHAOS_SERIALIZE / active-fault-plan degradation so
        # chaos replay stays bit-identical
        def run(p: MicroPartition) -> MicroPartition:
            rb = p.combined()
            out = device_agg(rb)
            return out if out is not None else host_agg(rb)

        yield from _ordered_parallel(child, run)

    def _pipelined_fragment_morsels(self, child, morsel_gate,
                                    submit_morsel, drain_device_agg,
                                    host_agg, window: int):
        """Bounded-window async device pipeline over the morsel stream
        (device/pipeline.py). Each device morsel's slot admits its
        encoded host+HBM footprint on submit (before the dispatch) and
        releases on drain; host-routed morsels bypass the window so a
        host-heavy stream keeps full pool parallelism. Ordering is
        preserved."""
        import time as _time

        from ..device import column as dcol, pipeline as dpipe

        def submit(p, seq, gate):
            rb = p.combined()
            prog = morsel_gate(rb, window=window)
            if prog is None:
                return host_agg(rb)
            est = dcol.encoded_nbytes(rb, prog.compiled.needs_cols)
            slot = dpipe.acquire_slot(gate, seq, self.mem, est)
            try:
                t0 = _time.perf_counter()
                with dpipe.upload_span(seq, window):
                    tok = submit_morsel(prog, rb)
                sub_s = _time.perf_counter() - t0
            except BaseException:
                dpipe.release_slot(slot)
                raise
            if tok is None:
                dpipe.release_slot(slot)
                return host_agg(rb)
            return dpipe.InflightItem(slot, (tok, rb), sub_s=sub_s,
                                      t_dispatched_us=dpipe.now_us())

        def drain(ret, seq):
            if not isinstance(ret, dpipe.InflightItem):
                return ret  # host result, already computed on the pool
            tok, rb = ret.token
            dpipe.note_compute_span(seq, window, ret.t_dispatched_us)
            with dpipe.download_span(seq, window):
                out = drain_device_agg(tok)
            return out if out is not None else host_agg(rb)

        yield from dpipe.run_pipelined(child, submit, drain,
                                       window=window,
                                       poll=self._poll_cancel)

    def _fragment_scan_tasks(self, node, prog, src, agg_cols, host_agg):
        """The fused aggregate over a scan's tasks, a window at a time
        (:meth:`_scan_windows`): each window's programs are dispatched
        together and ALL their packed partials fetched in one transfer."""
        from ..device import costmodel, fragment, runtime as drt
        needs = prog.compiled.needs_cols
        packed_out = fragment.packed_bytes_per_group(
            prog.nk, len(prog.ops)) * fragment._OUT_CAP0

        def upload_wins(rb, col_bytes, cacheable, n_sharing, pwin):
            # the packed fetch's round trips amortize over the tasks that
            # actually SHARE the transfer: committed cache hits + gate
            # candidates (r4 advisor: dividing by the whole window length
            # under-charged device tasks in mixed windows where forced-host
            # tasks never join the fetch). Still optimistic by candidates
            # the gate itself rejects — the safe direction, since fewer
            # sharers only makes the gate stricter.
            return costmodel.agg_upload_wins(
                col_bytes, packed_out, cacheable=cacheable,
                round_trips=2.0 / max(1, n_sharing),
                host_bytes=drt._batch_cols_nbytes(rb, needs),
                # overlap pricing when the windows really pipeline
                window=pwin)

        def submit(tables, at):
            return fragment.submit_fused_agg_tables(
                prog, tables, src.schema(), node.group_by, agg_cols,
                node.schema(), at)

        yield from self._scan_windows(
            src.tasks, needs,
            upload_wins=upload_wins, submit=submit,
            drain=fragment.drain_fused_agg_tables,
            host=lambda rb, i, reread: host_agg(rb),
            wrap=lambda batch: MicroPartition.from_recordbatch(
                batch.cast_to_schema(node.schema())))

    def _scan_windows(self, tasks, needs_cols, upload_wins, submit, drain,
                      host, wrap, wanted=None, pristine=None):
        """Windowed streaming over scan tasks, shared by every program
        that runs over a scan's encoded tables (the fused aggregate,
        :meth:`_fragment_scan_tasks`; the selection, :meth:`_scan_select`):
        resolve each task of a window to an encoded DeviceTable (HBM cache
        hit on the chip that holds it, or load + gate + encode + insert)
        or leave it to the host, hand the window's tables to ``submit``
        (one program a table, no fetch), and ``drain`` them with ONE
        transfer a window. The window bounds host RAM and non-cached HBM
        residency like the morsel pipeline's in-flight limit.

        ``tasks``: what is loaded, encoded and cached under its
        fingerprint. ``wanted(i, dt)``: may the device take task ``i``'s
        table (``dt`` its resident table, None on a miss, asked before
        anything is loaded); default: always. ``upload_wins(rb, col_bytes,
        cacheable, n_sharing, pwin)``: the consumer's price of a miss.
        ``submit(tables, at)`` -> token, ``drain(token)`` ->
        ``fragment.DecodedRun`` s. ``pristine``: the tasks a table the
        device does not answer is re-read from (never decoded back from
        the lossy device encoding); default: ``tasks``. ``host(rb, i,
        reread)``: task ``i``'s result on the host, from ``rb``: its
        pristine task's rows (``reread``), or the batch loaded from
        ``tasks[i]`` for the gate, which declined it; ``wrap(batch)``: a
        decoded run's partition."""
        import itertools
        from .. import tracing
        from ..device import cache as dcache, column as dcol, costmodel
        from ..device import runtime as drt

        n_tasks = len(tasks)
        pristine = tasks if pristine is None else pristine
        # the chips the tables are spread over; with one, nothing is
        # placed (``chip`` None: the default device, as ever)
        from ..parallel import mesh as pmesh
        n_chips = max(len(pmesh.scan_devices()), 1)

        def chip_for(i, fp):
            """A task's chip: where the HBM cache already holds planes of
            it, else its index in the scan modulo the chips, so that a
            scan's tables are spread evenly and a repeated scan finds
            each one where the first put it."""
            if n_chips == 1:
                return None
            home = dcache.get_cache().home(fp) if fp is not None else None
            return i % n_chips if home is None else home

        def load(t) -> RecordBatch:
            est = t.size_bytes() or 0
            self.mem.acquire(est)
            try:
                mp = _load_with_retry(t)
                with tracing.span("scan:load", lane="scan",
                                  attrs={"step": "combine"}):
                    return mp.combined()
            finally:
                self.mem.release(est)

        def classify(it):
            """Phase A: cache hits are committed device participants;
            too-small / pyobject batches are forced host; the rest are
            candidates for the cost gate (phase B). Every task is
            tallied once by where its table came from, here or in the
            gate (``costmodel.scan_table_counts``)."""
            i, t = it
            fp = dcache.task_fingerprint(t)
            dt = dcache.get_cache().get_table(fp, needs_cols) \
                if fp is not None else None
            if wanted is not None and not wanted(i, dt):
                costmodel.count_scan_table("host")
                # here, beside the window's other tasks, not one by one
                # in the drain
                return ("read", load(pristine[i]), i)
            if dt is not None:
                costmodel.count_scan_table("from_cache", dt.chip,
                                           dt.row_count)
                return ("dev", dt, i)
            rb = load(t)
            if len(rb) < max(drt._min_rows(), 1):
                costmodel.count_scan_table("host")
                return ("host", rb, i)
            for nm in needs_cols:
                if rb.get_column(nm).is_pyobject():
                    costmodel.count_scan_table("host")
                    return ("host", rb, i)
            return ("cand", rb, i, fp, chip_for(i, fp))

        def gate(cand, n_sharing):
            """Phase B: measured cost gate. A cacheable upload is an
            investment the HBM cache repays on every later scan of the
            same task — but only if the whole scan's working set actually
            FITS the budget (otherwise LRU thrash re-pays the upload every
            query and put_table would refuse oversized tables anyway).
            The budget is a chip's, so the test is the fullest chip's:
            its share of the tasks against one budget."""
            _, rb, i, fp, chip = cand
            col_bytes = dcol.encoded_nbytes(rb, needs_cols)
            fits = col_bytes * -(-max(n_tasks, 1) // n_chips) \
                <= dcache._budget()
            # (pwin is assigned before any window resolves)
            if not upload_wins(rb, col_bytes, fp is not None and fits,
                               n_sharing, pwin):
                costmodel.count_scan_table("host")
                return ("host", rb, i)
            try:
                dt = dcol.encode_batch(rb, needs_cols, chip=chip)
            except (ValueError, TypeError):
                costmodel.count_scan_table("host")
                return ("host", rb, i)
            if fp is not None and fits:
                # only cache working sets that FIT the budget: caching a
                # slice of an oversized scan just LRU-evicts entries other
                # queries still repay (SF10 thrash, r4) — the upload then
                # streams through as a one-shot morsel instead
                dcache.get_cache().put_table(fp, dt)
            costmodel.count_scan_table("encoded", chip, dt.row_count)
            return ("dev", dt, i)

        width = max((os.cpu_count() or 4), 4) * 2
        from ..device import pipeline as dpipe
        pwin = dpipe.inflight_window()
        if pwin > 0:
            # the async pipeline needs windows to overlap: one giant
            # window over a small scan starves it (fetches stay batched
            # per window either way). Aim for window+1 windows — enough
            # to fill the in-flight ladder without multiplying the
            # per-window fetch round-trips an RTT-bound query pays
            width = max(1, min(width, -(-n_tasks // max(pwin + 1, 1))))
        # over several chips a window is launched round by round (a table
        # of every chip, ``fragment.submit_fused_agg_tables``): a width
        # that is a multiple of the chips leaves no round ragged
        width = -(-width // n_chips) * n_chips

        def windows():
            it = iter(enumerate(tasks))
            while True:
                w = list(itertools.islice(it, width))
                if not w:
                    return
                yield w

        def resolve(window_tasks):
            classified = list(_ordered_parallel(iter(window_tasks),
                                                classify))
            n_sharing = sum(1 for c in classified
                            if c[0] in ("dev", "cand"))
            gated = _ordered_parallel(
                iter([c for c in classified if c[0] == "cand"]),
                lambda c: gate(c, n_sharing))
            gated_it = iter(list(gated))
            costmodel.note_resident_chips(n_chips)
            return [c if c[0] != "cand" else next(gated_it)
                    for c in classified]

        def dev_tables(resolved):
            """The window's device tables and each one's place among its
            tasks: the drain decodes neighbours into one batch, and a
            task the host answers between two of them keeps them apart."""
            at = [j for j, r in enumerate(resolved) if r[0] == "dev"]
            return [resolved[j][1] for j in at], at

        def emit(resolved, runs):
            """The window's results in task order: one partition for each
            run of device tables that decoded together
            (``fragment.DecodedRun``), a host result for every other task."""
            runs, inside = iter(runs), 0
            for kind, val, i in resolved:
                if kind != "dev":
                    yield host(val, i, kind == "read")
                elif inside:    # its rows left with its run's batch
                    inside -= 1
                else:
                    n, batch = next(runs)
                    inside = n - 1
                    if batch is None:  # device failure → pristine host re-read
                        yield host(load(pristine[i]), i, True)
                    else:
                        yield wrap(batch)

        if pwin <= 0:
            # synchronous window loop, kept verbatim as the chaos /
            # fault-plan degradation: window N+1's loads wait for
            # window N's fetch, exactly the pre-pipeline event order
            for w in windows():
                resolved = resolve(w)
                tables, at = dev_tables(resolved)
                yield from emit(resolved, drain(submit(tables, at)))
            return

        # round 17 async pipeline over windows: window N+1's classify /
        # load / encode / dispatch runs on the submit pool while window
        # N's packed results download and decode here. Each in-flight
        # window's slot admits the encoded HBM footprint it keeps
        # resident until its drain (the transient load bytes are
        # separately admitted inside load()).
        import time as _time

        def p_submit(window_tasks, seq, wgate):
            # device:submit covers the whole submit stage: resolve (load,
            # encode, put) and the dispatch; the wait for a slot between
            # them is the window's, and inside it too
            with dpipe.upload_span(seq, pwin):
                t0 = _time.perf_counter()
                resolved = resolve(window_tasks)
                tables, at = dev_tables(resolved)
                est = sum(
                    int(c.data.nbytes) + int(c.validity.nbytes)
                    for dt in tables for c in dt.columns.values())
                pre_s = _time.perf_counter() - t0
                slot = dpipe.acquire_slot(wgate, seq, self.mem, est)
                try:
                    t1 = _time.perf_counter()
                    tok = submit(tables, at)
                    sub_s = pre_s + (_time.perf_counter() - t1)
                except BaseException:
                    dpipe.release_slot(slot)
                    raise
                return dpipe.InflightItem(slot, (resolved, tok),
                                          sub_s=sub_s,
                                          t_dispatched_us=dpipe.now_us())

        def p_drain(ret, seq):
            resolved, tok = ret.token
            dpipe.note_compute_span(seq, pwin, ret.t_dispatched_us)
            with dpipe.download_span(seq, pwin):
                runs = drain(tok)
            # release BEFORE emitting: a device-failure fallback re-reads
            # its task through load()'s own admission, which must not
            # wait on this very slot's bytes (release_slot is idempotent
            # — the driver's release after drain becomes a no-op)
            dpipe.release_slot(ret.slot)
            return list(emit(resolved, runs))

        for outs in dpipe.run_pipelined(windows(), p_submit, p_drain,
                                        window=pwin, width=pwin + 1,
                                        poll=self._poll_cancel):
            yield from outs

    def _scan_select(self, src: pp.ScanSource, exprs=None, out_schema=None):
        """A filtered Parquet scan that ends in rows, its filter (and the
        projection directly above it, ``exprs``) run as the chain program
        over the scan's encoded tables: resolved table by table by the
        resolver the fused aggregate uses (:meth:`_scan_windows`), so a
        table whose columns lie in the HBM column cache is neither read
        nor decoded nor filtered on the host. The cache holds the tasks'
        UNFILTERED columns (``ScanTask.unfiltered``), which every filter
        over the same files shares. Returns the stream of survivors as
        ordinary partitions and the program, or None for the stream where
        the reader is to scan: with the program where it could have run
        (the reader's scan then teaches it the predicate's share of
        survivors), with None where the scan is not of that kind.

        ``DAFT_TPU_FUSION``: ``0`` keeps every scan on the host, ``1``
        sends every table the program can take to the device, ``auto``
        asks ``costmodel.select_wins`` table by table. A table the device
        does not answer (priced out, more survivors than the ladder's
        ceiling holds, a failed dispatch) is read from its pristine task
        by the reader, filter included."""
        from ..device import column as dcol, costmodel, fragment
        from ..device import runtime as drt
        from ..physical import fusion as pfusion
        mode = pfusion.fusion_mode(self.cfg)
        if mode == "0" or not drt.device_enabled():
            return None, None
        pd = src.tasks[0].pushdowns
        if pd.filters is None or pd.limit is not None:
            return None, None
        for t in src.tasks:
            # (a scan's tasks share one Pushdowns object)
            if t.file_format != "parquet" or t.generator is not None \
                    or t.partition_values or t.pushdowns is not pd:
                return None, None
        in_schema = src.tasks[0].materialized_schema()
        if exprs is None:
            exprs = [col(c) for c in src.schema().column_names]
            out_schema = src.schema()
        prog = _select_program(exprs, pd.filters, in_schema, out_schema)
        if prog is None:
            return None, None
        needs = prog.compiled.needs_cols
        words = prog.out_words
        forced = mode == "1"

        def slots(i, rows):
            """The bucket the expected survivors fill: by the share this
            predicate last kept, else the footer's estimate (which then
            stands as the program's first bet, rung included); None where
            nothing is known."""
            if prog.survivors_hint is None:
                from ..io import readers
                prog.survivors_hint = readers.footer_selectivity(
                    src.tasks[i])
            share = prog.survivors_hint
            if share is None:
                return None
            return min(dcol.bucket_capacity(
                max(int(share * rows), fragment._OUT_CAP0)),
                dcol.bucket_capacity(max(rows, 1)))

        def wanted(i, dt, resident=False):
            rows = dt.row_count if dt is not None \
                else (src.tasks[i].rows_scanned() or 0)
            if rows < max(drt._min_rows(), 1):
                return False
            if forced:
                return True
            if dt is not None or resident:
                return costmodel.select_wins(rows, len(needs),
                                             slots(i, rows), words, True)
            up = sum(np.dtype(d).itemsize + 1
                     for d in prog.in_np_dtypes.values()) \
                * dcol.bucket_capacity(rows)
            # priced as cacheable; a task that is not (no fingerprint, a
            # scan over the budget) is declined in ``upload_wins``
            return costmodel.select_wins(rows, len(needs), slots(i, rows),
                                         words, False, bytes_up=up,
                                         cacheable=True)

        def max_w(dt):
            if forced:
                return dt.capacity
            most = costmodel.select_max_rows(dt.row_count, len(needs), words)
            return min(max(1 << max(most.bit_length() - 1, 0),
                           fragment._OUT_CAP0), dt.capacity)

        def host(rb, i, reread):
            rows_in = src.tasks[i].rows_scanned()
            if not reread:  # loaded whole for the gate, which declined it
                rows_in = len(rb)
                rb = rb.filter(pd.filters)
            fragment.note_select(prog, "host", 1,
                                 len(rb) if rows_in is None else rows_in,
                                 len(rb))
            return MicroPartition.from_recordbatch(
                rb.eval_expression_list(exprs).cast_to_schema(out_schema))

        if not any(wanted(i, None, resident=True)
                   for i in range(len(src.tasks))):
            # no table of this scan is worth the device even resident: the
            # reader's streaming scan, not a window of fallbacks
            return None, prog
        return self._scan_windows(
            [t.unfiltered() for t in src.tasks], needs, wanted=wanted,
            pristine=src.tasks,
            upload_wins=lambda rb, nbytes, cacheable, n, pwin:
                forced or cacheable,
            submit=lambda tables, at: fragment.submit_select_tables(
                prog, tables, exprs, out_schema, at, max_w),
            drain=fragment.drain_select_tables, host=host,
            wrap=lambda batch: MicroPartition.from_recordbatch(
                batch.cast_to_schema(out_schema))), prog

    # fused regions (round 21 whole-query compilation) -----------------
    def _exec_FusedRegion(self, node: pp.FusedRegion):
        """Execute a planner-proposed fusion region: the region's whole
        operator chain runs as ONE device program per morsel (submit =
        encode+dispatch, drain = one packed fetch), riding the r17 async
        pipeline. Admission is priced per morsel by ``fusion_wins``
        (``DAFT_TPU_FUSION=1`` force-admits); every decline — cost gate,
        pyobject/encode failure, overflow past the ladder ceiling — runs
        the equivalent host chain per morsel, and a region whose program
        does not lower at all runs the untouched ``fallback`` subtree."""
        from ..device import runtime as drt
        from ..physical import fusion as pfusion
        mode = pfusion.fusion_mode(self.cfg)
        if mode == "0" or not drt.device_enabled():
            yield from self._exec(node.fallback)
            return
        if node.shape == "join_agg":
            yield from self._exec_region_join_agg(node, mode)
            return
        yield from self._exec_region_chain(node, mode)

    def _exec_region_chain(self, node: pp.FusedRegion, mode: str):
        """chain / topk shapes: predicate + projection (+ in-program
        argsort for topk) + compaction in one dispatch, packed survivors
        back in one transfer."""
        from ..device import column as dcol, costmodel, fragment
        from ..device import pipeline as dpipe, runtime as drt
        topk = node.shape == "topk"
        prog = fragment.get_fused_region(
            node.exprs, node.predicate, node.source.schema(),
            sort_by=node.sort_by, descending=node.descending,
            nulls_first=node.nulls_first, limit=node.limit,
            fused_ops=node.fused_ops)
        if prog is None:
            yield from self._exec(node.fallback)
            return
        n_ops = max(len(node.fused_ops) - 1, 2)

        def host_run(rb: RecordBatch) -> MicroPartition:
            if node.predicate is not None:
                rb = rb.filter(node.predicate)
            rb = rb.eval_expression_list(node.exprs) \
                .cast_to_schema(node.schema())
            if topk:
                # per-morsel top-k in the OUTPUT namespace (the TopN
                # fallback's sort keys live there); merged below
                rb = rb.top_n(node.fallback.sort_by, node.limit,
                              node.descending, node.nulls_first)
            return MicroPartition.from_recordbatch(rb)

        def gate(rb: RecordBatch, window: int = 0) -> bool:
            if len(rb) < max(drt._min_rows(), 1):
                return False
            if mode == "1":
                return True
            est_w = dcol.bucket_capacity(max(node.limit or 0, 1)) if topk \
                else dcol.bucket_capacity(max(len(rb), 1))
            return costmodel.fusion_wins(
                node.shape, len(rb),
                dcol.encoded_nbytes(rb, prog.compiled.needs_cols),
                prog.out_words * 8 * est_w, n_ops,
                host_bytes=drt._batch_cols_nbytes(
                    rb, prog.compiled.needs_cols),
                window=window)

        def device_submit(rb: RecordBatch):
            try:
                return fragment.submit_region(prog, rb, node.exprs,
                                              node.schema())
            except Exception as exc:
                drt.device_failed("executor.region.submit", exc)
                return None

        def device_drain(tok) -> Optional[MicroPartition]:
            try:
                out = fragment.drain_region(tok)
            except Exception as exc:
                drt.device_failed("executor.region.drain", exc)
                return None
            if out is None:
                return None
            out = out.cast_to_schema(node.schema())
            return MicroPartition.from_recordbatch(out)

        def emit():
            child = self._exec(node.source)
            window = dpipe.inflight_window()
            if window > 0:
                def submit(p, seq, wgate):
                    import time as _time
                    rb = p.combined()
                    if not gate(rb, window=window):
                        return host_run(rb)
                    est = dcol.encoded_nbytes(rb, prog.compiled.needs_cols)
                    slot = dpipe.acquire_slot(wgate, seq, self.mem, est)
                    try:
                        t0 = _time.perf_counter()
                        with dpipe.upload_span(seq, window):
                            tok = device_submit(rb)
                        sub_s = _time.perf_counter() - t0
                    except BaseException:
                        dpipe.release_slot(slot)
                        raise
                    if tok is None:
                        dpipe.release_slot(slot)
                        return host_run(rb)
                    return dpipe.InflightItem(
                        slot, (tok, rb), sub_s=sub_s,
                        t_dispatched_us=dpipe.now_us())

                def drain(ret, seq):
                    if not isinstance(ret, dpipe.InflightItem):
                        return ret
                    tok, rb = ret.token
                    dpipe.note_compute_span(seq, window, ret.t_dispatched_us)
                    with dpipe.download_span(seq, window):
                        out = device_drain(tok)
                    return out if out is not None else host_run(rb)

                yield from dpipe.run_pipelined(child, submit, drain,
                                               window=window,
                                               poll=self._poll_cancel)
                return

            def run(p: MicroPartition) -> MicroPartition:
                rb = p.combined()
                if not gate(rb):
                    return host_run(rb)
                tok = device_submit(rb)
                out = device_drain(tok) if tok is not None else None
                return out if out is not None else host_run(rb)

            yield from _ordered_parallel(child, run)

        if not topk:
            yield from emit()
            return
        # topk tail: each morsel arrives already reduced to its own top-k
        # bucket; one final host merge produces the query's k rows
        tops = list(emit())
        if not tops:
            yield MicroPartition.from_recordbatch(
                RecordBatch.empty(node.schema()))
            return
        merged = tops[0].concat(tops[1:]) if len(tops) > 1 else tops[0]
        yield MicroPartition.from_recordbatch(
            merged.combined().top_n(node.fallback.sort_by, node.limit,
                                    node.descending, node.nulls_first))

    def _exec_region_join_agg(self, node: pp.FusedRegion, mode: str):
        """join_agg shape: the broadcast build side materializes once
        (host) and is encoded + key-sorted once on device; every probe
        morsel then joins, projects, and partially aggregates in ONE
        dispatch. Output is partial group blocks — the parent final
        Aggregate merges them."""
        from ..aggs import split_agg_expr
        from ..device import column as dcol, costmodel, fragment
        from ..device import pipeline as dpipe, runtime as drt
        specs = [split_agg_expr(a) for a in node.aggs]
        child_exprs = [(c if c is not None else _lit_true())
                       .alias(f"__v{i}__")
                       for i, (op, c, nm, pr) in enumerate(specs)]
        ops = tuple(s[0] for s in specs)
        agg_cols = [col(s[2]) for s in specs]
        post_pred = getattr(node, "post_predicate", None)
        lkey = node.left_on[0].name()
        rkey = node.right_on[0].name()
        prog = fragment.get_fused_join_agg(
            node.group_by, child_exprs, ops, node.predicate, post_pred,
            lkey, rkey, node.source.schema(), node.build.schema(),
            fused_ops=node.fused_ops)
        if prog is None:
            yield from self._exec(node.fallback)
            return
        build_rb = _gather_all(self._exec(node.build)).combined()
        build = fragment.prepare_region_build(prog, build_rb)
        if build is None:
            yield from self._exec(node.fallback)
            return
        n_ops = max(len(node.fused_ops), 3)
        nk, nv = len(node.group_by), len(ops)
        # adaptive group-bucket start: seed the next morsel's ladder from
        # the last drained group count (q3-style high-NDV keys would pay
        # one overflow re-dispatch per morsel otherwise)
        g_hint = [fragment._OUT_CAP0]

        def host_run(rb: RecordBatch) -> MicroPartition:
            if node.predicate is not None:
                rb = rb.filter(node.predicate)
            joined = rb.hash_join(build_rb, list(node.left_on),
                                  list(node.right_on), "inner")
            if post_pred is not None:
                joined = joined.filter(post_pred)
            return MicroPartition.from_recordbatch(
                joined.agg(list(node.aggs), list(node.group_by))
                .cast_to_schema(node.schema()))

        def gate(rb: RecordBatch, window: int = 0) -> bool:
            if len(rb) < max(drt._min_rows(), 1):
                return False
            if mode == "1":
                return True
            need = list(dict.fromkeys(
                [lkey] + list(prog.probe_needs)
                + list(prog.c_pred.needs_cols
                       if prog.c_pred is not None else ())))
            return costmodel.fusion_wins(
                "join_agg", len(rb), dcol.encoded_nbytes(rb, need),
                (1 + 2 * (nk + nv)) * 8
                * dcol.bucket_capacity(max(g_hint[0], 1)),
                n_ops, host_bytes=drt._batch_cols_nbytes(rb, need),
                window=window)

        def device_submit(rb: RecordBatch):
            try:
                return fragment.submit_join_agg(
                    prog, rb, build, node.group_by, agg_cols,
                    node.schema(), start_out_cap=g_hint[0])
            except Exception as exc:
                drt.device_failed("executor.join_agg.submit", exc)
                return None

        def device_drain(tok) -> Optional[MicroPartition]:
            try:
                res = fragment.drain_join_agg(tok)
            except Exception as exc:
                drt.device_failed("executor.join_agg.drain", exc)
                return None
            if res is None:
                return None
            out, g = res
            g_hint[0] = max(g, fragment._OUT_CAP0)
            return MicroPartition.from_recordbatch(
                out.cast_to_schema(node.schema()))

        child = self._exec(node.source)
        window = dpipe.inflight_window()
        if window > 0:
            def submit(p, seq, wgate):
                import time as _time
                rb = p.combined()
                if not gate(rb, window=window):
                    return host_run(rb)
                need = list(dict.fromkeys([lkey] + list(prog.probe_needs)))
                est = dcol.encoded_nbytes(rb, need)
                slot = dpipe.acquire_slot(wgate, seq, self.mem, est)
                try:
                    t0 = _time.perf_counter()
                    with dpipe.upload_span(seq, window):
                        tok = device_submit(rb)
                    sub_s = _time.perf_counter() - t0
                except BaseException:
                    dpipe.release_slot(slot)
                    raise
                if tok is None:
                    dpipe.release_slot(slot)
                    return host_run(rb)
                return dpipe.InflightItem(slot, (tok, rb), sub_s=sub_s,
                                          t_dispatched_us=dpipe.now_us())

            def drain(ret, seq):
                if not isinstance(ret, dpipe.InflightItem):
                    return ret
                tok, rb = ret.token
                dpipe.note_compute_span(seq, window, ret.t_dispatched_us)
                with dpipe.download_span(seq, window):
                    out = device_drain(tok)
                return out if out is not None else host_run(rb)

            yield from dpipe.run_pipelined(child, submit, drain,
                                           window=window,
                                           poll=self._poll_cancel)
            return

        def run(p: MicroPartition) -> MicroPartition:
            rb = p.combined()
            if not gate(rb):
                return host_run(rb)
            tok = device_submit(rb)
            out = device_drain(tok) if tok is not None else None
            return out if out is not None else host_run(rb)

        yield from _ordered_parallel(child, run)

    def _exec_DeviceExchangeAgg(self, node: pp.DeviceExchangeAgg):
        """Shuffle+final-merge as ONE mesh program: shard the partial group
        blocks over the device mesh, all_to_all by key hash over ICI, merge,
        and decode one disjoint group block per shard."""
        from . import memory
        parts = memory.materialize(self._exec(node.children[0]),
                                   memory.breaker_budget_bytes())
        try:
            outs = self._mesh_exchange_agg(node, parts)
            if outs is not None:
                yield from outs
                return
            # host fallback: hash exchange + final aggregate (what
            # translate would have emitted without the mesh, including its
            # partition cap) — bucket-store backed
            n = max(min(len(parts),
                        self.cfg.shuffle_aggregation_default_partitions), 1)
            store = self._key_bucket_store(iter(parts),
                                           list(node.group_by), n)
            try:
                yield from _ordered_parallel(
                    self._emit_buckets(store, node.children[0].schema()),
                    lambda p: MicroPartition.from_recordbatch(
                        p.combined().agg(node.aggs, node.group_by)
                        .cast_to_schema(node.schema())))
            finally:
                store.close()
        finally:
            parts.close()

    def _mesh_exchange_agg(self, node, parts) -> Optional[List[MicroPartition]]:
        import jax
        import numpy as np
        from ..aggs import split_agg_expr
        from ..device import column as dcol, runtime as drt
        from ..parallel import exchange, mesh as pmesh
        if not drt.device_enabled():
            return None
        mesh = pmesh.get_mesh()
        if mesh is None or pmesh.mesh_size() < 2:
            return None
        rb = RecordBatch.concat([p.combined() for p in parts]) \
            if len(parts) > 1 else parts[0].combined()
        if len(rb) == 0:
            return [MicroPartition.from_recordbatch(
                RecordBatch.empty(node.schema()))]
        key_names = [g.name() for g in node.group_by]
        specs = [split_agg_expr(a) for a in node.aggs]
        ops = tuple(s[0] for s in specs)
        val_names = [s[1]._unalias().params[0] for s in specs]
        out_names = [s[2] for s in specs]
        n = pmesh.mesh_size()
        total = len(rb)
        # per-shard capacity padded to a size class so literal-different
        # row counts re-enter the memoized collective program instead of
        # tracing one program per row count (the r16 retrace budget)
        C = dcol.bucket_capacity((total + n - 1) // n)
        cap = n * C

        encode = _np_plane_encoder(rb, cap)
        kplanes = _encode_plane_lists(encode, key_names)
        vplanes = _encode_plane_lists(encode, val_names)
        if kplanes is None or vplanes is None:
            return None
        keys, kvalids, kdicts = kplanes
        vals, vvalids, vdicts = vplanes
        mask = np.arange(cap) < total
        try:
            sb = lambda a: exchange.shard_blocks(mesh, a)
            fk, fkv, fv, fvv, gmask = exchange.sharded_grouped_agg(
                mesh, tuple(sb(k) for k in keys),
                tuple(sb(k) for k in kvalids),
                tuple(sb(v) for v in vals),
                tuple(sb(v) for v in vvalids), sb(mask), ops)
            host = jax.device_get((fk, fkv, fv, fvv, gmask))
        except Exception as exc:
            drt.device_failed("executor.mesh.grouped_agg", exc)
            return None
        _count_ici_exchange(total, list(keys) + list(vals),
                            list(kvalids) + list(vvalids))
        fk, fkv, fv, fvv, gmask = [
            [np.asarray(a) for a in grp] if isinstance(grp, (list, tuple))
            else np.asarray(grp) for grp in host]
        spec = [(nm, node.schema()[nm].dtype, fk[i], fkv[i], kdicts[i])
                for i, nm in enumerate(key_names)]
        spec += [(nm, node.schema()[nm].dtype, fv[j], fvv[j], vdicts[j])
                 for j, nm in enumerate(out_names)]
        return _decode_mesh_shards(n, gmask, spec, node.schema())

    def _mesh_hash_repartition(self, parts, by, n: int
                               ) -> Optional[List[MicroPartition]]:
        """Hash repartition as one all_to_all over the device mesh — chosen
        when the target partition count equals the mesh width and every
        column either round-trips the device encoding bit-exactly or is
        string/binary (those ride shared-dictionary codes built from the
        single concatenated batch; see _np_plane_encoder)."""
        import jax
        from ..device import column as dcol, runtime as drt
        from ..parallel import exchange, mesh as pmesh
        if not drt.device_enabled():
            return None
        if pmesh.mesh_size() < 2 or n != pmesh.mesh_size():
            return None
        mesh = pmesh.get_mesh()
        rb = RecordBatch.concat([p.combined() for p in parts]) \
            if len(parts) > 1 else parts[0].combined()
        # tiny repartitions don't repay the collective program's dispatch
        # against the host fanout: the cost model prices the exact bytes
        # against the calibrated ICI rate (DAFT_TPU_MESH_MIN_ROWS
        # force-overrides; =0 forces the mesh)
        if not pmesh.mesh_admits(
                len(rb), rb.size_bytes() / max(len(rb), 1)):
            return None
        schema = rb.schema
        # pure data movement must be bit-exact: every column must round-trip
        # the device encoding losslessly (no decimals-as-floats, no f64→f32).
        # String/binary columns qualify: the whole input is concatenated
        # into one batch, so their dictionary codes are shared across every
        # output shard and decode back exactly (see _np_plane_encoder).
        for f in schema:
            if not (dcol.is_lossless_device_dtype(f.dtype)
                    or f.dtype.is_string() or f.dtype.is_binary()):
                return None
        if len(rb) == 0:
            return [MicroPartition.from_recordbatch(RecordBatch.empty(schema))
                    for _ in range(n)]
        total = len(rb)
        # size-class padded per-shard capacity: one collective program per
        # bucket, not per literal row count (r16 retrace discipline)
        C = dcol.bucket_capacity((total + n - 1) // n)
        cap = n * C
        # destination shard from the SAME xxh64 chain as the host exchange
        # (partition_by_hash) so co-partitioned joins agree across tiers
        try:
            key_s = [rb.eval_expression(e) for e in by]
            h = key_s[0].hash()
            for k in key_s[1:]:
                h = k.hash(seed=h)
            pid = (h.to_numpy() % np.uint64(n)).astype(np.int32)
        except Exception:
            return None
        pid = np.concatenate(
            [pid, np.zeros(cap - total, dtype=np.int32)])
        encode = _np_plane_encoder(rb, cap)
        names = schema.column_names
        enc = _encode_plane_lists(encode, names)
        if enc is None:
            return None
        planes, valids, dicts = enc
        mask = np.arange(cap) < total
        try:
            sb = lambda a: exchange.shard_blocks(mesh, a)
            op, ov, om = exchange.sharded_hash_repartition(
                mesh, tuple(sb(p) for p in planes),
                tuple(sb(v) for v in valids), sb(mask), sb(pid))
            host = jax.device_get((op, ov, om))
        except Exception as exc:
            drt.device_failed("executor.mesh.hash_repartition", exc)
            return None
        _count_ici_exchange(total, planes, valids)
        op, ov, om = [[np.asarray(a) for a in grp]
                      if isinstance(grp, (list, tuple)) else np.asarray(grp)
                      for grp in host]
        spec = [(nm, schema[nm].dtype, op[j], ov[j], dicts[j])
                for j, nm in enumerate(names)]
        return _decode_mesh_shards(n, om, spec, schema)

    def _exec_Dedup(self, node: pp.Dedup):
        child = self._exec(node.children[0])
        yield from _ordered_parallel(child, lambda p: p.distinct(node.on))

    def _exec_Pivot(self, node: pp.Pivot):
        for p in self._exec(node.children[0]):
            yield p.pivot(node.group_by, node.pivot_col, node.value_col,
                          node.names).cast_to_schema(node.schema())

    def _exec_Window(self, node: pp.Window):
        from ..window_exec import run_window
        child = self._exec(node.children[0])
        yield from _ordered_parallel(
            child, lambda p: MicroPartition.from_recordbatch(
                run_window(p.combined(), node)))

    # sort -------------------------------------------------------------
    def _exec_Sort(self, node: pp.Sort):
        """Streaming external sort (the blocking sink shape of
        ``sinks/blocking_sink.rs:32-55``): ONE pass over the child spills
        morsels under the breaker budget while reservoir-sampling keys;
        boundaries from the sample range-fan the spilled stream into
        per-bucket stores; each bucket then sorts independently — peak RSS
        ≈ breaker budget + one bucket, never the whole child."""
        by = list(node.sort_by)
        desc, nf = list(node.descending), list(node.nulls_first)
        buf, samples = self._consume_sampling(
            self._exec(node.children[0]), by)
        try:
            if len(buf) == 0:
                yield MicroPartition.empty(node.schema())
                return
            n = self._breaker_fanout(buf.total_bytes)
            boundaries = None
            if n > 1 and len(buf) > 1 and samples:
                boundaries = self._sample_boundaries(
                    samples, [e.name() for e in by], desc, nf, n)
            from .. import tracing

            def sort(p: MicroPartition) -> MicroPartition:
                with tracing.span("sort:topn", lane="pipeline",
                                  attrs={"rows": len(p)}):
                    return p.sort(node.sort_by, node.descending,
                                  node.nulls_first)

            if boundaries is None:
                yield sort(_gather_all(iter(buf)))
                return
            yield from _ordered_parallel(
                self._stream_range_buckets(buf, by, boundaries, desc, n,
                                           node.schema()), sort)
        finally:
            buf.close()

    def _consume_sampling(self, stream, by: List[Expression]):
        """Drain a child ONCE into a breaker-budget SpillBuffer while
        reservoir-sampling its key columns (the old path re-walked the
        materialized child to sample, re-reading spill files)."""
        from . import memory
        k = self.cfg.sample_size_for_sort
        buf = memory.SpillBuffer(memory.breaker_budget_bytes())
        samples: List[RecordBatch] = []
        try:
            for p in stream:
                self._poll_cancel()
                rb = p.combined()
                if len(rb):
                    s = rb.sample(size=min(k, len(rb)))
                    samples.append(s.eval_expression_list(by))
                buf.append(p)
        except BaseException:
            buf.close()  # a failed drain must not leak the spill files
            raise
        return buf, samples

    def _breaker_fanout(self, total_bytes: int) -> int:
        """Bucket count for a streaming breaker: each bucket must fit
        comfortably in the breaker budget (it is loaded whole at read
        time), and stay near the configured partition size."""
        from . import memory
        target = min(self.cfg.target_partition_size_bytes,
                     max(memory.breaker_budget_bytes() // 4, 1))
        return max(1, min(1024, -(-int(total_bytes) // max(target, 1))))

    def _stream_range_buckets(self, buf, by, boundaries, desc, n,
                              schema):
        """Re-stream a spilled buffer, range-fanning each morsel into an
        n-bucket PartitionedSpillStore; emit buckets in range order."""
        from . import memory
        store = memory.PartitionedSpillStore(n)
        try:
            for mp in buf:
                self._poll_cancel()
                for i, piece in enumerate(
                        mp.partition_by_range(by, boundaries, desc)):
                    if len(piece):
                        store.push(i, piece.combined())
            buf.close()  # input spill frees before bucket reads begin
            store.finalize()
            yield from self._emit_buckets(store, schema)
        finally:
            store.close()

    def _emit_buckets(self, store, schema, groups=None):
        """One MicroPartition per bucket (or per GROUP of consecutive
        buckets, for AQE-coalesced shuffles). Resident batches pass
        through without any Arrow round-trip; consumers combine lazily."""
        for grp in (groups if groups is not None
                    else [[i] for i in range(store.n)]):
            batches = []
            for i in grp:
                batches.extend(store.bucket_batches(i))
            # normalize dtype drift (a spilled batch round-trips through
            # Arrow IPC; Series.concat later casts everything to the FIRST
            # batch's dtype, so each batch must match the declared schema)
            batches = [b if b.schema == schema else b.cast_to_schema(schema)
                       for b in batches if len(b)]
            if batches:
                yield MicroPartition.from_recordbatches(batches, schema)
            else:
                yield MicroPartition.empty(schema)

    def _exec_TopN(self, node: pp.TopN):
        from .. import tracing

        def top(p: MicroPartition) -> MicroPartition:
            rb = p.combined()
            with tracing.span("sort:topn", lane="pipeline",
                              attrs={"rows": len(rb), "k": node.limit}):
                return MicroPartition.from_recordbatch(
                    rb.top_n(node.sort_by, node.limit, node.descending,
                             node.nulls_first))

        child = self._exec(node.children[0])
        tops = list(_ordered_parallel(child, top))
        if not tops:  # an empty child STREAM (not just empty morsels)
            yield MicroPartition.from_recordbatch(
                RecordBatch.empty(node.schema()))
            return
        yield top(tops[0].concat(tops[1:]) if len(tops) > 1 else tops[0])

    # exchanges --------------------------------------------------------
    def _exec_Exchange(self, node: pp.Exchange):
        """Streaming shuffles: hash/random/range fan every incoming morsel
        into an n-bucket :class:`memory.PartitionedSpillStore` (RAM under
        the breaker budget, whole-bucket spill past it) — the child is
        never materialized as a unit. gather/split reshape partition
        boundaries by global position, so they drain into a breaker-budget
        SpillBuffer (spill-bounded, inherent to their contract)."""
        from . import memory
        kind, n = node.kind, node.num_partitions
        algo = getattr(self.cfg, "shuffle_algorithm", "auto")
        if algo not in ("auto", "naive", "spill_cache"):
            raise ValueError(
                f"shuffle_algorithm {algo!r}: expected 'auto', 'naive' or "
                f"'spill_cache'")
        if kind == "hash" and n > 1:
            if algo == "spill_cache":
                yield from self._spill_cache_hash_exchange(node, n)
            else:
                yield from self._hash_exchange_streaming(node, n)
            return
        if kind == "random" and n > 1:
            yield from self._fan_exchange_streaming(
                node, n, lambda mp, i: mp.partition_by_random(n, seed=i))
            return
        if kind == "range":
            yield from self._range_exchange_streaming(node, n)
            return
        # gather / split: global-position reshapes
        parts = memory.materialize(self._exec(node.children[0]),
                                   memory.breaker_budget_bytes())
        try:
            if len(parts) == 0:
                yield MicroPartition.empty(node.schema())
            elif kind in ("gather", "hash", "random") or n == 1:
                # hash/random collapse to a concat at n == 1 (the n > 1
                # cases took the streaming-store paths above)
                yield _gather_all(iter(parts))
            elif kind == "split":
                yield from self._split(list(parts), n)
            else:
                raise NotImplementedError(f"exchange kind {kind}")
        finally:
            parts.close()

    def _hash_exchange_streaming(self, node, n: int):
        from . import memory, out_of_core as ooc
        from ..device import runtime as drt
        from ..parallel import mesh as pmesh
        by = list(node.by)
        child = self._exec(node.children[0])

        # small morsels are partitioned together (coalescing only: the
        # bucket index is a contract here, so every row is still hashed)
        def fan(unit, i):
            mp, morsels = unit
            return mp.partition_by_hash(by, n, morsels)
        if drt.device_enabled() and pmesh.mesh_size() >= 2 \
                and n == pmesh.mesh_size():
            # the ICI collective repartition wants a partition list; fall
            # back to the streaming store with the same (spill-bounded)
            # buffer when it declines
            parts = memory.materialize(child, memory.breaker_budget_bytes())
            try:
                mesh_out = self._mesh_hash_repartition(list(parts), by, n)
                if mesh_out is not None:
                    yield from mesh_out
                    return
                yield from self._fan_exchange_streaming(
                    node, n, fan, stream=ooc.coalesce_small(
                        iter(parts), self._poll_cancel))
            finally:
                parts.close()
            return
        yield from self._fan_exchange_streaming(
            node, n, fan,
            stream=ooc.coalesce_small(child, self._poll_cancel))

    def _fan_exchange_streaming(self, node, n: int, fan, stream=None):
        """Shared streaming fanout: morsel → n pieces → bucket store; AQE
        may coalesce consecutive buckets from measured totals (growing
        beyond the planned n would need a re-hash of spilled buckets, so
        adaptation only shrinks — the common small-data correction)."""
        from . import memory
        store = memory.PartitionedSpillStore(n)
        try:
            for i, mp in enumerate(stream if stream is not None
                                   else self._exec(node.children[0])):
                self._poll_cancel()
                for j, piece in enumerate(fan(mp, i)):
                    if len(piece):
                        store.push(j, piece.combined())
            store.finalize()
            groups = None
            if self.cfg.enable_aqe \
                    and getattr(node, "engine_inserted", False):
                planner = self._aqe()
                n2 = min(planner.adapt_partition_count(
                    n, sum(store.nbytes), sum(store.rows)), n)
                if n2 < n:
                    bounds = [round(j * n / n2) for j in range(n2 + 1)]
                    groups = [list(range(bounds[j], bounds[j + 1]))
                              for j in range(n2)]
            yield from self._emit_buckets(store, node.schema(), groups)
        finally:
            store.close()

    def _range_exchange_streaming(self, node, n: int):
        by = list(node.by)
        desc = list(node.descending) or [False] * len(by)
        buf, samples = self._consume_sampling(
            self._exec(node.children[0]), by)
        try:
            boundaries = None
            if n > 1 and samples:
                boundaries = self._sample_boundaries(
                    samples, [e.name() for e in by], desc, desc, n)
            if boundaries is None:
                if len(buf) == 0:
                    yield MicroPartition.empty(node.schema())
                else:
                    yield _gather_all(iter(buf))
                for _ in range(max(n - 1, 0)):
                    yield MicroPartition.empty(node.schema())
                return
            yield from self._stream_range_buckets(buf, by, boundaries,
                                                  desc, n, node.schema())
        finally:
            buf.close()


    def _spill_cache_hash_exchange(self, node, n: int):
        """Streaming map-side shuffle: every incoming morsel is hash-
        partitioned and appended to a per-partition spill file; the reduce
        side then streams one partition at a time (reference:
        ``shuffle_cache.rs:14-80`` map/partition/spill → fetch)."""
        import pyarrow as pa

        from ..distributed.shuffle_service import (ShuffleCache,
                                                   _spill_file_batches)
        by = list(node.by)
        cache = ShuffleCache(dirs=list(self.cfg.flight_shuffle_dirs) or None)
        try:
            for mp in self._exec(node.children[0]):
                self._poll_cancel()
                for i, piece in enumerate(mp.partition_by_hash(by, n)):
                    if len(piece):
                        cache.push(i, piece.combined().to_arrow_table())
            cache.close()
            schema = node.schema().to_arrow()
            for i in range(n):
                # lazy per-batch read off the spill file: one partition's
                # batches in memory at a time, never the raw bytes too
                batches = [b for _, b in
                           _spill_file_batches(cache._path(i))]
                t = (pa.Table.from_batches(batches) if batches
                     else schema.empty_table())
                yield MicroPartition.from_recordbatch(
                    RecordBatch.from_arrow_table(t))
        finally:
            cache.cleanup()



    def _split(self, parts: List[MicroPartition], n: int):
        """Split/coalesce to exactly n partitions, preserving order."""
        total = sum(len(p) for p in parts)
        target = max((total + n - 1) // max(n, 1), 1)
        combined = parts[0].concat(parts[1:]) if len(parts) > 1 else parts[0]
        rb = combined.combined()
        out = 0
        start = 0
        while out < n:
            end = min(start + target, len(rb)) if out < n - 1 else len(rb)
            yield MicroPartition.from_recordbatch(rb.slice(start, end))
            start = end
            out += 1

    def _sample_boundaries(self, sampled_keys: List[RecordBatch],
                           key_names: List[str], descending: List[bool],
                           nulls_first: List[bool], n: int
                           ) -> Optional[RecordBatch]:
        return sample_boundaries(sampled_keys, key_names, descending,
                                 nulls_first, n)


    def _sort_merge_join(self, node: pp.HashJoin):
        """Distributed sort-merge join (reference: SortMergeJoin physical
        op with ``sort_merge_join_sort_with_aligned_boundaries``): sample
        BOTH sides' keys while spilling each under the breaker budget,
        derive ONE shared set of range boundaries, range-bucket both sides
        with them (co-ranged, not co-hashed), then join pairwise — one
        bucket pair resident at a time. Output comes out range-clustered
        by key."""
        how = node.how
        left_on, right_on = list(node.left_on), list(node.right_on)
        lbuf, lsamp = self._consume_sampling(self._exec(node.children[0]),
                                             left_on)
        rbuf, rsamp = self._consume_sampling(self._exec(node.children[1]),
                                             right_on)
        try:
            n = max(self._breaker_fanout(lbuf.total_bytes),
                    self._breaker_fanout(rbuf.total_bytes),
                    min(max(len(lbuf), len(rbuf)), 16))
            names = [e.name() for e in left_on]
            # right-side key names normalize to the left's so samples
            # concat into one boundary table (comparison is positional)
            samples = lsamp + [
                RecordBatch.from_series([c.rename(nm) for c, nm in
                                         zip(rb.columns(), names)])
                for rb in rsamp]
            desc = [False] * len(left_on)
            boundaries = self._sample_boundaries(samples, names, desc,
                                                 desc, n) \
                if n > 1 and samples else None
            if boundaries is None:
                lall = _gather_all_or_empty(iter(lbuf),
                                            node.children[0].schema())
                rall = _gather_all_or_empty(iter(rbuf),
                                            node.children[1].schema())
                yield lall.hash_join(rall, left_on, right_on, how)
                return
            yield from _ordered_parallel(
                zip(self._stream_range_buckets(
                        lbuf, left_on, boundaries, desc, n,
                        node.children[0].schema()),
                    self._stream_range_buckets(
                        rbuf, right_on, boundaries, desc, n,
                        node.children[1].schema())),
                lambda lr: lr[0].hash_join(lr[1], left_on, right_on, how))
        finally:
            lbuf.close()
            rbuf.close()

    def _exec_HashJoin(self, node: pp.HashJoin):
        how = node.how
        if node.strategy == "sort_merge":
            yield from self._sort_merge_join(node)
            return
        if node.strategy == "hash" and self.cfg.enable_aqe:
            lnode, rnode = node.children
            if getattr(lnode, "join_side", False) \
                    and getattr(rnode, "join_side", False):
                yield from self._adaptive_hash_join(node, lnode.children[0],
                                                    rnode.children[0])
                return
        if node.strategy == "broadcast_right":
            right = _gather_all(self._exec(node.children[1]))
            child = self._exec(node.children[0])
            yield from _ordered_parallel(
                child, lambda p: p.hash_join(right, node.left_on,
                                             node.right_on, how))
            return
        if node.strategy == "broadcast_left":
            left = _gather_all(self._exec(node.children[0]))
            child = self._exec(node.children[1])
            yield from _ordered_parallel(
                child, lambda p: left.hash_join(p, node.left_on,
                                                node.right_on, how))
            return
        from . import memory
        lnode, rnode = node.children
        copart = (isinstance(lnode, pp.Exchange) and lnode.kind == "hash"
                  and isinstance(rnode, pp.Exchange) and rnode.kind == "hash"
                  and lnode.num_partitions == rnode.num_partitions
                  # the exchanges must partition on the JOIN keys: index
                  # pairing is only valid when both sides were fanned by
                  # the same key chain (a future non-key hash Exchange
                  # under a join must not silently drop matches)
                  and [e._key() for e in lnode.by]
                  == [e._key() for e in node.left_on]
                  and [e._key() for e in rnode.by]
                  == [e._key() for e in node.right_on])
        from . import out_of_core as ooc
        if copart:
            # both exchanges emit exactly n partitions in index order and
            # partition on the join keys — zip the two streams and join
            # pairwise. Each side's exchange is a streaming bucket store,
            # so at most one partition PAIR (plus the stores' bounded
            # buffers) is resident; neither side materializes as a list
            # (reference: hash_join.rs build-then-stream-probe, with the
            # build side's state held by the exchange sink). A skewed
            # pair past the pair budget re-partitions with the rotated
            # radix instead of joining whole (out_of_core).
            for outs in _ordered_parallel(
                    zip(self._exec(lnode), self._exec(rnode)),
                    lambda lr: ooc.join_copartitioned_pair(
                        self, lr[0], lr[1], node, lnode.schema(),
                        rnode.schema())):
                yield from outs
            return
        # no static co-partitioning evidence: index pairing would join
        # unrelated partitions — grace hash join: stream BOTH sides into
        # rotated-radix spill stores (same xxh64 chain at depth 0 →
        # co-partitioned buckets), then join bucket pairs one at a time,
        # recursing on any pair that still exceeds the pair budget; peak
        # memory is one bucket pair, not both children
        if ooc.spill_join_mode(self.cfg) != "0":
            yield from ooc.grace_hash_join(self, node)
            return
        # DAFT_TPU_SPILL_JOIN=0: the legacy materialize-then-refan path
        # (no recursion; an oversized bucket pair loads whole)
        with memory.materialize(self._exec(lnode),
                                memory.breaker_budget_bytes()) as lbuf, \
                memory.materialize(self._exec(rnode),
                                   memory.breaker_budget_bytes()) as rbuf:
            # fanout sized from BOTH sides (a tiny left must not gather an
            # arbitrarily large right into RAM); both buffers are
            # spill-bounded, so sizing them first costs disk, not memory
            n = max(self._breaker_fanout(lbuf.total_bytes),
                    self._breaker_fanout(rbuf.total_bytes))
            if n <= 1:
                # both sides fit one bucket — direct in-memory join
                lall = _gather_all_or_empty(iter(lbuf), lnode.schema())
                rall = _gather_all_or_empty(iter(rbuf), rnode.schema())
                yield lall.hash_join(rall, node.left_on, node.right_on,
                                     how)
                return
            n = max(n, min(max(len(lbuf), len(rbuf)), 16))
            lstore = self._key_bucket_store(iter(lbuf),
                                            list(node.left_on), n)
            lbuf.close()
            try:
                rstore = self._key_bucket_store(iter(rbuf),
                                                list(node.right_on), n)
            except BaseException:
                lstore.close()
                raise
            rbuf.close()
            try:
                yield from _ordered_parallel(
                    zip(self._emit_buckets(lstore, lnode.schema()),
                        self._emit_buckets(rstore, rnode.schema())),
                    lambda lr: lr[0].hash_join(lr[1], node.left_on,
                                               node.right_on, how))
            finally:
                lstore.close()
                rstore.close()

    def _key_bucket_store(self, stream, by, n: int):
        """Drain a stream into an n-bucket store hashed on ``by``. The
        store closes itself when the drain fails; the caller owns it
        once it is returned whole."""
        from . import memory, out_of_core as ooc
        store = memory.PartitionedSpillStore(n)
        try:
            for mp, morsels in ooc.coalesce_small(stream,
                                                  self._poll_cancel):
                for j, piece in enumerate(
                        mp.partition_by_hash(by, n, morsels)):
                    if len(piece):
                        store.push(j, piece.combined())
            store.finalize()
        except BaseException:
            store.close()
            raise
        return store

    def _adaptive_hash_join(self, node: pp.HashJoin, li, ri):
        """AQE join-strategy demotion (reference: AdaptivePlanner re-plans
        the remaining query from materialized stats, ``physical_planner/
        planner.rs:451-640``): materialize each join input BELOW its
        planned hash exchange, and if the measured bytes of an eligible
        side fit the broadcast threshold, skip both shuffles and broadcast
        it; otherwise fan both materialized sides out as planned."""
        from . import memory
        how = node.how
        threshold = self.cfg.broadcast_join_size_bytes_threshold
        with memory.materialize(self._exec(li),
                                memory.breaker_budget_bytes()) as lparts:
            if lparts.total_bytes <= threshold and how in ("inner",
                                                           "right"):
                self._aqe().record_join("hash→broadcast_left",
                                        lparts.total_bytes)
                left = _gather_all(iter(lparts))
                lparts.close()
                yield from _ordered_parallel(
                    self._exec(ri), lambda p: left.hash_join(
                        p, node.left_on, node.right_on, how))
                return
            with memory.materialize(
                    self._exec(ri),
                    memory.breaker_budget_bytes()) as rparts:
                if rparts.total_bytes <= threshold \
                        and how in ("inner", "left", "semi", "anti"):
                    self._aqe().record_join("hash→broadcast_right",
                                            rparts.total_bytes)
                    right = _gather_all(iter(rparts))
                    rparts.close()
                    yield from _ordered_parallel(
                        iter(lparts), lambda p: p.hash_join(
                            right, node.left_on, node.right_on, how))
                    return
                n = node.children[0].num_partitions
                self._aqe().record_join(
                    "hash", lparts.total_bytes + rparts.total_bytes)
                yield from _ordered_parallel(
                    zip(self._refan(lparts, list(node.left_on), n,
                                    li.schema()),
                        self._refan(rparts, list(node.right_on), n,
                                    ri.schema())),
                    lambda lr: lr[0].hash_join(lr[1], node.left_on,
                                               node.right_on, how))

    def _refan(self, parts, by: List[Expression], n: int, schema):
        """Key-hash a (possibly spilled) partition buffer into n buckets
        and emit them in order — bucket-store backed, one bucket resident
        at a time."""
        from . import memory
        store = self._key_bucket_store(iter(parts), by, n)
        if isinstance(parts, memory.SpillBuffer):
            parts.close()

        def emit():
            try:
                yield from self._emit_buckets(store, schema)
            finally:
                store.close()
        return emit()

    def _exec_CrossJoin(self, node: pp.CrossJoin):
        right = _gather_all(self._exec(node.children[1]))
        child = self._exec(node.children[0])
        yield from _ordered_parallel(child, lambda p: p.cross_join(right))

    # writes -----------------------------------------------------------
    def _exec_Write(self, node: pp.Write):
        info = node.info
        if info.get("kind") == "sink":
            sink = info["sink"]
            sink.start()
            results = list(sink.write(self._exec(node.children[0])))
            yield sink.finalize(results)
            return
        from ..io import writers
        if info.get("mode") == "overwrite":
            writers.overwrite_dir(info["root_dir"])
        child = self._exec(node.children[0])
        outs = list(_ordered_parallel(
            child, lambda p: writers.write_micropartition(
                p, info["kind"], info["root_dir"],
                info.get("partition_cols"), info.get("options"))))
        outs = [o for o in outs if len(o)]
        if not outs:
            yield MicroPartition.empty(node.schema())
            return
        yield MicroPartition.from_recordbatch(
            RecordBatch.concat(outs).cast_to_schema(node.schema()))


def _task_column_ndv(tasks, name: str):
    """max-min+1 folded over ALL tasks' parquet footers for an int column
    (the scan-level twin of logical/stats.column_ndv). A single file's
    range would underestimate scans range-partitioned on the key and let
    a non-reductive grouping through the gate."""
    try:
        from ..io import footers
        lo = hi = None
        seen = set()
        for t in tasks:
            if t.file_format != "parquet" or not t.paths:
                return None
            md_cached = getattr(t, "pq_metadata", None)
            for k, path in enumerate(t.paths):
                if path in seen:
                    continue
                seen.add(path)
                md = md_cached if md_cached is not None \
                    and len(t.paths) == 1 \
                    else footers.footer(path, t.io_config,
                                        t.identity(k)).metadata
                idx = {md.schema.column(i).name: i
                       for i in range(md.num_columns)}.get(name)
                if idx is None:
                    return None
                for rg in range(md.num_row_groups):
                    st = md.row_group(rg).column(idx).statistics
                    if st is None or not st.has_min_max \
                            or not isinstance(st.min, int) \
                            or isinstance(st.min, bool):
                        return None
                    lo = st.min if lo is None else min(lo, st.min)
                    hi = st.max if hi is None else max(hi, st.max)
        return None if lo is None else float(hi - lo + 1)
    except Exception:
        return None


def _fragment_groups_affordable(node, src) -> bool:
    """Upfront group-cardinality gate for the fused device aggregation:
    a NON-reductive grouping (TPC-H Q18's near-unique l_orderkey, Q20's
    partkey×suppkey) would ship a group block rivaling the input over the
    link — estimate groups from parquet footer NDVs and refuse the device
    path when the packed transfer would exceed the host's own aggregation
    time (the same parity rule ``fragment._max_out_cap`` enforces at run
    time, applied before any upload or probe happens)."""
    import math

    from ..device import costmodel
    p = costmodel.link_profile()
    if p.down_bps == math.inf:
        return True
    ndvs = []
    for g in node.group_by:
        u = g._unalias()
        if u.op != "col":
            return True  # computed key: unknown → assume reductive
        ndv = _task_column_ndv(src.tasks, u.params[0])
        if ndv is None:
            return True  # strings/no stats → assume reductive
        ndvs.append(ndv)
    if not ndvs:
        return True  # global aggregation: one packed scalar row
    est_groups = 1.0
    for n in ndvs:
        est_groups *= n
    rows = sum(t.num_rows() or 0 for t in src.tasks)
    if rows:
        est_groups = min(est_groups, float(rows))
    from ..device.fragment import packed_bytes_per_group
    # node.aggs is the PARTIAL agg list (_split_aggs already decomposed
    # mean→sum+count etc. before _try_fuse_partial built this node), so its
    # length equals len(prog.ops) and prices the same packed layout that
    # run_packed emits
    bytes_per_group = packed_bytes_per_group(len(node.group_by),
                                             len(node.aggs))
    size = sum(t.size_bytes() or 0 for t in src.tasks)
    host_s = max(size, 1) / costmodel.HOST_AGG_BPS
    return est_groups * bytes_per_group <= host_s * p.down_bps


def _lit_true() -> Expression:
    from ..expressions.expressions import lit
    return lit(True)


def _count_ici_exchange(rows: int, planes, valids) -> None:
    """Account one completed mesh collective exchange in the shuffle
    data plane: bytes that rode ICI instead of the Flight wire (the
    encoded plane payload entering the all_to_all) — surfaced per query
    in ``explain(analyze=True)`` and at ``/metrics``."""
    try:
        from ..distributed.shuffle_service import shuffle_count
        nbytes = sum(int(p.nbytes) for p in planes) \
            + sum(int(v.nbytes) for v in valids)
        shuffle_count("ici_exchanges")
        shuffle_count("ici_rows", rows)
        shuffle_count("ici_bytes", nbytes)
    except Exception:
        pass  # accounting must never take the exchange down


def _encode_plane_lists(encode, names):
    """Encode columns into parallel (values, valids, dictionaries) plane
    lists; None when any column lacks a plain device representation."""
    vals, valids, dicts = [], [], []
    for nm in names:
        enc = encode(nm)
        if enc is None:
            return None
        vals.append(enc[0])
        valids.append(enc[1])
        dicts.append(enc[2])
    return vals, valids, dicts


def _decode_mesh_shards(n: int, live_mask: np.ndarray, cols_spec, schema
                        ) -> List[MicroPartition]:
    """Slice exchanged [n*C'] blocks into per-shard MicroPartitions.
    cols_spec: ordered (name, dtype, values_plane, valids_plane, dictionary)
    tuples — dictionary non-None for string/binary columns riding shared
    dictionary codes."""
    from ..device import column as dcol
    shard_len = live_mask.shape[0] // n
    outs = []
    for i in range(n):
        sl = slice(i * shard_len, (i + 1) * shard_len)
        live = live_mask[sl]
        cnt = int(live.sum())
        cols = []
        for nm, dtype, v, m, d in cols_spec:
            dc = dcol.DeviceColumn(v[sl][live], m[sl][live], dtype, d)
            cols.append(dcol.decode_column(nm, dc, cnt))
        outs.append(MicroPartition.from_recordbatch(
            RecordBatch.from_series(cols).cast_to_schema(schema)))
    return outs


def _select_program(exprs, predicate, in_schema, out_schema):
    """The chain program of a scan's selection, or None where its rows
    cannot come back as they are: every output a plain value the device
    holds exactly (a float64 rides float32 on a chip without it: the
    device's encoding), or a string / binary column passed through with
    its dictionary; every column the predicate reads exact on the device,
    so that the filter keeps the rows the host's would."""
    from ..device import column as dcol, fragment, runtime as drt

    def exact(dtype):
        return dcol.is_lossless_device_dtype(dtype) and not dtype.is_null()

    try:
        for e in exprs:
            f = out_schema[e.name()]
            if f.dtype.is_string() or f.dtype.is_binary():
                if drt._string_out_source(e) is None:
                    return None
            elif not (exact(f.dtype) or f.dtype.is_floating()):
                return None
        for name in predicate.column_names():
            dtype = in_schema[name].dtype
            if not (exact(dtype) or dtype.is_string()
                    or dtype.is_binary()):
                return None
    except (KeyError, ValueError):
        return None
    prog = fragment.get_fused_region(exprs, predicate, in_schema,
                                     fused_ops=("filter", "scan"))
    if prog is None or prog.in_np_dtypes is None:
        return None
    return prog


def _tally_host_select(tasks, stream, prog=None):
    """A filtered scan the reader answers: its tables, their rows and the
    survivors on the query's trace (``summary()["selects"]``), as the
    device's selection tallies its own; and on ``prog`` (the selection
    program that could have run) the share that survived, which is the
    gate's next bet."""
    from ..device import costmodel
    rows_in = [t.rows_scanned() for t in tasks]
    rows_out = 0
    for p in stream:
        rows_out += len(p)
        yield p
    known = None not in rows_in
    costmodel.count_select("host", len(tasks),
                           sum(rows_in) if known else rows_out, rows_out)
    if prog is not None and known and sum(rows_in):
        prog.survivors_hint = rows_out / sum(rows_in)


def _loaded_batches(task):
    """``task.stream_batches()`` with each pull (read + decode of the
    next batch) under a ``scan:load`` span; what the consumer does
    between pulls stays outside."""
    from .. import tracing
    it = iter(task.stream_batches())
    while True:
        with tracing.span("scan:load", lane="scan") as sp:
            try:
                rb = next(it)
            except StopIteration:
                return
            sp.set("rows", len(rb))
            sp.set("bytes", rb.size_bytes())
        yield rb


def _load_with_retry(task, tries: int = 2) -> MicroPartition:
    """Scan-task load with transient-IO retry (reference analogue: per-task
    lineage retry in the classic runner / flotilla max_task_retries —
    inputs are re-scannable from storage, so retrying the load is safe)."""
    from .. import tracing
    tries = max(tries, 1)
    last = None
    for attempt in range(tries):
        mp = MicroPartition.from_scan_task(task)
        try:
            with tracing.span("scan:load", lane="scan",
                              attrs={"files": len(task.paths)}) as sp:
                mp._load()
                sp.set("rows", len(mp))
                sp.set("bytes", mp.size_bytes())
            return mp
        except OSError as exc:
            last = exc
            if attempt + 1 < tries:
                import time
                time.sleep(min(0.2 * (2 ** attempt), 2.0))
    raise last


def _np_plane_encoder(rb: RecordBatch, cap: int):
    """Column name → (values, validity, dictionary) numpy planes zero-padded
    to cap, or None when the column has no plain device representation.

    String/binary columns ride dictionary codes. That is SOUND here even
    across shards: every mesh path concatenates its partitions into ONE
    RecordBatch before encoding, so all shards share a single dictionary —
    and ``_np_encode`` assigns rank codes over the SORTED dictionary, so
    code order is lexicographic order (min/max on codes is correct)."""
    import pyarrow as pa
    from ..device import column as dcol

    def encode(name):
        try:
            vals, valid, dictionary = dcol._np_encode(rb.get_column(name))
        except (ValueError, TypeError, pa.ArrowInvalid):
            return None
        if len(vals) < cap:
            vals = np.concatenate(
                [vals, np.zeros(cap - len(vals), dtype=vals.dtype)])
            valid = np.concatenate(
                [valid, np.zeros(cap - len(valid), dtype=np.bool_)])
        return vals, valid, dictionary

    return encode


def _gather_all(parts: Iterator[MicroPartition]) -> MicroPartition:
    ps = list(parts)
    return ps[0].concat(ps[1:]) if len(ps) > 1 else ps[0]


def _gather_all_or_empty(parts: Iterator[MicroPartition],
                         schema) -> MicroPartition:
    ps = list(parts)
    if not ps:
        return MicroPartition.empty(schema)
    return ps[0].concat(ps[1:]) if len(ps) > 1 else ps[0]


def sample_boundaries(sampled_keys: List[RecordBatch],
                      key_names: List[str], descending: List[bool],
                      nulls_first: List[bool], n: int
                      ) -> Optional[RecordBatch]:
    """Concatenated key samples → n-1 range boundaries (sorted,
    null-free), or None when there is nothing to sample. Shared by the
    local range exchange and the distributed worker-side sort protocol
    (the driver computes boundaries from samples only)."""
    merged = RecordBatch.concat(sampled_keys)
    by = [col(nm) for nm in key_names]
    merged = merged.filter(~_any_null(by, merged)) if len(merged) \
        else merged
    if len(merged) == 0:
        return None
    merged_sorted = merged.sort(by, descending, nulls_first)
    idx = [min(int(len(merged_sorted) * (i + 1) / n),
               len(merged_sorted) - 1) for i in range(n - 1)]
    return merged_sorted.take(np.asarray(idx, dtype=np.int64))


def _any_null(by: List[Expression], rb: RecordBatch) -> Expression:
    e = col(by[0].name()).is_null()
    for b in by[1:]:
        e = e | col(b.name()).is_null()
    return e
