"""Async device execution pipeline: overlap host encode/decode with
device compute and batch every device→host transfer.

Round 17 closes the second half of ROADMAP item 1.  r16 killed the
recompile tax; this module kills the per-morsel *transfer* tax.  The
synchronous chain — Arrow→numpy encode, ``jnp.asarray`` upload, dispatch,
blocking ``jax.device_get``, decode — serialized every stage even though
JAX dispatch is already asynchronous.  Three fixes live here:

- **a bounded in-flight window** (``DAFT_TPU_DEVICE_INFLIGHT``, default
  2) of double-buffered morsel slots driven by :func:`run_pipelined`:
  morsel N+1's host-side encode+upload runs on a dedicated submit pool
  while morsel N computes on device and morsel N−1 downloads/decodes on
  the consumer thread.  Each slot acquires MemoryManager admission for
  its host+HBM footprint on submit (:func:`acquire_slot`) and releases
  it when the slot drains (:func:`release_slot`) — the pairing is one
  row in the daft-lint Contract table (``device-slot-leak``), so the
  dataflow solver proves no slot leaks on any path, exception edges
  included.
- **one transfer per drain**: :func:`fetch_host` pulls a whole pytree of
  device arrays in ONE ``jax.device_get`` (per-leaf host copies start
  asynchronously and complete together) instead of one blocking get per
  column plane.
- **device-resident hand-off**: when a device op's decoded output feeds
  another device op, :func:`note_decoded` keeps the device planes alive
  (bounded LRU, keyed weakly by the host Series) and
  :func:`resident_planes` hands them back to the next ``encode`` —
  no host round-trip.  Reused tables are marked
  ``DeviceTable.resident`` so the r12/r14 donation discipline (proven
  by daft-lint's donation rules) keeps the shared buffers safe.

``DAFT_TPU_CHAOS_SERIALIZE=1`` (or an active fault plan) degrades every
caller to the verbatim synchronous path — :func:`inflight_window`
returns 0 — so chaos replay stays bit-identical, matching the
scan-prefetch and parallel-fetch precedents.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, Iterator, Optional

_MAX_WINDOW = 64


# context handle memo: get_context() takes the process-wide context
# lock on EVERY call — cache the singleton so the env-unset default
# path stays lock-free at decode/morsel rate (the execution_config
# attr read itself is a GIL-atomic load of the current config)
_ctx_memo = None


def _config_window() -> int:
    global _ctx_memo
    if _ctx_memo is None:
        try:
            from ..context import get_context
            # daft-lint: allow(unguarded-global-mutation) -- benign
            # last-wins memo of the process context singleton
            _ctx_memo = get_context()
        except Exception:
            return 2
    try:
        return int(_ctx_memo.execution_config.tpu_device_inflight)
    except Exception:
        return 2


def sequential_fallback() -> bool:
    """True when the pipeline must degrade to the synchronous path:
    ``DAFT_TPU_CHAOS_SERIALIZE=1`` or an active fault plan — the chaos
    replay contract requires the event order of the serial chain."""
    from ..analysis import knobs
    if knobs.env_bool("DAFT_TPU_CHAOS_SERIALIZE"):
        return True
    try:
        from ..distributed.resilience import active_fault_plan
        return active_fault_plan() is not None
    except Exception:
        return False


def inflight_window() -> int:
    """In-flight device slots (``DAFT_TPU_DEVICE_INFLIGHT``; the
    ``tpu_device_inflight`` config field is the per-query value).  0 =
    synchronous dispatch (also forced under chaos serialization)."""
    from ..analysis import knobs
    if sequential_fallback():
        return 0
    w = knobs.env_int("DAFT_TPU_DEVICE_INFLIGHT", default=None)
    if w is None:
        w = _config_window()
    return max(0, min(int(w), _MAX_WINDOW))


def fetch_host(tree):
    """ONE ``jax.device_get`` for a whole pytree of device arrays.

    JAX starts the host copy of every leaf asynchronously and waits for
    all of them together, so a table's data+validity planes (or a
    window's packed result matrices, which may lie on several chips:
    every chip's copy is started before any is waited for) cost one
    batched transfer instead of one blocking round-trip per plane."""
    import jax

    from .. import tracing
    with tracing.span("device:fetch", lane="device") as sp:
        out = jax.device_get(tree)
        if sp is not tracing._NOOP:
            leaves = jax.tree_util.tree_leaves(out)
            sp.set("arrays", len(leaves))
            sp.set("bytes",
                   sum(int(getattr(a, "nbytes", 0)) for a in leaves))
            on = sorted({d.id for a in jax.tree_util.tree_leaves(tree)
                         if isinstance(a, jax.Array)
                         for d in a.devices()})
            sp.set("chips", len(on))
            if len(on) == 1:
                sp.set("chip", on[0])
    return out


# ------------------------------------------------------------- submit pool

_PIPE_POOL = None
# guards pool creation (the executor's _pools_lock pattern): two racing
# first callers must not each build a pool and leak the loser's threads
_pipe_lock = threading.Lock()


def _pipe_pool():
    """Dedicated pool for pipeline submit bodies (encode + dispatch).
    NOT the shared exec pool: a submit body blocked on the window gate
    or memory admission must never hold an exec slot that a nested
    classify/load future needs (the scan-pool precedent)."""
    global _PIPE_POOL
    if _PIPE_POOL is not None:
        return _PIPE_POOL
    import concurrent.futures as cf
    import os
    with _pipe_lock:
        if _PIPE_POOL is None:
            _PIPE_POOL = cf.ThreadPoolExecutor(
                max_workers=max((os.cpu_count() or 4), 4),
                thread_name_prefix="daft-tpu-devpipe")
        return _PIPE_POOL


# -------------------------------------------------------- in-flight slots

class PipelineAborted(Exception):
    """The consumer tore the pipeline down while this slot waited."""


class WindowGate:
    """Window admission for in-flight device slots.

    A submit body may acquire a slot when fewer than ``window`` slots
    are live OR it owns the oldest undrained sequence number — the
    head-of-line slot is always admitted, so pool workers running out
    of order can never deadlock the consumer (which drains strictly in
    sequence).  ``is_set`` makes the gate double as a cancel signal for
    ``MemoryManager.try_acquire``."""

    def __init__(self, window: int):
        self.window = max(int(window), 1)
        self._cond = threading.Condition()
        self._live = 0
        self._drained = 0          # next sequence the consumer will drain
        self._aborted = False

    def is_set(self) -> bool:     # cancel-token protocol for try_acquire
        return self._aborted

    def acquire(self, seq: int) -> None:
        with self._cond:
            while (self._live >= self.window and seq > self._drained
                   and not self._aborted):
                self._cond.wait(0.1)
            if self._aborted:
                raise PipelineAborted()
            self._live += 1

    def note_drained(self, seq: int) -> None:
        with self._cond:
            self._drained = max(self._drained, seq + 1)
            self._cond.notify_all()

    def slot_released(self) -> None:
        with self._cond:
            self._live = max(self._live - 1, 0)
            self._cond.notify_all()

    def abort(self) -> None:
        with self._cond:
            self._aborted = True
            self._cond.notify_all()


class Slot:
    """One admitted in-flight pipeline slot: window-gate occupancy plus
    the MemoryManager bytes for its host+HBM footprint.  Created only by
    :func:`acquire_slot`; dies only through :func:`release_slot`."""

    __slots__ = ("gate", "mem", "nbytes", "released", "seq")

    def __init__(self, gate: WindowGate, mem, nbytes: int, seq: int):
        self.gate = gate
        self.mem = mem
        self.nbytes = int(nbytes)
        self.seq = seq
        self.released = False


#: bound on a slot's wait for memory admission.  Slots hold their bytes
#: from submit to DRAIN, and submit bodies run out of sequence order on
#: the pool — an unbounded wait could deadlock against bytes held by a
#: later-sequence slot the consumer cannot drain yet.  On timeout the
#: slot proceeds UNADMITTED (footprint 0, counted): backpressure is
#: advisory here exactly like the pre-pipeline morsel path, which never
#: admission-gated device dispatches at all.
_ADMIT_DEADLINE_S = 5.0


def acquire_slot(gate: WindowGate, seq: int, mem=None,
                 nbytes: int = 0) -> Slot:
    """Admit one in-flight device slot: window gate first (head-of-line
    exempt, deadlock-free), then memory admission for the slot's
    host+HBM footprint.  The returned Slot OWNS both; every caller must
    :func:`release_slot` it on all paths or hand it off whole (the
    ``device-slot-leak`` Contract row proves this statically).  Traced,
    the two waits together are one ``wait:window`` span (``seq``, and
    ``admitted``: False where the memory admission timed out)."""
    from .. import tracing
    with tracing.wait("wait:window") as sp:
        sp.set("seq", seq)
        sp.set("admitted", True)
        gate.acquire(seq)
        if mem is not None and nbytes > 0:
            # gate doubles as the cancel signal: a torn-down pipeline
            # must not leave a worker waiting forever on admission it
            # will never get (the consumer that would release bytes is
            # gone)
            # daft-lint: allow(memory-admission-leak) -- the admitted
            # bytes transfer into the returned Slot by design
            # (acquire-on-submit, release-on-drain); the device-slot-leak
            # contract proves every acquire_slot caller releases or hands
            # the Slot off whole
            if not mem.try_acquire(
                    nbytes, deadline=time.monotonic() + _ADMIT_DEADLINE_S,
                    cancel=gate):
                if gate.is_set():
                    gate.slot_released()
                    raise PipelineAborted()
                _count("admission_timeouts")
                nbytes = 0
                sp.set("admitted", False)
    return Slot(gate, mem, nbytes if mem is not None else 0, seq)


def release_slot(slot: Optional[Slot]) -> None:
    """Release a slot's admission + window occupancy. Idempotent — safe
    to call from both the drain path and teardown."""
    if slot is None or slot.released:
        return
    slot.released = True
    if slot.mem is not None and slot.nbytes > 0:
        slot.mem.release(slot.nbytes)
    slot.gate.slot_released()


# ------------------------------------------------------------- the driver

#: process-wide pipeline counters (bench evidence): slots run, stage
#: seconds, serial-equivalent vs pipelined wall
_counters_lock = threading.Lock()
_counters: Dict[str, float] = {}


def _count(key: str, v: float = 1.0) -> None:
    with _counters_lock:
        _counters[key] = _counters.get(key, 0) + v


def counters_snapshot() -> Dict[str, float]:
    with _counters_lock:
        return dict(_counters)


def reset_counters() -> None:
    with _counters_lock:
        _counters.clear()


class InflightItem:
    """A submit callback's in-flight device work: the acquired Slot, an
    opaque dispatch token for the drain callback, and the submit-stage
    wall (overlap accounting).  Submit callbacks that route an item to
    the host return a plain value instead — only InflightItems count
    against the window and the overlap ledger."""

    __slots__ = ("slot", "token", "sub_s", "t_dispatched_us")

    def __init__(self, slot: Optional[Slot], token, sub_s: float = 0.0,
                 t_dispatched_us: int = 0):
        self.slot = slot
        self.token = token
        self.sub_s = sub_s
        self.t_dispatched_us = t_dispatched_us


def run_pipelined(items: Iterator, submit: Callable, drain: Callable, *,
                  window: int, width: Optional[int] = None,
                  poll: Optional[Callable] = None) -> Iterator:
    """Drive the bounded-window async device pipeline.

    ``submit(item, seq, gate) -> InflightItem | host result`` runs on
    the dedicated submit pool: host-side encode + asynchronous device
    dispatch, acquiring an in-flight Slot (``acquire_slot(gate, seq,
    mem, bytes)``) for device work, or any plain value for host-routed
    items (which never touch the window — a host-heavy stream keeps the
    pool's full parallelism).  ``drain(ret, seq) -> result`` runs on
    the consumer thread: ONE batched fetch + decode for InflightItems,
    passthrough for host values.  Results yield in submission order.
    ``poll`` (the executor's cancellation poll) runs before each drain.

    Overlap comes from the three stages living on three threads: while
    the consumer blocks in slot N's fetch, slot N+1 computes on device
    and slot N+2 encodes on the pool.  Teardown (exception, early
    close, cancellation) aborts the gate, waits out in-flight submits,
    and releases every undrained slot — the admission-leak and
    cancellation tests pin this."""
    from .. import observability as obs
    from .. import tracing

    gate = WindowGate(window)
    pool = _pipe_pool()
    # (future, seq, what the submit carried: traced, a stamp that says
    # when the worker was done)
    pending = collections.deque()
    it = iter(items)
    seq_next = [0]
    # ACTIVE wall only: time the driver spends working (or waiting on
    # its own stages), excluding the stretches it sits suspended at
    # `yield` while downstream operators run — charging those would
    # dilute overlap_x toward zero on consumer-bound queries
    active_s = [0.0]
    serial_s = [0.0]
    slots_run = [0]
    if width is None:
        import os
        width = max((os.cpu_count() or 4), 4) * 2
    width = max(width, window + 1)
    # adaptive enqueue cap: device submits past the window BLOCK in
    # gate.acquire while holding a submit-pool thread, so a pipeline
    # must not park `width` of them — concurrent (serving) or stacked
    # (push-executor stage) pipelines sharing the bounded pool could
    # starve each other's head futures. Start at window+2 (a
    # device-heavy stream never blocks more than ~2 threads) and grow
    # toward full width only as HOST-routed results prove the stream
    # doesn't occupy slots.
    cap = [min(width, window + 2)]

    def _enqueue() -> bool:
        try:
            item = next(it)
        except StopIteration:
            return False
        seq = seq_next[0]
        seq_next[0] += 1
        stamp = obs.submit_attribution("devpipe")
        fut = pool.submit(obs.run_attributed, stamp, submit, item, seq,
                          gate)
        pending.append((fut, seq, stamp))
        return True

    def _fill() -> None:
        while len(pending) < cap[0] and _enqueue():
            pass

    t_resume = time.perf_counter()
    try:
        _fill()
        while pending:
            fut, seq, stamp = pending.popleft()
            try:
                # the consumer blocked on the head of the window
                with tracing.wait("wait:result") as sp:
                    sp.set("seq", seq)
                    try:
                        ret = fut.result()
                    finally:
                        sp.handed(getattr(stamp, "done_ns", 0))
            except PipelineAborted:
                gate.note_drained(seq)
                continue
            slot = ret.slot if isinstance(ret, InflightItem) else None
            try:
                if poll is not None:
                    poll()
                t0 = time.perf_counter()
                result = drain(ret, seq)
                if isinstance(ret, InflightItem):
                    serial_s[0] += ret.sub_s + (time.perf_counter() - t0)
                    slots_run[0] += 1
                else:
                    # host-routed item: it held no slot, so the stream
                    # can afford more in-flight futures
                    cap[0] = min(width, cap[0] * 2)
            finally:
                release_slot(slot)
                gate.note_drained(seq)
            active_s[0] += time.perf_counter() - t_resume
            yield result
            t_resume = time.perf_counter()
            _fill()
    finally:
        active_s[0] += time.perf_counter() - t_resume
        gate.abort()
        for fut, seq, _ in pending:
            if fut.cancel():
                continue
            try:
                ret = fut.result()
                if isinstance(ret, InflightItem):
                    release_slot(ret.slot)
            except BaseException:
                pass  # the submit body released its own slot
        if slots_run[0] > 0:
            _count("slots", slots_run[0])
            _count("runs")
            _count("serial_equiv_s", serial_s[0])
            _count("wall_s", active_s[0])
            # MFU-ledger overlap evidence: serial-equivalent stage
            # seconds vs the pipeline's ACTIVE wall, per dispatch family
            from . import costmodel
            costmodel.ledger_record("pipeline", dispatches=slots_run[0],
                                    seconds=active_s[0],
                                    serial_seconds=serial_s[0])


# ------------------------------------------------------- pipeline spans

def upload_span(seq: int, window: int):
    """``device:submit`` span covering a slot's submit stage: resolve
    (load, encode, put; each a span of its own inside) and the async
    dispatch, on its own lane with the in-flight slot id annotated —
    perfetto shows the overlap (or its absence) directly.  Keys are
    deterministic (morsel sequence), so chaos runs replay bit-identical
    span ids."""
    from .. import tracing
    return tracing.span("device:submit", key=f"devpipe.up.{seq}",
                        attrs={"slot": seq % max(window, 1), "seq": seq},
                        lane="dev:upload")


def note_compute_span(seq: int, window: int, t_dispatched_us: int) -> None:
    """``device:inflight`` span from dispatch completion to drain start:
    how long the slot's results waited for the host to come for them.
    Not device time (the device trace has that: a few percent of it).
    Emitted at drain time, into the recorder only (the host never blocks
    mid-flight to observe the device)."""
    from .. import tracing
    ctx = tracing.current()
    if ctx is None or not t_dispatched_us:
        return
    rec = ctx.recorder
    now = rec.now_us()
    rec.add("device:inflight", rec.unique_span_id(f"devpipe.comp.{seq}"),
            ctx.span_id, t_dispatched_us,
            max(now - t_dispatched_us, 0),
            attrs={"slot": seq % max(window, 1), "seq": seq},
            lane="dev:compute")


def download_span(seq: int, window: int):
    """``device:drain`` span covering a slot's drain stage: the batched
    fetch and the decode (``device:fetch``, ``device:decode`` inside)."""
    from .. import tracing
    return tracing.span("device:drain", key=f"devpipe.down.{seq}",
                        attrs={"slot": seq % max(window, 1), "seq": seq},
                        lane="dev:download")


def now_us() -> int:
    from .. import tracing
    ctx = tracing.current()
    return ctx.recorder.now_us() if ctx is not None else 0


# ------------------------------------------- device-resident hand-off

#: bounded LRU of decoded-output device planes, keyed by id(Series) with
#: a weakref reaper — a fragment output consumed by another device op
#: (fragment→join, fragment→topk) re-enters the device without a host
#: round trip.  Strong refs here pin HBM, so the budget is a slice of
#: the HBM cache's.
# RLock: the weakref reaper (_drop) can fire from GC while this thread
# already holds the lock (e.g. an eviction drops the last strong ref)
_res_lock = threading.RLock()
_resident: "collections.OrderedDict" = collections.OrderedDict()
_res_bytes = [0]
_res_counters: Dict[str, int] = {"hits": 0, "misses": 0, "evictions": 0}


def _res_budget() -> int:
    from ..analysis import knobs
    return int(knobs.env_bytes("DAFT_TPU_HBM_CACHE_BYTES")) // 8


def residency_counters() -> Dict[str, int]:
    with _res_lock:
        out = dict(_res_counters)
        out["entries"] = len(_resident)
        out["bytes"] = int(_res_bytes[0])
    return out


def reset_residency() -> None:
    with _res_lock:
        _resident.clear()
        _res_bytes[0] = 0
        for k in _res_counters:
            _res_counters[k] = 0


def _entry_nbytes(data, validity) -> int:
    try:
        return int(data.nbytes) + int(validity.nbytes)
    except Exception:
        return 0


def note_decoded(series, data, validity, dictionary, count: int,
                 capacity: int) -> None:
    """Register a decoded device column's planes for residency reuse.
    Called from ``column.decode_column`` when the planes are real device
    arrays and the pipeline is enabled; lossy encodings (decimals) must
    not register — reuse has to be bit-identical with a re-encode."""
    import weakref
    key = id(series)
    try:
        ref = weakref.ref(series, lambda _r, _k=key: _drop(_k))
    except TypeError:
        return
    nbytes = _entry_nbytes(data, validity)
    with _res_lock:
        if key in _resident:
            return
        budget = _res_budget()
        if nbytes > budget:
            return
        while _res_bytes[0] + nbytes > budget and _resident:
            _, old = _resident.popitem(last=False)
            _res_bytes[0] -= old[6]
            _res_counters["evictions"] += 1
        _resident[key] = (ref, data, validity, dictionary, count,
                          capacity, nbytes)
        _res_bytes[0] += nbytes


def _drop(key: int) -> None:
    with _res_lock:
        ent = _resident.pop(key, None)
        if ent is not None:
            _res_bytes[0] -= ent[6]


def resident_planes(series, n: int):
    """``(data, validity, dictionary, capacity)`` for a Series whose
    device planes are still resident, or None.  ``validity`` comes back
    masked to the live rows (one tiny jitted AND per reuse — the planes
    beyond the decoded count carry kernel garbage, where a fresh encode
    zero-pads)."""
    if not _resident:     # lock-free fast path: nothing ever registered
        return None
    if inflight_window() <= 0:
        # chaos-serialize / fault-plan degradation (or an explicit
        # window 0) must replay the VERBATIM synchronous chain — a
        # reuse hit would skip the upload events the replay contract
        # expects, even though planes registered before degradation
        # are still sitting in the registry
        return None
    key = id(series)
    with _res_lock:
        ent = _resident.get(key)
        if ent is None:
            _res_counters["misses"] += 1
            return None
        ref, data, validity, dictionary, count, capacity, _nb = ent
        if ref() is not series or count != n:
            _res_counters["misses"] += 1
            return None
        _resident.move_to_end(key)
        _res_counters["hits"] += 1
    if count == capacity:
        # no garbage tail to mask (rows [count:capacity) is empty) —
        # skip the identity dispatch on exactly the path built to
        # avoid round trips
        return data, validity, dictionary, capacity
    return data, _masked_validity(validity, n), dictionary, capacity


_mask_cache: Dict[int, object] = {}


def _masked_validity(validity, n: int):
    import jax
    import jax.numpy as jnp
    from ..analysis import retrace_sanitizer
    fn = _mask_cache.get(0)
    if fn is None:
        fn = jax.jit(
            lambda v, k: v & (jnp.arange(v.shape[0]) < k))
        _mask_cache[0] = fn
    # one trace per validity-plane capacity class (n rides as a traced
    # scalar, so literal-different live counts re-enter the program)
    with retrace_sanitizer.dispatch_scope(
            "pipeline.mask", (int(validity.shape[0]),)):
        return fn(validity, n)
