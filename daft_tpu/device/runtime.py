"""Device dispatch: decides per-batch whether an op runs as XLA or on host.

This is the dispatch seam the reference has per-operator
(SURVEY.md §7 hard-part #2: "keep a principled host-fallback per operator").
Returns None from ``try_*`` → caller falls back to the Arrow host tier.

Controls:
- ``DAFT_TPU_DEVICE=0`` disables the device tier entirely.
- ``DAFT_TPU_DEVICE_MIN_ROWS`` (default 0) bypasses the device for small
  batches where transfer overhead dominates.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from .. import tracing
from ..datatype import DataType
from ..expressions.expressions import Expression
from ..schema import Schema
from ..series import Series
from . import column as dcol
from . import compiler, kernels

_DEVICE_AGGS = {"sum", "mean", "min", "max", "count", "stddev", "var",
                "any_value", "bool_and", "bool_or"}


def device_enabled() -> bool:
    from ..analysis import knobs
    if not knobs.env_bool("DAFT_TPU_DEVICE"):
        return False
    from . import backend
    return backend.device_ready()


# ----------------------------------------------- loud device failures

logger = logging.getLogger(__name__)


def _is_resource_exhaustion(exc: BaseException) -> bool:
    """Device memory (or another device resource) ran out — the one kind
    of device failure a host run may stand in for."""
    return isinstance(exc, MemoryError) or "RESOURCE_EXHAUSTED" in str(exc)


def device_failed(site: str, exc: BaseException) -> None:
    """The ONE way a device-path exception may turn into a host run.

    Every ``except`` that used to swallow a device error and return None
    (the executor's fused-agg / region / join-agg / mesh sites,
    ``fragment``'s batch submit/drain) calls this instead. Lowering,
    tracing and compile errors (``NotImplementedError``, ``TypeError``,
    Mosaic/XLA refusals …) are bugs: re-raised, the query fails. Only
    device resource exhaustion returns, and then it is counted per site,
    the first exception text is kept, both surface in
    ``costmodel.ledger_snapshot()["device_failures"]`` and
    ``explain(analyze=True)``, and the site's first occurrence is logged
    at WARNING — a "device" run is never silently a host run."""
    if not _is_resource_exhaustion(exc):
        raise exc
    from . import costmodel
    from .. import observability as obs
    text = f"{type(exc).__name__}: {exc}"
    if len(text) > 600:
        text = text[:600] + " …"
    if costmodel.failure_record(site, text):
        logger.warning(
            "daft-tpu: device work at %s failed and runs on the HOST "
            "instead (first occurrence; counted in ledger_snapshot()"
            "['device_failures']): %s", site, text)
    obs.bump_plane("device_failures", site, 1)


def device_failures() -> dict:
    """``{site: {"count", "first_error"}}`` of degraded device failures."""
    from . import costmodel
    return costmodel.failures_snapshot()


def _is_transfer_bound() -> bool:
    """True when the device sits behind a host↔device link (an
    accelerator) rather than sharing host memory (CPU backend)."""
    from . import backend
    return (backend.backend_name() or "cpu") not in ("cpu",)


def _min_rows() -> int:
    from ..analysis import knobs
    env = knobs.env_int("DAFT_TPU_DEVICE_MIN_ROWS", default=None)
    if env is not None:
        return env
    # on a transfer-bound link, tiny batches are pure round-trip overhead
    return 4096 if _is_transfer_bound() else 0


def _series_nbytes(s: Series) -> int:
    try:
        return int(s.to_arrow().nbytes)
    except Exception:
        return 9 * len(s)


def _batch_cols_nbytes(batch, cols) -> int:
    return sum(_series_nbytes(batch.get_column(c)) for c in cols)


def _min_rows_override(n_rows: int) -> Optional[bool]:
    """An explicit DAFT_TPU_DEVICE_MIN_ROWS keeps its documented meaning on
    every backend (device runs at or above that many rows); FORCE trumps it.
    None → no override, consult the cost model."""
    from ..analysis import knobs
    env = knobs.env_int("DAFT_TPU_DEVICE_MIN_ROWS", default=None)
    if env is None or knobs.env_is_set("DAFT_TPU_DEVICE_FORCE"):
        return None
    return n_rows >= max(env, 1)


def _row_output_profitable(batch, needs_cols, n_outputs: int,
                           out_bytes_per_row: int = 8) -> bool:
    """Cost gate for ops whose OUTPUT is row-shaped (projection values, sort
    permutations, filter masks): the measured-link cost model compares
    transfer+RTT against a host vector pass (``costmodel.py``). On a
    slow link (~40 MB/s) this picks host, on a fast local link it picks
    the device — same code, measured numbers. Reduction-shaped ops are gated
    separately (their outputs are packed group blocks). An explicit
    DAFT_TPU_DEVICE_MIN_ROWS keeps its documented meaning (the device runs
    at or above that many rows) on every backend."""
    from . import costmodel
    n_rows = len(batch)
    ov = _min_rows_override(n_rows)
    if ov is not None:
        return ov
    bytes_up = dcol.encoded_nbytes(batch, needs_cols)
    bytes_down = n_rows * out_bytes_per_row * max(n_outputs, 1)
    return costmodel.row_output_op_wins(
        bytes_up, bytes_down,
        host_bytes=_batch_cols_nbytes(batch, needs_cols))


_projection_cache: Dict[Tuple, compiler.Compiled] = {}
# single-flight compile coordination for the serving plane: N concurrent
# identical cold queries must produce ONE trace/lowering, with the other
# N-1 waiting on the winner instead of burning N duplicate compiles
_compile_lock = threading.Lock()
_compile_inflight: Dict[Tuple, threading.Event] = {}
_compile_counters: Dict[str, int] = {"hits": 0, "misses": 0, "compiles": 0,
                                     "waits": 0}


def compile_cache_counters() -> Dict[str, int]:
    """Process-wide projection-compile cache counters (the serving
    bench's evidence that jitted fragments are reused across
    submissions)."""
    with _compile_lock:
        out = dict(_compile_counters)
    out["entries"] = len(_projection_cache)
    return out


def _schema_key(schema: Schema) -> Tuple:
    return tuple((f.name, hash(f.dtype)) for f in schema)


def _get_compiled(exprs: List[Expression], schema: Schema
                  ) -> Optional[compiler.Compiled]:
    key = (tuple(e._key() for e in exprs), _schema_key(schema))
    while True:
        with _compile_lock:
            hit = _projection_cache.get(key)
            if hit is not None:
                _compile_counters["hits"] += 1
                return hit
            ev = _compile_inflight.get(key)
            if ev is None:
                _compile_inflight[key] = threading.Event()
                _compile_counters["misses"] += 1
                break
            _compile_counters["waits"] += 1
        # someone else is compiling this projection — wait, then re-check
        # (compile failures don't cache, so the loop may compile after all)
        ev.wait()
    try:
        try:
            c = compiler.compile_projection(exprs, schema)
        except (compiler.NotCompilable, NotImplementedError, ValueError,
                TypeError, KeyError, OverflowError):
            return None
        with _compile_lock:
            _projection_cache[key] = c
            _compile_counters["compiles"] += 1
        return c
    finally:
        with _compile_lock:
            ev2 = _compile_inflight.pop(key, None)
        if ev2 is not None:
            ev2.set()


def _string_out_source(e: Expression) -> Optional[str]:
    """If expr output is a passthrough of a string column, its source name."""
    inner = e._unalias()
    return inner.params[0] if inner.op == "col" else None


def _scalar_planes(c: compiler.Compiled, dt: dcol.DeviceTable) -> list:
    """The program's runtime scalars for ``dt``, on the host: each a
    function of one column's own dictionary."""
    planes = []
    for spec in c.scalar_specs:
        d = dt.columns[spec.col].dictionary
        if d is None:
            d = pa.array([], type=pa.large_string())
        planes.append(spec.fn(d))
    return planes


def _prep_scalars(c: compiler.Compiled, dt: dcol.DeviceTable):
    # beside the table's planes, so a dispatch on another chip moves
    # nothing from the default one
    return tuple(dcol.put_plane(x, dt.chip) for x in _scalar_planes(c, dt))


def encode_for(c: compiler.Compiled, batch):
    """Encode a batch's needed columns for a compiled program.
    Returns (DeviceTable, arrays, valids, scalars)."""
    dt = dcol.encode_batch(batch, c.needs_cols)
    arrays = {n: col.data for n, col in dt.columns.items()}
    valids = {n: col.validity for n, col in dt.columns.items()}
    scalars = _prep_scalars(c, dt)
    return dt, arrays, valids, scalars


def decode_group_key(e: Expression, field, kv, km, dt: dcol.DeviceTable,
                     count: int) -> Series:
    """Decode one group-key output, routing string dictionaries from the
    encoded source column."""
    dictionary = None
    if field.dtype.is_string() or field.dtype.is_binary():
        dictionary = dt.columns[_string_out_source(e)].dictionary
    dc = dcol.DeviceColumn(kv, km, field.dtype, dictionary)
    return dcol.decode_column(field.name, dc, count)


def _run_compiled(c: compiler.Compiled, batch, exprs: List[Expression]):
    """Encode inputs, run the fused program, return per-expr device outputs."""
    from ..analysis import retrace_sanitizer
    dt, arrays, valids, scalars = encode_for(c, batch)
    # declared trace signature (dispatch_registry: compiler.projection):
    # one trace per compiled projection x capacity class x scalar-plane
    # shapes — never per raw row count
    with retrace_sanitizer.dispatch_scope(
            "compiler.projection",
            (id(c), dt.capacity, tuple(s.shape for s in scalars))), \
            tracing.launch("compiler.projection", dt.chip or 0):
        outs = c.fn(arrays, valids, dt.row_mask, scalars)
    return dt, outs


def try_eval_projection(batch, exprs: List[Expression]):
    """Full projection on device; None → host fallback."""
    from ..recordbatch import RecordBatch
    if not device_enabled():
        return None
    schema = batch.schema
    out_fields = []
    try:
        for e in exprs:
            out_fields.append(e.to_field(schema))
    except Exception:
        return None
    # every output must be decodable
    for e, f in zip(exprs, out_fields):
        if f.dtype.is_string() or f.dtype.is_binary():
            if _string_out_source(e) is None:
                return None
        elif f.dtype.device_repr() is None:
            return None
    c = _get_compiled(exprs, schema)
    if c is None:
        return None
    if not _row_output_profitable(batch, c.needs_cols, len(exprs)):
        return None
    for name in c.needs_cols:
        if batch.get_column(name).is_pyobject():
            return None
    import time as _time

    from . import costmodel
    t0 = _time.perf_counter()
    dt, outs = _run_compiled(c, batch, exprs)
    n = len(batch)
    named = []
    for e, f, (val, valid) in zip(exprs, out_fields, outs):
        dictionary = None
        if f.dtype.is_string() or f.dtype.is_binary():
            dictionary = dt.columns[_string_out_source(e)].dictionary
        named.append((f.name,
                      dcol.DeviceColumn(val, valid, f.dtype, dictionary)))
    # ONE batched transfer for every output plane (round 17) — and each
    # decoded column registers for device-resident hand-off, so a device
    # consumer (argsort/topk, grouped agg) skips the re-upload
    cols = dcol.decode_columns(named, n)
    costmodel.ledger_record(
        "projection", rows=n,
        nbytes=dcol.encoded_nbytes(batch, c.needs_cols)
        + n * 8 * max(len(exprs), 1),
        seconds=_time.perf_counter() - t0)
    return RecordBatch.from_series(cols)


def try_eval_predicate(batch, predicate: Expression) -> Optional[np.ndarray]:
    """Predicate → host boolean mask (for arrow-side filtering)."""
    if not device_enabled():
        return None
    c = _get_compiled([predicate], batch.schema)
    if c is None:
        return None
    if not _row_output_profitable(batch, c.needs_cols, 1,
                                  out_bytes_per_row=1):
        return None
    for name in c.needs_cols:
        if batch.get_column(name).is_pyobject():
            return None
    import time as _time

    from . import costmodel
    t0 = _time.perf_counter()
    dt, outs = _run_compiled(c, batch, [predicate])
    val, valid = outs[0]
    mask = np.asarray(jax.device_get(val & valid))[:len(batch)]
    costmodel.ledger_record(
        "predicate", rows=len(batch),
        nbytes=dcol.encoded_nbytes(batch, c.needs_cols) + len(batch),
        seconds=_time.perf_counter() - t0)
    return mask.astype(bool)


def try_argsort(key_series: List[Series], descending: List[bool],
                nulls_first: List[bool]) -> Optional[np.ndarray]:
    from . import costmodel
    if not device_enabled() or not key_series:
        return None
    n = len(key_series[0])
    if n < 2:
        return None
    ov = _min_rows_override(n)
    if ov is False:
        return None
    if ov is None and not costmodel.argsort_wins(
            n, sum(_series_nbytes(s) for s in key_series), len(key_series)):
        return None
    for s in key_series:
        if s.is_pyobject():
            return None
        dt = s.datatype()
        if not (dt.is_device_representable() or dt.is_string()):
            return None
    cap = dcol.bucket_capacity(n)
    try:
        # allow_resident: a key column decoded off a device projection
        # re-enters without re-uploading (argsort never donates planes)
        cols = [dcol.encode_series(s, cap, allow_resident=True)
                for s in key_series]
    except (ValueError, pa.ArrowInvalid):
        return None
    mask = np.zeros(cap, dtype=np.bool_)
    mask[:n] = True
    import time as _time

    from ..analysis import retrace_sanitizer
    from . import mfu
    t0 = _time.perf_counter()
    desc = tuple(bool(d) for d in descending)
    nf = tuple(bool(x) for x in nulls_first)
    mask = jnp.asarray(mask)  # ahead of the launch span: a copy, no launch
    with retrace_sanitizer.dispatch_scope(
            "kernels.argsort",
            (tuple(str(c.data.dtype) for c in cols), cap, desc, nf)), \
            tracing.launch("kernels.argsort"):
        perm = kernels.argsort_kernel(
            tuple(c.data for c in cols), tuple(c.validity for c in cols),
            mask, desc, nf)
    out = np.asarray(jax.device_get(perm))[:n].astype(np.int64)
    costmodel.ledger_record(
        "argsort", rows=n,
        nbytes=mfu.argsort_bytes_model(cap, [c.data.dtype for c in cols]),
        seconds=_time.perf_counter() - t0)
    return out


def try_agg(batch, to_agg: List[Expression], group_by: List[Expression]):
    """Grouped/global aggregation on device; None → host fallback."""
    from ..aggs import split_agg_expr
    from ..recordbatch import RecordBatch
    from . import costmodel
    if not device_enabled() or len(batch) < max(_min_rows(), 1):
        return None
    schema = batch.schema
    try:
        specs = [split_agg_expr(e) for e in to_agg]
    except ValueError:
        return None
    for op, child, name, params in specs:
        if op not in _DEVICE_AGGS:
            return None
        if op == "count" and params and params[0] != "valid":
            return None
    try:
        out_fields = [e.to_field(schema) for e in to_agg]
        key_fields = [e.to_field(schema) for e in group_by]
    except Exception:
        return None
    for e, f in zip(group_by, key_fields):
        if f.dtype.is_string() or f.dtype.is_binary():
            if _string_out_source(e) is None:
                return None
        elif f.dtype.device_repr() is None:
            return None
    for (op, child, _, _), f in zip(specs, out_fields):
        if f.dtype.is_string() or f.dtype.is_binary():
            if child is None or _string_out_source(child) is None:
                return None
        elif f.dtype.device_repr() is None:
            return None

    # compile keys + agg children as one projection
    child_exprs = []
    for i, (op, child, name, params) in enumerate(specs):
        child_exprs.append((child if child is not None
                            else Expression._lit(True)).alias(f"__in{i}__"))
    proj = list(group_by) + child_exprs
    c = _get_compiled(proj, schema)
    if c is None:
        return None
    for nm in c.needs_cols:
        if batch.get_column(nm).is_pyobject():
            return None
    # in-memory batch: no HBM-cache identity, the upload is one-shot
    from .fragment import _OUT_CAP0, packed_bytes_per_group
    packed_out = packed_bytes_per_group(len(group_by),
                                        len(to_agg)) * _OUT_CAP0
    if not costmodel.agg_upload_wins(
            dcol.encoded_nbytes(batch, c.needs_cols),
            packed_out, cacheable=False,
            host_bytes=_batch_cols_nbytes(batch, c.needs_cols)):
        return None

    dt, outs = _run_compiled(c, batch, proj)
    nk = len(group_by)
    key_outs = outs[:nk]
    val_outs = outs[nk:]
    ops = tuple(s[0] for s in specs)

    def bcast(v, m):
        if v.ndim == 0:
            v = jnp.broadcast_to(v, dt.row_mask.shape)
            m = jnp.broadcast_to(m, dt.row_mask.shape)
        return v, m

    import time as _time

    from ..analysis import retrace_sanitizer
    if nk == 0:
        t0 = _time.perf_counter()
        vals, valids = zip(*[bcast(v, m) for v, m in val_outs]) if val_outs \
            else ((), ())
        with retrace_sanitizer.dispatch_scope(
                "kernels.grouped_agg",
                ("global", ops, tuple(str(v.dtype) for v in vals),
                 dt.capacity)), \
                tracing.launch("kernels.grouped_agg", dt.chip or 0):
            results = kernels.global_agg_kernel(tuple(vals), tuple(valids),
                                                dt.row_mask, ops)
        # ONE batched transfer for all scalar results (round 17: the
        # per-scalar get pair cost 2 RTTs per aggregate)
        from . import pipeline as dpipe
        host_results = dpipe.fetch_host(results)
        costmodel.ledger_record(
            "global_agg", rows=len(batch),
            nbytes=(len(ops) + 1) * dt.capacity * 4,
            seconds=_time.perf_counter() - t0)
        cols = []
        for (op, child, name, params), f, (rv, rm) in zip(
                specs, out_fields, host_results):
            v = np.asarray(rv).reshape(1)
            m = np.asarray(rm).reshape(1)
            cols.append(_decode_scalar(name, f.dtype, v, m))
        return RecordBatch.from_series(cols)

    keys_b = [bcast(v, m) for v, m in key_outs]
    vals_b = [bcast(v, m) for v, m in val_outs]
    from . import mfu
    t0 = _time.perf_counter()
    karg = (tuple(v for v, _ in keys_b), tuple(m for _, m in keys_b),
            tuple(v for v, _ in vals_b), tuple(m for _, m in vals_b),
            dt.row_mask, ops)
    kdtypes = tuple(str(v.dtype) for v, _ in keys_b)
    vdtypes = tuple(str(v.dtype) for v, _ in vals_b)
    with retrace_sanitizer.dispatch_scope(
            "kernels.grouped_agg", (ops, kdtypes, vdtypes, dt.capacity)), \
            tracing.launch("kernels.grouped_agg", dt.chip or 0):
        out_keys, out_kvalids, out_vals, out_valids, gcount = \
            kernels.grouped_agg_kernel(*karg)
    costmodel.log_strategy_decision("groupby_strategy", "sort",
                                    rows=len(batch), out_cap=dt.capacity)
    # ONE batched transfer for the group count and every output plane
    # (round 17: this path issued 1 + 2×(nk+nvals) sequential gets)
    from . import pipeline as dpipe
    g, out_keys, out_kvalids, out_vals, out_valids = dpipe.fetch_host(
        (gcount, out_keys, out_kvalids, out_vals, out_valids))
    g = int(g)
    # bytes-bound: no MXU flops to claim
    _, nbytes = mfu.grouped_agg_models(dt.capacity, dt.capacity, nk,
                                       len(ops))
    costmodel.ledger_record("grouped_agg", rows=len(batch), nbytes=nbytes,
                            seconds=_time.perf_counter() - t0,
                            strategy="sort")
    cols = []
    for e, f, kv, km in zip(group_by, key_fields, out_keys, out_kvalids):
        cols.append(decode_group_key(e, f, kv, km, dt, g))
    for (op, child, name, params), f, vv, vm in zip(specs, out_fields,
                                                    out_vals, out_valids):
        dictionary = None
        if f.dtype.is_string() or f.dtype.is_binary():
            dictionary = dt.columns[_string_out_source(child)].dictionary
        dc = dcol.DeviceColumn(vv, vm, f.dtype, dictionary)
        cols.append(dcol.decode_column(name, dc, g))
    return RecordBatch.from_series(cols)


def _decode_scalar(name: str, dtype: DataType, v: np.ndarray, m: np.ndarray
                   ) -> Series:
    # v/m are already host-side numpy (fetched in the caller's single packed
    # transfer) — wrapping them in jnp.asarray would re-upload to the device
    # only for decode_column to fetch them straight back: 2 extra RTTs per
    # scalar (~0.2 s each on a 100 ms-RTT link; this was the whole Q6 regression)
    dc = dcol.DeviceColumn(v, m, dtype, None)
    return dcol.decode_column(name, dc, 1)
