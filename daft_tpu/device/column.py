"""Host⇄device column transport: Series/RecordBatch → DeviceTable and back.

The DeviceTable is the device twin of a RecordBatch (SURVEY.md §7.1
"DeviceColumnSet"): a dict of fixed-width JAX arrays plus validity planes and a
live-row mask, padded to a power-of-two capacity bucket.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

import jax
import jax.numpy as jnp

from ..datatype import DataType
from ..schema import Field, Schema
from ..series import Series

jax.config.update("jax_enable_x64", True)

# Persistent compile-cache configuration lives in backend.py (TPU-only:
# the TPU executables survive process restarts and machine moves, while
# CPU AOT artifacts are machine-feature-pinned — a cache written on one
# host reloads on another with SIGILL-risk warnings and forces a native
# recompile per (bucket, dtype, op) shape that burned minutes per SF100
# scan before the guard). column.py must not configure it at import:
# this module loads before the backend probe decides cpu vs tpu.

_MIN_CAPACITY = 16

# parsed DAFT_TPU_SIZE_CLASSES memo: (raw spec value, (step, explicit
# ladder)) — the knob is read per bucket_capacity call (morsel-rate), so
# the parse is cached against the raw string
_ladder_memo: "tuple" = (None, (2, None))
# context handle memo: get_context() takes the process-wide context
# lock on EVERY call — cache the singleton so the env-unset default
# path stays lock-free at morsel rate (the execution_config attr read
# itself is a GIL-atomic load of whatever config is current)
_ctx_memo = None


def _config_spec() -> str:
    global _ctx_memo
    if _ctx_memo is None:
        try:
            from ..context import get_context
            # daft-lint: allow(unguarded-global-mutation) -- benign
            # last-wins memo of the process context singleton
            _ctx_memo = get_context()
        except Exception:
            return "pow2"
    try:
        return _ctx_memo.execution_config.tpu_size_classes or "pow2"
    except Exception:
        return "pow2"


def _ladder() -> "tuple":
    """(geometric step, explicit capacities|None) from the
    ``DAFT_TPU_SIZE_CLASSES`` ladder spec: ``pow2`` (default) /
    ``pow4`` / an explicit comma list.  The env var is the per-process
    override; unset, the per-query ``ExecutionConfig.tpu_size_classes``
    field applies (the registry's config_field contract)."""
    global _ladder_memo
    from ..analysis import knobs
    raw = knobs.env_raw("DAFT_TPU_SIZE_CLASSES") or _config_spec()
    memo_raw, memo_val = _ladder_memo
    if raw == memo_raw:
        return memo_val
    if raw == "pow2":
        val = (2, None)
    elif raw == "pow4":
        val = (4, None)
    else:
        try:
            caps = sorted({max(int(x), _MIN_CAPACITY)
                           for x in raw.split(",") if x.strip()})
        except ValueError:
            raise ValueError(
                f"DAFT_TPU_SIZE_CLASSES={raw!r}: expected 'pow2', "
                f"'pow4', or a comma list of integer capacities")
        val = (2, tuple(caps) or None)
    # daft-lint: allow(unguarded-global-mutation) -- benign last-wins
    # memo of a pure parse; a racing duplicate computes the same value
    _ladder_memo = (raw, val)
    return val


def bucket_capacity(n: int) -> int:
    """Pad row counts to canonical size-class buckets so literal-
    different row counts re-enter already-jitted programs instead of
    re-tracing.  THE sanctioned chokepoint between row counts and
    shapes: ``rule_shapes`` statically flags any raw count reaching a
    device shape without passing through here.  The ladder is
    power-of-two by default (``DAFT_TPU_SIZE_CLASSES``)."""
    step, explicit = _ladder()
    if explicit is not None:
        for c in explicit:
            if c >= n:
                return c
        c = explicit[-1]
        while c < n:   # above the ladder top: keep doubling
            c <<= 1
        return c
    c = _MIN_CAPACITY
    while c < n:
        c *= step
    return c


def size_classes(max_capacity: int, min_capacity: int = _MIN_CAPACITY
                 ) -> "List[int]":
    """The ladder's capacities in ``[min_capacity, max_capacity]`` — the
    AOT warm-up grid (device/warmup.py) compiles each of these once so
    cold queries land on warm programs."""
    out = []
    c = bucket_capacity(min_capacity)
    while c <= max_capacity:
        out.append(c)
        nxt = bucket_capacity(c + 1)
        if nxt <= c:
            break
        c = nxt
    return out


def _backend() -> str:
    from . import backend
    return backend.backend_name() or "cpu"


def device_np_dtype(dt: DataType) -> np.dtype:
    """The numpy dtype a column of this logical type encodes to on
    device (mirrors ``_np_encode``'s physical lowering) — the AOT
    warm-up grid builds abstract ``ShapeDtypeStruct`` inputs from it.
    Raises ``ValueError`` for non-device-representable types."""
    if dt.is_null() or dt.is_string() or dt.is_binary():
        return np.dtype("int32")      # dict codes / zero payload plane
    if dt.kind == "date":
        return np.dtype("int32")
    if dt.is_boolean():
        return np.dtype("bool")
    if dt.is_temporal():
        return np.dtype("int64")
    rep = np.float64 if dt.is_decimal() \
        else dt.to_physical().device_repr()
    if rep is None:
        raise ValueError(f"{dt!r} is not device-representable")
    d = np.dtype(rep)
    if d == np.float64 and not supports_f64():
        d = np.dtype("float32")
    return d


def supports_f64() -> bool:
    """TPUs have no native f64; compute those columns in f32 on TPU."""
    return _backend() != "tpu"


def is_lossless_device_dtype(dtype: DataType) -> bool:
    """True when the device encoding round-trips bit-exactly: required for
    pure data-movement paths (mesh repartition) where the engine must not
    perturb values. Decimals ride float64 (lossy); float64 itself downcasts
    to float32 on backends without f64."""
    if dtype.is_decimal():
        return False
    if dtype.is_string() or dtype.is_binary():
        return False
    phys = dtype.to_physical()
    if phys.device_repr() is None:
        return False
    if phys.device_repr() == np.float64 and not supports_f64():
        return False
    return True


@dataclass
class DeviceColumn:
    data: jax.Array                  # [capacity]
    validity: jax.Array              # [capacity] bool
    dtype: DataType                  # logical dtype
    dictionary: Optional[pa.Array] = None  # sorted dictionary for code columns

    @property
    def is_coded(self) -> bool:
        return self.dictionary is not None


@dataclass
class DeviceTable:
    columns: Dict[str, DeviceColumn]
    row_mask: jax.Array              # [capacity] bool — live rows
    row_count: int                   # host-side live count
    capacity: int
    #: True for HBM-cache-resident tables — their buffers are SHARED with
    #: the cache and must never be donated to a fused program
    resident: bool = False
    #: the chip that holds every plane (data, validity, row mask), as an
    #: index into ``parallel.mesh.scan_devices()``; None: the default
    #: device, which is where everything lives when one chip is visible
    chip: Optional[int] = None

    def schema(self) -> Schema:
        return Schema([Field(n, c.dtype) for n, c in self.columns.items()])


def put_plane(x, chip: Optional[int] = None) -> jax.Array:
    """One host plane onto the device: the default one, or the chip a
    scan task's table was given (committed there, so the programs that
    read it run there and nothing crosses between chips)."""
    if chip is None:
        return jnp.asarray(x)
    from ..parallel import mesh
    return jax.device_put(x, mesh.scan_devices()[chip])


def _np_encode(s: Series) -> "tuple[np.ndarray, np.ndarray, Optional[pa.Array]]":
    """Series → (values ndarray, validity ndarray, dictionary|None)."""
    arr = s.to_arrow()
    dt = s.datatype()
    n = len(arr)
    validity = np.asarray(pc.is_valid(arr).to_numpy(zero_copy_only=False),
                          dtype=np.bool_)
    if dt.is_null():
        # all-null column: zero payload plane, validity already all-False
        return np.zeros(n, dtype=np.int32), validity, None
    if dt.is_string() or dt.is_binary():
        enc = arr.dictionary_encode()
        d = enc.dictionary
        sort_idx = pc.array_sort_indices(d).to_numpy()
        ranks = np.empty(len(d), dtype=np.int32)
        ranks[sort_idx] = np.arange(len(d), dtype=np.int32)
        codes_raw = pc.fill_null(enc.indices, 0).to_numpy(zero_copy_only=False)
        codes = ranks[np.asarray(codes_raw, dtype=np.int64)] if len(d) else \
            np.zeros(n, dtype=np.int32)
        sorted_dict = d.take(pa.array(sort_idx))
        return codes.astype(np.int32), validity, sorted_dict
    phys = dt.to_physical()
    rep = phys.device_repr()
    if rep is None:
        raise ValueError(f"column {s.name()!r}: {dt!r} is not device-representable")
    if dt.kind == "date":
        arr = arr.cast(pa.int32())
    elif dt.is_temporal():
        arr = arr.cast(pa.int64())
    elif dt.is_decimal():
        arr = arr.cast(pa.float64())
    if dt.is_boolean():
        vals = np.asarray(pc.fill_null(arr, False).to_numpy(zero_copy_only=False),
                          dtype=np.bool_)
    else:
        if not validity.all():
            # fill at the Arrow level so nullable ints don't decay to float64
            arr = pc.fill_null(arr, pa.scalar(0, type=arr.type))
        vals = np.asarray(arr.to_numpy(zero_copy_only=False))
    if vals.dtype == np.float64 and not supports_f64():
        vals = vals.astype(np.float32)
    return vals, validity, None


def encode_series(s: Series, capacity: int,
                  allow_resident: bool = False,
                  chip: Optional[int] = None) -> DeviceColumn:
    # device-resident hand-off (round 17): a series decoded from a device
    # op whose planes are still resident re-enters the device without a
    # host round trip (pipeline.py bounds + reaps the registry).  Opt-in
    # only: the returned planes are SHARED with the registry, so callers
    # that might donate buffers must stay on the fresh-encode path (the
    # all-or-nothing table reuse in encode_batch marks its table
    # ``resident`` instead).
    res = _resident_column(s, capacity) if allow_resident else None
    if res is not None:
        return res
    from .. import tracing
    # the numpy side: planes, validity, dictionary ranks, padding
    with tracing.span("device:encode", lane="device") as sp:
        vals, validity, dictionary = _np_encode(s)
        n = len(vals)
        if n < capacity:
            vals = np.concatenate(
                [vals, np.zeros(capacity - n, dtype=vals.dtype)])
            validity = np.concatenate(
                [validity, np.zeros(capacity - n, dtype=np.bool_)])
        nbytes = int(vals.nbytes) + int(validity.nbytes)
        sp.set("rows", n)
        sp.set("cols", 1)
        sp.set("bytes", nbytes)
    # the host's time in the two puts; the copy's device side is the
    # profile's transfer events. Bytes as the HBM cache counts them.
    with tracing.span("device:put", lane="device",
                      attrs={"bytes": nbytes, "cached": 0,
                             "chip": chip or 0}):
        return DeviceColumn(put_plane(vals, chip),
                            put_plane(validity, chip),
                            s.datatype(), dictionary)


def _resident_column(s: Series, capacity: int) -> Optional[DeviceColumn]:
    """Resident device planes for a decoded series, when their capacity
    matches the requested bucket exactly (encode_batch's table-wide reuse
    handles the larger-bucket case)."""
    from . import pipeline
    hit = pipeline.resident_planes(s, len(s))
    if hit is None:
        return None
    data, validity, dictionary, cap = hit
    if cap != capacity:
        return None
    return DeviceColumn(data, validity, s.datatype(), dictionary)


def encoded_nbytes(batch, columns) -> int:
    """Wire/HBM bytes these columns occupy once encoded: device-repr
    itemsize (f64→f32 on chips without f64, strings→i32 dict codes) times
    the power-of-two bucket capacity, plus one validity byte per slot.
    This is what uploads actually cost and what the HBM cache stores —
    ``_batch_cols_nbytes``'s raw-Arrow bytes overstated f64-heavy TPC-H
    columns ~2×, which both inflated upload-cost estimates and made the
    cache-fit check refuse workloads that fit (r4: SF10 Q1 never
    invested)."""
    n = len(batch)
    cap = bucket_capacity(max(n, 1))
    total = 0
    for nm in columns:
        dt = batch.get_column(nm).datatype()
        if dt.is_string() or dt.is_binary():
            itemsize = 4  # dictionary codes; the dictionary stays host-side
        else:
            rep = dt.to_physical().device_repr()
            if rep is None:
                itemsize = 8
            elif rep == np.float64 and not supports_f64():
                itemsize = 4
            else:
                itemsize = np.dtype(rep).itemsize
        total += cap * (itemsize + 1)  # +1: validity mask
    return total


def encode_batch(batch, columns: Optional[List[str]] = None,
                 chip: Optional[int] = None) -> DeviceTable:
    """``chip``: where a scan task's table goes (see ``DeviceTable.chip``);
    planes handed over from an earlier device op lie on the default
    device, so only an unplaced table may reuse them."""
    names = columns if columns is not None else batch.column_names()
    n = len(batch)
    cap = bucket_capacity(n)
    resident = _resident_batch(batch, names, n, cap) if chip is None \
        else None
    if resident is not None:
        return resident
    cols = {nm: encode_series(batch.get_column(nm), cap, chip=chip)
            for nm in names}
    mask = np.zeros(cap, dtype=np.bool_)
    mask[:n] = True
    from .. import tracing
    # the live-row mask is a put the HBM cache's byte count leaves out
    with tracing.span("device:put", lane="device",
                      attrs={"bytes": 0, "mask_bytes": cap, "cached": 0,
                             "chip": chip or 0}):
        row_mask = put_plane(mask, chip)
    return DeviceTable(cols, row_mask, n, cap, chip=chip)


def _resident_batch(batch, names, n: int, cap: int
                    ) -> Optional[DeviceTable]:
    """Table-wide residency reuse: when EVERY requested column's decoded
    device planes are still resident at one shared capacity ≥ the
    requested bucket, rebuild the DeviceTable from them — zero uploads
    beyond the tiny live-row mask.  Marked ``resident``: the planes are
    shared with the registry (and the host Series that keys it), so the
    donation discipline must never hand them to a fused program."""
    from . import pipeline
    hits = {}
    shared_cap = None
    for nm in names:
        hit = pipeline.resident_planes(batch.get_column(nm), n)
        if hit is None:
            return None
        data, validity, dictionary, ccap = hit
        if ccap < cap or (shared_cap is not None and ccap != shared_cap):
            return None
        shared_cap = ccap
        hits[nm] = DeviceColumn(data, validity,
                                batch.get_column(nm).datatype(), dictionary)
    if shared_cap is None:
        return None
    mask = np.zeros(shared_cap, dtype=np.bool_)
    mask[:n] = True
    return DeviceTable(hits, jnp.asarray(mask), n, shared_cap,
                       resident=True)


def decode_column(name: str, col: DeviceColumn, count: int) -> Series:
    """DeviceColumn → Series, taking the first ``count`` rows (post-compaction).
    Data + validity come back in ONE batched ``device_get`` (round 17: the
    two sequential blocking gets here were a full extra RTT per column on
    a transfer-bound link)."""
    return decode_columns([(name, col)], count)[0]


def decode_columns(named: "List[tuple]", count: int) -> "List[Series]":
    """Decode many DeviceColumns with ONE batched pytree ``device_get``
    for every data+validity plane (round 17's single-transfer
    discipline).  Each decoded Series registers its still-live device
    planes for residency hand-off when the async pipeline is enabled —
    a downstream device op then re-enters without a host round trip."""
    from . import pipeline
    fetched = [(c.data, c.validity) for _, c in named]
    if any(_is_device_array(c.data) for _, c in named):
        fetched = pipeline.fetch_host(fetched)  # else: planes of a
        # packed result, fetched with it and already on the host
    register = pipeline.inflight_window() > 0
    out = []
    for (name, col), (vals, validity) in zip(named, fetched):
        s = _decode_np(name, col, np.asarray(vals)[:count],
                       np.asarray(validity)[:count], count)
        if register and _is_device_array(col.data) \
                and not col.dtype.is_decimal() and not col.dtype.is_null():
            # decimals are excluded: their f64 encoding is lossy, so a
            # reuse would not be bit-identical with a fresh re-encode
            pipeline.note_decoded(s, col.data, col.validity,
                                  col.dictionary, count,
                                  int(col.data.shape[0]))
        out.append(s)
    return out


def _is_device_array(x) -> bool:
    return isinstance(x, jax.Array)


def _decode_np(name: str, col: DeviceColumn, vals: np.ndarray,
               validity: np.ndarray, count: int) -> Series:
    """Host-side decode of already-fetched planes (the single-transfer
    table path lands here with numpy arrays)."""
    dt = col.dtype
    if dt.is_null():
        return Series(name, dt, arrow=pa.nulls(count))
    if col.dictionary is not None:
        codes = np.where(validity, vals.astype(np.int64), 0)
        arr = col.dictionary.take(pa.array(codes, type=pa.int64()))
        if arr.type != dt.to_arrow():
            arr = arr.cast(dt.to_arrow())
        if not validity.all():
            arr = pc.if_else(pa.array(validity), arr,
                             pa.nulls(count, type=dt.to_arrow()))
        return Series(name, dt, arrow=arr)
    target = dt.to_arrow()
    if dt.kind == "date":
        arr = pa.array(vals.astype(np.int32), mask=~validity).cast(target)
    elif dt.is_temporal():
        arr = pa.array(vals.astype(np.int64), mask=~validity).cast(target)
    elif dt.is_boolean():
        arr = pa.array(vals.astype(np.bool_), mask=~validity)
    else:
        rep = dt.device_repr()
        if rep is not None and vals.dtype != rep:
            vals = vals.astype(rep)
        arr = pa.array(vals, mask=~validity)
        if arr.type != target:
            arr = arr.cast(target)
    return Series(name, dt, arrow=arr)


def decode_table(dt: DeviceTable, compact_perm: Optional[np.ndarray] = None):
    """DeviceTable → RecordBatch. If rows are not already compacted (live rows
    first), pass a permutation from ``kernels.compaction_perm``.

    The whole table downloads as ONE pytree ``device_get`` (round 17):
    every column's data+validity host copies start together instead of
    2×n_cols sequential blocking round trips."""
    from ..recordbatch import RecordBatch
    named = []
    for name, col in dt.columns.items():
        if compact_perm is not None:
            data = jnp.take(col.data, compact_perm, axis=0)
            valid = jnp.take(col.validity, compact_perm, axis=0)
            col = DeviceColumn(data, valid, col.dtype, col.dictionary)
        named.append((name, col))
    cols = decode_columns(named, dt.row_count)
    return RecordBatch.from_series(cols) if cols else RecordBatch.empty()
