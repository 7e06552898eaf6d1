"""Fused scan fragments: filter + project + partial aggregation as ONE XLA
program per morsel, with a single packed result transfer.

This is the TPU analogue of the reference's operator fusion inside Swordfish
pipelines (project/filter intermediate ops feeding the grouped-aggregate sink,
``src/daft-local-execution/src/{intermediate_ops,sinks/grouped_aggregate.rs}``)
— but instead of separate operators over channels, the whole chain compiles
into a single jit program: one host→device encode (amortized away entirely by
the HBM column cache for repeated scans), one kernel launch, and ONE
device→host transfer.

The single-transfer discipline matters because the device link is
latency/bandwidth-bound, not free: the aggregate outputs
are sliced device-side to a static group-capacity bucket and bit-packed into
a single int64 matrix, so a whole partial-aggregation result costs one
round-trip regardless of column count. Output dtypes are recorded at trace
time to reverse the packing host-side.
"""

from __future__ import annotations

import threading as _threading
from typing import TYPE_CHECKING, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..expressions.expressions import Expression
from ..schema import Schema
from . import column as dcol
from . import compiler, kernels, runtime

if TYPE_CHECKING:
    from ..recordbatch import RecordBatch

_fused_cache: Dict[Tuple, object] = {}
_fused_counters: Dict[str, int] = {"hits": 0, "misses": 0}


def fused_cache_counters() -> Dict[str, int]:
    """Fused-agg program cache counters (serving-plane evidence that
    repeated submissions re-enter previously traced device fragments)."""
    out = dict(_fused_counters)
    out["entries"] = len(_fused_cache)
    return out

# static group-capacity buckets for the packed output block: start tiny —
# TPC-H-style aggregations produce a handful of groups, and transferred bytes
# scale with the bucket — and grow geometrically on overflow (the packed
# header always carries the true group count, so overflow costs one re-run).
_OUT_CAP0 = 128


def _pack_i64(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-preserving lowering of any kernel output lane to int64."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int64)
    if x.dtype == jnp.float32:
        return lax.bitcast_convert_type(x, jnp.uint32).astype(jnp.int64)
    if x.dtype == jnp.float64:
        return lax.bitcast_convert_type(x, jnp.int64)
    return x.astype(jnp.int64)


def _unpack_i64(row: np.ndarray, dtype) -> np.ndarray:
    """Host-side inverse of :func:`_pack_i64`."""
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return row != 0
    if dt == np.float32:
        return (row & 0xFFFFFFFF).astype(np.uint32).view(np.float32)
    if dt == np.float64:
        return row.view(np.float64)
    return row.astype(dt)


#: a region's outputs share one validity word a row: bit ``i`` is output
#: ``i``'s, and the word's upper half holds the header in row 0
_MAX_ROW_OUTPUTS = 31


def _is_wide(dtype) -> bool:
    return np.dtype(dtype).itemsize == 8


def _packed_words(logical_dtypes) -> int:
    """Words a row of :func:`_pack_rows`' block takes for outputs of these
    logical types, by the device's encoding of each (a type with none
    counts a whole word: a price, not a layout)."""
    def wide(dt):
        try:
            return _is_wide(dcol.device_np_dtype(dt))
        except ValueError:
            return True
    n_wide = sum(wide(dt) for dt in logical_dtypes)
    return 1 + n_wide + (len(logical_dtypes) - n_wide + 1) // 2


def _pack_rows(vals, valids, live) -> jnp.ndarray:
    """Row-shaped outputs (``[w]`` each, their validity beside them) as one
    int64 block ``[words, w]``: word 0 holds every output's validity bit
    (bit ``i`` output ``i``'s) and, in row 0, ``live`` (the count of rows
    that are real) in its upper half; then one word an 8-byte output, and
    one word a PAIR of narrower ones (the first in the lower half). A
    float32 beside a date costs a row 8 bytes on the link, where a word a
    value and a word its validity cost 32."""
    assert len(vals) <= _MAX_ROW_OUTPUTS  # get_fused_region declines more
    head = jnp.zeros(vals[0].shape, jnp.int64)
    for i, m in enumerate(valids):
        head = head | (m.astype(jnp.int64) << i)
    head = head.at[0].add(live.astype(jnp.int64) << 32)
    words = [head] + [_pack_i64(v) for v in vals if _is_wide(v.dtype)]
    narrow = [_pack_i64(v.astype(jnp.int32) if jnp.issubdtype(
        v.dtype, jnp.signedinteger) else v) & 0xFFFFFFFF
        for v in vals if not _is_wide(v.dtype)]
    for lo, hi in zip(narrow[::2], narrow[1::2] + [None]):
        words.append(lo if hi is None else lo | (hi << 32))
    return jnp.stack(words)


def _packed_live(block: np.ndarray) -> int:
    """The header of a :func:`_pack_rows` block."""
    return int(block[0, 0]) >> 32


def _unpack_rows(block: np.ndarray, dtypes):
    """Host-side inverse of :func:`_pack_rows` over the columns given
    (a fetched block cut to its live rows, or several blocks' live rows
    side by side): ``[(values, validity)]`` an output, in order."""
    bits = block[0]
    out, wide_at, narrow_at = [], 1, 0
    first_narrow = 1 + sum(_is_wide(dt) for dt in dtypes)
    for i, dt in enumerate(dtypes):
        dt = np.dtype(dt)
        valid = ((bits >> i) & 1).astype(np.bool_)
        if _is_wide(dt):
            vals = _unpack_i64(block[wide_at], dt)
            wide_at += 1
        else:
            word = block[first_narrow + narrow_at // 2]
            half = ((word >> 32) if narrow_at % 2 else word) & 0xFFFFFFFF
            narrow_at += 1
            if dt == np.bool_:
                vals = half != 0
            elif dt == np.float32:
                vals = half.astype(np.uint32).view(np.float32)
            elif np.issubdtype(dt, np.signedinteger):
                vals = half.astype(np.uint32).view(np.int32).astype(dt)
            else:
                vals = half.astype(dt)
        out.append((vals, valid))
    return out


class FusedAggProgram:
    def __init__(self, packed_fn, run_packed, compiled: compiler.Compiled,
                 nk: int, ops: Tuple[str, ...], has_pred: bool, meta: dict):
        self.packed_fn = packed_fn      # single-transfer path (group
        # overflow re-runs it at a grown static out_cap bucket)
        self._run_packed = run_packed   # raw traceable fn — donating twin
        self._donate_fn = None          # lazily jitted with donate_argnums
        self.compiled = compiled
        self.nk = nk
        self.ops = ops
        self.has_pred = has_pred
        self.meta = meta                # trace-time dtype layout
        #: column → device numpy dtype (set by get_fused_agg; None when
        #: an input is not device-representable) — the AOT warm-up grid
        self.in_np_dtypes = None
        #: source column per group key when EVERY key is a string/binary
        #: passthrough (dictionary-coded plane) — dense-strategy
        #: eligibility; None otherwise
        self.key_sources = None
        #: (mesh, out_cap, strategy, dims) -> the round's program
        self._round_fns: Dict[Tuple, object] = {}

    def round_fn(self, mesh, out_cap: int, strategy: str,
                 dims: Tuple[int, ...]):
        """The SPMD twin of :attr:`packed_fn` over the 1-D ``data`` mesh:
        every shard runs ``run_packed`` on its own chip's planes (no
        collective: each chip's partials stay apart for the host's
        float64 merge) and the output is the chips' packed blocks one
        after another along axis 0. Its inputs are global arrays whose
        shards are a round's tables, one a chip (:func:`_dispatch_round`);
        a runtime scalar comes with a leading axis of one a chip. The
        statics are closed over, so one executable a (mesh, out_cap,
        strategy, dims), as ``packed_fn`` has one a chip; the jitted
        function is still called ``run_packed``, which is the name the
        device trace shows its module under."""
        key = (mesh, out_cap, strategy, dims)
        fn = self._round_fns.get(key)
        if fn is None:
            from jax.sharding import PartitionSpec as P
            base = self._run_packed

            def run_packed(arrays, valids, row_mask, scalars):
                return base(arrays, valids, row_mask,
                            tuple(s[0] for s in scalars), out_cap=out_cap,
                            strategy=strategy, dims=dims)

            fn = self._round_fns[key] = jax.jit(jax.shard_map(
                run_packed, mesh=mesh, in_specs=P("data"),
                out_specs=P("data"), check_vma=False))
        return fn

    def donate_fn(self):
        """The donating twin executable (round 12 megakernel discipline):
        the encoded input planes are dead after the in-program aggregation,
        so XLA reuses their HBM for the fragment's intermediates — no
        input column survives the dispatch. Only entered for one-shot
        (non-cache-resident) tables on real chips; jitted lazily so CPU
        runs never trace it."""
        if self._donate_fn is None:
            self._donate_fn = jax.jit(
                self._run_packed,
                static_argnames=("out_cap", "strategy", "dims"),
                donate_argnums=(0, 1))
        return self._donate_fn


def get_fused_agg(group_exprs: List[Expression], child_exprs: List[Expression],
                  ops: Tuple[str, ...], predicate: Optional[Expression],
                  schema: Schema) -> Optional[FusedAggProgram]:
    """Compile (or fetch) the fused filter→project→grouped-agg program."""
    key = (tuple(e._key() for e in group_exprs),
           tuple(e._key() for e in child_exprs), ops,
           predicate._key() if predicate is not None else None,
           runtime._schema_key(schema))
    hit = _fused_cache.get(key)
    if hit is not None:
        _fused_counters["hits"] += 1  # GIL-atomic; approximate under race
        return hit if isinstance(hit, FusedAggProgram) else None
    _fused_counters["misses"] += 1
    proj = list(group_exprs) + list(child_exprs) + \
        ([predicate] if predicate is not None else [])
    try:
        c = compiler.compile_projection(proj, schema, jit=False)
    except (compiler.NotCompilable, NotImplementedError, ValueError,
            TypeError, KeyError, OverflowError):
        _fused_cache[key] = False
        return None
    nk = len(group_exprs)
    nv = len(child_exprs)
    has_pred = predicate is not None
    meta: dict = {}

    def eval_inputs(arrays, valids, row_mask, scalars):
        outs = c.fn(arrays, valids, row_mask, scalars)
        if has_pred:
            pv, pm = outs[-1]
            row_mask = row_mask & pv.astype(jnp.bool_) & pm
            outs = outs[:-1]
        keys = tuple(v for v, _ in outs[:nk])
        kvalids = tuple(m for _, m in outs[:nk])
        vals = tuple(v for v, _ in outs[nk:nk + nv])
        vvalids = tuple(m for _, m in outs[nk:nk + nv])
        return keys, kvalids, vals, vvalids, row_mask

    def run_packed(arrays, valids, row_mask, scalars, out_cap: int,
                   strategy: str = "sort", dims: Tuple[int, ...] = ()):
        # named scopes land in every HLO op's metadata: the device
        # trace's operations name the stage they belong to
        with jax.named_scope("eval-inputs"):
            keys, kvalids, vals, vvalids, row_mask = eval_inputs(
                arrays, valids, row_mask, scalars)
        if nk == 0:
            with jax.named_scope("global/reduce"):
                results = kernels.global_agg_impl(vals, vvalids, row_mask,
                                                  ops)
            flat = [v for v, _ in results] + [m for _, m in results]
            meta["global_dtypes"] = [x.dtype for x in flat]
            with jax.named_scope("pack-i64"):
                return jnp.stack([_pack_i64(x.reshape(())) for x in flat])
        # the whole scan→filter→project→agg chain stays ONE jit program
        # either way — `strategy` only swaps the reduction's inner loop
        # (dense direct slot indexing vs radix sort + segment reduce)
        if strategy == "dense":
            ok, okv, ov, ovv, g = kernels.grouped_agg_dense_impl(
                keys, kvalids, vals, vvalids, row_mask, ops, out_cap, dims)
        else:
            ok, okv, ov, ovv, g = kernels.grouped_agg_block_impl(
                keys, kvalids, vals, vvalids, row_mask, ops, out_cap)
        flat = list(ok) + list(okv) + list(ov) + list(ovv)
        meta["grouped_dtypes"] = [x.dtype for x in flat]
        with jax.named_scope("pack-i64"):
            rows = [jnp.full((out_cap,), 0, jnp.int64).at[0]
                    .set(g.astype(jnp.int64))]
            rows += [_pack_i64(x) for x in flat]  # already [out_cap]-wide
            return jnp.stack(rows)

    prog = FusedAggProgram(
        jax.jit(run_packed, static_argnames=("out_cap", "strategy", "dims")),
        run_packed, c, nk, ops, has_pred, meta)
    # dense-strategy eligibility: every group key must be a plain
    # string/binary column passthrough, so its device plane carries
    # sorted-dictionary codes the mixed-radix group id can index directly
    srcs = []
    for e, f in zip(group_exprs, c.out_fields[:nk]):
        src = runtime._string_out_source(e) \
            if (f.dtype.is_string() or f.dtype.is_binary()) else None
        if src is None:
            srcs = None
            break
        srcs.append(src)
    prog.key_sources = tuple(srcs) if srcs else None
    try:
        # device input dtypes per needed column — the AOT warm-up grid
        # (device/warmup.py) rebuilds abstract inputs from this
        prog.in_np_dtypes = {
            n: dcol.device_np_dtype(schema[n].dtype)
            for n in c.needs_cols}
    except (ValueError, KeyError):
        prog.in_np_dtypes = None
    _fused_cache[key] = prog
    return prog


def fused_programs() -> List[FusedAggProgram]:
    """Every fused-agg program compiled so far (the 'fragment library'
    the AOT warm-up iterates)."""
    return [p for p in _fused_cache.values()
            if isinstance(p, FusedAggProgram)]


def run_fused_agg(prog: FusedAggProgram, batch, group_exprs, agg_exprs,
                  out_schema: Schema):
    """Execute the fused program on one RecordBatch; returns a RecordBatch of
    partial groups (or None → caller falls back to the host chain)."""
    tok = submit_fused_agg(prog, batch, group_exprs, agg_exprs, out_schema)
    return None if tok is None else drain_fused_agg_table(tok)


def submit_fused_agg(prog: FusedAggProgram, batch, group_exprs, agg_exprs,
                     out_schema: Schema):
    """Pipeline submit half of :func:`run_fused_agg`: host encode +
    asynchronous dispatch of the first ladder rung, NO blocking fetch.
    Returns an in-flight token for :func:`drain_fused_agg_table`, or
    None → host fallback (pyobject inputs)."""
    for nm in prog.compiled.needs_cols:
        if batch.get_column(nm).is_pyobject():
            return None
    dt = dcol.encode_batch(batch, prog.compiled.needs_cols)
    return submit_fused_agg_table(
        prog, dt, batch.schema, group_exprs, agg_exprs, out_schema,
        # the donating fast path invalidates the input planes; an overflow
        # re-dispatch re-encodes from the host batch we still hold
        reencode=lambda: dcol.encode_batch(batch, prog.compiled.needs_cols))


def _how_attrs(strategy: str, dims: Tuple[int, ...]) -> dict:
    """What a launch's span and its strategy decision say of the
    reduction: the ``strategy`` and, for a dense one, the ``inner`` loop
    its slot count takes (``kernels.dense_inner_loop``: ``masked`` /
    ``matmul``), known from ``dims`` before the launch."""
    if strategy != "dense":
        return {"strategy": strategy}
    return {"strategy": strategy, "inner": kernels.dense_inner_loop(dims)}


def _dispatch_packed(prog: FusedAggProgram, dt: dcol.DeviceTable,
                     out_cap: int, strategy: str = "sort",
                     donate: bool = False, dims: Tuple[int, ...] = ()):
    from .. import tracing
    from ..analysis import retrace_sanitizer
    # the host's time to enqueue one program (the device runs it later)
    with tracing.span("device:dispatch", lane="device",
                      attrs={"program": "fused_agg",
                             "capacity": dt.capacity,
                             **_how_attrs(strategy, dims),
                             "chip": dt.chip or 0}):
        arrays = {n: col.data for n, col in dt.columns.items()}
        valids = {n: col.validity for n, col in dt.columns.items()}
        scalars = runtime._prep_scalars(prog.compiled, dt)
        fn = prog.donate_fn() if donate else prog.packed_fn
        # the declared trace signature (dispatch_registry:
        # fragment.packed / fragment.donate) — everything the jit cache
        # key may depend on; a second trace for the SAME key is the
        # retrace tax and a sanitizer budget violation
        site = "fragment.donate" if donate else "fragment.packed"
        with retrace_sanitizer.dispatch_scope(
                site,
                (id(prog), dt.capacity, out_cap, strategy, dims,
                 tuple(s.shape for s in scalars), dt.chip)), \
                tracing.launch(site, dt.chip or 0):
            # runs where its arguments lie: on the table's chip
            return fn(arrays, valids, dt.row_mask, scalars,
                      out_cap=out_cap, strategy=strategy, dims=dims)


def _round_key(dt: dcol.DeviceTable, how, scalars) -> Tuple:
    """What the packed program is traced for over ``dt`` at ``how`` =
    ``(out_cap, strategy, dims)`` with the runtime ``scalars``, but the
    table's chip: the tables of a round must agree in it to be the
    shards of one program."""
    return (dt.capacity, how,
            tuple((n, c.data.dtype, c.data.shape, c.validity.dtype)
                  for n, c in dt.columns.items()),
            dt.row_mask.dtype,
            tuple((x.shape, x.dtype) for x in scalars))


def _launches(prog: "FusedAggProgram", tables, hows):
    """The window's launches where its tables lie on several chips, each
    the places in ``tables`` it answers, and every table's runtime
    scalars on the host (``runtime._scalar_planes``, made once here): the
    tables are taken chip by chip in task order and the j-th of every
    chip form a *round*, ONE launch of the SPMD program
    (:func:`_dispatch_round`) when it has a table on every chip of
    ``mesh.scan_devices()`` and they agree in :func:`_round_key`; the
    tables of a ragged round are launched one by one. None where one
    chip is visible (``DeviceTable.chip`` None) or the window's tables
    lie on one chip: a launch a table, as ever."""
    from ..parallel import mesh as pmesh
    n_chips = len(pmesh.scan_devices())
    if n_chips < 2:
        return None
    by_chip: List[List[int]] = [[] for _ in range(n_chips)]
    for i, dt in enumerate(tables):
        if dt.chip is None or not 0 <= dt.chip < n_chips:
            return None
        by_chip[dt.chip].append(i)
    if sum(1 for on in by_chip if on) < 2:
        return None
    scalars = [runtime._scalar_planes(prog.compiled, dt) for dt in tables]
    out: List[Tuple[int, ...]] = []
    for j in range(max(len(on) for on in by_chip)):
        members = tuple(on[j] for on in by_chip if j < len(on))
        if len(members) == n_chips and len(
                {_round_key(tables[i], hows[i], scalars[i])
                 for i in members}) == 1:
            out.append(members)
        else:
            out.extend((i,) for i in members)
    return out, scalars


def _dispatch_round(prog: FusedAggProgram, dts, scalars, out_cap: int,
                    strategy: str, dims: Tuple[int, ...] = ()):
    """ONE launch for a round: ``dts`` (a table a chip, in chip order,
    agreeing in :func:`_round_key`, each with its runtime ``scalars`` on
    the host) as the shards of the SPMD program
    ``prog.round_fn``. Every input is a global array over the ``data``
    mesh made of the planes the tables hold, where they lie: no copy, no
    device operation (for resident tables assembled once and kept on the
    HBM cache beside them, ``DeviceColumnCache.round_inputs``: the span
    says ``assembled`` 1 or 0). The output is the chips' packed blocks
    along axis 0, shard ``k`` what :func:`_dispatch_packed` gives for
    ``dts[k]``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import tracing
    from ..analysis import retrace_sanitizer
    from ..parallel import mesh as pmesh
    from . import cache as dcache
    n = len(dts)
    with tracing.span("device:dispatch", lane="device",
                      attrs={"program": "fused_agg",
                             "capacity": dts[0].capacity,
                             **_how_attrs(strategy, dims),
                             "tables": n, "chips": n}) as sp:
        mesh = pmesh.get_mesh()
        sharding = NamedSharding(mesh, P("data"))

        def whole(planes):
            shape = planes[0].shape
            return jax.make_array_from_single_device_arrays(
                (n * shape[0],) + shape[1:], sharding, planes)

        def assemble():
            return ({nm: whole([dt.columns[nm].data for dt in dts])
                     for nm in dts[0].columns},
                    {nm: whole([dt.columns[nm].validity for dt in dts])
                     for nm in dts[0].columns},
                    whole([dt.row_mask for dt in dts]))

        if all(dt.resident for dt in dts):
            # the cache's own planes: their global arrays are kept beside
            # them (15 of them are a third of a round's dispatch at sf100)
            (arrays, valids, row_mask), kept = \
                dcache.get_cache().round_inputs(
                    tuple(p for dt in dts for c in dt.columns.values()
                          for p in (c.data, c.validity))
                    + tuple(dt.row_mask for dt in dts), assemble)
            sp.set("assembled", 0 if kept else 1)
        else:
            arrays, valids, row_mask = assemble()
            sp.set("assembled", 1)
        # a scalar of each table's own dictionary, the chips' side by side
        scalars = tuple(jax.device_put(np.stack(per_chip), sharding)
                        for per_chip in zip(*scalars))
        fn = prog.round_fn(mesh, out_cap, strategy, dims)
        # dispatch_registry: fragment.round, fragment.packed's signature
        # with the chips of the round for the table's chip
        with retrace_sanitizer.dispatch_scope(
                "fragment.round",
                (id(prog), dts[0].capacity, out_cap, strategy, dims,
                 tuple(s.shape for s in scalars), n)), \
                tracing.launch("fragment.round", None, tables=n, chips=n):
            return fn(arrays, valids, row_mask, scalars)


#: dense-strategy slot ceiling: K = prod(dim+1) static slots per dispatch;
#: past this the slot planes outgrow the group blocks they stand in for
#: and sort territory begins anyway
DENSE_MAX_SLOTS = 4096


def dense_dims(prog: FusedAggProgram,
               dt: dcol.DeviceTable) -> Optional[Tuple[int, ...]]:
    """Pow2-bucketed dictionary width per group key, or None when this
    table is ineligible for the dense direct-index strategy (a key is not
    a dictionary-coded passthrough, a dictionary is missing, or the slot
    product exceeds :data:`DENSE_MAX_SLOTS`). Bucketing to powers of two
    bounds the static-arg space: per-morsel dictionaries drift in size,
    but their buckets — and therefore the traced programs — do not."""
    if not prog.key_sources:
        return None
    dims = []
    K = 1
    for src in prog.key_sources:
        col = dt.columns.get(src)
        if col is None or col.dictionary is None:
            return None
        d = len(col.dictionary)
        d = max(1 << (max(d - 1, 0)).bit_length(), 1)  # pow2 ceiling
        dims.append(d)
        K *= d + 1
        if K > DENSE_MAX_SLOTS:
            return None
    return tuple(dims)


def dense_plan(prog: FusedAggProgram, dt: dcol.DeviceTable,
               cap_limit: int) -> Optional[Tuple[Tuple[int, ...], int]]:
    """``(dims, out_cap)`` for a dense dispatch, or None when ineligible.
    The bucket is sized to hold every possible slot up front — dense
    output can never overflow, so the ladder never re-dispatches."""
    dims = dense_dims(prog, dt)
    if dims is None:
        return None
    out_cap = dcol.bucket_capacity(max(kernels.dense_slots(dims), _OUT_CAP0))
    if out_cap > cap_limit:
        return None
    return dims, out_cap


def _donation_ok(dt: dcol.DeviceTable) -> bool:
    """Donate the encoded input planes to the fused program? Never for
    HBM-cache-resident tables (their buffers are SHARED with the cache —
    donating them would poison every later hit) and never on CPU (XLA
    ignores donation there and warns per executable)."""
    from . import backend
    return backend.is_accelerator() and not dt.resident


def _overflowed(mat: np.ndarray, dt: dcol.DeviceTable) -> bool:
    """A packed group block whose header counts more groups than its
    bucket holds (the caller re-runs the table at a grown bucket)."""
    out_cap = mat.shape[1]
    return int(mat[0, 0]) > out_cap and out_cap < dt.capacity


def _key_dictionary_runs(src: str, tables) -> List[Tuple[int, int]]:
    """``[a, b)`` stretches of ``tables`` whose dictionaries of column
    ``src`` are one object or ``equals()`` each other: a key lane carries
    codes of each table's OWN dictionary, so only such a stretch decodes
    through one ``take``."""
    def same(d0, d1):
        return d1 is d0 or (d0 is not None and d1 is not None
                            and d1.equals(d0))

    runs, a = [], 0
    for b in range(1, len(tables)):
        if not same(tables[a].columns[src].dictionary,
                    tables[b].columns[src].dictionary):
            runs.append((a, b))
            a = b
    runs.append((a, len(tables)))
    return runs


def _decode_lanes(tok, mats, tables):
    """Packed results of ``tables`` (one ``mats`` entry each; a grouped
    block must hold its groups, see :func:`_overflowed`) -> ONE
    RecordBatch, the tables' partial rows in order, and ``ends``: table
    ``k``'s rows of it are ``ends[k]:ends[k + 1]``. Every lane is
    unpacked and decoded once over all the tables: what a table costs
    here is a slice and its share of a concatenation, not a round of
    Arrow arrays for a handful of groups."""
    from .. import tracing
    from ..recordbatch import RecordBatch
    from ..series import Series
    prog, agg_fields = tok.prog, tok.agg_fields
    nk, nv = prog.nk, len(agg_fields)
    if nk == 0:
        dtypes = prog.meta["global_dtypes"]
        # [T, 2*nv] -> one contiguous row a lane
        lanes = np.ascontiguousarray(np.stack(mats).T)
        counts = [1] * len(mats)
    else:
        dtypes = prog.meta["grouped_dtypes"]
        # blocks of a window differ in out_cap (dense dims are bucketed
        # per table, sort starts at _OUT_CAP0): cut each to its groups
        counts = [int(m[0, 0]) for m in mats]
        lanes = np.concatenate(
            [m[1:, :g] for m, g in zip(mats, counts)], axis=1)
    ends = np.cumsum([0] + counts).tolist()

    def lane(v, m):
        # run_packed's layout: keys, key validity, values, value validity
        return (_unpack_i64(lanes[v], dtypes[v]),
                _unpack_i64(lanes[m], dtypes[m]).astype(np.bool_))

    cols = []
    for i, (e, f) in enumerate(zip(tok.group_exprs, tok.key_fields)):
        kv, km = lane(i, nk + i)
        coded = f.dtype.is_string() or f.dtype.is_binary()
        runs = _key_dictionary_runs(runtime._string_out_source(e), tables) \
            if coded else [(0, len(tables))]
        parts = [runtime.decode_group_key(
            e, f, kv[ends[a]:ends[b]], km[ends[a]:ends[b]], tables[a],
            ends[b] - ends[a]) for a, b in runs]
        cols.append(parts[0] if len(parts) == 1 else Series.concat(parts))
    for i, f in enumerate(agg_fields):
        vv, vm = lane(2 * nk + i, 2 * nk + nv + i)
        cols.append(dcol.decode_column(
            f.name, dcol.DeviceColumn(vv, vm, f.dtype, None), ends[-1]))
    tracing.tally("decode_tables", len(mats))
    tracing.tally("decode_batches")
    return RecordBatch.from_series(cols), ends


def packed_bytes_per_group(nk: int, nops: int) -> int:
    """Bytes one group row occupies in the packed result matrix (the
    header row amortizes; keys+values each carry a validity plane). The
    executor's cost gates price transfers with this — it must stay in
    lockstep with ``run_packed``'s layout."""
    return (1 + 2 * (nk + nops)) * 8


def _max_out_cap(prog: FusedAggProgram, dt: dcol.DeviceTable) -> int:
    """Group-capacity ceiling from the measured link: the packed-result
    transfer must not exceed what the HOST would spend aggregating the
    same rows outright — a non-reductive grouping (TPC-H Q18's
    near-unique l_orderkey) makes device partials pure freight, while a
    reductive one (Q1's 4 groups) is almost free. Shared-memory links are
    unbounded."""
    import math

    from . import costmodel
    p = costmodel.link_profile()
    full = dcol.bucket_capacity(max(dt.capacity, 1))
    if p.down_bps == math.inf:
        return full
    bytes_per_group = packed_bytes_per_group(prog.nk, len(prog.ops))
    in_bytes = sum(int(c.data.nbytes) + int(c.validity.nbytes)
                   for c in dt.columns.values())
    host_s = in_bytes / costmodel.HOST_AGG_BPS
    raw = int(host_s * p.down_bps // bytes_per_group)
    if raw < _OUT_CAP0:
        return _OUT_CAP0
    # round DOWN to a power of two: dispatch caps are static jit args, so
    # arbitrary integers would compile a fresh executable per value
    return min(1 << (raw.bit_length() - 1), full)


def _ledger_grouped(prog: FusedAggProgram, rows: int, cap: int,
                    out_cap: int, seconds: float, dispatches: int,
                    strategy: str, tables: Optional[int] = None) -> None:
    """Per-dispatch MFU accounting for the fused grouped-agg family; the
    byte model follows the strategy the dispatch actually ran. The work
    is modeled a table (``tables``; default: one a dispatch), the
    dispatches are the launches: a round is one over several tables."""
    from . import costmodel, mfu
    model = mfu.dense_agg_models if strategy == "dense" \
        else mfu.grouped_agg_models
    flops, nbytes = model(cap, out_cap, max(prog.nk, 1), len(prog.ops))
    tables = dispatches if tables is None else tables
    costmodel.ledger_record("grouped_agg", rows=rows,
                            nbytes=tables * nbytes,
                            flops=tables * flops, seconds=seconds,
                            dispatches=dispatches, strategy=strategy)


def _ledger_global(prog: FusedAggProgram, rows: int, cap: int,
                   seconds: float, dispatches: int,
                   tables: Optional[int] = None) -> None:
    """Ledger record for the fused SCALAR-agg fragment (no group keys —
    TPC-H Q6's shape): one streaming read of each value plane plus the
    row mask (a table; ``dispatches`` are the launches, as in
    :func:`_ledger_grouped`). Until PR 23 this site dispatched without a
    record, so a query made only of it showed no kernel family at all."""
    from . import costmodel
    tables = dispatches if tables is None else tables
    costmodel.ledger_record(
        "global_agg", rows=rows,
        nbytes=tables * (len(prog.ops) + 1) * cap * 4,
        seconds=seconds, dispatches=dispatches)


class InflightFusedAgg:
    """One in-flight fused-agg dispatch: the device-side packed result
    plus the ladder state a drain needs to finish (overflow re-dispatch,
    ledger accounting)."""

    __slots__ = ("prog", "dt", "group_exprs", "key_fields", "agg_fields",
                 "reencode", "cap_limit", "out_cap", "donate",
                 "strategy", "dims", "packed", "t0", "submitted_s",
                 "dispatches")

    def __init__(self, prog, dt, group_exprs, key_fields, agg_fields,
                 reencode):
        import time as _time
        self.prog = prog
        self.dt = dt
        self.group_exprs = group_exprs
        self.key_fields = key_fields
        self.agg_fields = agg_fields
        self.reencode = reencode
        self.cap_limit = 0
        self.out_cap = _OUT_CAP0
        self.donate = False
        self.strategy = "sort"
        self.dims: Tuple[int, ...] = ()
        self.packed = None
        self.t0 = _time.perf_counter()
        #: submit-stage wall (dispatch only) — the ledger charges
        #: submitted_s + drain wall, NOT t0→drain-end, which under the
        #: async window would include time the token sat undrained and
        #: deflate the achieved-GB/s evidence
        self.submitted_s = 0.0
        self.dispatches = 0   # ladder rungs dispatched so far


def _ladder_dispatch(tok: InflightFusedAgg) -> None:
    """Dispatch the current ladder rung asynchronously (no fetch) and
    tally the strategy it ran."""
    from . import costmodel
    tok.packed = _dispatch_packed(tok.prog, tok.dt, tok.out_cap,
                                  tok.strategy, tok.donate, tok.dims)
    tok.dispatches += 1
    costmodel.log_strategy_decision(
        "groupby_strategy", rows=tok.dt.row_count, out_cap=tok.out_cap,
        **_how_attrs(tok.strategy, tok.dims))


def submit_fused_agg_table(prog: FusedAggProgram, dt: dcol.DeviceTable,
                           in_schema: Schema, group_exprs, agg_exprs,
                           out_schema: Schema,
                           start_out_cap: int = _OUT_CAP0, reencode=None
                           ) -> InflightFusedAgg:
    """Async submit half of :func:`run_fused_agg_table`: dispatch the
    first ladder rung and return without blocking on the result — the
    device computes while the caller encodes the next morsel."""
    key_fields = [e.to_field(in_schema) for e in group_exprs]
    agg_fields = [out_schema[e.name()] for e in agg_exprs]
    import time as _time
    tok = InflightFusedAgg(prog, dt, group_exprs, key_fields, agg_fields,
                           reencode)
    if prog.nk == 0:
        tok.packed = _dispatch_packed(prog, dt, _OUT_CAP0)
        tok.submitted_s = _time.perf_counter() - tok.t0
        return tok
    tok.cap_limit = _max_out_cap(prog, dt)
    tok.out_cap = min(start_out_cap, tok.cap_limit)
    tok.donate = reencode is not None and _donation_ok(dt)
    # dense first: a direct-indexed dispatch streams the rows once with
    # no sort, so whenever the key dictionaries fit the slot budget it
    # wins; its bucket holds every slot, so only sort ever climbs a rung
    plan = dense_plan(prog, dt, tok.cap_limit)
    if plan is not None:
        tok.strategy = "dense"
        tok.dims, tok.out_cap = plan
    _ladder_dispatch(tok)
    tok.submitted_s = _time.perf_counter() - tok.t0
    return tok


def drain_fused_agg_table(tok: InflightFusedAgg):
    """Blocking drain half: ONE batched fetch of the packed result, then
    decode — continuing the overflow ladder synchronously if the group
    count outgrew the bucket (rare; each retry is dispatch+fetch).
    Returns None → host fallback when groups exceed the link-budgeted
    ceiling."""
    import time as _time

    from .. import tracing
    from . import pipeline
    prog, dt = tok.prog, tok.dt
    t_drain0 = _time.perf_counter()
    if prog.nk == 0:
        packed = np.asarray(pipeline.fetch_host(tok.packed))
        _ledger_global(prog, dt.row_count, dt.capacity,
                       tok.submitted_s + (_time.perf_counter() - t_drain0),
                       1)
        with tracing.span("device:decode", lane="device",
                          attrs={"tables": 1, "batches": 1, "groups": 1}):
            return _decode_lanes(tok, [packed], [dt])[0]
    while True:
        packed = np.asarray(pipeline.fetch_host(tok.packed))
        # the packed header carries the true group count
        g = int(packed[0, 0])
        if not _overflowed(packed, tok.dt):
            # a window of one table
            with tracing.span("device:decode", lane="device",
                              attrs={"tables": 1, "batches": 1,
                                     "groups": g}):
                out, _ = _decode_lanes(tok, [packed], [tok.dt])
            # submit wall + drain wall — NOT t0→now, which under the
            # async window would charge time the token sat undrained
            # behind its predecessors
            _ledger_grouped(prog, tok.dt.row_count, tok.dt.capacity,
                            tok.out_cap,
                            tok.submitted_s
                            + (_time.perf_counter() - t_drain0),
                            tok.dispatches, tok.strategy)
            return out
        if g > tok.cap_limit:
            return None
        if tok.donate:
            tok.dt = tok.reencode()
        tok.out_cap = min(dcol.bucket_capacity(max(g, _OUT_CAP0)),
                          tok.cap_limit)
        _ladder_dispatch(tok)


def run_fused_agg_table(prog: FusedAggProgram, dt: dcol.DeviceTable,
                        in_schema: Schema, group_exprs, agg_exprs,
                        out_schema: Schema, start_out_cap: int = _OUT_CAP0,
                        reencode=None):
    """Execute on one encoded DeviceTable (possibly HBM-cache-resident).
    Returns None (→ host fallback) when the group count exceeds the
    link-budgeted packed-output ceiling. With ``reencode`` (a thunk
    rebuilding the DeviceTable from host data), one-shot tables DONATE
    their input planes to the fused program on real chips — an overflow
    re-dispatch then re-encodes instead of reusing dead buffers.
    (Single-sourced as submit + drain so the async pipeline and the
    synchronous chaos-degradation path run the same ladder.)"""
    return drain_fused_agg_table(submit_fused_agg_table(
        prog, dt, in_schema, group_exprs, agg_exprs, out_schema,
        start_out_cap=start_out_cap, reencode=reencode))


class DecodedRun(NamedTuple):
    """Neighbouring tables of a window and the ONE record batch their
    partial rows were decoded into, in task order; ``batch`` None: one
    table the device did not answer (the caller runs it on the host)."""
    tables: int
    batch: "Optional[RecordBatch]"


class InflightFusedAggBatch:
    """A window's worth of in-flight fused-agg dispatches (one per
    DeviceTable, or one per round of tables that lie one on each chip)
    awaiting ONE batched pytree fetch."""

    __slots__ = ("prog", "tables", "places", "in_schema", "group_exprs",
                 "agg_exprs", "out_schema", "key_fields", "agg_fields",
                 "strategy", "inner", "packs", "cuts", "t0", "submitted_s",
                 "failed")

    def __init__(self, prog, tables, places, in_schema, group_exprs,
                 agg_exprs, out_schema):
        import time as _time
        self.prog = prog
        self.tables = tables
        #: each table's place among the window's tasks: tables are
        #: neighbours, and may share a run, where these are consecutive
        self.places = list(range(len(tables))) if places is None \
            else list(places)
        self.in_schema = in_schema
        self.group_exprs = group_exprs
        self.agg_exprs = agg_exprs
        self.out_schema = out_schema
        self.key_fields = [e.to_field(in_schema) for e in group_exprs]
        self.agg_fields = [out_schema[e.name()] for e in agg_exprs]
        self.strategy = "sort"
        #: a dense window's inner loop(s), ``kernels.dense_inner_loop`` of
        #: its tables' ``dims`` (``masked+matmul`` where they differ)
        self.inner: Optional[str] = None
        self.packs: list = []
        #: the tables each of ``packs`` answers, as places in ``tables``
        #: (several: a round, their blocks along axis 0); None: ``packs``
        #: is one a table, in order
        self.cuts: Optional[List[Tuple[int, ...]]] = None
        self.t0 = _time.perf_counter()
        self.submitted_s = 0.0   # dispatch wall (see InflightFusedAgg)
        self.failed = False


def submit_fused_agg_tables(prog: FusedAggProgram, tables,
                            in_schema: Schema, group_exprs, agg_exprs,
                            out_schema: Schema, places=None
                            ) -> InflightFusedAggBatch:
    """Async submit half of :func:`run_fused_agg_tables`: dispatch every
    table's fused program (no fetch; :func:`_dispatch_window`: a launch a
    table, or a launch a round where the tables lie on several chips).
    Dispatch failures mark the token failed → the drain falls back
    per-table."""
    import time as _time
    tok = InflightFusedAggBatch(prog, tables, places, in_schema,
                                group_exprs, agg_exprs, out_schema)
    if not tables:
        return tok
    # dense first, per table: each morsel carries its own dictionaries
    # (pow2-bucketed, so same-scan tables share one traced program); a
    # table that misses the slot budget rides the batch strategy instead
    from .. import tracing
    with tracing.span("device:dispatch", lane="device",
                      attrs={"program": "fused_agg", "strategy": "plan",
                             "tables": len(tables)}):
        plans = [dense_plan(prog, dt, _max_out_cap(prog, dt))
                 for dt in tables]
    if all(p is not None for p in plans):
        tok.strategy = "dense"
        tok.inner = "+".join(sorted(
            {kernels.dense_inner_loop(dims) for dims, _ in plans}))
        try:
            tok.packs, tok.cuts = _dispatch_window(
                prog, tables, [(p[1], "dense", p[0]) for p in plans])
            tok.submitted_s = _time.perf_counter() - tok.t0
            return tok
        except Exception as exc:
            # resource exhaustion only (anything else propagates): fall
            # through to the sort batch path, counted
            runtime.device_failed("fragment.fused_agg_tables.dense", exc)
            tok.packs, tok.cuts = [], None
    tok.strategy, tok.inner = "sort", None
    try:
        tok.packs, tok.cuts = _dispatch_window(
            prog, tables, [(_OUT_CAP0, "sort", ())] * len(tables))
    except Exception as exc:
        runtime.device_failed("fragment.fused_agg_tables.submit", exc)
        tok.failed = True
    tok.submitted_s = _time.perf_counter() - tok.t0
    return tok


def _dispatch_window(prog: FusedAggProgram, tables, hows):
    """Launch the packed program over a window's ``tables``, table ``i``
    at ``hows[i]`` = ``(out_cap, strategy, dims)``: one launch a table,
    or, where the tables lie on several chips, one a round
    (:func:`_launches`). Returns the launches' outputs and, with rounds,
    the tables each answers (``InflightFusedAggBatch.cuts``). A round
    whose launch fails (resource exhaustion, counted) is launched table
    by table."""
    from .. import tracing
    rounds = _launches(prog, tables, hows)
    if rounds is None:
        tracing.tally("agg_tables_single", len(tables))
        return [_dispatch_packed(prog, dt, cap, strategy, dims=dims)
                for dt, (cap, strategy, dims) in zip(tables, hows)], None
    launches, scalars = rounds
    packs, cuts = [], []
    for members in launches:
        cap, strategy, dims = hows[members[0]]
        if len(members) > 1:
            try:
                packs.append(_dispatch_round(
                    prog, [tables[i] for i in members],
                    [scalars[i] for i in members], cap, strategy, dims))
                cuts.append(members)
                tracing.tally("agg_tables_round", len(members))
                continue
            except Exception as exc:
                runtime.device_failed("fragment.fused_agg_tables.round",
                                      exc)
        for i in members:
            cap, strategy, dims = hows[i]
            packs.append(_dispatch_packed(prog, tables[i], cap, strategy,
                                          dims=dims))
            cuts.append((i,))
        tracing.tally("agg_tables_single", len(members))
    return packs, cuts


def _cut_rounds(fetched, cuts, n_tables: int) -> List[np.ndarray]:
    """A window's fetched launch outputs as one packed result a table,
    in the tables' order: a round's output holds its tables' blocks one
    after another along axis 0."""
    if cuts is None:
        return [np.asarray(m) for m in fetched]
    mats: list = [None] * n_tables
    for m, members in zip(fetched, cuts):
        m = np.asarray(m)
        if len(members) == 1:
            mats[members[0]] = m
        else:
            for i, block in zip(members, np.split(m, len(members))):
                mats[i] = block
    return mats


def _decode_window(tok: InflightFusedAggBatch, idx, mats, pieces) -> list:
    """Decode the packed results ``mats`` of the tables ``idx`` into one
    batch and note each table's rows of it in ``pieces`` as ``(batch,
    lo, hi)``. A grouped block that overflowed its bucket is left out:
    returned as ``(table, grown out_cap)`` to re-run, or, past the
    link-budgeted ceiling, left None in ``pieces`` (host fallback)."""
    from .. import tracing
    prog, tables = tok.prog, tok.tables
    retry, fit = [], []
    with tracing.span("device:decode", lane="device",
                      attrs={"tables": len(idx)}) as sp:
        for i, mat in zip(idx, mats):
            if not prog.nk or not _overflowed(mat, tables[i]):
                fit.append((i, mat))
                continue
            g = int(mat[0, 0])
            cap_limit = _max_out_cap(prog, tables[i])
            if g <= cap_limit:
                retry.append(
                    (i, min(dcol.bucket_capacity(max(g, _OUT_CAP0)),
                            cap_limit)))
        if not fit:
            return retry
        try:
            batch, ends = _decode_lanes(tok, [m for _, m in fit],
                                        [tables[i] for i, _ in fit])
        except Exception as exc:
            runtime.device_failed("fragment.fused_agg_tables.decode", exc)
            return retry
        for k, (i, _) in enumerate(fit):
            pieces[i] = (batch, ends[k], ends[k + 1])
        sp.set("batches", 1)
        sp.set("groups", len(batch))
    return retry


def _runs(pieces, places) -> List[DecodedRun]:
    """Tables in task order -> runs: neighbours (consecutive ``places``)
    whose ``pieces`` follow each other in one batch leave as that batch,
    or the slice of it they cover; a table with no piece stands alone."""
    runs: List[DecodedRun] = []
    cur = None  # [batch, lo, hi, tables, place of the last one]

    def close():
        if cur is not None:
            batch, lo, hi, n, _ = cur
            runs.append(DecodedRun(n, batch if hi - lo == len(batch)
                                   else batch.slice(lo, hi)))

    for piece, at in zip(pieces, places):
        if piece is None:
            close()
            cur = None
            runs.append(DecodedRun(1, None))
        elif cur is not None and piece[0] is cur[0] \
                and piece[1] == cur[2] and at == cur[4] + 1:
            cur[2], cur[3], cur[4] = piece[2], cur[3] + 1, at
        else:
            close()
            cur = [*piece, 1, at]
    close()
    return runs


def drain_fused_agg_tables(tok: InflightFusedAggBatch) -> List[DecodedRun]:
    """Blocking drain half: ALL packed results come back in a single
    pytree ``device_get`` (one batched transfer for the whole window —
    per-task gets would serialize one round trip each; a round's output
    is cut into its tables' blocks, :func:`_cut_rounds`), then decode
    lane by lane over all the window's tables at once
    (:func:`_decode_lanes`); overflowed tables re-dispatch as one batch
    and decode as one. Returns the window's :class:`DecodedRun` s in
    task order, every table in exactly one: a batch for each stretch of
    neighbours decoded together, ``batch`` None for a table that failed
    (the caller falls back for that table alone)."""
    import time as _time

    from . import pipeline
    prog, tables = tok.prog, tok.tables
    if not tables:
        return []
    failed = [DecodedRun(1, None)] * len(tables)
    if tok.failed:
        return failed
    strategy = tok.strategy
    t_drain0 = _time.perf_counter()
    try:
        stacked = _cut_rounds(pipeline.fetch_host(tok.packs), tok.cuts,
                              len(tables))
    except Exception as exc:
        runtime.device_failed("fragment.fused_agg_tables.fetch", exc)
        return failed
    if prog.nk:
        from . import costmodel
        # ONE decision acted on across the whole batch
        costmodel.log_strategy_decision(
            "groupby_strategy", strategy,
            rows=sum(dt.row_count for dt in tables), out_cap=_OUT_CAP0,
            tables=len(tables),
            **({"inner": tok.inner} if tok.inner else {}))
        # submit wall + fetch wall, excluding any in-window queue wait
        # between them (see InflightFusedAgg.submitted_s)
        _ledger_grouped(prog, sum(dt.row_count for dt in tables),
                        max(dt.capacity for dt in tables), _OUT_CAP0,
                        tok.submitted_s
                        + (_time.perf_counter() - t_drain0),
                        len(tok.packs), strategy, tables=len(tables))
    else:
        _ledger_global(prog, sum(dt.row_count for dt in tables),
                       max(dt.capacity for dt in tables),
                       tok.submitted_s + (_time.perf_counter() - t_drain0),
                       len(tok.packs), tables=len(tables))
    pieces: list = [None] * len(tables)
    # overflowed tables are re-dispatched as ONE batch, not per table
    # (each serial round trip pays the link RTT)
    retry = _decode_window(tok, range(len(tables)), stacked, pieces)
    if retry:
        # only the sort strategy overflows (a dense bucket holds every
        # slot): the retried tables re-run it at their grown buckets
        try:
            packs2 = [_dispatch_packed(prog, tables[i], cap, "sort")
                      for i, cap in retry]
            mats = [np.asarray(m) for m in pipeline.fetch_host(packs2)]
            from . import costmodel
            for i, cap in retry:
                costmodel.log_strategy_decision(
                    "groupby_strategy", "sort", rows=tables[i].row_count,
                    out_cap=cap)
        except Exception as exc:
            runtime.device_failed("fragment.fused_agg_tables.retry", exc)
            mats = None
        if mats is not None:
            # a table that overflows its grown bucket too stays None
            _decode_window(tok, [i for i, _ in retry], mats, pieces)
    return _runs(pieces, tok.places)


def run_fused_agg_tables(prog: FusedAggProgram, tables, in_schema: Schema,
                         group_exprs, agg_exprs, out_schema: Schema,
                         places=None) -> List[DecodedRun]:
    """Batched execution over many DeviceTables: dispatch every fused
    program asynchronously, then fetch ALL packed results in a single
    batched device→host transfer (one round of transfers for the whole
    scan instead of one per task) and decode them together. Returns the
    :class:`DecodedRun` s of :func:`drain_fused_agg_tables`; ``places``
    (each table's index among the window's tasks; default: they are all
    neighbours) keeps a run from spanning a task another tier answers.
    Inputs are never donated here:
    the batched overflow retry re-dispatches over the same tables, and
    cache-resident tables share their buffers with the HBM column cache
    anyway.  (Single-sourced as submit + drain so the async pipeline
    overlaps window N+1's submit with window N's drain.)"""
    return drain_fused_agg_tables(submit_fused_agg_tables(
        prog, tables, in_schema, group_exprs, agg_exprs, out_schema,
        places))


# ---------------------------------------------------------------------------
# FusedRegion programs (round 21 whole-query compilation)
#
# A FusedRegion compiles a maximal operator chain — filter/project chains,
# top-k tails, and join→project→partial-agg spines — into ONE traced
# program whose intermediates stay device-resident; only the region's
# packed output crosses the link. Three program families mirror the three
# planner grammars (physical/fusion.py):
#
# - chain: predicate + projection + in-program compaction; the survivors
#   transfer at a static width bucket (overflow re-dispatches grown, the
#   grouped-agg ladder discipline).
# - topk: a chain whose tail argsort runs in-program; only a static
#   ``bucket_capacity(limit)`` slice transfers, never the full table.
# - join_agg: the broadcast build side is encoded + radix-sorted ONCE and
#   stays resident; each probe morsel runs predicate → searchsorted join →
#   joined-plane gather → post-projection → partial grouped agg as one
#   dispatch, with DUAL overflow ladders (join pair width W and group
#   bucket out_cap), both read from the packed header.

_region_cache: Dict[Tuple, object] = {}
#: (predicate, the columns it reads) -> the share of rows it last kept
#: (``FusedRegionProgram.survivors_hint``)
_survivor_shares: Dict[Tuple, float] = {}

#: join pair-width ceiling: past this the fused join's expand planes cost
#: more HBM than the morsel itself and the host join is the right tool
_REGION_MAX_W = 1 << 22


#: rows of a block, and blocks of a block of blocks, in the block search of
#: :func:`_survivor_rows`, and the rows :func:`_take_rows` moves at once: a
#: vector register's lanes
_LANES = 128


def _splits_into_blocks(C: int, w: int) -> bool:
    """May ``w`` slots of a ``C``-row table be served by gathers of
    128-lane rows: the table splits into blocks of 128 x 128 rows, and the
    bucket is narrow enough that the gathered ``[w, 128]`` rows do not
    outweigh the table."""
    return C % (_LANES * _LANES) == 0 and w * 8 <= C


def _survivor_rows(row_mask: jnp.ndarray, w: int) -> jnp.ndarray:
    """Stable compaction: the source row of each of the first ``w`` live
    rows of ``row_mask`` (``[C]`` bool), in source order, as ``[w]`` int32;
    slot ``k`` holds the first row whose running count of live rows
    reaches ``k + 1`` (a slot past the last live row holds some valid row
    index; the caller masks it by the live count).

    On the chip an element gather costs ~20 ns a slot and a gather of a
    128-lane row a fraction of that, while compares over a ``[w, 128]``
    block are nearly free (read on a TPU v5e, PRs 41 and 42:
    ``chip_proof/compact_bench.py``). So the search runs over three
    levels of running counts -- blocks of 128 x 128 rows, blocks of 128
    rows, rows -- each a dense compare-and-count over one gathered row of
    128 counts: 4.4 ms at 4 M rows and 262 144 slots, where the 22-step
    binary search over the flat running count (one element gather a step
    a slot) took 44 ms and a stable sort of the inverted mask 5.3 ms and
    15-20 s of compile a rung. The output stage (:func:`_take_rows`)
    brings the survivors' values back the same way, for the same reason.
    A table that does not split into such blocks, or whose bucket is so
    wide that the gathered rows would outweigh it
    (:func:`_splits_into_blocks`), takes the binary search."""
    C = row_mask.shape[0]
    t = jnp.arange(1, w + 1, dtype=jnp.int32)       # the rank wanted
    B = _LANES
    if not _splits_into_blocks(C, w):
        running = jnp.cumsum(row_mask.astype(jnp.int32))
        return jnp.minimum(jnp.searchsorted(running, t, side="left"),
                           C - 1).astype(jnp.int32)
    n2 = C // (B * B)
    r0 = jnp.cumsum(row_mask.astype(jnp.int32).reshape(n2, B, B), axis=-1)
    e1 = jnp.cumsum(r0[..., -1], axis=-1)   # ends of the 128-row blocks
    e2 = jnp.cumsum(e1[..., -1])            # ends of the 128 x 128 blocks

    def count_below(row, rank):
        return jnp.minimum(jnp.sum(row < rank[:, None], axis=1,
                                   dtype=jnp.int32), B - 1)

    b2 = jnp.minimum(jnp.sum(e2[None, :] < t[:, None], axis=1,
                             dtype=jnp.int32), n2 - 1)
    t1 = t - jnp.take(e2 - e1[..., -1], b2)          # rank in its block
    row1 = jnp.take(e1, b2, axis=0)                  # [w, 128]
    b1 = count_below(row1, t1)
    t0 = t1 - jnp.max(jnp.where(row1 < t1[:, None], row1, 0), axis=1)
    p0 = count_below(jnp.take(r0.reshape(n2 * B, B), b2 * B + b1, axis=0),
                     t0)
    return ((b2 * B + b1) * B + p0).astype(jnp.int32)


#: :func:`gathers_rows`: the row path composes planes over ALL the table's
#: rows before it gathers, so a bucket under this share of the table
#: (1 / 1024: top-k at a small k, a filter that keeps next to nothing) is
#: no dearer by element gathers (read on a TPU v5e, PR 42, 4 M rows and
#: Q19's outputs, elements / rows: 0.31 / 0.30 ms at 1 024 slots, 0.52 /
#: 0.30 at 4 096, 1.74 / 0.54 at 16 384)
_ROW_GATHER_MIN_SHARE = 1024


def gathers_rows(C: int, w: int) -> bool:
    """Does the chain / top-k program of a ``C``-row table at the bucket
    ``w`` bring its outputs back by gathers of 128-lane rows
    (:func:`_take_rows`) and not by element gathers: static a (capacity,
    rung), read from the shapes inside the traced function and by the
    dispatch's tally alike."""
    return _splits_into_blocks(C, w) and w * _ROW_GATHER_MIN_SHARE >= C


def _bit_planes(v: jnp.ndarray):
    """``v``'s bits as int32 planes over its rows -- one, or the two halves
    of an 8-byte type -- and the inverse over planes of any length: the
    values are moved, never computed on."""
    dt = v.dtype
    if dt.itemsize == 8:
        bits = _pack_i64(v)

        def back(lo, hi):
            x = (lo.astype(jnp.int64) & 0xFFFFFFFF) \
                | (hi.astype(jnp.int64) << 32)
            return x if dt == jnp.int64 else lax.bitcast_convert_type(x, dt)
        return [bits.astype(jnp.int32), (bits >> 32).astype(jnp.int32)], back
    if dt == jnp.bool_:
        return [v.astype(jnp.int32)], lambda p: p != 0
    if dt.itemsize == 4:
        if dt == jnp.int32:
            return [v], lambda p: p
        return [lax.bitcast_convert_type(v, jnp.int32)], \
            lambda p: lax.bitcast_convert_type(p, dt)
    same = jnp.dtype(f"uint{8 * dt.itemsize}")  # 1- and 2-byte types
    return [lax.bitcast_convert_type(v, same).astype(jnp.int32)], \
        lambda p: lax.bitcast_convert_type(p.astype(same), dt)


def _take_rows(outs, idx: jnp.ndarray, sel: jnp.ndarray):
    """The output stage of the chain / top-k program by row gathers:
    ``outs`` (``[(values, validity)]`` over the table's ``C`` rows) at the
    source rows ``idx`` (``[w]`` int32), as ``([w] values, [w] validity &
    sel)``, bit for bit what ``jnp.take`` of every plane gives.

    Composed before gathering, densely: every output's validity bit goes
    into ONE int32 plane over all ``C`` rows (the layout of
    :func:`_pack_rows`' word 0), so ``n`` validity gathers become one.
    Then each plane is viewed ``[C / 128, 128]``, the row ``idx // 128`` is
    gathered (``[w, 128]``) and the lane ``idx % 128`` picked by a compare
    against an iota and a masked sum (of one value and 127 zeros, in
    int32: exact). One plane after another (an ``optimization_barrier``
    ties a plane's indices to the plane before it): the ``[w, 128]`` rows
    of a plane are dead before the next plane's are gathered, so the
    program holds one such block, not one a plane (1.08 GB -> 0.16 GB of
    temporaries at Q19's shape), and the TPU's compiler then keeps every
    16 MB plane in on-chip memory while it is gathered: 0.57 ms a plane
    at 262 144 slots where the gather from HBM took 3.7 (device traces of
    the star cell on a TPU v5e, PR 42). Needs
    ``C % 128 == 0``; the caller asks :func:`gathers_rows`."""
    C, w = outs[0][0].shape[0], idx.shape[0]
    bits = jnp.zeros((C,), jnp.int32)
    for i, (_, m) in enumerate(outs):
        bits = bits | (m.astype(jnp.int32) << i)
    planes, backs = [bits], []
    for v, _ in outs:
        ps, back = _bit_planes(v)
        backs.append((len(ps), back))
        planes += ps
    row, lane = idx >> 7, idx & (_LANES - 1)
    got: list = []
    for p in planes:
        if got:
            row, lane, _ = lax.optimization_barrier((row, lane, got[-1]))
        hit = lax.broadcasted_iota(jnp.int32, (w, _LANES), 1) \
            == lane[:, None]
        got.append(jnp.sum(jnp.where(hit, jnp.take(
            p.reshape(C // _LANES, _LANES), row, axis=0), 0), axis=1,
            dtype=jnp.int32))
    vals, at = [], 1
    for n, back in backs:
        vals.append(back(*got[at:at + n]))
        at += n
    return vals, [((got[0] >> i) & 1).astype(jnp.bool_) & sel
                  for i in range(len(outs))]


class FusedRegionProgram:
    """One compiled fusion region (chain or topk shape)."""

    def __init__(self, shape: str, packed_fn, run_packed,
                 compiled: compiler.Compiled,
                 nout: int, has_pred: bool, meta: dict,
                 fused_ops: Tuple[str, ...] = (), limit: int = 0):
        self.shape = shape              # chain | topk
        self.packed_fn = packed_fn
        self._run_packed = run_packed
        self._donate_fn = None
        self.compiled = compiled
        self.nout = nout
        self.has_pred = has_pred
        self.meta = meta
        self.fused_ops = fused_ops
        self.limit = limit
        self.in_np_dtypes = None
        #: i64 words a row of the packed block takes (:func:`_pack_rows`);
        #: what the gates price the fetch with
        self.out_words = _packed_words([f.dtype for f in
                                        compiled.out_fields[:nout]])
        #: per input-capacity survivor bucket observed on the last drain:
        #: the ladder's learned first rung (benign race: worst case one
        #: extra overflow re-dispatch)
        self.w_hint: Dict[int, int] = {}
        #: what :attr:`survivors_hint` is kept under: the predicate and
        #: the columns it reads, whatever the program projects
        self.share_key: Optional[Tuple] = None

    @property
    def survivors_hint(self) -> Optional[float]:
        """Survivors over rows of the tables this program's PREDICATE last
        ran on, on the device or in the host's reader (the scan
        selection's gate prices a table with it, the ladder takes its
        first rung from it); None until one has run. Shared by every
        program of the same predicate over the same columns: a scan with
        a projection above it and the bare scan learn from each other."""
        return _survivor_shares.get(self.share_key)

    @survivors_hint.setter
    def survivors_hint(self, share: Optional[float]) -> None:
        if share is not None:
            _survivor_shares[self.share_key] = share

    def donate_fn(self):
        """Donating twin (r12 discipline): one-shot input planes are dead
        after the in-program compaction, so XLA reuses their HBM. Guarded
        by ``_donation_ok`` — never for cache-resident tables, never on
        CPU."""
        if self._donate_fn is None:
            self._donate_fn = jax.jit(
                self._run_packed, static_argnames=("out_w",),
                donate_argnums=(0, 1))
        return self._donate_fn


def get_fused_region(exprs, predicate, schema: Schema,
                     sort_by=(), descending=(), nulls_first=(),
                     limit: Optional[int] = None,
                     fused_ops: Tuple[str, ...] = ()
                     ) -> Optional[FusedRegionProgram]:
    """Compile (or fetch) a chain/topk region program. None → the region
    does not lower (caller runs the fallback subtree).

    The program: predicate and projection over the table's planes, the
    survivors' source rows (the stable compaction, :func:`_survivor_rows`;
    top-k: the head of the in-program argsort), the output stage that
    brings each output's value and validity at those rows, and the packed
    block (:func:`_pack_rows`). The output stage adapts to the static
    shapes the traced function sees (:func:`gathers_rows`): gathers of
    128-lane rows (:func:`_take_rows`) where the table splits into blocks
    and the bucket is neither too wide nor too narrow for them, element
    gathers everywhere else (an unfiltered chain at ``w == C``, top-k at a
    small ``k``, odd capacities). Either way the block is the same to the
    bit."""
    shape = "topk" if sort_by else "chain"
    key = ("region", shape, tuple(e._key() for e in exprs),
           predicate._key() if predicate is not None else None,
           tuple(e._key() for e in sort_by), tuple(descending),
           tuple(nulls_first), limit, runtime._schema_key(schema))
    hit = _region_cache.get(key)
    if hit is not None:
        _fused_counters["hits"] += 1
        return hit if isinstance(hit, FusedRegionProgram) else None
    _fused_counters["misses"] += 1
    proj = list(exprs) + list(sort_by) + \
        ([predicate] if predicate is not None else [])
    try:
        c = compiler.compile_projection(proj, schema, jit=False)
    except (compiler.NotCompilable, NotImplementedError, ValueError,
            TypeError, KeyError, OverflowError):
        _region_cache[key] = False
        return None
    n = len(exprs)
    if n > _MAX_ROW_OUTPUTS:    # one validity bit an output (_pack_rows)
        _region_cache[key] = False
        return None
    ns = len(sort_by)
    has_pred = predicate is not None
    desc = tuple(bool(d) for d in descending)
    nf = tuple(bool(x) for x in nulls_first)
    k_lim = int(limit or 0)
    meta: dict = {}

    def run_packed(arrays, valids, row_mask, scalars, out_w: int):
        outs = c.fn(arrays, valids, row_mask, scalars)
        if has_pred:
            pv, pm = outs[-1]
            row_mask = row_mask & pv.astype(jnp.bool_) & pm
            outs = outs[:-1]
        # encode_batch capacities are bucket_capacity outputs already;
        # min(shape, bucket(shape)) re-asserts that through the
        # sanctioned chokepoint without ever changing the value
        C = min(row_mask.shape[0], dcol.bucket_capacity(row_mask.shape[0]))
        live = jnp.sum(row_mask).astype(jnp.int32)
        if ns:
            skeys = tuple(v for v, _ in outs[n:])
            svalids = tuple(m for _, m in outs[n:])
            perm = kernels._packed_argsort(
                kernels._sort_codes(skeys, svalids, row_mask, desc, nf), C)
            live = jnp.minimum(live, jnp.asarray(k_lim, jnp.int32))
            outs = outs[:n]
        w = min(out_w, C)
        if ns:
            idx = perm[:w]
        else:
            idx = _survivor_rows(row_mask, w)
        sel = jnp.arange(w, dtype=jnp.int32) < live
        if gathers_rows(C, w):
            vals, valids = _take_rows(outs, idx, sel)
        else:
            vals = [jnp.take(v, idx) for v, _ in outs]
            valids = [jnp.take(m, idx) & sel for _, m in outs]
        meta["region_dtypes"] = [x.dtype for x in vals]
        return _pack_rows(vals, valids, live)

    # the profile names a program after its function: ``jit_run_select``
    # (chain) / ``jit_run_topk``, apart from the fused aggregate's
    # ``jit_run_packed``
    run_packed.__name__ = run_packed.__qualname__ = \
        "run_topk" if ns else "run_select"
    prog = FusedRegionProgram(
        shape, jax.jit(run_packed, static_argnames=("out_w",)),
        run_packed, c, n, has_pred, meta, fused_ops=fused_ops, limit=k_lim)
    # by the columns the predicate reads, not by what else the scan keeps
    prog.share_key = (predicate._key(), tuple(
        (nm, repr(schema[nm].dtype))
        for nm in sorted(set(predicate.column_names())))) \
        if has_pred else None
    try:
        prog.in_np_dtypes = {nm: dcol.device_np_dtype(schema[nm].dtype)
                             for nm in c.needs_cols}
    except (ValueError, KeyError):
        prog.in_np_dtypes = None
    _region_cache[key] = prog
    return prog


def region_start_w(prog: FusedRegionProgram, dt: dcol.DeviceTable) -> int:
    """First transfer-width rung. Top-k transfers its static k bucket;
    an unfiltered chain can never shrink, so it transfers whole; a
    filtered chain bets on selectivity with a quarter-capacity bucket —
    one overflow re-dispatch costs a dispatch, not a scan."""
    if prog.shape == "topk":
        return min(dcol.bucket_capacity(max(prog.limit, 1)), dt.capacity)
    if not prog.has_pred:
        return dt.capacity
    hint = prog.w_hint.get(dt.capacity)
    if hint is not None:
        # learned rung: the last morsel at this capacity drained at this
        # survivor bucket — steady-state selectivity makes it right for
        # the next one, turning the ladder into a one-dispatch path
        return min(hint, dt.capacity)
    if prog.survivors_hint is not None:
        # no table of this capacity has drained, but the predicate's
        # share of survivors is known (the host's reader ran it, or the
        # footer's min / max bound it): that share's bucket
        return min(dcol.bucket_capacity(
            max(int(prog.survivors_hint * dt.row_count), _OUT_CAP0)),
            dt.capacity)
    return min(dcol.bucket_capacity(
        max(min(dt.capacity, dt.row_count) // 4, _OUT_CAP0)), dt.capacity)


class InflightRegion:
    """One in-flight chain/topk region dispatch awaiting its packed
    fetch (+ the ladder state an overflow re-dispatch needs)."""

    __slots__ = ("prog", "dt", "exprs", "fields", "out_w", "donate",
                 "reencode", "packed", "t0", "submitted_s")

    def __init__(self, prog, dt, exprs, fields, out_w, donate, reencode):
        import time as _time
        self.prog = prog
        self.dt = dt
        self.exprs = exprs
        self.fields = fields
        self.out_w = out_w
        self.donate = donate
        self.reencode = reencode
        self.packed = None
        self.t0 = _time.perf_counter()
        self.submitted_s = 0.0


def _dispatch_region(prog: FusedRegionProgram, dt: dcol.DeviceTable,
                     out_w: int, donate: bool = False):
    from .. import tracing
    from ..analysis import retrace_sanitizer
    with tracing.span("device:dispatch", lane="device",
                      attrs={"program": "region", "capacity": dt.capacity,
                             "strategy": prog.shape,
                             "gather": "rows" if gathers_rows(
                                 dt.capacity, min(out_w, dt.capacity))
                             else "elements",
                             "chip": dt.chip or 0}):
        arrays = {n: col.data for n, col in dt.columns.items()}
        valids = {n: col.validity for n, col in dt.columns.items()}
        scalars = runtime._prep_scalars(prog.compiled, dt)
        fn = prog.donate_fn() if donate else prog.packed_fn
        site = "region.topk" if prog.shape == "topk" else "region.chain"
        with retrace_sanitizer.dispatch_scope(
                site,
                (id(prog), dt.capacity, out_w,
                 tuple(s.shape for s in scalars), dt.chip)), \
                tracing.launch(site, dt.chip or 0):
            # runs where its arguments lie: on the table's chip
            return fn(arrays, valids, dt.row_mask, scalars, out_w=out_w)


def submit_region(prog: FusedRegionProgram, batch, exprs, out_schema: Schema
                  ) -> Optional[InflightRegion]:
    """Encode + async dispatch of one chain/topk region morsel; None →
    host fallback (pyobject inputs / encode failure)."""
    import time as _time
    for nm in prog.compiled.needs_cols:
        if batch.get_column(nm).is_pyobject():
            return None
    try:
        dt = dcol.encode_batch(batch, prog.compiled.needs_cols)
    except (ValueError, TypeError):
        return None
    fields = [out_schema[e.name()] for e in exprs]
    donate = _donation_ok(dt)
    tok = InflightRegion(prog, dt, exprs, fields,
                         region_start_w(prog, dt), donate,
                         lambda: dcol.encode_batch(
                             batch, prog.compiled.needs_cols))
    tok.packed = _dispatch_region(prog, dt, tok.out_w, donate=donate)
    tok.submitted_s = _time.perf_counter() - tok.t0
    return tok


def _decode_survivors(prog: FusedRegionProgram, exprs, fields, mats,
                      tables):
    """Packed chain results of ``tables`` (one ``mats`` entry each, every
    one holding its survivors: header <= width) -> ONE RecordBatch, the
    tables' surviving rows in order, and ``ends``: table ``k``'s rows of it
    are ``ends[k]:ends[k + 1]``. Every lane is unpacked and decoded once
    over all the tables (as :func:`_decode_lanes` does for partial
    aggregates); a string column decodes through each table's OWN
    dictionary, a stretch of equal dictionaries at a time."""
    from .. import tracing
    from ..recordbatch import RecordBatch
    from ..series import Series
    counts = [_packed_live(m) for m in mats]
    block = mats[0][:, :counts[0]] if len(mats) == 1 else np.concatenate(
        [m[:, :g] for m, g in zip(mats, counts)], axis=1)
    ends = np.cumsum([0] + counts).tolist()
    cols = []
    for (e, f), (v, m) in zip(zip(exprs, fields), _unpack_rows(
            block, prog.meta["region_dtypes"])):
        coded = f.dtype.is_string() or f.dtype.is_binary()
        runs = _key_dictionary_runs(runtime._string_out_source(e), tables) \
            if coded else [(0, len(tables))]
        parts = [runtime.decode_group_key(
            e, f, v[ends[a]:ends[b]], m[ends[a]:ends[b]], tables[a],
            ends[b] - ends[a]) for a, b in runs]
        cols.append(parts[0] if len(parts) == 1 else Series.concat(parts))
    tracing.tally("decode_tables", len(mats))
    tracing.tally("decode_batches")
    return RecordBatch.from_series(cols), ends


def _ledger_region(prog: FusedRegionProgram, rows: int, nbytes: int,
                   seconds: float, dispatches: int = 1) -> None:
    from . import costmodel
    n_ops = max(len(prog.fused_ops), 2)
    costmodel.ledger_record(
        "region", rows=rows, nbytes=nbytes, seconds=seconds,
        dispatches=dispatches, strategy=prog.shape, fused_ops=n_ops,
        round_trips_saved=n_ops - 1,
        fusion_serial_seconds=costmodel.fusion_serial_estimate(rows, n_ops))


def drain_region(tok: InflightRegion):
    """Blocking drain: one packed fetch → RecordBatch, continuing the
    width ladder when a chain's survivor count outgrew the bucket."""
    import time as _time

    from . import pipeline
    prog = tok.prog
    t_drain0 = _time.perf_counter()
    while True:
        packed = np.asarray(pipeline.fetch_host(tok.packed))
        live = _packed_live(packed)
        w = packed.shape[1]
        if live <= w:
            from .. import tracing
            with tracing.span("device:decode", lane="device",
                              attrs={"tables": 1, "batches": 1,
                                     "groups": live}):
                out, _ = _decode_survivors(prog, tok.exprs, tok.fields,
                                           [packed], [tok.dt])
            if prog.has_pred and prog.shape != "topk":
                prog.w_hint[tok.dt.capacity] = min(
                    dcol.bucket_capacity(max(live, _OUT_CAP0)),
                    tok.dt.capacity)
            _ledger_region(prog, tok.dt.row_count,
                           prog.out_words * 8 * w,
                           tok.submitted_s
                           + (_time.perf_counter() - t_drain0))
            return out
        if tok.donate:
            tok.dt = tok.reencode()
            tok.donate = False
        tok.out_w = min(dcol.bucket_capacity(live), tok.dt.capacity)
        tok.packed = _dispatch_region(prog, tok.dt, tok.out_w)


# A scan's selection over a window of encoded tables (HBM-cache-resident
# or just uploaded): the chain program once a table, ONE fetch and ONE
# decode a window -- the fused aggregate's window discipline
# (``submit_fused_agg_tables`` / ``drain_fused_agg_tables``) with rows,
# not partial groups, coming back.

class InflightSelect:
    """A window's chain dispatches (one a DeviceTable) awaiting ONE
    batched fetch."""

    __slots__ = ("prog", "tables", "places", "exprs", "fields", "max_w",
                 "packs", "t0", "submitted_s", "failed")

    def __init__(self, prog, tables, places, exprs, fields, max_w):
        import time as _time
        self.prog = prog
        self.tables = tables
        self.places = list(range(len(tables))) if places is None \
            else list(places)
        self.exprs = exprs
        self.fields = fields
        #: table -> the widest survivor bucket worth fetching (the gate's
        #: ceiling); past it the table is the host's
        self.max_w = max_w
        self.packs: list = []
        self.t0 = _time.perf_counter()
        self.submitted_s = 0.0
        self.failed = False


def submit_select_tables(prog: FusedRegionProgram, tables, exprs,
                         out_schema: Schema, places=None, max_w=None
                         ) -> InflightSelect:
    """Dispatch the chain program over every table of a window at its
    first rung (:func:`region_start_w`: the learned survivor bucket), no
    fetch. Never donates: resident tables share their planes with the
    HBM cache, and an overflow re-dispatches over the same table. A
    dispatch failure marks the token failed -> the drain hands every
    table to the host."""
    import time as _time
    fields = [out_schema[e.name()] for e in exprs]
    tok = InflightSelect(prog, tables, places, exprs, fields,
                         max_w or (lambda dt: dt.capacity))
    try:
        tok.packs = [_dispatch_region(
            prog, dt, min(region_start_w(prog, dt), tok.max_w(dt)))
            for dt in tables]
    except Exception as exc:
        runtime.device_failed("fragment.select_tables.submit", exc)
        tok.failed = True
    tok.submitted_s = _time.perf_counter() - tok.t0
    return tok


def _decode_select_window(tok: InflightSelect, idx, mats, pieces,
                          rerun: bool = False) -> list:
    """Decode the packed survivors ``mats`` of the tables ``idx`` into one
    batch and note each table's rows of it in ``pieces`` as ``(batch, lo,
    hi)``. A table whose survivors outgrew its bucket is tallied
    (``select_overflows``) and left out: returned as ``(table, grown
    width)`` to re-run, or, past the ceiling ``tok.max_w``, left None in
    ``pieces`` (the caller re-reads its task on the host)."""
    from .. import tracing
    prog, tables = tok.prog, tok.tables
    retry, fit = [], []
    with tracing.span("device:decode", lane="device",
                      attrs={"tables": len(idx)}) as sp:
        for i, mat in zip(idx, mats):
            live, w = _packed_live(mat), mat.shape[1]
            if live <= w:
                fit.append((i, mat))
                continue
            tracing.tally("select_overflows")
            grown = min(dcol.bucket_capacity(live), tables[i].capacity)
            if grown <= tok.max_w(tables[i]):
                retry.append((i, grown))
        if not fit:
            return retry
        try:
            batch, ends = _decode_survivors(
                prog, tok.exprs, tok.fields, [m for _, m in fit],
                [tables[i] for i, _ in fit])
        except Exception as exc:
            runtime.device_failed("fragment.select_tables.decode", exc)
            return retry
        rows_in = 0
        widest: Dict[int, int] = {}
        for k, (i, _) in enumerate(fit):
            pieces[i] = (batch, ends[k], ends[k + 1])
            dt = tables[i]
            rows_in += dt.row_count
            widest[dt.capacity] = max(
                widest.get(dt.capacity, 0),
                min(dcol.bucket_capacity(
                    max(ends[k + 1] - ends[k], _OUT_CAP0)), dt.capacity))
        for cap, w in widest.items():
            # the next scan's first rung: the widest bucket this window's
            # tables of that capacity drained at (a re-run only widens it)
            prog.w_hint[cap] = max(w, prog.w_hint.get(cap, 0)) if rerun \
                else w
        note_select(prog, "device", len(fit), rows_in, ends[-1],
                    row_gather=sum(gathers_rows(tables[i].capacity,
                                                m.shape[1]) for i, m in fit))
        sp.set("batches", 1)
        sp.set("groups", len(batch))
    return retry


def note_select(prog: FusedRegionProgram, tier: str, tables: int,
                rows_in: int, rows_out: int, row_gather: int = 0) -> None:
    """Tally tables this predicate ran over (``costmodel.count_select``;
    ``row_gather``: how many of the device's brought their survivors back
    by row gathers, :func:`gathers_rows`) and keep the survivors' share on
    the program for the gate's next bet and the ladder's first rung."""
    from . import costmodel
    costmodel.count_select(tier, tables, rows_in, rows_out, row_gather)
    if rows_in > 0:
        prog.survivors_hint = rows_out / rows_in


def drain_select_tables(tok: InflightSelect) -> List[DecodedRun]:
    """Blocking drain: ALL the window's packed survivors in a single
    ``device_get``, decoded lane by lane over all its tables at once;
    tables that outgrew their rung re-dispatch as one batch at the grown
    bucket and decode as one. Returns the window's :class:`DecodedRun` s
    in task order; ``batch`` None for a table the device did not answer
    (failed, or more survivors than the ceiling holds)."""
    import time as _time

    from . import pipeline
    prog, tables = tok.prog, tok.tables
    if not tables:
        return []
    failed = [DecodedRun(1, None)] * len(tables)
    if tok.failed:
        return failed
    t_drain0 = _time.perf_counter()
    try:
        mats = [np.asarray(m) for m in pipeline.fetch_host(tok.packs)]
    except Exception as exc:
        runtime.device_failed("fragment.select_tables.fetch", exc)
        return failed
    _ledger_region(prog, sum(dt.row_count for dt in tables),
                   sum(int(m.nbytes) for m in mats),
                   tok.submitted_s + (_time.perf_counter() - t_drain0),
                   len(mats))
    pieces: list = [None] * len(tables)
    retry = _decode_select_window(tok, range(len(tables)), mats, pieces)
    if retry:
        try:
            packs2 = [_dispatch_region(prog, tables[i], w)
                      for i, w in retry]
            mats2 = [np.asarray(m) for m in pipeline.fetch_host(packs2)]
        except Exception as exc:
            runtime.device_failed("fragment.select_tables.retry", exc)
            mats2 = None
        if mats2 is not None:
            _decode_select_window(tok, [i for i, _ in retry], mats2, pieces,
                                  rerun=True)
    return _runs(pieces, tok.places)


class FusedJoinAggProgram:
    """One compiled join_agg region: probe predicate → searchsorted join
    against the pre-sorted resident build side → joined-plane gather →
    post projection → partial grouped agg, as ONE traced program."""

    def __init__(self, packed_fn, run_packed, c_pred, c_post,
                 lkey: str, rkey: str,
                 probe_needs, build_needs, nk: int, ops: Tuple[str, ...],
                 has_post_pred: bool, meta: dict,
                 fused_ops: Tuple[str, ...] = ()):
        self.packed_fn = packed_fn
        self._run_packed = run_packed
        self.c_pred = c_pred            # probe-side predicate (or None)
        self.c_post = c_post            # joined-namespace projection
        self.lkey = lkey
        self.rkey = rkey
        self.probe_needs = probe_needs  # raw probe planes the gather feeds
        self.build_needs = build_needs
        self.nk = nk
        self.ops = ops
        self.has_post_pred = has_post_pred
        self.meta = meta
        self.fused_ops = fused_ops
        self.in_np_dtypes = None        # probe-side planes (warm-up grid)
        self.build_np_dtypes = None     # build-side planes (warm-up grid)


class RegionBuild:
    """The join_agg build side, encoded + radix-sorted once per query;
    every probe morsel's program reuses these resident planes."""

    __slots__ = ("dt", "sorted_key", "perm", "live_count")

    def __init__(self, dt, sorted_key, perm, live_count):
        self.dt = dt
        self.sorted_key = sorted_key
        self.perm = perm
        self.live_count = live_count


_join_sort_jit = None
_join_sort_lock = _threading.Lock()


def prepare_region_build(prog: FusedJoinAggProgram, build_rb
                         ) -> Optional[RegionBuild]:
    """Encode the broadcast build side and sort its join-key plane —
    ONE dispatch for the whole query. None → region declines."""
    global _join_sort_jit
    from ..analysis import retrace_sanitizer
    cols = list(dict.fromkeys([prog.rkey] + list(prog.build_needs)))
    for nm in cols:
        if build_rb.get_column(nm).is_pyobject():
            return None
    try:
        dt = dcol.encode_batch(build_rb, cols)
    except (ValueError, TypeError):
        return None
    if _join_sort_jit is None:
        with _join_sort_lock:
            if _join_sort_jit is None:
                _join_sort_jit = jax.jit(kernels.join_sort_impl)
    k = dt.columns[prog.rkey]
    with retrace_sanitizer.dispatch_scope("region.build",
                                          (dt.capacity,)):
        sorted_key, perm, live = _join_sort_jit(k.data, k.validity,
                                                dt.row_mask)
    return RegionBuild(dt, sorted_key, perm, live)


def get_fused_join_agg(group_exprs, child_exprs, ops: Tuple[str, ...],
                       probe_pred, post_pred, lkey: str, rkey: str,
                       src_schema: Schema, build_schema: Schema,
                       fused_ops: Tuple[str, ...] = ()
                       ) -> Optional[FusedJoinAggProgram]:
    """Compile (or fetch) the join_agg region program. None → the region
    does not lower."""
    key = ("region_ja", tuple(e._key() for e in group_exprs),
           tuple(e._key() for e in child_exprs), ops,
           probe_pred._key() if probe_pred is not None else None,
           post_pred._key() if post_pred is not None else None,
           lkey, rkey, runtime._schema_key(src_schema),
           runtime._schema_key(build_schema))
    hit = _region_cache.get(key)
    if hit is not None:
        _fused_counters["hits"] += 1
        return hit if isinstance(hit, FusedJoinAggProgram) else None
    _fused_counters["misses"] += 1
    from ..schema import Field
    src_names = set(src_schema.column_names)
    joined_schema = Schema(
        [Field(f.name, f.dtype) for f in src_schema]
        + [Field(f.name, f.dtype) for f in build_schema])
    nk = len(group_exprs)
    has_post_pred = post_pred is not None
    proj = list(group_exprs) + list(child_exprs) + \
        ([post_pred] if post_pred is not None else [])
    try:
        c_post = compiler.compile_projection(proj, joined_schema, jit=False)
        c_pred = compiler.compile_projection([probe_pred], src_schema,
                                             jit=False) \
            if probe_pred is not None else None
    except (compiler.NotCompilable, NotImplementedError, ValueError,
            TypeError, KeyError, OverflowError):
        _region_cache[key] = False
        return None
    probe_needs = tuple(nm for nm in c_post.needs_cols if nm in src_names)
    build_needs = tuple(nm for nm in c_post.needs_cols
                        if nm not in src_names)
    meta: dict = {}

    def run_packed(p_arrays, p_valids, p_mask, p_scalars,
                   b_arrays, b_valids, b_sorted, b_perm, b_live,
                   post_scalars, W: int, out_cap: int):
        if c_pred is not None:
            pv, pm = c_pred.fn(p_arrays, p_valids, p_mask, p_scalars)[-1]
            p_mask = p_mask & pv.astype(jnp.bool_) & pm
        counts, starts, total = kernels.join_count_impl(
            p_arrays[lkey], p_valids[lkey], p_mask, b_sorted, b_live)
        owner, ridx, pair_valid = kernels.join_expand_impl(
            counts, starts, b_perm, W)
        j_arrays, j_valids = {}, {}
        for nm in probe_needs:
            j_arrays[nm] = jnp.take(p_arrays[nm], owner)
            j_valids[nm] = jnp.take(p_valids[nm], owner) & pair_valid
        for nm in build_needs:
            j_arrays[nm] = jnp.take(b_arrays[nm], ridx)
            j_valids[nm] = jnp.take(b_valids[nm], ridx) & pair_valid
        outs = c_post.fn(j_arrays, j_valids, pair_valid, post_scalars)
        mask = pair_valid
        if has_post_pred:
            qv, qm = outs[-1]
            mask = mask & qv.astype(jnp.bool_) & qm
            outs = outs[:-1]
        keys = tuple(v for v, _ in outs[:nk])
        kvalids = tuple(m for _, m in outs[:nk])
        vals = tuple(v for v, _ in outs[nk:])
        vvalids = tuple(m for _, m in outs[nk:])
        ok, okv, ov, ovv, g = kernels.grouped_agg_block_impl(
            keys, kvalids, vals, vvalids, mask, ops, out_cap)
        flat = list(ok) + list(okv) + list(ov) + list(ovv)
        meta["grouped_dtypes"] = [x.dtype for x in flat]
        head = jnp.zeros((out_cap,), jnp.int64) \
            .at[0].set(g.astype(jnp.int64)) \
            .at[1].set(total.astype(jnp.int64))
        return jnp.stack([head] + [_pack_i64(x) for x in flat])

    prog = FusedJoinAggProgram(
        jax.jit(run_packed, static_argnames=("W", "out_cap")),
        run_packed, c_pred, c_post, lkey, rkey, probe_needs, build_needs,
        nk, ops, has_post_pred, meta, fused_ops=fused_ops)
    try:
        need = set(probe_needs) | {lkey} \
            | set(c_pred.needs_cols if c_pred is not None else ())
        prog.in_np_dtypes = {
            nm: dcol.device_np_dtype(src_schema[nm].dtype) for nm in need}
        bneed = set(build_needs) | {rkey}
        prog.build_np_dtypes = {
            nm: dcol.device_np_dtype(build_schema[nm].dtype)
            for nm in bneed}
    except (ValueError, KeyError):
        prog.in_np_dtypes = None
        prog.build_np_dtypes = None
    _region_cache[key] = prog
    return prog


class InflightJoinAgg:
    """One in-flight join_agg region dispatch (+ dual-ladder state)."""

    __slots__ = ("prog", "dt", "build", "group_exprs", "key_fields",
                 "agg_fields", "W", "out_cap", "packed", "t0",
                 "submitted_s")

    def __init__(self, prog, dt, build, group_exprs, key_fields,
                 agg_fields, W, out_cap):
        import time as _time
        self.prog = prog
        self.dt = dt
        self.build = build
        self.group_exprs = group_exprs
        self.key_fields = key_fields
        self.agg_fields = agg_fields
        self.W = W
        self.out_cap = out_cap
        self.packed = None
        self.t0 = _time.perf_counter()
        self.submitted_s = 0.0


def _dispatch_join_agg(prog: FusedJoinAggProgram, dt: dcol.DeviceTable,
                       build: RegionBuild, W: int, out_cap: int):
    from .. import tracing
    from ..analysis import retrace_sanitizer
    with tracing.span("device:dispatch", lane="device",
                      attrs={"program": "region", "capacity": dt.capacity,
                             "strategy": "join_agg"}):
        p_arrays = {n: col.data for n, col in dt.columns.items()}
        p_valids = {n: col.validity for n, col in dt.columns.items()}
        b_arrays = {n: col.data for n, col in build.dt.columns.items()}
        b_valids = {n: col.validity
                    for n, col in build.dt.columns.items()}
        p_scalars = runtime._prep_scalars(prog.c_pred, dt) \
            if prog.c_pred is not None else ()
        post_scalars = _prep_scalars_joined(prog.c_post, dt, build.dt)
        with retrace_sanitizer.dispatch_scope(
                "region.join_agg",
                (id(prog), dt.capacity, build.dt.capacity, W, out_cap,
                 tuple(s.shape for s in p_scalars),
                 tuple(s.shape for s in post_scalars))), \
                tracing.launch("region.join_agg", dt.chip or 0):
            return prog.packed_fn(
                p_arrays, p_valids, dt.row_mask, p_scalars, b_arrays,
                b_valids, build.sorted_key, build.perm, build.live_count,
                post_scalars, W=W, out_cap=out_cap)


def _prep_scalars_joined(c: compiler.Compiled, p_dt: dcol.DeviceTable,
                         b_dt: dcol.DeviceTable):
    """Scalar prep over the joined namespace: each spec's dictionary
    comes from whichever side encoded the column."""
    import pyarrow as pa
    scalars = []
    for spec in c.scalar_specs:
        src = p_dt.columns.get(spec.col) or b_dt.columns.get(spec.col)
        d = src.dictionary if src is not None else None
        if d is None:
            d = pa.array([], type=pa.large_string())
        scalars.append(jnp.asarray(spec.fn(d)))
    return tuple(scalars)


def submit_join_agg(prog: FusedJoinAggProgram, batch, build: RegionBuild,
                    group_exprs, agg_exprs, out_schema: Schema,
                    start_out_cap: int = _OUT_CAP0
                    ) -> Optional[InflightJoinAgg]:
    """Encode + async dispatch of one probe morsel; None → host
    fallback."""
    import time as _time
    need = list(dict.fromkeys(
        [prog.lkey] + list(prog.probe_needs)
        + list(prog.c_pred.needs_cols if prog.c_pred is not None else ())))
    for nm in need:
        if batch.get_column(nm).is_pyobject():
            return None
    try:
        dt = dcol.encode_batch(batch, need)
    except (ValueError, TypeError):
        return None
    key_fields = [out_schema[e.name()] for e in group_exprs]
    agg_fields = [out_schema[e.name()] for e in agg_exprs]
    # expected ≤1 build match per probe row (FK equi-join): start the pair
    # bucket at the probe capacity; the header's true total grows it
    W = dt.capacity
    tok = InflightJoinAgg(prog, dt, build, group_exprs, key_fields,
                          agg_fields, W,
                          min(dcol.bucket_capacity(max(start_out_cap,
                                                       _OUT_CAP0)),
                              dcol.bucket_capacity(W)))
    tok.packed = _dispatch_join_agg(prog, dt, build, tok.W, tok.out_cap)
    tok.submitted_s = _time.perf_counter() - tok.t0
    return tok


def drain_join_agg(tok: InflightJoinAgg):
    """Blocking drain: one packed fetch → partial-group RecordBatch,
    continuing the DUAL overflow ladder (pair width W, group bucket
    out_cap) read from the packed header. None → host fallback."""
    import time as _time

    from . import costmodel, pipeline
    prog = tok.prog
    t_drain0 = _time.perf_counter()
    while True:
        packed = np.asarray(pipeline.fetch_host(tok.packed))
        g = int(packed[0, 0])
        total = int(packed[0, 1])
        grown = False
        if total > tok.W:
            if total > _REGION_MAX_W:
                return None
            tok.W = dcol.bucket_capacity(total)
            grown = True
        if g > tok.out_cap:
            cap_limit = dcol.bucket_capacity(max(tok.W, tok.dt.capacity))
            if g > cap_limit:
                return None
            tok.out_cap = min(dcol.bucket_capacity(g), cap_limit)
            grown = True
        if grown:
            tok.packed = _dispatch_join_agg(prog, tok.dt, tok.build,
                                            tok.W, tok.out_cap)
            continue
        from .. import tracing
        from ..recordbatch import RecordBatch
        dtypes = prog.meta["grouped_dtypes"]
        nk, nv = prog.nk, len(tok.agg_fields)
        with tracing.span("device:decode", lane="device",
                          attrs={"tables": 1, "groups": g}):
            rows = packed[1:]
            cols = []
            for i, (e, f) in enumerate(zip(tok.group_exprs,
                                           tok.key_fields)):
                kv = _unpack_i64(rows[i][:g], dtypes[i])
                km = _unpack_i64(rows[nk + i][:g],
                                 dtypes[nk + i]).astype(np.bool_)
                dc = dcol.DeviceColumn(kv, km, f.dtype, None)
                cols.append(dcol.decode_column(f.name, dc, g))
            for i, f in enumerate(tok.agg_fields):
                vv = _unpack_i64(rows[2 * nk + i][:g], dtypes[2 * nk + i])
                vm = _unpack_i64(rows[2 * nk + nv + i][:g],
                                 dtypes[2 * nk + nv + i]).astype(np.bool_)
                dc = dcol.DeviceColumn(vv, vm, f.dtype, None)
                cols.append(dcol.decode_column(f.name, dc, g))
            out = RecordBatch.from_series(cols)
        # the region matched this probe morsel against its build side
        # inside the program: ``joins.match_indices`` never saw the pair
        from .. import joins
        joins.tally_pair("device", tok.dt.row_count,
                         tok.build.dt.row_count, total)
        n_ops = max(len(prog.fused_ops), 3)
        secs = tok.submitted_s + (_time.perf_counter() - t_drain0)
        costmodel.ledger_record(
            "region", rows=tok.dt.row_count,
            nbytes=(1 + 2 * (nk + nv)) * 8 * tok.out_cap, seconds=secs,
            strategy="join_agg", fused_ops=n_ops,
            round_trips_saved=n_ops - 1,
            fusion_serial_seconds=costmodel.fusion_serial_estimate(
                tok.dt.row_count, n_ops))
        return out, g


def fused_region_programs() -> List[object]:
    """Every region program compiled so far — the AOT warm-up grid
    (device/warmup.py) iterates these alongside the fused-agg library."""
    return [p for p in _region_cache.values()
            if isinstance(p, (FusedRegionProgram, FusedJoinAggProgram))]
