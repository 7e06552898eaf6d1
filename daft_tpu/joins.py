"""Join kernels (host tier).

Reference capability: ``src/daft-recordbatch/src/ops/joins/mod.rs:78-195``
(hash_join / sort_merge_join / cross_join) and the probe-table machinery
(``probeable/probe_table.rs:19``). Here the host path factorizes join keys to
dense group ids (Arrow C++ dictionary encode + np.unique over code rows), then
runs a fully vectorized sort+searchsorted merge — the same sort-merge
formulation the TPU tier uses in ``device.kernels.join_fused_kernel``, so the
two tiers share one algorithm family.

Join semantics follow the reference: inner/left/right/outer/semi/anti; NULL
keys never match; right-side columns colliding with left names get a
``right.`` prefix; outer joins coalesce key columns.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .expressions import Expression
from .series import Series


def _factorize_pair(l_arrs: List[pa.Array], r_arrs: List[pa.Array]
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Map rows of (left, right) key columns to shared dense ids.

    Returns (l_gids, r_gids, l_valid, r_valid); gid comparisons implement
    multi-column key equality. NULL in any key column marks the row invalid.
    """
    n_l = len(l_arrs[0]) if l_arrs else 0
    n_r = len(r_arrs[0]) if r_arrs else 0
    code_cols = []
    l_valid = np.ones(n_l, dtype=bool)
    r_valid = np.ones(n_r, dtype=bool)
    for la, ra in zip(l_arrs, r_arrs):
        if la.type != ra.type:
            from .datatype import DataType
            from .expressions.typing import supertype
            st = supertype(DataType.from_arrow_type(la.type),
                           DataType.from_arrow_type(ra.type)).to_arrow()
            la, ra = la.cast(st), ra.cast(st)
        combined = pa.chunked_array([la, ra]).combine_chunks()
        if pa.types.is_integer(combined.type) \
                and not pa.types.is_uint64(combined.type):
            # integer keys: range-based codes (value - min) skip the
            # dictionary hash table entirely — O(n) with no table build.
            # TPC-H/TPC-DS keys are dense ints, so the range stays tight.
            # Validity comes from Arrow's null mask, never a value
            # sentinel (INT64_MIN is a legal key); uint64 keys ≥ 2^63
            # don't fit int64 and take the dictionary path below.
            valid = np.asarray(pc.is_valid(combined)
                               .to_numpy(zero_copy_only=False), dtype=bool)
            vals = np.asarray(pc.fill_null(combined.cast(pa.int64()), 0)
                              .to_numpy(zero_copy_only=False),
                              dtype=np.int64)
            live = vals[valid]
            lo = int(live.min()) if live.size else 0
            hi = int(live.max()) if live.size else 0
            if hi - lo < (1 << 40):
                codes = np.where(valid, vals - lo, -1)
                l_valid &= valid[:n_l]
                r_valid &= valid[n_l:]
                code_cols.append(codes)
                continue
        codes_arr = combined.dictionary_encode().indices
        codes = np.asarray(pc.fill_null(codes_arr, -1)
                           .to_numpy(zero_copy_only=False), dtype=np.int64)
        valid = codes >= 0
        l_valid &= valid[:n_l]
        r_valid &= valid[n_l:]
        code_cols.append(codes)
    if len(code_cols) == 1:
        gids = code_cols[0]
    else:
        # arithmetic packing: per-column codes are bounded, so
        # gid = ((c0 * card1 + c1) * card2 + c2)… fits int64 while the
        # cardinality product stays under 2^62 — the structured-void
        # np.unique fallback (memcmp sort, ~µs/row) only runs past that
        maxes = [int(c.max()) + 2 if c.size else 2 for c in code_cols]
        prod = 1
        for m in maxes:
            prod *= m
        if 0 < prod < (1 << 62):
            gids = code_cols[0].astype(np.int64, copy=True)
            for c, m in zip(code_cols[1:], maxes[1:]):
                gids *= m
                gids += c
        else:
            stacked = np.ascontiguousarray(
                np.stack(code_cols, axis=1).astype(np.int64))
            void = stacked.view([("", np.int64)] * stacked.shape[1]).ravel()
            _, gids = np.unique(void, return_inverse=True)
            gids = gids.astype(np.int64)
    return gids[:n_l], gids[n_l:], l_valid, r_valid


def match_indices(l_gids: np.ndarray, r_gids: np.ndarray,
                  l_valid: np.ndarray, r_valid: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized merge: for each left row, all matching right rows.

    Returns (li, ri, l_match_counts): parallel index arrays of the matching
    pairs plus per-left-row match counts.

    The device tier's FUSED sort/searchsorted/expand kernel
    (``device.kernels.join_fused_kernel`` — one dispatch, one packed
    result transfer) is chosen by the measured link cost model
    (``device.costmodel.join_wins``): the output is row-shaped (one
    index pair per match) and the kernel's rate as read on a TPU v5e
    (``costmodel.DEV_JOIN_ROWS_PER_S``) is under the host's, so the
    model picks numpy for every bucket pair until the kernel changes.
    ``DAFT_TPU_DEVICE_JOIN=1/0`` force-overrides. Each pair is tallied on
    the query's trace by the tier that matched it (``_tally_pair``).
    """
    from . import tracing
    from .analysis import knobs
    env = knobs.env_raw("DAFT_TPU_DEVICE_JOIN")
    use_device = env == "1"
    n_l, n_r = len(l_gids), len(r_gids)
    if env is None:
        from .device import costmodel, runtime as drt
        # output estimate: FK-join shaped — about one match per probe row
        est_out = 2 * 8 * max(n_l, n_r)
        # priced SERIAL on purpose: the join dispatch runs inline on its
        # calling thread, not inside the r17 in-flight window, so there
        # are no neighbor dispatches to hide its transfer behind —
        # join_wins(window=) waits for the join path to ride the
        # pipeline before claiming the overlap discount
        use_device = (drt.device_enabled()
                      and n_l + n_r >= 8192
                      and costmodel.join_wins(
                          n_l, n_r,
                          l_gids.nbytes + r_gids.nbytes
                          + l_valid.nbytes + r_valid.nbytes, est_out))
    if use_device:
        out = _device_match_indices(l_gids, r_gids, l_valid, r_valid)
        if out is not None:
            tally_pair("device", n_l, n_r, len(out[0]))
            return out
    # build: the right side's keys, sorted
    with tracing.span("join:build", lane="pipeline",
                      attrs={"rows": n_r, "side": "right", "step": "sort",
                             "rows_left": n_l, "rows_right": n_r}):
        r_idx = np.flatnonzero(r_valid)
        r_vals = r_gids[r_idx]
        order = np.argsort(r_vals, kind="stable")
        r_sorted_vals = r_vals[order]
        r_sorted_idx = r_idx[order]

    # probe: each left key's run in them, expanded to index pairs
    with tracing.span("join:probe", lane="pipeline",
                      attrs={"rows": n_l, "side": "left",
                             "step": "match"}) as sp:
        starts = np.searchsorted(r_sorted_vals, l_gids, side="left")
        ends = np.searchsorted(r_sorted_vals, l_gids, side="right")
        counts = np.where(l_valid, ends - starts, 0)
        total = int(counts.sum())
        li = np.repeat(np.arange(n_l), counts)
        cum = np.cumsum(counts) - counts  # exclusive prefix, same length as counts
        offsets = np.arange(total) - np.repeat(cum, counts)
        ri = r_sorted_idx[np.repeat(starts, counts) + offsets]
        sp.set("pairs", total)
    tally_pair("host", n_l, n_r, total)
    return li, ri, counts


def tally_pair(tier: str, n_l: int, n_r: int, pairs: int) -> None:
    """One matched bucket pair on the query's trace: which tier matched
    it, its rows, the rows of its smaller side, the index pairs it gave
    (its output rows) and the largest pair so far
    (``summary()["joins"]``). ``match_indices`` tallies here, and so does
    a join matched without passing it (``fragment.drain_join_agg``: a
    probe morsel against the fused region's build side)."""
    from . import tracing
    tracing.tally(f"join_pairs_{tier}")
    tracing.tally(f"join_rows_{tier}", n_l + n_r)
    tracing.tally("join_rows_small", min(n_l, n_r))
    tracing.tally("join_rows_out", pairs)
    tracing.tally_max("join_max_pair_rows", n_l + n_r)


def _take_nullable(s: Series, idx: np.ndarray, valid: np.ndarray) -> Series:
    if s.is_pyobject():
        out = np.empty(len(idx), dtype=object)
        vals = s._pyobjs
        for i, (j, v) in enumerate(zip(idx, valid)):
            out[i] = vals[j] if v else None
        return Series(s.name(), s.datatype(), pyobjs=out)
    ia = pa.array(idx, mask=~valid)
    return Series(s.name(), s.datatype(), arrow=s.to_arrow().take(ia))


def _device_match_indices(l_gids, r_gids, l_valid, r_valid):
    """Fused single-dispatch device join index generation: build-side
    sort + probe counts + prefix-sum expansion
    (``kernels.join_fused_kernel``), ONE jit program returning ONE packed
    index matrix (r5's three-phase pipeline paid two host round-trips
    between phases).
    The output bucket is sized FK-shaped (≈ one match per probe row); a
    larger true total re-dispatches once at the fitting bucket, the
    grouped-agg overflow discipline. None on device-off."""
    from .device import runtime as drt
    if not drt.device_enabled():
        return None
    import time as _time

    import jax
    import jax.numpy as jnp

    from .device import costmodel, kernels as K, mfu
    from .device.column import bucket_capacity

    def pad(a, cap, fill=0):
        out = np.full(cap, fill, dtype=a.dtype)
        out[:len(a)] = a
        return out

    n_l, n_r = len(l_gids), len(r_gids)
    c_l, c_r = bucket_capacity(n_l), bucket_capacity(n_r)
    lmask = np.zeros(c_l, bool)
    lmask[:n_l] = True
    rmask = np.zeros(c_r, bool)
    rmask[:n_r] = True

    def dispatch(cap):
        # device arrays are rebuilt per dispatch: the kernel DONATES the
        # build side's buffers on real chips, so an overflow re-dispatch
        # cannot reuse them
        from .analysis import retrace_sanitizer
        # declared trace signature: build/probe capacity classes + the
        # out-capacity bucket; the same signature must re-enter the jit
        # cache, never re-trace. The fetch is ``join:device``'s own: a
        # ``device:fetch`` span here would nest one leaf in another
        args = (jnp.asarray(pad(l_gids.astype(np.int64), c_l)),
                jnp.asarray(pad(l_valid, c_l)), jnp.asarray(lmask),
                jnp.asarray(pad(r_gids.astype(np.int64), c_r)),
                jnp.asarray(pad(r_valid, c_r)), jnp.asarray(rmask))
        with retrace_sanitizer.dispatch_scope("kernels.join_fused",
                                              (c_l, c_r, cap)), \
                tracing.launch("kernels.join_fused"):
            packed = K.join_fused_kernel(*args, out_capacity=cap)
        return np.asarray(jax.device_get(packed))

    from . import tracing
    with tracing.span("join:device", lane="device",
                      attrs={"rows": n_l + n_r, "rows_left": n_l,
                             "rows_right": n_r}) as sp:
        t0 = _time.perf_counter()
        cap = max(bucket_capacity(max(n_l, n_r, 1)), 1024)
        packed = dispatch(cap)
        counts = packed[2, :n_l].astype(np.int64)
        total = int(counts.sum())
        dispatches, nbytes = 1, mfu.join_bytes_model(c_l, c_r, cap)
        if total > cap:  # rare: many-to-many blowup past the FK estimate
            cap = bucket_capacity(total)
            packed = dispatch(cap)
            dispatches += 1
            nbytes += mfu.join_bytes_model(c_l, c_r, cap)
        li = packed[0, :total].astype(np.int64)
        ri = packed[1, :total].astype(np.int64)
        seconds = _time.perf_counter() - t0
        sp.set("capacity", cap)
        sp.set("pairs", total)
        sp.set("bytes", int(packed.nbytes))
    # after the leaf closed: the ledger's own ``device:join`` span is the
    # pair's sibling, not its child
    costmodel.ledger_record("join", rows=n_l + n_r, nbytes=nbytes,
                            seconds=seconds, dispatches=dispatches,
                            strategy="sort")
    return li, ri, counts


def join_recordbatch(left, right, left_on: List[Expression],
                     right_on: List[Expression], how: str = "inner"):
    from . import tracing
    from .recordbatch import RecordBatch

    # build: both sides' keys to shared dense ids (then, in
    # ``match_indices``, the right side's ids sorted)
    with tracing.span("join:build", lane="pipeline",
                      attrs={"rows": len(left) + len(right),
                             "side": "both", "step": "keys"}):
        l_keys = [left.eval_expression(e) for e in left_on]
        r_keys = [right.eval_expression(e) for e in right_on]
        l_gids, r_gids, l_valid, r_valid = _factorize_pair(
            [k.to_arrow() for k in l_keys], [k.to_arrow() for k in r_keys])

    if how in ("semi", "anti"):
        with tracing.span("join:probe", lane="pipeline",
                          attrs={"rows": len(left), "side": "left",
                                 "step": how}):
            matched_gids = np.unique(r_gids[r_valid])
            has = np.isin(l_gids, matched_gids) & l_valid
            mask = has if how == "semi" else ~has
            return RecordBatch(left.schema,
                               [c.filter(mask) for c in left.columns()],
                               int(mask.sum()))

    li, ri, counts = match_indices(l_gids, r_gids, l_valid, r_valid)
    with tracing.span("join:probe", lane="pipeline",
                      attrs={"rows": len(li), "side": "both",
                             "step": "take"}):
        return _assemble_join(left, right, left_on, right_on, how, r_keys,
                              li, ri, counts)


def _assemble_join(left, right, left_on, right_on, how: str, r_keys,
                   li, ri, counts):
    """The join's output rows from the matched index pairs: the
    unmatched rows of an outer side, then every column taken."""
    from .recordbatch import RecordBatch
    l_matched_mask = np.ones(len(li), dtype=bool)
    r_matched_mask = np.ones(len(ri), dtype=bool)

    if how in ("left", "outer", "full"):
        unmatched_l = np.flatnonzero(counts == 0)
        li = np.concatenate([li, unmatched_l])
        ri = np.concatenate([ri, np.zeros(len(unmatched_l), dtype=ri.dtype)])
        l_matched_mask = np.concatenate(
            [l_matched_mask, np.ones(len(unmatched_l), dtype=bool)])
        r_matched_mask = np.concatenate(
            [r_matched_mask, np.zeros(len(unmatched_l), dtype=bool)])
    if how in ("right", "outer", "full"):
        r_hit = np.zeros(len(right), dtype=bool)
        r_hit[ri[r_matched_mask]] = True
        unmatched_r = np.flatnonzero(~r_hit)
        li = np.concatenate([li, np.zeros(len(unmatched_r), dtype=li.dtype)])
        ri = np.concatenate([ri, unmatched_r])
        l_matched_mask = np.concatenate(
            [l_matched_mask, np.zeros(len(unmatched_r), dtype=bool)])
        r_matched_mask = np.concatenate(
            [r_matched_mask, np.ones(len(unmatched_r), dtype=bool)])

    # column assembly --------------------------------------------------
    l_key_names = [e.name() for e in left_on]
    r_key_names = [e.name() for e in right_on]
    left_names = set(left.column_names())

    out_cols: List[Series] = []
    for c in left.columns():
        s = _take_nullable(c, li, l_matched_mask)
        if how in ("outer", "full") and c.name() in l_key_names:
            # coalesce join keys from both sides
            ki = l_key_names.index(c.name())
            r_key_taken = _take_nullable(r_keys[ki], ri, r_matched_mask)
            merged = pc.if_else(
                pa.array(l_matched_mask),
                s.to_arrow(),
                r_key_taken.cast(s.datatype()).to_arrow())
            s = Series(c.name(), s.datatype(), arrow=merged)
        out_cols.append(s)
    for c in right.columns():
        if c.name() in r_key_names:
            ki = r_key_names.index(c.name())
            # drop right key when it pairs with an identically-named left key
            if ki < len(l_key_names) and l_key_names[ki] == c.name():
                continue
        nm = c.name()
        if nm in left_names:
            nm = f"right.{nm}"
        out_cols.append(_take_nullable(c, ri, r_matched_mask).rename(nm))
    return RecordBatch.from_series(out_cols) if out_cols else RecordBatch.empty()
