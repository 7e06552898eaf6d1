"""Hash-based relational kernels as Pallas programs (round 12).

STATUS (PR 23): INTERPRETER-ONLY. These kernels have never compiled for a
TPU. Asked for a described ``v5e:2x2`` (JAX 0.9.0 / libtpu 0.0.34,
``interpret=False``), the TPU kernel compiler refuses both entry points
with ``NotImplementedError: Unimplemented primitive in Pallas TPU lowering
for KernelType.TC: dynamic_slice`` — the bodies index vectors with traced
scalars, scatter with ``.at[j].set/add/min/max`` inside
``fori_loop``/``while_loop`` and carry ``uint64`` words. So the cost model
(``costmodel._hash_capable_backend``) never picks them in ``auto``; a
forced ``DAFT_TPU_KERNEL_GROUPBY=hash`` / ``DAFT_TPU_KERNEL_JOIN=hash`` on
an accelerator raises the compiler's error. Rewrite-for-the-TPU or delete
is ROADMAP A2(b). What follows describes the design, which the Pallas
interpreter executes on the CPU (parity tests only):

- ``hash_grouped_agg_impl``: an open-addressing hash table (linear
  probing over the r6 packed u64 key codes — ``kernels._sort_codes`` /
  ``kernels._packed_chunks`` are reused verbatim, so hash and sort agree
  bit-for-bit on key identity), accumulating the DECOMPOSABLE partial
  states of ``aggs.AGG_DECOMPOSITION`` (count / sum / sumsq / min / max /
  first) directly in the table slots. One pass over the data replaces
  sort + inverse-permutation sort + segment reductions.
- ``hash_join_impl``: build the same table over the build side with
  per-slot insertion-order chains (head/tail/next links), then stream the
  probe side through a second Pallas kernel emitting matched index pairs
  into the r6 packed ``[3, W]`` result matrix — same overflow
  re-dispatch contract as ``kernels.join_fused_impl``, same pair order
  (left-major, ascending right row), so it is a drop-in strategy swap.

Intended kernel shape: each grid step streams one row block, loads the
table planes into loop-carried VALUES, runs the probe/insert loop as pure
JAX (``lax.while_loop`` probing, ``.at[].set/add/min/max`` updates), and
writes the planes back once. Tables above ``DAFT_TPU_KERNEL_MAX_TABLE``
slots stay on the sort path.

``interpret_default()``: the CPU backend interprets; an accelerator NEVER
interprets unless the user set ``DAFT_TPU_KERNEL_INTERPRET=1`` themselves.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# same x64 requirement as kernels.py: the packed key codes are u64 words
jax.config.update("jax_enable_x64", True)

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xBF58476D1CE4E5B9)
_M3 = np.uint64(0x94D049BB133111EB)


class HashKeyWidthError(ValueError):
    """The key set packs wider than the hash-table key budget — the
    dispatch site must route this (program, key set) to the sort path,
    which handles any width as a stable LSD radix. A DEDICATED type so
    fallback handlers cannot swallow unrelated ``ValueError``s raised
    while tracing the hash program (those must surface, not silently pin
    the program to sort)."""


# ------------------------------------------------------------ configuration

def interpret_default() -> bool:
    """Pallas interpreter mode on the CPU backend only. On an accelerator
    this is False unless the user set ``DAFT_TPU_KERNEL_INTERPRET=1``
    themselves — a kernel the chip's compiler refuses must fail loudly,
    not crawl through the emulator. Stable per process (the backend
    cannot change under us), so reading it at trace time cannot mask a
    retrace."""
    from ..analysis import knobs
    v = knobs.env_raw("DAFT_TPU_KERNEL_INTERPRET")
    if v is not None:
        s = v.strip().lower()
        if s in ("1", "true", "on", "yes"):
            return True
        if s in ("0", "false", "off", "no"):
            return False
        # "auto" (the documented default spelling) or anything else:
        # backend autodetection — an operator exporting the displayed
        # default must not silently force the emulator onto silicon
    from . import backend
    return not backend.is_accelerator()


def block_rows(cap: int) -> int:
    """Rows per Pallas grid step (power of two, divides the padded
    capacity — both are powers of two)."""
    from ..analysis import knobs
    b = knobs.env_int("DAFT_TPU_KERNEL_BLOCK")
    b = 1 << max(int(b).bit_length() - 1, 0)  # round down to a power of 2
    return max(min(b, cap), 1)


def max_table_slots() -> int:
    from ..analysis import knobs
    return knobs.env_int("DAFT_TPU_KERNEL_MAX_TABLE")


def hash_load_factor() -> float:
    """Clamped STRICTLY below 1.0: the overflow contract needs the table
    to hold more slots than the group budget (a table with exactly
    ``out_cap`` slots fills silently instead of signalling ``group_count
    > out_cap``, dropping groups from the answer)."""
    from ..analysis import knobs
    return min(max(knobs.env_float("DAFT_TPU_KERNEL_HASH_LOAD"), 0.05),
               0.95)


def hash_pack_words(dtypes: Sequence) -> Optional[int]:
    """u64 words one table key occupies for these key dtypes (per-key
    null-rank bit + value bits, no dead bit — liveness is a separate
    mask), or None when the pack exceeds the hash-key budget
    (``DAFT_TPU_KERNEL_HASH_MAX_BITS``, ≤128) and the caller must take
    the sort path (which handles any width as a stable LSD radix)."""
    from ..analysis import knobs
    from . import kernels
    bits = sum(1 + kernels._key_bits(jnp.dtype(dt)) for dt in dtypes)
    limit = min(int(knobs.env_int("DAFT_TPU_KERNEL_HASH_MAX_BITS")), 128)
    if bits > limit:
        return None
    return 1 if bits <= 64 else 2


def table_capacity(out_cap: int) -> int:
    """Table slots for a group budget of ``out_cap``: the load-factor
    knob bounds probe-chain length (power of two for the mask probe)."""
    want = int(np.ceil(out_cap / hash_load_factor()))
    t = 128
    while t < want:
        t <<= 1
    return t


def _mix(w0: jnp.ndarray, w1: Optional[jnp.ndarray], tmask: int) -> jnp.ndarray:
    """splitmix64 finalizer over the packed key word(s) → table slot."""
    x = w0 if w1 is None else w0 ^ (w1 * _M1)
    x = (x + _M1)
    x = (x ^ (x >> jnp.uint64(30))) * _M2
    x = (x ^ (x >> jnp.uint64(27))) * _M3
    x = x ^ (x >> jnp.uint64(31))
    return (x.astype(jnp.uint32) & jnp.uint32(tmask)).astype(jnp.int32)


# --------------------------------------------------------- agg state planes

def agg_state_specs(ops: Tuple[str, ...], val_dtypes: Sequence
                    ) -> List[Tuple[int, str, str, object]]:
    """Table state planes for one agg list: ``(val_index, op, kind,
    dtype)`` rows, ``kind`` ∈ {cnt, sum, sumsq, min, max, first}.

    Accumulator dtypes mirror the sort kernels exactly so the two
    strategies stay value-parity (int/bool sums exact in i64, float sums
    in the value's own float width)."""
    specs: List[Tuple[int, str, str, object]] = []
    for i, (op, dt) in enumerate(zip(ops, val_dtypes)):
        dt = jnp.dtype(dt)
        is_float = jnp.issubdtype(dt, jnp.floating)
        acc = dt if is_float else jnp.int64
        specs.append((i, op, "cnt", jnp.int32))
        if op in ("sum", "mean", "var", "stddev"):
            specs.append((i, op, "sum", acc))
        if op in ("var", "stddev"):
            fdt = dt if dt == jnp.float32 else \
                jnp.zeros((), jnp.float64).dtype
            specs.append((i, op, "sumsq", fdt))
        if op in ("min", "bool_and"):
            specs.append((i, op, "min", jnp.int8 if dt == jnp.bool_ else dt))
        if op in ("max", "bool_or"):
            specs.append((i, op, "max", jnp.int8 if dt == jnp.bool_ else dt))
        if op == "any_value":
            specs.append((i, op, "first", jnp.int8 if dt == jnp.bool_
                          else dt))
    return specs


def _plane_identity(kind: str, dtype) -> jnp.ndarray:
    from . import kernels
    if kind in ("cnt", "sum", "sumsq", "first"):
        return jnp.zeros((), dtype)
    return kernels._identity_for(dtype, "min" if kind == "min" else "max")


# --------------------------------------------------- grouped-agg build call

def _agg_build_call(n_words: int, specs, val_dtypes, T: int, B: int,
                    C: int, interpret: bool):
    """The table-build ``pallas_call`` for one static (key width, agg
    plane set, table size, block size) signature."""
    tmask = T - 1

    def kernel(*refs):
        w_refs = refs[:n_words]
        live_ref = refs[n_words]
        v_refs = refs[n_words + 1: n_words + 1 + len(val_dtypes)]
        c_refs = refs[n_words + 1 + len(val_dtypes):
                      n_words + 1 + 2 * len(val_dtypes)]
        out = refs[n_words + 1 + 2 * len(val_dtypes):]
        tk_refs = out[:n_words]
        occ_ref, frow_ref = out[n_words], out[n_words + 1]
        plane_refs = out[n_words + 2: n_words + 2 + len(specs)]
        info_ref = out[-1]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            for tr in tk_refs:
                tr[...] = jnp.zeros_like(tr)
            occ_ref[...] = jnp.zeros_like(occ_ref)
            frow_ref[...] = jnp.zeros_like(frow_ref)
            for pr, (_, _, kind, dt) in zip(plane_refs, specs):
                pr[...] = jnp.full_like(pr, _plane_identity(kind, dt))
            info_ref[...] = jnp.zeros_like(info_ref)

        words = [r[0, :] for r in w_refs]
        live = live_ref[0, :]
        vals = [r[0, :] for r in v_refs]
        contribs = [r[0, :] for r in c_refs]
        base = i * B

        def row(r, st):
            tks = list(st[:n_words])
            occ, frow = st[n_words], st[n_words + 1]
            planes = list(st[n_words + 2: n_words + 2 + len(specs)])
            g = st[-1]
            w0 = words[0][r]
            w1 = words[1][r] if n_words == 2 else None
            h = _mix(w0, w1, tmask)

            def cond(pst):
                j, steps = pst
                same = tks[0][j] == w0
                if n_words == 2:
                    same = same & (tks[1][j] == w1)
                return (occ[j] != 0) & (~same) & (steps < T)

            def step(pst):
                j, steps = pst
                return ((j + 1) & tmask, steps + 1)

            j, steps = lax.while_loop(cond, step, (h, jnp.int32(0)))
            ok = live[r] & (steps < T)  # steps == T: table full, drop —
            # the claim count then reads T > out_cap, forcing the caller's
            # overflow re-dispatch, so the dropped rows are never decoded
            claim = ok & (occ[j] == 0)
            tks[0] = jnp.where(claim, tks[0].at[j].set(w0), tks[0])
            if n_words == 2:
                tks[1] = jnp.where(claim, tks[1].at[j].set(w1), tks[1])
            frow = jnp.where(claim, frow.at[j].set(base + r), frow)
            g = g + claim.astype(jnp.int32)
            occ = jnp.where(claim, occ.at[j].set(1), occ)
            cnt_cache = {}
            for pi, (vi, op, kind, dt) in enumerate(specs):
                p = planes[pi]
                contrib = ok & contribs[vi][r]
                v = vals[vi][r]
                if kind == "cnt":
                    cnt_cache[vi] = p[j]  # pre-update count, for `first`
                    planes[pi] = p.at[j].add(contrib.astype(jnp.int32))
                elif kind in ("sum", "sumsq"):
                    x = v.astype(dt)
                    if kind == "sumsq":
                        x = x * x
                    planes[pi] = p.at[j].add(
                        jnp.where(contrib, x, jnp.zeros((), dt)))
                elif kind == "min":
                    planes[pi] = jnp.where(
                        contrib, p.at[j].min(v.astype(dt)), p)
                elif kind == "max":
                    planes[pi] = jnp.where(
                        contrib, p.at[j].max(v.astype(dt)), p)
                else:  # first (any_value): write on the 0→1 count edge
                    planes[pi] = jnp.where(
                        contrib & (cnt_cache[vi] == 0),
                        p.at[j].set(v.astype(dt)), p)
            return tuple(tks) + (occ, frow) + tuple(planes) + (g,)

        st0 = tuple(tr[0, :] for tr in tk_refs) \
            + (occ_ref[0, :], frow_ref[0, :]) \
            + tuple(pr[0, :] for pr in plane_refs) + (info_ref[0, 0],)
        st = lax.fori_loop(0, B, row, st0)
        for tr, v in zip(tk_refs, st[:n_words]):
            tr[0, :] = v
        occ_ref[0, :] = st[n_words]
        frow_ref[0, :] = st[n_words + 1]
        for pr, v in zip(plane_refs,
                         st[n_words + 2: n_words + 2 + len(specs)]):
            pr[0, :] = v
        info_ref[0, 0] = st[-1]

    blk = lambda: pl.BlockSpec((1, B), lambda i: (0, i))      # noqa: E731
    tbl = lambda n: pl.BlockSpec((1, n), lambda i: (0, 0))    # noqa: E731
    in_specs = [blk() for _ in range(n_words)] + [blk()] \
        + [blk() for _ in range(2 * len(val_dtypes))]
    out_specs = [tbl(T) for _ in range(n_words)] \
        + [tbl(T), tbl(T)] + [tbl(T) for _ in specs] + [tbl(8)]
    out_shape = [jax.ShapeDtypeStruct((1, T), jnp.uint64)
                 for _ in range(n_words)] \
        + [jax.ShapeDtypeStruct((1, T), jnp.int32),
           jax.ShapeDtypeStruct((1, T), jnp.int32)] \
        + [jax.ShapeDtypeStruct((1, T), dt) for _, _, _, dt in specs] \
        + [jax.ShapeDtypeStruct((1, 8), jnp.int32)]
    return pl.pallas_call(kernel, grid=(C // B,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret)


def hash_grouped_agg_impl(keys, key_valids, vals, val_valids, row_mask,
                          ops: Tuple[str, ...], out_cap: int,
                          table_cap: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          block: Optional[int] = None):
    """One-pass hash grouped aggregation over padded device columns.

    Pure and traceable (composable inside the fused scan fragments);
    drop-in for :func:`kernels.grouped_agg_block_impl` — same argument
    shapes, same ``([out_cap] keys/valids/vals/valids, group_count)``
    return contract, same overflow discipline (``group_count > out_cap``
    → the caller re-dispatches at a grown bucket). Requires the key set
    to pack into ≤2 u64 words (``hash_pack_words``); wider key sets must
    stay on the sort path.

    Groups come back in table-slot order (deterministic for a given
    input, NOT key-sorted — grouped-aggregate output order is
    unspecified engine-wide, and partial blocks get re-merged anyway).
    """
    from . import kernels
    C = row_mask.shape[0]
    codes = kernels._sort_codes(keys, key_valids, row_mask,
                                (False,) * len(keys), (False,) * len(keys),
                                with_dead=False)
    chunks = kernels._packed_chunks(codes)
    if len(chunks) != 1:
        raise HashKeyWidthError(
            "hash grouped-agg requires ≤128-bit packed keys (caller must "
            "route wide key sets to the sort path)")
    words = chunks[0]
    n_words = len(words)
    T = table_cap if table_cap is not None else table_capacity(out_cap)
    B = block if block is not None else block_rows(C)
    if interpret is None:
        interpret = interpret_default()
    val_dtypes = tuple(v.dtype for v in vals)
    specs = agg_state_specs(ops, val_dtypes)

    def as_block(x, dt=None):
        x = x.astype(dt) if dt is not None else x
        return x.reshape(1, C)

    contribs = [as_block(vv & row_mask) for vv in val_valids]
    call = _agg_build_call(n_words, specs, val_dtypes, T, B, C, interpret)
    outs = call(*[as_block(w) for w in words], as_block(row_mask),
                *[as_block(v) for v in vals], *contribs)
    tk = outs[:n_words]
    occ, frow = outs[n_words][0], outs[n_words + 1][0]
    planes = [o[0] for o in outs[n_words + 2: n_words + 2 + len(specs)]]
    group_count = outs[-1][0, 0]

    # compact occupied slots to the front ([T]-sized 2-operand sort — tiny
    # next to the row pass, and stable so slot order is deterministic)
    order = lax.sort(((1 - occ).astype(jnp.int8),
                      jnp.arange(T, dtype=jnp.int32)), num_keys=1,
                     is_stable=True)[1]
    sel = order[:out_cap] if out_cap <= T else jnp.pad(
        order, (0, out_cap - T))
    j = jnp.arange(out_cap, dtype=jnp.int32)
    live_group = j < jnp.minimum(group_count, out_cap)

    first_row = jnp.clip(jnp.take(frow, sel), 0, C - 1)
    out_keys = tuple(jnp.take(k, first_row) for k in keys)
    out_kvalids = tuple(jnp.take(kv & row_mask, first_row) & live_group
                        for kv in key_valids)

    by_val: dict = {}
    for pi, (vi, op, kind, dt) in enumerate(specs):
        by_val.setdefault(vi, {})[kind] = jnp.take(planes[pi], sel)

    out_vals = []
    out_valids = []
    for vi, (v, op) in enumerate(zip(vals, ops)):
        st = by_val[vi]
        cnt = st["cnt"]
        has = live_group & (cnt > 0)
        if op == "count":
            out_vals.append(cnt.astype(jnp.int64))
            out_valids.append(live_group)
            continue
        if op in ("sum", "mean", "var", "stddev"):
            s1 = st["sum"]
            if op == "sum":
                out_vals.append(s1)
                out_valids.append(has)
                continue
            fdt = jnp.float32 if s1.dtype == jnp.float32 \
                else s1.astype(jnp.float64).dtype
            safe = jnp.maximum(cnt, 1).astype(fdt)
            mean = s1.astype(fdt) / safe
            if op == "mean":
                out_vals.append(mean)
                out_valids.append(has)
                continue
            var = jnp.maximum(st["sumsq"].astype(fdt) / safe - mean * mean,
                              0.0)
            out_vals.append(jnp.sqrt(var) if op == "stddev" else var)
            out_valids.append(has)
            continue
        if op in ("min", "bool_and", "max", "bool_or"):
            r = st["min" if op in ("min", "bool_and") else "max"]
            if v.dtype == jnp.bool_:
                r = r.astype(jnp.bool_)
            out_vals.append(r)
            out_valids.append(has)
            continue
        if op == "any_value":
            r = st["first"]
            if v.dtype == jnp.bool_:
                r = r.astype(jnp.bool_)
            out_vals.append(r)
            out_valids.append(has)
            continue
        raise ValueError(f"unsupported device agg {op}")

    return out_keys, out_kvalids, tuple(out_vals), tuple(out_valids), \
        group_count


_hash_agg_jit_cache: dict = {}


def hash_grouped_agg_kernel(keys, key_valids, vals, val_valids, row_mask,
                            ops: Tuple[str, ...], out_cap: int,
                            table_cap: Optional[int] = None):
    """Jitted entry (interpret/block resolved OUTSIDE the trace so the
    jit-hygiene contract — no host reads inside the program — holds)."""
    C = row_mask.shape[0]
    key = (len(keys), len(vals), ops, out_cap, table_cap,
           interpret_default(), block_rows(C))
    fn = _hash_agg_jit_cache.get(key)
    if fn is None:
        fn = jax.jit(partial(hash_grouped_agg_impl, ops=ops,
                             out_cap=out_cap, table_cap=table_cap,
                             interpret=key[5], block=key[6]))
        _hash_agg_jit_cache[key] = fn
    return fn(keys, key_valids, vals, val_valids, row_mask)


# ----------------------------------------------------------- hash join

def _join_build_call(T: int, B: int, C: int, interpret: bool):
    """Chained-bucket build: one pass over the build side inserting every
    live row into its key's slot chain (head/tail/next), ascending row
    order so probe output matches the sort path's pair order."""
    tmask = T - 1

    def kernel(code_ref, live_ref, tk_ref, occ_ref, head_ref, tail_ref,
               nxt_ref, info_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            tk_ref[...] = jnp.zeros_like(tk_ref)
            occ_ref[...] = jnp.zeros_like(occ_ref)
            head_ref[...] = jnp.full_like(head_ref, -1)
            tail_ref[...] = jnp.full_like(tail_ref, -1)
            nxt_ref[...] = jnp.full_like(nxt_ref, -1)
            info_ref[...] = jnp.zeros_like(info_ref)

        codes = code_ref[0, :]
        live = live_ref[0, :]
        base = i * B

        def row(r, st):
            tk, occ, head, tail, nxt, g = st
            code = codes[r]
            h = _mix(code, None, tmask)

            def cond(pst):
                j, steps = pst
                return (occ[j] != 0) & (tk[j] != code) & (steps < T)

            def step(pst):
                j, steps = pst
                return ((j + 1) & tmask, steps + 1)

            j, steps = lax.while_loop(cond, step, (h, jnp.int32(0)))
            # T ≥ 2 × build capacity: distinct keys ≤ live rows ≤ T/2, so
            # the table can never fill — `steps < T` is purely defensive
            ok = live[r] & (steps < T)
            claim = ok & (occ[j] == 0)
            rowid = jnp.int32(base + r)
            tk = jnp.where(claim, tk.at[j].set(code), tk)
            occ = jnp.where(claim, occ.at[j].set(1), occ)
            head = jnp.where(claim, head.at[j].set(rowid), head)
            # append at the tail: chains stay in ascending build-row order
            prev_tail = tail[j]
            nxt = jnp.where(ok & ~claim,
                            nxt.at[jnp.clip(prev_tail, 0, C - 1)]
                            .set(rowid), nxt)
            tail = jnp.where(ok, tail.at[j].set(rowid), tail)
            g = g + claim.astype(jnp.int32)
            return tk, occ, head, tail, nxt, g

        st0 = (tk_ref[0, :], occ_ref[0, :], head_ref[0, :], tail_ref[0, :],
               nxt_ref[0, :], info_ref[0, 0])
        tk, occ, head, tail, nxt, g = lax.fori_loop(0, B, row, st0)
        tk_ref[0, :] = tk
        occ_ref[0, :] = occ
        head_ref[0, :] = head
        tail_ref[0, :] = tail
        nxt_ref[0, :] = nxt
        info_ref[0, 0] = g

    blk = pl.BlockSpec((1, B), lambda i: (0, i))
    tbl = lambda n: pl.BlockSpec((1, n), lambda i: (0, 0))  # noqa: E731
    return pl.pallas_call(
        kernel, grid=(C // B,), in_specs=[blk, blk],
        out_specs=[tbl(T), tbl(T), tbl(T), tbl(T), tbl(C), tbl(8)],
        out_shape=[jax.ShapeDtypeStruct((1, T), jnp.uint64),
                   jax.ShapeDtypeStruct((1, T), jnp.int32),
                   jax.ShapeDtypeStruct((1, T), jnp.int32),
                   jax.ShapeDtypeStruct((1, T), jnp.int32),
                   jax.ShapeDtypeStruct((1, C), jnp.int32),
                   jax.ShapeDtypeStruct((1, 8), jnp.int32)],
        interpret=interpret)


def _join_probe_call(T: int, B: int, C_l: int, C_r: int, cap: int,
                     interpret: bool):
    """Probe stream: per probe row, walk the matched slot's chain emitting
    (left, right) pairs at a running cursor. Writes past ``cap`` are
    dropped but still COUNTED — the caller compares ``counts.sum()``
    against ``cap`` and re-dispatches at a grown bucket (the r6 overflow
    contract), so a too-small bucket costs one extra dispatch, never a
    wrong answer."""
    tmask = T - 1

    def kernel(code_ref, live_ref, tk_ref, occ_ref, head_ref, nxt_ref,
               counts_ref, owner_ref, ridx_ref, info_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            owner_ref[...] = jnp.zeros_like(owner_ref)
            ridx_ref[...] = jnp.zeros_like(ridx_ref)
            info_ref[...] = jnp.zeros_like(info_ref)

        codes = code_ref[0, :]
        live = live_ref[0, :]
        tk = tk_ref[0, :]
        occ = occ_ref[0, :]
        head = head_ref[0, :]
        nxt = nxt_ref[0, :]

        def row(r, st):
            counts, owner, ridx, cursor = st
            code = codes[r]
            h = _mix(code, None, tmask)

            def cond(pst):
                j, steps = pst
                return (occ[j] != 0) & (tk[j] != code) & (steps < T)

            def step(pst):
                j, steps = pst
                return ((j + 1) & tmask, steps + 1)

            j, steps = lax.while_loop(cond, step, (h, jnp.int32(0)))
            found = live[r] & (steps < T) & (occ[j] != 0) \
                & (tk[j] == code)
            ptr0 = jnp.where(found, head[j], jnp.int32(-1))

            def wcond(wst):
                return wst[0] != -1

            def wbody(wst):
                ptr, c, ow, ri = wst
                slot = cursor + c
                fits = slot < cap
                slot_c = jnp.clip(slot, 0, cap - 1)
                ow = jnp.where(fits, ow.at[slot_c].set(i * B + r), ow)
                ri = jnp.where(fits, ri.at[slot_c].set(ptr), ri)
                return nxt[jnp.clip(ptr, 0, C_r - 1)], c + 1, ow, ri

            _, c, owner, ridx = lax.while_loop(
                wcond, wbody, (ptr0, jnp.int32(0), owner, ridx))
            counts = counts.at[r].set(c)
            return counts, owner, ridx, cursor + c

        st0 = (jnp.zeros(B, jnp.int32), owner_ref[0, :], ridx_ref[0, :],
               info_ref[0, 0])
        counts, owner, ridx, cursor = lax.fori_loop(0, B, row, st0)
        counts_ref[0, :] = counts
        owner_ref[0, :] = owner
        ridx_ref[0, :] = ridx
        info_ref[0, 0] = cursor

    blk = pl.BlockSpec((1, B), lambda i: (0, i))
    tbl = lambda n: pl.BlockSpec((1, n), lambda i: (0, 0))  # noqa: E731
    return pl.pallas_call(
        kernel, grid=(C_l // B,),
        in_specs=[blk, blk, tbl(T), tbl(T), tbl(T), tbl(C_r)],
        out_specs=[blk, tbl(cap), tbl(cap), tbl(8)],
        out_shape=[jax.ShapeDtypeStruct((1, C_l), jnp.int32),
                   jax.ShapeDtypeStruct((1, cap), jnp.int32),
                   jax.ShapeDtypeStruct((1, cap), jnp.int32),
                   jax.ShapeDtypeStruct((1, 8), jnp.int32)],
        interpret=interpret)


def join_table_capacity(c_r: int) -> int:
    """Build-table slots: 2× the (power-of-two) build capacity, so the
    table can never fill (distinct keys ≤ live rows ≤ T/2)."""
    return max(2 * c_r, 128)


def hash_join_impl(l_key, l_valid, l_mask, r_key, r_valid, r_mask,
                   out_capacity: int,
                   interpret: Optional[bool] = None,
                   block: Optional[int] = None,
                   block_build: Optional[int] = None,
                   block_probe: Optional[int] = None):
    """Hash build/probe inner-equi-join index generation, one jit program
    returning the SAME packed int32 ``[3, max(out_capacity, C_l)]``
    matrix as :func:`kernels.join_fused_impl` (row 0/1: left/right row
    per output slot, row 2: per-left-row match counts; slots at or past
    ``counts.sum()`` are garbage; a total above ``out_capacity`` means
    the caller re-dispatches at a grown bucket). Pair order matches the
    sort path: left-major, ascending right row within a left row."""
    C_l, C_r = l_key.shape[0], r_key.shape[0]
    T = join_table_capacity(C_r)
    if interpret is None:
        interpret = interpret_default()
    if block_build is None:
        block_build = block if block is not None else block_rows(C_r)
    if block_probe is None:
        block_probe = block if block is not None else block_rows(C_l)
    b_build, b_probe = block_build, block_probe
    # NULL keys never match: liveness folds validity in, and dead rows
    # skip insert/probe entirely (their key word is never compared)
    r_code = r_key.astype(jnp.uint64).reshape(1, C_r)
    l_code = l_key.astype(jnp.uint64).reshape(1, C_l)
    r_live = (r_valid & r_mask).reshape(1, C_r)
    l_live = (l_valid & l_mask).reshape(1, C_l)
    tk, occ, head, _tail, nxt, _info = _join_build_call(
        T, b_build, C_r, interpret)(r_code, r_live)
    counts, owner, ridx, _cursor = _join_probe_call(
        T, b_probe, C_l, C_r, out_capacity, interpret)(
        l_code, l_live, tk, occ, head, nxt)
    W = max(out_capacity, C_l)
    packed = jnp.zeros((3, W), dtype=jnp.int32)
    packed = packed.at[0, :out_capacity].set(owner[0])
    packed = packed.at[1, :out_capacity].set(ridx[0])
    packed = packed.at[2, :C_l].set(counts[0])
    return packed


_hash_join_jit_cache: dict = {}


def hash_join_kernel(l_key, l_valid, l_mask, r_key, r_valid, r_mask,
                     out_capacity: int):
    """The jitted single-dispatch hash join. Build-side buffers are
    DONATED off-cpu (dead after the in-program table build, so XLA reuses
    their HBM for the table planes) — the same discipline as
    ``kernels.join_fused_kernel``."""
    from . import backend
    # daft-lint: allow(donation-unguarded) -- same as join_fused_kernel:
    # the donated build planes are per-dispatch packed key codes owned by
    # this call, never cache-shared DeviceTable buffers; residency is not
    # a concept for them
    donate = backend.is_accelerator()
    key = (donate, out_capacity, interpret_default(),
           block_rows(l_key.shape[0]), block_rows(r_key.shape[0]))
    fn = _hash_join_jit_cache.get(key)
    if fn is None:
        # interpret/block resolved OUTSIDE the trace and passed in (the
        # knob reads are host effects; the jit-hygiene discipline of
        # hash_grouped_agg_kernel) — the cache key already carries them
        fn = jax.jit(partial(hash_join_impl, out_capacity=out_capacity,
                             interpret=key[2], block_probe=key[3],
                             block_build=key[4]),
                     donate_argnums=(3, 4, 5) if donate else ())
        _hash_join_jit_cache[key] = fn
    return fn(l_key, l_valid, l_mask, r_key, r_valid, r_mask)
