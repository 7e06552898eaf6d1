"""Sort-based relational kernels as jit-compiled XLA programs.

These replace the reference's hash-table kernels (probe tables
``src/daft-recordbatch/src/probeable/probe_table.rs:19``, grouped aggregate
``src/daft-local-execution/src/sinks/grouped_aggregate.rs``) with the
XLA-friendly sort + segment-reduce formulation (SURVEY.md §7 hard-part #3):

- ``grouped_agg``: packed-key ``lax.sort`` → segment ids via boundary
  cumsum → ``jax.ops.segment_*`` reductions. Static shapes throughout;
  outputs padded to capacity with a live-group count.
- ``argsort``: multi-key, per-key descending + nulls-first, returns a
  permutation (host applies it with Arrow take — device computes *indices*,
  variable-width payloads never leave the host).
- ``join_fused_kernel``: sort/searchsorted/expand inner-equi-join index
  generation as ONE jit program returning ONE packed result matrix.

Roofline discipline (round 6): TPU sort cost grows steeply with operand
count — every log2(C) bitonic pass re-streams every operand plane through
HBM, and the 2k+1-plane lexicographic formulation hit a compile-time cliff
past ~10 operands. All sorts here therefore bit-pack their key planes into
at most two u64 *radix words* whose unsigned order equals the requested
lexicographic order (IEEE-total-order float codes, sign-flipped ints, XOR
for descending, null-rank bits above each value), so any key count sorts
as ≤ 3 operands (word(s) + row index). Key sets wider than 128 bits run as
a stable LSD radix: one ≤3-operand pass per 128-bit chunk.
"""

from __future__ import annotations

from functools import partial
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the u64 radix words require real 64-bit lanes — idempotent here so the
# kernels are safe to import without the column transport layer
jax.config.update("jax_enable_x64", True)

_U64_TOP = np.uint64(1 << 63)


def _key_bits(dtype) -> int:
    """Static value-code width (bits) of one sort key of this dtype."""
    if dtype == jnp.bool_:
        return 1
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).bits
    return jnp.iinfo(dtype).bits


def _value_code(x: jnp.ndarray, valid: jnp.ndarray,
                descending: bool) -> jnp.ndarray:
    """u64 radix code: unsigned-ascending code order == key order.

    Floats use the IEEE total-order transform (flip all bits when
    negative, else set the sign bit) — this matches ``lax.sort``'s
    -NaN < -inf < … < inf < NaN ordering bit-for-bit, so the packed and
    plane formulations agree on every input including NaNs and -0.0.
    Signed ints flip the sign bit; descending XOR-inverts the code
    (negation would wrap INT64_MIN). Invalid rows collapse to 0 — null
    placement is the separate rank bit the caller packs above."""
    w = _key_bits(x.dtype)
    if x.dtype == jnp.bool_ or jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        c = x.astype(jnp.uint64)
    elif jnp.issubdtype(x.dtype, jnp.floating):
        if w == 32:
            b = lax.bitcast_convert_type(x, jnp.uint32)
            c = jnp.where(b >> 31 != 0, ~b,
                          b | jnp.uint32(1 << 31)).astype(jnp.uint64)
        else:
            b = lax.bitcast_convert_type(x, jnp.uint64)
            c = jnp.where(b >> 63 != 0, ~b, b | _U64_TOP)
    elif w == 64:
        c = lax.bitcast_convert_type(x, jnp.uint64) ^ _U64_TOP
    else:
        c = (x.astype(jnp.int64) + (1 << (w - 1))).astype(jnp.uint64)
    if descending:
        c = c ^ np.uint64((1 << w) - 1 if w < 64 else 0xFFFFFFFFFFFFFFFF)
    return jnp.where(valid, c, jnp.uint64(0))


def _null_rank_code(valid: jnp.ndarray, nulls_first: bool) -> jnp.ndarray:
    """1-bit code placed ABOVE the value code: the null-placement plane."""
    rank_of_valid = 1 if nulls_first else 0
    return jnp.where(valid, jnp.uint64(rank_of_valid),
                     jnp.uint64(1 - rank_of_valid))


def _sort_codes(keys, valids, row_mask, descending, nulls_first,
                with_dead: bool = True):
    """The (code, width) list for one multi-key sort: optional dead-row
    bit, then per-key (null_rank, value) codes, most-significant first."""
    codes: list = []
    if with_dead:
        codes.append(((~row_mask).astype(jnp.uint64), 1))
    for v, valid, d, nf in zip(keys, valids, descending, nulls_first):
        live = valid & row_mask
        codes.append((_null_rank_code(live, nf), 1))
        codes.append((_value_code(v, live, d), _key_bits(v.dtype)))
    return codes


def _packed_chunks(codes) -> List[Tuple[jnp.ndarray, ...]]:
    """Pack (code, width) planes — big-endian concatenated — into 128-bit
    chunks of one or two u64 words each.

    Layout rules (all shifts static):

    - The global bit string is cut every 128 bits REGARDLESS of code
      boundaries: a code may straddle two chunks (stable LSD radix
      composes on arbitrary digit boundaries, so per-chunk comparisons
      still realize the full lexicographic order). Pass count is thus
      exactly ``ceil(total_bits / 128)``.
    - Each chunk is LEFT-aligned: the first code's top bit lands on bit
      63 of the chunk's first word, so a leading dead-row bit is always
      ``word0 >> 63``.
    - Within a two-word chunk, lexicographic unsigned (hi, lo) order —
      what ``lax.sort`` with num_keys=2 compares — equals 128-bit
      unsigned order of the concatenation."""
    offs: List[int] = []
    off = 0
    for _, w in codes:
        offs.append(off)  # MSB-first global bit offset of this code
        off += w
    W = off
    C = codes[0][0].shape[0]
    zero = jnp.zeros(C, dtype=jnp.uint64)
    chunks: List[Tuple[jnp.ndarray, ...]] = []
    for cs in range(0, W, 128):
        ce = min(cs + 128, W)
        span = 64 if ce - cs <= 64 else 128  # chunk word span in bits
        words = [zero, zero]
        for (c, w), s in zip(codes, offs):
            a, b = max(s, cs), min(s + w, ce)
            if a >= b:
                continue  # no overlap with this chunk
            ln = b - a
            piece = c >> (s + w - b) if s + w - b else c
            if ln < 64:
                piece = piece & np.uint64((1 << ln) - 1)
            p = span - (a - cs) - ln  # LSB bit position within the chunk
            if span == 64:
                words[0] = words[0] | (piece << p)
            elif p >= 64:
                words[0] = words[0] | (piece << (p - 64))
            elif p + ln <= 64:
                words[1] = words[1] | (piece << p)
            else:  # straddles the word boundary: split (shift truncates)
                words[1] = words[1] | (piece << p)
                words[0] = words[0] | (piece >> (64 - p))
        chunks.append(tuple(words[:1] if span == 64 else words))
    return chunks


def _packed_argsort(codes, C: int,
                    want_words: bool = False):
    """Stable permutation ordering rows ascending by the big-endian
    concatenation of ``codes``. Chunks wider than 128 bits run as an LSD
    radix — least-significant chunk first, each pass ONE stable
    ``lax.sort`` with ≤3 operands (this is the operand-count cliff the
    plane formulation hit). ``want_words`` additionally returns every
    chunk's word planes in final sorted order (for boundary detection)."""
    chunks = _packed_chunks(codes)
    perm = jnp.arange(C, dtype=jnp.int32)
    sorted_last: Tuple[jnp.ndarray, ...] = ()
    for i, words in enumerate(reversed(chunks)):
        if i > 0:
            words = tuple(jnp.take(w, perm) for w in words)
        out = lax.sort(tuple(words) + (perm,), num_keys=len(words),
                       is_stable=True)
        perm = out[-1]
        sorted_last = out[:-1]
    if not want_words:
        return perm
    sorted_words: List[jnp.ndarray] = []
    for ci, words in enumerate(chunks):
        if ci == 0 and len(chunks) >= 1:
            # the most-significant chunk ran last: its sort outputs are
            # already in final order — no gathers in the common 1-chunk case
            sorted_words.extend(sorted_last)
        else:
            sorted_words.extend(jnp.take(w, perm) for w in words)
    return perm, tuple(sorted_words)


def argsort_pack_plan(dtypes) -> List[int]:
    """Words per sort pass for keys of these dtypes (dead bit + per-key
    null-rank bit + value bits) — the traffic model behind the mfu
    ledger. Length of the list = number of radix passes
    (``ceil(total_bits / 128)``)."""
    total = 1 + sum(1 + _key_bits(jnp.dtype(dt)) for dt in dtypes)
    return [2 if min(total - cs, 128) > 64 else 1
            for cs in range(0, total, 128)]


@partial(jax.jit, static_argnames=("descending", "nulls_first"))
def argsort_kernel(keys, valids, row_mask, descending: Tuple[bool, ...],
                   nulls_first: Tuple[bool, ...]):
    """Returns the permutation placing live rows first in key order."""
    C = row_mask.shape[0]
    codes = _sort_codes(keys, valids, row_mask, descending, nulls_first)
    return _packed_argsort(codes, C)


@partial(jax.jit)
def compaction_perm(row_mask):
    """Permutation moving live rows to the front (stable)."""
    C = row_mask.shape[0]
    out = lax.sort(((~row_mask).astype(jnp.int8),
                    jnp.arange(C, dtype=jnp.int32)), num_keys=1, is_stable=True)
    return out[1]


# ---------------------------------------------------------------------------
# grouped aggregation

_SEGMENT_AGGS = ("sum", "count", "min", "max", "mean", "var", "stddev",
                 "any_value", "bool_and", "bool_or")


def _identity_for(dtype, op):
    if op == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def grouped_agg_impl(keys, key_valids, vals, val_valids, row_mask,
                     ops: Tuple[str, ...]):
    """Sort-based grouped aggregation over padded device columns (pure —
    composable inside larger jit programs, e.g. fused scan fragments).

    keys/vals: tuples of [C] arrays. Returns (out_keys, out_key_valids,
    out_vals, out_val_valids, group_count); outputs are [C]-padded, groups in
    ascending key order (so string-code groups decode in sorted order).
    """
    C = row_mask.shape[0]
    # Sort ONLY packed key words + a row index, then gather payloads
    # through the permutation: TPU sort compile time and runtime grow
    # steeply with operand count, while gathers are cheap single-fusion
    # ops. The u64 packing caps the sort at 3 operands. Even so the
    # installed TPU compiler (libtpu 0.0.34) is slow on lax.sort itself,
    # growing with the bucket and ~2x on 64-bit words: this program
    # compiles in under a second at 2048 rows, 24 s at 16384 and ~2 min at
    # 131072 (PR 23, described v5e; ROADMAP A8).
    codes = _sort_codes(keys, key_valids, row_mask,
                        (False,) * len(keys), (False,) * len(keys))
    perm = _packed_argsort(codes, C)
    s_keys = [jnp.take(k, perm) for k in keys]
    s_kvalids = [jnp.take(kv & row_mask, perm) for kv in key_valids]
    s_vals = [jnp.take(v, perm) for v in vals]
    s_vvalids = [jnp.take(vv & row_mask, perm) for vv in val_valids]
    s_live = jnp.take(row_mask, perm)

    # boundary detection over (key value, key validity) among live rows
    idx = jnp.arange(C)
    diff = jnp.zeros(C, dtype=jnp.bool_).at[0].set(True)
    for k, kv in zip(s_keys, s_kvalids):
        prev_k = jnp.concatenate([k[:1], k[:-1]])
        prev_v = jnp.concatenate([kv[:1], kv[:-1]])
        diff = diff | (k != prev_k) | (kv != prev_v)
    prev_live = jnp.concatenate([jnp.zeros(1, jnp.bool_), s_live[:-1]])
    diff = diff | (s_live & ~prev_live)
    flags = diff & s_live
    seg = jnp.cumsum(flags.astype(jnp.int32)) - 1
    seg = jnp.where(s_live, seg, C - 1)  # dead rows -> trailing segment
    group_count = jnp.sum(flags.astype(jnp.int32))

    first_idx = jax.ops.segment_min(
        jnp.where(s_live, idx, C - 1), seg, num_segments=C)
    first_idx = jnp.clip(first_idx, 0, C - 1)

    out_keys = tuple(jnp.take(k, first_idx) for k in s_keys)
    out_kvalids = tuple(jnp.take(kv, first_idx) for kv in s_kvalids)

    out_vals = []
    out_valids = []
    live_group = idx < group_count
    for v, vv, op in zip(s_vals, s_vvalids, ops):
        contrib = s_live & vv
        cnt = jax.ops.segment_sum(contrib.astype(jnp.int64), seg, num_segments=C)
        if op == "count":
            out_vals.append(cnt)
            out_valids.append(live_group)
            continue
        if op in ("sum", "mean", "var", "stddev"):
            acc_dt = v.dtype if jnp.issubdtype(v.dtype, jnp.floating) else jnp.int64
            x = jnp.where(contrib, v, jnp.zeros((), v.dtype)).astype(acc_dt)
            s1 = jax.ops.segment_sum(x, seg, num_segments=C)
            if op == "sum":
                out_vals.append(s1)
                out_valids.append(live_group & (cnt > 0))
                continue
            # widest float the backend supports (f64, or f32 under TPU x32)
            fdt = s1.astype(jnp.float64).dtype if s1.dtype != jnp.float32 \
                else jnp.float32
            safe_cnt = jnp.maximum(cnt, 1).astype(fdt)
            mean = s1.astype(fdt) / safe_cnt
            if op == "mean":
                out_vals.append(mean)
                out_valids.append(live_group & (cnt > 0))
                continue
            x2 = x.astype(fdt) * x.astype(fdt)
            s2 = jax.ops.segment_sum(x2, seg, num_segments=C)
            var = s2 / safe_cnt - mean * mean
            var = jnp.maximum(var, 0.0)
            out_vals.append(jnp.sqrt(var) if op == "stddev" else var)
            out_valids.append(live_group & (cnt > 0))
            continue
        if op in ("min", "max", "bool_and", "bool_or"):
            base = v.astype(jnp.int8) if v.dtype == jnp.bool_ else v
            red_op = "min" if op in ("min", "bool_and") else "max"
            ident = _identity_for(base.dtype, red_op)
            x = jnp.where(contrib, base, ident)
            fn = jax.ops.segment_min if red_op == "min" else jax.ops.segment_max
            r = fn(x, seg, num_segments=C)
            if v.dtype == jnp.bool_:
                r = r.astype(jnp.bool_)
            out_vals.append(r)
            out_valids.append(live_group & (cnt > 0))
            continue
        if op == "any_value":
            fi = jax.ops.segment_min(
                jnp.where(contrib, idx, C - 1), seg, num_segments=C)
            fi = jnp.clip(fi, 0, C - 1)
            out_vals.append(jnp.take(v, fi))
            out_valids.append(live_group & (cnt > 0))
            continue
        raise ValueError(f"unsupported device agg {op}")

    return out_keys, out_kvalids, tuple(out_vals), tuple(out_valids), group_count


grouped_agg_kernel = partial(jax.jit, static_argnames=("ops",))(grouped_agg_impl)


# ---------------------------------------------------------------------------
# block-width grouped aggregation (the fused-fragment fast path)

def grouped_agg_block_impl(keys, key_valids, vals, val_valids, row_mask,
                           ops: Tuple[str, ...], out_cap: int):
    """Grouped aggregation emitting [out_cap]-wide group blocks directly.

    TPU-shaped replacement for the scatter-based ``grouped_agg_impl`` on the
    hot path, built around two facts measured on a v5e: row-width GATHERS
    are the enemy (~22 ms per 1M-row `take`, the dominant cost of the naive
    sort+gather formulation), and one-hot matmuls ride the MXU for ~free.
    So: (1) sort ONLY the key planes plus a row index; (2) invert the
    permutation with a second tiny 2-operand sort, yielding each ORIGINAL
    row's segment id — after which every reduction (one-hot matmul sums /
    counts, block-width scatter min/max) runs over the original, un-gathered
    value planes. The only gathers left are [out_cap]-sized.

    Returns (out_keys, out_kvalids, out_vals, out_valids, group_count) with
    every output [out_cap]; groups beyond out_cap are dropped (the caller
    re-runs at a grown bucket when group_count > out_cap).
    """
    C = row_mask.shape[0]
    with jax.named_scope("sort/argsort"):
        codes = _sort_codes(keys, key_valids, row_mask,
                            (False,) * len(keys), (False,) * len(keys))
        perm, s_words = _packed_argsort(codes, C, want_words=True)
        # dead bit is the MSB of the first sorted word: live rows sort first
        s_live = (s_words[0] >> np.uint64(63)) == 0

    with jax.named_scope("sort/segments"):
        # group boundaries on the sorted packed words — word equality ⟺
        # (null_rank, value) equality for every key, and the words come free
        # from the sort outputs (no payload gathers)
        diff = jnp.zeros(C, dtype=jnp.bool_).at[0].set(True)
        for w in s_words:
            diff = diff | (w != jnp.concatenate([w[:1], w[:-1]]))
        flags = diff & s_live
        segf = jnp.cumsum(flags.astype(jnp.int32)) - 1
        group_count = jnp.sum(flags.astype(jnp.int32))
        seg_sorted = jnp.where(s_live, jnp.minimum(segf, out_cap),
                               out_cap).astype(jnp.int32)
        # invert the permutation with one more (cheap, 2-operand) sort: the
        # segment id of every ORIGINAL row
        seg = lax.sort((perm, seg_sorted), num_keys=1, is_stable=True)[1]

    with jax.named_scope("sort/keys"):
        j = jnp.arange(out_cap, dtype=jnp.int32)
        starts = jnp.searchsorted(seg_sorted, j, side="left")
        starts_c = jnp.clip(starts, 0, C - 1)
        live_group = j < group_count

        # group keys: [out_cap]-sized gathers from the ORIGINAL key planes
        # through perm∘starts (the packed words no longer carry the raw
        # values, but two tiny composed gathers are as cheap as one)
        first_row = jnp.take(perm, starts_c)
        out_keys = tuple(jnp.take(k, first_row) for k in keys)
        out_kvalids = tuple(jnp.take(kv & row_mask, first_row) & live_group
                            for kv in key_valids)

    # One-hot matmul rides the MXU but materializes [C, out_cap]; past a
    # width threshold that escalates to HBM-exhausting sizes (overflow
    # retries grow out_cap ×16), so wide group blocks fall back to the
    # O(C)-memory scatter segment-sum. HIGHEST precision keeps the f32
    # matmul in true f32 (TPU default would drop the operands to bf16).
    with jax.named_scope("sort/onehot"):
        f32_ok = all(v.dtype != jnp.float64 for v in vals)
        acc_dt = jnp.float32 if f32_ok else jnp.float64
        use_matmul = out_cap <= 2048
        oh = jax.nn.one_hot(seg, out_cap, dtype=acc_dt) if use_matmul else None

    def matmul_sum(x):
        if use_matmul:
            return jnp.matmul(x.astype(acc_dt), oh,
                              precision=lax.Precision.HIGHEST)
        # seg is in ORIGINAL row order (inverse-permuted) — not sorted
        return jax.ops.segment_sum(x.astype(acc_dt), seg,
                                   num_segments=out_cap + 1)[:out_cap]

    with jax.named_scope("sort/reduce"):
        idx = jnp.arange(C, dtype=jnp.int32)
        out_vals = []
        out_valids = []
        for v, vv, op in zip(vals, val_valids, ops):
            contrib = row_mask & vv  # ORIGINAL row order — no gathers
            cnt = matmul_sum(contrib)  # counts < 2^24 → exact in f32
            has = live_group & (cnt > 0)
            if op == "count":
                out_vals.append(cnt.astype(jnp.int64))
                out_valids.append(live_group)
                continue
            if op in ("sum", "mean", "var", "stddev"):
                if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == jnp.bool_:
                    # exact integer sums: scatter segment-add at block width
                    x = jnp.where(contrib, v, jnp.zeros((), v.dtype)) \
                        .astype(jnp.int64)
                    s1 = jax.ops.segment_sum(x, seg,
                                             num_segments=out_cap + 1)[:out_cap]
                else:
                    s1 = matmul_sum(jnp.where(contrib, v,
                                              jnp.zeros((), v.dtype)))
                if op == "sum":
                    out_vals.append(s1)
                    out_valids.append(has)
                    continue
                # widest float the backend supports (f64, or f32 under TPU x32)
                # — mirrors grouped_agg_impl so int means don't round at f32
                fdt = s1.astype(jnp.float64).dtype if s1.dtype != jnp.float32 \
                    else jnp.float32
                safe = jnp.maximum(cnt, 1).astype(fdt)
                mean = s1.astype(fdt) / safe
                if op == "mean":
                    out_vals.append(mean)
                    out_valids.append(has)
                    continue
                xf = jnp.where(contrib, v, jnp.zeros((), v.dtype)).astype(fdt)
                if fdt == acc_dt:
                    s2 = matmul_sum(xf * xf)
                else:  # keep the wide accumulator (matmul lanes run in acc_dt)
                    s2 = jax.ops.segment_sum(xf * xf, seg,
                                             num_segments=out_cap + 1)[:out_cap]
                var = jnp.maximum(s2 / safe - mean * mean, 0.0)
                out_vals.append(jnp.sqrt(var) if op == "stddev" else var)
                out_valids.append(has)
                continue
            if op in ("min", "max", "bool_and", "bool_or"):
                base = v.astype(jnp.int8) if v.dtype == jnp.bool_ else v
                red = "min" if op in ("min", "bool_and") else "max"
                ident = _identity_for(base.dtype, red)
                x = jnp.where(contrib, base, ident)
                fn = jax.ops.segment_min if red == "min" else jax.ops.segment_max
                r = fn(x, seg, num_segments=out_cap + 1)[:out_cap]
                if v.dtype == jnp.bool_:
                    r = r.astype(jnp.bool_)
                out_vals.append(r)
                out_valids.append(has)
                continue
            if op == "any_value":
                fi = jax.ops.segment_min(jnp.where(contrib, idx, C - 1), seg,
                                         num_segments=out_cap + 1)[:out_cap]
                out_vals.append(jnp.take(v, jnp.clip(fi, 0, C - 1)))
                out_valids.append(has)
                continue
            raise ValueError(f"unsupported device agg {op}")

    return out_keys, out_kvalids, tuple(out_vals), tuple(out_valids), \
        group_count


# ---------------------------------------------------------------------------
# dense direct-indexed grouped aggregation (dictionary-coded keys)

#: slots at and under which the dense aggregate's additive reductions are
#: masked sums a slot and a plane (``"masked"``), over which they are ONE
#: one-hot matmul over the stacked planes (``"matmul"``). The masked form
#: is the vector unit's: ncols x K selects and adds a row, and ncols x K
#: reductions in the HLO (165 at TPC-H Q1: 11 planes, 15 slots), so it
#: grows with K. The stack is HBM's: every plane written, copied into a
#: sublane-padded ``[ncols, C]`` operand and read back, ~250 B a row
#: whatever K. Both grow with the plane count alike, so K alone decides.
#: Read on a TPU v5e inside Q1's fused program at C = 4 194 304 (PR 47,
#: ``chip_proof/dense_inner.py``; ms a table, masked / matmul): K = 15
#: 0.80 / 1.63, 27 1.26 / 1.64, 32 1.50 / 1.64, 33 1.51 / 1.73, 45 2.25 /
#: 1.78: they cross near 38, and the TPU compile at the bound takes 6.5 s
#: against 2–3. (The stack was chosen on a CPU, where a scatter is a
#: serial loop and a GEMM is multithreaded; at Q1's 15 slots it rode
#: nothing on the chip: 0.79 ms of a 1.48 ms table copied planes.)
DENSE_MASKED_MAX_SLOTS = 32


def dense_slots(dims: Tuple[int, ...]) -> int:
    """K = prod(d + 1): a dense dispatch's static slot count (slot ``d``
    of a key holds its nulls)."""
    K = 1
    for d in dims:
        K *= d + 1
    return K


def dense_inner_loop(dims: Tuple[int, ...]) -> str:
    """The inner loop ``grouped_agg_dense_impl`` runs at ``dims``:
    ``"masked"`` or ``"matmul"`` (:data:`DENSE_MASKED_MAX_SLOTS`). Known
    on the host before the launch, so a dispatch span can say which."""
    return "masked" if dense_slots(dims) <= DENSE_MASKED_MAX_SLOTS \
        else "matmul"


def grouped_agg_dense_impl(keys, key_valids, vals, val_valids, row_mask,
                           ops: Tuple[str, ...], out_cap: int,
                           dims: Tuple[int, ...]):
    """Grouped aggregation by DIRECT slot indexing — no sort, no hash table.

    When every group key rides sorted-dictionary codes (string/binary
    planes encode as dense ints < dictionary size, ``column._np_encode``),
    a row's group id is pure arithmetic over its codes: a mixed-radix
    number over the per-key slot widths ``dims`` (each dictionary size
    rounded up to a power of two so the static-arg space stays bounded;
    slot ``d`` of a key holds its nulls). Aggregation is then per-slot
    sums over ``K = prod(d+1)`` slots (:func:`dense_inner_loop`: masked
    sums with few slots, a one-hot matmul with many; integer sums and
    min / max / ``any_value`` by scatter) — the radix sort +
    inverse-permutation sort of the sort strategy (≥4 streaming passes
    over the packed row planes) disappears entirely.

    Strides are most-significant-first over the keys with nulls at each
    key's top slot, so occupied slots enumerate groups in ascending key
    order with nulls last — the same group order the sort strategy emits.
    Requires ``K <= out_cap`` (the dispatch site sizes the bucket);
    dense output can never overflow, because group_count <= K.

    Returns the [out_cap]-wide block layout of
    :func:`grouped_agg_block_impl`.
    """
    C = row_mask.shape[0]
    K = dense_slots(dims)
    if K > out_cap:
        raise ValueError("dense dispatch requires K <= out_cap")
    # mixed-radix group id per ORIGINAL row (no gathers, no sort)
    with jax.named_scope("dense/pack"):
        gid = jnp.zeros(C, dtype=jnp.int32)
        for k, kv, d in zip(keys, key_valids, dims):
            comp = jnp.where(kv & row_mask,
                             jnp.clip(k.astype(jnp.int32), 0, d), d)
            gid = gid * (d + 1) + comp
        seg = jnp.where(row_mask, gid, out_cap).astype(jnp.int32)

    # Every additive reduction below (slot occupancy, contribution counts,
    # float sums, squared sums) is one row of ``R``, [ncols, K]: plane i
    # summed into slot k, by the inner loop the slot count asks for.
    acc_dt = jnp.float64 if any(
        v.dtype == jnp.float64 for v in vals) else jnp.float32

    def slot_pad(x):
        """[K] slot vector → [out_cap] (slots past K are empty)."""
        return jnp.zeros((out_cap,), x.dtype).at[:K].set(x)

    # pass 1 — collect every DISTINCT additive plane. Integer sums keep the
    # exact int64 scatter, and min/max/any/bool reductions scatter too
    # (no additive form).
    with jax.named_scope("dense/pack"):
        mm_cols = []
        col_ix = {}

        def want(i, tag, x, src):
            # queries reuse planes (q1 sums l_quantity three ways over one
            # validity mask) — identical sources collapse to one row of R
            shared = (tag,) + src
            ix = col_ix.get(shared)
            if ix is None:
                ix = len(mm_cols)
                mm_cols.append(x.astype(acc_dt))
                col_ix[shared] = ix
            col_ix[(i, tag)] = ix

        want(-1, "occ", row_mask, (id(row_mask),))
        for i, (v, vv, op) in enumerate(zip(vals, val_valids, ops)):
            contrib = row_mask & vv
            want(i, "cnt", contrib, (id(vv),))
            if op in ("sum", "mean", "var", "stddev") \
                    and jnp.issubdtype(v.dtype, jnp.floating):
                x = jnp.where(contrib, v, jnp.zeros((), v.dtype))
                want(i, "s1", x, (id(v), id(vv)))
                if op in ("var", "stddev"):
                    xa = x.astype(acc_dt)
                    want(i, "s2", xa * xa, (id(v), id(vv)))
    with jax.named_scope("dense/onehot-matmul"):
        if dense_inner_loop(dims) == "masked":
            # a masked sum a slot and a plane: what makes a plane fuses
            # into what reduces it, so no plane is written out only to be
            # read back. f32 summed in f32 (in a tree: another order than
            # the matmul's), counts exact below 2^24 rows
            zero = jnp.zeros((), acc_dt)
            hits = [seg == k for k in range(K)]   # a masked row hits none
            sums = [jnp.sum(jnp.where(h, x, zero))
                    for x in mm_cols for h in hits]
            # the K x ncols scalars are laid into R by ONE chain of selects
            # over its grid (one fusion on the TPU), not stacked a plane:
            # either way a scalar costs ~0.36 us to fetch there, but the
            # chain leaves the compiler a better plan for the C-wide
            # fusions (PERF.md §6, PR 47: 803 against 868 us a table)
            grid = (len(mm_cols), K)
            at = lax.broadcasted_iota(jnp.int32, grid, 0) * K \
                + lax.broadcasted_iota(jnp.int32, grid, 1)
            R = jnp.zeros_like(at, acc_dt)
            for n, total in enumerate(sums):
                R = jnp.where(at == n, total, R)
        else:
            # a masked row's seg is out_cap >= K: an all-zero one-hot row.
            # The planes are stacked along axis 0 (each lands contiguously)
            # and the row axis contracted directly — the axis=1/transpose
            # formulation pays an extra interleaving copy of the matrix
            oh = jax.nn.one_hot(seg, K, dtype=acc_dt)
            M = jnp.stack(mm_cols, axis=0)
            R = jnp.matmul(M, oh, precision=lax.Precision.HIGHEST)

    with jax.named_scope("dense/reduce"):
        occ = R[col_ix[(-1, "occ")]]
        occupied = slot_pad(occ > 0.0)
        group_count = jnp.sum(occ > 0.0).astype(jnp.int32)
        j = jnp.arange(out_cap, dtype=jnp.int32)
        # compact occupied slots to the front: one stable [out_cap]-sized
        # 2-operand sort (ascending slot order — the group order — survives)
        slot_of = lax.sort((jnp.where(occupied, 0, 1).astype(jnp.int32), j),
                           num_keys=1, is_stable=True)[1]
        live_group = j < group_count

        # each slot's key codes come back by mixed-radix decomposition —
        # nothing is gathered from the row planes
        strides = []
        s = 1
        for d in reversed(dims):
            strides.append(s)
            s *= d + 1
        strides.reverse()
        out_keys = []
        out_kvalids = []
        for k, d, st in zip(keys, dims, strides):
            comp = (slot_of // st) % (d + 1)
            out_keys.append(comp.astype(k.dtype))
            out_kvalids.append(live_group & (comp != d))

        def slot_take(r):
            """[K] slot sums → compacted [out_cap] group order."""
            return jnp.take(slot_pad(r), slot_of)

        def red_scatter(x, fn=jax.ops.segment_sum):
            return jnp.take(fn(x, seg, num_segments=out_cap + 1)[:out_cap],
                            slot_of)

        idx = jnp.arange(C, dtype=jnp.int32)
        out_vals = []
        out_vvalids = []
        for i, (v, vv, op) in enumerate(zip(vals, val_valids, ops)):
            contrib = row_mask & vv
            cntf = slot_take(R[col_ix[(i, "cnt")]])  # counts exact in float
            cnt = cntf.astype(jnp.int64)
            has = live_group & (cnt > 0)
            if op == "count":
                out_vals.append(cnt)
                out_vvalids.append(live_group)
                continue
            if op in ("sum", "mean", "var", "stddev"):
                if (i, "s1") in col_ix:
                    s1 = slot_take(R[col_ix[(i, "s1")]])
                else:  # integer/bool input: exact int64 scatter sum
                    x = jnp.where(contrib, v, jnp.zeros((), v.dtype)) \
                        .astype(jnp.int64)
                    s1 = red_scatter(x)
                if op == "sum":
                    out_vals.append(s1)
                    out_vvalids.append(has)
                    continue
                # widest float the backend supports (mirrors the sort path)
                fdt = s1.astype(jnp.float64).dtype if s1.dtype != jnp.float32 \
                    else jnp.float32
                safe = jnp.maximum(cnt, 1).astype(fdt)
                mean = s1.astype(fdt) / safe
                if op == "mean":
                    out_vals.append(mean)
                    out_vvalids.append(has)
                    continue
                if (i, "s2") in col_ix:
                    s2 = slot_take(R[col_ix[(i, "s2")]]).astype(fdt)
                else:
                    xf = jnp.where(contrib, v,
                                   jnp.zeros((), v.dtype)).astype(fdt)
                    s2 = red_scatter(xf * xf)
                var = jnp.maximum(s2 / safe - mean * mean, 0.0)
                out_vals.append(jnp.sqrt(var) if op == "stddev" else var)
                out_vvalids.append(has)
                continue
            if op in ("min", "max", "bool_and", "bool_or"):
                base = v.astype(jnp.int8) if v.dtype == jnp.bool_ else v
                red = "min" if op in ("min", "bool_and") else "max"
                ident = _identity_for(base.dtype, red)
                x = jnp.where(contrib, base, ident)
                fn = jax.ops.segment_min if red == "min" else jax.ops.segment_max
                r = red_scatter(x, fn)
                if v.dtype == jnp.bool_:
                    r = r.astype(jnp.bool_)
                out_vals.append(r)
                out_vvalids.append(has)
                continue
            if op == "any_value":
                fi = jax.ops.segment_min(jnp.where(contrib, idx, C - 1), seg,
                                         num_segments=out_cap + 1)[:out_cap]
                fi = jnp.take(jnp.clip(fi, 0, C - 1), slot_of)
                out_vals.append(jnp.take(v, fi))
                out_vvalids.append(has)
                continue
            raise ValueError(f"unsupported device agg {op}")

    return tuple(out_keys), tuple(out_kvalids), tuple(out_vals), \
        tuple(out_vvalids), group_count


# ---------------------------------------------------------------------------
# global aggregation

def global_agg_impl(vals, val_valids, row_mask, ops: Tuple[str, ...]):
    outs = []
    for v, vv, op in zip(vals, val_valids, ops):
        contrib = row_mask & vv
        cnt = jnp.sum(contrib.astype(jnp.int64))
        if op == "count":
            outs.append((cnt, jnp.asarray(True)))
            continue
        if op in ("sum", "mean", "var", "stddev"):
            acc_dt = v.dtype if jnp.issubdtype(v.dtype, jnp.floating) else jnp.int64
            x = jnp.where(contrib, v, jnp.zeros((), v.dtype)).astype(acc_dt)
            s1 = jnp.sum(x)
            if op == "sum":
                outs.append((s1, cnt > 0))
                continue
            fdt = jnp.float32 if v.dtype == jnp.float32 else s1.astype(jnp.float64).dtype
            safe = jnp.maximum(cnt, 1).astype(fdt)
            mean = s1.astype(fdt) / safe
            if op == "mean":
                outs.append((mean, cnt > 0))
                continue
            s2 = jnp.sum(x.astype(fdt) * x.astype(fdt))
            var = jnp.maximum(s2 / safe - mean * mean, 0.0)
            outs.append((jnp.sqrt(var) if op == "stddev" else var, cnt > 0))
            continue
        if op in ("min", "max", "bool_and", "bool_or"):
            base = v.astype(jnp.int8) if v.dtype == jnp.bool_ else v
            red = "min" if op in ("min", "bool_and") else "max"
            ident = _identity_for(base.dtype, red)
            x = jnp.where(contrib, base, ident)
            r = jnp.min(x) if red == "min" else jnp.max(x)
            if v.dtype == jnp.bool_:
                r = r.astype(jnp.bool_)
            outs.append((r, cnt > 0))
            continue
        if op == "any_value":
            C = row_mask.shape[0]
            fi = jnp.min(jnp.where(contrib, jnp.arange(C), C - 1))
            outs.append((v[fi], cnt > 0))
            continue
        raise ValueError(f"unsupported device agg {op}")
    return tuple(outs)


global_agg_kernel = partial(jax.jit, static_argnames=("ops",))(global_agg_impl)


# ---------------------------------------------------------------------------
# sort-merge equi-join (index generation)
#
# Pure phase impls (composable inside larger programs — the mesh broadcast
# join runs them inside its own shard_map program) plus ONE fused jitted
# kernel: the three-dispatch formulation paid two host round-trips between
# phases (sort → count → fetch total → expand), which cost more than the
# kernels themselves whenever a round trip is not free.

def join_sort_impl(r_key, r_valid, r_mask):
    """Sort the right side's key column; invalid/dead rows to the end."""
    C = r_key.shape[0]
    live = r_valid & r_mask
    x = jnp.where(live, r_key, jnp.zeros((), r_key.dtype))
    dead = (~live).astype(jnp.int8)
    s = lax.sort((dead, x, jnp.arange(C, dtype=jnp.int32)), num_keys=2,
                 is_stable=True)
    live_count = jnp.sum(live.astype(jnp.int32))
    # dead/padding slots carry value 0 after sort; overwrite with the dtype max
    # so the array stays monotonic for searchsorted
    maxval = jnp.asarray(jnp.inf, x.dtype) \
        if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.asarray(jnp.iinfo(x.dtype).max, x.dtype)
    sorted_keys = jnp.where(jnp.arange(C) < live_count, s[1], maxval)
    return sorted_keys, s[2], live_count


def join_count_impl(l_key, l_valid, l_mask, r_sorted, r_live_count):
    """Per-left-row match counts against the sorted right keys."""
    live = l_valid & l_mask
    starts = jnp.searchsorted(r_sorted, l_key, side="left")
    ends = jnp.searchsorted(r_sorted, l_key, side="right")
    ends = jnp.minimum(ends, r_live_count)
    starts = jnp.minimum(starts, r_live_count)
    counts = jnp.where(live, ends - starts, 0)
    return counts, starts, jnp.sum(counts)


def join_expand_impl(counts, starts, r_perm, out_capacity: int):
    """Prefix-sum expansion: slot j → (left row, right row) index pair."""
    C = counts.shape[0]
    cum = jnp.cumsum(counts)
    total = cum[-1]
    j = jnp.arange(out_capacity, dtype=counts.dtype)
    owner = jnp.searchsorted(cum, j, side="right")
    owner = jnp.clip(owner, 0, C - 1)
    cum0 = cum - counts  # exclusive prefix
    offset = j - jnp.take(cum0, owner)
    r_slot = jnp.take(starts, owner) + offset
    # clip against the RIGHT side's capacity — the two sides' buckets can
    # differ, and clipping to C (the left capacity) would remap legitimate
    # high right slots onto wrong rows
    r_idx = jnp.take(r_perm, jnp.clip(r_slot, 0, r_perm.shape[0] - 1))
    valid = j < total
    return owner.astype(jnp.int32), r_idx.astype(jnp.int32), valid


def join_fused_impl(l_key, l_valid, l_mask, r_key, r_valid, r_mask,
                    out_capacity: int):
    """Build-sort + probe-count + expand as one program, result as ONE
    packed int32 matrix ``[3, max(out_capacity, C_l)]``:

    - row 0: left row index per output slot (``[:out_capacity]``)
    - row 1: right row index per output slot (``[:out_capacity]``)
    - row 2: per-left-row match counts (``[:C_l]``)

    The true match total is ``counts.sum()`` host-side; output slots at or
    past it are garbage, and a total above ``out_capacity`` means the
    caller re-dispatches at a grown static bucket (the grouped-agg
    overflow discipline). One dispatch + one transfer replaces the
    three-dispatch, two-round-trip phase pipeline."""
    C_l = l_key.shape[0]
    r_sorted, r_perm, r_live_count = join_sort_impl(r_key, r_valid, r_mask)
    counts, starts, _total = join_count_impl(l_key, l_valid, l_mask,
                                             r_sorted, r_live_count)
    owner, r_idx, _valid = join_expand_impl(counts, starts, r_perm,
                                            out_capacity)
    W = max(out_capacity, C_l)
    packed = jnp.zeros((3, W), dtype=jnp.int32)
    packed = packed.at[0, :out_capacity].set(owner)
    packed = packed.at[1, :out_capacity].set(r_idx)
    packed = packed.at[2, :C_l].set(counts.astype(jnp.int32))
    return packed


_join_fused_cache: dict = {}


def join_fused_kernel(l_key, l_valid, l_mask, r_key, r_valid, r_mask,
                      out_capacity: int):
    """The jitted single-dispatch join. The build side's buffers are
    DONATED on real chips (they are dead after the in-program sort, so
    XLA reuses their HBM for the sorted planes); CPU backends ignore
    donation and would warn per call, so the donating executable is only
    built off-cpu."""
    from . import backend
    # daft-lint: allow(donation-unguarded) -- the donated build-side
    # planes are per-dispatch packed key codes minted by the caller for
    # exactly this call; they are never DeviceTable buffers, so the
    # HBM-cache resident guard does not apply (only the backend gate does)
    donate = (backend.backend_name() or "cpu") != "cpu"
    fn = _join_fused_cache.get(donate)
    if fn is None:
        fn = jax.jit(join_fused_impl, static_argnames=("out_capacity",),
                     donate_argnums=(3, 4, 5) if donate else ())
        _join_fused_cache[donate] = fn
    return fn(l_key, l_valid, l_mask, r_key, r_valid, r_mask,
              out_capacity=out_capacity)
