#!/usr/bin/env python3
"""The chain program's two stages, timed alone on the chip at the star cell's
shapes (4 194 304 rows; Q14: 4 outputs at the 65 536 rung, Q19: 6 outputs at
the 262 144 rung).

(1) Three ways to compact a mask's live rows to the front in source order:
a stable sort of the inverted mask, a running count and a binary search a
slot, a three-level block search (PR 41).

(2) Ways to bring the survivors' outputs back as the packed ``[words, w]``
int64 block (PR 42), each over the program's own planes: the outputs'
VALUES and their VALIDITY planes, not bare columns:
  elements     ``jnp.take`` of every value and every validity plane (PR 41)
  planes       one int32 validity plane for all outputs, every plane viewed
               ``[C/128, 128]``: a row gather a plane, the lane picked by a
               compare against an iota and a masked reduce
  chained      the same, one plane after another (an optimization barrier
               between planes): one ``[w, 128]`` block alive at a time
  tile         the planes stacked ``[C/128, P, 128]``: ONE gather of
               ``[P, 128]`` tiles, the same lane pick
  stacked      the planes stacked ``[P, C/128, 128]``: one gather along
               axis 1
  interleaved  the planes interleaved row by row ``[C, L]`` (L = P rounded
               up to 8, 16 or 32), viewed ``[C*L/128, 128]``: ONE gather of
               128-lane rows, L masked reduces pick the row's lanes
Every block is checked bit for bit against numpy's. Prints ms a call."""
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from daft_tpu.device import fragment  # noqa: E402

jax.config.update("jax_enable_x64", True)

B = 128


def by_sort(mask, w):
    C = mask.shape[0]
    perm = lax.sort(((~mask).astype(jnp.int8), jnp.arange(C, dtype=jnp.int32)),
                    num_keys=1, is_stable=True)[1]
    return perm[:w]


def by_search(mask, w):
    C = mask.shape[0]
    running = jnp.cumsum(mask.astype(jnp.int32))
    return jnp.minimum(jnp.searchsorted(
        running, jnp.arange(1, w + 1, dtype=jnp.int32), side="left"),
        C - 1).astype(jnp.int32)


def by_blocks(mask, w):
    C = mask.shape[0]
    n2 = C // (B * B)
    m = mask.astype(jnp.int32).reshape(n2, B, B)
    r0 = jnp.cumsum(m, axis=-1)                  # within an L1 block
    e1 = jnp.cumsum(r0[..., -1], axis=-1)        # L1 ends within an L2 block
    e2 = jnp.cumsum(e1[..., -1])                 # L2 ends, global
    t = jnp.arange(1, w + 1, dtype=jnp.int32)
    b2 = jnp.sum(e2[None, :] < t[:, None], axis=1, dtype=jnp.int32)
    b2 = jnp.minimum(b2, n2 - 1)
    s2 = jnp.take(e2 - e1[..., -1], b2)
    t1 = t - s2
    row1 = jnp.take(e1, b2, axis=0)              # [w, B]
    below = row1 < t1[:, None]
    b1 = jnp.minimum(jnp.sum(below, axis=1, dtype=jnp.int32), B - 1)
    s1 = jnp.max(jnp.where(below, row1, 0), axis=1)
    t0 = t1 - s1
    row0 = jnp.take(r0.reshape(n2 * B, B), b2 * B + b1, axis=0)
    p0 = jnp.minimum(jnp.sum(row0 < t0[:, None], axis=1, dtype=jnp.int32),
                     B - 1)
    return ((b2 * B + b1) * B + p0).astype(jnp.int32)


# ------------------------------------------- the packed block, in numpy

def is_wide(dt):
    return np.dtype(dt).itemsize == 8


def pack_rows_np(vals, valids, live):
    """``fragment._pack_rows``' layout, written again in numpy."""
    head = np.zeros(len(vals[0]), np.int64)
    for i, m in enumerate(valids):
        head |= m.astype(np.int64) << i
    head[0] += np.int64(live) << 32

    def word(v):
        if v.dtype == np.float32:
            return v.view(np.uint32).astype(np.int64)
        return v.astype(np.int64)
    words = [head] + [word(v) for v in vals if is_wide(v.dtype)]
    narrow = [word(v) & 0xFFFFFFFF for v in vals if not is_wide(v.dtype)]
    for lo, hi in zip(narrow[::2], narrow[1::2] + [None]):
        words.append(lo if hi is None else lo | (hi << 32))
    return np.stack(words)


# ------------------------------------------------ the output stage (PR 42)

def to_planes(vals, valids):
    """Every bit the block carries, as int32 planes over the C source rows
    (the validity bits of all outputs in one, then ``fragment._bit_planes``
    of each value), and the way back from the gathered planes to ``[w]``
    values and their validity."""
    bits = jnp.zeros(vals[0].shape, jnp.int32)
    for i, m in enumerate(valids):
        bits = bits | (m.astype(jnp.int32) << i)
    planes, backs = [bits], []
    for v in vals:
        ps, back = fragment._bit_planes(v)
        backs.append((len(ps), back))
        planes += ps

    def from_planes(got, sel):
        out, at = [], 1
        for n, back in backs:
            out.append(back(*got[at:at + n]))
            at += n
        return out, [((got[0] >> i) & 1).astype(jnp.bool_) & sel
                     for i in range(len(valids))]
    return planes, from_planes


def lane_pick(rows, lane):
    """``rows[k, lane[k]]`` of ``[w, 128]`` by a compare and a masked
    reduce."""
    hit = lax.broadcasted_iota(jnp.int32, rows.shape, 1) == lane[:, None]
    return jnp.sum(jnp.where(hit, rows, 0), axis=1, dtype=jnp.int32)


def gather_elements(planes, idx):
    return [jnp.take(p, idx) for p in planes]


def gather_planes(planes, idx):
    C = planes[0].shape[0]
    row, lane = idx >> 7, idx & (B - 1)
    return [lane_pick(jnp.take(p.reshape(C // B, B), row, axis=0), lane)
            for p in planes]


def gather_chained(planes, idx):
    """``planes``, one plane after another: a plane's indices wait for the
    plane before it, so one ``[w, 128]`` block is alive at a time."""
    C = planes[0].shape[0]
    row, lane = idx >> 7, idx & (B - 1)
    got = []
    for p in planes:
        if got:
            row, lane, _ = lax.optimization_barrier((row, lane, got[-1]))
        got.append(lane_pick(jnp.take(p.reshape(C // B, B), row, axis=0),
                             lane))
    return got


def gather_tile(planes, idx):
    C = planes[0].shape[0]
    row, lane = idx >> 7, idx & (B - 1)
    tiles = jnp.stack([p.reshape(C // B, B) for p in planes], axis=1)
    got = jnp.take(tiles, row, axis=0)                  # [w, P, 128]
    hit = lax.broadcasted_iota(jnp.int32, got.shape, 2) \
        == lane[:, None, None]
    picked = jnp.sum(jnp.where(hit, got, 0), axis=2, dtype=jnp.int32)
    return [picked[:, p] for p in range(len(planes))]


def gather_stacked(planes, idx):
    C = planes[0].shape[0]
    row, lane = idx >> 7, idx & (B - 1)
    stack = jnp.stack([p.reshape(C // B, B) for p in planes])
    got = jnp.take(stack, row, axis=1)                  # [P, w, 128]
    hit = lax.broadcasted_iota(jnp.int32, got.shape, 2) \
        == lane[None, :, None]
    picked = jnp.sum(jnp.where(hit, got, 0), axis=2, dtype=jnp.int32)
    return [picked[p] for p in range(len(planes))]


def gather_interleaved(planes, idx):
    C, P = planes[0].shape[0], len(planes)
    L = 8 if P <= 8 else 16 if P <= 16 else 32
    zero = jnp.zeros((C,), jnp.int32)
    lines = jnp.stack(planes + [zero] * (L - P), axis=-1) \
        .reshape(C * L // B, B)
    per = B // L                                        # rows a line
    got = jnp.take(lines, idx // per, axis=0)           # [w, 128]
    first = (idx % per) * L
    return [lane_pick(got, first + j) for j in range(P)]


LAYOUTS = {"elements": gather_elements, "planes": gather_planes,
           "chained": gather_chained,
           "tile": gather_tile, "stacked": gather_stacked,
           "interleaved": gather_interleaved}


def output_stage(gather, elements=False):
    def run(vals, valids, idx, live):
        w = idx.shape[0]
        sel = jnp.arange(w, dtype=jnp.int32) < live
        if elements:    # the program as PR 41 left it
            return fragment._pack_rows(
                [jnp.take(v, idx) for v in vals],
                [jnp.take(m, idx) & sel for m in valids], live)
        planes, from_planes = to_planes(vals, valids)
        return fragment._pack_rows(*from_planes(gather(planes, idx), sel),
                                   live)
    return run


def whole(stage):
    def run(mask, vals, valids, w):
        live = jnp.sum(mask).astype(jnp.int32)
        return stage(vals, valids, by_blocks(mask, w), live)
    return jax.jit(run, static_argnames=("w",))


def timed(fn, *args, reps=10, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    first = time.time() - t0
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, (time.time() - t0) / reps * 1e3, first


Q19 = (np.int64, np.float32, np.float32, np.float32, np.int32, np.int32)
SHAPES = {
    # name: (share kept, rung, the outputs' types)
    "q14": (0.0128, 65536, (np.int64, np.float32, np.float32, np.int32)),
    "q19": (0.0357, 262144, Q19),
    # asked for by name: where the row path's dense compose over the whole
    # table stops paying (``fragment._ROW_GATHER_MIN_SHARE``)
    "w1024": (0.0002, 1024, Q19),
    "w4096": (0.0008, 4096, Q19),
    "w16384": (0.003, 16384, Q19),
}


def main(argv):
    C = 4194304
    rows = 3_750_000
    shapes = [a for a in argv if a in SHAPES] or ["q14", "q19"]
    only = set(argv) - set(SHAPES)
    rng = np.random.default_rng(1)
    print("device", jax.devices()[0].device_kind, flush=True)
    for name in shapes:
        share, w, dtypes = SHAPES[name]
        mask_np = rng.random(C) < share
        mask_np[rows:] = False
        want = np.nonzero(mask_np)[0]
        mask = jnp.asarray(mask_np)
        if not only or "compaction" in only:
            for cname, fn in (("sort", by_sort), ("search", by_search),
                              ("blocks", by_blocks)):
                idx, ms, first = timed(jax.jit(fn, static_argnames=("w",)),
                                       mask, w=w)
                ok = np.array_equal(np.asarray(idx)[:len(want)], want)
                print(f"{name} w {w} compaction {cname}: correct {ok} "
                      f"{ms:.2f} ms (first call {first:.1f} s)", flush=True)
        vals_np, valids_np = [], []
        for dt in dtypes:
            if dt == np.float32:
                v = rng.normal(0, 1e4, C).astype(np.float32)
                v[::1000] = -0.0
            elif dt == np.int64:
                v = rng.integers(-2**62, 2**62, C, dtype=np.int64)
            else:
                v = rng.integers(-2**31, 2**31 - 1, C, dtype=np.int32)
            vals_np.append(v)
            valids_np.append(rng.random(C) < 0.9)
        vals = [jnp.asarray(v) for v in vals_np]
        valids = [jnp.asarray(m) for m in valids_np]
        live = len(want)
        idx_np = np.zeros(w, np.int32)
        idx_np[:live] = want
        want_block = pack_rows_np(
            [v[idx_np] for v in vals_np],
            [m[idx_np] & (np.arange(w) < live) for m in valids_np], live)
        idx = jax.jit(by_blocks, static_argnames=("w",))(mask, w=w)
        live_d = jnp.asarray(live, jnp.int32)
        base = None
        for lname, gather in LAYOUTS.items():
            if only and lname not in only:
                continue
            stage = output_stage(gather, elements=lname == "elements")
            try:
                block, ms, first = timed(jax.jit(stage), vals, valids, idx,
                                         live_d)
                both, ms_whole, first_whole = timed(whole(stage), mask, vals,
                                                    valids, w=w)
            except Exception as e:    # a layout the compiler refuses
                print(f"{name} w {w} outputs {lname}: FAILED "
                      f"{type(e).__name__}: {str(e)[:300]}", flush=True)
                continue
            block, both = np.asarray(block), np.asarray(both)
            ok = np.array_equal(block[:, :live], want_block[:, :live]) \
                and block[0, 0] >> 32 == live
            if base is None:
                base = block
            same = np.array_equal(block, base) and np.array_equal(both, base)
            mem = jax.devices()[0].memory_stats() or {}
            print(f"{name} w {w} P {len(to_planes(vals, valids)[0])} outputs "
                  f"{lname}: correct {ok} same-bits {same} stage {ms:.2f} ms"
                  f" whole program {ms_whole:.2f} ms (first calls "
                  f"{first:.1f} / {first_whole:.1f} s) peak "
                  f"{mem.get('peak_bytes_in_use', 0) / 1e9:.2f} GB",
                  flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
