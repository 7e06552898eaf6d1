"""Achieved-utilization measurement for the device kernel families.

Rows/s says nothing about how close a kernel runs to the silicon, so this
module reports the two currencies that do (BASELINE's "TPU-efficient"
criterion; the public scaling-book framing):

- **MFU** for the MXU-shaped grouped-agg kernel: its one-hot matmul has
  statically known dims (``[C, out_cap]`` accumulation), so FLOPs are
  exact: ``2 * C * out_cap`` per reduced value plane.
- **Roofline %** (achieved bytes/s vs HBM bandwidth) for the
  memory-bound families: the fused sort-merge join and the packed-key
  multi-key argsort — their arithmetic is negligible; the ceiling is HBM
  traffic.

Timing methodology (round 6, after the r5 postmortem: back-to-back async
dispatches did NOT amortize the host↔device round trip, and the recorded
0.23%-of-roofline "argsort" number was measuring the wire): repetition
now runs INSIDE one jit program — ``lax.fori_loop`` over K kernel
iterations with a loop-carried input perturbation so XLA's while-loop
invariant code motion cannot hoist the kernel out of the loop. One
dispatch + one fence covers K iterations; per-iteration time is silicon
plus 1/K of one round trip.

Byte models are conservative LOWER bounds (≥2 passes per sorted operand
plane; one read per input plane), so reported roofline percentages are
under-, never over-stated.

This module also carries the **byte/flop models** the per-dispatch MFU
ledger (``costmodel.ledger_record``) prices real engine dispatches with —
single-sourced here so the synthetic benchmarks and the production ledger
can never disagree on the model.

Peaks come from ONE table keyed by ``device_kind``
(``costmodel.DEVICE_PEAKS``, with its source). On the CPU, or a chip the
table does not know, there are no peaks and every ``*_pct`` field is
omitted: a share of a chip that is not attached is not a number.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import costmodel, kernels

_peak_flops = costmodel.peak_flops
_hbm_bps = costmodel.hbm_bps


def _with_pcts(row: Dict, **per_second) -> Dict:
    """Add ``mfu_pct`` / ``roofline_pct`` to a measurement row when the
    attached chip has published peaks; leave them out otherwise."""
    peaks = {"mfu_pct": _peak_flops(), "roofline_pct": _hbm_bps()}
    for key, rate in per_second.items():
        pct = costmodel.pct_of_peak(rate, peaks[key], digits=3)
        if pct is not None:
            row[key] = pct
    return row

#: in-jit repetitions per measurement — per-iteration time carries 1/K of
#: one dispatch + round trip
_ITERS = 16


# ------------------------------------------------------------ byte models

def argsort_bytes_model(cap: int, dtypes: Sequence) -> int:
    """Modeled HBM traffic of one packed-key argsort over ``cap`` rows:
    one read of each raw key plane (code construction) plus ≥2 streaming
    passes per radix pass over the packed word(s) + the i32 row index."""
    plan = kernels.argsort_pack_plan(dtypes)
    key_read = cap * sum(np.dtype(d).itemsize for d in dtypes)
    return int(key_read + sum(2 * cap * (8 * words + 4) for words in plan))


def join_bytes_model(c_l: int, c_r: int, out_cap: int) -> int:
    """Modeled HBM traffic of one fused join dispatch: build-side sort
    (≥2 passes over dead+key+index planes), two searchsorted probes of
    the probe keys, one pass over the sorted build keys, and the
    expansion's reads/writes."""
    return int(2 * c_r * (1 + 8 + 4)      # sort: dead i8 + key i64 + iota i32
               + 2 * c_l * 8              # two searchsorted probes
               + c_r * 8                  # sorted-keys pass
               + 2 * c_l * 8              # counts/starts planes
               + out_cap * (4 + 4))       # owner/ridx writes


def grouped_agg_models(cap: int, out_cap: int, n_keys: int,
                       n_vals: int, val_bytes: int = 4):
    """(flops, bytes) of one grouped-agg dispatch. FLOPs: the one-hot
    matmul accumulates ``2 * cap * out_cap`` per reduced plane (values +
    the count plane the kernel always reduces). Bytes: packed key sort
    (2 passes) + the inverse-permutation sort + one read of each value
    plane."""
    flops = 2.0 * cap * out_cap * (n_vals + 1)
    plan = kernels.argsort_pack_plan([jnp.int64] * max(n_keys, 1))
    sort_bytes = sum(2 * cap * (8 * w + 4) for w in plan)
    inv_bytes = 2 * cap * (4 + 4)  # (perm, seg) 2-operand inverse sort
    nbytes = int(sort_bytes + inv_bytes + (n_vals + 1) * cap * val_bytes)
    return flops, nbytes


def dense_agg_models(cap: int, out_cap: int, n_keys: int, n_vals: int,
                     val_bytes: int = 4):
    """(flops, bytes) of one DENSE direct-indexed grouped-agg dispatch,
    as the LEAST either inner loop (``kernels.dense_inner_loop``) moves:
    one read of each key-code plane (the mixed-radix group id is pure
    arithmetic), of the row mask, and of each reduced plane with its
    validity (values + the count plane), and the [out_cap] slot planes.
    No sort — the lighter byte model of the two strategies, which is why
    the dispatch sites prefer it whenever the dictionaries fit.

    What the loops really move (TPU v5e, Q1 at 4 194 304 rows, PR 47):
    ``masked`` (K x planes masked sums, at and under
    ``kernels.DENSE_MASKED_MAX_SLOTS`` slots) reads the inputs once or
    twice and writes a few shared planes — about this model; its cost is
    the vector unit's, planes x K selects and adds a row, which the zero
    flops here leave out. ``matmul`` (over the bound) writes every
    distinct additive plane, copies them into one ``[planes, cap]`` stack
    (sublane-padded to a multiple of 8 rows) and reads that back: ~250 B
    a row at Q1's 11 planes, between three and four times this model. The
    numbers stay the model's: the
    ledger's ``grouped_agg`` bytes calibrate ``DEV_AGG_BPS`` (where
    ``DAFT_TPU_CALIBRATION`` is on), so they are a gate's input."""
    row_bytes = cap * (n_keys * 4 + 1 + (n_vals + 1) * (val_bytes + 1))
    slot_bytes = out_cap * (n_vals + 2) * 8
    return 0.0, int(row_bytes + slot_bytes)


# ------------------------------------------------------- timing harness

def _timed_iters(jitted, args, iters: int = _ITERS) -> float:
    """Seconds per kernel iteration: one warm (compile) dispatch, then one
    timed dispatch whose program runs ``iters`` iterations in-jit."""
    jitted(*args, iters=iters).block_until_ready()
    t0 = time.perf_counter()
    jitted(*args, iters=iters).block_until_ready()
    return max((time.perf_counter() - t0) / iters, 1e-9)


def measure_grouped_agg(n: int = 1 << 20, groups: int = 256,
                        n_vals: int = 2) -> Dict:
    """MFU of the SORT strategy's grouped aggregation
    (``grouped_agg_block_impl``: packed-key sort, then a one-hot matmul
    over the sorted segments) at few groups and several reduced value
    planes. Not TPC-H Q1's program: dictionary-coded keys take the dense
    strategy, whose few slots are summed by masked sums and never touch
    the MXU (``kernels.dense_inner_loop``)."""
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, groups, n).astype(np.int64))
    valid = jnp.ones(n, dtype=bool)
    vals = tuple(jnp.asarray(rng.uniform(0, 100, n).astype(np.float32))
                 for _ in range(n_vals))
    mask = jnp.ones(n, dtype=bool)
    out_cap = max(256, groups)
    ops = ("sum",) * n_vals

    @partial(jax.jit, static_argnames=("iters",))
    def run(k, kv, v, vv, m, iters: int):
        def body(i, carry):
            # loop-carried perturbation (0/1 added to the key plane):
            # defeats while-loop invariant code motion without changing
            # the group structure's shape
            k2 = k + carry.astype(k.dtype)
            _, _, ov, _, g = kernels.grouped_agg_block_impl(
                (k2,), (kv,), v, vv, m, ops, out_cap)
            return (g % 2).astype(jnp.int32)
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    t = _timed_iters(run, (keys, valid, vals, (valid,) * n_vals, mask))
    # At TPC-H-like shapes (many rows, few groups) the kernel is
    # SORT/bandwidth-bound, not FLOP-bound — so the bytes-based roofline
    # is reported alongside MFU (the one-hot matrix is fused by XLA,
    # never materialized).
    flops, bytes_touched = grouped_agg_models(n, out_cap, 1, n_vals)
    return _with_pcts(
        {"kernel": "grouped_agg_matmul", "strategy": "sort", "rows": n,
         "groups": groups,
         "iters": _ITERS, "time_s": round(t, 6), "flops": flops,
         "achieved_tflops": round(flops / t / 1e12, 3),
         "achieved_gbps": round(bytes_touched / t / 1e9, 2)},
        mfu_pct=flops / t, roofline_pct=bytes_touched / t)


def measure_join(n: int = 1 << 20) -> Dict:
    """Roofline % of the FUSED sort-merge join kernel (one dispatch:
    build sort + probe counts + prefix-sum expansion)."""
    rng = np.random.default_rng(1)
    r_key = jnp.asarray(rng.integers(0, n // 2, n).astype(np.int64))
    l_key = jnp.asarray(rng.integers(0, n // 2, n).astype(np.int64))
    ones = jnp.ones(n, dtype=bool)

    @partial(jax.jit, static_argnames=("iters",))
    def run(lk, rk, m, iters: int):
        def body(i, carry):
            packed = kernels.join_fused_impl(
                lk + carry.astype(lk.dtype), m, m, rk, m, m, n)
            return packed[2, 0] % 2
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    t = _timed_iters(run, (l_key, r_key, ones))
    bytes_touched = join_bytes_model(n, n, n)
    return _with_pcts(
        {"kernel": "join_fused", "strategy": "sort", "rows": n,
         "iters": _ITERS,
         "time_s": round(t, 6), "bytes": bytes_touched,
         "achieved_gbps": round(bytes_touched / t / 1e9, 2)},
        roofline_pct=bytes_touched / t)


def measure_argsort(n: int = 1 << 20, n_keys: int = 2) -> Dict:
    """Roofline % of the packed-key multi-key argsort behind ORDER BY /
    window partitioning (two f32 keys + null ranks + the dead bit pack
    into one 67-bit word pair: a single 3-operand sort pass)."""
    rng = np.random.default_rng(2)
    keys = tuple(jnp.asarray(rng.uniform(0, 1e6, n).astype(np.float32))
                 for _ in range(n_keys))
    ones = jnp.ones(n, dtype=bool)
    flags = tuple(False for _ in range(n_keys))

    @partial(jax.jit, static_argnames=("iters",))
    def run(ks, m, iters: int):
        def body(i, carry):
            k0 = ks[0] + carry.astype(ks[0].dtype)
            perm = kernels.argsort_kernel((k0,) + ks[1:], (m,) * n_keys,
                                          m, flags, flags)
            return perm[0] % 2
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    t = _timed_iters(run, (keys, ones))
    bytes_touched = argsort_bytes_model(n, [k.dtype for k in keys])
    return _with_pcts(
        {"kernel": "argsort_packed", "strategy": "sort", "rows": n,
         "n_keys": n_keys,
         "iters": _ITERS, "time_s": round(t, 6), "bytes": bytes_touched,
         "sort_passes": len(kernels.argsort_pack_plan(
         [k.dtype for k in keys])),
         "achieved_gbps": round(bytes_touched / t / 1e9, 2)},
        roofline_pct=bytes_touched / t)


def report(n: int = 1 << 20) -> Dict:
    """All kernel families + the per-dispatch ledger; the bench device
    child embeds this in its detail and the compact summary carries the
    headline numbers. The synthetic sections isolate silicon (in-jit
    repetition); ``ledger`` is what REAL engine dispatches achieved
    end-to-end (includes host↔device link time — a lower bound)."""
    out = {"peak_flops": _peak_flops(), "hbm_bps": _hbm_bps(),
           "method": f"in-jit lax.fori_loop x{_ITERS}, one fence"}
    try:
        out["grouped_agg"] = measure_grouped_agg(n)
        out["join"] = measure_join(n)
        out["argsort"] = measure_argsort(n)
    except Exception as exc:  # a wedged backend must not kill the bench
        out["error"] = str(exc)[:200]
    out["ledger"] = costmodel.ledger_snapshot()
    return out
