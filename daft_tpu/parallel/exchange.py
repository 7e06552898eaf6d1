"""Mesh-collective exchange kernels: repartition as ICI collectives.

The TPU-native replacement for the reference's shuffle service
(``src/daft-shuffles``: map-side hash partitioning + Arrow Flight transport):
device shards hold padded column blocks; a jit+shard_map program hash-buckets
rows locally and exchanges buckets with ``lax.all_to_all`` over the mesh's ICI
links; a fused partial→exchange→final grouped aggregation keeps the whole
map/shuffle/reduce in one XLA program (SURVEY.md §2.6 "TPU mapping").

All programs here are SPMD over a 1-D ``data`` mesh axis and compile for any
device count — the multichip dry-run drives them on a virtual CPU mesh.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..device import kernels


#: (fn code + closure values, mesh, specs) → jitted collective program.
#: The callers below build their mapped fns as per-call closures, so the
#: function OBJECT differs every call while the program it traces to is
#: identical — keying on the code object plus the closure's cell values
#: (the shard count, op tuple, plane counts the closure captured) makes
#: repeated mesh exchanges re-enter jax's trace cache instead of paying
#: a fresh trace + compile per exchange (the round-16 retrace tax:
#: ~70 s eager vs milliseconds compiled was already fixed in r6; this
#: removes the remaining per-call re-trace of the SAME collective).
_program_cache: dict = {}
_program_counters = {"hits": 0, "misses": 0, "uncacheable": 0}


def exchange_cache_counters() -> dict:
    """Collective-program cache counters (the regression test's evidence
    that two same-shape exchanges share one trace)."""
    out = dict(_program_counters)
    out["entries"] = len(_program_cache)
    return out


def _program_key(f, mesh, in_specs, out_specs, check_vma):
    """Hashable identity of the collective program, or None when a
    closure cell holds something unhashable (those fall back to a fresh
    jit, exactly the old behavior)."""
    try:
        cells = tuple(c.cell_contents for c in (f.__closure__ or ()))
        # defaults are the THIRD identity channel besides code + cells:
        # two fns differing only in a default-argument value must not
        # share one compiled program
        defaults = (f.__defaults__ or (),
                    tuple(sorted((f.__kwdefaults__ or {}).items())))
        key = (f.__code__, cells, defaults, mesh, tuple(in_specs),
               tuple(out_specs), check_vma)
        return hash(key), key
    except (TypeError, ValueError):
        return None


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` (JAX 0.9: top-level, ``check_vma``), returned
    JITTED and MEMOIZED on (fn identity, mesh, in/out specs): un-jitted
    shard_map executes eagerly (per-op dispatch over every mesh shard —
    ~70 s for one tiny mesh-exchanged Q1 on the 8-device CPU mesh, vs
    milliseconds compiled), and a fresh ``jax.jit`` wrapper per call could
    never hit jax's trace cache, so every exchange re-traced the same
    collective."""
    keyed = _program_key(f, mesh, in_specs, out_specs, check_vma)
    if keyed is not None:
        hit = _program_cache.get(keyed[1])
        if hit is not None:
            _program_counters["hits"] += 1  # GIL-atomic; approx. on race
            return hit
        _program_counters["misses"] += 1
    else:
        _program_counters["uncacheable"] += 1
    mapped = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=check_vma)
    from ..analysis import retrace_sanitizer
    program = jax.jit(mapped)
    # uncacheable programs (unhashable closure cell) each get a UNIQUE
    # scope key: they legitimately trace once apiece, and sharing one
    # key would spuriously trip the per-signature retrace budget
    scope_key = keyed[1] if keyed is not None \
        else ("uncacheable", id(program))
    jitted = retrace_sanitizer.scoped_callable(
        "exchange.shard_map", scope_key, program)
    if keyed is not None:
        _program_cache[keyed[1]] = jitted
    return jitted


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


def _hash_u32(x: jnp.ndarray) -> jnp.ndarray:
    h = x.astype(jnp.uint32)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def all_to_all_by_hash(keys: jnp.ndarray, payload: Tuple[jnp.ndarray, ...],
                       row_mask: jnp.ndarray, n_shards: int, axis: str):
    """Inside shard_map: bucket local rows by key hash and exchange so shard i
    receives every row with ``hash(key) % n == i``.

    Per-shard block size is static (= local capacity); buckets are padded.
    Returns (keys, payload..., row_mask) blocks of shape [n*cap_per_bucket]
    on each shard.
    """
    pid = (_hash_u32(keys) % jnp.uint32(n_shards)).astype(jnp.int32)
    k2, out, m2 = all_to_all_by_pid(pid, (keys,) + payload, row_mask,
                                    n_shards, axis)
    return out[0], out[1:], m2


def all_to_all_by_pid(pid: jnp.ndarray, payload: Tuple[jnp.ndarray, ...],
                      row_mask: jnp.ndarray, n_shards: int, axis: str):
    """all_to_all routing by a precomputed destination-shard plane. Used when
    the partition assignment must agree with the host tier's hash (join
    co-partitioning: both sides of a hash join must route identically, so
    the pid is computed once with the engine-wide xxh64 chain and the mesh
    merely moves the rows)."""
    C = pid.shape[0]
    pid = jnp.where(row_mask, pid, n_shards)  # dead rows bucket to the end
    # stable sort rows by destination bucket
    order = jnp.argsort(pid, stable=True)
    sorted_pid = jnp.take(pid, order)
    # each bucket gets a fixed C-slot frame: scatter rows to bucket-local
    # slots; dead rows (pid == n_shards) get out-of-range slots → dropped
    in_bucket_pos = jnp.arange(C) - jnp.searchsorted(
        sorted_pid, sorted_pid, side="left")
    slots = jnp.where(sorted_pid < n_shards,
                      sorted_pid * C + in_bucket_pos, n_shards * C)
    live_sorted = jnp.take(row_mask, order)
    frame_mask = jnp.zeros((n_shards * C,), jnp.bool_)
    frame_mask = frame_mask.at[slots].set(live_sorted, mode="drop")
    out_payload = []
    for p in payload:
        fp = jnp.zeros((n_shards * C,), p.dtype)
        fp = fp.at[slots].set(jnp.take(p, order), mode="drop")
        out_payload.append(fp)
    # [n_shards, C] frames → all_to_all over the mesh axis
    m2 = lax.all_to_all(frame_mask.reshape(n_shards, C), axis, 0, 0,
                        tiled=False)
    out2 = []
    for fp in out_payload:
        out2.append(lax.all_to_all(fp.reshape(n_shards, C), axis, 0, 0,
                                   tiled=False).reshape(-1))
    return pid, tuple(out2), m2.reshape(-1)


def sharded_grouped_sum(mesh: Mesh, keys_sharded, vals_sharded,
                        mask_sharded, axis: str = "data"):
    """Fused map→all_to_all→reduce grouped sum over the mesh.

    keys/vals/mask: [n_shards * C] arrays sharded on dim 0. Each device:
    (1) partial grouped-sum of its block, (2) all_to_all partials by key hash,
    (3) final grouped-sum. Output: per-shard padded group blocks.
    """
    n = mesh.shape[axis]

    @partial(shard_map_compat, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
             out_specs=(P(axis), P(axis), P(axis), P(axis)),
             check_vma=False)
    def run(k, v, m):
        k, v, m = k.reshape(-1), v.reshape(-1), m.reshape(-1)
        # (1) local partial aggregation (shrinks data before the exchange)
        (pk,), (pkv,), (ps,), (psv,), cnt = kernels.grouped_agg_kernel(
            (k,), (m,), (v,), (m,), m, ("sum",))
        pmask = jnp.arange(pk.shape[0]) < cnt
        # (2) exchange partials so equal keys land on one shard
        k2, (v2,), m2 = all_to_all_by_hash(pk, (ps,), pmask & pkv, n, axis)
        # (3) final aggregation of received partials
        (fk,), (fkv,), (fs,), (fsv,), fcnt = kernels.grouped_agg_kernel(
            (k2,), (m2,), (v2,), (m2,), m2, ("sum",))
        fmask = jnp.arange(fk.shape[0]) < fcnt
        return fk, fs, fmask, jnp.broadcast_to(fcnt, (fk.shape[0],))

    return run(keys_sharded, vals_sharded, mask_sharded)


def shard_blocks(mesh: Mesh, arr: np.ndarray, axis: str = "data"):
    """Host ndarray → device array sharded along dim 0 of the mesh axis."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.device_put(arr, sharding)


def _combine_hashes(keys, kvalids) -> jnp.ndarray:
    """Multi-key → one u32 hash plane (boost-style hash_combine)."""
    h = jnp.zeros(keys[0].shape, jnp.uint32)
    for k, kv in zip(keys, kvalids):
        x = k
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.uint32)
        elif jnp.issubdtype(x.dtype, jnp.floating):
            x = lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint32)
        elif x.dtype in (jnp.int64, jnp.uint64):
            lo = (x & 0xFFFFFFFF).astype(jnp.uint32)
            hi = ((x >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
            x = lo ^ (hi * jnp.uint32(0x9E3779B9))
        else:
            x = x.astype(jnp.uint32)
        hk = _hash_u32(x ^ kv.astype(jnp.uint32))
        h = h ^ (hk + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))
    return h


# final-merge ops that combine with themselves (x ⊕ x is the correct merge of
# two partials): the partial/final agg split upstream reduces count/mean/var
# to sums before this layer.
MERGEABLE_OPS = ("sum", "min", "max", "any_value", "bool_and", "bool_or")


def sharded_grouped_agg(mesh: Mesh, keys, kvalids, vals, vvalids, mask,
                        ops: Tuple[str, ...], axis: str = "data"):
    """Fused map→all_to_all→reduce grouped aggregation over the mesh, for any
    number of key/value planes. The general engine path behind
    ``DeviceExchangeAgg`` (reference seam: the ShuffleExchange strategy enum,
    ``src/daft-physical-plan/src/ops/shuffle_exchange.rs:41-58`` — here the
    strategy *is* an ICI collective inside one XLA program).

    keys/vals: tuples of [n*C] arrays sharded on dim 0; ops must all be in
    MERGEABLE_OPS. Returns (keys, kvalids, vals, vvalids, group_mask) blocks,
    one [C']-sized group block per shard with disjoint key sets.
    """
    n = mesh.shape[axis]
    nk, nv = len(keys), len(vals)
    assert all(op in MERGEABLE_OPS for op in ops), ops

    spec_in = (P(axis),) * (2 * nk + 2 * nv + 1)
    spec_out = (P(axis),) * (2 * nk + 2 * nv + 1)

    @partial(shard_map_compat, mesh=mesh, in_specs=spec_in,
             out_specs=spec_out,
             check_vma=False)
    def run(*args):
        ks = tuple(a.reshape(-1) for a in args[:nk])
        kvs = tuple(a.reshape(-1) for a in args[nk:2 * nk])
        vs = tuple(a.reshape(-1) for a in args[2 * nk:2 * nk + nv])
        vvs = tuple(a.reshape(-1) for a in args[2 * nk + nv:2 * nk + 2 * nv])
        m = args[-1].reshape(-1)
        # (1) local partial merge (shrinks data before the exchange)
        ok, okv, ov, ovv, cnt = kernels.grouped_agg_impl(ks, kvs, vs, vvs,
                                                         m, ops)
        pmask = jnp.arange(ok[0].shape[0]) < cnt
        # (2) exchange group blocks so equal keys land on one shard
        h = _combine_hashes(ok, okv)
        payload = tuple(ok) + tuple(okv) + tuple(ov) + tuple(ovv)
        _, payload2, m2 = all_to_all_by_hash(h.astype(jnp.int32), payload,
                                             pmask, n, axis)
        ks2 = payload2[:nk]
        kvs2 = payload2[nk:2 * nk]
        vs2 = payload2[2 * nk:2 * nk + nv]
        vvs2 = payload2[2 * nk + nv:]
        # (3) final merge of received partials
        fk, fkv, fv, fvv, fcnt = kernels.grouped_agg_impl(
            ks2, kvs2, vs2, vvs2, m2, ops)
        fmask = jnp.arange(fk[0].shape[0]) < fcnt
        return fk + fkv + fv + fvv + (fmask,)

    flat = run(*(tuple(keys) + tuple(kvalids) + tuple(vals) + tuple(vvalids)
                 + (mask,)))
    fk = flat[:nk]
    fkv = flat[nk:2 * nk]
    fv = flat[2 * nk:2 * nk + nv]
    fvv = flat[2 * nk + nv:2 * nk + 2 * nv]
    return fk, fkv, fv, fvv, flat[-1]


def sharded_broadcast_join(mesh: Mesh, l_key, l_valid, l_mask,
                           r_key, r_valid, r_mask,
                           out_capacity_per_shard: int, axis: str = "data"):
    """Broadcast equi-join over the mesh: the left key plane is sharded on
    the mesh axis; the small right side is REPLICATED to every device (the
    strategy the planner picks when one side is under the broadcast
    threshold — no all_to_all at all, the build side rides one broadcast).
    Each shard sort-merges its local block against the replicated build
    side in one XLA program (``kernels.join_*_impl``).

    Returns per-shard (left_idx, right_idx, valid) gather-index blocks
    stacked to [n_shards * out_capacity_per_shard]; left indices are
    SHARD-LOCAL (caller adds ``shard * C`` to globalize).
    """
    @partial(shard_map_compat, mesh=mesh,
             in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
             out_specs=(P(axis), P(axis), P(axis)), check_vma=False)
    def run(lk, lv, lm, rk, rv, rm):
        lk = lk.reshape(-1)
        lv = lv.reshape(-1)
        lm = lm.reshape(-1)
        rs, rperm, rcnt = kernels.join_sort_impl(rk, rv, rm)
        counts, starts, _ = kernels.join_count_impl(lk, lv, lm, rs, rcnt)
        return kernels.join_expand_impl(counts, starts, rperm,
                                        out_capacity_per_shard)

    return run(l_key, l_valid, l_mask, r_key, r_valid, r_mask)


def sharded_hash_repartition(mesh: Mesh, planes, valids, mask, pid,
                             axis: str = "data"):
    """Hash-repartition row blocks across the mesh with one all_to_all: shard
    i ends up holding every row whose ``pid`` plane says i. The pid is
    computed HOST-side with the engine-wide xxh64 chain
    (``recordbatch.py partition_by_hash``) so mesh- and host-exchanged
    partitions of the same key agree — a hash join may co-partition one side
    on the mesh and the other on the host. planes: tuple of [n*C] column
    arrays. Returns (planes, valids, row_mask) received blocks per shard."""
    n = mesh.shape[axis]
    np_ = len(planes)

    spec_in = (P(axis),) * (2 * np_ + 2)
    spec_out = (P(axis),) * (2 * np_ + 1)

    @partial(shard_map_compat, mesh=mesh, in_specs=spec_in,
             out_specs=spec_out,
             check_vma=False)
    def run(*args):
        ps = tuple(a.reshape(-1) for a in args[:np_])
        vs = tuple(a.reshape(-1) for a in args[np_:2 * np_])
        m = args[-2].reshape(-1)
        p = args[-1].reshape(-1)
        _, payload2, m2 = all_to_all_by_pid(p, ps + vs, m, n, axis)
        return tuple(payload2) + (m2,)

    flat = run(*(tuple(planes) + tuple(valids) + (mask, pid)))
    return flat[:np_], flat[np_:2 * np_], flat[-1]
