"""Round-6 kernel contracts: packed-key argsort (≤3 sort operands, exact
host agreement), the fused single-dispatch join, and the per-dispatch MFU
ledger.

The argsort parity sweep is property-based in the seeded-random style
(hypothesis is not guaranteed in every environment): ~60 random
configurations over mixed dtypes × descending × nulls_first × null
density, each asserting EXACT permutation agreement with the pyarrow
host path (both sides are stable sorts, so ties must agree too).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import daft_tpu
from daft_tpu.analysis import rule_jit
from daft_tpu.device import costmodel, kernels as K
from daft_tpu.recordbatch import RecordBatch


# ---------------------------------------------------------------- argsort

def _random_frame(rng, n, dtypes):
    """pydict of random columns (with nulls) for the requested dtypes."""
    data = {}
    for i, dt in enumerate(dtypes):
        nulls = rng.random(n) < rng.choice([0.0, 0.15, 0.5])
        if dt == "int":
            v = rng.integers(-2**40, 2**40, n).tolist()
        elif dt == "small_int":
            v = rng.integers(-3, 3, n).tolist()  # heavy ties
        elif dt == "float":
            v = np.round(rng.uniform(-1e6, 1e6, n), 3).tolist()
        elif dt == "bool":
            v = (rng.random(n) > 0.5).tolist()
        else:  # string
            v = ["s" + str(rng.integers(0, 8)) for _ in range(n)]
        data[f"c{i}"] = [None if m else x for x, m in zip(v, nulls)]
    return data


@pytest.mark.parametrize("seed", range(12))
def test_argsort_device_matches_host_property(seed, monkeypatch):
    """Exact permutation agreement between the packed-key device argsort
    and the pyarrow host path over random frames/orderings."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 80))
    n_keys = int(rng.integers(1, 4))
    dtypes = [rng.choice(["int", "small_int", "float", "bool", "string"])
              for _ in range(n_keys)]
    data = _random_frame(rng, n, dtypes)
    rb = RecordBatch.from_pydict(data)
    keys = [daft_tpu.col(f"c{i}") for i in range(n_keys)]
    for trial in range(5):
        desc = [bool(rng.integers(0, 2)) for _ in range(n_keys)]
        nf = [bool(rng.integers(0, 2)) for _ in range(n_keys)]
        monkeypatch.delenv("DAFT_TPU_DEVICE_FORCE", raising=False)
        monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
        host = rb.argsort(keys, desc, nf)
        monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
        monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
        dev = rb.argsort(keys, desc, nf)
        assert list(dev) == list(host), (dtypes, desc, nf)


def test_argsort_f32_codes_match_reference():
    """f32 value codes (the TPU backend's float plane — f64 rides f32
    there) order exactly like the float values, including -0.0."""
    vals = np.array([0.0, -0.0, 1.5, -1.5, np.inf, -np.inf, 3e-9],
                    np.float32)
    C = 16
    k = np.zeros(C, np.float32)
    k[:len(vals)] = vals
    mask = np.zeros(C, bool)
    mask[:len(vals)] = True
    ones = np.ones(C, bool)
    for desc in (False, True):
        perm = np.asarray(K.argsort_kernel(
            (jnp.asarray(k),), (jnp.asarray(ones),), jnp.asarray(mask),
            (desc,), (False,)))[:len(vals)]
        got = [vals[i] for i in perm]
        # IEEE total order (what lax.sort uses too): -0.0 before 0.0
        ref = sorted(list(vals),
                     key=lambda v: (v, not np.signbit(v)), reverse=desc)
        assert [str(x) for x in got] == [str(x) for x in ref], (desc, got)


# the jaxpr walk + contract numbers are single-sourced in the jit-hygiene
# lint rule (daft_tpu/analysis/rule_jit.py) — tests and
# `python -m daft_tpu.analysis` prove the SAME contracts


@pytest.mark.parametrize("n_keys,dtype", rule_jit.ARGSORT_CASES)
def test_argsort_compiles_with_at_most_3_sort_operands(n_keys, dtype):
    """The operand-count cliff contract: ≤3 operands per lax.sort for ANY
    key count (the 2k+1-plane formulation hit >5-minute TPU compiles)."""
    jaxpr = rule_jit.argsort_jaxpr(n_keys, dtype)
    assert rule_jit.max_sort_operands(jaxpr.jaxpr) \
        <= rule_jit.ARGSORT_MAX_SORT_OPERANDS


def test_grouped_agg_sorts_stay_under_operand_cliff():
    """The grouped-agg kernels ride the same packed sort: ≤3 operands
    regardless of key count."""
    jaxpr = rule_jit.grouped_agg_jaxpr(n_keys=5)
    assert rule_jit.max_sort_operands(jaxpr.jaxpr) \
        <= rule_jit.ARGSORT_MAX_SORT_OPERANDS


def test_fused_join_jaxpr_has_no_host_callbacks():
    """The single-dispatch contract, statically: the fused join program
    contains zero host-callback primitives (a host round-trip inside the
    fused program would silently reintroduce the per-phase transfers)."""
    jx = rule_jit.join_fused_jaxpr()
    for prim in rule_jit.FORBIDDEN_IN_FUSED_JOIN:
        assert rule_jit.count_primitive(jx.jaxpr, prim) == 0


def test_lint_dispatch_contract_checker_is_clean():
    """The lint rule's own contract re-verification (what CI runs via
    `python -m daft_tpu.analysis`) agrees with the tests above."""
    assert rule_jit.check_dispatch_contracts() == []


def test_argsort_radix_passes_scale_with_key_bits():
    assert K.argsort_pack_plan([np.float32]) == [1]       # 34 bits
    assert K.argsort_pack_plan([np.float32] * 2) == [2]   # 67 bits
    assert K.argsort_pack_plan([np.int64]) == [2]         # 66 bits
    # 3 x 65-bit keys = 196 bits → two passes
    assert len(K.argsort_pack_plan([np.int64] * 3)) == 2


# ------------------------------------------------------------- fused join

def _join_keys(seed=3):
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, 50, 400)
    rk = rng.integers(0, 50, 150)
    lv = rng.random(400) > 0.1
    rv = rng.random(150) > 0.1
    return lk, rk, lv, rv


def test_fused_join_is_one_dispatch_with_host_identical_indices(
        monkeypatch):
    """The fused kernel must be dispatched EXACTLY once per build/probe
    pair (no per-phase dispatches, no host round-trips between phases),
    and its indices must match the host merge exactly."""
    from daft_tpu import joins
    lk, rk, lv, rv = _join_keys()
    calls = {"n": 0}
    real = K.join_fused_kernel

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(K, "join_fused_kernel", counting)
    out = joins._device_match_indices(lk, rk, lv, rv)
    assert out is not None
    assert calls["n"] == 1, f"expected ONE dispatch, saw {calls['n']}"
    dli, dri, dcnt = out
    monkeypatch.setenv("DAFT_TPU_DEVICE_JOIN", "0")
    hli, hri, hcnt = joins.match_indices(lk, rk, lv, rv)
    assert sorted(zip(dli.tolist(), dri.tolist())) == \
        sorted(zip(hli.tolist(), hri.tolist()))
    assert np.array_equal(dcnt, hcnt)


def test_fused_join_overflow_redispatches_once(monkeypatch):
    """A many-to-many blowup past the FK-shaped output estimate re-runs
    at the fitting bucket — two dispatches, still correct."""
    from daft_tpu import joins
    n = 1200  # 1200*1200 pairs ≫ bucket_capacity(1200)=2048 slots
    lk = np.zeros(n, np.int64)
    rk = np.zeros(n, np.int64)
    ones = np.ones(n, bool)
    calls = {"n": 0}
    real = K.join_fused_kernel

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(K, "join_fused_kernel", counting)
    dli, dri, dcnt = joins._device_match_indices(lk, rk, ones, ones)
    assert calls["n"] == 2
    assert len(dli) == n * n
    assert dcnt.tolist() == [n] * n


# ------------------------------------------------------------- MFU ledger

def test_ledger_records_and_derives(monkeypatch):
    costmodel.ledger_reset()
    costmodel.ledger_record("argsort", rows=100, nbytes=1e9, seconds=0.5)
    costmodel.ledger_record("argsort", rows=50, nbytes=1e9, seconds=0.5)
    snap = costmodel.ledger_snapshot()
    d = snap["argsort"]
    assert d["dispatches"] == 2 and d["rows"] == 150
    assert d["achieved_gbps"] == 2.0
    # the CPU has no published peaks: no share of a chip that isn't there
    assert costmodel.device_peaks() is None
    assert "roofline_pct" not in d and "mfu_pct" not in d
    # a chip in the DEVICE_PEAKS table gets its shares
    monkeypatch.setattr(costmodel, "device_peaks",
                        lambda: costmodel.DEVICE_PEAKS["TPU v5 lite"])
    d = costmodel.ledger_snapshot()["argsort"]
    assert d["roofline_pct"] == pytest.approx(100.0 * 2e9 / 819e9, rel=1e-4)
    costmodel.ledger_reset()
    assert costmodel.ledger_snapshot() == {}


def test_ledger_delta_isolates_a_query(monkeypatch):
    monkeypatch.setattr(costmodel, "device_peaks",
                        lambda: costmodel.DEVICE_PEAKS["TPU v5 lite"])
    costmodel.ledger_reset()
    costmodel.ledger_record("join", rows=10, nbytes=100.0, seconds=0.1)
    before = costmodel.ledger_snapshot(raw=True)
    costmodel.ledger_record("join", rows=7, nbytes=50.0, seconds=0.1)
    costmodel.ledger_record("grouped_agg", rows=3, nbytes=10.0,
                            flops=1e12, seconds=0.2)
    delta = costmodel.ledger_delta(before,
                                   costmodel.ledger_snapshot(raw=True))
    assert delta["join"]["rows"] == 7
    assert delta["grouped_agg"]["mfu_pct"] > 0
    costmodel.ledger_reset()


def test_real_dispatches_feed_the_ledger(monkeypatch):
    """try_argsort and the device join both account their dispatches."""
    costmodel.ledger_reset()
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    rb = RecordBatch.from_pydict({"a": [3, 1, 2, None, 5]})
    rb.argsort([daft_tpu.col("a")], [False], [False])
    from daft_tpu import joins
    lk, rk, lv, rv = _join_keys()
    joins._device_match_indices(lk, rk, lv, rv)
    snap = costmodel.ledger_snapshot()
    assert snap["argsort"]["dispatches"] == 1
    assert snap["argsort"]["rows"] == 5
    assert snap["join"]["dispatches"] == 1
    assert snap["join"]["bytes"] > 0 and snap["join"]["seconds"] > 0
    costmodel.ledger_reset()


def test_query_stats_carry_ledger_delta(monkeypatch):
    """observability: a query's RuntimeStatsContext reports the device
    dispatches IT caused, and render() prints them."""
    from daft_tpu import observability as obs
    costmodel.ledger_reset()
    ctx = obs.new_query_stats()
    costmodel.ledger_record("argsort", rows=9, nbytes=1e6, seconds=0.01)
    ctx.finish()
    assert ctx.device_kernels["argsort"]["rows"] == 9
    assert "argsort" in ctx.render()
    # a later query must not re-report the same work
    ctx2 = obs.new_query_stats()
    ctx2.finish()
    assert ctx2.device_kernels == {}
    costmodel.ledger_reset()


def test_mfu_report_embeds_ledger():
    from daft_tpu.device import mfu
    costmodel.ledger_reset()
    costmodel.ledger_record("join", rows=4, nbytes=1.0, seconds=0.1)
    r = mfu.report(n=1 << 10)
    assert "error" not in r, r
    assert r["ledger"]["join"]["dispatches"] == 1
    assert r["argsort"]["sort_passes"] == 1
    costmodel.ledger_reset()


def test_dispatch_log_appends_are_serialized(tmp_path, monkeypatch):
    """Concurrent decision logging must never interleave JSONL lines."""
    import json
    import threading
    log = tmp_path / "d.jsonl"
    monkeypatch.setenv("DAFT_TPU_DISPATCH_LOG", str(log))
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "10")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "50")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "50")
    costmodel.reset_for_tests()

    def spam():
        for _ in range(200):
            costmodel.row_output_op_wins(1e6, 1e6, host_bytes=2e6)

    threads = [threading.Thread(target=spam) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lines = log.read_text().splitlines()
    assert len(lines) == 1600
    for ln in lines:
        json.loads(ln)  # every line parses — no interleaving
    costmodel.reset_for_tests()


# ------------------------------------------------- fused-agg group gate

def test_fused_gate_falls_back_to_row_estimate():
    from daft_tpu.execution import pipeline as pl

    class Node:
        group_by = ("k",)
        aggs = ("s",)

    n = Node()
    n.group_ndv = None
    n.group_rows_est = None
    assert pl._fused_groups_admissible(n)          # no evidence: default
    n.group_rows_est = pl._FUSE_MAX_GROUPS + 1
    assert not pl._fused_groups_admissible(n)      # row estimate declines
    n.group_ndv = 1000.0                           # footer evidence wins
    assert pl._fused_groups_admissible(n)


def test_fused_gate_respects_memory_budget(monkeypatch):
    from daft_tpu.execution import pipeline as pl

    class Node:
        group_by = ("k",)
        aggs = ("a", "b")

    n = Node()
    n.group_ndv = 10_000_000.0  # under the group cap …
    n.group_rows_est = None
    monkeypatch.setenv("DAFT_TPU_MEMORY_LIMIT", "64MB")
    assert not pl._fused_groups_admissible(n)  # … but not under 64MB
    monkeypatch.setenv("DAFT_TPU_MEMORY_LIMIT", "64GB")
    assert pl._fused_groups_admissible(n)


# ------------------------------------- grouped-agg strategies: dense, sort
#
# The device has two grouped-aggregate formulations with one argument and
# return contract: ``dense`` (direct slot indexing over dictionary codes —
# all of the device time behind the benchmark's ``agg_hbm_pct``) and
# ``sort`` (packed-key radix sort + segment reduce). Parity is proven
# three ways — kernel-vs-kernel over seeded random configurations,
# kernel-vs-numpy (an independent host reference), and engine-vs-host
# with the kernel ledger naming the strategy that dispatched.

from daft_tpu.device import column as dcol, fragment  # noqa: E402


def _agg_args(rng, C, nk, nv, null_keys=True):
    """Random [C]-padded kernel inputs with a live-row prefix mask."""
    n = int(rng.integers(3, C))
    mask = np.zeros(C, bool)
    mask[:n] = True
    keys, kvalids = [], []
    for _ in range(nk):
        dt = rng.choice(["int64", "int32", "float32", "bool"])
        if dt == "bool":
            k = rng.integers(0, 2, C).astype(bool)
        elif dt == "float32":
            k = rng.integers(-4, 5, C).astype(np.float32)
        else:
            k = rng.integers(-6, 7, C).astype(dt)
        kv = np.ones(C, bool) if not null_keys \
            else rng.random(C) > rng.choice([0.0, 0.3])
        keys.append(jnp.asarray(k))
        kvalids.append(jnp.asarray(kv))
    vals, vvalids, ops = [], [], []
    for _ in range(nv):
        vals.append(jnp.asarray(
            np.round(rng.uniform(-50, 50, C), 2).astype(np.float32)))
        vvalids.append(jnp.asarray(rng.random(C) > 0.2))
        ops.append(rng.choice(["sum", "count", "min", "max", "mean"]))
    return (tuple(keys), tuple(kvalids), tuple(vals), tuple(vvalids),
            jnp.asarray(mask), tuple(ops))


def _dense_args(rng, C, nk, nv):
    """:func:`_agg_args` with every key replaced by a dictionary-coded
    plane (int32 codes under a random dictionary size, NULLs kept) and
    the pow2 slot width ``dims`` the dispatch site would derive."""
    _, kvalids, vals, vvalids, mask, ops = _agg_args(rng, C, nk, nv)
    keys, dims = [], []
    for _ in range(nk):
        d = int(rng.integers(1, 7))
        keys.append(jnp.asarray(rng.integers(0, d, C).astype(np.int32)))
        dims.append(max(1 << (d - 1).bit_length(), 1))
    return tuple(keys), kvalids, vals, vvalids, mask, ops, tuple(dims)


def _grouped(strategy, keys, kvalids, vals, vvalids, mask, ops, out_cap,
             dims):
    """One kernel call at the strategy ``run_packed`` would take."""
    if strategy == "dense":
        return K.grouped_agg_dense_impl(keys, kvalids, vals, vvalids, mask,
                                        ops, out_cap, dims)
    return K.grouped_agg_block_impl(keys, kvalids, vals, vvalids, mask,
                                    ops, out_cap)


def _agg_rows(out):
    """[(group key tuple, value tuple)] for the live groups of a kernel
    result, in the order the kernel emitted them."""
    ok, okv, ov, ovv, g = out
    g = int(np.asarray(jax.device_get(g)))
    ok = [np.asarray(k) for k in ok]
    okv = [np.asarray(k) for k in okv]
    ov = [np.asarray(v) for v in ov]
    ovv = [np.asarray(v) for v in ovv]
    return [(tuple(k[i].item() if kv[i] else None
                   for k, kv in zip(ok, okv)),
             tuple(v[i].item() if vv[i] else None
                   for v, vv in zip(ov, ovv)))
            for i in range(g)]


def _agg_map(out, nk, nv):
    """{group key tuple: value tuple} for the live groups of a kernel
    result — order-insensitive (engine-wide, grouped output order is
    unspecified)."""
    return dict(_agg_rows(out))


def _maps_close(a, b):
    assert set(a) == set(b), (sorted(a, key=repr), sorted(b, key=repr))
    for k in a:
        for x, y in zip(a[k], b[k]):
            if x is None or y is None:
                assert x == y, (k, a[k], b[k])
            else:
                assert x == pytest.approx(y, rel=1e-4, abs=1e-4), \
                    (k, a[k], b[k])


@pytest.mark.parametrize("seed", range(10))
def test_dense_agg_matches_sort_kernel_property(seed):
    """Seeded-property parity: direct slot indexing and the
    sort+segment-reduce formulation agree on every group and every
    aggregate over dictionary sizes × null densities × op mixes."""
    rng = np.random.default_rng(seed)
    C = int(rng.choice([64, 128, 256]))
    nk = int(rng.integers(1, 3))
    nv = int(rng.integers(1, 3))
    *args, dims = _dense_args(rng, C, nk, nv)
    dense = _grouped("dense", *args, out_cap=C, dims=dims)
    sorted_ = _grouped("sort", *args, out_cap=C, dims=dims)
    _maps_close(_agg_map(dense, nk, nv), _agg_map(sorted_, nk, nv))


@pytest.mark.parametrize("nk", [1, 2])
def test_dense_emits_groups_in_the_sort_strategys_order(nk):
    """What ``grouped_agg_dense_impl`` promises: occupied slots enumerate
    groups in ascending key order with NULLs last — the order the sort
    strategy emits, so a strategy swap is invisible above the dispatch
    site."""
    rng = np.random.default_rng(40 + nk)
    *args, dims = _dense_args(rng, 128, nk, 1)
    args[1] = tuple(jnp.asarray(rng.random(128) > 0.25) for _ in range(nk))
    dense = [k for k, _ in _agg_rows(_grouped("dense", *args, out_cap=128,
                                              dims=dims))]
    sorted_ = [k for k, _ in _agg_rows(_grouped("sort", *args, out_cap=128,
                                                dims=dims))]
    assert dense == sorted_
    assert dense == sorted(
        dense, key=lambda t: tuple((x is None, x or 0) for x in t))
    assert any(None in k for k in dense), "the case must hold a NULL key"


@pytest.mark.parametrize("strategy", ["dense", "sort"])
def test_grouped_agg_matches_numpy_reference(strategy):
    """Independent host reference: sums over known data with NULL keys
    and NULL values, computed with numpy, no engine code."""
    C = 64
    k = np.array([1, 2, 1, 3, 2, 1, 0, 3] + [0] * (C - 8), np.int32)
    kv = np.array([1, 1, 1, 1, 1, 0, 1, 1] + [1] * (C - 8), bool)
    v = np.arange(C, dtype=np.float32)
    vv = np.array([1, 1, 0, 1, 1, 1, 1, 1] + [1] * (C - 8), bool)
    mask = np.zeros(C, bool)
    mask[:8] = True
    out = _grouped(
        strategy, (jnp.asarray(k),), (jnp.asarray(kv),), (jnp.asarray(v),),
        (jnp.asarray(vv),), jnp.asarray(mask), ("sum",), C, (4,))
    got = _agg_map(out, 1, 1)
    ref = {}
    for i in range(8):
        key = int(k[i]) if kv[i] else None
        ref.setdefault(key, []).append(float(v[i]) if vv[i] else None)
    want = {(key,): (sum(x for x in xs if x is not None)
                     if any(x is not None for x in xs) else None,)
            for key, xs in ref.items()}
    _maps_close(got, want)


@pytest.mark.parametrize("strategy", ["dense", "sort"])
def test_grouped_agg_all_duplicate_and_all_unique_keys(strategy):
    """Adversarial cardinalities: one group total, and one group per
    row (every dense slot of the dictionary occupied)."""
    C = 128
    ones = jnp.ones(C, bool)
    dup = _grouped(
        strategy, (jnp.full(C, 7, jnp.int32),), (ones,),
        (jnp.ones(C, jnp.float32),), (ones,), ones, ("sum",), 256, (8,))
    assert int(np.asarray(dup[-1])) == 1
    assert np.asarray(dup[2][0])[0] == C
    uniq = _grouped(
        strategy, (jnp.arange(C, dtype=jnp.int32),), (ones,),
        (jnp.ones(C, jnp.float32),), (ones,), ones, ("count",), 256, (C,))
    assert int(np.asarray(uniq[-1])) == C
    m = _agg_map(uniq, 1, 1)
    assert len(m) == C and all(v == (1,) for v in m.values())


def test_dense_keeps_int_sums_exact_and_f64_sums_in_f64():
    """The one-hot matmul accumulates floats at the widest value dtype
    and leaves integer sums to an exact int64 scatter: neither may fall
    to f32 (2**24 + 1 does not survive it)."""
    C = 64
    ones = jnp.ones(C, bool)
    big = (1 << 24) + 1
    keys = (jnp.asarray((np.arange(C) % 2).astype(np.int32)),)
    vals = (jnp.full(C, big, jnp.int64), jnp.full(C, float(big), jnp.float64))
    args = (keys, (ones,), vals, (ones, ones), ones, ("sum", "sum"))
    dense = _agg_map(_grouped("dense", *args, out_cap=C, dims=(2,)), 1, 2)
    assert dense == {(0,): (32 * big, 32.0 * big),
                     (1,): (32 * big, 32.0 * big)}
    assert dense == _agg_map(_grouped("sort", *args, out_cap=C, dims=(2,)),
                             1, 2)


def test_dense_refuses_a_bucket_smaller_than_its_slots():
    """``K = prod(d + 1)`` slots must fit ``out_cap``: the kernel raises
    at trace time instead of dropping groups (``dense_plan`` sizes the
    bucket, so no dispatch site reaches this)."""
    C = 32
    ones = jnp.ones(C, bool)
    k = jnp.zeros(C, jnp.int32)
    with pytest.raises(ValueError, match="K <= out_cap"):
        K.grouped_agg_dense_impl((k, k), (ones, ones),
                                 (jnp.ones(C, jnp.float32),), (ones,), ones,
                                 ("sum",), 16, (4, 4))


# The dense strategy's two inner loops (``K.dense_inner_loop``: masked sums
# a slot and a plane at and under ``K.DENSE_MASKED_MAX_SLOTS`` slots, the
# stacked one-hot matmul over it) against numpy in float64. A case's data
# is reduced once at slot widths that keep K under the bound and once at
# wider ones that put it over: the codes are the same, so the groups, their
# order and every aggregate must be too.

def _over_the_bound(dims):
    """``dims`` with the first key's width grown until K passes the
    bound (the least such width: the one-hot stays small on the CPU)."""
    return (max(dims[0], _BOUND // K.dense_slots(dims[1:])),) + dims[1:]


def _ref_dense(keys, kvalids, vals, vvalids, mask, ops):
    """[(key tuple, value tuple)] in the kernel's group order (ascending
    keys, most significant first, NULLs last), float64 / exact ints."""
    keys, kvalids, vals, vvalids = (
        [np.asarray(x) for x in xs] for xs in (keys, kvalids, vals, vvalids))
    mask = np.asarray(mask)
    rows = {}
    for r in np.flatnonzero(mask):
        key = tuple(int(k[r]) if kv[r] else None
                    for k, kv in zip(keys, kvalids))
        rows.setdefault(key, []).append(r)
    out = []
    for key in sorted(rows, key=lambda t: tuple(
            (x is None, x or 0) for x in t)):
        got = []
        for v, vv, op in zip(vals, vvalids, ops):
            x = v[[r for r in rows[key] if vv[r]]]
            if op == "count":
                got.append(len(x))
            elif len(x) == 0:
                got.append(None)
            elif op == "sum":
                got.append(int(x.astype(np.int64).sum()) if x.dtype.kind
                           in "bi" else float(x.astype(np.float64).sum()))
            elif op == "any_value":
                got.append(x[0].item())
            else:
                fn = {"mean": np.mean, "var": np.var, "stddev": np.std,
                      "min": np.min, "max": np.max}[op]
                got.append(fn(x.astype(np.float64) if op in
                              ("mean", "var", "stddev") else x).item())
        out.append((key, tuple(got)))
    return out


def _q1_layout(rng, C):
    """Two code keys (3 flags, 2 statuses), seven f32 planes that share
    ONE validity plane (the distinct-plane collapse), sum / mean /
    count."""
    keys = tuple(jnp.asarray(rng.integers(0, d, C).astype(np.int32))
                 for d in (3, 2))
    ones = jnp.ones(C, bool)
    vv = jnp.asarray(rng.random(C) > 0.01)
    vals = tuple(jnp.asarray(rng.uniform(0, s, C).astype(np.float32))
                 for s in (50, 1e5, 1e5, 1e5, 0.1, 50, 1e5))
    ops = ("sum", "sum", "sum", "sum", "mean", "mean", "count")
    return (keys, (ones, ones), vals, (vv,) * 7,
            jnp.asarray(np.arange(C) < C - C // 9), ops), (4, 2)


def _nulls(rng, C):
    """NULLs in both keys and in the values; a row mask with holes."""
    keys = tuple(jnp.asarray(rng.integers(0, d, C).astype(np.int32))
                 for d in (4, 2))
    kvalids = tuple(jnp.asarray(rng.random(C) > 0.2) for _ in keys)
    vals = (jnp.asarray(rng.uniform(-9, 9, C).astype(np.float32)),) * 2
    vvalids = tuple(jnp.asarray(rng.random(C) > 0.3) for _ in vals)
    return (keys, kvalids, vals, vvalids, jnp.asarray(rng.random(C) > 0.4),
            ("sum", "count")), (4, 2)


def _emptied_slot(rng, C):
    """The row mask takes every row of code 1 away: its slot stays
    empty and the groups after it move up."""
    k = rng.integers(0, 4, C).astype(np.int32)
    ones = jnp.ones(C, bool)
    v = jnp.asarray(rng.uniform(0, 9, C).astype(np.float32))
    return ((jnp.asarray(k),), (ones,), (v, v), (ones, ones),
            jnp.asarray(k != 1), ("sum", "mean")), (4,)


def _empty_table(rng, C):
    (keys, kv, vals, vv, _, ops), dims = _nulls(rng, C)
    return (keys, kv, vals, vv, jnp.zeros(C, bool), ops), dims


def _moments(rng, C):
    """var / stddev / mean over float64 planes (the f64 accumulator)."""
    keys = (jnp.asarray(rng.integers(0, 5, C).astype(np.int32)),)
    v = jnp.asarray(rng.normal(3.0, 2.0, C))
    vv = jnp.asarray(rng.random(C) > 0.1)
    return (keys, (jnp.asarray(rng.random(C) > 0.1),), (v, v, v),
            (vv, vv, vv), jnp.ones(C, bool),
            ("var", "stddev", "mean")), (8,)


def _exact_kinds(rng, C):
    """What never rides the additive planes: integer and bool sums (the
    exact int64 scatter), min / max, ``any_value``; beside a float sum."""
    keys = (jnp.asarray(rng.integers(0, 3, C).astype(np.int32)),)
    i = jnp.asarray(rng.integers(-2**40, 2**40, C))
    b = jnp.asarray(rng.random(C) > 0.5)
    f = jnp.asarray(rng.uniform(-5, 5, C).astype(np.float32))
    vvalids = tuple(jnp.asarray(rng.random(C) > 0.2) for _ in range(6))
    return (keys, (jnp.ones(C, bool),), (i, b, f, f, f, i), vvalids,
            jnp.asarray(rng.random(C) > 0.1),
            ("sum", "sum", "min", "max", "sum", "any_value")), (4,)


def _one_key_at(width):
    def build(rng, C):
        """One key whose codes fill ``width`` slots (none for K = 1)."""
        ones = jnp.ones(C, bool)
        keys = () if width is None else (
            jnp.asarray((np.arange(C) % width).astype(np.int32)),)
        v = jnp.asarray(rng.uniform(0, 9, C).astype(np.float32))
        return (keys, (ones,) * len(keys), (v, v), (ones, ones),
                jnp.asarray(rng.random(C) > 0.1), ("sum", "count")), \
            (() if width is None else (width,))
    return build


_BOUND = K.DENSE_MASKED_MAX_SLOTS
_INNER_CASES = [
    (name, build, C, wide, inner)
    for name, build, C in [("q1_layout_524288", _q1_layout, 524288),
                           ("nulls", _nulls, 512),
                           ("emptied_slot", _emptied_slot, 256),
                           ("empty_table", _empty_table, 64),
                           ("var_stddev_f64", _moments, 2048),
                           ("int_bool_min_max_any", _exact_kinds, 1024)]
    for wide, inner in [(False, "masked"), (True, "matmul")]
] + [("K_is_1", _one_key_at(None), 256, False, "masked"),
     ("K_at_the_bound", _one_key_at(_BOUND - 1), 1024, False, "masked"),
     ("K_one_past_the_bound", _one_key_at(_BOUND), 1024, False, "matmul")]


@pytest.mark.parametrize("name,build,C,wide,inner", _INNER_CASES,
                         ids=[f"{c[0]}-{c[4]}" for c in _INNER_CASES])
def test_dense_inner_loops_match_float64_reference(name, build, C, wide,
                                                   inner):
    """Both inner loops give numpy's groups in numpy's order: counts and
    integer sums bit-equal, min / max / ``any_value`` equal, float sums
    within 1e-5 relative (f32 summed in f32 over up to 524 288 rows)."""
    args, dims = build(np.random.default_rng(len(name) * 1000 + C), C)
    if wide:
        dims = _over_the_bound(dims)
    assert K.dense_inner_loop(dims) == inner
    assert (K.dense_slots(dims) <= _BOUND) == (inner == "masked")
    out_cap = dcol.bucket_capacity(max(K.dense_slots(dims), 128))
    fn = jax.jit(K.grouped_agg_dense_impl,
                 static_argnames=("ops", "out_cap", "dims"))
    got = _agg_rows(fn(*args[:5], ops=args[5], out_cap=out_cap, dims=dims))
    want = _ref_dense(*args)
    assert [k for k, _ in got] == [k for k, _ in want]
    for (key, g), (_, w) in zip(got, want):
        for x, y, op in zip(g, w, args[5]):
            if y is None or isinstance(y, (int, bool)) \
                    or op in ("min", "max", "any_value"):
                assert x == y and type(x) is type(y), (key, op, x, y)
            else:
                assert x == pytest.approx(y, rel=1e-5, abs=1e-9), \
                    (key, op, x, y)


def test_sort_agg_overflow_signals_and_redispatch_recovers():
    """More groups than ``out_cap``: the returned group count exceeds the
    bucket (the overflow contract — the caller re-dispatches at a grown
    bucket), and the re-dispatch at a fitting bucket is complete."""
    C, ndv = 256, 200
    ones = jnp.ones(C, bool)
    keys = (jnp.asarray(np.arange(C) % ndv, jnp.int64),)
    args = (keys, (ones,), (jnp.ones(C, jnp.float32),), (ones,), ones,
            ("sum",))
    small = K.grouped_agg_block_impl(*args, out_cap=128)
    assert int(np.asarray(small[-1])) == ndv > 128  # overflow signalled
    big = _agg_map(K.grouped_agg_block_impl(*args, out_cap=256), 1, 1)
    assert big == {(i,): (2.0 if i < C - ndv else 1.0,)
                   for i in range(ndv)}


# ------------------------------------------------ dense_dims / dense_plan

def _fused_prog_and_table(data, keys):
    """(fused program, encoded DeviceTable) of ``sum(v) group by keys``."""
    rb = RecordBatch.from_pydict(data)
    prog = fragment.get_fused_agg(
        [daft_tpu.col(k) for k in keys],
        [daft_tpu.col("v").alias("__v0__")], ("sum",), None, rb.schema)
    assert prog is not None
    return prog, dcol.encode_batch(rb, prog.compiled.needs_cols)


def _strings(n_distinct, n=200):
    return [f"s{i % n_distinct:03d}" for i in range(n)]


_PLAN_CASES = {
    # name: (data, keys, cap_limit, dims, plan)
    "two_dictionary_keys": (
        {"a": _strings(3), "b": _strings(2)}, ("a", "b"), 1 << 20,
        (4, 2), ((4, 2), 128)),
    "pow2_bucket_of_the_dictionary": (
        {"a": _strings(5), "b": _strings(1)}, ("a", "b"), 1 << 20,
        (8, 1), ((8, 1), 128)),
    "bucket_grows_with_the_slots": (
        {"a": _strings(60), "b": _strings(20)}, ("a", "b"), 1 << 20,
        (64, 32), ((64, 32), 4096)),
    "non_dictionary_key": (
        {"a": _strings(3), "b": list(range(200))}, ("a", "b"), 1 << 20,
        None, None),
    "slot_product_past_the_ceiling": (
        {"a": _strings(64), "b": _strings(33)}, ("a", "b"), 1 << 20,
        None, None),
    "out_cap_past_the_cap_limit": (
        {"a": _strings(60), "b": _strings(20)}, ("a", "b"), 2048,
        (64, 32), None),
}


@pytest.mark.parametrize("case", sorted(_PLAN_CASES))
def test_dense_plan_cases(case):
    """``dense_plan`` is the whole strategy choice: dense iff every key
    is a dictionary-coded passthrough, the pow2-bucketed slot product is
    within ``DENSE_MAX_SLOTS`` and its bucket within the link-budgeted
    ``cap_limit``; else the sort strategy runs."""
    data, keys, cap_limit, dims, plan = _PLAN_CASES[case]
    data = dict(data, v=[1.0] * 200)
    prog, dt = _fused_prog_and_table(data, keys)
    assert fragment.dense_dims(prog, dt) == dims
    assert fragment.dense_plan(prog, dt, cap_limit) == plan
    if plan is not None:
        K_ = int(np.prod([d + 1 for d in plan[0]]))
        assert K_ <= fragment.DENSE_MAX_SLOTS and K_ <= plan[1]


def test_dense_plan_needs_every_keys_dictionary():
    """A table whose key plane lost its dictionary (or lacks the column)
    cannot be slot-indexed: no dims, no plan."""
    prog, dt = _fused_prog_and_table(
        {"a": _strings(3), "b": _strings(2), "v": [1.0] * 200}, ("a", "b"))
    assert fragment.dense_dims(prog, dt) == (4, 2)
    col_b = dt.columns["b"]
    dt.columns["b"] = dcol.DeviceColumn(col_b.data, col_b.validity,
                                        col_b.dtype, None)
    assert fragment.dense_dims(prog, dt) is None
    assert fragment.dense_plan(prog, dt, 1 << 20) is None
    del dt.columns["b"]
    assert fragment.dense_dims(prog, dt) is None


# ------------------------------------------------------ fused sort join

def _join_pairs(packed, n_l):
    """(pairs list, counts) from the packed [3, W] result matrix."""
    counts = packed[2, :n_l]
    total = int(counts.sum())
    return list(zip(packed[0, :total].tolist(),
                    packed[1, :total].tolist())), counts


@pytest.mark.parametrize("seed", range(6))
def test_fused_join_matches_numpy_nested_loop(seed):
    """Pair-exact agreement of the fused sort join with a nested loop
    over the live, non-NULL rows — including pair ORDER (left-major,
    ascending right row) and the per-left-row match counts."""
    rng = np.random.default_rng(seed)
    C = int(rng.choice([64, 128]))
    lk = rng.integers(0, C // 3, C).astype(np.int64)
    rk = rng.integers(0, C // 3, C).astype(np.int64)
    lv = rng.random(C) > 0.15
    rv = rng.random(C) > 0.15
    lm = np.arange(C) < int(rng.integers(4, C))
    rm = np.arange(C) < int(rng.integers(4, C))
    cap = 4 * C
    got = np.asarray(K.join_fused_impl(*map(jnp.asarray,
                                            (lk, lv, lm, rk, rv, rm)), cap))
    want = [(i, j) for i in range(C) if lm[i] and lv[i]
            for j in range(C) if rm[j] and rv[j] and lk[i] == rk[j]]
    assert len(want) <= cap, "grow the cap for this seed"
    pairs, counts = _join_pairs(got, C)
    assert pairs == want
    assert counts.tolist() == [sum(1 for i, _ in want if i == r)
                               for r in range(C)]


def test_fused_join_null_keys_never_match():
    """NULL-keyed rows (validity False) on either side produce no pairs,
    even when their padded key words are bit-equal."""
    C = 16
    k = jnp.asarray(np.full(C, 5, np.int64))
    valid_l = jnp.asarray(np.arange(C) == 0)   # one live left row
    valid_r = jnp.asarray(np.arange(C) < 2)    # two live right rows
    ones = jnp.ones(C, bool)
    packed = np.asarray(K.join_fused_impl(k, valid_l, ones, k, valid_r,
                                          ones, 64))
    pairs, counts = _join_pairs(packed, C)
    assert pairs == [(0, 0), (0, 1)]
    assert counts.tolist() == [2] + [0] * (C - 1)


def test_join_ledger_counts_every_dispatch_under_the_one_strategy():
    """One kernel, one ledger row: the overflow re-dispatch adds a
    dispatch and its bytes to the ``join`` family's ``sort`` record."""
    from daft_tpu import joins
    costmodel.ledger_reset()
    lk, rk, lv, rv = _join_keys()
    joins._device_match_indices(lk, rk, lv, rv)
    one = costmodel.ledger_snapshot()["join"]
    assert (one["dispatches"], one["strategy"]) == (1, "sort")
    costmodel.ledger_reset()
    n = 1200  # many-to-many blowup: re-dispatched once at a grown bucket
    z, ones = np.zeros(n, np.int64), np.ones(n, bool)
    joins._device_match_indices(z, z, ones, ones)
    two = costmodel.ledger_snapshot()["join"]
    assert (two["dispatches"], two["strategy"]) == (2, "sort")
    assert two["rows"] == 2 * n and two["bytes"] > one["bytes"]
    costmodel.ledger_reset()


def test_engine_join_on_the_device_tier_matches_host(monkeypatch):
    """Whole-engine parity: a DataFrame join routed to the device
    (`DAFT_TPU_DEVICE_JOIN=1`) equals the host join, and the kernel
    ledger says the fused sort join ran."""
    rng = np.random.default_rng(21)
    left = {"k": [None if rng.random() < 0.1 else int(x)
                  for x in rng.integers(0, 60, 500)],
            "a": list(range(500))}
    right = {"k": rng.integers(0, 60, 200).tolist(),
             "b": list(range(200))}

    def run():
        return (daft_tpu.from_pydict(left)
                .join(daft_tpu.from_pydict(right), on="k")
                .sort(["a", "b"]).to_pydict())

    monkeypatch.setenv("DAFT_TPU_DEVICE_JOIN", "0")
    host = run()
    monkeypatch.setenv("DAFT_TPU_DEVICE_JOIN", "1")
    costmodel.ledger_reset()
    dev = run()
    snap = costmodel.ledger_snapshot()
    costmodel.ledger_reset()
    assert dev == host and len(dev["k"]) > 500
    assert snap["join"]["strategy"] == "sort"
    assert snap["join"]["dispatches"] >= 1


# ----------------------------------------------- strategy in the ledger

def test_ledger_carries_strategy():
    """`strategy` rides the same per-family ledger rows the stats block
    and dashboard render; a family that ran both reads `mixed` with a
    count each."""
    costmodel.ledger_reset()
    costmodel.ledger_record("grouped_agg", rows=10, nbytes=1e6,
                            seconds=0.1, strategy="dense")
    snap = costmodel.ledger_snapshot()
    assert snap["grouped_agg"]["strategy"] == "dense"
    costmodel.ledger_record("grouped_agg", rows=5, nbytes=1e6,
                            seconds=0.1, strategy="sort", dispatches=2)
    snap = costmodel.ledger_snapshot()
    assert snap["grouped_agg"]["strategy"] == "mixed"
    assert snap["grouped_agg"]["strategy_dense"] == 1
    assert snap["grouped_agg"]["strategy_sort"] == 2
    costmodel.ledger_reset()


def test_query_stats_render_strategy(monkeypatch):
    """The per-query device_kernels block shows the chosen strategy."""
    from daft_tpu import observability as obs
    costmodel.ledger_reset()
    ctx = obs.new_query_stats()
    costmodel.ledger_record("grouped_agg", rows=9, nbytes=1e6,
                            seconds=0.01, strategy="dense")
    ctx.finish()
    assert ctx.device_kernels["grouped_agg"]["strategy"] == "dense"
    assert "strategy=dense" in ctx.render()
    costmodel.ledger_reset()


# -------------------------------------------- engine end-to-end (device)

def _host_groupby(data, keys, aggs, monkeypatch):
    import daft_tpu as dtpu
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.delenv("DAFT_TPU_DEVICE_FORCE", raising=False)
    df = dtpu.from_pydict(data)
    return df.groupby(*keys).agg(*aggs).sort(list(keys)).to_pydict()


def _device_groupby(data, keys, aggs, monkeypatch):
    import daft_tpu as dtpu
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    df = dtpu.from_pydict(data)
    return df.groupby(*keys).agg(*aggs).sort(list(keys)).to_pydict()


def _pydicts_close(a, b):
    assert set(a) == set(b)
    for c in a:
        for x, y in zip(a[c], b[c]):
            if isinstance(x, float) and isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-5), c
            else:
                assert x == y, c


def _strategy_counts():
    """(dense, sort) dispatches of the grouped_agg family so far."""
    d = costmodel.ledger_snapshot(raw=True).get("grouped_agg", {})
    return d.get("strategy_dense", 0), d.get("strategy_sort", 0)


def test_engine_string_key_groupby_dispatches_dense_once(monkeypatch):
    """Whole-engine parity: a group-by on string keys (NULLs included)
    agrees with the pure host path and its fused fragment runs the
    dense strategy ONCE for its one table — no overflow rung. (The other
    dispatch is the merge of the partials, an in-memory batch on the
    sort kernel.)"""
    rng = np.random.default_rng(11)
    n = 500
    data = {
        "k": [None if rng.random() < 0.1 else f"g{int(x):02d}"
              for x in rng.integers(0, 40, n)],
        "j": [("x", "y", None)[int(x)] for x in rng.integers(0, 3, n)],
        "v": rng.uniform(-10, 10, n).round(3).tolist(),
    }
    aggs = (daft_tpu.col("v").sum().alias("s"),
            daft_tpu.col("v").mean().alias("m"),
            daft_tpu.col("v").count().alias("c"))
    host = _host_groupby(data, ("k", "j"), aggs, monkeypatch)
    costmodel.ledger_reset()
    dev = _device_groupby(data, ("k", "j"), aggs, monkeypatch)
    dense, sort = _strategy_counts()
    costmodel.ledger_reset()
    _pydicts_close(dev, host)
    assert (dense, sort) == (1, 1)


def test_engine_int_key_groupby_dispatches_sort(monkeypatch):
    """An integer key has no dictionary to index: every dispatch of the
    query (NULL keys included) rides the sort strategy and the answer is
    the host's."""
    rng = np.random.default_rng(11)
    n = 500
    data = {
        "k": [None if rng.random() < 0.1 else int(x)
              for x in rng.integers(0, 40, n)],
        "v": rng.uniform(-10, 10, n).round(3).tolist(),
    }
    aggs = (daft_tpu.col("v").sum().alias("s"),
            daft_tpu.col("v").mean().alias("m"),
            daft_tpu.col("v").count().alias("c"))
    host = _host_groupby(data, ("k",), aggs, monkeypatch)
    costmodel.ledger_reset()
    dev = _device_groupby(data, ("k",), aggs, monkeypatch)
    snap = costmodel.ledger_snapshot()
    costmodel.ledger_reset()
    _pydicts_close(dev, host)
    assert snap["grouped_agg"]["strategy"] == "sort"


def _run_fused(data, key, monkeypatch):
    """``sum(v) group by key`` through the fused-fragment ladder alone
    (no merge stage) → ({key: sum}, ledger row, strategy tally)."""
    from daft_tpu.aggs import split_agg_expr
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    rb = RecordBatch.from_pydict(data)
    agg = daft_tpu.col("v").sum().alias("s")
    op, child, name, _pred = split_agg_expr(agg)
    gexprs = [daft_tpu.col(key)]
    prog = fragment.get_fused_agg(gexprs, [child.alias("__v0__")], (op,),
                                  None, rb.schema)
    assert prog is not None
    out_schema = rb.agg([agg], gexprs).schema
    with costmodel._counts_lock:
        costmodel.decision_counts.pop("groupby_strategy", None)
    costmodel.ledger_reset()
    out = fragment.run_fused_agg(prog, rb, gexprs, [daft_tpu.col(name)],
                                 out_schema)
    snap = costmodel.ledger_snapshot()["grouped_agg"]
    costmodel.ledger_reset()
    assert out is not None
    got = out.to_pydict()
    return (dict(zip(got[key], got["s"])), snap,
            dict(costmodel.decision_counts["groupby_strategy"]))


def test_sort_overflow_ladder_grows_its_bucket(monkeypatch):
    """More groups than the first packed-output bucket (128): the header
    carries the true count, the ladder re-dispatches the sort program
    ONCE at the fitting bucket, the answer is exact, and the ledger row
    counts both rungs under `sort`."""
    n, ndv = 2048, 1500
    data = {"k": [int(i % ndv) for i in range(n)],
            "v": [float(i % 7) for i in range(n)]}
    got, snap, tally = _run_fused(data, "k", monkeypatch)
    want = {}
    for k, v in zip(data["k"], data["v"]):
        want[k] = want.get(k, 0.0) + v
    assert got == pytest.approx(want)
    assert (snap["dispatches"], snap["strategy"]) == (2, "sort")
    assert snap["rows"] == n
    assert tally == {"device": 0, "host": 2}   # one tally a rung


def test_dense_ladder_is_one_rung_whatever_the_groups(monkeypatch):
    """The same 1 500 groups behind a string key: the dictionary's pow2
    bucket (2 048 + the NULL slot) is within the slot budget, the bucket
    holds every slot, and the ladder stops at its first rung."""
    n, ndv = 8192, 1500
    data = {"k": [f"k{i % ndv:04d}" for i in range(n)],
            "v": [float(i % 7) for i in range(n)]}
    got, snap, tally = _run_fused(data, "k", monkeypatch)
    want = {}
    for k, v in zip(data["k"], data["v"]):
        want[k] = want.get(k, 0.0) + v
    assert got == pytest.approx(want)
    assert (snap["dispatches"], snap["strategy"]) == (1, "dense")
    assert tally == {"device": 0, "host": 1}


@pytest.mark.parametrize("big_file,strategy", [(False, "dense"),
                                               (True, "sort")])
def test_scan_batch_rides_one_strategy(tmp_path, monkeypatch, big_file,
                                       strategy):
    """The batch path plans dense per table and dispatches it only when
    EVERY table of the window fits the slot budget; one file whose
    dictionary outgrows it (5 000 strings -> 8 193 slots) sends the
    whole window down the sort strategy (its 5 000 groups re-dispatched
    at a grown bucket) and the ledger says so. Either way the answer is
    the host's."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from daft_tpu.context import execution_config_ctx
    from daft_tpu.device import cache as dcache
    n_files = 3
    for i in range(n_files):
        ndv = 5000 if (big_file and i == 0) else 3
        n = 6000
        pq.write_table(
            pa.table({"k": [f"k{(j * 7 + i) % ndv:04d}" for j in range(n)],
                      "v": [float(j % 11) for j in range(n)]}),
            str(tmp_path / f"part{i}.parquet"))

    def run():
        # one scan task a file, one synchronous window over all of them
        with execution_config_ctx(scan_tasks_min_size_bytes=1):
            return (daft_tpu.read_parquet(f"{tmp_path}/*.parquet")
                    .groupby("k").agg(daft_tpu.col("v").sum().alias("s"))
                    .sort("k").to_pydict())

    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "0")
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    host = run()
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    dcache.get_cache().clear()
    with costmodel._counts_lock:
        costmodel.decision_counts.pop("groupby_strategy", None)
    costmodel.ledger_reset()
    dev = run()
    dense, sort = _strategy_counts()
    tally = costmodel.decision_counts["groupby_strategy"]["host"]
    costmodel.ledger_reset()
    dcache.get_cache().clear()
    _pydicts_close(dev, host)
    # beside the window's dispatches: the merge of the partials, an
    # in-memory batch on the sort kernel (one dispatch, one tally)
    if strategy == "dense":
        assert (dense, sort) == (n_files, 1)   # one a table, no retry
        assert tally == 1 + 1                  # the window, the merge
    else:
        assert (dense, sort) == (0, n_files + 1)
        assert tally == 1 + 1 + 1   # the window, its retried table, merge


def test_fused_agg_strategy_counts_tally_dispatches(monkeypatch):
    """decision_counts describes what DISPATCHED: a group-by tallies one
    `groupby_strategy` decision a dispatch of its grouped_agg family, on
    the "host" side of the counts whichever strategy ran — the side the
    benchmark's `device_decisions_pct` has in its denominator."""
    n, ndv = 1000, 64  # fits the first bucket: no overflow ladder
    data = {"k": [f"k{i % ndv:02d}" for i in range(n)],
            "i": [int(i % ndv) for i in range(n)],
            "v": [float(i) for i in range(n)]}
    aggs = (daft_tpu.col("v").sum().alias("s"),)
    for key, want in (("k", (1, 1)), ("i", (0, 2))):
        host = _host_groupby(data, (key,), aggs, monkeypatch)
        with costmodel._counts_lock:
            costmodel.decision_counts.pop("groupby_strategy", None)
        costmodel.ledger_reset()
        dev = _device_groupby(data, (key,), aggs, monkeypatch)
        assert _strategy_counts() == want
        snap = costmodel.ledger_snapshot()
        costmodel.ledger_reset()
        _pydicts_close(dev, host)
        counts = costmodel.decision_counts.get("groupby_strategy")
        assert counts["device"] == 0
        assert counts["host"] == snap["grouped_agg"]["dispatches"] == 2, \
            (counts, snap["grouped_agg"])


def test_pow2_dims_share_one_traced_program(monkeypatch):
    """Per-morsel dictionaries drift in size; their pow2 buckets — the
    static `dims` of the dense program — do not, so tables with 3 and 4
    distinct keys re-enter ONE trace and a 5th key costs one more."""
    traces = []
    real = K.grouped_agg_dense_impl

    def counting(*a, **kw):
        traces.append(a[-1])
        return real(*a, **kw)

    monkeypatch.setattr(K, "grouped_agg_dense_impl", counting)
    fragment._fused_cache.clear()   # a fresh program: nothing traced yet
    for ndv in (3, 4, 5):
        got, snap, _ = _run_fused(
            {"k": _strings(ndv), "v": [1.0] * 200}, "k", monkeypatch)
        assert len(got) == ndv and snap["strategy"] == "dense"
    assert traces == [(4,), (8,)]


def test_warmup_compiles_one_sort_program_a_size_class():
    """The AOT warm-up asks the compiler for the sort twin of each fused
    program at each size class and for nothing it cannot build: no
    errors to count."""
    from daft_tpu.device import warmup
    prog, _ = _fused_prog_and_table(
        {"a": list(range(200)), "v": [1.0] * 200}, ("a",))
    st = warmup.warmup_fragments([128, 256], progs=[prog])
    assert st == {"programs": 2, "skipped": 0, "errors": 0}
