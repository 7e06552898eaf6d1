"""Round-6 kernel contracts: packed-key argsort (≤3 sort operands, exact
host agreement), the fused single-dispatch join, and the per-dispatch MFU
ledger.

The argsort parity sweep is property-based in the seeded-random style
(hypothesis is not guaranteed in every environment): ~60 random
configurations over mixed dtypes × descending × nulls_first × null
density, each asserting EXACT permutation agreement with the pyarrow
host path (both sides are stable sorts, so ties must agree too).
"""

import os

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


# ------------------------------------------- hash kernels (round 12)
#
# The hash grouped-agg / hash join are STRATEGY swaps for the sort
# kernels above: same argument shapes, same return contracts, same
# overflow discipline. Parity is proven three ways — kernel-vs-kernel
# (hash vs sort over seeded random configurations), kernel-vs-numpy
# (an independent host reference), and engine-vs-host (forced-hash
# queries against the pure host path). On this CPU tier every Pallas
# program runs under the interpreter (`interpret=True`), which is
# itself a tested contract: tier-1 proves parity without silicon.

from daft_tpu.device import mfu, pallas_kernels as pk  # noqa: E402


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


def _agg_map(out, nk, nv):
    """{group key tuple: value tuple} for the live groups of a kernel
    result — strategy-order-insensitive (hash emits slot order, sort
    emits key order; engine-wide, grouped output order is unspecified)."""
    ok, okv, ov, ovv, g = out
    g = int(np.asarray(jax.device_get(g)))
    ok = [np.asarray(k) for k in ok]
    okv = [np.asarray(k) for k in okv]
    ov = [np.asarray(v) for v in ov]
    ovv = [np.asarray(v) for v in ovv]
    m = {}
    for i in range(g):
        key = tuple(k[i].item() if kv[i] else None
                    for k, kv in zip(ok, okv))
        m[key] = tuple(v[i].item() if vv[i] else None
                       for v, vv in zip(ov, ovv))
    return m


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
def test_hash_agg_matches_sort_kernel_property(seed):
    """Seeded-property parity: the one-pass hash table and the
    sort+segment-reduce formulation agree on every group and every
    aggregate over random dtypes × null densities × op mixes."""
    rng = np.random.default_rng(seed)
    C = int(rng.choice([64, 128, 256]))
    nk = int(rng.integers(1, 3))
    nv = int(rng.integers(1, 3))
    keys, kvalids, vals, vvalids, mask, ops = _agg_args(rng, C, nk, nv)
    if pk.hash_pack_words([k.dtype for k in keys]) is None:
        pytest.skip("key set too wide for the hash budget")
    out_cap = C
    hashed = pk.hash_grouped_agg_impl(
        keys, kvalids, vals, vvalids, mask, ops, out_cap,
        interpret=True, block=int(rng.choice([16, 32, C])))
    sorted_ = K.grouped_agg_block_impl(
        keys, kvalids, vals, vvalids, mask, ops, out_cap)
    _maps_close(_agg_map(hashed, nk, nv), _agg_map(sorted_, nk, nv))


def test_hash_agg_matches_numpy_reference():
    """Independent host reference: sums/counts/min over known data with
    NULL keys and NULL values, computed with numpy, no engine code."""
    C = 64
    k = np.array([1, 2, 1, 3, 2, 1, 0, 3] + [0] * (C - 8), np.int64)
    kv = np.array([1, 1, 1, 1, 1, 0, 1, 1] + [1] * (C - 8), bool)
    v = np.arange(C, dtype=np.float32)
    vv = np.array([1, 1, 0, 1, 1, 1, 1, 1] + [1] * (C - 8), bool)
    mask = np.zeros(C, bool)
    mask[:8] = True
    out = pk.hash_grouped_agg_impl(
        (jnp.asarray(k),), (jnp.asarray(kv),), (jnp.asarray(v),),
        (jnp.asarray(vv),), jnp.asarray(mask), ("sum",), C,
        interpret=True, block=16)
    got = _agg_map(out, 1, 1)
    ref = {}
    for i in range(8):
        key = int(k[i]) if kv[i] else None
        ref.setdefault(key, []).append(float(v[i]) if vv[i] else None)
    want = {(key,): (sum(x for x in xs if x is not None)
                     if any(x is not None for x in xs) else None,)
            for key, xs in ref.items()}
    _maps_close(got, want)


def test_hash_agg_all_duplicate_and_all_unique_keys():
    """Adversarial cardinalities: one group total, and one group per
    row (the table at its load-factor ceiling)."""
    C = 128
    ones = jnp.ones(C, bool)
    dup = pk.hash_grouped_agg_impl(
        (jnp.full(C, 7, jnp.int64),), (ones,),
        (jnp.ones(C, jnp.float32),), (ones,), ones, ("sum",), C,
        interpret=True, block=32)
    assert int(np.asarray(dup[-1])) == 1
    assert np.asarray(dup[2][0])[0] == C
    uniq = pk.hash_grouped_agg_impl(
        (jnp.arange(C, dtype=jnp.int64),), (ones,),
        (jnp.ones(C, jnp.float32),), (ones,), ones, ("count",), C,
        interpret=True, block=32)
    assert int(np.asarray(uniq[-1])) == C
    m = _agg_map(uniq, 1, 1)
    assert len(m) == C and all(v == (1,) for v in m.values())


def test_hash_agg_overflow_signals_and_redispatch_recovers():
    """More groups than ``out_cap``: the returned group count exceeds the
    bucket (the r6 overflow contract — the caller re-dispatches at a
    grown bucket), and the re-dispatch at a fitting bucket is complete
    and sort-parity."""
    C = 256
    ndv = 200
    ones = jnp.ones(C, bool)
    keys = (jnp.asarray(np.arange(C) % ndv, jnp.int64),)
    vals = (jnp.ones(C, jnp.float32),)
    args = (keys, (ones,), vals, (ones,), ones, ("sum",))
    small = pk.hash_grouped_agg_impl(*args, out_cap=128, interpret=True,
                                     block=64)
    assert int(np.asarray(small[-1])) > 128  # overflow signalled
    big = pk.hash_grouped_agg_impl(*args, out_cap=256, interpret=True,
                                   block=64)
    ref = K.grouped_agg_block_impl(*args, out_cap=256)
    _maps_close(_agg_map(big, 1, 1), _agg_map(ref, 1, 1))


def test_hash_agg_wide_key_sets_raise_and_route_to_sort():
    """>128-bit packed key sets: ``hash_pack_words`` declines (the
    dispatch-site routing signal) and the kernel itself raises — wide
    keys always run as the sort path's LSD radix."""
    assert pk.hash_pack_words([np.dtype(d) for d in
                               rule_jit.HASH_UNFIT_KEY_DTYPES]) is None
    C = 32
    ones = jnp.ones(C, bool)
    k = jnp.asarray(np.arange(C), jnp.int64)
    with pytest.raises(ValueError):
        pk.hash_grouped_agg_impl(
            (k, k, k), (ones,) * 3, (jnp.ones(C, jnp.float32),), (ones,),
            ones, ("sum",), C, interpret=True, block=16)
    # the strategy model never picks hash for them, even when forced
    s, _ = costmodel.groupby_strategy(
        1000, 10.0, [np.dtype("int64")] * 3, 128, log=False)
    assert s == "sort"


def test_interpreter_mode_is_the_cpu_default():
    """Tier-1 runs every Pallas program under the interpreter: the CPU
    backend auto-selects it, and the knob force-overrides both ways."""
    assert pk.interpret_default() is True  # JAX_PLATFORMS=cpu in tier-1
    os.environ["DAFT_TPU_KERNEL_INTERPRET"] = "0"
    try:
        assert pk.interpret_default() is False
    finally:
        del os.environ["DAFT_TPU_KERNEL_INTERPRET"]


# --------------------------------------------------- hash join (round 12)

def _join_pairs(packed, n_l):
    """(pairs list, counts) from the packed [3, W] result matrix."""
    counts = packed[2, :n_l]
    total = int(counts.sum())
    return list(zip(packed[0, :total].tolist(),
                    packed[1, :total].tolist())), counts


@pytest.mark.parametrize("seed", range(6))
def test_hash_join_matches_sort_kernel_property(seed):
    """Pair-exact parity between the Pallas hash build/probe and the
    fused sort join — including pair ORDER (left-major, ascending right
    row), the contract that makes the strategies drop-in swaps."""
    rng = np.random.default_rng(seed)
    C = int(rng.choice([64, 128]))
    lk = jnp.asarray(rng.integers(0, C // 3, C).astype(np.int64))
    rk = jnp.asarray(rng.integers(0, C // 3, C).astype(np.int64))
    lv = jnp.asarray(rng.random(C) > 0.15)
    rv = jnp.asarray(rng.random(C) > 0.15)
    lm = jnp.asarray(np.arange(C) < int(rng.integers(4, C)))
    rm = jnp.asarray(np.arange(C) < int(rng.integers(4, C)))
    cap = 4 * C
    hashed = np.asarray(pk.hash_join_impl(lk, lv, lm, rk, rv, rm, cap,
                                          interpret=True, block=32))
    sorted_ = np.asarray(K.join_fused_impl(lk, lv, lm, rk, rv, rm, cap))
    hp, hc = _join_pairs(hashed, C)
    sp, sc = _join_pairs(sorted_, C)
    assert int(hc.sum()) <= cap, "grow the cap for this seed"
    assert hp == sp
    assert hc.tolist() == sc.tolist()


def test_hash_join_null_keys_never_match():
    """NULL-keyed rows (validity False) on either side produce no pairs,
    even when their padded key words are bit-equal."""
    C = 16
    k = jnp.asarray(np.full(C, 5, np.int64))
    valid_l = jnp.asarray(np.arange(C) == 0)   # one live left row
    valid_r = jnp.asarray(np.arange(C) < 2)    # two live right rows
    ones = jnp.ones(C, bool)
    packed = np.asarray(pk.hash_join_impl(
        k, valid_l, ones, k, valid_r, ones, 64, interpret=True, block=16))
    pairs, counts = _join_pairs(packed, C)
    assert pairs == [(0, 0), (0, 1)]
    assert counts.tolist() == [2] + [0] * (C - 1)


def test_engine_join_hash_single_dispatch_matches_host(monkeypatch):
    """`DAFT_TPU_KERNEL_JOIN=hash` routes `_device_match_indices` through
    the Pallas kernel — exactly ONE dispatch, host-identical indices."""
    from daft_tpu import joins
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_KERNEL_JOIN", "hash")
    lk, rk, lv, rv = _join_keys()
    calls = {"n": 0}
    real = pk.hash_join_kernel

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pk, "hash_join_kernel", counting)
    costmodel.ledger_reset()
    out = joins._device_match_indices(lk, rk, lv, rv)
    assert out is not None
    assert calls["n"] == 1, f"expected ONE dispatch, saw {calls['n']}"
    dli, dri, dcnt = out
    monkeypatch.setenv("DAFT_TPU_DEVICE_JOIN", "0")
    hli, hri, hcnt = joins.match_indices(lk, rk, lv, rv)
    assert sorted(zip(dli.tolist(), dri.tolist())) == \
        sorted(zip(hli.tolist(), hri.tolist()))
    assert np.array_equal(dcnt, hcnt)
    snap = costmodel.ledger_snapshot()
    assert snap["join"]["strategy"] == "hash"
    assert 0 < snap["join"]["load_factor"] <= 0.5  # 2x-capacity table
    costmodel.ledger_reset()


def test_engine_join_hash_overflow_redispatches_once(monkeypatch):
    """A many-to-many blowup past the FK-shaped output estimate re-runs
    the HASH kernel at the fitting bucket — two dispatches, correct."""
    from daft_tpu import joins
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_KERNEL_JOIN", "hash")
    n = 400  # 400*400 pairs >> bucket_capacity(400) slots
    lk = np.zeros(n, np.int64)
    rk = np.zeros(n, np.int64)
    ones = np.ones(n, bool)
    calls = {"n": 0}
    real = pk.hash_join_kernel

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pk, "hash_join_kernel", counting)
    dli, dri, dcnt = joins._device_match_indices(lk, rk, ones, ones)
    assert calls["n"] == 2
    assert len(dli) == n * n
    assert dcnt.tolist() == [n] * n


# ------------------------------------- strategy model + ledger (round 12)

def test_groupby_strategy_decision_rule(monkeypatch):
    """The hash-vs-sort decision ladder: silicon-only in auto, forced by
    the knob, NDV-fraction decline, table-ceiling decline."""
    dts = [np.dtype("int64")]
    # CPU backend in auto mode: the interpreter exists for parity, not
    # speed — stays on sort
    assert costmodel.groupby_strategy(10_000, 64.0, dts, 128,
                                      log=False)[0] == "sort"
    monkeypatch.setenv("DAFT_TPU_KERNEL_GROUPBY", "hash")
    s, lf = costmodel.groupby_strategy(10_000, 64.0, dts, 128, log=False)
    assert s == "hash" and 0 < lf <= 1.0
    monkeypatch.setenv("DAFT_TPU_KERNEL_GROUPBY", "sort")
    assert costmodel.groupby_strategy(10_000, 64.0, dts, 128,
                                      log=False)[0] == "sort"
    # auto + silicon: hash at aggregation-shaped NDV …
    monkeypatch.setenv("DAFT_TPU_KERNEL_GROUPBY", "auto")
    monkeypatch.setattr(costmodel, "_hash_capable_backend", lambda: True)
    assert costmodel.groupby_strategy(10_000, 64.0, dts, 128,
                                      log=False)[0] == "hash"
    # … sort on near-unique keys (the table grows as large as the data)
    assert costmodel.groupby_strategy(10_000, 9_000.0, dts, 16384,
                                      log=False)[0] == "sort"
    # … sort when the table exceeds the on-chip slot ceiling
    monkeypatch.setenv("DAFT_TPU_KERNEL_MAX_TABLE", "256")
    assert costmodel.groupby_strategy(10_000, 64.0, dts, 4096,
                                      log=False)[0] == "sort"


def test_join_strategy_decision_rule(monkeypatch):
    assert costmodel.join_strategy(1000, 1000) == "sort"  # CPU auto
    monkeypatch.setenv("DAFT_TPU_KERNEL_JOIN", "hash")
    assert costmodel.join_strategy(1000, 1000) == "hash"
    monkeypatch.setenv("DAFT_TPU_KERNEL_JOIN", "auto")
    monkeypatch.setattr(costmodel, "_hash_capable_backend", lambda: True)
    assert costmodel.join_strategy(1000, 1000) == "hash"
    monkeypatch.setenv("DAFT_TPU_KERNEL_MAX_TABLE", "256")
    assert costmodel.join_strategy(100_000, 100_000) == "sort"


def test_ledger_carries_strategy_and_load_factor():
    """`strategy`/`load_factor` ride the same per-family ledger rows the
    stats block and dashboard render."""
    costmodel.ledger_reset()
    costmodel.ledger_record("grouped_agg", rows=10, nbytes=1e6,
                            seconds=0.1, strategy="hash", load_factor=0.4)
    snap = costmodel.ledger_snapshot()
    assert snap["grouped_agg"]["strategy"] == "hash"
    assert snap["grouped_agg"]["load_factor"] == 0.4
    costmodel.ledger_record("grouped_agg", rows=5, nbytes=1e6,
                            seconds=0.1, strategy="sort")
    snap = costmodel.ledger_snapshot()
    assert snap["grouped_agg"]["strategy"] == "mixed"
    assert snap["grouped_agg"]["strategy_hash"] == 1
    assert snap["grouped_agg"]["strategy_sort"] == 1
    costmodel.ledger_reset()


def test_query_stats_render_strategy(monkeypatch):
    """The per-query device_kernels block shows the chosen strategy."""
    from daft_tpu import observability as obs
    costmodel.ledger_reset()
    ctx = obs.new_query_stats()
    costmodel.ledger_record("grouped_agg", rows=9, nbytes=1e6,
                            seconds=0.01, strategy="hash",
                            load_factor=0.25)
    ctx.finish()
    assert ctx.device_kernels["grouped_agg"]["strategy"] == "hash"
    assert ctx.device_kernels["grouped_agg"]["load_factor"] == 0.25
    assert "strategy=hash" in ctx.render()
    assert "load=0.25" in ctx.render()
    costmodel.ledger_reset()


def test_hash_byte_models_beat_sort_at_agg_shapes():
    """The pricing the strategy model acts on: at aggregation-shaped NDV
    the one-pass hash model touches fewer bytes than the multi-pass sort
    model; both are positive."""
    rows, out_cap = 1 << 20, 256
    table = pk.table_capacity(out_cap)
    _, sort_b = mfu.grouped_agg_models(rows, out_cap, 1, 2)
    _, hash_b = mfu.hash_agg_models(rows, out_cap, table, 1, 2)
    assert 0 < hash_b < sort_b
    assert mfu.hash_join_bytes_model(1 << 16, 1 << 16, 1 << 16) > 0


# -------------------------------------- engine end-to-end (forced hash)

def _host_groupby(data, keys, aggs, monkeypatch):
    import daft_tpu as dtpu
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.delenv("DAFT_TPU_DEVICE_FORCE", raising=False)
    df = dtpu.from_pydict(data)
    return df.groupby(*keys).agg(*aggs).sort(list(keys)).to_pydict()


def _device_groupby(data, keys, aggs, monkeypatch, strategy="hash"):
    import daft_tpu as dtpu
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_KERNEL_GROUPBY", strategy)
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


def test_engine_groupby_forced_hash_matches_host(monkeypatch):
    """Whole-engine parity: a forced-hash grouped aggregation (NULL keys
    included) agrees with the pure host path, and the query's ledger row
    says the hash strategy really ran."""
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
    _pydicts_close(dev, host)
    assert snap["grouped_agg"]["strategy"] == "hash"
    assert snap["grouped_agg"]["load_factor"] > 0
    costmodel.ledger_reset()


def test_engine_groupby_hash_overflow_grows_bucket(monkeypatch):
    """More groups than the first packed-output bucket (128) but fewer
    than the first hash TABLE's slots: the fused path re-dispatches the
    HASH program at a grown bucket and the answer is still host-exact.
    (NDV past the table size saturates it and switches the ladder to
    sort — covered by test_saturated_hash_overflow_switches_to_sort.)"""
    n = 2000
    ndv = 200  # > _OUT_CAP0, < table_capacity(_OUT_CAP0) so never saturated
    data = {"k": [int(i % ndv) for i in range(n)],
            "v": [float(i) for i in range(n)]}
    aggs = (daft_tpu.col("v").sum().alias("s"),)
    host = _host_groupby(data, ("k",), aggs, monkeypatch)
    costmodel.ledger_reset()
    dev = _device_groupby(data, ("k",), aggs, monkeypatch)
    snap = costmodel.ledger_snapshot()
    _pydicts_close(dev, host)
    assert snap["grouped_agg"]["strategy"] == "hash"
    costmodel.ledger_reset()


def test_engine_groupby_wide_keys_fall_back_to_sort(monkeypatch):
    """Three i64 key columns pack past the 128-bit hash budget: even
    forced-hash queries route to the sort path and stay host-exact."""
    rng = np.random.default_rng(5)
    n = 300
    big = 1 << 60
    data = {
        "a": (rng.integers(-big, big, n)).tolist(),
        "b": (rng.integers(-big, big, n) | 1).tolist(),
        "c": rng.integers(0, 3, n).tolist(),
        "v": rng.uniform(0, 10, n).round(2).tolist(),
    }
    # only 3 distinct (a, b, c) triples → grouping is real
    for col_ in ("a", "b"):
        data[col_] = [data[col_][i % 3] for i in range(n)]
    aggs = (daft_tpu.col("v").sum().alias("s"),)
    host = _host_groupby(data, ("a", "b", "c"), aggs, monkeypatch)
    costmodel.ledger_reset()
    dev = _device_groupby(data, ("a", "b", "c"), aggs, monkeypatch)
    snap = costmodel.ledger_snapshot()
    _pydicts_close(dev, host)
    assert snap["grouped_agg"]["strategy"] == "sort"
    costmodel.ledger_reset()


# ------------------------------------------ hash dispatch contracts

def test_hash_agg_jaxpr_contracts():
    """Single-sourced with the lint rule: ONE pallas_call (the table
    build), slot compaction within the ≤3-operand sort budget, zero
    host callbacks."""
    jx = rule_jit.hash_agg_jaxpr()
    assert rule_jit.count_primitive(jx.jaxpr, "pallas_call") \
        == rule_jit.HASH_AGG_PALLAS_CALLS
    assert rule_jit.max_sort_operands(jx.jaxpr) \
        <= rule_jit.ARGSORT_MAX_SORT_OPERANDS
    for prim in rule_jit.FORBIDDEN_IN_FUSED_JOIN:
        assert rule_jit.count_primitive(jx.jaxpr, prim) == 0


def test_hash_join_jaxpr_contracts():
    """TWO pallas_calls (build + probe) fused in one jit program, NO
    lax.sort anywhere, zero host callbacks."""
    jx = rule_jit.hash_join_jaxpr()
    assert rule_jit.count_primitive(jx.jaxpr, "pallas_call") \
        == rule_jit.HASH_JOIN_PALLAS_CALLS
    assert rule_jit.max_sort_operands(jx.jaxpr) \
        <= rule_jit.HASH_JOIN_MAX_SORT_OPERANDS
    for prim in rule_jit.FORBIDDEN_IN_FUSED_JOIN:
        assert rule_jit.count_primitive(jx.jaxpr, prim) == 0


def test_mfu_report_has_hash_rows_with_strategy():
    """`mfu.report()` times the hash kernels in-jit too (shrunk smoke
    size under the interpreter) and tags every row with its strategy."""
    r = mfu.report(n=1 << 10)
    assert "hash_error" not in r, r.get("hash_error")
    assert r["grouped_agg_hash"]["strategy"] == "hash"
    assert r["grouped_agg_hash"]["interpret"] is True
    assert r["join_hash"]["strategy"] == "hash"
    assert r["grouped_agg"]["strategy"] == "sort"
    assert r["join"]["strategy"] == "sort"


# ----------------------------------- review-hardening regressions (r12)

def test_load_factor_one_cannot_silently_drop_groups(monkeypatch):
    """`DAFT_TPU_KERNEL_HASH_LOAD=1.0` used to make the table exactly
    `out_cap` slots — it filled silently instead of signalling
    `group_count > out_cap`, truncating the answer. The clamp now keeps
    the table strictly larger than the group budget, so overflow always
    signals."""
    monkeypatch.setenv("DAFT_TPU_KERNEL_HASH_LOAD", "1.0")
    assert pk.table_capacity(128) > 128
    C, ndv = 256, 200
    ones = jnp.ones(C, bool)
    out = pk.hash_grouped_agg_impl(
        (jnp.asarray(np.arange(C) % ndv, jnp.int64),), (ones,),
        (jnp.ones(C, jnp.float32),), (ones,), ones, ("sum",), 128,
        interpret=True, block=64)
    assert int(np.asarray(out[-1])) > 128  # overflow signalled, not eaten


def test_saturated_hash_overflow_switches_to_sort(monkeypatch):
    """A completely FULL hash table reports only a lower bound on the
    group count, so the overflow re-dispatch switches to the sort
    strategy (whose header is exact) instead of doubling the hash
    bucket one full row pass at a time: hash@128 (saturated) →
    sort (true count) → hash at the fitting bucket = 3 dispatches."""
    from daft_tpu.aggs import split_agg_expr
    from daft_tpu.device import fragment
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_KERNEL_GROUPBY", "hash")
    n, ndv = 2048, 1500
    rb = RecordBatch.from_pydict(
        {"k": [int(i % ndv) for i in range(n)],
         "v": [float(i % 7) for i in range(n)]})
    agg = daft_tpu.col("v").sum().alias("s")
    op, child, name, _pred = split_agg_expr(agg)
    gexprs = [daft_tpu.col("k")]
    prog = fragment.get_fused_agg(
        gexprs, [(child if child is not None else daft_tpu.lit(True))
                 .alias("__v0__")], (op,), None, rb.schema)
    assert prog is not None
    host = rb.agg([agg], gexprs)
    costmodel.ledger_reset()
    out = fragment.run_fused_agg(prog, rb, gexprs, [daft_tpu.col(name)],
                                 host.schema)
    snap = costmodel.ledger_snapshot()
    costmodel.ledger_reset()
    assert out is not None
    got = dict(zip(out.to_pydict()["k"], out.to_pydict()["s"]))
    want = dict(zip(host.to_pydict()["k"], host.to_pydict()["s"]))
    assert len(got) == ndv
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-5)
    assert snap["grouped_agg"]["dispatches"] == 3, snap["grouped_agg"]


def test_interpret_knob_auto_means_autodetect(monkeypatch):
    """Exporting the knob's documented default spelling (`auto`) must
    mean backend autodetection, not force-the-emulator — on silicon that
    would silently run every hash kernel as a python-level emulation."""
    from daft_tpu.device import backend
    monkeypatch.setenv("DAFT_TPU_KERNEL_INTERPRET", "auto")
    monkeypatch.setattr(backend, "backend_name", lambda: "tpu")
    assert pk.interpret_default() is False   # autodetect follows silicon
    monkeypatch.setenv("DAFT_TPU_KERNEL_INTERPRET", "1")
    assert pk.interpret_default() is True    # explicit force still wins
    monkeypatch.setattr(backend, "backend_name", lambda: "cpu")
    monkeypatch.setenv("DAFT_TPU_KERNEL_INTERPRET", "0")
    assert pk.interpret_default() is False


def test_join_overflow_past_table_ceiling_switches_to_sort(monkeypatch):
    """A many-to-many blowup whose grown output bucket exceeds the
    on-chip slot ceiling re-dispatches on the SORT kernel (the hash
    probe pins two cap-sized index planes on-chip; XLA's buffers live in
    HBM) — and the ledger accounts each strategy's dispatch separately."""
    from daft_tpu import joins
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_KERNEL_JOIN", "hash")
    monkeypatch.setenv("DAFT_TPU_KERNEL_MAX_TABLE", "2048")
    n = 400  # 400*400 pairs → bucket_capacity(160000) >> 2048 slots
    lk = np.zeros(n, np.int64)
    rk = np.zeros(n, np.int64)
    ones = np.ones(n, bool)
    calls = {"hash": 0, "sort": 0}
    real_h, real_s = pk.hash_join_kernel, K.join_fused_kernel

    def counting_h(*a, **kw):
        calls["hash"] += 1
        return real_h(*a, **kw)

    def counting_s(*a, **kw):
        calls["sort"] += 1
        return real_s(*a, **kw)

    monkeypatch.setattr(pk, "hash_join_kernel", counting_h)
    monkeypatch.setattr(K, "join_fused_kernel", counting_s)
    costmodel.ledger_reset()
    dli, dri, dcnt = joins._device_match_indices(lk, rk, ones, ones)
    snap = costmodel.ledger_snapshot()
    costmodel.ledger_reset()
    assert calls == {"hash": 1, "sort": 1}
    assert len(dli) == n * n
    assert dcnt.tolist() == [n] * n
    assert snap["join"]["strategy"] == "mixed"
    assert snap["join"]["strategy_hash"] == 1
    assert snap["join"]["strategy_sort"] == 1
    assert snap["join"]["dispatches"] == 2


def test_join_strategy_declines_oversized_probe_output(monkeypatch):
    """Auto mode declines hash when the FIRST dispatch's output bucket
    (sized from the larger side) already exceeds the slot ceiling — the
    probe kernel's cap-sized output planes must fit on-chip like the
    build table."""
    monkeypatch.setattr(costmodel, "_hash_capable_backend", lambda: True)
    monkeypatch.delenv("DAFT_TPU_KERNEL_JOIN", raising=False)
    monkeypatch.setenv("DAFT_TPU_KERNEL_MAX_TABLE", "2048")
    assert costmodel._join_strategy(128, 128) == "hash"
    assert costmodel._join_strategy(100_000, 128) == "sort"


def test_mfu_hash_join_measures_admissible_config(monkeypatch):
    """measure_hash_join clamps its row count so the measured config is
    one the strategy model would dispatch: the 2× build table must stay
    within the slot ceiling (an inadmissible config fails to lower on
    silicon and would erase the roofline row)."""
    monkeypatch.setenv("DAFT_TPU_KERNEL_MAX_TABLE", "512")
    out = mfu.measure_hash_join(1 << 20)
    assert out["rows"] == 256
    assert out["table_slots"] <= 512


def test_hash_join_kernel_block_knob_retrace(monkeypatch):
    """The block size is resolved OUTSIDE the trace and passed into the
    jitted program (jit hygiene): changing `DAFT_TPU_KERNEL_BLOCK`
    re-traces at the new block and the answer is unchanged."""
    rng = np.random.default_rng(11)
    C = 64
    lk = jnp.asarray(rng.integers(0, 8, C).astype(np.int64))
    rk = jnp.asarray(rng.integers(0, 8, C).astype(np.int64))
    ones = jnp.ones(C, bool)
    monkeypatch.setenv("DAFT_TPU_KERNEL_BLOCK", "32")
    a = np.asarray(pk.hash_join_kernel(lk, ones, ones, rk, ones, ones,
                                       out_capacity=1024))
    monkeypatch.setenv("DAFT_TPU_KERNEL_BLOCK", "16")
    b = np.asarray(pk.hash_join_kernel(lk, ones, ones, rk, ones, ones,
                                       out_capacity=1024))
    assert np.array_equal(a, b)


def test_fused_agg_strategy_counts_tally_dispatches(monkeypatch):
    """decision_counts describes what DISPATCHED: one fused forced-hash
    group-by tallies exactly its acted-on dispatches (strategy_for is a
    pure ask — the old pre-dispatch logging double-counted re-asks and
    missed width-gate fallbacks entirely)."""
    n, ndv = 1000, 64  # fits the first bucket: no overflow ladder
    data = {"k": [int(i % ndv) for i in range(n)],
            "v": [float(i) for i in range(n)]}
    aggs = (daft_tpu.col("v").sum().alias("s"),)
    host = _host_groupby(data, ("k",), aggs, monkeypatch)
    with costmodel._counts_lock:
        costmodel.decision_counts.pop("groupby_strategy", None)
    costmodel.ledger_reset()
    dev = _device_groupby(data, ("k",), aggs, monkeypatch)
    snap = costmodel.ledger_snapshot()
    costmodel.ledger_reset()
    _pydicts_close(dev, host)
    counts = costmodel.decision_counts.get("groupby_strategy")
    assert counts["host"] == 0  # forced hash: no sort decision tallied
    assert counts["device"] == snap["grouped_agg"]["dispatches"], \
        (counts, snap["grouped_agg"])
