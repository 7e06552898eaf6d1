"""Round 17 async device pipeline tests: pipelined-vs-synchronous
parity, slot admission hygiene (leak / cancellation / exception
unwinding), chaos-serialize degradation, overlap spans + ledger, the
single-transfer download discipline, device-resident hand-off, and the
overlap-aware cost model."""

import numpy as np
import pytest

import daft_tpu as daft
from daft_tpu import col, tracing
from daft_tpu import observability as obs
from daft_tpu.device import costmodel as cm
from daft_tpu.device import column as dcol
from daft_tpu.device import pipeline as dpipe
from daft_tpu.execution.memory import MemoryManager


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    dpipe.reset_counters()
    dpipe.reset_residency()
    yield
    dpipe.reset_counters()
    dpipe.reset_residency()


@pytest.fixture(scope="module")
def pq_dir(tmp_path_factory):
    """A multi-file parquet 'lineitem' so the fragment path takes the
    windowed scan-task route with several windows in flight."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    root = tmp_path_factory.mktemp("devpipe_pq")
    rng = np.random.default_rng(7)
    for i in range(6):
        n = 800
        pq.write_table(
            pa.table({"flag": rng.integers(0, 4, n),
                      "qty": rng.random(n) * 50,
                      "price": rng.random(n) * 1000}),
            str(root / f"part{i}.parquet"))
    return str(root)


def _q1_scan(root):
    return (daft.read_parquet(f"{root}/*.parquet")
            .groupby("flag")
            .agg(col("qty").sum().alias("sum_qty"),
                 col("price").mean().alias("avg_price"),
                 col("qty").count().alias("cnt"))
            .sort(col("flag")))


def _q1_shape(n=4000, ndv=4):
    # bare in-memory source → the fused fragment's per-morsel path
    rng = np.random.default_rng(7)
    return (daft.from_pydict({
        "flag": rng.integers(0, ndv, n),
        "qty": rng.random(n) * 50,
        "price": rng.random(n) * 1000})
        .groupby("flag")
        .agg(col("qty").sum().alias("sum_qty"),
             col("price").mean().alias("avg_price"),
             col("qty").count().alias("cnt"))
        .sort(col("flag")))


def _q6_shape(n=4000):
    rng = np.random.default_rng(11)
    return (daft.from_pydict({
        "qty": rng.random(n) * 50,
        "disc": rng.random(n) * 0.1,
        "price": rng.random(n) * 1000})
        .where(col("qty") < 24)
        .agg((col("price") * col("disc")).sum().alias("revenue")))


def _q3_shape(n=2000, parts=3):
    rng = np.random.default_rng(13)
    orders = daft.from_pydict({
        "okey": np.arange(n), "cust": rng.integers(0, 50, n)})
    items = daft.from_pydict({
        "okey": rng.integers(0, n, 3 * n),
        "rev": rng.random(3 * n) * 100}).into_partitions(parts)
    return (items.join(orders, on="okey")
            .groupby("cust").agg(col("rev").sum().alias("rev"))
            .sort(col("rev"), desc=True).limit(10))


def _run(df):
    from daft_tpu.context import execution_config_ctx
    # tiny scan tasks → one task per parquet file → several windows
    with execution_config_ctx(scan_tasks_min_size_bytes=1):
        return df.to_pydict()


@pytest.mark.parametrize("shape", [_q1_shape, _q6_shape, _q3_shape])
def test_pipelined_matches_synchronous_bit_identical(monkeypatch, shape):
    """Parity gate: the async pipeline must produce byte-identical
    results to the verbatim synchronous chain on q1/q6/q3 shapes."""
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    piped = _run(shape())
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "0")
    sync = _run(shape())
    assert piped == sync


def test_pipelined_scan_windows_match_synchronous(monkeypatch, pq_dir):
    """The windowed scan-task route (several windows in flight) must be
    bit-identical to its synchronous degradation too."""
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    piped = _run(_q1_scan(pq_dir))
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "0")
    sync = _run(_q1_scan(pq_dir))
    assert piped == sync


def test_pipelined_parity_on_forced_overflow_redispatch(monkeypatch):
    """A group count far past the first packed bucket (128) forces the
    overflow ladder to re-dispatch mid-drain — results must still match
    the synchronous path AND the pure host tier."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    host = _run(_q1_shape(n=6000, ndv=1500))
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    piped = _run(_q1_shape(n=6000, ndv=1500))
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "0")
    sync = _run(_q1_shape(n=6000, ndv=1500))
    assert piped == sync
    assert piped["flag"] == host["flag"]
    for a, b in zip(piped["sum_qty"], host["sum_qty"]):
        assert a == pytest.approx(b, rel=1e-9)


# ------------------------------------------------ slot admission hygiene

def test_exception_mid_window_releases_every_slot():
    mem = MemoryManager(budget=1 << 30)

    def submit(item, seq, gate):
        slot = dpipe.acquire_slot(gate, seq, mem, 1000)
        return dpipe.InflightItem(slot, item)

    def drain(ret, seq):
        if seq == 2:
            raise RuntimeError("boom mid-window")
        return ret.token

    with pytest.raises(RuntimeError, match="boom"):
        list(dpipe.run_pipelined(range(8), submit, drain, window=3))
    assert mem.outstanding == 0


def test_cancellation_unwinds_partially_drained_window():
    """Closing the consumer generator mid-stream (cancellation /
    early-limit abandonment) must release every in-flight slot's
    admission and window occupancy."""
    mem = MemoryManager(budget=1 << 30)

    def submit(item, seq, gate):
        slot = dpipe.acquire_slot(gate, seq, mem, 500)
        return dpipe.InflightItem(slot, item)

    def drain(ret, seq):
        return ret.token

    gen = dpipe.run_pipelined(range(16), submit, drain, window=2)
    assert next(gen) == 0
    assert next(gen) == 1
    gen.close()  # partially drained window unwinds here
    assert mem.outstanding == 0


def test_submit_failure_releases_slot_and_propagates():
    mem = MemoryManager(budget=1 << 30)

    def submit(item, seq, gate):
        slot = dpipe.acquire_slot(gate, seq, mem, 100)
        try:
            if seq == 1:
                raise ValueError("encode failed")
        except BaseException:
            dpipe.release_slot(slot)
            raise
        return dpipe.InflightItem(slot, item)

    with pytest.raises(ValueError, match="encode failed"):
        list(dpipe.run_pipelined(range(4), submit, drain=lambda r, s: r.token,
                                 window=2))
    assert mem.outstanding == 0


def test_host_routed_items_bypass_the_window():
    """Host results don't occupy device slots: a host-heavy stream runs
    at pool width, and ordering is still preserved."""
    seen = []

    def submit(item, seq, gate):
        return item * 10  # plain value = host routed

    out = list(dpipe.run_pipelined(range(20), submit,
                                   drain=lambda r, s: seen.append(s) or r,
                                   window=2))
    assert out == [i * 10 for i in range(20)]
    assert seen == list(range(20))


def test_engine_slot_acquire_release_balanced(monkeypatch, pq_dir):
    """End-to-end: every slot a pipelined device query acquires is
    released by the time the query completes (the acquire-on-submit ↔
    release-on-drain contract, observed at the real chokepoint)."""
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    monkeypatch.setenv("DAFT_TPU_MEMORY_LIMIT", "1GiB")
    acquired = []
    real_acquire = dpipe.acquire_slot

    def tracking(*args, **kw):
        slot = real_acquire(*args, **kw)
        acquired.append(slot)
        return slot

    monkeypatch.setattr(dpipe, "acquire_slot", tracking)
    _run(_q1_scan(pq_dir))
    assert acquired, "the pipelined device path never engaged"
    assert all(s.released for s in acquired)


# ------------------------------------------- chaos-serialize degradation

def test_chaos_serialize_forces_synchronous_window(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "4")
    assert dpipe.inflight_window() == 4
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    assert dpipe.inflight_window() == 0


def test_active_fault_plan_forces_synchronous_window(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "4")
    monkeypatch.setenv("DAFT_TPU_FAULT_SPEC", "task:0.5")
    from daft_tpu.distributed import resilience as rz
    rz.reset_for_tests()
    try:
        assert dpipe.inflight_window() == 0
    finally:
        monkeypatch.delenv("DAFT_TPU_FAULT_SPEC")
        rz.reset_for_tests()


def test_config_field_applies_when_env_unset(monkeypatch):
    from daft_tpu.context import execution_config_ctx
    monkeypatch.delenv("DAFT_TPU_DEVICE_INFLIGHT", raising=False)
    with execution_config_ctx(tpu_device_inflight=7):
        assert dpipe.inflight_window() == 7
    # env override wins over the config field
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "3")
    with execution_config_ctx(tpu_device_inflight=7):
        assert dpipe.inflight_window() == 3


def test_chaos_serialized_results_match_pipelined(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    piped = _run(_q1_shape())
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    serialized = _run(_q1_shape())
    assert piped == serialized


# ---------------------------------------------------- spans + overlap

def test_pipeline_spans_on_distinct_lanes_with_slot_ids(monkeypatch, pq_dir):
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    tracing.reset_for_tests()
    _run(_q1_scan(pq_dir))
    stats = obs.last_query_stats()
    assert stats is not None and stats.trace_ctx is not None
    spans = stats.trace_ctx.recorder.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for name, lane in (("device:submit", "dev:upload"),
                       ("device:inflight", "dev:compute"),
                       ("device:drain", "dev:download")):
        assert by_name.get(name), f"missing {name} spans"
        for s in by_name[name]:
            assert s["lane"] == lane
            assert "slot" in s.get("attrs", {})
    tracing.reset_for_tests()


def test_span_ids_deterministic_under_chaos_serialize(monkeypatch):
    """r13 discipline: under DAFT_TPU_CHAOS_SERIALIZE=1 (which degrades
    the pipeline to the synchronous path) two identical runs replay
    bit-identical span id sets."""
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")

    def one_run():
        tracing.reset_for_tests()
        _run(_q1_shape())
        stats = obs.last_query_stats()
        assert stats is not None and stats.trace_ctx is not None
        return stats.trace_ctx.recorder.span_ids()

    ids1 = one_run()
    ids2 = one_run()
    assert sorted(ids1) == sorted(ids2)
    tracing.reset_for_tests()


def test_overlap_recorded_in_mfu_ledger(monkeypatch, pq_dir):
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    before = cm.ledger_snapshot(raw=True)
    _run(_q1_scan(pq_dir))
    delta = cm.ledger_delta(before, cm.ledger_snapshot(raw=True))
    assert "pipeline" in delta, delta
    row = delta["pipeline"]
    assert row["dispatches"] >= 1
    assert row["serial_equiv_s"] > 0
    assert row["overlap_x"] > 0


# ------------------------------------------- single-transfer downloads

def test_decode_table_is_one_device_get(monkeypatch):
    import jax
    from daft_tpu.recordbatch import RecordBatch
    batch = RecordBatch.from_pydict({
        "a": np.arange(100, dtype=np.int64),
        "b": np.arange(100, dtype=np.float64),
        "c": np.arange(100) % 2 == 0})
    dt = dcol.encode_batch(batch)
    calls = []
    real = jax.device_get

    def counting(tree):
        calls.append(1)
        return real(tree)

    monkeypatch.setattr(jax, "device_get", counting)
    out = dcol.decode_table(dt)
    assert len(calls) == 1, f"{len(calls)} device_get calls for 3 columns"
    assert out.to_pydict()["a"] == list(range(100))


def test_decode_column_batches_data_and_validity(monkeypatch):
    import jax
    from daft_tpu.series import Series
    s = Series.from_numpy(np.arange(64, dtype=np.int64), "x")
    c = dcol.encode_series(s, 64)
    calls = []
    real = jax.device_get

    def counting(tree):
        calls.append(1)
        return real(tree)

    monkeypatch.setattr(jax, "device_get", counting)
    out = dcol.decode_column("x", c, 64)
    assert len(calls) == 1
    assert out.to_pylist() == list(range(64))


# ------------------------------------------- device-resident hand-off

def test_residency_reuse_skips_reencode(monkeypatch):
    """A decoded device column re-entering the device (projection →
    argsort / agg) hits the residency registry instead of re-uploading;
    reused validity is masked to the live rows."""
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    from daft_tpu.recordbatch import RecordBatch
    batch = RecordBatch.from_pydict({
        "a": np.arange(128, dtype=np.int64),
        "b": np.arange(128, dtype=np.float64)})
    dt = dcol.encode_batch(batch)
    decoded = dcol.decode_table(dt)  # registers planes (window > 0)
    assert dpipe.residency_counters()["entries"] == 2
    dt2 = dcol.encode_batch(decoded)
    assert dpipe.residency_counters()["hits"] >= 2
    assert dt2.resident, "reused planes must be donation-protected"
    from daft_tpu.device.fragment import _donation_ok
    assert not _donation_ok(dt2)
    # round-trip stays bit-identical
    assert dcol.decode_table(dt2).to_pydict() == decoded.to_pydict()


def test_residency_masks_garbage_validity_beyond_live_rows(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    import jax.numpy as jnp
    from daft_tpu.series import Series
    s = Series.from_numpy(np.arange(5, dtype=np.int64), "x")
    # capacity-16 planes whose validity beyond the 5 live rows is
    # GARBAGE-true (a kernel output tail)
    data = jnp.arange(16, dtype=jnp.int64)
    validity = jnp.ones(16, dtype=jnp.bool_)
    dpipe.note_decoded(s, data, validity, None, count=5, capacity=16)
    hit = dpipe.resident_planes(s, 5)
    assert hit is not None
    _, masked, _, cap = hit
    assert cap == 16
    host = np.asarray(masked)
    assert host[:5].all() and not host[5:].any()


def test_residency_skipped_when_pipeline_disabled(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "0")
    from daft_tpu.recordbatch import RecordBatch
    batch = RecordBatch.from_pydict({"a": np.arange(32, dtype=np.int64)})
    dcol.decode_table(dcol.encode_batch(batch))
    assert dpipe.residency_counters()["entries"] == 0


def test_residency_lookup_disabled_under_chaos_serialize(monkeypatch):
    """Planes registered BEFORE degradation must not serve reuse hits
    once chaos-serialize forces the verbatim synchronous chain — a hit
    would skip the upload events the replay contract expects."""
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    import jax.numpy as jnp
    from daft_tpu.series import Series
    s = Series.from_numpy(np.arange(16, dtype=np.int64), "x")
    dpipe.note_decoded(s, jnp.arange(16, dtype=jnp.int64),
                       jnp.ones(16, dtype=jnp.bool_), None, 16, 16)
    assert dpipe.resident_planes(s, 16) is not None
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    assert dpipe.resident_planes(s, 16) is None


def test_residency_registry_is_byte_bounded(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", "2")
    monkeypatch.setenv("DAFT_TPU_HBM_CACHE_BYTES", "8192")  # budget = 1KiB
    import jax.numpy as jnp
    from daft_tpu.series import Series
    kept = []
    for i in range(8):
        s = Series.from_numpy(np.arange(16, dtype=np.int64), f"c{i}")
        kept.append(s)
        dpipe.note_decoded(s, jnp.arange(16, dtype=jnp.int64),
                           jnp.ones(16, dtype=jnp.bool_), None, 16, 16)
    c = dpipe.residency_counters()
    assert c["bytes"] <= 1024
    assert c["evictions"] > 0


# ------------------------------------------------- overlap-aware pricing

def test_pipelined_seconds_never_exceeds_serial():
    lp = cm.LinkProfile(rtt_s=0.04, up_bps=40e6, down_bps=40e6)
    serial = lp.device_seconds(8e6, 1e5, 2.0, 0.01)
    piped = lp.pipelined_seconds(8e6, 1e5, 2.0, 0.01)
    assert piped < serial
    assert piped >= max(8e6 / 40e6, 0.01)  # bottleneck stage survives


def test_agg_upload_overlap_pricing_admits_more(monkeypatch):
    """A transfer-bound upload the serial model declines is admitted
    once the pipeline hides the wire behind device compute."""
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "100")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "40")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "40")
    cm.reset_for_tests()
    try:
        # serial: 0.2 s wire + 0.2 s RTTs + kernel ≈ 0.41 s vs a 0.35 s
        # host pass → declines; pipelined: max(wire, kernel) + 1 RTT
        # ≈ 0.30 s → accepts
        up, down, host_b = 8e6, 1e4, 105e6
        assert not cm.agg_upload_wins(up, down, cacheable=False,
                                      host_bytes=host_b)
        assert cm.agg_upload_wins(up, down, cacheable=False,
                                  host_bytes=host_b, window=2)
    finally:
        cm.reset_for_tests()


def test_join_overlap_pricing_admits_more(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "40")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "40")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "40")
    # a fused join faster than the host's: the rates the chip showed
    # (PR 38: 2e6 against 10e6 rows/s) leave no overlap to discount
    monkeypatch.setattr(cm, "HOST_JOIN_ROWS_PER_S", 25.0e6)
    monkeypatch.setattr(cm, "DEV_JOIN_ROWS_PER_S", 40.0e6)
    cm.reset_for_tests()
    try:
        # host ≈ 0.32 s; serial device ≈ 0.49 s (declines); pipelined
        # ≈ 0.29 s (wire and kernel overlap neighbors → accepts)
        n_l = n_r = 4_000_000
        up, down = 5e6, 5e6
        assert not cm.join_wins(n_l, n_r, up, down)
        assert cm.join_wins(n_l, n_r, up, down, window=2)
    finally:
        cm.reset_for_tests()


# ------------------------------------- a window decodes as one record batch

def _ref_decode_global(prog, packed, agg_fields):
    """The per-table decoder the lane decoder replaced (PR 37), kept as
    the reference: one Series a lane a table."""
    from daft_tpu.device import fragment, runtime as drt
    from daft_tpu.recordbatch import RecordBatch
    dtypes = prog.meta["global_dtypes"]
    nv = len(agg_fields)
    cols = []
    for i, f in enumerate(agg_fields):
        v = fragment._unpack_i64(packed[i:i + 1], dtypes[i])
        m = fragment._unpack_i64(packed[nv + i:nv + i + 1], dtypes[nv + i])
        cols.append(drt._decode_scalar(f.name, f.dtype, v,
                                       m.astype(np.bool_)))
    return RecordBatch.from_series(cols)


def _ref_decode_grouped(prog, packed, dt, group_exprs, key_fields,
                        agg_fields):
    """As above, for a packed group block; None when it overflowed."""
    from daft_tpu.device import fragment, runtime as drt
    from daft_tpu.recordbatch import RecordBatch
    g = int(packed[0, 0])
    out_cap = packed.shape[1]
    if g > out_cap and out_cap < dt.capacity:
        return None
    dtypes = prog.meta["grouped_dtypes"]
    nk, nv = prog.nk, len(agg_fields)
    rows = packed[1:]
    cols = []
    for i, (e, f) in enumerate(zip(group_exprs, key_fields)):
        kv = fragment._unpack_i64(rows[i][:g], dtypes[i])
        km = fragment._unpack_i64(rows[nk + i][:g],
                                  dtypes[nk + i]).astype(np.bool_)
        cols.append(drt.decode_group_key(e, f, kv, km, dt, g))
    for i, f in enumerate(agg_fields):
        vv = fragment._unpack_i64(rows[2 * nk + i][:g], dtypes[2 * nk + i])
        vm = fragment._unpack_i64(rows[2 * nk + nv + i][:g],
                                  dtypes[2 * nk + nv + i]).astype(np.bool_)
        dc = dcol.DeviceColumn(vv, vm, f.dtype, None)
        cols.append(dcol.decode_column(f.name, dc, g))
    return RecordBatch.from_series(cols)


def _ref_table(prog, dt, tok, cap_limit):
    """One table through the ladder alone, decoded per table: its first
    block as the window dispatched it, re-run once at the grown bucket if
    it overflowed; None past ``cap_limit`` (host fallback)."""
    from daft_tpu.device import fragment
    if prog.nk == 0:
        packed = np.asarray(fragment._dispatch_packed(
            prog, dt, fragment._OUT_CAP0))
        return _ref_decode_global(prog, packed, tok.agg_fields)
    plan = fragment.dense_plan(prog, dt, fragment._max_out_cap(prog, dt))
    packed = np.asarray(
        fragment._dispatch_packed(prog, dt, plan[1], "dense", dims=plan[0])
        if tok.strategy == "dense" else
        fragment._dispatch_packed(prog, dt, fragment._OUT_CAP0, "sort"))
    args = (tok.group_exprs, tok.key_fields, tok.agg_fields)
    out = _ref_decode_grouped(prog, packed, dt, *args)
    if out is not None:
        return out
    g = int(packed[0, 0])
    if g > cap_limit:
        return None
    cap = min(dcol.bucket_capacity(max(g, fragment._OUT_CAP0)), cap_limit)
    return _ref_decode_grouped(
        prog, np.asarray(fragment._dispatch_packed(prog, dt, cap, "sort")),
        dt, *args)


def _bits(series):
    """A column as (arrow type, validity, value bits)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    arr = series.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid = pc.is_valid(arr).to_pylist()
    if pa.types.is_floating(arr.type):
        vals = np.asarray(pc.fill_null(arr, 0.0)).view(
            np.uint64 if arr.type == pa.float64() else np.uint32).tolist()
    else:
        vals = arr.to_pylist()
    return arr.type, valid, vals


def _keyed(keys, n, seed, null_every=0):
    rng = np.random.default_rng(seed)
    out = [keys[i] for i in rng.integers(0, len(keys), n)]
    if null_every:
        out[::null_every] = [None] * len(out[::null_every])
    return out


def _window_tables(case):
    """(tables as pydicts, group keys, aggs, predicate, strategy, the
    window's runs as table counts, None where the host answers)."""
    rng = np.random.default_rng(5)

    def vals(n, null_every=0):
        v = (rng.random(n) * 100).tolist()
        if null_every:
            v[::null_every] = [None] * len(v[::null_every])
        return v

    q1_aggs = [col("qty").sum().alias("sum_qty"),
               col("price").sum().alias("sum_price"),
               (col("price") * (1 - col("disc"))).sum().alias("sum_disc"),
               col("qty").count().alias("n_qty"),
               col("price").min().alias("min_price"),
               col("disc").max().alias("max_disc")]
    if case == "q1_dense_6_tables":
        # every file holds all of A/N/R and F/O: equal dictionaries,
        # each table's own object
        tables = [{"flag": _keyed("ANR", 600, s) + list("ANR"),
                   "status": _keyed("FO", 600, 10 + s) + list("FOF"),
                   "qty": vals(603), "price": vals(603),
                   "disc": vals(603)} for s in range(6)]
        return tables, ["flag", "status"], q1_aggs, None, "dense", [6]
    if case == "sort_different_groups_a_table":
        tables = [{"k": rng.integers(0, ndv, 500).tolist(),
                   "qty": vals(500), "price": vals(500), "disc": vals(500)}
                  for ndv in (3, 40, 1, 117, 9)]
        return tables, ["k"], q1_aggs, None, "sort", [5]
    if case == "overflow_retried":
        # tables 1 and 3 outgrow the 128-group bucket: re-run together,
        # decoded as one batch, each back in its place
        tables = [{"k": (np.arange(900) % ndv).tolist(),
                   "qty": vals(900), "price": vals(900), "disc": vals(900)}
                  for ndv in (5, 300, 7, 200, 2)]
        return tables, ["k"], q1_aggs, None, "sort", [1, 1, 1, 1, 1]
    if case == "q6_global":
        tables = [{"qty": vals(400), "price": vals(400),
                   "disc": (rng.random(400) * 0.1).tolist()}
                  for _ in range(5)]
        aggs = [(col("price") * col("disc")).sum().alias("revenue"),
                col("qty").count().alias("n")]
        return tables, [], aggs, col("qty") < 24, "sort", [5]
    if case == "null_keys_and_null_aggregates":
        tables = [{"flag": _keyed("ANR", 300, s, null_every=7),
                   "status": _keyed("FO", 300, 20 + s),
                   "qty": vals(300, null_every=3),
                   # all of a table's prices NULL: every group's sum is
                   "price": [None] * 300 if s == 1 else vals(300),
                   "disc": vals(300)} for s in range(4)]
        return tables, ["flag", "status"], q1_aggs, None, "dense", [4]
    if case == "different_dictionaries":
        # codes 0..2 mean other strings in each table: tables 1 and 2
        # share a dictionary, 0 and 3 have their own
        tables = [{"flag": _keyed(keys, 300, s) + list(keys),
                   "qty": vals(303), "price": vals(303), "disc": vals(303)}
                  for s, keys in enumerate(("ABC", "BCD", "BCD", "XYZ"))]
        return tables, ["flag"], q1_aggs, None, "dense", [4]
    if case == "failed_table_in_the_middle":
        # table 2 holds more groups than the link-budgeted ceiling (held
        # to 128 by the test): the host answers it, the run is split
        tables = [{"k": (np.arange(900) % ndv).tolist(),
                   "qty": vals(900), "price": vals(900), "disc": vals(900)}
                  for ndv in (5, 9, 300, 7, 2)]
        return tables, ["k"], q1_aggs, None, "sort", [2, None, 2]
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "q1_dense_6_tables", "sort_different_groups_a_table",
    "overflow_retried", "q6_global", "null_keys_and_null_aggregates",
    "different_dictionaries", "failed_table_in_the_middle"])
def test_window_decodes_as_one_batch_bit_identical(monkeypatch, case):
    """The lane decoder (one unpack, one mask, one Arrow array a lane a
    WINDOW) against the per-table decoder it replaced: the window's runs,
    laid end to end, are the per-table batches laid end to end, column
    by column and bit for bit, in task order."""
    from daft_tpu.aggs import split_agg_expr
    from daft_tpu.device import fragment
    from daft_tpu.recordbatch import RecordBatch
    data, keys, aggs, pred, strategy, want_runs = _window_tables(case)
    cap_limit = 1 << 20
    if case == "failed_table_in_the_middle":
        cap_limit = fragment._OUT_CAP0
        monkeypatch.setattr(fragment, "_max_out_cap",
                            lambda prog, dt: cap_limit)
    rbs = [RecordBatch.from_pydict(d) for d in data]
    gexprs = [col(k) for k in keys]
    specs = [split_agg_expr(a) for a in aggs]
    prog = fragment.get_fused_agg(
        gexprs, [s[1].alias(f"__v{i}__") for i, s in enumerate(specs)],
        tuple(s[0] for s in specs), pred, rbs[0].schema)
    assert prog is not None
    out_schema = (rbs[0].filter(pred) if pred is not None else rbs[0]) \
        .agg(aggs, gexprs).schema
    tables = [dcol.encode_batch(rb, prog.compiled.needs_cols) for rb in rbs]
    tok = fragment.submit_fused_agg_tables(
        prog, tables, rbs[0].schema, gexprs, [col(s[2]) for s in specs],
        out_schema)
    assert tok.strategy == strategy and not tok.failed
    runs = fragment.drain_fused_agg_tables(tok)
    refs = [_ref_table(prog, dt, tok, cap_limit) for dt in tables]

    assert [r.tables if r.batch is not None else None for r in runs] \
        == want_runs
    at = 0
    for run in runs:
        ref = refs[at:at + run.tables]
        at += run.tables
        if run.batch is None:
            assert ref == [None]    # the fallback keeps its place
            continue
        want = RecordBatch.concat(ref)
        assert run.batch.column_names() == want.column_names()
        assert len(run.batch) == len(want)
        for got_c, want_c in zip(run.batch.columns(), want.columns()):
            assert got_c.datatype() == want_c.datatype(), got_c.name()
            assert _bits(got_c) == _bits(want_c), got_c.name()
    assert at == len(tables)


def test_a_host_task_between_device_tables_splits_the_run():
    """`places`: tables whose tasks are not neighbours (the host answers
    one between them) never share a run, so the executor can yield in
    task order."""
    from daft_tpu.device import fragment
    from daft_tpu.recordbatch import RecordBatch
    rb = RecordBatch.from_pydict({"x": list(range(10))})
    pieces = [(rb, 0, 2), (rb, 2, 5), (rb, 5, 6), None, (rb, 6, 10)]
    runs = fragment._runs(pieces, [0, 1, 3, 4, 5])
    assert [(r.tables, None if r.batch is None else r.batch.to_pydict()["x"])
            for r in runs] == [(2, [0, 1, 2, 3, 4]), (1, [5]), (1, None),
                               (1, [6, 7, 8, 9])]
    whole = fragment._runs(pieces[:3] + pieces[4:], [0, 1, 2, 3])
    assert len(whole) == 1 and whole[0].batch is rb   # no slice, no copy


@pytest.mark.parametrize("inflight", ["2", "0"])
def test_q1_over_16_files_decodes_a_batch_a_window(monkeypatch, tmp_path,
                                                   inflight):
    """The normal path: `read_parquet` over 16 files -> Q1. The answer is
    the host tier's; the 16 packed results are decoded as one batch a
    window (6 + 6 + 4 tables under the default in-flight window of 2 on
    one chip, 8 + 8 over the suite's eight; the synchronous loop's one
    wide window under 0) and the fragment yields one partition a batch,
    not one a table."""
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    from daft_tpu.execution.executor import LocalExecutor
    for i in range(16):
        rng = np.random.default_rng(100 + i)
        n = 500
        pq.write_table(
            pa.table({"flag": _keyed("ANR", n, i) + list("ANR"),
                      "status": _keyed("FO", n, 50 + i) + list("FOF"),
                      "qty": np.append(rng.random(n) * 50, [1., 2., 3.]),
                      "price": np.append(rng.random(n) * 1000,
                                         [1., 2., 3.])}),
            str(tmp_path / f"part{i:02d}.parquet"))

    def q1():
        return (daft.read_parquet(f"{tmp_path}/*.parquet")
                .groupby("flag", "status")
                .agg(col("qty").sum().alias("sum_qty"),
                     col("price").mean().alias("avg_price"),
                     col("qty").count().alias("cnt"))
                .sort([col("flag"), col("status")]))

    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    host = _run(q1())
    yielded = []
    real = LocalExecutor._exec_DeviceFragmentAgg

    def counting(self, node):
        for mp in real(self, node):
            yielded.append(len(mp))
            yield mp

    monkeypatch.setattr(LocalExecutor, "_exec_DeviceFragmentAgg", counting)
    monkeypatch.setenv("DAFT_TPU_DEVICE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_FORCE", "1")
    monkeypatch.setenv("DAFT_TPU_DEVICE_INFLIGHT", inflight)
    monkeypatch.setenv("DAFT_TPU_TRACE", "1")
    tracing.reset_for_tests()
    dev = _run(q1())
    summary = tracing.finished()[-1]
    tracing.reset_for_tests()

    assert dev["flag"] == host["flag"] and dev["status"] == host["status"]
    assert dev["cnt"] == host["cnt"] and len(dev["flag"]) == 6
    for name in ("sum_qty", "avg_price"):
        assert dev[name] == pytest.approx(host[name], rel=1e-9)
    # the executor's window: cores x 2 wide, cut so that the in-flight
    # window + 1 windows cover the scan, then rounded up to a multiple of
    # the chips the tables are spread over (PR 44: no ragged round)
    from daft_tpu.parallel import mesh as pmesh
    chips = max(len(pmesh.scan_devices()), 1)
    width = max(os.cpu_count() or 4, 4) * 2
    if inflight == "2":
        width = min(width, -(-16 // 3))
    windows = -(-16 // (-(-width // chips) * chips))
    assert summary["decode"] == {"tables": 16, "batches": windows}
    assert summary["tables"]["host"] == 0
    assert len(yielded) == windows and sum(yielded) == 16 * 6
    decode = summary["phases"]["device:decode"]
    assert decode["count"] == windows


# ------------------------------------------ waits as spans (PR 43)

def _traced():
    rec = tracing.SpanRecorder("d" * 32)
    return rec, tracing.SpanContext(rec, rec.root_id)


def test_a_window_gate_held_shut_is_a_wait_window_span():
    """A slot beyond the window waits at the gate until the consumer
    drains the head: at least the 50 ms the head is held here."""
    import threading
    import time
    rec, ctx = _traced()
    gate = dpipe.WindowGate(1)
    head = dpipe.acquire_slot(gate, 0)       # untraced: the window is full
    got = []

    def second():
        with tracing.attach(ctx):
            got.append(dpipe.acquire_slot(gate, 1,
                                          MemoryManager(budget=1 << 20), 64))

    t = threading.Thread(target=second)
    t.start()
    time.sleep(0.05)
    dpipe.release_slot(head)
    gate.note_drained(0)
    t.join(5)
    assert got and got[0].nbytes == 64
    (w,) = [s for s in rec.spans() if s["name"] == "wait:window"]
    assert w["dur_us"] >= 50_000 and w["lane"] == "wait"
    assert w["attrs"] == {"seq": 1, "admitted": True}
    assert "cpu_us" not in w        # a wait reads no CPU clock (CPU_SPANS)
    dpipe.release_slot(got[0])
    # the head's own, untraced, left nothing; a wait under the floor is
    # counted, not stored
    with tracing.attach(ctx):
        dpipe.release_slot(dpipe.acquire_slot(gate, 2))
    assert len([s for s in rec.spans() if s["name"] == "wait:window"]) == 1
    assert rec._tallies["waits_short"] == 1


def test_the_consumers_wait_on_the_head_is_a_wait_result_span():
    """``run_pipelined``'s consumer blocks in ``fut.result()`` while the
    pool runs the head submit (30 ms here); the hand-off is from the
    worker's last act to the consumer running."""
    import time
    rec, ctx = _traced()

    def submit(item, seq, gate):
        time.sleep(0.03)
        return item                       # host-routed: holds no slot

    with tracing.attach(ctx):
        out = list(dpipe.run_pipelined(range(3), submit,
                                       lambda ret, seq: ret, window=2))
    assert out == [0, 1, 2]
    waits = [s for s in rec.spans() if s["name"] == "wait:result"]
    assert waits and waits[0]["attrs"]["seq"] == 0
    assert waits[0]["dur_us"] >= 25_000
    # the tail is what came after the worker was done, not its 30 ms
    assert 0 <= waits[0]["attrs"]["tail_us"] <= waits[0]["dur_us"] - 20_000
    assert [s["attrs"]["pool"] for s in rec.spans()
            if s["name"] == "wait:pool"] in ([], ["devpipe"] * 1,
                                             ["devpipe"] * 2,
                                             ["devpipe"] * 3)
    rec.finish()
    # three submits started on the pool + three results handed over
    assert rec.summary()["handoffs"]["count"] == 6


def test_untraced_pipeline_makes_no_stamp_and_no_span(monkeypatch):
    import time
    monkeypatch.setattr(time, "thread_time_ns", lambda: 1 / 0)
    tracing.reset_for_tests()
    seen = []

    def submit(item, seq, gate):
        seen.append(obs.current_attribution())
        slot = dpipe.acquire_slot(gate, seq)
        return dpipe.InflightItem(slot, item)

    assert tracing.current() is None
    out = list(dpipe.run_pipelined(range(4), submit,
                                   lambda ret, seq: ret.token, window=2))
    assert out == [0, 1, 2, 3] and seen == [None] * 4
    assert tracing.finished() == []
