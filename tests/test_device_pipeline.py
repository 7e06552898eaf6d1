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
