"""Dispatch cost model: measured-link decisions, bounded investment,
persisted link profile, decision logging.

Reference seam: the per-operator dispatch decision the reference makes
implicitly by construction (CUDA ops run where the data lives); here the
slow-link/fast-link split forces an explicit model (SURVEY.md §7 hard-part
#2, ``daft_tpu/device/costmodel.py``)."""

import json
import os

import pytest

from daft_tpu.device import costmodel as cm


@pytest.fixture
def slow_link(monkeypatch):
    """A slow link: 10 MB/s, 80 ms RTT."""
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "80")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "10")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "10")
    cm.reset_for_tests()
    yield
    cm.reset_for_tests()


@pytest.fixture
def fast_link(monkeypatch):
    """A fast(er) link: ~100 MB/s, 40 ms RTT."""
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "40")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "100")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "100")
    cm.reset_for_tests()
    yield
    cm.reset_for_tests()


def test_invest_refused_on_slow_link(slow_link):
    """A 210 MB cache fill at 10 MB/s is ~21 s against a ~1.1 s host pass
    (ratio ~19): no workload re-runs the scan 19 times, so the bounded
    investment rule must refuse (r4's 64× bound let these through and
    one-shot suites never amortized them)."""
    assert not cm.agg_upload_wins(
        bytes_up=210e6, bytes_down=1e5, cacheable=True,
        host_bytes=336e6)


def test_invest_accepted_on_fast_link(fast_link):
    """Same fill at 100 MB/s is ~2 s (ratio ~2): residency repays within
    a couple of queries — invest."""
    assert cm.agg_upload_wins(
        bytes_up=210e6, bytes_down=1e5, cacheable=True,
        host_bytes=336e6)


def test_noncacheable_upload_must_beat_host_outright(fast_link):
    # 210MB upload at 100MB/s = 2.1s vs 1.1s host pass: refuse
    assert not cm.agg_upload_wins(
        bytes_up=210e6, bytes_down=1e5, cacheable=False, host_bytes=336e6)


def test_rtt_bound_tiny_aggregates_stay_host(slow_link):
    """TPC-H Q22 shape: tiny per-task aggregates are RTT-bound even when
    resident — the resident-pays check must refuse investment."""
    assert not cm.agg_upload_wins(
        bytes_up=2e5, bytes_down=1e5, cacheable=True,
        round_trips=2.0, host_bytes=3e5)


def test_host_bytes_defaults_to_bytes_up(fast_link):
    a = cm.agg_upload_wins(1e6, 1e4, cacheable=False)
    b = cm.agg_upload_wins(1e6, 1e4, cacheable=False, host_bytes=1e6)
    assert a == b


def test_decision_counts_and_jsonl_log(tmp_path, slow_link, monkeypatch):
    log = tmp_path / "dispatch.jsonl"
    monkeypatch.setenv("DAFT_TPU_DISPATCH_LOG", str(log))
    cm.row_output_op_wins(1e6, 1e6)
    cm.agg_upload_wins(1e6, 1e4, cacheable=True, host_bytes=1e6)
    cm.join_wins(1000, 1000, 1e5, 1e5)
    assert cm.decision_counts["row_output"]["host"] == 1
    recs = [json.loads(x) for x in log.read_text().splitlines()]
    assert [r["kind"] for r in recs] == \
        ["row_output", "agg_upload_invest", "join"]
    assert all({"device", "host_s", "dev_s"} <= set(r) for r in recs)


def test_link_profile_persistence_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("DAFT_TPU_LINK_CACHE_PATH",
                       str(tmp_path / "link.json"))
    p = cm.LinkProfile(rtt_s=0.05, up_bps=2e7, down_bps=1e7)
    cm._store("tpu", p)
    got, age = cm._load_stored("tpu")
    assert got == p and age is not None and age < 5
    # backend mismatch → miss
    assert cm._load_stored("other") == (None, None)


def test_link_profile_cpu_is_shared_memory(monkeypatch):
    for k in ("DAFT_TPU_LINK_RTT_MS", "DAFT_TPU_LINK_UP_MBPS",
              "DAFT_TPU_LINK_DOWN_MBPS"):
        monkeypatch.delenv(k, raising=False)
    cm.reset_for_tests()
    lp = cm.link_profile()  # tests run on the CPU backend
    assert lp.rtt_s == 0.0 and lp.up_bps == float("inf")
    cm.reset_for_tests()


def test_encoded_nbytes_compacts_f64():
    import daft_tpu as dt
    from daft_tpu.device import column as dcol
    from daft_tpu.recordbatch import RecordBatch
    rb = RecordBatch.from_pydict({
        "f": [1.0] * 1000, "s": ["ab"] * 1000, "i": [1] * 1000})
    enc = dcol.encoded_nbytes(rb, ["f", "s", "i"])
    cap = dcol.bucket_capacity(1000)
    # f64→f32 (4) on f64-less chips or 8 locally; strings→codes (4);
    # i64 stays 8; +1 validity each
    f_item = 4 if not dcol.supports_f64() else 8
    assert enc == cap * ((f_item + 1) + (4 + 1) + (8 + 1))


@pytest.mark.parametrize("peaks", [None, "TPU v5 lite"],
                         ids=["cpu-no-peaks", "v5e-peaks"])
def test_mfu_report_shape(peaks, monkeypatch):
    """Kernel-efficiency report: correct families/fields on any backend
    (values are only meaningful on a real chip). Shares of peak appear
    ONLY when the attached chip is in ``costmodel.DEVICE_PEAKS``: the CPU
    has no peaks, so no ``*_pct`` field is printed for it."""
    from daft_tpu.device import mfu
    if peaks is not None:
        monkeypatch.setattr(cm, "device_peaks",
                            lambda: cm.DEVICE_PEAKS[peaks])
    r = mfu.report(n=1 << 12)
    assert "error" not in r, r
    if peaks is None:
        assert r["peak_flops"] is None and r["hbm_bps"] is None
        assert not any(k.endswith("_pct") for fam in r.values()
                       if isinstance(fam, dict) for k in fam)
        r["join"]["roofline_pct"] = 0.0   # shape check below
    else:
        assert r["peak_flops"] == 197e12 and r["hbm_bps"] == 819e9
        assert r["grouped_agg"]["mfu_pct"] >= 0
    # rounded fields can floor to 0.0 on a slow CPU — assert the raw
    # inputs instead
    assert r["grouped_agg"]["time_s"] > 0 and r["grouped_agg"]["flops"] > 0
    assert r["join"]["bytes"] > 0 and r["join"]["time_s"] > 0
    assert r["argsort"]["bytes"] > 0 and r["argsort"]["time_s"] > 0
    assert {"roofline_pct", "time_s", "achieved_gbps"} <= set(r["join"])
    assert r["grouped_agg"]["flops"] == 2.0 * (1 << 12) * 256 * 3


def test_image_resize_gate(slow_link, monkeypatch):
    # 50MB batch over a 10MB/s link (~5s) vs PIL (~0.6s): host keeps it
    assert not cm.image_resize_wins(50e6, 12.5e6)


def test_image_resize_gate_local_chip(monkeypatch):
    # shared-memory link: the batched device resize wins by orders of mag
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "0.01")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "50000")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "50000")
    cm.reset_for_tests()
    try:
        assert cm.image_resize_wins(50e6, 12.5e6)
    finally:
        cm.reset_for_tests()
