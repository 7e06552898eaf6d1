"""Self-tuning engine (round 20): the calibrated cost-model profile
(``device/calibration.py``) and distributed runtime re-planning
(``distributed/replan.py`` + the StageRunner wiring) — EWMA/floor/
persistence, constants-override plumbing, re-plan decision picks
(broadcast demotion, combine flips on mis-estimated NDV, estimate
rewrites), the ``adaptive`` stats block + ``/metrics``, serving
admission seeding from per-fingerprint history, the AdaptivePlanner
history bound, knob-off verbatim-static parity, and the extended
chaos-determinism contract (feedback state frozen, replay
bit-identical)."""

import os

import numpy as np
import pytest

import daft_tpu as dt
import daft_tpu.context as dctx
from daft_tpu import col
from daft_tpu.device import calibration as cal
from daft_tpu.device import costmodel
from daft_tpu.distributed import replan
from daft_tpu.distributed import resilience as rz
from daft_tpu.physical import adaptive
from daft_tpu.runners.distributed_runner import DistributedRunner


@pytest.fixture(autouse=True)
def _fresh_feedback_state():
    cal.reset_for_tests()
    adaptive.counters_reset()
    # pin the config mirrors to their defaults: the process-global
    # context may have been created while another test's env was set,
    # baking tpu_calibration/tpu_adaptive=True into it
    with dctx.execution_config_ctx(tpu_calibration=False,
                                   tpu_adaptive=False,
                                   tpu_calibration_dir=""):
        yield
    cal.reset_for_tests()
    adaptive.counters_reset()


def _run_distributed(q, num_workers=3):
    runner = DistributedRunner(num_workers=num_workers)
    old = dctx.get_context()._runner
    dctx.get_context().set_runner(runner)
    try:
        return q()
    finally:
        dctx.get_context().set_runner(old)
        if runner._manager is not None:
            runner._manager.shutdown()


# ------------------------------------------------------- calibration (a)

def test_ewma_update_and_sample_floor(monkeypatch, tmp_path):
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "3")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_ALPHA", "0.5")
    # below the floor: the default wins
    cal.observe("DEV_AGG_BPS", 1e9)
    cal.observe("DEV_AGG_BPS", 1e9)
    assert cal.const("DEV_AGG_BPS", 4e9) == 4e9
    cal.observe("DEV_AGG_BPS", 2e9)
    got = cal.const("DEV_AGG_BPS", 4e9)
    assert got != 4e9
    # EWMA with alpha 0.5: 1e9 -> 1e9 -> 1.5e9
    assert got == pytest.approx(1.5e9)
    s = cal.summary()["DEV_AGG_BPS"]
    assert s["active"] and s["samples"] == 3


def test_disabled_by_default_and_observe_noop():
    cal.observe("DEV_AGG_BPS", 1e9)
    assert not cal.enabled()
    assert cal.const("DEV_AGG_BPS", 4e9) == 4e9
    assert cal.summary()["DEV_AGG_BPS"]["samples"] == 0


def test_persistence_roundtrip(monkeypatch, tmp_path):
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_DIR", str(tmp_path))
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "2")
    cal.observe("DEV_SORT_ROWS_PER_S", 9e6)
    cal.observe("DEV_SORT_ROWS_PER_S", 9e6)
    cal.flush()  # the atexit hook's path, invoked deterministically
    files = os.listdir(str(tmp_path))
    assert any(f.startswith("calibration_") and f.endswith(".json")
               for f in files), files
    learned = cal.const("DEV_SORT_ROWS_PER_S", 50e6)
    assert learned == pytest.approx(9e6)
    # a fresh process (reset) reloads the persisted per-backend profile
    cal.reset_for_tests()
    assert cal.const("DEV_SORT_ROWS_PER_S", 50e6) == pytest.approx(9e6)


def test_chaos_serialize_freezes_calibration(monkeypatch, tmp_path):
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
    cal.observe("DEV_AGG_BPS", 1e9)
    assert cal.const("DEV_AGG_BPS", 4e9) == pytest.approx(1e9)
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    assert cal.frozen()
    # reads return defaults, observations are dropped
    assert cal.const("DEV_AGG_BPS", 4e9) == 4e9
    cal.observe("DEV_AGG_BPS", 2e9)
    monkeypatch.delenv("DAFT_TPU_CHAOS_SERIALIZE")
    assert cal.summary()["DEV_AGG_BPS"]["samples"] == 1


def test_active_fault_plan_freezes_calibration(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_FAULT_SPEC", "task:0.1")
    rz.reset_for_tests()
    try:
        assert cal.frozen()
        cal.observe("DEV_AGG_BPS", 1e9)
        assert cal.summary()["DEV_AGG_BPS"]["samples"] == 0
    finally:
        rz.reset_for_tests()


def test_ledger_record_feeds_observations(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
    # dense and sort dispatches calibrate the one rate the gate prices
    for n, strategy in enumerate(("dense", "sort"), 1):
        costmodel.ledger_record("grouped_agg", rows=1 << 16,
                                nbytes=1 << 24, seconds=0.1,
                                strategy=strategy)
        assert cal.summary()["DEV_AGG_BPS"]["samples"] == n
    assert cal.const("DEV_AGG_BPS", 0.0) > 0
    costmodel.ledger_record("argsort", rows=1 << 16, nbytes=1 << 20,
                            seconds=0.05)
    assert cal.const("DEV_SORT_ROWS_PER_S", 0.0) > 0
    # tiny dispatches (RTT-dominated) are skipped
    before = cal.summary()["DEV_SORT_ROWS_PER_S"]["samples"]
    costmodel.ledger_record("argsort", rows=16, nbytes=128, seconds=0.01)
    assert cal.summary()["DEV_SORT_ROWS_PER_S"]["samples"] == before


def test_constants_override_changes_decision(monkeypatch):
    """The override plumbing end to end: a calibrated (much slower)
    device agg rate flips ``agg_upload_wins`` for a borderline dispatch
    that the hard-coded constants accept."""
    monkeypatch.setenv("DAFT_TPU_LINK_RTT_MS", "1")
    monkeypatch.setenv("DAFT_TPU_LINK_UP_MBPS", "1000")
    monkeypatch.setenv("DAFT_TPU_LINK_DOWN_MBPS", "1000")
    costmodel.reset_for_tests()
    try:
        nbytes = 64 << 20
        default_dec = costmodel.agg_upload_wins(nbytes, 1 << 10,
                                                cacheable=False)
        assert default_dec  # fast link + fast kernel: device wins
        monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
        monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
        cal.observe("DEV_AGG_BPS", 1e6)  # observed: kernel is terrible
        assert not costmodel.agg_upload_wins(nbytes, 1 << 10,
                                             cacheable=False)
    finally:
        costmodel.reset_for_tests()


def test_ndv_ratio_damps_footer_evidence(monkeypatch):
    """A calibrated actual/footer NDV ratio flips ``shuffle_combine_wins``
    for footer evidence that reads near-unique but is 10x off."""
    rows, parts = 400_000, 4
    assert not costmodel.shuffle_combine_wins(rows, rows, parts)
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
    cal.observe("NDV_FOOTER_RATIO", 0.05)
    assert costmodel.shuffle_combine_wins(rows, rows, parts)
    # EXACT evidence (measured by the re-planner) is never damped
    assert not costmodel.shuffle_combine_wins(rows, rows, parts,
                                              exact_groups=True)


def test_flight_history_ingest(monkeypatch, tmp_path):
    """A fresh process seeds its profile from the flight recorder's
    device_kernels blocks (the same evidence ledger_record observes
    live, recovered from disk)."""
    import json
    log = tmp_path / "queries.jsonl"
    entry = {"device_kernels": {"grouped_agg": {
        "dispatches": 4, "rows": 1 << 20, "bytes": float(1 << 26),
        "seconds": 0.5, "strategy": "sort"}}}
    log.write_text(json.dumps(entry) + "\n")
    monkeypatch.setenv("DAFT_TPU_QUERY_LOG", str(log))
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
    n = cal.ingest_flight_history()
    assert n == 1
    assert cal.const("DEV_AGG_BPS", 0.0) > 0
    # idempotent: a second call ingests nothing
    assert cal.ingest_flight_history() == 0


# ------------------------------------------- distributed re-planning (b)

def _join_frames(n=60_000, k=1000):
    big = dt.from_pydict({"k": (np.arange(n) % k).tolist(),
                          "v": np.arange(n).tolist()}).into_partitions(4)
    small = dt.from_pydict({"k": list(range(k)),
                            "w": list(range(k))}).into_partitions(2)
    return big, small


def _join_q():
    big, small = _join_frames()
    return (big.join(small, on="k", strategy="hash")
            .groupby("k").agg(col("v").sum(), col("w").sum())
            .sort("k").to_pydict())


def _nearuniq_q(n=60_000):
    d = dt.from_pydict({"k": np.arange(n).tolist(),
                        "v": np.arange(n).tolist()}).into_partitions(4)
    return d.groupby("k").agg(col("v").sum()).sort("k").to_pydict()


def test_knob_off_is_verbatim_static(monkeypatch):
    """DAFT_TPU_ADAPTIVE unset: zero adaptive counters, identical
    results — the static path is untouched."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    ref = _run_distributed(_join_q)
    assert adaptive.counters_snapshot() == {}
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "0")
    assert _run_distributed(_join_q) == ref
    assert adaptive.counters_snapshot() == {}


def test_broadcast_demotion_small_side(monkeypatch):
    """The measured-small join side demotes its hash boundary to a
    replicated gather — the SMALLER side, join-type gated — with
    identical results."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    ref = _run_distributed(_join_q)
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    out = _run_distributed(_join_q)
    assert out == ref
    c = adaptive.counters_snapshot()
    assert c.get("broadcast_demotions") == 1
    assert c.get("est_rewrites", 0) >= 1
    # the decision names the demoted (small, right) side in the history
    hist = adaptive.last_planner().explain_analyze()
    assert "hash→broadcast_right" in hist


def test_no_demotion_for_outer_join_on_probe_side(monkeypatch):
    """A full-outer join tolerates no replicated side: no demotion."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")

    def q():
        big, small = _join_frames()
        return (big.join(small, on="k", how="outer", strategy="hash")
                .groupby("k").agg(col("v").sum(), col("w").sum())
                .sort("k").to_pydict())

    monkeypatch.delenv("DAFT_TPU_ADAPTIVE")
    ref = _run_distributed(q)
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    out = _run_distributed(q)
    assert out == ref
    assert adaptive.counters_snapshot().get("broadcast_demotions") is None


def test_combine_flip_on_measured_near_unique_keys(monkeypatch):
    """Mis-estimated NDV, measured: with no cardinality evidence the
    static plan default-accepts the map-side combine; the re-planner
    measures the in-memory keys near-unique (exact NDV) and flips it
    OFF — saving the wasted map-side agg pass — with identical
    results."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    ref = _run_distributed(_nearuniq_q)
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    costmodel.decision_counts.clear()
    out = _run_distributed(_nearuniq_q)
    assert out == ref
    c = adaptive.counters_snapshot()
    assert c.get("combine_flips") == 1
    assert c.get("ndv_measured", 0) >= 1
    d = costmodel.decision_counts.get("shuffle_combine")
    assert d and d["host"] >= 1  # the evidence-priced decision: decline


def test_est_rewrites_reach_fragment_nodes(monkeypatch):
    """The consumer fragment's HashJoin bytes estimates and Aggregate
    NDV evidence are rewritten from receipts before dispatch (the spill
    fanout and kernel-strategy inputs)."""
    from daft_tpu.distributed.replan import BoundaryActuals, StageReplanner
    from daft_tpu.distributed.stages import StagePlan
    from daft_tpu.physical.translate import translate

    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    big, small = _join_frames(n=5000, k=50)
    plan = (big.join(small, on="k", strategy="hash")
            .groupby("k").agg(col("v").sum()))
    pplan = translate(plan._builder.optimize().plan)
    sp = StagePlan.from_physical(pplan)
    join_stage = next(
        s for s in sp.stages if s.boundaries
        and StageReplanner._join_side(s.plan, s.boundaries[0].upstream))
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    rp = StageReplanner(sp)
    acts = {b.upstream: BoundaryActuals(rows=1000, nbytes=4096, ndv=50)
            for b in join_stage.boundaries}
    rp._rewrite_estimates(join_stage, acts)

    import daft_tpu.physical.plan as pp

    def find(n, t):
        if isinstance(n, t):
            return n
        for ch in n.children:
            r = find(ch, t)
            if r is not None:
                return r
        return None

    j = find(join_stage.plan, pp.HashJoin)
    assert j.left_bytes_est == 4096 and j.right_bytes_est == 4096
    assert adaptive.counters_snapshot().get("est_rewrites", 0) >= 2


def test_distributed_aqe_materialize_loop(monkeypatch):
    """``enable_aqe=True`` on the distributed runner runs the native
    runner's materialize-and-reoptimize loop THROUGH the stage runner:
    join inputs materialize distributed, re-plans land in the shared
    history, results match the static run."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    ref = _run_distributed(_join_q)
    with dctx.execution_config_ctx(enable_aqe=True):
        out = _run_distributed(_join_q)
    assert out == ref
    hist = adaptive.last_planner().explain_analyze()
    assert "materialized join input distributed" in hist


def test_adaptive_stats_block_and_metrics(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    _run_distributed(_join_q)
    from daft_tpu import observability as obs
    from daft_tpu import tracing
    stats = obs.last_query_stats()
    assert stats.adaptive.get("broadcast_demotions") == 1
    rendered = stats.render()
    assert "adaptive (self-tuning):" in rendered
    assert "broadcast_demotions=1" in rendered
    text = tracing.prometheus_text()
    parsed = tracing.parse_prometheus_text(text)
    assert parsed.get("daft_tpu_adaptive_broadcast_demotions_total",
                      0) >= 1
    # flight-recorder entries carry the block
    entry = obs.flight_entry(stats)
    assert entry["adaptive"].get("broadcast_demotions") == 1


def test_calibrated_constants_listed_in_render(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
    cal.observe("DEV_AGG_BPS", 1e9)
    from daft_tpu import observability as obs
    lines = obs.render_adaptive_block({})
    joined = "\n".join(lines)
    assert "calibrated constants" in joined and "DEV_AGG_BPS" in joined
    assert cal.calibrated_names() == ["DEV_AGG_BPS"]


# --------------------------------------- chaos-determinism contract (r20)

def test_feedback_knobs_do_not_perturb_chaos_replay(monkeypatch):
    """The extended chaos contract: with DAFT_TPU_ADAPTIVE=1 and
    DAFT_TPU_CALIBRATION=1 both ON, a chaos-serialized seeded run
    replays the SAME fault events and answer as with them OFF — the
    feedback state is frozen (no observations, no re-plans)."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_DISTRIBUTED_SHUFFLE", "flight")
    monkeypatch.setenv("DAFT_TPU_FAULT_SPEC", "task:0.08,fetch:0.08")
    monkeypatch.setenv("DAFT_TPU_FAULT_SEED", "7")
    monkeypatch.setenv("DAFT_TPU_RETRY_BACKOFF", "0.01")
    monkeypatch.setenv("DAFT_TPU_CHAOS_SERIALIZE", "1")
    monkeypatch.setenv("DAFT_TPU_SPECULATIVE_MULTIPLIER", "0")

    def one_run(knobs):
        for k, v in knobs.items():
            monkeypatch.setenv(k, v)
        rz.reset_for_tests()
        adaptive.counters_reset()
        out = _run_distributed(_join_q)
        return out, sorted(rz.fault_events())

    out1, ev1 = one_run({"DAFT_TPU_ADAPTIVE": "0",
                         "DAFT_TPU_CALIBRATION": "0"})
    out2, ev2 = one_run({"DAFT_TPU_ADAPTIVE": "1",
                         "DAFT_TPU_CALIBRATION": "1"})
    assert ev1, "the fixed spec/seed injected nothing — tune the seed"
    assert ev1 == ev2
    assert out1 == out2
    # frozen means FROZEN: no observations, no re-plan decisions
    c = adaptive.counters_snapshot()
    assert c.get("calibration_observations") is None
    assert not any(k for k in c
                   if k not in ("replan_frozen",)), c
    rz.reset_for_tests()


def test_replan_disabled_under_active_fault_plan(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    monkeypatch.setenv("DAFT_TPU_FAULT_SPEC", "task:0.01")
    rz.reset_for_tests()
    try:
        assert not replan.adaptive_enabled()
        assert adaptive.counters_snapshot().get("replan_frozen") == 1
    finally:
        rz.reset_for_tests()


# -------------------------------------------------- history bound (sat 1)

def test_adaptive_planner_history_is_bounded(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE_HISTORY", "5")
    p = adaptive.AdaptivePlanner(dctx.get_context().execution_config)
    for i in range(12):
        p.record_replan(f"decision {i}")
    assert len(p.history) == 5
    assert p.evictions == 7
    assert p.history[0].decision == "decision 7"  # oldest evicted first
    assert adaptive.counters_snapshot().get("history_evictions") == 7
    assert "7 oldest entries evicted" in p.explain_analyze()


def test_history_cap_config_mirror(monkeypatch):
    monkeypatch.delenv("DAFT_TPU_ADAPTIVE_HISTORY", raising=False)
    with dctx.execution_config_ctx(tpu_adaptive_history=3):
        assert adaptive.history_cap() == 3
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE_HISTORY", "9")
    with dctx.execution_config_ctx(tpu_adaptive_history=3):
        assert adaptive.history_cap() == 9  # env overrides


# ------------------------------------------- admission seeding (sat 2/4c)

def test_admission_estimate_seeded_from_history(monkeypatch, tmp_path):
    """ROADMAP 4c (minimal): when the cost model is blind, a repeat
    query's admission estimate comes from the per-fingerprint observed
    result bytes instead of the flat 64 MiB default."""
    from daft_tpu.logical import stats as lstats
    from daft_tpu.serving import QueryScheduler
    from daft_tpu.serving import scheduler as sched_mod

    root = tmp_path / "t"
    dt.from_pydict({"g": [i % 5 for i in range(4000)],
                    "v": [float(i) for i in range(4000)]}) \
        .write_parquet(str(root))
    glob = str(root / "*.parquet")

    def q():
        return dt.read_parquet(glob).groupby("g") \
            .agg(col("v").sum().alias("s")).sort("g")

    # blind the cost model so the history path is the only evidence
    monkeypatch.setattr(lstats, "estimate",
                        lambda plan: lstats.Stats(None, None))
    s = QueryScheduler(concurrency=1, result_cache_bytes=0)
    try:
        h1 = s.submit(q())
        h1.result(60)
        assert h1._fp_hist_key is not None
        # first (cold) submission used the flat default
        assert s.counters_snapshot().get("est_seeded_history") is None
        h2 = s.submit(q())
        h2.result(60)
        assert s.counters_snapshot().get("est_seeded_history") == 1
        # the recorded observation is the real result size, not 64 MiB
        with s._hist_lock:
            (bytes_ewma, wall_us, n) = s._fp_hist[h1._fp_hist_key]
        assert n == 2 and 0 < bytes_ewma < sched_mod._DEFAULT_EST_BYTES
    finally:
        s.shutdown()


def test_exact_rewrite_never_observed_as_footer_ratio(monkeypatch):
    """Review regression: when the re-planner rewrote an Aggregate's NDV
    from EXACT measured evidence (no original footer existed), the
    observed actual/exact ratio ≈ 1.0 must NOT feed NDV_FOOTER_RATIO —
    it would EWMA-erase the learned damping."""
    monkeypatch.setenv("DAFT_TPU_DEVICE", "0")
    monkeypatch.setenv("DAFT_TPU_ADAPTIVE", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION", "1")
    monkeypatch.setenv("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "1")
    # in-memory group-by: no footer evidence, measured-NDV rewrite runs
    _run_distributed(_nearuniq_q)
    assert cal.summary()["NDV_FOOTER_RATIO"]["samples"] == 0


def test_history_key_distinguishes_datasets(monkeypatch, tmp_path):
    """Review regression: same-shape queries over DIFFERENT datasets
    must not share one admission-history key (a small table's observed
    bytes would under-admit the big one)."""
    from daft_tpu.serving.scheduler import _history_fingerprint
    keys = []
    for name, rows in (("a", 100), ("b", 100)):
        root = tmp_path / name
        dt.from_pydict({"g": [i % 5 for i in range(rows)],
                        "v": [float(i) for i in range(rows)]}) \
            .write_parquet(str(root))
        q = dt.read_parquet(str(root / "*.parquet")).groupby("g") \
            .agg(col("v").sum().alias("s"))
        keys.append(_history_fingerprint(q._builder))
    assert keys[0] is not None and keys[1] is not None
    assert keys[0] != keys[1]


def test_admission_history_seeds_from_flight_recorder(monkeypatch,
                                                      tmp_path):
    """A fresh scheduler seeds its per-fingerprint history from
    flight-recorder serving blocks of earlier processes."""
    import json

    from daft_tpu.logical import stats as lstats
    from daft_tpu.serving import QueryScheduler
    log = tmp_path / "q.jsonl"
    key = "abcd1234abcd1234"
    log.write_text(json.dumps({
        "serving": {"fp_hist_key": key, "result_bytes": 5 << 20,
                    "run_us": 1000}}) + "\n")
    monkeypatch.setenv("DAFT_TPU_QUERY_LOG", str(log))
    s = QueryScheduler(concurrency=1)
    try:
        est = s._history_estimate(key)
        assert est == 5 << 20
    finally:
        s.shutdown()
