"""Benchmark driver: all five BASELINE.json config families through the
daft_tpu engine.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

Config families (BASELINE.json):
1. TPC-H Q1 @ SF1  — the headline metric (rows/s/chip), host + device tiers
2. TPC-H Q3/Q5/Q10 @ SF10 — 3-way joins + aggregate (runs when the SF10
   dataset is present or BENCH_SF10=1 generates it; ~25 min one-time gen)
3. TPC-H full Q1–Q22 — per-query hot + total wall-clock @ SF1 always, and
   @ SF10 when present
4. TPC-DS Q47/Q63/Q89 — window/rolling trio via the SQL frontend
5. LAION-style multimodal — PNG decode → resize → random-projection
   embedding (device matmul) → cosine sim → groupby

Structure (hang-proof AND deadline-proof by construction; round-1 and
round-3 postmortems):
- a GLOBAL wall-clock budget (`BENCH_TOTAL_BUDGET_S`, default 600 s) is
  enforced across all sections: each checks the remaining budget before
  starting; sections that don't fit are named in `skipped_sections` and
  the single JSON line is always emitted within the budget.
- the Arrow baseline is pinned (best-of-3, persisted per dataset) so the
  headline `vs_baseline` denominator is stable across runs.
- any section failure lands in the top-level `section_errors`, never
  silently inside a detail dict.
- the Arrow CPU baseline and the host tier (DAFT_TPU_DEVICE=0) run
  in-process: they never touch the JAX backend and cannot hang.
- the device tier runs in a CHILD process under BENCH_DEVICE_TIMEOUT
  (default 900 s), printing one JSON line per completed section so a stall
  only loses the sections after it. A wedged TPU plugin kills the child,
  never the driver; the engine watchdog additionally pins a dead backend
  to the host tier.
The reported headline is the best tier on Q1@SF1. vs_baseline =
arrow_baseline_s / ours_s (>1 → we're faster).
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SF = float(os.environ.get("BENCH_SF", "1"))
PARTS = int(os.environ.get("BENCH_PARTS", "8"))
# _v2: chunked datagen (different RNG streams) — old caches are a different dataset
DATA = os.path.join(REPO, ".cache", f"tpch_sf{SF}_v2")
SF10_DATA = os.path.join(REPO, ".cache", "tpch_sf10.0_v2")
# version-stamped: regenerates when the datagen schema grows
TPCDS_DATA = os.path.join(REPO, ".cache", "tpcds_s1_v3")
LAION_DATA = os.path.join(REPO, ".cache", "laion_4k")
DEVICE_TIMEOUT = float(os.environ.get("BENCH_DEVICE_TIMEOUT", "900"))

# Global wall-clock budget (round-3 postmortem: two of three driver runs
# timed out because per-section budgets never summed to a bound). EVERY
# section checks the remaining budget before starting; whatever doesn't fit
# is named in `skipped_sections` and the one JSON line is still emitted.
# 480 (not 600): sections check the budget BEFORE starting a query, so a
# long SF10 query that starts at T-1 overruns by its own duration (~90s
# worst observed single query). 480 + 90 stays inside every driver window
# that 600 nominally targeted (round-3 postmortem: rc=124 twice).
TOTAL_BUDGET = float(os.environ.get("BENCH_TOTAL_BUDGET_S", "480"))
_T0 = time.time()


def _remaining() -> float:
    return TOTAL_BUDGET - (time.time() - _T0)

TPCH_QUERIES = [f"q{i}" for i in range(1, 23)]


def ensure_data():
    if not os.path.isdir(os.path.join(DATA, "lineitem")):
        from benchmarking.tpch.datagen import generate_tpch
        print(f"generating TPC-H SF{SF} …", file=sys.stderr, flush=True)
        generate_tpch(DATA, SF, PARTS)
    if os.environ.get("BENCH_SF10") == "1" \
            and not os.path.isdir(os.path.join(SF10_DATA, "lineitem")):
        from benchmarking.tpch.datagen import generate_tpch
        print("generating TPC-H SF10 (one-time, ~25 min) …",
              file=sys.stderr, flush=True)
        generate_tpch(SF10_DATA, 10.0, 16)
    if not os.path.isdir(os.path.join(TPCDS_DATA, "store_sales")):
        from benchmarking.tpcds.datagen import generate_tpcds
        print("generating TPC-DS …", file=sys.stderr, flush=True)
        generate_tpcds(TPCDS_DATA, scale=1.0)
    if not os.path.isdir(LAION_DATA):
        _gen_laion(LAION_DATA)


def _gen_laion(root: str, n: int = 4096, px: int = 64):
    """Synthetic LAION-like shard: (id, label, png) parquet. Labels are the
    dominant color channel so the downstream groupby has semantics."""
    import io as _io

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from PIL import Image
    rng = np.random.default_rng(7)
    labels, blobs = [], []
    for i in range(n):
        lab = i % 3
        img = rng.integers(0, 96, size=(px, px, 3), dtype=np.uint8)
        img[..., lab] += 128
        b = _io.BytesIO()
        Image.fromarray(img).save(b, format="PNG")
        labels.append("rgb"[lab])
        blobs.append(b.getvalue())
    os.makedirs(root, exist_ok=True)
    pq.write_table(
        pa.table({"id": pa.array(range(n), pa.int64()),
                  "label": pa.array(labels),
                  "png": pa.array(blobs, pa.large_binary())}),
        os.path.join(root, "images.parquet"))


# --------------------------------------------------------------- sections

def _get_df_factory(root):
    import daft_tpu as dt

    def get_df(name):
        return dt.read_parquet(f"{root}/{name}/*.parquet")
    return get_df


def run_tpch_query(root, qname: str):
    """(warm_s, hot_s) for one TPC-H query over `root`."""
    from benchmarking.tpch import queries as Q
    get_df = _get_df_factory(root)
    fn = getattr(Q, qname)
    t0 = time.time()
    out = fn(get_df).to_pydict()
    warm = time.time() - t0
    t0 = time.time()
    fn(get_df).to_pydict()
    hot = time.time() - t0
    return out, warm, hot


def _decisions_delta(before: dict, after: dict) -> dict:
    """Flattened per-kind strategy-pick deltas from costmodel's nested
    ``decision_counts`` (``{kind: {side: n}}`` or ``{kind: n}``)."""
    out = {}
    for kind, v in after.items():
        if isinstance(v, dict):
            b = before.get(kind) if isinstance(before.get(kind), dict) \
                else {}
            for side, n in v.items():
                d = n - b.get(side, 0)
                if d:
                    out[f"{kind}_{side}"] = int(d)
        else:
            d = v - (before.get(kind) or 0)
            if d:
                out[kind] = int(d)
    return out


def _rich_counters_start() -> dict:
    """Per-query counter bookends for the scale-trajectory artifact:
    spill plane, governor plane, adaptive (replan) plane, cost-model
    strategy picks, and a fresh peak-RSS baseline."""
    from daft_tpu.device import costmodel as _cm
    from daft_tpu.execution import governor as _gov
    from daft_tpu.execution import memory as _mem
    try:
        from daft_tpu.physical import adaptive as _ad
        ad0 = _ad.counters_snapshot()
    except Exception:
        ad0 = {}
    return {"spill": _mem.spill_counters_snapshot(),
            "gov": _gov.counters_snapshot(), "adaptive": ad0,
            "decisions": json.loads(json.dumps(_cm.decision_counts)),
            "rss0": _gov.reset_peak()}


def _rich_counters_finish(s0: dict) -> dict:
    """The per-query record the scale bench commits: spill bytes (logical
    + post-codec disk), partitions/recursion depth, governor actions,
    peak RSS, replan counts, exchange rung changes, strategy picks."""
    from daft_tpu.device import costmodel as _cm
    from daft_tpu.execution import governor as _gov
    from daft_tpu.execution import memory as _mem
    rec: dict = {}
    sd = _mem.spill_counters_delta(s0["spill"])
    if sd.get("bytes_written") or sd.get("joins_partitioned"):
        depths = [int(k[len("recursions_d"):]) for k in sd
                  if k.startswith("recursions_d")]
        rec["spill"] = {
            "bytes_written": int(sd.get("bytes_written", 0)),
            "disk_bytes_written": int(sd.get("disk_bytes_written", 0)),
            "partitions": int(sd.get("partitions_spilled", 0)),
            "recursions": int(sd.get("recursions", 0)),
            "max_depth": max(depths) if depths else 0,
        }
    gd = _gov.counters_delta(s0["gov"])
    if gd:
        rec["governor"] = {k: int(v) for k, v in sorted(gd.items())}
    rec["rss_peak_bytes"] = int(_gov.peak_rss_bytes())
    try:
        from daft_tpu.physical import adaptive as _ad
        ad = _ad.counters_delta(s0["adaptive"])
    except Exception:
        ad = {}
    replans = sum(int(ad.get(k, 0)) for k in
                  ("combine_flips", "exchange_repicks",
                   "broadcast_demotions", "est_rewrites"))
    if replans:
        rec["replans"] = replans
    if ad.get("exchange_repicks"):
        rec["exchange_repicks"] = int(ad["exchange_repicks"])
    picks = _decisions_delta(s0["decisions"], _cm.decision_counts)
    if picks:
        rec["strategy_picks"] = picks
    return rec


def run_tpch_suite(root, queries=TPCH_QUERIES, budget_s: float = 1e9,
                   rich: bool = False):
    """Hot per-query times + totals. Respects a wall-clock budget:
    queries past it are skipped, named in the result, AND itemized per
    query as ``{"skipped": "budget", "remaining_s": ...}`` so the
    artifact shows exactly how much budget each skipped query saw.
    ``rich=True`` (the scale-trajectory mode) additionally records each
    query's spill bytes (logical + disk), spill partitions/recursion
    depth, governor actions, peak RSS, replan count, and strategy
    picks. Each query's spill-tier logical bytes (both runs) ride along
    either way so out-of-core rounds carry per-query spill evidence."""
    from daft_tpu.execution import memory as _mem
    per_q = {}
    rich_q = {}
    spill_q = {}
    skipped = []
    t_start = time.time()
    total_hot = 0.0
    for qn in queries:
        remaining = budget_s - (time.time() - t_start)
        if remaining < 0:
            skipped.append(qn)
            per_q[qn] = {"skipped": "budget",
                         "remaining_s": round(remaining, 1)}
            continue
        s0 = _rich_counters_start() if rich \
            else {"spill": _mem.spill_counters_snapshot()}
        try:
            _, warm, hot = run_tpch_query(root, qn)
        except Exception as exc:  # a failing query must not kill the bench
            per_q[qn] = {"error": str(exc)[:200]}
            continue
        if rich:
            rq = _rich_counters_finish(s0)
            rq["hot_s"] = round(min(warm, hot), 3)
            rich_q[qn] = rq
            sd = {"bytes_written":
                  rq.get("spill", {}).get("bytes_written", 0)}
        else:
            sd = _mem.spill_counters_delta(s0["spill"])
        if sd.get("bytes_written"):
            spill_q[qn] = int(sd["bytes_written"])
        per_q[qn] = round(min(warm, hot), 3)
        total_hot += min(warm, hot)
    out = {"per_query_hot_s": per_q, "total_hot_s": round(total_hot, 3)}
    if rich_q:
        out["per_query"] = rich_q
    if spill_q:
        out["per_query_spill_bytes"] = spill_q
    if skipped:
        out["skipped"] = skipped
    return out


def run_tpcds_trio(root):
    from benchmarking.tpcds import queries as Q
    get_df = _get_df_factory(root)
    out = {}
    for qnum in (47, 63, 89):
        t0 = time.time()
        Q.run(qnum, get_df).to_pydict()
        warm = time.time() - t0
        t0 = time.time()
        Q.run(qnum, get_df).to_pydict()
        out[f"q{qnum}_hot_s"] = round(min(warm, time.time() - t0), 3)
    return out


def run_laion(root):
    """decode → resize → 128-d random-projection embedding → cosine sim →
    groupby(label). The embed matmul is the MXU-shaped step: on the device
    tier it runs as one jit batched matmul; host tier uses numpy."""
    import numpy as np

    import daft_tpu as dt
    from daft_tpu import col
    from daft_tpu.datatype import DataType

    rng = np.random.default_rng(3)
    P = rng.standard_normal((32 * 32 * 3, 128)).astype(np.float32)
    qv = rng.standard_normal(128).astype(np.float32)
    qv /= np.linalg.norm(qv)

    def _embed_on_device() -> bool:
        """The embed matmul goes to the accelerator only when the measured
        link can afford the per-batch transfers (the engine's own cost
        model) — on a slow-link chip the MXU win can't repay ~40 MB/s
        freight, on a local chip it can."""
        if os.environ.get("DAFT_TPU_DEVICE", "1") == "0":
            return False
        from daft_tpu.device import costmodel
        n, d_in, d_out = 4096, 32 * 32 * 3, 128
        return costmodel.row_output_op_wins(
            bytes_up=n * d_in * 4, bytes_down=n * d_out * 4)

    use_device = _embed_on_device()

    @dt.udf(return_dtype=DataType.float32())
    def cos_sim(images):
        arrs = images.to_pylist()
        if not arrs:
            return []
        x = np.stack([np.asarray(a, dtype=np.float32).reshape(-1)
                      for a in arrs])
        x /= 255.0
        if use_device:
            import jax.numpy as jnp
            emb = np.asarray(jnp.asarray(x) @ jnp.asarray(P))
        else:
            emb = x @ P
        norms = np.linalg.norm(emb, axis=1)
        norms[norms == 0] = 1.0
        return (emb @ qv / norms).tolist()

    def pipeline():
        df = dt.read_parquet(os.path.join(root, "images.parquet"))
        df = df.with_column("img", col("png").image.decode(mode="RGB"))
        df = df.with_column("small", col("img").image.resize(32, 32))
        df = df.with_column("sim", cos_sim(col("small")))
        return (df.groupby("label")
                .agg(col("sim").mean().alias("mean_sim"),
                     col("sim").count().alias("n"))
                .sort("label").to_pydict())

    t0 = time.time()
    out = pipeline()
    warm = time.time() - t0
    t0 = time.time()
    pipeline()
    hot = time.time() - t0
    n_imgs = sum(out["n"])
    best = min(warm, hot)
    return {"hot_s": round(best, 3),
            "images_per_s": round(n_imgs / best, 1),
            "groups": len(out["label"])}


def run_chaos(root):
    """``--chaos``: one distributed TPC-H query (Q3) under a fixed seeded
    fault spec covering all three injection sites. Records the
    recovery-event counters and whether the chaotic answer matched the
    fault-free one — the artifact's evidence that the resilience plane
    recovers real queries, not just unit fixtures."""
    import daft_tpu.context as dctx
    from benchmarking.tpch import queries as Q
    from daft_tpu.distributed import resilience as rz
    from daft_tpu.runners.distributed_runner import DistributedRunner

    get_df = _get_df_factory(root)
    baseline = Q.q3(get_df).to_pydict()

    env = {"DAFT_TPU_FAULT_SPEC": "task:0.05,fetch:0.05,crash:0.05",
           "DAFT_TPU_FAULT_SEED": "1",
           "DAFT_TPU_DISTRIBUTED_SHUFFLE": "flight",
           "DAFT_TPU_RETRY_BACKOFF": "0.02"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    rz.reset_for_tests()
    runner = DistributedRunner(num_workers=3)
    old = dctx.get_context()._runner
    dctx.get_context().set_runner(runner)
    t0 = time.time()
    try:
        chaotic = Q.q3(get_df).to_pydict()
    finally:
        dctx.get_context().set_runner(old)
        if runner._manager is not None:  # don't leak worker pools into
            runner._manager.shutdown()   # the timed sections that follow
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    elapsed = time.time() - t0

    def canon(d):
        cols = sorted(d)
        return [tuple(round(v, 6) if isinstance(v, float) else v
                      for v in row)
                for row in zip(*(d[c] for c in cols))]

    counters = rz.counters_snapshot()
    rz.reset_for_tests()
    return {"query": "q3", "spec": env["DAFT_TPU_FAULT_SPEC"],
            "seed": env["DAFT_TPU_FAULT_SEED"],
            "match": canon(chaotic) == canon(baseline),
            "elapsed_s": round(elapsed, 3),
            "recovery_events": {k: v for k, v in sorted(counters.items())}}


def run_spill_bench():
    """``--spill``: out-of-core execution bench — a grace hash join plus
    a near-unique-key group-by under a FORCED tiny memory budget vs the
    unbounded in-memory run. Records parity (must be bit-exact), wall
    ratios, and the spill evidence (disk bytes written/read, radix
    recursions, per-store peak residency — the peak-RSS claim).

    r23 adds the fast-path A/B: the same spilled workload runs once on
    the LEGACY plane (serial writes, no codec — the r19 path, forced via
    DAFT_TPU_SPILL_IO_PARALLELISM=0 + compression none) and once on the
    fast plane (bounded writer pool + lz4 + prefetch-piped reads); both
    walls and both on-disk byte totals land in the artifact, so the
    before/after claim is a committed number, not a narrative."""
    import numpy as np

    import daft_tpu as dt
    from daft_tpu import col
    from daft_tpu.execution import memory as mem

    n = 400_000
    k = np.arange(n) % 120_000
    left = dt.from_pydict({"k": k.tolist(), "v": np.arange(n).tolist()})
    right = dt.from_pydict({"k": k[: n // 2].tolist(),
                            "w": (np.arange(n // 2) * 3).tolist()})

    def join_q():
        return _canon_rows(left.join(right, on="k", strategy="hash")
                           .groupby("k")
                           .agg(col("v").sum(), col("w").sum())
                           .to_pydict())

    def agg_q():
        return _canon_rows(left.groupby("k").agg(col("v").sum())
                           .to_pydict())

    # discarded warm-up pass: plan/translate caches and jit traces are
    # one-time costs — charging them to whichever side runs first would
    # skew the spilled-vs-in-memory ratio (both timed passes below run
    # warm)
    join_q()
    agg_q()
    t0 = time.time()
    ref_join = join_q()
    ref_agg = agg_q()
    in_mem_s = time.time() - t0

    def spilled_pass(extra_env):
        env = {"DAFT_TPU_MEMORY_LIMIT": "2MB", "DAFT_TPU_SPILL_AGG": "1"}
        env.update(extra_env)
        saved = {kk: os.environ.get(kk) for kk in env}
        os.environ.update(env)
        mem._spill_ipc_cache.clear()
        s0 = mem.spill_counters_snapshot()
        t0 = time.time()
        try:
            sj = join_q()
            sa = agg_q()
        finally:
            for kk, v in saved.items():
                if v is None:
                    os.environ.pop(kk, None)
                else:
                    os.environ[kk] = v
            mem._spill_ipc_cache.clear()
        wall = time.time() - t0
        sd = mem.spill_counters_delta(s0)
        return sj, sa, wall, sd

    # best-of-2 per plane: on a 1-core box a single spilled pass sees
    # multi-hundred-ms scheduler noise, which would drown the A/B signal
    legacy_env = {"DAFT_TPU_SPILL_IO_PARALLELISM": "0",
                  "DAFT_TPU_SPILL_COMPRESSION": "none"}
    fast_env = {"DAFT_TPU_SPILL_IO_PARALLELISM": "4",
                "DAFT_TPU_SPILL_COMPRESSION": "lz4"}
    lj, la, legacy_s, legacy_sd = spilled_pass(legacy_env)
    _, _, legacy_s2, _ = spilled_pass(legacy_env)
    legacy_s = min(legacy_s, legacy_s2)
    spilled_join, spilled_agg, spilled_s, sd = spilled_pass(fast_env)
    _, _, fast_s2, _ = spilled_pass(fast_env)
    spilled_s = min(spilled_s, fast_s2)
    legacy_disk = int(legacy_sd.get("disk_bytes_written", 0))
    fast_disk = int(sd.get("disk_bytes_written", 0))
    return {
        "rows": n,
        "budget": "2MB",
        "join_match": spilled_join == ref_join and lj == ref_join,
        "agg_match": spilled_agg == ref_agg and la == ref_agg,
        "spilled_s": round(spilled_s, 3),
        "in_memory_s": round(in_mem_s, 3),
        "slowdown_x": round(spilled_s / max(in_mem_s, 1e-9), 3),
        "spill_bytes_written": int(sd.get("bytes_written", 0)),
        "spill_bytes_read": int(sd.get("bytes_read", 0)),
        "recursions": int(sd.get("recursions", 0)),
        "depth_exhausted": int(sd.get("depth_exhausted", 0)),
        "agg_buckets_merged": int(sd.get("agg_buckets_merged", 0)),
        "store_peak_bytes": int(sd.get("store_peak_bytes", 0)),
        "legacy": {
            "spilled_s": round(legacy_s, 3),
            "disk_bytes_written": legacy_disk,
            "spill_bytes_written": int(legacy_sd.get("bytes_written", 0)),
        },
        "fast": {
            "spilled_s": round(spilled_s, 3),
            "disk_bytes_written": fast_disk,
            "io_parallelism": 4,
            "compression": "lz4",
        },
        "fast_vs_legacy_wall_x": round(
            legacy_s / max(spilled_s, 1e-9), 3),
        "fast_vs_legacy_disk_ratio": round(
            fast_disk / max(legacy_disk, 1), 3),
    }


def _canon_rows(d: dict):
    """Column dict → sorted row tuples (floats rounded) for an
    order-insensitive answer comparison."""
    cols = sorted(d)
    return sorted(tuple(round(v, 6) if isinstance(v, float) else v
                        for v in row)
                  for row in zip(*(d[c] for c in cols)))


def run_fuzz_smoke() -> int:
    """``--fuzz-smoke``: the plan-discipline CI gate. Runs the
    differential plan fuzzer (seeded random queries; every engine mode
    matrix — optimized / fused / spilled / replanned / combined — must
    answer bit-identically to the unoptimized reference) with the plan
    sanitizer armed, and emits seeds-run / mismatch / sanitizer-
    violation counts. Exit 1 on any mismatch, error, or contract
    violation."""
    os.environ.setdefault("DAFT_TPU_SANITIZE_PLAN", "1")
    from daft_tpu.analysis import plan_fuzzer, plan_sanitizer
    if plan_sanitizer.enabled_by_env() and not plan_sanitizer.is_enabled():
        plan_sanitizer.enable()
    res = plan_fuzzer.run_fuzz(log=print)
    s = res.summary()
    detail = dict(s)
    detail["modes"] = list(plan_fuzzer.MODES)
    for m in res.mismatches:
        print("plan fuzzer MISMATCH\n" + m.repro())
    for e in res.errors:
        print(f"plan fuzzer error: {e}")
    if plan_sanitizer.is_enabled():
        print(plan_sanitizer.report())
    print(json.dumps({"fuzz_smoke": detail}), flush=True)
    ok = not (res.mismatches or res.errors or res.sanitizer_violations)
    print(f"fuzz smoke: {s['seeds_run']} seeds, "
          f"{s['cases_compared']} comparisons, "
          f"{s['mismatches']} mismatches, "
          f"{s['sanitizer_violations']} sanitizer violations -> "
          + ("OK" if ok else "FAIL"))
    return 0 if ok else 1


def run_scale_smoke() -> int:
    """``--scale-smoke``: the out-of-core CI gate. The FULL 22-query
    TPC-H suite at a small SF under a forced-tiny memory limit (every
    join/agg takes the spill path) with the sanitizer on; every answer
    is checked against the unbounded in-memory run. Exit 1 on a wrong
    answer, unbounded RSS (peak past the ceiling), a leaked spill file,
    or a lock-order cycle."""
    import shutil
    import tempfile

    os.environ.setdefault("DAFT_TPU_SANITIZE", "1")
    sf = float(os.environ.get("BENCH_SCALE_SMOKE_SF", "0.1"))
    limit = os.environ.get("BENCH_SCALE_SMOKE_LIMIT", "400KB")
    ceiling = _parse_bytes_env("BENCH_SCALE_SMOKE_RSS_CEILING", 4 << 30)
    budget_s = float(os.environ.get("BENCH_SCALE_SMOKE_BUDGET_S", "900"))
    root = os.path.join(REPO, ".cache", f"tpch_sf{sf}_v2")
    if not os.path.isdir(os.path.join(root, "lineitem")):
        from benchmarking.tpch.datagen import generate_tpch
        print(f"generating TPC-H SF{sf} …", file=sys.stderr, flush=True)
        generate_tpch(root, sf, 4)

    from daft_tpu.execution import governor as gov
    from daft_tpu.execution import memory as mem
    spill_dir = tempfile.mkdtemp(prefix="daft_tpu_scale_smoke_")
    os.environ["DAFT_TPU_SPILL_DIR"] = spill_dir
    mem._spill_dir = None
    gov.reset_peak()
    t0 = time.time()
    mismatches, errors, completed, skipped = [], {}, [], []
    spill_bytes = 0
    try:
        for qn in TPCH_QUERIES:
            if time.time() - t0 > budget_s:
                skipped.append(qn)
                continue
            try:
                ref, _, _ = run_tpch_query(root, qn)
                # FORCED spill: the knobs (not the cost model) pick the
                # out-of-core path, so even a tiny SF exercises it
                forced = {"DAFT_TPU_MEMORY_LIMIT": limit,
                          "DAFT_TPU_SPILL_AGG": "1",
                          "DAFT_TPU_SPILL_JOIN": "1"}
                os.environ.update(forced)
                s0 = mem.spill_counters_snapshot()
                try:
                    got, _, _ = run_tpch_query(root, qn)
                finally:
                    for kk in forced:
                        os.environ.pop(kk, None)
                sd = mem.spill_counters_delta(s0)
                spill_bytes += int(sd.get("bytes_written", 0))
                if _canon_rows(got) != _canon_rows(ref):
                    mismatches.append(qn)
                completed.append(qn)
            except Exception as exc:  # noqa: BLE001
                errors[qn] = str(exc)[:200]
        leaked = []
        for r, _d, fs in os.walk(spill_dir):
            leaked.extend(os.path.join(r, f) for f in fs)
        cycles = 0
        try:
            from daft_tpu.analysis import lock_sanitizer
            if lock_sanitizer.is_enabled():
                cycles = int(lock_sanitizer.counters_snapshot()
                             .get("graph_cycles", 0))
        except Exception:
            pass
        peak = gov.peak_rss_bytes()
        result = {"scale_smoke": {
            "sf": sf, "limit": limit,
            "completed": len(completed), "skipped": skipped,
            "mismatches": mismatches, "errors": errors,
            "spill_bytes_written": spill_bytes,
            "rss_peak_bytes": int(peak), "rss_ceiling_bytes": ceiling,
            "leaked_spill_files": leaked[:5],
            "sanitizer_cycles": cycles,
            "elapsed_s": round(time.time() - t0, 1),
        }}
        print(json.dumps(result), flush=True)
        ok = (not mismatches and not errors and not leaked
              and not cycles and peak <= ceiling and completed
              and spill_bytes > 0)
        return 0 if ok else 1
    finally:
        shutil.rmtree(spill_dir, ignore_errors=True)
        os.environ.pop("DAFT_TPU_SPILL_DIR", None)
        mem._spill_dir = None


def _parse_bytes_env(name: str, default: int) -> int:
    v = os.environ.get(name)
    if not v:
        return default
    from daft_tpu.execution.memory import parse_bytes
    return parse_bytes(v)


def run_adaptive_bench():
    """``--adaptive``: the self-tuning feedback loops on mis-estimated
    data (round 20). Two probes:

    1. **runtime re-planning** — a distributed group-by over NEAR-UNIQUE
       in-memory keys (no cardinality evidence: the static plan
       default-accepts the map-side combine and pays a wasted full agg
       pass per map task); DAFT_TPU_ADAPTIVE measures the keys exactly
       and flips the combine OFF. Static-vs-adaptive wall, identical
       results, decision counters.
    2. **calibrated cost model** — a parquet group-by whose footer NDV
       (int min/max range) over-predicts the true key count >100x, so
       the hard-coded model DECLINES the combine that would collapse
       the wire; one calibrated pass observes the actual/footer ratio
       (NDV_FOOTER_RATIO) and the re-run flips the decision ON —
       wire-row reduction + the decision diff vs the hard-coded
       constants, identical results.
    """
    import numpy as np

    import daft_tpu as dt
    import daft_tpu.context as dctx
    from daft_tpu import col
    from daft_tpu.device import calibration as cal
    from daft_tpu.device import costmodel
    from daft_tpu.distributed import shuffle_service as ss
    from daft_tpu.physical import adaptive
    from daft_tpu.runners.distributed_runner import DistributedRunner

    def one_run(q, env):
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        runner = DistributedRunner(num_workers=3)
        old = dctx.get_context()._runner
        dctx.get_context().set_runner(runner)
        s0 = ss.shuffle_counters_snapshot()
        a0 = adaptive.counters_snapshot()
        t0 = time.time()
        try:
            out = _canon_rows(q())
        finally:
            dctx.get_context().set_runner(old)
            if runner._manager is not None:
                runner._manager.shutdown()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return (out, time.time() - t0,
                ss.shuffle_counters_delta(s0),
                adaptive.counters_delta(a0))

    # ---- probe 1: runtime re-planning on near-unique in-memory keys.
    # A wide decomposable agg set: the map-side combine the static plan
    # default-accepts re-aggregates EVERY column per partition — the
    # wasted pass the measured-NDV flip avoids scales with it
    n = 800_000
    nu = {"k": np.arange(n).tolist(), "v": np.arange(n).tolist(),
          "w": (np.arange(n) * 3 % 997).tolist(),
          "x": np.arange(n, dtype="float64").tolist()}

    def q_nearuniq():
        return (dt.from_pydict(nu).into_partitions(4)
                .groupby("k").agg(col("v").sum().alias("sv"),
                                  col("w").sum().alias("sw"),
                                  col("x").sum().alias("sx"),
                                  col("v").count().alias("cv"),
                                  col("x").mean().alias("mx"))
                .to_pydict())

    common = {"DAFT_TPU_DEVICE": "0",
              "DAFT_TPU_DISTRIBUTED_SHUFFLE": "flight"}
    one_run(q_nearuniq, {**common, "DAFT_TPU_ADAPTIVE": "0"})  # warm-up
    # min-of-3 per mode: the combine-pass delta must clear run noise
    s_runs, a_runs = [], []
    for _ in range(3):
        s_out, s_wall, s_sh, _ = one_run(
            q_nearuniq, {**common, "DAFT_TPU_ADAPTIVE": "0"})
        s_runs.append(s_wall)
        a_out, a_wall, a_sh, a_cnt = one_run(
            q_nearuniq, {**common, "DAFT_TPU_ADAPTIVE": "1"})
        a_runs.append(a_wall)
    s_best, a_best = min(s_runs), min(a_runs)
    replan = {
        "rows": n,
        "match": a_out == s_out,
        "static_s": round(s_best, 3),
        "adaptive_s": round(a_best, 3),
        "static_runs_s": [round(x, 3) for x in s_runs],
        "adaptive_runs_s": [round(x, 3) for x in a_runs],
        "speedup_x": round(s_best / max(a_best, 1e-9), 3),
        "static_combine_rows_in": int(s_sh.get("combine_rows_in", 0)),
        "adaptive_combine_rows_in": int(a_sh.get("combine_rows_in", 0)),
        "decisions": {k: int(v) for k, v in sorted(a_cnt.items())},
    }

    # ---- probe 2: calibrated NDV ratio flips a footer-mispredicted
    # combine — k has 500 true values spread over a ~5M range, so the
    # footer NDV (min/max range clamped to rows) reads near-unique
    import pyarrow as pa
    import pyarrow.parquet as pq
    import tempfile
    nrows, ndv = 600_000, 500
    d = tempfile.mkdtemp(prefix="daft_tpu_adaptive_bench_")
    k = ((np.arange(nrows) % ndv) * 9973).astype(np.int64)
    for i in range(4):
        sl = slice(i * nrows // 4, (i + 1) * nrows // 4)
        pq.write_table(pa.table({"k": k[sl],
                                 "v": np.arange(nrows)[sl].astype(
                                     "float64")}),
                       os.path.join(d, f"{i}.parquet"))

    def q_footer():
        return (dt.read_parquet(os.path.join(d, "*.parquet"))
                .groupby("k").agg(col("v").sum()).to_pydict())

    cal_dir = tempfile.mkdtemp(prefix="daft_tpu_calibration_")
    cal_env = {**common, "DAFT_TPU_ADAPTIVE": "1",
               "DAFT_TPU_CALIBRATION": "1",
               "DAFT_TPU_CALIBRATION_DIR": cal_dir,
               "DAFT_TPU_CALIBRATION_MIN_SAMPLES": "1"}
    from daft_tpu.context import execution_config_ctx
    with execution_config_ctx(scan_tasks_min_size_bytes=1 << 18,
                              default_morsel_size=4096):
        # discarded warm-up (feedback OFF): jit traces / footer caches
        # are one-time costs that must not skew the warm-vs-warm walls
        one_run(q_footer, {**common, "DAFT_TPU_ADAPTIVE": "0"})
        # first pass: hard-coded constants decline the combine (footer
        # reads near-unique); the run OBSERVES the actual/footer ratio
        f_out, f_wall, f_sh, _ = one_run(q_footer, cal_env)
        static_dec = costmodel.combine_wins_pure(nrows, nrows, 4)
        saved = {k2: os.environ.get(k2) for k2 in cal_env}
        os.environ.update(cal_env)
        try:
            ratio = cal.summary().get("NDV_FOOTER_RATIO", {}).get(
                "value") or 1.0
        finally:
            for k2, v in saved.items():
                if v is None:
                    os.environ.pop(k2, None)
                else:
                    os.environ[k2] = v
        # calibrated re-run: the observed ratio damps the footer
        # evidence and flips the combine ON
        dc0 = dict(costmodel.decision_counts.get("shuffle_combine",
                                                 {"device": 0}))
        c_out, c_wall, c_sh, c_cnt = one_run(q_footer, cal_env)
        dc1 = costmodel.decision_counts.get("shuffle_combine",
                                            {"device": 0})
        calibrated_dec = dc1.get("device", 0) > dc0.get("device", 0)
        # static CONTROL at the same warmth (feedback off — the
        # hard-coded decision): the wall the calibrated re-plan must
        # beat on this mis-estimated data
        g_out, g_wall, _, _ = one_run(
            q_footer, {**common, "DAFT_TPU_ADAPTIVE": "0",
                       "DAFT_TPU_CALIBRATION": "0"})
    calibrated = {
        "rows": nrows, "true_ndv": ndv,
        "footer_ndv_overestimate_x": round(nrows / ndv, 1),
        "match": c_out == f_out,
        "observed_ndv_ratio": round(ratio, 5),
        "static_combine_decision": bool(static_dec),
        "calibrated_combine_decision": calibrated_dec,
        "decision_changed": bool(static_dec) != calibrated_dec,
        "first_pass_s": round(f_wall, 3),
        "calibrated_pass_s": round(c_wall, 3),
        "static_control_s": round(g_wall, 3),
        "static_control_match": g_out == f_out,
        "speedup_x": round(g_wall / max(c_wall, 1e-9), 3),
        "first_combine_rows_out": int(f_sh.get("combine_rows_out", 0)),
        "calibrated_combine_rows_out":
            int(c_sh.get("combine_rows_out", 0)),
        "calibrated_combine_rows_in":
            int(c_sh.get("combine_rows_in", 0)),
        "wire_mbps_observed": round(
            (cal.summary().get("SHUFFLE_WIRE_BPS", {}).get("value")
             or 0.0) / 1e6, 1),
        "decisions": {k2: int(v) for k2, v in sorted(c_cnt.items())},
    }
    # the gate rides the calibrated probe: on footer-mispredicted data
    # the re-planned (calibrated) run must beat the static-decision wall
    # with identical results, AND the calibrated model must have changed
    # a decision vs the hard-coded constants. Probe 1's wall is reported
    # but not gated — one avoided combine pass is real yet small next to
    # run noise on a loaded box.
    return {"replan": replan, "calibrated": calibrated,
            "gate_pass": bool(replan["match"] and calibrated["match"]
                              and calibrated["static_control_match"]
                              and calibrated["speedup_x"] > 1.0
                              and calibrated["decision_changed"])}


def run_shuffle_bench():
    """``--shuffle``: microbench of the distributed shuffle data plane.
    Two probes, both landing in the artifact so the trajectory finally
    captures shuffle throughput:

    1. a TPC-H Q1-shaped distributed group-by (low-cardinality keys,
       sum/mean/count aggs) through the flight shuffle with the fast path
       OFF (no combine, no compression) and ON (defaults) — rows/s through
       the hash exchange, bytes over the wire, compression ratio, combine
       reduction factor;
    2. a multi-source reduce fetch, serial vs the bounded parallel pool —
       the overlap evidence (parallel wall < serial sum).
    """
    import numpy as np

    import daft_tpu as dt
    import daft_tpu.context as dctx
    from daft_tpu import col
    from daft_tpu.distributed import shuffle_service as ss
    from daft_tpu.runners.distributed_runner import DistributedRunner

    rng = np.random.default_rng(8)
    n = 300_000
    data = {
        "rf": rng.integers(0, 3, n).tolist(),
        "ls": rng.integers(0, 2, n).tolist(),
        "qty": rng.integers(1, 50, n).astype("float64").tolist(),
        "price": rng.uniform(1, 100, n).round(2).tolist(),
    }

    def q1_shape(df):
        return (df.groupby("rf", "ls")
                .agg(col("qty").sum().alias("sum_qty"),
                     col("price").sum().alias("sum_price"),
                     col("qty").mean().alias("avg_qty"),
                     col("price").mean().alias("avg_price"),
                     col("qty").count().alias("cnt"))
                .sort("rf").to_pydict())

    def one_run(env):
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        runner = DistributedRunner(num_workers=3)
        old = dctx.get_context()._runner
        dctx.get_context().set_runner(runner)
        before = ss.shuffle_counters_snapshot()
        t0 = time.time()
        try:
            out = q1_shape(dt.from_pydict(data).into_partitions(4))
        finally:
            dctx.get_context().set_runner(old)
            if runner._manager is not None:
                runner._manager.shutdown()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        elapsed = time.time() - t0
        d = ss.shuffle_counters_delta(before)
        return out, elapsed, d

    common = {"DAFT_TPU_DISTRIBUTED_SHUFFLE": "flight",
              "DAFT_TPU_DEVICE": "0"}
    base_out, base_s, base_c = one_run({
        **common, "DAFT_TPU_SHUFFLE_COMBINE": "0",
        "DAFT_TPU_SHUFFLE_COMPRESSION": "none"})
    fast_out, fast_s, fast_c = one_run({
        **common, "DAFT_TPU_SHUFFLE_COMBINE": "auto",
        "DAFT_TPU_SHUFFLE_COMPRESSION": "lz4"})

    def wire(c):
        return int(c.get("bytes_written", 0))

    res = {
        "rows": n,
        "baseline": {  # pre-PR data plane: raw rows, uncompressed, serial
            "elapsed_s": round(base_s, 3),
            "rows_per_s": round(n / base_s, 1),
            "wire_bytes": wire(base_c),
            "rows_on_wire": int(base_c.get("rows_pushed", 0)),
        },
        "fast_path": {
            "elapsed_s": round(fast_s, 3),
            "rows_per_s": round(n / fast_s, 1),
            "wire_bytes": wire(fast_c),
            "rows_on_wire": int(fast_c.get("rows_pushed", 0)),
            "compression_ratio": round(
                fast_c.get("bytes_pushed_raw", 0)
                / max(wire(fast_c), 1), 3),
            "combine_reduction": round(
                fast_c.get("combine_rows_in", 0)
                / max(fast_c.get("combine_rows_out", 1), 1), 2),
            "fetch_wall_s": round(fast_c.get("fetch_span_us", 0) / 1e6, 4),
            "fetch_serial_equiv_s": round(
                fast_c.get("fetch_wall_us", 0) / 1e6, 4),
        },
        "wire_bytes_saved_ratio": round(
            wire(base_c) / max(wire(fast_c), 1), 2),
        # canonicalized: the query sorts by rf only, so tie order among
        # equal-rf groups is unspecified across the two runs
        "answers_match": _canon_rows(base_out) == _canon_rows(fast_out),
    }

    # probe 2: multi-source fetch overlap, serial loop vs the bounded pool
    import pyarrow as pa

    from daft_tpu.distributed.worker import FetchSpec, _ParallelFetch
    srv = ss.make_shuffle_server()
    caches = []
    big = pa.table({"x": np.arange(400_000, dtype=np.int64),
                    "y": rng.uniform(size=400_000)})
    for _ in range(6):
        c = ss.ShuffleCache()
        c.push(0, big)
        srv.register(c)
        caches.append(c)
    srcs = [(srv.address, c.shuffle_id) for c in caches]
    # discarded warm-up pass: both timed measurements below run against
    # warm page cache + warm server threads, so the speedup isolates
    # fetch OVERLAP rather than cache warmth
    for addr, sid in srcs:
        ss.fetch_partition(addr, sid, 0)
    t0 = time.time()
    for addr, sid in srcs:
        ss.fetch_partition(addr, sid, 0)
    serial_s = time.time() - t0
    t0 = time.time()
    parts = list(_ParallelFetch(FetchSpec(srcs, 0)))
    parallel_s = time.time() - t0
    for c in caches:
        srv.unregister(c.shuffle_id)
    srv.shutdown()
    res["fetch_overlap"] = {
        "sources": len(srcs),
        "bytes_per_source": int(big.nbytes),
        "serial_s": round(serial_s, 4),
        "parallel_s": round(parallel_s, 4),
        "speedup": round(serial_s / max(parallel_s, 1e-9), 2),
        "rows_fetched": sum(len(p) for p in parts),
    }

    # probe 3: codec spill/wire sizes on a real-size payload (the Q1
    # probe's wire tables are tiny combined group states where IPC
    # framing dominates and a ratio would mislead)
    comp = {}
    for codec in ("none", "lz4", "zstd"):
        saved = os.environ.get("DAFT_TPU_SHUFFLE_COMPRESSION")
        os.environ["DAFT_TPU_SHUFFLE_COMPRESSION"] = codec
        try:
            c = ss.ShuffleCache()
            c.push(0, big)
            c.close()
            comp[codec] = c.partition_size(0)
            c.cleanup()
        finally:
            if saved is None:
                os.environ.pop("DAFT_TPU_SHUFFLE_COMPRESSION", None)
            else:
                os.environ["DAFT_TPU_SHUFFLE_COMPRESSION"] = saved
    res["compression_bytes"] = comp
    if comp.get("none"):
        res["compression_ratio_lz4"] = round(
            comp["none"] / max(comp.get("lz4", 1), 1), 3)
    return res


def _mesh_exchange_child():
    """``--mesh-exchange-child``: one cold process (the parent sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the
    virtual pod mesh exists) driving ONE hash-repartition boundary
    through the distributed stage runner on the exchange path named by
    ``DAFT_TPU_EXCHANGE_PATH``. Prints one JSON line: warm elapsed,
    rows/s, the shuffle-plane counter delta (bytes per link: ici vs
    wire, stream counts, path decisions), and an order-insensitive
    row-set checksum for the parity gate."""
    import hashlib
    import shutil
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    import daft_tpu as dt
    import daft_tpu.context as dctx
    from daft_tpu import col
    from daft_tpu.distributed import shuffle_service as ss
    from daft_tpu.runners.distributed_runner import DistributedRunner

    n = int(os.environ.get("BENCH_MESH_ROWS", "400000"))
    nparts = 8  # == the virtual pod's mesh width
    nfiles = 8  # one scan task per file → map tasks shard over workers
    rng = np.random.default_rng(17)
    root = tempfile.mkdtemp(prefix="daft_tpu_meshbench_")
    per = n // nfiles
    for i in range(nfiles):
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 1 << 20, per)),
            "v": pa.array(rng.integers(0, 1 << 30, per)),
            "w": pa.array(rng.integers(0, 1 << 30, per)),
        }), os.path.join(root, f"part-{i}.parquet"))

    def q():
        df = dt.read_parquet(os.path.join(root, "*.parquet"))
        return df.repartition(nparts, col("k")).to_arrow()

    def checksum(t: "pa.Table"):
        arr = np.stack([t.column(c).to_numpy().astype(np.int64)
                        for c in ("k", "v", "w")], axis=1)
        arr = arr[np.lexsort(arr.T[::-1])]
        return hashlib.sha256(np.ascontiguousarray(arr).tobytes()) \
            .hexdigest()

    runner = DistributedRunner(num_workers=4)
    old = dctx.get_context()._runner
    dctx.get_context().set_runner(runner)
    try:
        q()  # warm-up: compiles, server boot, page cache, trace cache
        before = ss.shuffle_counters_snapshot()
        t0 = time.time()
        out = q()
        elapsed = time.time() - t0
        delta = ss.shuffle_counters_delta(before)
    finally:
        dctx.get_context().set_runner(old)
        if runner._manager is not None:
            runner._manager.shutdown()
        shutil.rmtree(root, ignore_errors=True)
    counters = {k: int(v) for k, v in sorted(delta.items())
                if k in ("ici_bytes", "ici_rows", "ici_exchanges",
                         "bytes_written", "bytes_fetched", "fetches",
                         "streams_registered", "hierarchical_streams",
                         "rows_pushed")
                or k.startswith("exchange_path_")}
    print(json.dumps({
        "path": os.environ.get("DAFT_TPU_EXCHANGE_PATH", "auto"),
        "rows": n,
        "partitions": nparts,
        "elapsed_s": round(elapsed, 4),
        "rows_per_s": round(n / elapsed, 1),
        "counters": counters,
        "checksum": checksum(out),
    }))


def run_mesh_exchange_bench():
    """``--shuffle`` family 2: the pod-native exchange ladder on a
    simulated multi-device pod (8 virtual CPU devices). One identical
    hash boundary (400k rows × 24 B into 8 partitions, 4 workers) runs
    per rung in a cold child process:

    - ``flight``       — per-worker map streams over the socket (today);
    - ``collective``   — the boundary rides the mesh all_to_all, zero
      Flight streams (admission forced so the virtual mesh is used);
    - ``hierarchical`` — workers split across two simulated pods; each
      pod exchanges intra-mesh and serves ONE stream per mesh.

    The artifact carries rows/s per rung, bytes per LINK (ici vs wire),
    stream counts (the hierarchical claim: streams == meshes, not
    workers), and the bit-parity verdict from the row-set checksums."""
    mesh_flags = "--xla_force_host_platform_device_count=8"

    def child(path, extra):
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": mesh_flags,
               # one scan task per file: map tasks really shard across
               # the 4 workers (flight registers one stream per task)
               "DAFT_SCAN_TASKS_MIN_SIZE_BYTES": "1",
               "DAFT_TPU_EXCHANGE_PATH": path, **extra}
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--mesh-exchange-child"],
            capture_output=True, text=True, timeout=420, cwd=REPO,
            env=env)
        merged = _merge_lines(proc.stdout or "")
        if merged is None:
            raise RuntimeError(
                f"mesh-exchange child ({path}) rc={proc.returncode}: "
                f"{(proc.stderr or '')[-500:]}")
        return merged

    flight = child("flight", {"DAFT_TPU_DEVICE": "0"})
    collective = child("collective", {"DAFT_TPU_DEVICE": "1",
                                      "DAFT_TPU_MESH_MIN_ROWS": "0"})
    hier = child("hierarchical", {
        "DAFT_TPU_DEVICE": "1", "DAFT_TPU_MESH_MIN_ROWS": "0",
        "DAFT_TPU_WORKER_TOPOLOGY":
            "podA=worker-0,worker-1;podB=worker-2,worker-3"})
    out = {"flight": flight, "collective": collective,
           "hierarchical": hier}
    out["parity"] = {
        "collective": collective["checksum"] == flight["checksum"],
        "hierarchical": hier["checksum"] == flight["checksum"]}
    out["collective_speedup_vs_flight"] = round(
        flight["elapsed_s"] / max(collective["elapsed_s"], 1e-9), 2)
    out["hierarchical_speedup_vs_flight"] = round(
        flight["elapsed_s"] / max(hier["elapsed_s"], 1e-9), 2)
    # the stream-count claim: flight registers one stream per map task,
    # hierarchical one per mesh
    out["streams"] = {
        "flight": flight["counters"].get("streams_registered", 0),
        "hierarchical": hier["counters"].get("streams_registered", 0),
        "meshes": 2}
    # bytes per link: what rode ICI instead of the wire
    out["bytes_per_link"] = {
        "flight_wire": flight["counters"].get("bytes_written", 0),
        "collective_ici": collective["counters"].get("ici_bytes", 0),
        "collective_wire": collective["counters"].get("bytes_written", 0),
        "hierarchical_ici": hier["counters"].get("ici_bytes", 0),
        "hierarchical_wire": hier["counters"].get("bytes_written", 0)}
    return out


def run_scan_bench():
    """``--scan``: microbench of the scan-side IO plane against a
    latency-injected local HTTP object store (every request pays a fixed
    service delay, modeling object-store RTT). One projected, filtered
    multi-file parquet read runs twice: the pre-PR path
    (``DAFT_TPU_IO_PLANNED_READS=0`` + ``DAFT_TPU_SCAN_PREFETCH=0`` —
    per-column-chunk ranged GETs, whole-task loads) and the fast path
    (defaults: planned coalesced ranges, parallel fetch,
    prefetch-pipelined tasks). Records GET-request reduction, scan
    wall-clock speedup, answer parity, and the per-query ``io`` stats
    block."""
    import http.server
    import shutil
    import tempfile
    import threading

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    import daft_tpu as dt
    import daft_tpu.observability as obs
    from daft_tpu import col
    from daft_tpu.io import read_planner as rp

    delay_s = float(os.environ.get("BENCH_SCAN_DELAY_MS", "15")) / 1e3
    nfiles, rows = 8, 160_000
    root = tempfile.mkdtemp(prefix="daft_tpu_scanbench_")
    rng = np.random.default_rng(9)
    for i in range(nfiles):
        t = pa.table({
            "seq": pa.array(np.arange(i * rows, (i + 1) * rows)),
            "k": pa.array(rng.integers(0, 1000, rows)),
            "v": pa.array(rng.uniform(size=rows)),
            "w": pa.array(rng.uniform(size=rows)),
            "pad_f": pa.array(rng.uniform(size=rows)),
            "pad_s": pa.array([f"pad-{j % 97:04d}" for j in range(rows)]),
        })
        pq.write_table(t, os.path.join(root, f"part-{i}.parquet"),
                       row_group_size=rows // 8)

    class _Store(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _file(self):
            p = os.path.join(root, self.path.lstrip("/"))
            return p if os.path.isfile(p) else None

        def do_HEAD(self):
            time.sleep(delay_s)
            p = self._file()
            if p is None:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Length", str(os.path.getsize(p)))
            self.end_headers()

        def do_GET(self):
            time.sleep(delay_s)
            p = self._file()
            if p is None:
                self.send_response(404)
                self.end_headers()
                return
            with open(p, "rb") as f:
                data = f.read()
            rng_hdr = self.headers.get("Range")
            if rng_hdr:
                spec = rng_hdr.split("=")[1]
                a, b = spec.split("-")
                start, end = int(a), min(int(b), len(data) - 1)
                chunk = data[start:end + 1]
                self.send_response(206)
            else:
                chunk = data
                self.send_response(200)
            self.send_header("Content-Length", str(len(chunk)))
            self.end_headers()
            self.wfile.write(chunk)

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Store)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    urls = [f"http://127.0.0.1:{srv.server_port}/part-{i}.parquet"
            for i in range(nfiles)]
    half = nfiles * rows // 2  # ordered seq → half the row groups prune

    def query():
        return (dt.read_parquet(urls)
                .where(col("seq") < half)
                .select("k", "v")
                .sum("v").to_pydict())

    def one_run(env):
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        before = rp.scan_counters_snapshot()
        t0 = time.time()
        try:
            out = query()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        elapsed = time.time() - t0
        return out, elapsed, rp.scan_counters_delta(before)

    try:
        # both runs pin their knobs via env (the context may have frozen
        # either set into its config at first touch; env always wins)
        naive_out, naive_s, naive_c = one_run(
            {"DAFT_TPU_IO_PLANNED_READS": "0", "DAFT_TPU_SCAN_PREFETCH": "0"})
        fast_out, fast_s, fast_c = one_run(
            {"DAFT_TPU_IO_PLANNED_READS": "1", "DAFT_TPU_SCAN_PREFETCH": "2"})
    finally:
        srv.shutdown()
        shutil.rmtree(root, ignore_errors=True)

    st = obs.last_query_stats()
    res = {
        "files": nfiles, "rows": nfiles * rows,
        "rows_scanned": half,
        "request_delay_ms": delay_s * 1e3,
        "naive": {
            "elapsed_s": round(naive_s, 3),
            "rows_per_s": round(half / naive_s, 1),
            "gets": int(naive_c.get("gets", 0)),
            "bytes_fetched": int(naive_c.get("bytes_fetched", 0)),
        },
        "fast_path": {
            "elapsed_s": round(fast_s, 3),
            "rows_per_s": round(half / fast_s, 1),
            "gets": int(fast_c.get("gets", 0)),
            "bytes_fetched": int(fast_c.get("bytes_fetched", 0)),
            "ranges_planned": int(fast_c.get("ranges_planned", 0)),
            "range_requests": int(fast_c.get("range_requests", 0)),
            "bytes_used": int(fast_c.get("bytes_used", 0)),
            "prefetch_wall_s": round(fast_c.get("scan_span_us", 0) / 1e6, 4),
            "prefetch_serial_equiv_s": round(
                fast_c.get("scan_task_us", 0) / 1e6, 4),
        },
        "request_reduction": round(
            naive_c.get("gets", 0) / max(fast_c.get("gets", 1), 1), 2),
        "scan_speedup": round(naive_s / max(fast_s, 1e-9), 2),
        "answers_match": _canon_rows(naive_out) == _canon_rows(fast_out),
        # the io stats block explain(analyze=True) renders for this query
        "io_stats_block": obs.render_io_block(st.io) if st is not None
        else None,
    }
    return res


def _pct(sorted_vals, p: float):
    """p-quantile of a pre-sorted list (nearest-rank)."""
    if not sorted_vals:
        return None
    i = min(int(p * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def run_serve_bench(root=None, duration_s=None, concurrency=None):
    """``--serve``: sustained mixed traffic through the query scheduler.

    Closed-loop clients (one per worker slot, 3 sessions) submit a
    rotating mix of TPC-H shapes (q1/q6/q3) + point lookups for
    ``BENCH_SERVE_SECONDS`` (default 20s) at ``BENCH_SERVE_CONCURRENCY``
    (default 4). Reports QPS, p50/p99 latency, queue wait, admission
    rejections, plan/result cache hit rates, the repeated-vs-cold mean
    latency ratio (the plan/result caches' amortization evidence), and
    the admission-accounting leak check (outstanding admitted bytes must
    return to zero after drain)."""
    import threading

    from benchmarking.tpch import queries as Q

    from daft_tpu import col, serving

    if root is None:
        # serving traffic is interactive-shaped: a dedicated small TPC-H
        # dataset (SF0.1) keeps per-query latency in the hundreds of ms
        # so a bounded run actually exercises repeats, queuing, and the
        # caches (SF1 queries run ~15s+ on this class of box — a 20s
        # window would barely complete one per worker)
        root = os.path.join(REPO, ".cache", "tpch_sf0.1_serve_v1")
        if not os.path.isdir(os.path.join(root, "lineitem")):
            from benchmarking.tpch.datagen import generate_tpch
            print("generating TPC-H SF0.1 (serve bench, one-time) …",
                  file=sys.stderr, flush=True)
            generate_tpch(root, 0.1, 2)
    duration_s = duration_s if duration_s is not None \
        else float(os.environ.get("BENCH_SERVE_SECONDS", "20"))
    concurrency = concurrency if concurrency is not None \
        else int(os.environ.get("BENCH_SERVE_CONCURRENCY", "4"))
    get_df = _get_df_factory(root)

    def lookup(k):
        return get_df("lineitem").where(col("l_orderkey") == k) \
            .select("l_orderkey", "l_partkey", "l_quantity",
                    "l_extendedprice").limit(10)

    shapes = [("q1", lambda: Q.q1(get_df)),
              ("q6", lambda: Q.q6(get_df)),
              ("q3", lambda: Q.q3(get_df))] + \
             [(f"lookup{k}", (lambda k=k: lookup(k)))
              for k in (1, 7, 32, 69)]
    sched = serving.QueryScheduler(concurrency=concurrency)
    recs = []
    rec_lock = threading.Lock()
    submit_counts = {}
    t_end = time.time() + duration_s

    def client(ci):
        i = ci
        while time.time() < t_end:
            name, fac = shapes[i % len(shapes)]
            i += concurrency
            with rec_lock:
                n_prior = submit_counts.get(name, 0)
                submit_counts[name] = n_prior + 1
            t0 = time.time()
            try:
                h = sched.submit(fac(), session=f"s{ci % 3}")
                h.result(timeout=120)
            except serving.AdmissionRejected as exc:
                with rec_lock:
                    recs.append((name, None, None, False,
                                 f"rejected:{exc.kind}"))
                continue
            except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                with rec_lock:
                    recs.append((name, None, None, False,
                                 f"error:{str(exc)[:80]}"))
                continue
            with rec_lock:
                recs.append((name, time.time() - t0, h.queue_wait_s,
                             n_prior == 0, "ok"))

    t_wall0 = time.time()
    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 150)
    wall = time.time() - t_wall0
    sched_counters = sched.counters_snapshot()
    outstanding = sched.admission.outstanding
    sched.shutdown()

    ok = [r for r in recs if r[4] == "ok"]
    lats = sorted(r[1] for r in ok)
    waits = sorted(r[2] for r in ok)
    cold = [r[1] for r in ok if r[3]]
    warm = [r[1] for r in ok if not r[3]]
    errors = [r[4] for r in recs if r[4].startswith("error")]
    pc_hits = sched_counters.get("plan_cache_hits", 0)
    pc_miss = sched_counters.get("plan_cache_misses", 0)
    rc_hits = sched_counters.get("result_cache_hits", 0)
    rc_miss = sched_counters.get("result_cache_misses", 0)
    out = {
        "concurrency": concurrency,
        "duration_s": round(wall, 2),
        "completed": len(ok),
        "qps": round(len(ok) / max(wall, 1e-9), 2),
        "latency_p50_ms": round(1e3 * (_pct(lats, 0.50) or 0), 2),
        "latency_p99_ms": round(1e3 * (_pct(lats, 0.99) or 0), 2),
        "queue_wait_mean_ms": round(
            1e3 * (sum(waits) / len(waits) if waits else 0), 2),
        "queue_wait_p99_ms": round(1e3 * (_pct(waits, 0.99) or 0), 2),
        "rejections": {
            k.replace("rejected_", ""): int(v)
            for k, v in sched_counters.items()
            if k.startswith("rejected_") and v},
        "plan_cache_hit_rate": round(
            pc_hits / max(pc_hits + pc_miss, 1), 3),
        "result_cache_hit_rate": round(
            rc_hits / max(rc_hits + rc_miss, 1), 3),
        "plan_cache_structure_hits": int(
            sched_counters.get("plan_cache_structure_hits", 0)),
        "cold_mean_ms": round(
            1e3 * sum(cold) / len(cold), 2) if cold else None,
        "repeat_mean_ms": round(
            1e3 * sum(warm) / len(warm), 2) if warm else None,
        "admitted_bytes_outstanding_after_drain": int(outstanding),
    }
    if cold and warm and sum(warm):
        out["repeat_speedup"] = round(
            (sum(cold) / len(cold)) / (sum(warm) / len(warm)), 2)
    try:
        from daft_tpu.device.runtime import compile_cache_counters
        out["jit_projection_cache"] = compile_cache_counters()
    except Exception:
        pass
    try:
        from daft_tpu.analysis import lock_sanitizer
        if lock_sanitizer.is_enabled():
            out["sanitizer_cycles"] = int(
                lock_sanitizer.counters_snapshot().get("graph_cycles", 0))
    except Exception:
        pass
    if errors:
        out["errors"] = errors[:5]
        out["n_errors"] = len(errors)
    return out


def run_serve_smoke() -> int:
    """``--serve-smoke``: the CI gate. A few seconds of mixed traffic over
    a small temp table; exit 1 on an admission-accounting leak
    (outstanding admitted bytes after drain), a wrong answer, or any
    lock-order sanitizer cycle. No TPC-H datagen required."""
    import shutil
    import tempfile

    import daft_tpu as dt
    from daft_tpu import col

    d = tempfile.mkdtemp(prefix="daft_tpu_serve_smoke_")
    try:
        n = 4000
        dt.from_pydict({
            "k": list(range(n)),
            "g": [i % 13 for i in range(n)],
            "v": [float(i % 97) for i in range(n)],
        }).write_parquet(os.path.join(d, "t"))
        root_glob = os.path.join(d, "t", "*.parquet")

        def table():
            return dt.read_parquet(root_glob)

        expected = table().groupby("g") \
            .agg(col("v").sum().alias("s")).sort("g").to_pydict()

        import threading

        from daft_tpu import serving
        shapes = [
            ("agg", lambda: table().groupby("g")
             .agg(col("v").sum().alias("s")).sort("g")),
            ("topk", lambda: table().sort("v", desc=True).limit(5)),
            ("lookup", lambda: table().where(col("k") == 1234).limit(1)),
        ]
        sched = serving.QueryScheduler(concurrency=4)
        t_end = time.time() + float(
            os.environ.get("BENCH_SERVE_SMOKE_SECONDS", "4"))
        failures = []
        done = [0]
        lock = threading.Lock()

        def client(ci):
            i = ci
            while time.time() < t_end:
                name, fac = shapes[i % len(shapes)]
                i += 1
                try:
                    h = sched.submit(fac(), session=f"s{ci % 3}")
                    ps = h.result(timeout=60)
                    if name == "agg":
                        got = ps.to_recordbatch().to_pydict()
                        if got != expected:
                            raise AssertionError(
                                "agg answer mismatch under concurrency")
                    with lock:
                        done[0] += 1
                except Exception as exc:  # noqa: BLE001
                    with lock:
                        failures.append(f"{name}: {exc!r}"[:200])

        threads = [threading.Thread(target=client, args=(ci,), daemon=True)
                   for ci in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        outstanding = sched.admission.outstanding
        counters = sched.counters_snapshot()
        sched.shutdown()
        cycles = 0
        try:
            from daft_tpu.analysis import lock_sanitizer
            if lock_sanitizer.is_enabled():
                cycles = int(lock_sanitizer.counters_snapshot()
                             .get("graph_cycles", 0))
        except Exception:
            pass
        result = {
            "serve_smoke": {
                "completed": done[0],
                "failures": failures[:5],
                "admitted_bytes_outstanding": int(outstanding),
                "sanitizer_cycles": cycles,
                "plan_cache_hits": int(counters.get("plan_cache_hits", 0)),
                "result_cache_hits": int(
                    counters.get("result_cache_hits", 0)),
            }}
        print(json.dumps(result), flush=True)
        if failures or outstanding or cycles or done[0] == 0:
            return 1
        return 0
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _fleet_make_table(prefix: str, n: int = 20000):
    """Temp parquet table for fleet traffic; returns (dir, glob)."""
    import tempfile

    import daft_tpu as dt
    d = tempfile.mkdtemp(prefix=prefix)
    dt.from_pydict({
        "k": list(range(n)),
        "g": [i % 13 for i in range(n)],
        "v": [float(i % 97) for i in range(n)],
    }).write_parquet(os.path.join(d, "t"))
    return d, os.path.join(d, "t", "*.parquet")


class _LatencyFileServer:
    """Serves ONE local file under every requested path, with a fixed
    per-request sleep — object-store GET latency emulation for the fleet
    bench. Distinct object names behave like distinct partitions in a
    bucket (path-keyed caches miss), and the sleep happens server-side
    in a blocked thread, so on a small CI host aggregate throughput is
    bounded by the fleet's admission slots × storage latency — the
    serving-capacity quantity the replica count actually scales — not by
    this host's core count."""

    def __init__(self, file_path: str, latency_s: float = 0.1):
        with open(file_path, "rb") as f:
            self.data = f.read()
        self.latency_s = latency_s
        self._httpd = None

    def start(self) -> str:
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        srv = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _serve(self, head_only: bool):
                time.sleep(srv.latency_s)
                body = srv.data
                code = 200
                rng = self.headers.get("Range")
                if rng and rng.startswith("bytes="):
                    a, _, b = rng[len("bytes="):].partition("-")
                    start = int(a or 0)
                    end = min(int(b) + 1 if b else len(body), len(body))
                    body, code = body[start:end], 206
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                # a stable ETag is the version signal that lets the
                # serving caches key remote-sourced plans (fingerprint
                # sources = size + etag, like a real object store)
                self.send_header("ETag", f'"bench-{len(srv.data)}"')
                self.end_headers()
                if not head_only:
                    self.wfile.write(body)

            def do_GET(self):
                try:
                    self._serve(head_only=False)
                except Exception:
                    pass

            def do_HEAD(self):
                try:
                    self._serve(head_only=True)
                except Exception:
                    pass

        import threading
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._httpd.daemon_threads = True
        t = threading.Thread(target=self._httpd.serve_forever,
                             daemon=True)
        t.start()
        return f"http://127.0.0.1:{self._httpd.server_address[1]}"

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


def _fleet_shapes(source, n_rows: int = 4000, heavy: bool = False,
                  label: str = ""):
    """SQL traffic mix. Default (smoke): ``source`` is a local glob; two
    repeat shapes (cacheable) + a rotating parameterized lookup whose
    25-literal cycle wraps, so the result cache dominates. Heavy
    (bench): ``source`` is a :class:`_LatencyFileServer` base URL; one
    repeat shape on a fixed object + two effectively-unique windowed
    aggregations per round, each scanning a DISTINCT object name — every
    miss pays real object-store GET latency, which is what makes
    aggregate QPS scale with replica count."""
    if heavy:
        agg = (f"SELECT g, sum(v) AS s FROM "
               f"read_parquet('{source}/hot.parquet') "
               "GROUP BY g ORDER BY g")

        def shape(i):
            if i % 3 == 0:
                return "agg", agg
            off = (i * 7919) % max(n_rows - 2000, 1)
            return "window", (
                f"SELECT g, sum(v) AS s, count(v) AS c FROM "
                f"read_parquet('{source}/w{label}-{off}.parquet') "
                f"WHERE k >= {off} AND k < {off + 2000} "
                "GROUP BY g ORDER BY g")
        return shape, agg

    agg = (f"SELECT g, sum(v) AS s FROM read_parquet('{source}') "
           "GROUP BY g ORDER BY g")
    topk = (f"SELECT k, v FROM read_parquet('{source}') "
            "ORDER BY v DESC, k LIMIT 5")

    def shape(i):
        j = i % 3
        if j == 0:
            return "agg", agg
        if j == 1:
            return "topk", topk
        kk = (i // 3) % 25
        return "lookup", (f"SELECT k, v FROM read_parquet('{source}') "
                          f"WHERE k = {kk * 37} LIMIT 5")
    return shape, agg


def _agg_matches(data, expected) -> bool:
    """Float-tolerant pydict comparison: group keys must match exactly,
    sums within 1e-6 relative (partial-sum order differs per process)."""
    try:
        if list(data.get("g", [])) != list(expected.get("g", [])):
            return False
        a, b = data.get("s", []), expected.get("s", [])
        if len(a) != len(b):
            return False
        return all(abs(float(x) - float(y))
                   <= 1e-6 * max(1.0, abs(float(y)))
                   for x, y in zip(a, b))
    except Exception:
        return False


def _fleet_traffic(router, glob, duration_s, n_clients, label,
                   expected_agg=None, n_rows: int = 4000,
                   heavy: bool = False):
    """Closed-loop SQL traffic through the router; returns the traffic
    summary (qps, latency percentiles, cache-outcome mix, failures)."""
    import threading
    shape, _agg_sql = _fleet_shapes(glob, n_rows=n_rows, heavy=heavy,
                                    label=label)
    recs = []
    failures = []
    lock = threading.Lock()
    t_end = time.time() + duration_s

    def client(ci):
        i = ci
        while time.time() < t_end:
            name, sql = shape(i)
            i += n_clients
            t0 = time.time()
            try:
                out = router.sql(sql, session=f"{label}-s{ci}",
                                 timeout_s=120.0)
            except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                with lock:
                    failures.append(f"{name}: {exc!r}"[:160])
                continue
            lat = time.time() - t0
            if name == "agg" and expected_agg is not None \
                    and not _agg_matches(out.get("data") or {},
                                         expected_agg):
                with lock:
                    failures.append("agg answer mismatch")
                continue
            with lock:
                recs.append(
                    (lat, (out.get("serving") or {}).get("result_cache"),
                     name))

    t0 = time.time()
    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 150)
    wall = time.time() - t0
    lats = sorted(r[0] for r in recs)
    outcomes = [r[1] for r in recs]
    hits = sum(1 for o in outcomes if o in ("hit", "fleet_hit"))
    misses = sum(1 for o in outcomes if o == "miss")
    # hit rate restricted to the REPEAT shape — the apples-to-apples
    # "does the fleet cache what one process caches" number, independent
    # of how many unique-miss shapes the mix carries
    hot = [o for _, o, n in recs if n == "agg"]
    hot_hits = sum(1 for o in hot if o in ("hit", "fleet_hit"))
    hot_misses = sum(1 for o in hot if o == "miss")
    return {
        "completed": len(recs),
        "qps": round(len(recs) / max(wall, 1e-9), 2),
        "latency_p50_ms": round(1e3 * (_pct(lats, 0.50) or 0), 2),
        "latency_p99_ms": round(1e3 * (_pct(lats, 0.99) or 0), 2),
        "result_cache_hit_rate": round(hits / max(hits + misses, 1), 3),
        "hot_shape_hit_rate": round(
            hot_hits / max(hot_hits + hot_misses, 1), 3),
        "fleet_hits": sum(1 for o in outcomes if o == "fleet_hit"),
        "failures": failures[:5],
        "n_failures": len(failures),
    }


def run_fleet_bench():
    """``--fleet``: 1 vs 3 subprocess driver replicas under identical
    closed-loop SQL traffic (grpc-free control-plane path). Reports the
    aggregate-QPS scaling factor, the fleet result-cache hit rate vs the
    single-replica run, and the cold-replica warm-start evidence (a 4th
    replica added after the fact answers its FIRST query from the fleet
    cache tier and inherits the gossiped state store)."""
    import shutil
    import threading

    from daft_tpu.fleet.cache_tier import CacheSidecar
    from daft_tpu.fleet.router import FleetRouter, SubprocessReplica

    duration_s = float(os.environ.get("BENCH_FLEET_SECONDS", "12"))
    # closed-loop client count must exceed (fleet slots × full latency /
    # exec latency) or the single replica never saturates its admission
    # slots and the ratio measures client count, not capacity
    n_clients = int(os.environ.get("BENCH_FLEET_CLIENTS", "36"))
    n_rows = int(os.environ.get("BENCH_FLEET_ROWS", "4000"))
    get_ms = float(os.environ.get("BENCH_FLEET_GET_MS", "150"))
    d, local_glob = _fleet_make_table("daft_tpu_fleet_bench_", n=n_rows)
    import glob as globmod
    pq_file = sorted(globmod.glob(local_glob))[0]
    store = _LatencyFileServer(pq_file, latency_s=get_ms / 1e3)
    base = store.start()
    out = {"duration_s": duration_s, "clients": n_clients,
           "rows": n_rows, "emulated_get_ms": get_ms}
    sidecar = CacheSidecar(budget_bytes=256 << 20)
    addr = sidecar.start()
    env = {"DAFT_TPU_FLEET_SIDECAR": addr, "DAFT_TPU_CALIBRATION": "1"}
    _shape, agg_sql = _fleet_shapes(base, n_rows=n_rows, heavy=True)
    try:
        # ---- phase 1: one replica (same sidecar, same env) ----------
        solo = SubprocessReplica.spawn("solo", env=env)
        router1 = FleetRouter([solo])
        _fleet_traffic(router1, base, min(3.0, duration_s), n_clients,
                       "warm", n_rows=n_rows, heavy=True)  # jit warm-up
        out["single"] = _fleet_traffic(router1, base, duration_s,
                                       n_clients, "single",
                                       n_rows=n_rows, heavy=True)
        solo.shutdown()
        # the sidecar keeps phase-1 results; phase 2 uses distinct
        # sessions but identical shapes — which is exactly the fleet
        # tier's job, so count those hits rather than hiding them
        # ---- phase 2: three replicas + gossip -----------------------
        reps = [SubprocessReplica.spawn(f"r{i}", env=env)
                for i in range(3)]
        router3 = FleetRouter(reps)
        stop_gossip = threading.Event()

        def gossip_loop():
            while not stop_gossip.wait(1.0):
                try:
                    router3.gossip_round()
                except Exception:
                    pass

        gt = threading.Thread(target=gossip_loop, daemon=True)
        gt.start()
        _fleet_traffic(router3, base, min(3.0, duration_s), n_clients,
                       "fwarm", n_rows=n_rows, heavy=True)  # per-replica
        out["fleet3"] = _fleet_traffic(router3, base, duration_s,
                                       n_clients, "fleet",
                                       n_rows=n_rows, heavy=True)
        out["fleet3"]["replicas"] = 3
        if out["single"]["qps"]:
            out["scaling_x"] = round(
                out["fleet3"]["qps"] / out["single"]["qps"], 2)
        # ---- phase 3: cold replica inherits fleet state -------------
        cold = SubprocessReplica.spawn("cold", env=env)
        router3.add_replica(cold)
        router3.gossip_round()  # cold pulls the union of fleet history
        inherited = len(cold.state_snapshot().get("origins") or {}) - 1
        t0 = time.time()
        first = cold.sql(agg_sql, session="cold-probe", timeout_s=120.0)
        first_ms = round(1e3 * (time.time() - t0), 2)
        # replay one EXACT window query a warm replica already ran: same
        # fingerprint history key, so a blind admission estimate must
        # seed from the gossiped fleet history instead of the default
        shape_fleet, _ = _fleet_shapes(base, n_rows=n_rows, heavy=True,
                                       label="fleet")
        cold.sql(shape_fleet(1)[1], session="cold-probe", timeout_s=120.0)
        counters = cold.counters()
        state = cold.state_snapshot().get("origins") or {}
        out["cold_replica"] = {
            "origins_inherited": inherited,
            "admission_history_inherited": sum(
                len((s or {}).get("admission") or {})
                for o, s in state.items() if o != "cold"),
            "calibration_inherited": sum(
                len((s or {}).get("calib") or {})
                for o, s in state.items() if o != "cold"),
            "first_query_result_cache":
                (first.get("serving") or {}).get("result_cache"),
            "first_query_ms": first_ms,
            "single_cold_p50_ms": out["single"]["latency_p50_ms"],
            # admission estimates seeded from the gossiped history when
            # the cost model is blind (the flat-default fallback path)
            "est_seeded_fleet": counters.get("est_seeded_fleet", 0),
            "est_seeded_history": counters.get("est_seeded_history", 0),
            "state_gen": counters.get("state_gen", 0),
        }
        stop_gossip.set()
        gt.join(timeout=5)
        out["router_counters"] = {
            k: v for k, v in router3.gauges().get("aggregate", {}).items()}
        out["scale_signal"] = router3.scale_signal()
        for r in reps + [cold]:
            r.shutdown()
        return out
    finally:
        sidecar.stop()
        store.stop()
        shutil.rmtree(d, ignore_errors=True)


def run_fleet_smoke() -> int:
    """``--fleet-smoke``: the CI gate for the serving fleet. Three REAL
    replica subprocesses behind the router take mixed SQL traffic; one
    replica is killed mid-run (traffic must re-route, answers must stay
    right) and one is gracefully drained after (its sessions must be
    released, not orphaned). Exit 1 on a wrong answer, an admission
    leak, an orphaned session queue, zero fleet-tier hits, or any
    lock-order sanitizer cycle inside any replica."""
    import shutil
    import threading

    import daft_tpu as dt
    from daft_tpu import col
    from daft_tpu.fleet.cache_tier import CacheSidecar
    from daft_tpu.fleet.router import FleetRouter, SubprocessReplica

    d, glob = _fleet_make_table("daft_tpu_fleet_smoke_", n=4000)
    sidecar = CacheSidecar(budget_bytes=64 << 20)
    addr = sidecar.start()
    problems = []
    try:
        expected = dt.read_parquet(glob).groupby("g") \
            .agg(col("v").sum().alias("s")).sort("g").to_pydict()
        reps = [SubprocessReplica.spawn(
            f"r{i}", env={"DAFT_TPU_FLEET_SIDECAR": addr})
            for i in range(3)]
        router = FleetRouter(reps)
        duration_s = float(
            os.environ.get("BENCH_FLEET_SMOKE_SECONDS", "8"))
        traffic = {}

        def run_traffic():
            traffic.update(_fleet_traffic(
                router, glob, duration_s, 6, "smoke",
                expected_agg=expected))

        tt = threading.Thread(target=run_traffic, daemon=True)
        tt.start()
        time.sleep(duration_s * 0.4)
        router.gossip_round()
        victim = reps[0].name
        router.kill(victim)   # mid-traffic crash: re-route must absorb
        tt.join(timeout=duration_s + 160)
        router.gossip_round()
        if traffic.get("completed", 0) == 0:
            problems.append("no queries completed")
        # the kill window races in-flight requests: those surface as
        # recorded failures; anything else (wrong answer) is fatal
        fatal = [f for f in traffic.get("failures", [])
                 if "mismatch" in f]
        if fatal:
            problems.append(f"wrong answers: {fatal}")
        if traffic.get("fleet_hits", 0) == 0:
            problems.append("no fleet cache-tier hits across replicas")
        alive = [r for r in reps if r.name != victim]
        # graceful drain: sessions must be RELEASED on the drained
        # replica (no orphaned queues) and re-homed by the router
        drained = alive[0]
        router.drain(drained.name)
        leftover = drained.sessions()
        if leftover:
            problems.append(
                f"orphaned session queues on drained replica: {leftover}")
        for r in alive:
            g = r.gauges()
            if g.get("admitted_bytes", 0):
                problems.append(
                    f"admission leak on {r.name}: {g['admitted_bytes']}")
            c = r.counters()
            if c.get("lock_graph_cycles", 0):
                problems.append(
                    f"lock-order cycles on {r.name}: "
                    f"{c['lock_graph_cycles']}")
            if len([o for o in (r.state_snapshot().get("origins") or {})
                    ]) < 2:
                problems.append(f"gossip never reached {r.name}")
        result = {"fleet_smoke": {
            "completed": traffic.get("completed", 0),
            "qps": traffic.get("qps", 0),
            "fleet_hits": traffic.get("fleet_hits", 0),
            "result_cache_hit_rate":
                traffic.get("result_cache_hit_rate", 0),
            "rerouted_failures_during_kill":
                traffic.get("n_failures", 0),
            "killed": victim, "drained": drained.name,
            "problems": problems[:8],
        }}
        print(json.dumps(result), flush=True)
        for r in reps:
            r.shutdown()
        return 1 if problems else 0
    finally:
        sidecar.stop()
        shutil.rmtree(d, ignore_errors=True)


def run_obs_bench():
    """``--obs``: tracing-overhead measurement on the serve-bench mixed
    workload. Three runs of the same closed-loop traffic: tracing OFF,
    SAMPLED (10%), and FULL — the artifact records QPS and p99 deltas
    vs the off baseline. Gate (documented in README): full tracing must
    cost < 5% QPS."""
    modes = [("off", {"DAFT_TPU_TRACE": "0"}),
             ("sampled", {"DAFT_TPU_TRACE": "1",
                          "DAFT_TPU_TRACE_SAMPLE": "0.1"}),
             ("full", {"DAFT_TPU_TRACE": "1",
                       "DAFT_TPU_TRACE_SAMPLE": "1.0"})]
    duration = float(os.environ.get("BENCH_OBS_SECONDS", "12"))
    # discarded FULL-LENGTH warm-up: the first serve run pays datagen +
    # per-shape jit warm-up (7 query shapes); charging any of that to
    # the "off" baseline would fake a tracing speedup — a 6s warm-up
    # measurably wasn't enough (first committed r13 attempt)
    run_serve_bench(duration_s=duration)
    out = {}
    for name, env in modes:
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            r = run_serve_bench(duration_s=duration)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        out[name] = {"qps": r.get("qps"),
                     "latency_p50_ms": r.get("latency_p50_ms"),
                     "latency_p99_ms": r.get("latency_p99_ms"),
                     "completed": r.get("completed")}
    base_qps = out["off"]["qps"] or 1e-9
    for name in ("sampled", "full"):
        qps = out[name]["qps"] or 0
        out[name]["qps_overhead_pct"] = round(
            100.0 * (base_qps - qps) / base_qps, 2)
        p99b = out["off"]["latency_p99_ms"] or 1e-9
        out[name]["p99_delta_pct"] = round(
            100.0 * ((out[name]["latency_p99_ms"] or 0) - p99b) / p99b, 2)
    out["gate_full_overhead_pct"] = 5.0
    out["gate_pass"] = out["full"]["qps_overhead_pct"] < 5.0
    return out


def run_obs_smoke() -> int:
    """``--obs-smoke``: the observability CI gate. Runs a traced local
    query and a traced distributed query, validates the exported Chrome
    trace against the schema (required fields, monotonic non-negative
    timestamps, matched phases), checks parent-child consistency (no
    orphan spans), scrapes the dashboard's ``/metrics`` with the strict
    text-format parser, and exercises the flight recorder's byte-cap
    rotation. Exit 1 on any failure (daft-lint runs as its own CI
    step)."""
    import tempfile
    import urllib.request

    import daft_tpu as dt
    import daft_tpu.context as dctx
    from daft_tpu import col, dashboard, tracing
    from daft_tpu import observability as obs
    from daft_tpu.runners.distributed_runner import DistributedRunner

    failures = []
    tmp = tempfile.mkdtemp(prefix="daft_tpu_obs_smoke_")
    os.environ["DAFT_TPU_TRACE"] = "1"
    os.environ["DAFT_TPU_TRACE_DIR"] = os.path.join(tmp, "traces")
    os.environ["DAFT_TPU_QUERY_LOG"] = os.path.join(tmp, "queries.jsonl")
    os.environ["DAFT_TPU_QUERY_LOG_BYTES"] = "20000"
    try:
        # 1) traced local query → exported chrome trace validates
        df = (dt.from_pydict({"x": list(range(5000)),
                              "g": [i % 11 for i in range(5000)]})
              .where(col("x") > 10)
              .groupby("g").agg(col("x").sum().alias("s")))
        assert len(df.sort("g").to_pydict()["g"]) == 11
        import glob as g
        files = g.glob(os.path.join(tmp, "traces", "trace_*.json"))
        if not files:
            failures.append("no chrome trace exported for local query")
        else:
            doc = json.load(open(files[0]))
            probs = tracing.validate_chrome_trace(doc)
            if probs:
                failures.append(f"chrome trace invalid: {probs[:3]}")
            names = {e["name"] for e in doc["traceEvents"]
                     if e.get("ph") == "X"}
            for want in ("query", "plan:optimize"):
                if want not in names:
                    failures.append(f"trace missing {want!r} span")

        # 2) traced distributed query → merged trace, no orphans,
        #    worker/fetch spans present
        runner = DistributedRunner(num_workers=2)
        old = dctx.get_context()._runner
        dctx.get_context().set_runner(runner)
        try:
            ddf = (dt.from_pydict({"k": [i % 5 for i in range(4000)],
                                   "v": [float(i) for i in range(4000)]})
                   .into_partitions(3)
                   .groupby("k").agg(col("v").sum().alias("s")))
            assert len(ddf.sort("k").to_pydict()["k"]) == 5
        finally:
            dctx.get_context().set_runner(old)
            if runner._manager is not None:
                runner._manager.shutdown()
        stats = obs.last_query_stats()
        rec = stats.trace_ctx.recorder if stats.trace_ctx else None
        if rec is None:
            failures.append("distributed query produced no trace")
        else:
            orph = tracing.orphan_spans(rec)
            if orph:
                failures.append(f"{len(orph)} orphan spans")
            kinds = {s["name"] for s in rec.spans()}
            for want in ("task", "task:run", "stage"):
                if want not in kinds:
                    failures.append(f"merged trace missing {want!r}")

        # 3) /metrics scrapes and parses strictly
        port = dashboard.launch(0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                text = r.read().decode()
            metrics = tracing.parse_prometheus_text(text)
            if "daft_tpu_flight_recorder_queries_total" not in metrics:
                failures.append("flight recorder metric missing")
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/api/history",
                    timeout=10) as r:
                hist = json.loads(r.read())
            if not hist:
                failures.append("/api/history empty after traced queries")
        finally:
            dashboard.shutdown()

        # 4) flight recorder rotates at its byte cap
        for i in range(200):
            tracing.flight_record({"ts": "t", "wall_us": i,
                                   "pad": "x" * 256})
        qlog = os.environ["DAFT_TPU_QUERY_LOG"]
        if not os.path.exists(qlog + ".1"):
            failures.append("flight recorder never rotated at byte cap")
        elif os.path.getsize(qlog) > 20000:
            failures.append("flight recorder exceeded its byte cap")

        print(json.dumps({"obs_smoke": {
            "failures": failures[:10], "ok": not failures}}), flush=True)
        return 1 if failures else 0
    finally:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
        for k in ("DAFT_TPU_TRACE", "DAFT_TPU_TRACE_DIR",
                  "DAFT_TPU_QUERY_LOG", "DAFT_TPU_QUERY_LOG_BYTES"):
            os.environ.pop(k, None)


def run_arrow_baseline():
    import pyarrow.compute as pc
    import pyarrow.dataset as pads
    t0 = time.time()
    t = pads.dataset(os.path.join(DATA, "lineitem")).to_table()
    t = t.filter(pc.field("l_shipdate") <= datetime.date(1998, 9, 2))
    disc = pc.multiply(t.column("l_extendedprice"),
                       pc.subtract(1.0, t.column("l_discount")))
    charge = pc.multiply(disc, pc.add(1.0, t.column("l_tax")))
    t = t.append_column("disc_price", disc).append_column("charge", charge)
    g = t.group_by(["l_returnflag", "l_linestatus"]).aggregate(
        [("l_quantity", "sum"), ("l_extendedprice", "sum"),
         ("disc_price", "sum"), ("charge", "sum"), ("l_quantity", "mean"),
         ("l_extendedprice", "mean"), ("l_discount", "mean"),
         ("l_quantity", "count")])
    g = g.sort_by([("l_returnflag", "ascending"),
                   ("l_linestatus", "ascending")])
    return g, time.time() - t0


def pinned_arrow_baseline():
    """Best-of-3 Arrow Q1 baseline, persisted once per dataset. The r2→r3
    headline `vs_baseline` swung 105×→13× purely on denominator contention;
    pinning makes consecutive runs agree. Delete the cache file to re-pin.

    Returns (num_q1_groups, seconds)."""
    cache = os.path.join(DATA, "arrow_baseline_q1.json")
    if os.path.exists(cache):
        with open(cache) as f:
            d = json.load(f)
        return d["q1_groups"], d["seconds"]
    best, groups = None, None
    for _ in range(3):
        tbl, s = run_arrow_baseline()
        groups = tbl.num_rows
        best = s if best is None else min(best, s)
    with open(cache, "w") as f:
        json.dump({"q1_groups": groups, "seconds": round(best, 3),
                   "method": "best-of-3, uncontended"}, f)
    return groups, best


# ----------------------------------------------------------- device child

def _emit(obj):
    print(json.dumps(obj), flush=True)


def _device_child():
    """Child-process entry with the device tier on. One JSON line per
    section, cheapest/most-important first, so a stall or timeout only
    loses the sections after it."""
    os.environ["DAFT_TPU_DEVICE"] = "1"
    budget = float(os.environ.get("BENCH_DEVICE_BUDGET_S", DEVICE_TIMEOUT))
    deadline = time.time() + budget * 0.92

    out, warm, hot = run_tpch_query(DATA, "q1")
    from daft_tpu.device import backend as dbackend
    # emit the headline BEFORE the extra spread samples: a timeout during
    # them must only lose the spread, never the Q1 section itself
    _emit({"warm": warm, "hot": hot,
           "groups": len(next(iter(out.values()))),
           "backend": dbackend.backend_name() or "host-fallback"})
    _, w3, h3 = run_tpch_query(DATA, "q1")  # 3 hot samples → median + spread
    _emit({"runs": sorted(round(x, 3) for x in (hot, w3, h3))})

    # single-chip kernel efficiency: MFU for the MXU grouped agg, HBM
    # roofline % for the memory-bound families (BASELINE's efficiency
    # currency). Round 6: repetition runs INSIDE one jit program
    # (lax.fori_loop) so the number measures silicon, not link RTT —
    # the r5 artifact's 0.23%/0.004% figures were mostly wire time. The
    # embedded `ledger` carries the per-dispatch accounting of the REAL
    # Q1 dispatches that already ran above.
    if time.time() < deadline:
        from daft_tpu.device import mfu
        # 1M rows saturates a real chip; a CPU backend (virtual-mesh dev
        # box) takes minutes at that size and would eat the child budget
        # before the suites — scale down, the numbers are only meaningful
        # on silicon anyway
        n_mfu = 1 << 20 if (dbackend.backend_name() or "cpu") != "cpu" \
            else 1 << 16
        _emit({"mfu": mfu.report(n=n_mfu)})

    for qn in ("q6", "q3", "q10"):
        if time.time() > deadline:
            return
        _, w, h = run_tpch_query(DATA, qn)
        _emit({f"{qn}_warm": round(w, 3), f"{qn}_hot": round(h, 3)})

    if time.time() < deadline:
        suite = run_tpch_suite(DATA, budget_s=deadline - time.time())
        _emit({"tpch_sf1_suite": suite})

    if time.time() < deadline:
        try:
            _emit({"tpcds": run_tpcds_trio(TPCDS_DATA)})
        except Exception as exc:
            _emit({"tpcds": {"error": str(exc)[:200]}})

    if time.time() < deadline:
        try:
            _emit({"laion": run_laion(LAION_DATA)})
        except Exception as exc:
            _emit({"laion": {"error": str(exc)[:200]}})

    if os.path.isdir(os.path.join(SF10_DATA, "lineitem")) \
            and time.time() < deadline:
        sf10 = run_tpch_suite(SF10_DATA, budget_s=deadline - time.time())
        _emit({"tpch_sf10_suite": sf10})

    # whole-suite per-dispatch ledger LAST: every device dispatch of every
    # section above is accounted (the committed artifact's evidence that
    # the efficiency numbers describe real engine work, not just the
    # synthetic harness)
    from daft_tpu.device import costmodel
    _emit({"mfu_ledger": costmodel.ledger_snapshot()})


def _try_device_tier(budget_s: float):
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--device-child"],
            capture_output=True, text=True, timeout=budget_s,
            cwd=REPO, env={**os.environ, "DAFT_TPU_DEVICE": "1",
                           "BENCH_DEVICE_BUDGET_S": str(budget_s)})
    except subprocess.TimeoutExpired as exc:
        print("device tier: timed out; using partial output",
              file=sys.stderr)
        partial = exc.stdout or b""
        if isinstance(partial, bytes):
            partial = partial.decode(errors="replace")
        return _merge_lines(partial)
    if proc.returncode != 0:
        print(f"device tier: child failed rc={proc.returncode}\n"
              f"{proc.stderr[-2000:]}", file=sys.stderr)
        return _merge_lines(proc.stdout or "")
    return _merge_lines(proc.stdout or "")


def _warmup_child():
    """``--warmup-child``: one cold process measuring the recompile tax.
    Runs q1/q6/q3 twice each under the armed retrace sanitizer and
    reports first/hot latency plus per-run trace/compile counters (the
    shape-discipline evidence: hot runs must show ZERO trace events).
    With BENCH_WARMUP_AOT=1 it runs the AOT warm-up first, so a
    populated JAX_COMPILATION_CACHE_DIR turns compiles into disk
    reads."""
    os.environ.setdefault("DAFT_TPU_DEVICE", "1")
    from daft_tpu.analysis import retrace_sanitizer as rs
    if not rs.is_enabled():
        rs.enable(1)
    out = {}
    if os.environ.get("BENCH_WARMUP_AOT") == "1":
        from daft_tpu.device import warmup
        t0 = time.time()
        st = warmup.warmup_session()
        out["aot"] = {"seconds": round(time.time() - t0, 3),
                      "size_classes": st.get("size_classes"),
                      "kernels": st.get("kernels"),
                      "fragments": st.get("fragments")}
    for qn in ("q1", "q6", "q3"):
        s0 = rs.counters_snapshot()
        _out, first, hot = run_tpch_query(DATA, qn)
        s2 = rs.counters_snapshot()
        # run_tpch_query runs warm+hot internally; re-split the counters
        # with one more hot run so the HOT figures are isolated
        s_hot0 = rs.counters_snapshot()
        t0 = time.time()
        run_tpch_query_once(DATA, qn)
        hot2 = time.time() - t0
        s_hot1 = rs.counters_snapshot()
        out[qn] = {
            "first_s": round(first, 3), "hot_s": round(min(hot, hot2), 3),
            "first_traces": int(s2.get("traces", 0) - s0.get("traces", 0)),
            "first_compiles": int(s2.get("compiles", 0)
                                  - s0.get("compiles", 0)),
            "first_compile_s": round(s2.get("compile_seconds", 0)
                                     - s0.get("compile_seconds", 0), 3),
            "hot_traces": int(s_hot1.get("traces", 0)
                              - s_hot0.get("traces", 0)),
            "hot_compiles": int(s_hot1.get("compiles", 0)
                                - s_hot0.get("compiles", 0)),
        }
    s = rs.summary()
    out["retrace_violations"] = s.get("violations", [])
    print(json.dumps(out))


def run_tpch_query_once(root, qname: str):
    from benchmarking.tpch import queries as Q
    get_df = _get_df_factory(root)
    return getattr(Q, qname)(get_df).to_pydict()


def run_warmup_bench():
    """``--warmup``: cold-process → first-query latency and hot repeat,
    with and without AOT warm-up + a persisted XLA compilation cache,
    plus per-query retrace counts (ROADMAP item 1's <5s warm-up gate).
    Three children: cold baseline; cache-populating AOT run; warm-start
    run re-reading the persisted cache."""

    def child(extra, budget=420.0):
        # NOTE: no DAFT_TPU_SANITIZE here — the lock sanitizer's proxy
        # overhead would skew the latency numbers; _warmup_child arms
        # the retrace listener directly, which is passive off the
        # trace path
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--warmup-child"],
            capture_output=True, text=True, timeout=budget, cwd=REPO,
            env={**os.environ, "DAFT_TPU_DEVICE": "1", **extra})
        merged = _merge_lines(proc.stdout or "")
        if merged is None:
            raise RuntimeError(
                f"warmup child rc={proc.returncode}: "
                f"{(proc.stderr or '')[-500:]}")
        return merged

    cold = child({"JAX_ENABLE_COMPILATION_CACHE": "0"})
    # ONE cache rule (device/backend.py): the directory the environment
    # names, else the fixed <repo>/.cache/jax — the path is part of the
    # cache key, so a directory that moves (mkdtemp, pid, time) never hits
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO, ".cache", "jax")
    aot_env = {"JAX_COMPILATION_CACHE_DIR": cache_dir,
               "DAFT_TPU_AOT_WARMUP": "1", "BENCH_WARMUP_AOT": "1"}
    populate = child(aot_env)
    persisted = child(aot_env)
    out = {"cold": cold, "aot_populate": populate,
           "aot_persisted": persisted}
    # the violations gate FIRST and unconditionally: a missing derived
    # metric below must never silently drop real violations from the
    # committed artifact
    out["violations"] = [
        v for child in (cold, populate, persisted)
        for v in child.get("retrace_violations", [])]
    try:
        cold_first = cold["q1"]["first_s"]
        warm_first = persisted["q1"]["first_s"]
        out["q1_cold_first_s"] = cold_first
        out["q1_aot_persisted_first_s"] = warm_first
        out["q1_first_query_speedup"] = round(cold_first / warm_first, 3) \
            if warm_first else None
        out["hot_zero_retraces"] = all(
            child[q]["hot_traces"] == 0
            for child in (cold, populate, persisted)
            for q in ("q1", "q6", "q3"))
        out["compile_s_cold_vs_persisted"] = [
            cold["q1"]["first_compile_s"],
            persisted["q1"]["first_compile_s"]]
    except (KeyError, TypeError):
        pass
    return out


def _device_pipeline_child():
    """``--device-pipeline-child``: one process running device-forced
    q1/q6 at a given ``DAFT_TPU_DEVICE_INFLIGHT``, optionally with a
    simulated transfer-bound link: ``BENCH_PIPE_LINK_MS`` sleeps at the
    engine's real upload/download chokepoints (``column.encode_batch``,
    ``pipeline.fetch_host``) — the scan bench's latency-injected object
    store, applied to the device link, so a CPU dev box exercises the
    overlap a slow-link chip would see.  Reports hot walls, answers
    (parity evidence), the pipeline overlap ledger row, and residency
    counters."""
    os.environ["DAFT_TPU_DEVICE"] = "1"
    os.environ.setdefault("DAFT_TPU_DEVICE_FORCE", "1")
    delay_ms = float(os.environ.get("BENCH_PIPE_LINK_MS", "0"))
    link_mbps = float(os.environ.get("BENCH_PIPE_LINK_MBPS", "40"))
    if delay_ms > 0:
        import jax

        import daft_tpu.device.column as dcol
        import daft_tpu.device.pipeline as dpipe
        real_fetch, real_encode = dpipe.fetch_host, dcol.encode_batch

        def _link_sleep(nbytes):
            # one RTT per transfer + wire time at the simulated
            # bandwidth — the r9 scan bench's latency-injected object
            # store, applied to the device link
            time.sleep(delay_ms / 1e3 + nbytes / (link_mbps * 1e6))

        def slow_fetch(tree):
            # charge the link only for REAL device transfers — numpy
            # passthroughs (already-fetched planes re-entering decode)
            # cost nothing on a real wire either
            dev = [x for x in jax.tree_util.tree_leaves(tree)
                   if isinstance(x, jax.Array)]
            if dev:
                _link_sleep(sum(int(x.nbytes) for x in dev))
            return real_fetch(tree)

        def slow_encode(batch, columns=None):
            dt = real_encode(batch, columns)
            # residency-reuse hits perform no upload — a real wire
            # carries nothing for them (symmetric with slow_fetch's
            # numpy-passthrough filter)
            if not dt.resident:
                _link_sleep(sum(
                    int(c.data.nbytes) + int(c.validity.nbytes)
                    for c in dt.columns.values()))
            return dt

        dpipe.fetch_host = slow_fetch
        dcol.encode_batch = slow_encode
    if os.environ.get("DAFT_TPU_AOT_WARMUP") == "1":
        from daft_tpu.device import warmup
        warmup.warmup_session()
    from daft_tpu.device import costmodel, pipeline as dpipe2
    out = {"window": int(os.environ.get("DAFT_TPU_DEVICE_INFLIGHT", "2")),
           "link_delay_ms": delay_ms}
    for qn in ("q1", "q6"):
        res, warm, hot = run_tpch_query(DATA, qn)
        out[qn] = {"warm_s": round(warm, 3), "hot_s": round(hot, 3),
                   "answer": {k: v[:8] for k, v in res.items()}}
    snap = costmodel.ledger_snapshot()
    out["pipeline_ledger"] = snap.get("pipeline", {})
    # per-dispatch-family evidence (grouped_agg / projection / argsort
    # rows with seconds + overlap fields where the pipeline drove them)
    out["mfu_ledger"] = snap
    out["residency"] = dpipe2.residency_counters()
    print(json.dumps(out))


def run_device_pipeline_bench():
    """``--device-pipeline``: pipelined vs synchronous device execution.
    Five cold children — windows {0 (synchronous), 2, BENCH_PIPE_WINDOW
    (default 4)} on the simulated slow link plus a bare {0, deep} pair —
    measure q1/q6 hot walls, verify bit-identical answers, and report
    the overlap ratio (serial-equivalent stage seconds vs pipelined
    active wall) plus the transfer seconds the window hid.  The
    headline gate: pipelined device q1 hot ≤ 0.6× the synchronous
    path on the transfer-bound configuration."""
    def child(window, delay_ms):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--device-pipeline-child"],
            capture_output=True, text=True, timeout=420, cwd=REPO,
            env={**os.environ, "DAFT_TPU_DEVICE": "1",
                 "DAFT_TPU_DEVICE_FORCE": "1",
                 "DAFT_TPU_DEVICE_INFLIGHT": str(window),
                 # r16 AOT warm-up rides along so the walls measure the
                 # pipeline, not first-trace compiles
                 "DAFT_TPU_AOT_WARMUP": "1",
                 # finer scan tasks → enough windows for the in-flight
                 # ladder to actually overlap on SF1
                 "DAFT_SCAN_TASKS_MIN_SIZE_BYTES": str(8 << 20),
                 "BENCH_PIPE_LINK_MS": str(delay_ms)})
        merged = _merge_lines(proc.stdout or "")
        if merged is None:
            raise RuntimeError(
                f"device-pipeline child rc={proc.returncode}: "
                f"{(proc.stderr or '')[-500:]}")
        return merged

    delay = float(os.environ.get("BENCH_PIPE_LINK_MS", "50"))
    deep = int(os.environ.get("BENCH_PIPE_WINDOW", "4"))
    sync = child(0, delay)
    piped2 = child(2, delay)
    piped_deep = child(deep, delay)
    bare_sync = child(0, 0)
    bare_piped = child(deep, 0)
    out = {"link_delay_ms": delay, "sync": sync,
           "pipelined_w2": piped2, f"pipelined_w{deep}": piped_deep,
           "bare_sync_hot_s": {qn: bare_sync[qn]["hot_s"]
                               for qn in ("q1", "q6")},
           "bare_pipelined_hot_s": {qn: bare_piped[qn]["hot_s"]
                                    for qn in ("q1", "q6")}}
    out["parity_all"] = all(
        piped2[qn]["answer"] == sync[qn]["answer"]
        and piped_deep[qn]["answer"] == sync[qn]["answer"]
        and bare_piped[qn]["answer"] == bare_sync[qn]["answer"]
        for qn in ("q1", "q6"))
    best = piped_deep if piped_deep["q1"]["hot_s"] <= piped2["q1"]["hot_s"] \
        else piped2
    out["best_window"] = best["window"]
    for qn in ("q1", "q6"):
        s, p = sync[qn]["hot_s"], best[qn]["hot_s"]
        out[f"{qn}_hot_ratio"] = round(p / s, 3) if s else None
        out[f"{qn}_hot_ratio_w2"] = round(
            piped2[qn]["hot_s"] / s, 3) if s else None
    led = best.get("pipeline_ledger") or {}
    if led.get("serial_equiv_s") and led.get("seconds"):
        out["overlap_x"] = led.get("overlap_x")
        out["transfer_s_hidden"] = round(
            led["serial_equiv_s"] - led["seconds"], 3)
    out["gate_q1_ratio_le_0.6"] = bool(
        out.get("q1_hot_ratio") is not None
        and out["q1_hot_ratio"] <= 0.6)
    return out


def _fusion_link_micro():
    """In-process micro: filter→project→top-k over an in-memory source,
    device-forced, per-operator vs fused-region, with the r17 simulated
    transfer-bound link charging every upload/download.  Per-operator
    must ship the FULL projected planes back for the host top-k; the
    fused region sorts in-program and transfers only the k-bucket — the
    download the region eliminates becomes measurable wall time on a
    CPU box the same way it would on a slow-link chip."""
    import jax
    import numpy as np

    import daft_tpu as dt
    import daft_tpu.device.column as dcol
    import daft_tpu.device.pipeline as dpipe
    from daft_tpu import col
    delay_ms = float(os.environ.get("BENCH_FUSION_LINK_MS", "2"))
    link_mbps = float(os.environ.get("BENCH_FUSION_LINK_MBPS", "40"))
    real_fetch, real_encode = dpipe.fetch_host, dcol.encode_batch
    xfer = {}

    def _link_sleep(nbytes):
        time.sleep(delay_ms / 1e3 + nbytes / (link_mbps * 1e6))

    def slow_fetch(tree):
        dev = [x for x in jax.tree_util.tree_leaves(tree)
               if isinstance(x, jax.Array)]
        if dev:
            nb = sum(int(x.nbytes) for x in dev)
            xfer["down_bytes"] = xfer.get("down_bytes", 0) + nb
            xfer["downloads"] = xfer.get("downloads", 0) + 1
            _link_sleep(nb)
        return real_fetch(tree)

    def slow_encode(batch, columns=None):
        t = real_encode(batch, columns)
        if not t.resident:   # residency hits carry nothing on a real wire
            nb = sum(int(c.data.nbytes) + int(c.validity.nbytes)
                     for c in t.columns.values())
            xfer["up_bytes"] = xfer.get("up_bytes", 0) + nb
            xfer["uploads"] = xfer.get("uploads", 0) + 1
            _link_sleep(nb)
        return t

    rng = np.random.default_rng(21)
    n = 1 << 21
    data = {"a": rng.integers(0, 100, n).astype(np.int64),
            "b": rng.normal(size=n), "c": rng.normal(size=n)}

    def q():
        df = dt.from_pydict(data)
        return (df.where(col("a") < 95)
                .select((col("b") * 2.0 + col("c")).alias("x"), col("a"))
                .sort(col("x"), desc=True).limit(32)
                .to_pydict())

    saved = {k: os.environ.get(k)
             for k in ("DAFT_TPU_FUSION", "DAFT_TPU_DEVICE_FORCE")}
    os.environ["DAFT_TPU_DEVICE_FORCE"] = "1"
    dpipe.fetch_host, dcol.encode_batch = slow_fetch, slow_encode
    res = {}
    try:
        for mode in ("0", "1"):
            os.environ["DAFT_TPU_FUSION"] = mode
            q()   # warm: traces + compiles off the measured run
            xfer.clear()
            t0 = time.time()
            out = q()
            res[mode] = {"hot_s": round(time.time() - t0, 3),
                         "rows": len(out["x"]),
                         "answer": _canon_rows(out), **xfer}
    finally:
        dpipe.fetch_host, dcol.encode_batch = real_fetch, real_encode
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    parity = res["0"]["answer"] == res["1"]["answer"]
    for m in res.values():
        m.pop("answer")
    fused, per_op = res["1"]["hot_s"], res["0"]["hot_s"]
    return {"rows": n, "link_delay_ms": delay_ms, "link_mbps": link_mbps,
            "per_operator": res["0"], "fused": res["1"],
            "fused_over_per_op": round(fused / per_op, 3) if per_op
            else None, "parity": parity}


def _fusion_child():
    """``--fusion-child``: one process, one fusion configuration (the
    driver sets DAFT_TPU_DEVICE / DAFT_TPU_FUSION / DAFT_TPU_CALIBRATION
    in the env).  Emits q1/q3/q6 walls + canonical answers (cross-config
    parity evidence), the SF1 suite wall, the ``region`` ledger family,
    and — with BENCH_FUSION_MICRO=1 — the simulated-link chain micro."""
    budget = float(os.environ.get("BENCH_FUSION_BUDGET_S", "360"))
    deadline = time.time() + budget * 0.92

    def safe_rows(rows):
        # date cells aren't JSON; stringified they still compare equal
        # across children
        return [[v if isinstance(v, (str, int, float, bool, type(None)))
                 else str(v) for v in r] for r in rows]

    for qn in ("q1", "q3", "q6"):
        out, warm, hot = run_tpch_query(DATA, qn)
        _emit({qn: {"warm_s": round(warm, 3), "hot_s": round(hot, 3),
                    "answer": safe_rows(_canon_rows(out))}})
    if os.environ.get("BENCH_FUSION_MICRO") == "1" \
            and time.time() < deadline:
        try:
            _emit({"link_micro": _fusion_link_micro()})
        except Exception as exc:
            _emit({"link_micro": {"error": str(exc)[:200]}})
    if time.time() < deadline:
        _emit({"tpch_sf1_suite": run_tpch_suite(
            DATA, budget_s=deadline - time.time())})
    from daft_tpu.device import costmodel, fragment
    snap = costmodel.ledger_snapshot()
    _emit({"region_ledger": snap.get("region", {}),
           "region_programs": len(fragment.fused_region_programs())})


def _rows_close(a, b, rtol=1e-6, atol=1e-6):
    """Order-insensitive row-set comparison with float tolerance: the
    fused region and the host tier sum in different orders, so revenue
    columns agree to ~1e-9 relative, not bitwise."""
    if a is None or b is None or len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                if va is None or vb is None:
                    if va is not vb:
                        return False
                elif abs(va - vb) > atol + rtol * abs(vb):
                    return False
            elif va != vb:
                return False
    return True


def run_fusion_bench():
    """``--fusion``: whole-query device compilation (round 21).  Three
    cold children over identical data — host, device per-fragment
    (DAFT_TPU_FUSION=0), device fused (DAFT_TPU_FUSION=auto) — report
    q1/q3/q6 hot walls + the SF1 suite wall; answers must agree across
    all three (``parity_all``).  Both device children run with the
    runtime-calibrated cost model (round 20) — the honest device tier,
    with observed rates routing device-losing fragments host.  The
    fused child also runs the simulated-link chain micro: per-operator
    vs one-program dispatch with every round-trip charged wire time."""
    budget = float(os.environ.get("BENCH_FUSION_BUDGET_S", "360"))

    def child(env):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--fusion-child"],
            capture_output=True, text=True, timeout=budget + 60, cwd=REPO,
            env={**os.environ, "BENCH_FUSION_BUDGET_S": str(budget),
                 **env})
        merged = _merge_lines(proc.stdout or "")
        if merged is None:
            raise RuntimeError(f"fusion child rc={proc.returncode}: "
                               f"{(proc.stderr or '')[-500:]}")
        return merged

    host = child({"DAFT_TPU_DEVICE": "0", "DAFT_TPU_FUSION": "0"})
    frag = child({"DAFT_TPU_DEVICE": "1", "DAFT_TPU_FUSION": "0",
                  "DAFT_TPU_CALIBRATION": "1"})
    fused = child({"DAFT_TPU_DEVICE": "1", "DAFT_TPU_FUSION": "auto",
                   "DAFT_TPU_CALIBRATION": "1", "BENCH_FUSION_MICRO": "1"})

    out = {"budget_s": budget}
    parity_all = True
    for qn in ("q1", "q3", "q6"):
        h, f, u = host.get(qn), frag.get(qn), fused.get(qn)
        if not (h and f and u):
            parity_all = False
            continue
        parity = _rows_close(f["answer"], h["answer"]) \
            and _rows_close(u["answer"], h["answer"])
        parity_all &= parity
        out[qn] = {"host_hot_s": h["hot_s"],
                   "device_per_fragment_hot_s": f["hot_s"],
                   "device_fused_hot_s": u["hot_s"],
                   "parity": parity}
    micro = fused.get("link_micro")
    if micro is not None:
        out["link_micro"] = micro
        if "parity" in micro:
            parity_all &= bool(micro["parity"])
    for name, c in (("host", host), ("device_per_fragment", frag),
                    ("device_fused", fused)):
        s = c.get("tpch_sf1_suite")
        if s is not None:
            out[f"sf1_suite_{name}"] = s
    out["region_ledger"] = fused.get("region_ledger", {})
    out["region_programs"] = fused.get("region_programs", 0)
    out["parity_all"] = parity_all
    return out


def _merge_lines(text: str):
    merged = {}
    for line in text.strip().splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict):
            merged.update(parsed)
    return merged or None


# ------------------------------------------------------------------ main

def main():
    skipped: list = []
    errors: dict = {}

    def section(name, fn, min_needed=5.0):
        """Run `fn` only if the global budget affords it; name it in
        `skipped_sections` otherwise; any exception lands LOUDLY in the
        top-level `section_errors`, never silently inside a detail dict."""
        if _remaining() < min_needed:
            skipped.append(name)
            return None
        try:
            return fn()
        except Exception as exc:
            errors[name] = str(exc)[:200]
            return None

    ensure_data()
    import glob as g

    import pyarrow.parquet as pq
    nrows = sum(pq.ParquetFile(p).metadata.num_rows
                for p in g.glob(f"{DATA}/lineitem/*.parquet"))

    base_groups, base_s = pinned_arrow_baseline()

    # host tier first: hang-free, guarantees a number is always reported.
    # Three runs (not two): the r4 postmortem showed the device-vs-host Q1
    # margin flipping sign inside run-to-run noise, so both tiers report
    # median-of-3 plus the spread, and a "win" is only claimed when the
    # margin exceeds the combined spread.
    os.environ["DAFT_TPU_DEVICE"] = "0"
    out, host_warm, host_hot = run_tpch_query(DATA, "q1")
    assert len(out["l_returnflag"]) == base_groups, \
        (len(out["l_returnflag"]), base_groups)
    _, h3w, h3h = run_tpch_query(DATA, "q1")
    host_runs = sorted([host_hot, h3w, h3h])

    host_med = host_runs[1]
    host_spread = host_runs[-1] - host_runs[0]
    detail = {
        "host_warm_s": round(host_warm, 3), "host_hot_s": round(host_hot, 3),
        "host_q1_runs_s": [round(x, 3) for x in host_runs],
        "host_q1_median_s": round(host_med, 3),
        "host_q1_spread_s": round(host_spread, 3),
        "arrow_cpu_baseline_s": round(base_s, 3), "lineitem_rows": nrows,
        "backend": "host",
        "total_budget_s": TOTAL_BUDGET,
    }
    for qn in ("q6", "q3", "q10"):
        r = section(f"{qn}_host", lambda qn=qn: run_tpch_query(DATA, qn))
        if r is not None:
            detail[f"{qn}_host_hot_s"] = round(min(r[1], r[2]), 3)

    ours = min(host_warm, host_hot)

    # device tier next (it carries the headline's best case and its own
    # per-section emission tolerates truncation); it gets at most half the
    # remaining budget so the host suites below always run too
    dev_budget = min(DEVICE_TIMEOUT, max(_remaining() * 0.5, 60.0))
    dev = (section("device_tier", lambda: _try_device_tier(dev_budget),
                   min_needed=60.0))
    if dev is not None and dev.get("backend") == "host-fallback":
        detail["device_backend"] = "host-fallback"
        dev = None
    if dev is not None:
        # independent sections are recorded regardless of the Q1 sanity
        # gate below — a Q1 regression must not silently hide them
        for k in ("q6_hot", "q3_hot", "q10_hot"):
            if k in dev:
                detail[f"{k.split('_')[0]}_device_hot_s"] = dev[k]
        for k in ("tpch_sf1_suite", "tpcds", "laion", "tpch_sf10_suite",
                  "mfu", "mfu_ledger"):
            if k in dev:
                detail[f"{k}_device"] = dev[k]
        if dev.get("groups") == base_groups:
            detail["device_warm_s"] = round(dev["warm"], 3)
            detail["device_hot_s"] = round(dev["hot"], 3)
            detail["device_backend"] = dev.get("backend")
            dev_runs = sorted(dev.get("runs") or [dev["hot"]])
            dev_med = dev_runs[len(dev_runs) // 2]
            dev_spread = dev_runs[-1] - dev_runs[0]
            detail["device_q1_runs_s"] = dev_runs
            detail["device_q1_median_s"] = round(dev_med, 3)
            detail["device_q1_spread_s"] = round(dev_spread, 3)
            # variance-aware verdict: a tier only "wins" Q1 when the median
            # margin exceeds the combined observed spread (r4: the claim
            # flipped sign between two same-box runs inside ±5%)
            margin = host_med - dev_med
            noise = host_spread + dev_spread
            detail["q1_winner"] = ("device" if margin > noise
                                   else "host" if -margin > noise else "tie")
            if dev["hot"] < ours:
                ours = dev["hot"]
                detail["backend"] = dev.get("backend", "device")
        elif "groups" in dev:
            detail["device_q1_mismatch"] = \
                {"groups": dev["groups"], "expected": base_groups}

    if "--chaos" in sys.argv:
        # seeded chaos run: recovery-event counts land in the artifact
        # (~55 s observed: Q3 distributed with ~30 map recomputations)
        r = section("chaos", lambda: run_chaos(DATA), min_needed=70.0)
        if r is not None:
            detail["chaos"] = r

    if "--shuffle" in sys.argv:
        # shuffle data-plane microbench: hash-exchange rows/s, wire bytes,
        # compression ratio, combine reduction, fetch overlap
        r = section("shuffle", run_shuffle_bench, min_needed=40.0)
        if r is not None:
            detail["shuffle_bench"] = r
        # pod-native exchange ladder: flight vs collective vs hierarchical
        # on the simulated 8-device pod (cold children), rows/s +
        # bytes-per-link + stream counts + parity
        r = section("mesh_exchange", run_mesh_exchange_bench,
                    min_needed=60.0)
        if r is not None:
            detail["mesh_exchange_bench"] = r

    if "--spill" in sys.argv or "--scale" in sys.argv:
        # out-of-core execution: forced-tiny-budget grace join + spilled
        # agg parity vs in-memory, spill bytes + recursion evidence, and
        # the r23 fast-path A/B (legacy serial+none vs pooled+lz4)
        r = section("spill", run_spill_bench, min_needed=40.0)
        if r is not None:
            detail["spill_bench"] = r

    if "--adaptive" in sys.argv:
        # self-tuning feedback loops: runtime re-plan vs static wall on
        # near-unique keys (identical results), calibrated NDV ratio
        # flipping a footer-mispredicted combine decision
        r = section("adaptive", run_adaptive_bench, min_needed=60.0)
        if r is not None:
            detail["adaptive_bench"] = r

    if "--scan" in sys.argv:
        # scan-side IO plane microbench: GET coalescing + parallel fetch +
        # prefetch pipelining against a latency-injected local object store
        r = section("scan", run_scan_bench, min_needed=40.0)
        if r is not None:
            detail["scan_bench"] = r

    if "--device-pipeline" in sys.argv:
        # async device pipeline: pipelined vs synchronous q1/q6 device
        # walls (simulated transfer-bound link), parity, overlap ratio
        r = section("device_pipeline", run_device_pipeline_bench,
                    min_needed=60.0)
        if r is not None:
            detail["device_pipeline_bench"] = r

    if "--fusion" in sys.argv:
        # whole-query compilation: host vs per-fragment vs fused-region
        # q1/q3/q6 + SF1 suite walls, link-charged chain micro, parity
        r = section("fusion", run_fusion_bench, min_needed=120.0)
        if r is not None:
            detail["fusion_bench"] = r

    if "--warmup" in sys.argv:
        # shape-discipline bench: cold vs AOT+persisted-cache first-query
        # latency + per-query retrace counts (hot repeats must be zero)
        r = section("warmup", run_warmup_bench, min_needed=60.0)
        if r is not None:
            detail["warmup_bench"] = r

    if "--obs" in sys.argv:
        # tracing-overhead measurement: off vs sampled vs full tracing on
        # the serve-bench mixed workload (QPS/p99 deltas, <5% full gate)
        r = section("obs", run_obs_bench, min_needed=120.0)
        if r is not None:
            detail["obs_bench"] = r

    if "--serve" in sys.argv:
        # serving plane: sustained mixed traffic through the query
        # scheduler — QPS, p50/p99 latency, queue wait, rejections,
        # plan/result cache hit rates, repeated-vs-cold latency ratio
        # min_needed covers one-time SF0.1 datagen on a fresh checkout
        r = section("serve", run_serve_bench, min_needed=120.0)
        if r is not None:
            detail["serve_bench"] = r

    if "--fleet" in sys.argv:
        # serving fleet: 1 vs 3 subprocess driver replicas under the same
        # closed-loop SQL traffic — aggregate-QPS scaling, shared cache-
        # tier hit rate, cold-replica warm-start from gossiped state
        r = section("fleet", run_fleet_bench, min_needed=90.0)
        if r is not None:
            detail["fleet_bench"] = r

    # --scale: the suite-trajectory mode — per-query spill/governor/RSS/
    # replan/strategy counters ride along in the artifact
    rich = "--scale" in sys.argv
    r = section("tpch_sf1_suite_host",
                lambda: run_tpch_suite(DATA, budget_s=_remaining() - 10,
                                       rich=rich),
                min_needed=20.0)
    if r is not None:
        detail["tpch_sf1_suite_host"] = r
    r = section("tpcds_host", lambda: run_tpcds_trio(TPCDS_DATA),
                min_needed=15.0)
    if r is not None:
        detail["tpcds_host"] = r
    r = section("laion_host", lambda: run_laion(LAION_DATA), min_needed=15.0)
    if r is not None:
        detail["laion_host"] = r

    if os.path.isdir(os.path.join(SF10_DATA, "lineitem")) \
            and os.environ.get("BENCH_SKIP_SF10") != "1":
        # last: whatever global budget is left, queries past it are named
        # reserve the worst observed single SF10 query (~90s) so the
        # last query to START cannot push the emit past the window
        r = section("tpch_sf10_suite_host",
                    lambda: run_tpch_suite(SF10_DATA,
                                           budget_s=_remaining() - 100,
                                           rich=True),
                    min_needed=110.0)
        if r is not None:
            detail["tpch_sf10_suite_host"] = r
            from daft_tpu.execution import governor as _gov
            # per-query bookends reset the peak, so the suite-wide max
            # is the max over the per-query peaks, not the live gauge
            detail["rss_peak_bytes"] = max(
                [int(q.get("rss_peak_bytes", 0))
                 for q in r.get("per_query", {}).values()]
                + [int(_gov.peak_rss_bytes())])

    # errors that older rounds buried inside detail dicts surface here too
    for k, v in list(detail.items()):
        if isinstance(v, dict) and "error" in v:
            errors.setdefault(k, v["error"])

    # Full detail goes to a file; stdout's LAST line is a compact summary.
    # Four rounds of driver artifacts failed to parse because the final JSON
    # line (~10 KB) overflowed the driver's 2000-char tail window — the
    # driver only sees the tail, so the line must stay well under that.
    full = {
        "metric": f"tpch_q1_sf{SF}_rows_per_sec_per_chip",
        "value": round(nrows / ours, 1),
        "unit": "rows/s",
        "vs_baseline": round(base_s / ours, 3),
        "detail": detail,
    }
    if skipped:
        full["skipped_sections"] = skipped
    if errors:
        full["section_errors"] = errors
    full["elapsed_s"] = round(time.time() - _T0, 1)

    results_dir = os.path.join(REPO, "benchmarking", "results")
    os.makedirs(results_dir, exist_ok=True)
    artifact = os.path.join(results_dir, "r23_bench_driver.json")
    with open(artifact, "w") as f:
        json.dump(full, f, indent=1)
    # progress/bulk lines first (NOT last): full detail for humans reading
    # the whole log, then the parseable compact line closes stdout
    print("bench detail written to " + artifact, flush=True)

    def _suite_total(d):
        return d.get("total_hot_s") if isinstance(d, dict) else None

    fam: dict = {}
    for side in ("host", "device"):
        q1k = f"{side}_q1_median_s"
        if q1k in detail:
            fam.setdefault("q1_sf1", {})[side] = detail[q1k]
            fam["q1_sf1"][f"{side}_spread"] = detail[f"{side}_q1_spread_s"]
        s = _suite_total(detail.get(f"tpch_sf1_suite_{side}"))
        if s is not None:
            fam.setdefault("tpch_sf1_22q", {})[side] = s
        s = _suite_total(detail.get(f"tpch_sf10_suite_{side}"))
        if s is not None:
            fam.setdefault("tpch_sf10", {})[side] = s
        lai = detail.get(f"laion_{side}")
        if isinstance(lai, dict) and "images_per_s" in lai:
            fam.setdefault("laion_img_per_s", {})[side] = lai["images_per_s"]
        ds = detail.get(f"tpcds_{side}")
        if isinstance(ds, dict) and not ds.get("error"):
            tot = sum(v for v in ds.values() if isinstance(v, (int, float)))
            fam.setdefault("tpcds_trio", {})[side] = round(tot, 3)

    compact = {
        "metric": full["metric"], "value": full["value"],
        "unit": "rows/s", "vs_baseline": full["vs_baseline"],
        "q1_winner": detail.get("q1_winner"),
        "families": fam,
        "backend": detail.get("backend"),
        "artifact": os.path.relpath(artifact, REPO),
        "elapsed_s": full["elapsed_s"],
    }
    m = detail.get("mfu_device")
    if isinstance(m, dict) and "error" not in m:
        compact["mfu"] = {
            "agg_mfu_pct": m.get("grouped_agg", {}).get("mfu_pct"),
            "agg_roofline_pct": m.get("grouped_agg", {}).get(
                "roofline_pct"),
            "join_roofline_pct": m.get("join", {}).get("roofline_pct"),
            "argsort_roofline_pct": m.get("argsort", {}).get(
                "roofline_pct"),
        }
    led = detail.get("mfu_ledger_device")
    if isinstance(led, dict) and led:
        compact["ledger_dispatches"] = {
            k: v.get("dispatches") for k, v in led.items()}
    ch = detail.get("chaos")
    if isinstance(ch, dict) and "error" not in ch:
        compact["chaos"] = {
            "match": ch.get("match"),
            "events": sum(ch.get("recovery_events", {}).values())}
    sb = detail.get("shuffle_bench")
    if isinstance(sb, dict) and "error" not in sb:
        compact["shuffle"] = {
            "rows_per_s": sb["fast_path"]["rows_per_s"],
            "wire_saved": sb.get("wire_bytes_saved_ratio"),
            "combine_x": sb["fast_path"].get("combine_reduction"),
            "fetch_speedup": sb.get("fetch_overlap", {}).get("speedup")}
    me = detail.get("mesh_exchange_bench")
    if isinstance(me, dict) and "error" not in me:
        compact["mesh"] = {
            "coll_x": me.get("collective_speedup_vs_flight"),
            "hier_x": me.get("hierarchical_speedup_vs_flight"),
            "parity": all(me.get("parity", {}).values()),
            "hier_streams": me.get("streams", {}).get("hierarchical")}
    sc = detail.get("scan_bench")
    if isinstance(sc, dict) and "error" not in sc:
        compact["scan"] = {
            "req_reduction": sc.get("request_reduction"),
            "speedup": sc.get("scan_speedup"),
            "match": sc.get("answers_match")}
    sp = detail.get("spill_bench")
    if isinstance(sp, dict) and "error" not in sp:
        compact["spill"] = {
            "join_match": sp.get("join_match"),
            "agg_match": sp.get("agg_match"),
            "bytes": sp.get("spill_bytes_written"),
            "recursions": sp.get("recursions"),
            "slowdown_x": sp.get("slowdown_x"),
            "fast_x": sp.get("fast_vs_legacy_wall_x"),
            "disk_ratio": sp.get("fast_vs_legacy_disk_ratio")}
    ad = detail.get("adaptive_bench")
    if isinstance(ad, dict) and "error" not in ad:
        compact["adaptive"] = {
            "gate_pass": ad.get("gate_pass"),
            "cal_speedup_x": ad.get("calibrated", {}).get("speedup_x"),
            "match": ad.get("replan", {}).get("match"),
            "cal_decision_changed":
                ad.get("calibrated", {}).get("decision_changed"),
            "ndv_ratio":
                ad.get("calibrated", {}).get("observed_ndv_ratio")}
    sv = detail.get("serve_bench")
    if isinstance(sv, dict) and "error" not in sv:
        compact["serve"] = {
            "qps": sv.get("qps"),
            "p99_ms": sv.get("latency_p99_ms"),
            "repeat_x": sv.get("repeat_speedup"),
            "rc_hit": sv.get("result_cache_hit_rate"),
            "leak": sv.get("admitted_bytes_outstanding_after_drain")}
    fl = detail.get("fleet_bench")
    if isinstance(fl, dict) and "error" not in fl:
        compact["fleet"] = {
            "scaling_x": fl.get("scaling_x"),
            "qps1": fl.get("single", {}).get("qps"),
            "qps3": fl.get("fleet3", {}).get("qps"),
            "rc_hit": fl.get("fleet3", {}).get("result_cache_hit_rate"),
            "cold_first": fl.get("cold_replica", {}).get(
                "first_query_result_cache")}
    ob = detail.get("obs_bench")
    if isinstance(ob, dict) and "error" not in ob:
        compact["obs"] = {
            "full_overhead_pct": ob.get("full", {}).get(
                "qps_overhead_pct"),
            "sampled_overhead_pct": ob.get("sampled", {}).get(
                "qps_overhead_pct"),
            "gate_pass": ob.get("gate_pass")}
    if skipped:
        compact["n_skipped"] = len(skipped)
    if errors:
        compact["n_errors"] = len(errors)
    # hard cap: drop optional keys until the line fits the driver's window
    for drop in ("obs", "fleet", "serve", "scan", "adaptive",
                 "spill", "shuffle", "mesh", "chaos", "ledger_dispatches",
                 "mfu", "families", "q1_winner", "backend"):
        if len(json.dumps(compact)) <= 1500:
            break
        compact.pop(drop, None)
    line = json.dumps(compact)
    assert len(line) <= 1500, len(line)
    print(line)


if __name__ == "__main__":
    if "--device-child" in sys.argv:
        _device_child()
    elif "--device-pipeline-child" in sys.argv:
        _device_pipeline_child()
    elif "--mesh-exchange-child" in sys.argv:
        _mesh_exchange_child()
    elif "--fusion-child" in sys.argv:
        _fusion_child()
    elif "--warmup-child" in sys.argv:
        _warmup_child()
    elif "--fuzz-smoke" in sys.argv:
        # CI gate: differential plan fuzzer across all engine mode
        # matrices with the plan sanitizer armed — any mismatch vs the
        # unoptimized reference or plan-contract violation exits 1
        sys.exit(run_fuzz_smoke())
    elif "--scale-smoke" in sys.argv:
        # CI gate: forced-spill full 22-query suite at a small SF under
        # the sanitizer — wrong answers, RSS past the ceiling, leaked
        # spill files, or lock cycles exit 1
        sys.exit(run_scale_smoke())
    elif "--serve-smoke" in sys.argv:
        # CI gate: no datagen, no device tier — a few seconds of serving
        # traffic with leak + sanitizer-cycle checks
        sys.exit(run_serve_smoke())
    elif "--obs-smoke" in sys.argv:
        # CI gate: traced local + distributed queries, chrome-trace schema
        # validation, strict /metrics parse, flight-recorder rotation
        sys.exit(run_obs_smoke())
    elif "--fleet-smoke" in sys.argv:
        # CI gate: 3 real replica subprocesses behind the router; mixed
        # traffic + a mid-run kill and a graceful drain, with answer /
        # admission-leak / orphaned-session / lock-cycle checks
        sys.exit(run_fleet_smoke())
    else:
        main()
