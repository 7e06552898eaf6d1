#!/usr/bin/env python3
"""The control of PR 43's off-CPU reading: each compute span's body called
alone, one thread busy, under a trace. ``timed_us - cpu_us`` should be near 0
(under 5% of the span's wall) before the number under load is believed.

    DAFT_TPU_DEVICE=0 python3 chip_proof/control_alone.py [rows] -> JSON

Runs on the host only (no chip is touched): 2 M rows of a lineitem-like batch
made here, then filter + projection (``expr:eval``), a hash fan-out
(``exchange:partition``), ``size_bytes`` (``mem:size``), a grouped aggregate
(``agg:host``), a join (``join:build`` / ``join:probe``) and a top-n
(``sort:topn`` rides the executor, so it is read from a small query)."""
import json
import os
import sys

os.environ.setdefault("DAFT_TPU_DEVICE", "0")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())


def main():
    import numpy as np
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    from daft_tpu import col, lit, tracing
    from daft_tpu.micropartition import MicroPartition
    from daft_tpu.recordbatch import RecordBatch
    rng = np.random.default_rng(7)
    batch = RecordBatch.from_pydict({
        "k": rng.integers(0, 200_000, rows),
        "g": rng.integers(0, 6, rows),
        "q": rng.random(rows) * 50,
        "p": rng.random(rows) * 1e5,
        "d": rng.random(rows) * 0.1,
        "s": np.array(["AIR", "RAIL", "SHIP", "TRUCK"])[
            rng.integers(0, 4, rows)]})
    dim = RecordBatch.from_pydict({
        "k": np.arange(200_000), "v": rng.random(200_000)})
    rec = tracing.SpanRecorder("control", max_spans=4096)
    ctx = tracing.SpanContext(rec, rec.root_id)
    with tracing.attach(ctx):
        for _ in range(3):
            kept = batch.filter((col("q") < lit(24.0)) & (col("s") == lit("AIR")))
            batch.eval_expression_list(
                [(col("p") * (1 - col("d"))).alias("rev"), col("g")])
            batch.partition_by_hash([col("k")], 8)
            MicroPartition.from_recordbatch(batch).size_bytes()
            batch.agg([col("p").sum().alias("sp"), col("q").mean().alias("mq")],
                      [col("g")])
            kept.hash_join(dim, [col("k")], [col("k")], "inner")
    rec.finish()
    phases = rec.summary()["phases"]
    out = {"rows": rows, "switch_interval_s": sys.getswitchinterval()}
    for name, p in sorted(phases.items()):
        if p.get("timed_us"):
            out[name] = {"count": p["count"],
                         "timed_ms": round(p["timed_us"] / 1e3, 2),
                         "cpu_ms": round(p["cpu_us"] / 1e3, 2),
                         "offcpu_pct": round(
                             100.0 * (p["timed_us"] - p["cpu_us"]) / p["timed_us"], 2)}
    print(json.dumps(out, indent=1))
    os.makedirs("/root/repo/chiprun_out", exist_ok=True)
    with open("/root/repo/chiprun_out/control_alone.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
