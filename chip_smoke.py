#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the query path starts on the chip.

Runs TPC-H Q1, Q6 and Q3 at SF1 through the public API
(``daft_tpu.read_parquet`` -> ``benchmarking/tpch/queries.py`` ->
``.to_pydict()``) on ONE TPU chip, in ONE process, and checks every answer
against the host tier (``DAFT_TPU_DEVICE=0``) and — for Q1/Q6 — against an
independent pyarrow computation.  Each query runs in two modes:

- *forced* (``DAFT_TPU_DEVICE_FORCE=1``, "the device always wins"): the
  assertions are made here — every kernel family the plan implies must show
  at least one real device dispatch in the ledger delta;
- *auto* (cost model decides): reported, and checked for the right answer.

In both modes the device-failure count (``runtime.device_failures``) must
stay 0: a device program that failed and was replaced by a host run is a
failed smoke, not a slow one.

    python chip_smoke.py                # on the chip (the driver's call)
    python chip_smoke.py --chips 4      # 4-chip mesh path only (builder-run)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --sf 0.05   # CPU walk

Without a TPU the script always exits non-zero and never prints
``"ok": true``; ``--rehearse`` only walks the phases on the CPU to find wrong
paths and arguments.  The last stdout line is one JSON object.
"""

from __future__ import annotations

import argparse
import datetime
import json
import logging
import math
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
T_START = time.time()

#: float columns: relative tolerance of device answers against the host
#: tier and pyarrow.  f64 columns ride f32 on the TPU (device/column.py
#: ``supports_f64``), so per-batch sums carry f32 rounding; partials are
#: merged on the host in f64.
RTOL = 1e-4

#: parquet parts per large table: decides rows per device batch and so the
#: bucket every program compiles at. 16 parts put SF1 lineitem at ~375k rows
#: a part (the 524288 bucket) and keep Q6's post-filter batches (~7k rows)
#: above the 4096-row device floor an accelerator backend applies
#: (runtime._min_rows), so its fused scalar-agg fragment really dispatches;
#: Q3's two joins then sort 65536- and 262144-row build sides. Compile
#: seconds per program, per part count: CHANGES.md PR 23.
PARTS = 16

QUERIES = ("q1", "q6", "q3")

#: ledger families each query's FORCED plan must dispatch at least once, in
#: the cold and in the warm run, on an accelerator backend. Reported but not
#: required: scan-side ``predicate`` dispatches (a warm run served from the
#: HBM column cache loads nothing, so evaluates no scan predicate), and Q3's
#: post-join group-by and top-k, whose batches fall under the 4096-row
#: device floor at this layout and stay on the host by routing, not by
#: failure.
IMPLIED = {
    "q1": ("grouped_agg",),
    "q6": ("global_agg",),
    "q3": ("join",),
}


def say(msg: str = "") -> None:
    print(msg, flush=True)


class CompileMeter:
    """Counts XLA compiles and persistent-cache hits (jax.monitoring) and
    keeps each program's name and compile-or-load seconds (JAX's own
    "Finished XLA compilation of <name> in <s> sec" debug line)."""

    _FINISHED = re.compile(r"Finished XLA compilation of (.+) in ([0-9.e-]+) sec")

    def __init__(self):
        self.requests = 0          # backend compile requests (hit or miss)
        self.cache_hits = 0        # served from the persistent cache
        self.seconds = 0.0         # wall inside compile-or-load
        self.programs = []         # (name, seconds), in order
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)
        meter = self

        class _Names(logging.Handler):
            def emit(self, record):
                m = meter._FINISHED.search(record.getMessage())
                if m:
                    meter.programs.append((m.group(1), float(m.group(2))))

        lg = logging.getLogger("jax._src.dispatch")
        lg.setLevel(logging.DEBUG)
        lg.addHandler(_Names())
        lg.propagate = False       # keep JAX's debug chatter off stderr

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self):
        return (self.requests, self.cache_hits, self.seconds,
                len(self.programs))

    def delta(self, a, b, slow_s: float = 1.0):
        return {"programs": b[0] - a[0], "cache_hits": b[1] - a[1],
                "compiled": (b[0] - a[0]) - (b[1] - a[1]),
                "seconds": round(b[2] - a[2], 3),
                f"programs_over_{slow_s:g}s": [
                    f"{n}:{s:.1f}s" for n, s in self.programs[a[3]:b[3]]
                    if s >= slow_s]}


# ------------------------------------------------------------------ data

def dataset(sf: float, parts: int, seed: int) -> str:
    from benchmarking.tpch.datagen import generate_tpch
    root = os.path.join(REPO, ".cache", f"tpch_sf{sf:g}_p{parts}_s{seed}")
    marker = os.path.join(root, "_COMPLETE")
    if os.path.exists(marker):
        say(f"[data] reusing {root}")
        return root
    t0 = time.time()
    generate_tpch(root, scale_factor=sf, num_parts=parts, seed=seed)
    with open(marker, "w") as f:
        f.write("ok\n")
    say(f"[data] generated TPC-H SF{sf:g} seed={seed} parts={parts} "
        f"in {time.time() - t0:.1f}s -> {root}")
    return root


def get_df_factory(root: str):
    import daft_tpu

    def get_df(name):
        return daft_tpu.read_parquet(f"{root}/{name}/*.parquet")
    return get_df


def run_query(root: str, qname: str) -> dict:
    from benchmarking.tpch import queries as Q
    return getattr(Q, qname)(get_df_factory(root)).to_pydict()


# ------------------------------------------------------------ references

def arrow_q1(root: str) -> dict:
    import pyarrow.compute as pc
    import pyarrow.dataset as pads
    t = pads.dataset(os.path.join(root, "lineitem")).to_table(columns=[
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"])
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
    d = g.to_pydict()
    return {"l_returnflag": d["l_returnflag"],
            "l_linestatus": d["l_linestatus"],
            "sum_qty": d["l_quantity_sum"],
            "sum_base_price": d["l_extendedprice_sum"],
            "sum_disc_price": d["disc_price_sum"],
            "sum_charge": d["charge_sum"],
            "avg_qty": d["l_quantity_mean"],
            "avg_price": d["l_extendedprice_mean"],
            "avg_disc": d["l_discount_mean"],
            "count_order": d["l_quantity_count"]}


def arrow_q6(root: str) -> dict:
    import pyarrow.compute as pc
    import pyarrow.dataset as pads
    t = pads.dataset(os.path.join(root, "lineitem")).to_table(columns=[
        "l_quantity", "l_extendedprice", "l_discount", "l_shipdate"])
    f = pc.field
    t = t.filter((f("l_shipdate") >= datetime.date(1994, 1, 1))
                 & (f("l_shipdate") < datetime.date(1995, 1, 1))
                 & (f("l_discount") >= 0.05) & (f("l_discount") <= 0.07)
                 & (f("l_quantity") < 24))
    rev = pc.sum(pc.multiply(t.column("l_extendedprice"),
                             t.column("l_discount"))).as_py()
    return {"revenue": [rev]}


def compare(label: str, got: dict, ref: dict, rtol: float) -> float:
    """Keys, counts, integers, strings and dates exact; floats within
    ``rtol`` relative.  Returns the worst relative float error seen;
    raises AssertionError on any mismatch."""
    if list(got.keys()) != list(ref.keys()):
        raise AssertionError(
            f"{label}: columns {list(got)} != reference {list(ref)}")
    worst = 0.0
    for name in ref:
        g, r = got[name], ref[name]
        if len(g) != len(r):
            raise AssertionError(
                f"{label}.{name}: {len(g)} rows != reference {len(r)}")
        for i, (a, b) in enumerate(zip(g, r)):
            if isinstance(b, float) or isinstance(a, float):
                if a is None or b is None:
                    if a is not b:
                        raise AssertionError(
                            f"{label}.{name}[{i}]: {a!r} != {b!r}")
                    continue
                if not math.isfinite(a):
                    raise AssertionError(
                        f"{label}.{name}[{i}]: non-finite {a!r}")
                err = abs(a - b) / max(abs(b), 1e-300)
                worst = max(worst, err)
                if err > rtol:
                    raise AssertionError(
                        f"{label}.{name}[{i}]: {a!r} vs reference {b!r} "
                        f"(rel err {err:.3e} > rtol {rtol:g})")
            elif a != b:
                raise AssertionError(
                    f"{label}.{name}[{i}]: {a!r} != reference {b!r}")
    return worst


# -------------------------------------------------------------- counters

def _flat_decisions(d: dict) -> dict:
    return {f"{k}.{side}": n for k, v in d.items() for side, n in v.items()}


def counters():
    from daft_tpu.device import costmodel, runtime
    return {"ledger": costmodel.ledger_snapshot(raw=True),
            "decisions": _flat_decisions(
                {k: dict(v) for k, v in costmodel.decision_counts.items()}),
            "failures": runtime.device_failures()}


def counters_delta(a, b) -> dict:
    from daft_tpu.device import costmodel
    slim = {}
    for fam, d in costmodel.ledger_delta(a["ledger"], b["ledger"]).items():
        slim[fam] = {k: d[k] for k in ("dispatches", "rows", "bytes",
                                       "seconds", "strategy",
                                       "strategy_sort", "strategy_hash",
                                       "strategy_dense") if k in d}
    dec = {k: n - a["decisions"].get(k, 0)
           for k, n in b["decisions"].items()
           if n - a["decisions"].get(k, 0)}
    fa, fb = a["failures"], b["failures"]
    fails = {site: v["count"] - fa.get(site, {}).get("count", 0)
             for site, v in fb.items()
             if v["count"] - fa.get(site, {}).get("count", 0)}
    return {"ledger": slim, "decisions": dec, "failures": fails}


def set_mode(mode: str) -> None:
    """host: device tier off.  forced: device always wins.  auto: the cost
    model decides."""
    os.environ.pop("DAFT_TPU_DEVICE_FORCE", None)
    os.environ["DAFT_TPU_DEVICE"] = "0" if mode == "host" else "1"
    if mode == "forced":
        os.environ["DAFT_TPU_DEVICE_FORCE"] = "1"


# ------------------------------------------------------------ the phases

def phase_device(rehearse: bool, want_chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = "absent"
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    say(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu_v}")
    if d0.platform != "tpu":
        if not rehearse:
            raise SystemExit(
                f"chip_smoke: no TPU — jax.devices()[0].platform is "
                f"{d0.platform!r}; this script measures nothing without "
                f"the chip (use --rehearse to walk the phases on the CPU)")
        say("[device] REHEARSAL: no TPU attached; walking the phases on "
            "the CPU backend — this run cannot succeed")
    if len(devs) < want_chips:
        raise SystemExit(f"chip_smoke: --chips {want_chips} needs "
                         f"{want_chips} devices, found {len(devs)}")
    from daft_tpu.device import backend
    name = backend.backend_name()
    err = backend.probe_error()
    if err is not None or (not rehearse and name != "tpu"):
        raise SystemExit(f"chip_smoke: daft_tpu backend probe reports "
                         f"{name!r} (error: {err}); expected 'tpu'")
    say(f"[device] daft_tpu backend={name!r} compile cache="
        f"{jax.config.jax_compilation_cache_dir!r} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    return info


def phase_link() -> None:
    from daft_tpu.device import costmodel
    lp = costmodel.link_profile()
    say(f"[link] measured host<->device profile: rtt={lp.rtt_s * 1e3:.3f} ms "
        f"up={lp.up_bps / 1e6:.1f} MB/s down={lp.down_bps / 1e6:.1f} MB/s "
        f"(persisted at {costmodel._link_cache_path()})")


def phase_references(root: str) -> dict:
    refs = {}
    set_mode("host")
    before = counters()
    for q in QUERIES:
        t0 = time.time()
        refs[q] = run_query(root, q)
        say(f"[host] {q}: {time.time() - t0:.2f}s "
            f"rows={len(next(iter(refs[q].values())))}")
    d = counters_delta(before, counters())
    if d["ledger"]:
        raise AssertionError(
            f"host tier (DAFT_TPU_DEVICE=0) dispatched to the device: {d}")
    for q, fn in (("q1", arrow_q1), ("q6", arrow_q6)):
        t0 = time.time()
        ar = fn(root)
        worst = compare(f"host-vs-pyarrow {q}", refs[q], ar, 1e-9)
        say(f"[pyarrow] {q}: {time.time() - t0:.2f}s; host tier agrees "
            f"(worst rel err {worst:.2e}, rtol 1e-09)")
        refs[q + "_arrow"] = ar
    return refs


def phase_queries(root: str, refs: dict, mode: str,
                  meter: CompileMeter) -> None:
    set_mode(mode)
    for q in QUERIES:
        c0, m0 = counters(), meter.snap()
        t0 = time.time()
        got = run_query(root, q)
        cold = time.time() - t0
        c1, m1 = counters(), meter.snap()
        t0 = time.time()
        got2 = run_query(root, q)
        warm = time.time() - t0
        c2, m2 = counters(), meter.snap()
        d_cold, d_warm = counters_delta(c0, c1), counters_delta(c1, c2)
        comp_cold, comp_warm = meter.delta(m0, m1), meter.delta(m1, m2)
        rows = len(next(iter(got.values())))
        say(f"[{mode}] {q}: cold={cold:.3f}s warm={warm:.3f}s rows={rows}")
        say(f"[{mode}] {q}: cold compile {json.dumps(comp_cold)} "
            f"warm compile {json.dumps(comp_warm)}")
        say(f"[{mode}] {q}: ledger(cold run) {json.dumps(d_cold['ledger'])}")
        say(f"[{mode}] {q}: decisions(cold run) "
            f"{json.dumps(d_cold['decisions'])}")
        say(f"[{mode}] {q}: dispatches(warm run) " + json.dumps(
            {fam: d["dispatches"] for fam, d in d_warm["ledger"].items()}))
        say(f"[{mode}] {q}: device failures cold={d_cold['failures']} "
            f"warm={d_warm['failures']}")
        worst = 0.0
        for label, ans in (("cold", got), ("warm", got2)):
            worst = max(worst, compare(f"{mode} {q} {label} vs host tier",
                                       ans, refs[q], RTOL))
            if q + "_arrow" in refs:
                worst = max(worst, compare(
                    f"{mode} {q} {label} vs pyarrow", ans,
                    refs[q + "_arrow"], RTOL))
        say(f"[{mode}] {q}: answers match host tier"
            f"{' and pyarrow' if q + '_arrow' in refs else ''} "
            f"(worst rel err {worst:.3e}, rtol {RTOL:g})")
        if d_cold["failures"] or d_warm["failures"]:
            raise AssertionError(
                f"{mode} {q}: device failures {d_cold['failures']} "
                f"{d_warm['failures']} — first errors: "
                f"{counters()['failures']}")
        if mode == "forced":
            for led in (d_cold["ledger"], d_warm["ledger"]):
                missing = [f for f in IMPLIED[q]
                           if led.get(f, {}).get("dispatches", 0) < 1]
                if missing:
                    raise AssertionError(
                        f"forced {q}: no device dispatch recorded for "
                        f"{missing}; ledger delta {led}")


# ------------------------------------------------- the 4-chip mesh path

def _plan_has(df, node_type) -> bool:
    from daft_tpu.physical import translate as pt

    def find(node):
        return isinstance(node, node_type) or any(
            find(c) for c in node.children)
    return find(pt.translate(df._builder.optimize().plan))


def _ici():
    from daft_tpu.distributed.shuffle_service import shuffle_counters_snapshot
    snap = shuffle_counters_snapshot()
    return {k: snap.get(k, 0) for k in ("ici_exchanges", "ici_rows",
                                        "ici_bytes")}


def _q1_int(get_df):
    """TPC-H Q1's scan, filter and group keys with INTEGER aggregates. The
    mesh exchange only carries dtypes the device encoding round-trips
    bit-exactly, and on a TPU f64 is not one of them (it rides f32), so
    Q1's own f64 money sums are never planned onto the mesh there."""
    from daft_tpu import DataType, col, lit
    li = get_df("lineitem")
    return (li.where(col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
            .groupby("l_returnflag", "l_linestatus")
            .agg(col("l_quantity").cast(DataType.int64()).sum()
                 .alias("sum_qty"),
                 col("l_linenumber").sum().alias("sum_lineno"),
                 col("l_orderkey").max().alias("max_orderkey"),
                 col("l_quantity").count().alias("count_order"))
            .sort(["l_returnflag", "l_linestatus"]))


def _copartitioned_join(get_df, n: int):
    """Mesh hash-repartition of both sides on the join key (all_to_all
    over ICI), then the co-partitioned join; integer columns only (pure
    data movement must be bit-exact)."""
    from daft_tpu import col, lit
    o = (get_df("orders")
         .where((col("o_orderdate") >= lit(datetime.date(1995, 1, 1)))
                & (col("o_orderdate") < lit(datetime.date(1995, 3, 15))))
         .select("o_orderkey", "o_custkey", "o_shippriority")
         .repartition(n, col("o_orderkey")))
    li = (get_df("lineitem")
          .where((col("l_shipdate") >= lit(datetime.date(1995, 3, 15)))
                 & (col("l_shipdate") < lit(datetime.date(1995, 4, 15))))
          .select("l_orderkey", "l_linenumber", "l_suppkey")
          .repartition(n, col("l_orderkey")))
    return (o.join(li, left_on="o_orderkey", right_on="l_orderkey")
            .groupby("l_linenumber")
            .agg(col("o_orderkey").count().alias("n"),
                 col("o_custkey").sum().alias("sum_cust"),
                 col("l_suppkey").sum().alias("sum_supp"))
            .sort("l_linenumber"))


def phase_mesh(root: str, meter: CompileMeter) -> None:
    import jax
    import numpy as np
    from benchmarking.tpch import queries as Q
    from daft_tpu.parallel import exchange, mesh as pmesh
    from daft_tpu.physical import plan as pp
    n = pmesh.mesh_size()
    mesh = pmesh.get_mesh()
    say(f"[mesh] mesh_size={n} devices={[str(d) for d in mesh.devices.flat]}")
    if n != 4:
        raise AssertionError(f"--chips 4 needs a 4-device mesh, got {n}")
    blk = exchange.shard_blocks(mesh, np.arange(4 * 1024, dtype=np.int32))
    placed = [str(sh.device) for sh in blk.addressable_shards]
    say(f"[mesh] shard_blocks places one block on each of: {placed}")
    if len(set(placed)) != 4 or {sh.data.shape for sh in
                                 blk.addressable_shards} != {(1024,)}:
        raise AssertionError(f"shard_blocks did not spread 4 blocks over 4 "
                             f"devices: {placed}")
    get_df = get_df_factory(root)
    cases = (("q1", Q.q1, None),
             ("q1_int", _q1_int, pp.DeviceExchangeAgg),
             ("repartition_join", lambda g: _copartitioned_join(g, n), None))
    for name, build, must_plan in cases:
        set_mode("host")
        t0 = time.time()
        ref = build(get_df).to_pydict()
        host_s = time.time() - t0
        set_mode("auto")
        planned = _plan_has(build(get_df), pp.DeviceExchangeAgg)
        c0, i0, m0 = counters(), _ici(), meter.snap()
        t0 = time.time()
        got = build(get_df).to_pydict()
        cold = time.time() - t0
        t0 = time.time()
        got2 = build(get_df).to_pydict()
        warm = time.time() - t0
        d, i1 = counters_delta(c0, counters()), _ici()
        moved = {k: i1[k] - i0[k] for k in i1}
        say(f"[mesh] {name}: DeviceExchangeAgg planned={planned} "
            f"host={host_s:.3f}s cold={cold:.3f}s warm={warm:.3f}s "
            f"rows={len(next(iter(got.values())))} ici(2 runs)={moved} "
            f"compile {json.dumps(meter.delta(m0, meter.snap()))}")
        say(f"[mesh] {name}: ledger {json.dumps(d['ledger'])} "
            f"failures={d['failures']}")
        worst = max(compare(f"mesh {name} cold vs host tier", got, ref, RTOL),
                    compare(f"mesh {name} warm vs host tier", got2, ref,
                            RTOL))
        say(f"[mesh] {name}: answers match host tier (worst rel err "
            f"{worst:.3e}, rtol {RTOL:g})")
        if d["failures"]:
            raise AssertionError(f"mesh {name}: device failures "
                                 f"{counters()['failures']}")
        if must_plan is not None and not planned:
            raise AssertionError(f"mesh {name}: {must_plan.__name__} is not "
                                 f"in the physical plan")
        if name != "q1" and moved["ici_exchanges"] < 1:
            raise AssertionError(f"mesh {name}: no collective exchange ran "
                                 f"over the mesh ({moved})")
        if name == "q1" and not planned:
            say("[mesh] q1: FINDING — Q1's f64 sums are not planned onto "
                "the mesh on this backend (f64 is not a lossless device "
                "dtype on a TPU: translate._try_mesh_exchange_agg)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = the mesh path only (builder-run)")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the phases on the CPU; always ends non-zero")
    args = ap.parse_args()

    if args.chips == 4:
        # the mesh planner sizes itself from the visible devices; pin it to
        # all four and admit SF1-sized inputs without consulting the ICI
        # cost model (the smoke must not depend on its pricing)
        os.environ.setdefault("DAFT_TPU_MESH_MIN_ROWS", "0")

    info = phase_device(args.rehearse, args.chips)
    meter = CompileMeter()
    say(f"[config] sf={args.sf:g} parts={PARTS} seed={args.seed} "
        f"chips={args.chips} rtol={RTOL:g}")
    root = dataset(args.sf, PARTS, args.seed)
    phase_link()

    if args.chips == 4:
        phase_mesh(root, meter)
    else:
        refs = phase_references(root)
        for mode in ("forced", "auto"):
            phase_queries(root, refs, mode, meter)

    req, hits, secs, _ = meter.snap()
    say(f"[compile] total: {req} programs, {hits} persistent-cache hits, "
        f"{req - hits} compiled, {secs:.1f}s inside compile-or-load")
    say(f"[wall] whole script {time.time() - T_START:.1f}s")
    ok = info["platform"] == "tpu" and not args.rehearse
    if not ok:
        say("[result] rehearsal walked every phase; no TPU, so not ok")
    print(json.dumps({"ok": ok, "device": info}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
