#!/usr/bin/env python3
"""The instrument's own checks over the spans of traced queries (PR 43):

    python3 chip_proof/span_checks.py chiprun_out/<label>.spans.json

``cell.py`` with ``CELL_SPANS=1`` keeps every span of the traced queries
(``[{"t0_us", "wall_us", "spans": [...]}, ...]``). Checked here, over all of
them: ``cpu_us <= dur_us`` but for the clocks' grain on every span that
carries one; every ``dispatch:launch`` lies inside its parent span's interval,
and which span that is (``device:dispatch``, ``join:device``, or another: the
``device/runtime.py`` sites stand in no dispatch leaf); per span name the
share of its wall off the CPU; the hand-off tails by length.
"""
import json
import sys

GRAIN_US = 50   # two clocks read one after the other, a thread switch between


def check(traces) -> dict:
    over, worst = 0, 0
    n_cpu = 0
    launch_parents = {}
    outside = 0
    by_name = {}
    tails = []
    for t in traces:
        spans = t["spans"]
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            if "cpu_us" in s:
                n_cpu += 1
                d = by_name.setdefault(s["name"], [0, 0, 0])
                d[0] += 1
                d[1] += s["dur_us"]
                d[2] += s["cpu_us"]
                if s["cpu_us"] > s["dur_us"] + GRAIN_US:
                    over += 1
                worst = max(worst, s["cpu_us"] - s["dur_us"])
            tail = (s.get("attrs") or {}).get("tail_us")
            if tail is not None:
                tails.append(tail)
            if s["name"] == "dispatch:launch":
                p = by_id.get(s["parent_id"])
                pname = p["name"] if p else "?"
                launch_parents[pname] = launch_parents.get(pname, 0) + 1
                if p is not None and p["name"] != "query" and not (
                        p["ts_us"] <= s["ts_us"] + 1 and
                        s["ts_us"] + s["dur_us"]
                        <= p["ts_us"] + p["dur_us"] + 1):
                    outside += 1
    tails.sort()
    return {
        "traces": len(traces), "spans_with_cpu": n_cpu,
        "cpu_over_dur_by_more_than_grain": over,
        "worst_cpu_minus_dur_us": worst,
        "launch_parents": launch_parents,
        "launches_outside_parent": outside,
        "offcpu_pct_by_name": {
            n: {"count": c, "dur_ms": round(d / 1e3, 2),
                "offcpu_pct": round(100.0 * (d - u) / d, 1) if d else None}
            for n, (c, d, u) in sorted(by_name.items())},
        "handoff_tails": {
            "n": len(tails), "sum_ms": round(sum(tails) / 1e3, 2),
            "median_us": tails[len(tails) // 2] if tails else None,
            "over_1ms": sum(1 for x in tails if x >= 1000),
            "over_4ms": sum(1 for x in tails if x >= 4000),
            "max_us": tails[-1] if tails else None}}


def unnamed(traces, top: int = 12) -> dict:
    """The holes no span names (``summary()["holes"]["unnamed_us"]``), by
    what stood on either side: for every stretch of a query's wall under no
    leaf, none of ``tracing.HOLE_SPANS`` and no hand-off tail, the span that
    ended last before it and the one that began first after it. Milliseconds
    over all traces given, the largest pairs first."""
    sys.path.insert(0, ".")
    from daft_tpu import tracing as tr
    pairs, total = {}, 0
    for t in traces:
        spans = [s for s in t["spans"] if s["name"] not in tr._LIFELONG_SPANS]
        lo, hi = t["t0_us"], t["t0_us"] + t["wall_us"]
        cover = []
        for s in spans:
            end = s["ts_us"] + s["dur_us"]
            if s["name"] in tr.LEAF_SPANS or s["name"] in tr.HOLE_SPANS:
                cover.append((s["ts_us"], end))
            tail = (s.get("attrs") or {}).get("tail_us")
            if tail:
                cover.append((end - min(tail, s["dur_us"]), end))
        merged = tr._merged(cover, lo, hi)
        gaps, at = [], lo
        for a, b in merged:
            if a > at:
                gaps.append((at, a))
            at = b
        if hi > at:
            gaps.append((at, hi))
        marks = [s for s in spans if not s["name"].startswith(("op:", "wait:"))
                 and s["name"] != "device:pipeline"]
        ends = sorted((s["ts_us"] + s["dur_us"], s["name"]) for s in marks)
        starts = sorted((s["ts_us"], s["name"]) for s in marks)
        for a, b in gaps:
            before = [n for e, n in ends if e <= a + 1]
            after = [n for st, n in starts if st >= b - 1]
            key = (before[-1] if before else "query start",
                   after[0] if after else "query end")
            pairs[key] = pairs.get(key, 0) + (b - a)
            total += b - a
    ranked = sorted(pairs.items(), key=lambda kv: -kv[1])[:top]
    return {"unnamed_ms": round(total / 1e3, 2), "traces": len(traces),
            "between": [[f"{a} -> {b}", round(us / 1e3, 2)]
                        for (a, b), us in ranked]}


if __name__ == "__main__":
    traces = json.load(open(sys.argv[1]))
    print(json.dumps(check(traces), indent=1))
    print(json.dumps(unnamed(traces), indent=1))
