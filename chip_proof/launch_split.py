#!/usr/bin/env python3
"""Inside ``daft:dispatch:launch``: what the TPU client did, and how long the
thread then took to come back (the one split thread-CPU time cannot make).

    python3 chip_proof/launch_split.py <file.xplane.pb> [out.json]

A profile taken with ``host_tracer_level`` 2 holds the program's live spans
(``daft:<span>``, ``tracing._annotate``) and the client's own events on the
same host lines. For every ``daft:dispatch:launch`` event this lists the
events of the same line that lie inside it: per name how many, their summed
and median duration, counted at the top level only (an event inside another
child is its child's); ``inside_us`` the union of the top-level children,
``head_us`` from the launch's start to its first child, ``tail_us`` from its
last child's end to the launch's end. The client's execute call is the
outermost event with ``Execute`` in its name: ``before_us`` from the launch's
start to it (argument handling, the jit cache's lookup), ``execute_us`` its
length, ``back_us`` from its end to the launch's end: the thread coming back
to Python. ``children`` lists ``<depth>:<name>``.
``chipbench/run.py`` deletes its profile once reduced, so ``cell.py`` calls
:func:`split` from its wrapper around ``xplane.reduce`` (``CELL_PROFILE=1``).
"""
import json
import statistics
import sys

LAUNCH = "daft:dispatch:launch"
DISPATCH = "daft:device:dispatch"
#: how deep below the launch the events are listed by name
DEPTHS = 4


def _stats(values):
    values = sorted(values)
    if not values:
        return {"n": 0}
    return {"n": len(values), "sum_us": round(sum(values), 1),
            "median_us": round(statistics.median(values), 1),
            "p90_us": round(values[int(0.9 * (len(values) - 1))], 1),
            "max_us": round(values[-1], 1)}


def split(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    launches = []          # per launch: dict of the numbers above
    names = {}             # child name -> [durations, us]
    dispatch_us = []
    lines_with = 0
    windows = []           # every launch's (start, end), all lines
    lines = []             # (line name, events) of every host line
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events), key=lambda t: (t[0], -t[1]))
            spans = [ev for ev in events if ev[2] == LAUNCH]
            lines.append((line.name, events, bool(spans)))
            windows += [(s, e) for s, e, _ in spans]
            dispatch_us += [(e - s) / 1e3 for s, e, n in events
                            if n == DISPATCH]
            if not spans:
                continue
            lines_with += 1
            at = 0
            for s0, e0, _ in spans:
                while at < len(events) and events[at][0] < s0:
                    at += 1
                stack, levels, j = [], {}, at   # depth -> [(s, e, name)]
                while j < len(events) and events[j][0] < e0:
                    s, e, n = events[j]
                    j += 1
                    if (s, e, n) == (s0, e0, LAUNCH) or e > e0:
                        continue
                    while stack and stack[-1] <= s:
                        stack.pop()
                    stack.append(e)
                    levels.setdefault(len(stack), []).append((s, e, n))
                for depth, evs in levels.items():
                    if depth <= DEPTHS:
                        for s, e, n in evs:
                            names.setdefault((depth, n), []).append(
                                (e - s) / 1e3)
                top = levels.get(1, [])
                # the client's execute call: the outermost events named so
                execs = []
                for depth in sorted(levels):
                    for s, e, n in levels[depth]:
                        if "Execute" in n and not any(
                                a <= s and e <= b for a, b in execs):
                            execs.append((s, e))
                launches.append({
                    "dur_us": (e0 - s0) / 1e3,
                    "inside_us": sum(e - s for s, e, _ in top) / 1e3,
                    "head_us": ((top[0][0] - s0) / 1e3) if top else None,
                    "tail_us": ((e0 - top[-1][1]) / 1e3) if top else None,
                    "before_us": ((min(s for s, _ in execs) - s0) / 1e3)
                    if execs else None,
                    "execute_us": (sum(e - s for s, e in execs) / 1e3)
                    if execs else None,
                    "back_us": ((e0 - max(e for _, e in execs)) / 1e3)
                    if execs else None,
                    "children": len(top)})
    out = {"path": path, "host_lines_with_launches": lines_with,
           "launches": _stats([x["dur_us"] for x in launches]),
           "dispatches": _stats(dispatch_us),
           "inside": _stats([x["inside_us"] for x in launches]),
           "head": _stats([x["head_us"] for x in launches
                           if x["head_us"] is not None]),
           "tail": _stats([x["tail_us"] for x in launches
                           if x["tail_us"] is not None]),
           "before": _stats([x["before_us"] for x in launches
                             if x["before_us"] is not None]),
           "execute": _stats([x["execute_us"] for x in launches
                              if x["execute_us"] is not None]),
           "back": _stats([x["back_us"] for x in launches
                           if x["back_us"] is not None]),
           "without_children": sum(1 for x in launches if not x["children"]),
           "children": {f"{d}:{n}": _stats(v) for (d, n), v in sorted(
               names.items(), key=lambda kv: (kv[0][0], -sum(kv[1])))[:40]}}
    # what the OTHER host lines (the client's own threads) began while a
    # launch was open: where the client's execute shows, if not in-line
    windows.sort()
    starts = [w[0] for w in windows]
    import bisect
    elsewhere = {}
    for name, events, has in lines:
        if has:
            continue
        for s, e, n in events:
            at = bisect.bisect_right(starts, s) - 1
            if at >= 0 and windows[at][1] > s and not n.startswith("daft:"):
                elsewhere.setdefault(n, []).append((e - s) / 1e3)
    out["on_other_lines_during_launches"] = {
        n: _stats(v) for n, v in sorted(
            elsewhere.items(), key=lambda kv: -sum(kv[1]))[:20]}
    # the slow launches apart: is it the client, or the coming back?
    slow = sorted(launches, key=lambda x: -x["dur_us"])[:max(len(launches) // 10, 1)]
    out["slowest_tenth"] = {
        "launches": _stats([x["dur_us"] for x in slow]),
        "inside": _stats([x["inside_us"] for x in slow]),
        "tail": _stats([x["tail_us"] for x in slow
                        if x["tail_us"] is not None]),
        "before": _stats([x["before_us"] for x in slow
                          if x["before_us"] is not None]),
        "execute": _stats([x["execute_us"] for x in slow
                           if x["execute_us"] is not None]),
        "back": _stats([x["back_us"] for x in slow
                        if x["back_us"] is not None])}
    return out


if __name__ == "__main__":
    result = split(sys.argv[1])
    text = json.dumps(result, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(text)
