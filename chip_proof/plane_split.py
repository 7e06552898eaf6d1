#!/usr/bin/env python3
"""Every chip's plane of a profile, module by module: where a program's
device time lies (PR 44: a round's ``jit_run_packed`` reads 0.04 ms a run
longer than a table's; is it the operations, or the module around them?).

    python3 chip_proof/plane_split.py <file.xplane.pb> [out.json]

For each ``/device:TPU:<n>`` plane and each event name of its ``XLA
Modules`` line (``jit_<fn>(<fingerprint>)``: one a compiled program):
``runs``; the module events' mean length ``module_us``; of it the union of
the ``XLA Ops`` events inside ``ops_us``, the time before the first
``head_us`` and after the last ``tail_us``, and the gaps between them
``gaps_us``; ``top_ops``, the operations that took most, as mean us a run.
``skew`` is read over the planes together, for a program that ran equally
often on every plane: the k-th run's latest start less its earliest
(``start_us``) and the same of its ends (``end_us``), as medians; it says
something only where the k-th runs are one launch, a round.
``chipbench/run.py`` deletes its profile once reduced, so ``cell.py`` calls
:func:`split` from its wrapper around ``xplane.reduce`` (``CELL_PROFILE=1``).
"""
import bisect
import json
import re
import statistics
import sys

TOP = 8


def _union_len(intervals):
    got, at = 0.0, -1.0
    for s, e in sorted(intervals):
        if e > at:
            got += e - max(s, at)
            at = e
    return got


def split(path: str) -> dict:
    from jax.profiler import ProfileData
    planes, runs_of = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {ln.name: sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in ln.events)
                 for ln in plane.lines}
        ops = lines.get("XLA Ops", [])
        starts = [s for s, _, _ in ops]
        per = {}
        for s, e, name in lines.get("XLA Modules", []):
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
            m = per.setdefault(name, {"module": [], "ops": [], "head": [],
                                      "tail": [], "by_op": {}})
            m["module"].append(e - s)
            m["ops"].append(_union_len([(a, b) for a, b, _ in inside]))
            m["head"].append(inside[0][0] - s if inside else e - s)
            m["tail"].append(e - max(b for _, b, _ in inside)
                             if inside else 0.0)
            for a, b, op in inside:
                key = op.split(" = ", 1)[0].lstrip("%")
                m["by_op"][key] = m["by_op"].get(key, 0.0) + (b - a)
            runs_of.setdefault(name, {}).setdefault(plane.name, []).append(
                (s, e))
        out = {}
        for name, m in per.items():
            n = len(m["module"])
            mean = {k: sum(m[k]) / n / 1e3
                    for k in ("module", "ops", "head", "tail")}
            out[name] = {
                "runs": n, "module_us": mean["module"],
                "ops_us": mean["ops"], "head_us": mean["head"],
                "tail_us": mean["tail"],
                "gaps_us": mean["module"] - mean["ops"] - mean["head"]
                - mean["tail"],
                "top_ops": {k: v / n / 1e3 for k, v in sorted(
                    m["by_op"].items(), key=lambda kv: -kv[1])[:TOP]}}
        planes[plane.name] = out
    skew = {}
    for name, by_plane in runs_of.items():
        counts = {len(v) for v in by_plane.values()}
        if len(by_plane) == len(planes) > 1 and len(counts) == 1:
            kth = list(zip(*by_plane.values()))
            skew[name] = {
                "start_us": statistics.median(
                    max(s for s, _ in r) - min(s for s, _ in r)
                    for r in kth) / 1e3,
                "end_us": statistics.median(
                    max(e for _, e in r) - min(e for _, e in r)
                    for r in kth) / 1e3}
    return {"path": path, "planes": planes, "skew": skew}


if __name__ == "__main__":
    text = json.dumps(split(sys.argv[1]), indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(text)
