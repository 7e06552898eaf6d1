"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
the time of named programs, the operations that took most time, and the
device's idle gaps by what the host was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU trace
holds (looked at by hand, PR 25, ``selfcheck/small.xplane.pb``): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
run of a compiled program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one event per HLO operation, named by its HLO text) and ``Async XLA Ops``
(copies in flight); and a plane ``/host:CPU`` with one line per thread,
where ``jax.profiler.TraceAnnotation`` spans appear under their own names.
All of them count nanoseconds from the start of the profile, so a host
span and a device event can be laid over each other.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]   # [start, end) in nanoseconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
#: the benchmark's own host spans; a pass span holds the others
PASS = "pass:"
INNER = ("plan:", "execute:", "clear-cache")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.20 = f32[...] fusion(...)`` -> ``fusion.20``; a name that
    is not HLO text stays as it is."""
    head = hlo.split(" = ", 1)[0].strip()
    return head.lstrip("%") or hlo


def module_name(event: str) -> str:
    """``jit_run_packed(10941345621410993618)`` -> ``jit_run_packed``."""
    return event.split("(", 1)[0]


@dataclasses.dataclass
class TraceSummary:
    chips: int                       # device planes found
    passes: int                      # pass spans found
    window_s: float                  # first pass's start to last pass's end
    busy_s: float                    # device busy in the window, mean of chips
    module_s: Dict[str, float]       # program name -> device seconds
    module_runs: Dict[str, int]      # program name -> runs
    #: host span (``execute:q1``) -> program -> device seconds of the runs
    #: that started inside a span of that name; and how many such spans
    span_module_s: Dict[str, Dict[str, float]]
    span_count: Dict[str, int]
    device_ops: List[Tuple[str, float]]   # most time first
    idle_gaps: List[Tuple[str, float]]    # host span -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 1.0


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(path: str, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: List[Dict[str, list]] = []   # per chip: line -> [(name, s, e)]
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines: Dict[str, list] = {}
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            device.append(lines)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PASS) or e.name.startswith(INNER):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    passes = [(s, e) for n, s, e in spans if n.startswith(PASS)]
    if not passes:
        raise ValueError(f"{path}: no '{PASS}<n>' span; nothing to window")
    lo, hi = min(s for s, _ in passes), max(e for _, e in passes)
    window_ns = hi - lo

    inner = sorted((s, e, n) for n, s, e in spans if not n.startswith(PASS)
                   and e > lo and s < hi)
    span_count: Dict[str, int] = {}
    for _, _, n in inner:
        span_count[n] = span_count.get(n, 0) + 1
    span_module_s: Dict[str, Dict[str, float]] = {}

    busy_ns = 0.0
    idle: List[Interval] = []
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    ops: Dict[str, float] = {}
    for lines in device:
        every = [(s, e) for evs in lines.values() for _, s, e in evs]
        busy = clip(union(every), lo, hi)
        busy_ns += total(busy)
        if not idle:   # gaps are attributed on the first chip
            idle = complement(busy, lo, hi)
        for name, s, e in lines.get("XLA Modules", ()):
            if e > lo and s < hi:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0.0) + (e - s) / 1e9
                module_runs[key] = module_runs.get(key, 0) + 1
                at = bisect.bisect_right(inner, (s, math.inf, "")) - 1
                if at >= 0 and inner[at][1] > s:
                    per = span_module_s.setdefault(inner[at][2], {})
                    per[key] = per.get(key, 0.0) + (e - s) / 1e9
        for name, s, e in (lines.get("XLA Ops")
                           or lines.get("XLA Modules", ())):
            if e > lo and s < hi:
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
    chips = len(device)
    if not device:
        idle = [(lo, hi)]

    gaps: Dict[str, float] = {}
    labelled = 0.0
    for s, e, name in inner:
        got = overlap(idle, [(s, e)])
        if got:
            gaps[name] = gaps.get(name, 0.0) + got / 1e9
            labelled += got
    in_pass = overlap(idle, union(passes))
    gaps["pass:other"] = max(0.0, in_pass - labelled) / 1e9
    gaps["unlabelled"] = max(0.0, total(idle) - in_pass) / 1e9

    def ranked(d: Dict[str, float]) -> List[Tuple[str, float]]:
        return sorted(((k, v) for k, v in d.items() if v > 0),
                      key=lambda kv: -kv[1])[:top]

    return TraceSummary(
        chips=chips, passes=len(passes), window_s=window_ns / 1e9,
        busy_s=(busy_ns / chips / 1e9) if chips else 0.0,
        module_s=module_s, module_runs=module_runs,
        span_module_s=span_module_s, span_count=span_count,
        device_ops=ranked(ops), idle_gaps=ranked(gaps))
