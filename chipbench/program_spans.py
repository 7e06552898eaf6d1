"""The program's own spans, pass by pass: the second and last place where
the benchmark touches the program (``engine.py`` is the first).

A query that runs while a profile is being taken is traced by the program
(``daft_tpu.tracing``: a profile is a request for spans), and the summary
of every finished trace stays in a ring in the process:
``daft_tpu.tracing.finished()``, oldest first, each a dict with
``t0_perf_s`` (``time.perf_counter()`` at the query's start, the clock the
passes are timed on), ``wall_us``, ``covered_us`` (the union of the leaf
spans), ``tables`` (where each scan task's table came from) and ``phases``:
per span name ``count``, ``wall_us`` (the union of that name's intervals
over all threads), ``sum_us``, ``bytes`` and ``rows``.

Here the summaries are laid on the passes of the window. So the passes of a
``--trace 1`` run that lay under the profiler give numbers; a ``--trace 0``
run, and a program that keeps no such ring, give ``None`` and the metric is
left out.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def finished() -> Optional[List[dict]]:
    """The program's finished traces, or None if it keeps none."""
    try:
        from daft_tpu import tracing
        return list(tracing.finished())
    except (ImportError, AttributeError):
        return None


def by_pass(passes: Sequence, summaries: Sequence[dict]
            ) -> List[List[dict]]:
    """For each pass that holds a summary (its ``t0_perf_s`` inside the
    pass's ``start_s``..``end_s``), the summaries it holds."""
    out = []
    for p in passes:
        inside = [s for s in summaries
                  if p.start_s <= s.get("t0_perf_s", -1.0) <= p.end_s]
        if inside:
            out.append(inside)
    return out


def per_pass(ctx, summaries: Optional[Sequence[dict]] = None
             ) -> Optional[Dict[str, Dict[str, float]]]:
    """Per phase the median over the traced passes of what the pass's
    queries spent in it, summed: ``{name: {"count", "wall_us", "sum_us",
    "bytes", "rows"}}``. A phase that a pass never entered counts 0 there.
    None when no pass holds a summary."""
    if summaries is None:
        summaries = finished()
    if not summaries:
        return None
    traced = by_pass(ctx.passes, summaries)
    if not traced:
        return None
    names = {n for inside in traced for s in inside
             for n in s.get("phases", {})}
    out = {}
    for name in names:
        out[name] = {
            key: statistics.median(
                sum(s.get("phases", {}).get(name, {}).get(key, 0)
                    for s in inside) for inside in traced)
            for key in ("count", "wall_us", "sum_us", "bytes", "rows")}
    return out


def phase_ms(ctx, *names: str) -> Optional[float]:
    """Milliseconds a pass spent in the named phases: their ``wall_us``
    of :func:`per_pass`, added; 0 where none was entered."""
    phases = per_pass(ctx)
    if phases is None:
        return None
    return sum(phases.get(n, {}).get("wall_us", 0) for n in names) / 1e3


def totals(ctx) -> Optional[Dict[str, float]]:
    """Over all traced passes together: ``wall_us``, ``covered_us`` and
    the three ``tables`` counts."""
    summaries = finished()
    traced = by_pass(ctx.passes, summaries) if summaries else []
    if not traced:
        return None
    flat = [s for inside in traced for s in inside]
    out = {"wall_us": sum(s.get("wall_us", 0) for s in flat),
           "covered_us": sum(s.get("covered_us", 0) for s in flat)}
    for k in ("from_cache", "encoded", "host"):
        out[k] = sum(s.get("tables", {}).get(k, 0) for s in flat)
    return out
