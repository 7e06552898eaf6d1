"""Work apart from wait, pass by pass: what the seven readers of PR 43 share.

Since PR 43 a summary of ``daft_tpu.tracing.finished()`` says, beside what
``program_spans`` already lays on the passes: per phase ``cpu_us`` (the
thread-CPU time of that name's live spans) and ``timed_us`` (their
duration), so ``timed_us - cpu_us`` is how long the threads stood still
inside them; ``handoffs`` (``count``, ``us``, ``max_us``: items taken from
a channel or submits started on a pool, and how long their takers took to
run once the item was there); and ``holes`` (``us``: the wall no leaf span
covers; ``unnamed_us``: the part of it under no span that says what was
going on). ``program_spans.per_pass`` hands on a fixed set of keys, so
these are summed here, over ``program_spans.by_pass``'s summaries: a pass's
queries added, then the median over the traced passes, as the others; the
two metrics read from the thread-CPU clock take the mean over the traced
passes instead (:func:`mean_per_pass` says why).

A program whose summaries lack a field (the parent of PR 43) gives None,
and the metric is left out.
"""

from __future__ import annotations

import statistics
from typing import Callable, List, Optional

from chipbench import program_spans

#: the spans whose body is computation in the calling thread, so that
#: being off the CPU inside one is waiting for the GIL or a lock: the
#: program's ``tracing.COMPUTE_SPANS`` (``selfcheck/test_wait_metrics.py``
#: holds the two equal)
COMPUTE_SPANS = (
    "expr:eval", "exchange:partition", "exchange:gather", "mem:size",
    "join:build", "join:probe", "agg:host", "sort:topn", "device:decode",
    "device:encode", "plan:translate", "device:dispatch")


def traced(ctx) -> List[List[dict]]:
    """The summaries of each traced pass ([] when there is none)."""
    summaries = program_spans.finished()
    return program_spans.by_pass(ctx.passes, summaries) if summaries else []


def median_per_pass(ctx, of_summary: Callable[[dict], Optional[float]]
                    ) -> Optional[float]:
    """``of_summary`` added over a pass's queries, median over the traced
    passes; None when no pass is traced or a query's summary gives None."""
    sums = []
    for inside in traced(ctx):
        values = [of_summary(s) for s in inside]
        if any(v is None for v in values):
            return None
        sums.append(sum(values))
    return statistics.median(sums) if sums else None


def mean_per_pass(ctx, of_summary: Callable[[dict], Optional[float]]
                  ) -> Optional[float]:
    """``of_summary`` added over ALL traced passes' queries, over the
    number of traced passes. For what is read from the thread-CPU clock:
    on the machines with the chips that clock moves in steps of 10 ms (a
    tick charged to whichever thread runs at it: my chip runs, PR 43), so a
    pass's own sum is a multiple of 10 ms and a median of such sums is the
    nearest step; the total over a window of passes is what the ticks
    estimate."""
    passes = traced(ctx)
    values = [of_summary(s) for inside in passes for s in inside]
    if not values or any(v is None for v in values):
        return None
    return sum(values) / len(passes)


def offcpu_us(summary: dict, names) -> Optional[float]:
    """``timed_us - cpu_us`` over the named phases of one summary (a phase
    the query never entered counts 0); None when a phase that is there
    carries neither key."""
    total = 0
    for name in names:
        phase = summary.get("phases", {}).get(name)
        if phase is None:
            continue
        if "cpu_us" not in phase or "timed_us" not in phase:
            return None
        total += phase["timed_us"] - phase["cpu_us"]
    return total


def phase_sum_us(summary: dict, name: str) -> Optional[float]:
    """``sum_us`` of one phase of one summary, 0 where the query never
    entered it."""
    return summary.get("phases", {}).get(name, {}).get("sum_us", 0)


def splits(ctx) -> bool:
    """Whether the program's spans carry CPU time at all (any phase of any
    traced query has ``cpu_us``): False on the parent of PR 43, whose
    ``device:dispatch`` holds no ``dispatch:launch`` either."""
    return any("cpu_us" in p for inside in traced(ctx) for s in inside
               for p in s.get("phases", {}).values())
