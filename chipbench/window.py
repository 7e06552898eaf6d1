"""The measured window, as arithmetic on pass walls (no clock in here).

A *pass* runs the traffic's queries once. The window is made of whole
passes: one starts only while the time used so far plus the median pass so
far still fits, and the rate divides the queries of those passes by the time
*those passes* took, from the first one's start to the last one's end, gaps
included. PR 24 divided the queries finished when the clock stopped by the
clock: with 14 passes of 1.4 s in 20 s that rate moved by a whole pass, 7%,
with where the last pass fell (ledger, PR 24: spreads of 1.6% and 6.4% in two
sets of the same code). Here a window of 14 and one of 15 equal passes give
the same rate.
"""

from __future__ import annotations

import statistics
from typing import Sequence


def may_start(elapsed_s: float, walls: Sequence[float],
              seconds: float) -> bool:
    """Whether another pass may start ``elapsed_s`` into a window of
    ``seconds``: only if a pass of the median length seen so far still
    fits. The first pass always starts, so no window is empty."""
    if not walls:
        return True
    return elapsed_s + statistics.median(walls) <= seconds


def quantile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated quantile of ``values`` (0 <= q <= 1)."""
    s = sorted(values)
    if not s:
        raise ValueError("quantile of nothing")
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def queries_per_hour(n_passes: int, queries_per_pass: int,
                     first_start_s: float, last_end_s: float) -> float:
    """3600 x queries in the window's whole passes / the seconds those
    passes took, gaps between them included."""
    took = last_end_s - first_start_s
    if n_passes < 1 or took <= 0:
        raise ValueError("a rate needs a whole pass and its time")
    return 3600.0 * n_passes * queries_per_pass / took
