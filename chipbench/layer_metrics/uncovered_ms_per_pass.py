"""Executor: milliseconds of a pass's queries that lie in no leaf span of
the program (``wall_us - covered_us``, added over the pass's queries,
median over the traced passes): the absolute twin of ``span_coverage_pct``,
which a faster pass cannot make worse. None on a program that keeps no
summaries with both keys."""

from chipbench import wait_spans


def uncovered_us(summary):
    if "wall_us" not in summary or "covered_us" not in summary:
        return None
    return summary["wall_us"] - summary["covered_us"]


def read(ctx):
    us = wait_spans.median_per_pass(ctx, uncovered_us)
    return None if us is None else us / 1e3
