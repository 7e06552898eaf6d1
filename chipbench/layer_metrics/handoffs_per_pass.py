"""Executor: how many times a pass handed work from one thread to another
(``summary()["handoffs"]["count"]``: items taken from a channel between
stage threads, submits started on a pool, results of the device pipeline's
submits taken by its consumer), added over the pass's queries, median over
the traced passes. A count of the plan's shape: a change that takes a stage
or a pool out of a query moves it to the digit. None on a program that
tallies none (the parent of PR 43)."""

from chipbench import wait_spans


def read(ctx):
    return wait_spans.median_per_pass(
        ctx, lambda s: s.get("handoffs", {}).get("count"))
