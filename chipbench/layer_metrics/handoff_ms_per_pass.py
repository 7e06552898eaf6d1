"""Executor: the milliseconds a pass's hand-offs took
(``summary()["handoffs"]["us"]``: for each, the time from the later of
(the item was there, its taker began to wait) to the taker running; a
submit's whole time in its pool's queue), added over the pass's queries,
median over the traced passes. Over ``handoffs_per_pass`` it is the latency
of one hand-off, to hold against the interpreter's 5 ms switch interval.
None on a program that tallies none (the parent of PR 43)."""

from chipbench import wait_spans


def read(ctx):
    us = wait_spans.median_per_pass(
        ctx, lambda s: s.get("handoffs", {}).get("us"))
    return None if us is None else us / 1e3
