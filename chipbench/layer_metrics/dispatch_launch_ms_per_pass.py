"""Executor: milliseconds a pass's threads spent inside the calls of jitted
functions and nothing else (the program's ``dispatch:launch`` spans, opened
where ``retrace_sanitizer.dispatch_scope`` brackets the call: nested in
``device:dispatch`` / ``join:device``, alone at ``device/runtime.py``'s
sites), their durations added, median over the traced passes.
``dispatch_ms_per_pass`` less this is the Python around the calls. 0 where
a pass launched nothing; None on a program that has no such span (the
parent of PR 43: told by its spans carrying no CPU time either)."""

from chipbench import wait_spans


def read(ctx):
    if not wait_spans.splits(ctx):
        return None
    us = wait_spans.median_per_pass(
        ctx, lambda s: wait_spans.phase_sum_us(s, "dispatch:launch"))
    return None if us is None else us / 1e3
