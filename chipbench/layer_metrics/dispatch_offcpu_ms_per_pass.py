"""Executor: of the time a pass's threads spent in ``device:dispatch``
spans, the milliseconds they were off the CPU (``timed_us - cpu_us``: the
spans' durations less their threads' CPU time, ``time.thread_time_ns()``
read at both ends): a wait for the GIL or for a lock of the TPU client, not
launch work. Near 0 = dispatching is work, and fewer launches help; most of
``dispatch_ms_per_pass`` = it is waiting, and quieter threads help. The mean
over the traced passes (the CPU clock's grain, ``wait_spans.mean_per_pass``); 0 where a pass dispatched nothing; None on a
program whose spans carry no CPU time (the parent of PR 43)."""

from chipbench import wait_spans


def read(ctx):
    if not wait_spans.splits(ctx):
        return None
    us = wait_spans.mean_per_pass(
        ctx, lambda s: wait_spans.offcpu_us(s, ("device:dispatch",)))
    return None if us is None else us / 1e3
