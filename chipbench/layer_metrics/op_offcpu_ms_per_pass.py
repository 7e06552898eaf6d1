"""Host operators: the milliseconds a pass's threads were off the CPU
inside the spans whose body is computation in the calling thread
(``wait_spans.COMPUTE_SPANS`` less ``device:dispatch``, which
``dispatch_offcpu_ms_per_pass`` reads): ``timed_us - cpu_us`` added over
those phases and the pass's queries, the mean over the traced passes (the
CPU clock's grain, ``wait_spans.mean_per_pass``). Waiting
for the GIL behind the other stage threads, or for Arrow's own pool where a
kernel uses it; ``mem:size``, found by hand to be all wait (PR 27), is the
known answer in the join cells. None on a program whose spans carry no CPU
time (the parent of PR 43)."""

from chipbench import wait_spans

NAMES = tuple(n for n in wait_spans.COMPUTE_SPANS if n != "device:dispatch")


def read(ctx):
    if not wait_spans.splits(ctx):
        return None
    us = wait_spans.mean_per_pass(
        ctx, lambda s: wait_spans.offcpu_us(s, NAMES))
    return None if us is None else us / 1e3
