"""Executor: milliseconds of host time a pass spends enqueueing device
programs (the program's ``device:dispatch`` spans: arguments, scalars,
the dense plan, the jitted call), median over the traced passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "device:dispatch")
