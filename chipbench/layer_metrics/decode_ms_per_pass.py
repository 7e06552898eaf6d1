"""Partial decode: milliseconds a pass spends turning packed result
matrices back into record batches (the program's ``device:decode``
spans), median over the traced passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "device:decode")
