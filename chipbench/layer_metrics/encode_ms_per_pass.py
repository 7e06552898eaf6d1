"""Encode: milliseconds a pass spends on the numpy side of the device
encoding, planes, validity, dictionary ranks, padding (the program's
``device:encode`` spans, the union over its threads), median over the traced
passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "device:encode")
