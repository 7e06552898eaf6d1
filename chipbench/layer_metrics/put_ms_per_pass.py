"""Upload: milliseconds of host time a pass spends in the calls that put
encoded planes on the device (the program's ``device:put`` spans); the
copy's device side is not in it."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "device:put")
