"""Download: milliseconds a pass spends blocked in ``jax.device_get``,
waiting for the device and copying the packed results back (the
program's ``device:fetch`` spans), median over the traced passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "device:fetch")
