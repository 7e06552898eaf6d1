"""Device: milliseconds per pass in which an operation ran on the chip
(union of the device's event intervals in the traced passes)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.chips:
        return None
    return 1e3 * ctx.trace.busy_s / ctx.trace.passes
