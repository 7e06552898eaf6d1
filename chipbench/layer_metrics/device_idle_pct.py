"""Device: share of the traced passes in which nothing ran on the chip."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.chips:
        return None
    return 100.0 * ctx.trace.idle_share
