"""90th percentile of the pass walls; listed only for cells whose window
holds over 100 passes, so that ten or more lie beyond it."""

from chipbench import window


def read(ctx):
    return window.quantile(ctx.walls, 0.9)
