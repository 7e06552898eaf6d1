"""The yardstick beside the chip: median wall of the traced run's passes
with the device tier off (``DAFT_TPU_DEVICE=0``), same queries, same
files."""

import statistics


def read(ctx):
    walls = [p.wall_s for p in ctx.host_passes]
    return statistics.median(walls) if walls else None
