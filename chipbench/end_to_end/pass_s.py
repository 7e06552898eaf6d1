"""Median wall of a pass (the traffic's queries once, in order)."""

import statistics


def read(ctx):
    return statistics.median(ctx.walls)
