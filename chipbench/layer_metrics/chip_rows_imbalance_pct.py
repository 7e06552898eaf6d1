"""Executor: how unevenly the traced passes' rows were spread over the
chips: (most - fewest) / mean of the rows each chip's device programs
reduced, all traced passes together, over every chip the program lists
(one that got nothing counts 0). 0 where one chip is visible; equal files
dealt round-robin over four chips should stay within a few percent."""

from chipbench.layer_metrics import chips_with_tables


def read(ctx):
    passes = chips_with_tables.per_chip(ctx)
    if passes is None:
        return None
    rows = {}
    for chips in passes:
        for chip, c in chips.items():
            rows[chip] = rows.get(chip, 0) + c["rows"]
    if not rows or not sum(rows.values()):
        return 0.0
    mean = sum(rows.values()) / len(rows)
    return 100.0 * (max(rows.values()) - min(rows.values())) / mean
