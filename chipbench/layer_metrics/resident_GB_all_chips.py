"""Device memory: gigabytes the HBM column cache holds on all chips
together, as the program noted them on a traced pass's queries (per chip
the largest of the pass, added over the chips), median over the traced
passes. Beside ``hbm_peak_GB`` (the fullest chip) it shows a working set
that no single chip holds."""

import statistics

from chipbench.layer_metrics import chips_with_tables


def read(ctx):
    passes = chips_with_tables.per_chip(ctx)
    if passes is None:
        return None
    return statistics.median(
        sum(c["resident_bytes"] for c in chips.values())
        for chips in passes) / 1e9
