"""Device programs: the fused scan-aggregate programs' share of the HBM
roofline per chip-second, where the tables are spread over several chips.
It is ``agg_hbm_pct`` and calls it: the same bytes
(``peaks.scan_agg_bytes`` over the rows in the files) over the programs'
device seconds, which the trace's reduction adds over every chip's plane,
over one chip's 819 GB/s. The same kernel as on one chip, so it should
read what ``agg_hbm_pct`` reads in ``tpch-sf10.scan-agg-resident``."""

from chipbench.layer_metrics import agg_hbm_pct


def read(ctx):
    return agg_hbm_pct.read(ctx)
