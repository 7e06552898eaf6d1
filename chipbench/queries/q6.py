"""TPC-H Q6, forecasting revenue change: one scan of ``lineitem``, a
conjunction that keeps ~2% of it, one scalar sum. Copied from
``benchmarking/tpch/queries.py`` (PR 23's tree)."""

import datetime

from daft_tpu import col, lit

SCANS = {"lineitem": {"l_quantity": "float", "l_extendedprice": "float",
                      "l_discount": "float", "l_shipdate": "date"}}

#: one fused scan-aggregate program per file when the device takes it
FUSED_SCAN_AGG = True


def build(get_df):
    li = get_df("lineitem")
    return (li.where((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
                     & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
                     & col("l_discount").between(0.05, 0.07)
                     & (col("l_quantity") < 24))
            .agg((col("l_extendedprice") * col("l_discount")).sum()
                 .alias("revenue")))
