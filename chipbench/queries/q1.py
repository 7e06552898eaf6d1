"""TPC-H Q1, pricing summary report: one scan of ``lineitem``, a date
filter that keeps ~98% of it, a group-by on two keys of 3 x 2 values, eight
aggregates. Copied from ``benchmarking/tpch/queries.py`` (PR 23's tree)."""

import datetime

from daft_tpu import col, lit

#: columns read, with the kind that sizes them in ``peaks.scan_agg_bytes``
SCANS = {"lineitem": {"l_returnflag": "code", "l_linestatus": "code",
                      "l_quantity": "float", "l_extendedprice": "float",
                      "l_discount": "float", "l_tax": "float",
                      "l_shipdate": "date"}}

#: one fused scan-aggregate program per file when the device takes it
FUSED_SCAN_AGG = True


def build(get_df):
    li = get_df("lineitem")
    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    charge = disc_price * (1 + col("l_tax"))
    return (li.where(col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
            .groupby("l_returnflag", "l_linestatus")
            .agg(col("l_quantity").sum().alias("sum_qty"),
                 col("l_extendedprice").sum().alias("sum_base_price"),
                 disc_price.sum().alias("sum_disc_price"),
                 charge.sum().alias("sum_charge"),
                 col("l_quantity").mean().alias("avg_qty"),
                 col("l_extendedprice").mean().alias("avg_price"),
                 col("l_discount").mean().alias("avg_disc"),
                 col("l_quantity").count().alias("count_order"))
            .sort(["l_returnflag", "l_linestatus"]))
