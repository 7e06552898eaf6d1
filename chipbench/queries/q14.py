"""TPC-H Q14, promotion effect: one month of ``lineitem`` (~1.3% of it)
joined to ``part`` on the part key, two global sums and their ratio.
Copied from ``benchmarking/tpch/queries.py`` (PR 39's tree); validation
parameter DATE = 1995-09-01."""

import datetime

from daft_tpu import col, lit

#: columns read, with the kind that sizes them in ``peaks.MIN_BYTES``
SCANS = {"lineitem": {"l_partkey": "int", "l_extendedprice": "float",
                      "l_discount": "float", "l_shipdate": "date"},
         "part": {"p_partkey": "int", "p_type": "code"}}

#: the scan whose filter the device's selection program runs where the
#: gate sends it (``layer_metrics/select_hbm_pct.py``): the columns the
#: program reads, every row of them, and the columns its survivors carry
SELECT_SCAN = {"table": "lineitem",
               "reads": ["l_partkey", "l_extendedprice", "l_discount",
                         "l_shipdate"],
               "writes": ["l_partkey", "l_extendedprice", "l_discount",
                          "l_shipdate"]}


def build(get_df):
    li = get_df("lineitem").where(
        (col("l_shipdate") >= lit(datetime.date(1995, 9, 1)))
        & (col("l_shipdate") < lit(datetime.date(1995, 10, 1))))
    out = li.join(get_df("part"), left_on="l_partkey", right_on="p_partkey")
    vol = col("l_extendedprice") * (1 - col("l_discount"))
    promo = col("p_type").str.startswith("PROMO")
    return (out.with_column("volume", vol)
            .with_column("promo_volume", promo.if_else(col("volume"), 0.0))
            .agg(col("promo_volume").sum().alias("promo"),
                 col("volume").sum().alias("total"))
            .select((100.0 * col("promo") / col("total"))
                    .alias("promo_revenue")))
