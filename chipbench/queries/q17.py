"""TPC-H Q17, small-quantity-order revenue: the ~0.1% of ``part`` that is
one brand in one container, joined to ``lineitem`` on the part key: the
average quantity a part, then the rows under a fifth of it, one global
sum. ``lineitem`` has no predicate of its own: its only filter is the
join's key set, and the plan asks for that join twice. Copied from
``benchmarking/tpch/queries.py`` (PR 47's tree); validation parameters
BRAND = Brand#23, CONTAINER = MED BOX."""

from daft_tpu import col

#: columns read, with the kind that sizes them in ``peaks.MIN_BYTES``
SCANS = {"lineitem": {"l_partkey": "int", "l_quantity": "float",
                      "l_extendedprice": "float"},
         "part": {"p_partkey": "int", "p_brand": "code",
                  "p_container": "code"}}


def build(get_df):
    part = get_df("part").where((col("p_brand") == "Brand#23")
                                & (col("p_container") == "MED BOX"))
    li = get_df("lineitem")
    joined = part.join(li, left_on="p_partkey", right_on="l_partkey")
    avg_qty = (joined.groupby("p_partkey")
               .agg((col("l_quantity").mean() * 0.2)
                    .alias("avg_qty_threshold")))
    return (joined.join(avg_qty, on="p_partkey")
            .where(col("l_quantity") < col("avg_qty_threshold"))
            .agg((col("l_extendedprice").sum() / 7.0).alias("avg_yearly")))
