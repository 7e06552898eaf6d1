"""TPC-H Q3, shipping priority: customer x orders x lineitem (two joins),
a group-by on the order (about one group per joined order), top 10 by
revenue. Copied from ``benchmarking/tpch/queries.py`` (PR 23's tree)."""

import datetime

from daft_tpu import col, lit

SCANS = {"customer": {"c_custkey": "int", "c_mktsegment": "code"},
         "orders": {"o_orderkey": "int", "o_custkey": "int",
                    "o_orderdate": "date", "o_shippriority": "int"},
         "lineitem": {"l_orderkey": "int", "l_extendedprice": "float",
                      "l_discount": "float", "l_shipdate": "date"}}


def build(get_df):
    cust = get_df("customer").where(col("c_mktsegment") == "BUILDING")
    orders = get_df("orders").where(
        col("o_orderdate") < lit(datetime.date(1995, 3, 15)))
    li = get_df("lineitem").where(
        col("l_shipdate") > lit(datetime.date(1995, 3, 15)))
    return (cust.join(orders, left_on="c_custkey", right_on="o_custkey")
            .join(li, left_on="o_orderkey", right_on="l_orderkey")
            .with_column("volume",
                         col("l_extendedprice") * (1 - col("l_discount")))
            .groupby(col("o_orderkey"), col("o_orderdate"),
                     col("o_shippriority"))
            .agg(col("volume").sum().alias("revenue"))
            .sort([col("revenue"), col("o_orderdate")], desc=[True, False])
            .limit(10)
            .select("o_orderkey", "revenue", "o_orderdate", "o_shippriority"))
