"""TPC-H Q10, returned item reporting: customer x orders x lineitem x
nation (three joins), a group-by on seven columns of which four are long
strings, top 20 by revenue. Copied from ``benchmarking/tpch/queries.py``
(PR 23's tree)."""

import datetime

from daft_tpu import col, lit

SCANS = {"customer": {"c_custkey": "int", "c_nationkey": "int"},
         "orders": {"o_orderkey": "int", "o_custkey": "int",
                    "o_orderdate": "date"},
         "lineitem": {"l_orderkey": "int", "l_extendedprice": "float",
                      "l_discount": "float", "l_returnflag": "code"},
         "nation": {"n_nationkey": "int"}}


def build(get_df):
    orders = get_df("orders").where(
        (col("o_orderdate") >= lit(datetime.date(1993, 10, 1)))
        & (col("o_orderdate") < lit(datetime.date(1994, 1, 1))))
    li = get_df("lineitem").where(col("l_returnflag") == "R")
    out = (get_df("customer")
           .join(orders, left_on="c_custkey", right_on="o_custkey")
           .join(li, left_on="o_orderkey", right_on="l_orderkey")
           .join(get_df("nation"), left_on="c_nationkey",
                 right_on="n_nationkey"))
    return (out.with_column("volume",
                            col("l_extendedprice") * (1 - col("l_discount")))
            .groupby("c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
                     "c_address", "c_comment")
            .agg(col("volume").sum().alias("revenue"))
            .sort([col("revenue"), col("c_custkey")], desc=[True, False])
            .limit(20)
            .select("c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
                    "c_address", "c_phone", "c_comment"))
