"""TPC-H Q3 from the files, with pandas in float64. Returns the ranking
beyond the cut (``CANDIDATES`` rows) so that the comparison can tolerate
ties at the cut."""

import datetime

import numpy as np
import pyarrow.compute as pc

from . import common

K = 10
CANDIDATES = K + 32
#: top ``k`` by the first of ``by``; a row is known by ``keys``
COMPARE = {"kind": "topk", "k": K, "keys": ["o_orderkey"],
           "by": "revenue"}


def answer(root, rnd=common.exact):
    cut = datetime.date(1995, 3, 15)
    cust = common.frame(root, "customer", ["c_custkey", "c_mktsegment"])
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = common.frame(
        root, "orders",
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
        filters=pc.field("o_orderdate") < cut)
    orders = orders[orders.o_custkey.isin(cust.c_custkey)]
    li = common.frame(
        root, "lineitem",
        ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
        filters=pc.field("l_shipdate") > cut)
    j = orders.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j["volume"] = rnd(rnd(j.l_extendedprice.to_numpy(np.float64))
                      * rnd(1.0 - rnd(j.l_discount.to_numpy(np.float64))))
    g = (j.groupby(["o_orderkey", "o_orderdate", "o_shippriority"],
                   as_index=False).agg(revenue=("volume", "sum")))
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(CANDIDATES)
    return {"o_orderkey": g.o_orderkey.tolist(),
            "revenue": g.revenue.tolist(),
            "o_orderdate": [d.date() for d in g.o_orderdate],
            "o_shippriority": g.o_shippriority.tolist()}
