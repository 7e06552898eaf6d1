"""TPC-H Q10 from the files, with pandas in float64. Returns the ranking
beyond the cut, as Q3's reference does."""

import datetime

import numpy as np
import pyarrow.compute as pc

from . import common

K = 20
CANDIDATES = K + 32
COMPARE = {"kind": "topk", "k": K, "keys": ["c_custkey"], "by": "revenue"}


def answer(root, rnd=common.exact):
    f = pc.field
    orders = common.frame(
        root, "orders", ["o_orderkey", "o_custkey", "o_orderdate"],
        filters=(f("o_orderdate") >= datetime.date(1993, 10, 1))
        & (f("o_orderdate") < datetime.date(1994, 1, 1)))
    li = common.frame(
        root, "lineitem",
        ["l_orderkey", "l_extendedprice", "l_discount", "l_returnflag"],
        filters=f("l_returnflag") == "R")
    j = orders.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j["volume"] = rnd(rnd(j.l_extendedprice.to_numpy(np.float64))
                      * rnd(1.0 - rnd(j.l_discount.to_numpy(np.float64))))
    g = j.groupby("o_custkey", as_index=False).agg(revenue=("volume", "sum"))
    g = g.sort_values(["revenue", "o_custkey"],
                      ascending=[False, True]).head(CANDIDATES)
    cust = common.frame(root, "customer",
                        ["c_custkey", "c_name", "c_acctbal", "c_nationkey",
                         "c_address", "c_phone", "c_comment"])
    nation = common.frame(root, "nation", ["n_nationkey", "n_name"])
    g = (g.merge(cust, left_on="o_custkey", right_on="c_custkey")
         .merge(nation, left_on="c_nationkey", right_on="n_nationkey")
         .sort_values(["revenue", "c_custkey"], ascending=[False, True]))
    return {"c_custkey": g.c_custkey.tolist(),
            "c_name": g.c_name.tolist(),
            "revenue": g.revenue.tolist(),
            "c_acctbal": g.c_acctbal.tolist(),
            "n_name": g.n_name.tolist(),
            "c_address": g.c_address.tolist(),
            "c_phone": g.c_phone.tolist(),
            "c_comment": g.c_comment.tolist()}
