"""TPC-H Q6 from the files, in float64."""

import datetime

import pyarrow.compute as pc

from . import common

COMPARE = {"kind": "rows"}


def answer(root, rnd=common.exact):
    f = pc.field
    keep = ((f("l_shipdate") >= datetime.date(1994, 1, 1))
            & (f("l_shipdate") < datetime.date(1995, 1, 1))
            & (f("l_discount") >= 0.05) & (f("l_discount") <= 0.07)
            & (f("l_quantity") < 24))
    revenue = 0.0
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]
    for t in common.tables(root, "lineitem", cols):
        t = t.filter(keep)
        revenue += float(rnd(rnd(common.f64(t, "l_extendedprice"))
                             * rnd(common.f64(t, "l_discount"))).sum())
    return {"revenue": [revenue]}
