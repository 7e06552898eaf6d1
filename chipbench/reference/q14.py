"""TPC-H Q14 from the files, with pandas in float64."""

import datetime

import numpy as np
import pyarrow.compute as pc

from . import common

COMPARE = {"kind": "rows"}


def answer(root, rnd=common.exact):
    f = pc.field
    li = common.frame(
        root, "lineitem", ["l_partkey", "l_extendedprice", "l_discount"],
        filters=(f("l_shipdate") >= datetime.date(1995, 9, 1))
        & (f("l_shipdate") < datetime.date(1995, 10, 1)))
    part = common.frame(root, "part", ["p_partkey", "p_type"])
    j = li.merge(part, left_on="l_partkey", right_on="p_partkey")
    volume = rnd(rnd(j.l_extendedprice.to_numpy(np.float64))
                 * rnd(1.0 - rnd(j.l_discount.to_numpy(np.float64))))
    promo = j.p_type.str.startswith("PROMO").to_numpy()
    total = float(volume.sum())
    if not total:   # an empty month: null over null, as SQL's sums give
        return {"promo_revenue": [None]}
    share = rnd(rnd(100.0 * float(volume[promo].sum())) / total)
    return {"promo_revenue": [float(share)]}
