"""TPC-H Q19 from the files, with pandas in float64."""

import numpy as np
import pyarrow.compute as pc

from . import common

COMPARE = {"kind": "rows"}

#: brand, containers, least quantity, largest size (quantity spans 10)
BRANCHES = (("Brand#12", ("SM CASE", "SM BOX", "SM PACK", "SM PKG"), 1, 5),
            ("Brand#23", ("MED BAG", "MED BOX", "MED PKG", "MED PACK"),
             10, 10),
            ("Brand#34", ("LG CASE", "LG BOX", "LG PACK", "LG PKG"), 20, 15))


def answer(root, rnd=common.exact):
    f = pc.field
    li = common.frame(
        root, "lineitem",
        ["l_partkey", "l_quantity", "l_extendedprice", "l_discount"],
        filters=(f("l_shipinstruct") == "DELIVER IN PERSON")
        & f("l_shipmode").isin(["AIR", "AIR REG"]))
    part = common.frame(root, "part",
                        ["p_partkey", "p_brand", "p_size", "p_container"])
    j = li.merge(part, left_on="l_partkey", right_on="p_partkey")
    keep = np.zeros(len(j), dtype=bool)
    for brand, containers, qty, size in BRANCHES:
        keep |= ((j.p_brand == brand) & j.p_container.isin(containers)
                 & (j.l_quantity >= qty) & (j.l_quantity <= qty + 10)
                 & (j.p_size >= 1) & (j.p_size <= size)).to_numpy()
    j = j[keep]
    revenue = rnd(rnd(j.l_extendedprice.to_numpy(np.float64))
                  * rnd(1.0 - rnd(j.l_discount.to_numpy(np.float64))))
    # an empty selection sums to null, as SQL's sum does
    return {"revenue": [float(revenue.sum()) if len(j) else None]}
