"""TPC-H Q17 from the files, with pandas in float64."""

import numpy as np
import pyarrow.compute as pc

from . import common

COMPARE = {"kind": "rows"}

BRAND, CONTAINER = "Brand#23", "MED BOX"


def answer(root, rnd=common.exact):
    f = pc.field
    part = common.frame(
        root, "part", ["p_partkey"],
        filters=(f("p_brand") == BRAND) & (f("p_container") == CONTAINER))
    keys = part.p_partkey.to_numpy()
    # an empty key set sums nothing: null, as SQL's sum gives
    if not len(keys):
        return {"avg_yearly": [None]}
    li = common.frame(root, "lineitem",
                      ["l_partkey", "l_quantity", "l_extendedprice"],
                      filters=f("l_partkey").isin(keys))
    li["qty"] = rnd(li.l_quantity.to_numpy(np.float64))
    mean = li.groupby("l_partkey").qty.mean()
    # the threshold a part: a fifth of its mean quantity
    limit = rnd(0.2 * rnd(mean.reindex(li.l_partkey).to_numpy()))
    small = li.qty.to_numpy() < limit
    if not small.any():
        return {"avg_yearly": [None]}
    price = rnd(li.l_extendedprice.to_numpy(np.float64)[small])
    return {"avg_yearly": [float(rnd(float(price.sum()) / 7.0))]}
