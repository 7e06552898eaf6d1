"""TPC-H Q1 from the files, in float64: per-file partial sums and counts,
merged, then the means."""

import datetime

import numpy as np
import pyarrow.compute as pc

from . import common

#: rows in order; the two keys and the count exact, floats within ``rtol``
COMPARE = {"kind": "rows"}

_SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
         "sum_disc")


def answer(root, rnd=common.exact):
    acc = {}
    cols = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate"]
    for t in common.tables(root, "lineitem", cols):
        t = t.filter(pc.field("l_shipdate") <= datetime.date(1998, 9, 2))
        flag = t.column("l_returnflag").combine_chunks().dictionary_encode()
        stat = t.column("l_linestatus").combine_chunks().dictionary_encode()
        flags, stats = flag.dictionary.to_pylist(), stat.dictionary.to_pylist()
        code = (flag.indices.to_numpy() * len(stats)
                + stat.indices.to_numpy())
        qty = rnd(common.f64(t, "l_quantity"))
        price = rnd(common.f64(t, "l_extendedprice"))
        disc = rnd(common.f64(t, "l_discount"))
        tax = rnd(common.f64(t, "l_tax"))
        disc_price = rnd(price * rnd(1.0 - disc))
        charge = rnd(disc_price * rnd(1.0 + tax))
        n = len(flags) * len(stats)
        counts = np.bincount(code, minlength=n)
        sums = {name: np.bincount(code, weights=plane, minlength=n)
                for name, plane in zip(
                    _SUMS, (qty, price, disc_price, charge, disc))}
        for g in np.flatnonzero(counts):
            key = (flags[g // len(stats)], stats[g % len(stats)])
            row = acc.setdefault(key, dict.fromkeys(_SUMS, 0.0) | {"n": 0})
            row["n"] += int(counts[g])
            for name in _SUMS:
                row[name] += float(sums[name][g])
    keys = sorted(acc)
    rows = [acc[k] for k in keys]
    return {
        "l_returnflag": [k[0] for k in keys],
        "l_linestatus": [k[1] for k in keys],
        "sum_qty": [r["sum_qty"] for r in rows],
        "sum_base_price": [r["sum_base_price"] for r in rows],
        "sum_disc_price": [r["sum_disc_price"] for r in rows],
        "sum_charge": [r["sum_charge"] for r in rows],
        "avg_qty": [r["sum_qty"] / r["n"] for r in rows],
        "avg_price": [r["sum_base_price"] / r["n"] for r in rows],
        "avg_disc": [r["sum_disc"] / r["n"] for r in rows],
        "count_order": [r["n"] for r in rows],
    }
