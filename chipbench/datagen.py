"""Seeded TPC-H data for the benchmark: a frozen copy of the repo's generator.

Copied from ``benchmarking/tpch/datagen.py`` (PR 23's tree) so that a later
PR can change the program's generator and not the yardstick. Row counts and
value domains follow the TPC-H specification; value distributions are
uniform from seeded numpy (``assumed`` in every config file). What differs
from the original:

- the part count is fixed, not a minimum: a large table is written in
  exactly ``parts`` files, because rows per file decide the device batch
  size and so the shapes every program compiles at;
- ``tables`` selects what is written (``tpch-sf10`` writes ``lineitem``
  only and then skips the ``orders`` columns no ``lineitem`` column derives
  from, so its data differ from a full run's: the table list is part of the
  data directory's name);
- every chunk is one picklable task, run in a process pool *before* JAX is
  imported in the parent (numpy and pyarrow only; no process here touches
  the chip).

Deterministic for ``(scale, parts, tables, seed)``: each chunk has its own
generator seeded by ``[seed, table_id, chunk_id]``.
"""

from __future__ import annotations

import datetime
import multiprocessing
import os
import shutil
from typing import Iterable, List, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

_EPOCH = datetime.date(1970, 1, 1)
_START = (datetime.date(1992, 1, 1) - _EPOCH).days
_END = (datetime.date(1998, 12, 1) - _EPOCH).days
_TODAY = (datetime.date(1995, 6, 17) - _EPOCH).days

ALL_TABLES = ("region", "nation", "supplier", "customer", "part", "partsupp",
              "orders", "lineitem")

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
CONTAINERS = [f"{a} {b}" for a in ["SM", "LG", "MED", "JUMBO", "WRAP"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM"]]
TYPES = [f"{a} {b} {c}" for a in ["STANDARD", "SMALL", "MEDIUM", "LARGE",
                                  "ECONOMY", "PROMO"]
         for b in ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
         for c in ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]]
P_NAME_WORDS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
                "black", "blanched", "blue", "blush", "brown", "burlywood",
                "burnished", "chartreuse", "chiffon", "chocolate", "coral",
                "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
                "dim", "dodger", "drab", "firebrick", "floral", "forest",
                "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
                "honeydew", "hot", "hazel", "indian", "ivory", "khaki",
                "lace", "lavender", "lawn", "lemon", "light", "lime", "linen"]


def _dates(rng, n, lo=_START, hi=_END):
    return rng.integers(lo, hi, n).astype("datetime64[D]")


def _money(rng, n, lo, hi):
    return np.round(rng.uniform(lo, hi, n), 2)


def _pick(rng, choices, n):
    idx = rng.integers(0, len(choices), n).astype(np.int32)
    return pa.DictionaryArray.from_arrays(
        pa.array(idx), pa.array(choices)).cast(pa.string())


def _comment(rng, n, words=8):
    w = pa.array(P_NAME_WORDS)
    cols = [pc.take(w, pa.array(
        rng.integers(0, len(P_NAME_WORDS), n).astype(np.int32)))
        for _ in range(words)]
    return pc.binary_join_element_wise(*cols, " ")


def _tagged(prefix: str, keys: np.ndarray) -> pa.Array:
    padded = pc.utf8_lpad(pc.cast(pa.array(keys), pa.string()), 9, "0")
    return pc.binary_join_element_wise(
        pa.nulls(len(keys), pa.string()).fill_null(prefix + "#"), padded, "")


def _phone(rng, n, lo=0) -> pa.Array:
    i = np.arange(lo, lo + n, dtype=np.int64)
    cc = pc.cast(pa.array(rng.integers(10, 35, n)), pa.string())
    p1 = pc.utf8_lpad(pc.cast(pa.array(i % 999), pa.string()), 3, "0")
    p2 = pc.utf8_lpad(pc.cast(pa.array((i * 7) % 999), pa.string()), 3, "0")
    p3 = pc.utf8_lpad(pc.cast(pa.array((i * 13) % 9999), pa.string()), 4, "0")
    return pc.binary_join_element_wise(cc, p1, p2, p3, "-")


def _mark(base: pa.Array, rng, n, prob: float, marker: str) -> pa.Array:
    marks = pa.array(rng.random(n) < prob)
    marked = pc.binary_join_element_wise(
        base, pa.nulls(n, pa.string()).fill_null(marker), " ")
    return pc.if_else(marks, marked, base)


def _counts(sf: float) -> dict:
    return {"supp": max(int(10_000 * sf), 10),
            "cust": max(int(150_000 * sf), 30),
            "part": max(int(200_000 * sf), 40),
            "ord": max(int(1_500_000 * sf), 150),
            "clerk": max(int(1000 * sf), 10)}


def _write(root: str, name: str, idx: int, table: pa.Table) -> None:
    d = os.path.join(root, name)
    os.makedirs(d, exist_ok=True)
    pq.write_table(table, os.path.join(d, f"{name}.{idx}.parquet"))


# ------------------------------------------------- one task = one chunk

def _gen_dims(root, sf, seed, want, cid, lo, hi):
    rng = np.random.default_rng([seed, 0])
    region = pa.table({"r_regionkey": pa.array(range(5), pa.int64()),
                       "r_name": REGIONS, "r_comment": _comment(rng, 5)})
    nation = pa.table({
        "n_nationkey": pa.array(range(25), pa.int64()),
        "n_name": [n for n, _ in NATIONS],
        "n_regionkey": pa.array([r for _, r in NATIONS], pa.int64()),
        "n_comment": _comment(rng, 25)})
    if "region" in want:
        _write(root, "region", 0, region)
    if "nation" in want:
        _write(root, "nation", 0, nation)


def _gen_supplier(root, sf, seed, want, cid, lo, hi):
    r = np.random.default_rng([seed, 1, cid])
    sk = np.arange(lo + 1, hi + 1)
    m = hi - lo
    _write(root, "supplier", cid, pa.table({
        "s_suppkey": sk,
        "s_name": _tagged("Supplier", sk),
        "s_address": _comment(r, m, 3),
        "s_nationkey": r.integers(0, 25, m),
        "s_phone": _phone(r, m, lo),
        "s_acctbal": _money(r, m, -999.99, 9999.99),
        "s_comment": _mark(_comment(r, m, 6), r, m, 0.0005,
                           "Customer Complaints"),
    }))


def _gen_customer(root, sf, seed, want, cid, lo, hi):
    r = np.random.default_rng([seed, 2, cid])
    ck = np.arange(lo + 1, hi + 1)
    m = hi - lo
    _write(root, "customer", cid, pa.table({
        "c_custkey": ck,
        "c_name": _tagged("Customer", ck),
        "c_address": _comment(r, m, 3),
        "c_nationkey": r.integers(0, 25, m),
        "c_phone": _phone(r, m, lo),
        "c_acctbal": _money(r, m, -999.99, 9999.99),
        "c_mktsegment": _pick(r, SEGMENTS, m),
        "c_comment": _comment(r, m, 6),
    }))


def _gen_part(root, sf, seed, want, cid, lo, hi):
    n_supp = _counts(sf)["supp"]
    r = np.random.default_rng([seed, 3, cid])
    pk = np.arange(lo + 1, hi + 1)
    m = hi - lo
    wnames = pa.array(P_NAME_WORDS)
    name_cols = [pc.take(wnames, pa.array(
        r.integers(0, len(P_NAME_WORDS), m).astype(np.int32)))
        for _ in range(5)]
    brand = pc.binary_join_element_wise(
        pa.nulls(m, pa.string()).fill_null("Brand#"),
        pc.cast(pa.array(r.integers(1, 6, m)), pa.string()),
        pc.cast(pa.array(r.integers(1, 6, m)), pa.string()), "")
    mfgr = pc.binary_join_element_wise(
        pa.nulls(m, pa.string()).fill_null("Manufacturer#"),
        pc.cast(pa.array(r.integers(1, 6, m)), pa.string()), "")
    part = pa.table({
        "p_partkey": pk,
        "p_name": pc.binary_join_element_wise(*name_cols, " "),
        "p_mfgr": mfgr,
        "p_brand": brand,
        "p_type": _pick(r, TYPES, m),
        "p_size": r.integers(1, 51, m),
        "p_container": _pick(r, CONTAINERS, m),
        "p_retailprice": _money(r, m, 900, 2000),
        "p_comment": _comment(r, m, 3),
    })
    if "part" in want:
        _write(root, "part", cid, part)
    if "partsupp" not in want:
        return
    # 4 suppliers per part, by the formula lineitem uses, so that
    # (l_partkey, l_suppkey) joins hit
    ps_part = np.repeat(pk, 4)
    n_ps = len(ps_part)
    ps_supp = ((ps_part - 1 + (np.tile(np.arange(4), m)
                               * (n_supp // 4 + 1))) % n_supp) + 1
    _write(root, "partsupp", cid, pa.table({
        "ps_partkey": ps_part,
        "ps_suppkey": ps_supp,
        "ps_availqty": r.integers(1, 10_000, n_ps),
        "ps_supplycost": _money(r, n_ps, 1.0, 1000.0),
        "ps_comment": _comment(r, n_ps, 10),
    }))


def _gen_orders(root, sf, seed, want, cid, lo, hi):
    """orders and lineitem of one order-key range, made together so that
    lineitem's dates derive from its orders' without cross-chunk state."""
    n = _counts(sf)
    r = np.random.default_rng([seed, 4, cid])
    m = hi - lo
    ok = (np.arange(lo + 1, hi + 1)) * 4 - 3  # sparse keys like dbgen
    if "orders" in want:
        o_cust = r.integers(1, n["cust"] + 1, m)
        o_status = _pick(r, ["F", "O", "P"], m)
        o_total = _money(r, m, 1000, 500_000)
    o_date = _dates(r, m, _START, _END - 151)
    if "orders" in want:
        _write(root, "orders", cid, pa.table({
            "o_orderkey": ok,
            "o_custkey": o_cust,
            "o_orderstatus": o_status,
            "o_totalprice": o_total,
            "o_orderdate": o_date,
            "o_orderpriority": _pick(r, PRIORITIES, m),
            "o_clerk": _tagged("Clerk", r.integers(1, n["clerk"], m)),
            "o_shippriority": np.zeros(m, dtype=np.int32),
            "o_comment": _mark(_comment(r, m, 6), r, m, 0.01,
                               "special requests"),
        }))
    if "lineitem" not in want:
        return
    per_order = r.integers(1, 8, m)
    l_orderkey = np.repeat(ok, per_order)
    l_odate = np.repeat(o_date.astype(np.int64), per_order)
    n_li = len(l_orderkey)
    starts = np.repeat(np.cumsum(per_order) - per_order, per_order)
    linenumber = np.arange(n_li, dtype=np.int64) - starts + 1
    qty = r.integers(1, 51, n_li).astype(np.float64)
    partkey = r.integers(1, n["part"] + 1, n_li)
    price = np.round(qty * (90_000 + (partkey % 20_001) + 100 *
                            (partkey % 1000)) / 100.0 / 50.0, 2)
    ship_delta = r.integers(1, 122, n_li)
    commit_delta = r.integers(30, 91, n_li)
    receipt_delta = r.integers(1, 31, n_li)
    l_ship = l_odate + ship_delta
    l_receipt = l_ship + receipt_delta
    returnflag = np.where(
        l_receipt <= _TODAY,
        np.array(["R", "A"])[r.integers(0, 2, n_li)], "N")
    linestatus = np.where(l_ship > _TODAY, "O", "F")
    _write(root, "lineitem", cid, pa.table({
        "l_orderkey": l_orderkey,
        "l_partkey": partkey,
        # spec 4.2.3: a lineitem's supplier is one of its part's four
        # partsupp suppliers
        "l_suppkey": ((partkey - 1 + (linenumber % 4)
                       * (n["supp"] // 4 + 1)) % n["supp"]) + 1,
        "l_linenumber": linenumber,
        "l_quantity": qty,
        "l_extendedprice": price,
        "l_discount": np.round(r.integers(0, 11, n_li) / 100.0, 2),
        "l_tax": np.round(r.integers(0, 9, n_li) / 100.0, 2),
        "l_returnflag": pa.array(returnflag),
        "l_linestatus": pa.array(linestatus),
        "l_shipdate": l_ship.astype("datetime64[D]"),
        "l_commitdate": (l_odate + commit_delta).astype("datetime64[D]"),
        "l_receiptdate": l_receipt.astype("datetime64[D]"),
        "l_shipinstruct": _pick(r, INSTRUCTS, n_li),
        "l_shipmode": _pick(r, SHIPMODES, n_li),
        "l_comment": _comment(r, n_li, 4),
    }))


#: generator of a chunk, the tables it writes, the count it is cut from
_KINDS = (
    (_gen_dims, ("region", "nation"), None),
    (_gen_supplier, ("supplier",), "supp"),
    (_gen_customer, ("customer",), "cust"),
    (_gen_part, ("part", "partsupp"), "part"),
    (_gen_orders, ("orders", "lineitem"), "ord"),
)


def _run(task) -> None:
    kind, args = task
    _KINDS[kind][0](*args)


def chunk_tasks(root: str, sf: float, parts: int, tables: Sequence[str],
                seed: int) -> List[Tuple]:
    """The chunks that make ``tables``, largest first (so a pool ends on
    the small ones)."""
    want = tuple(tables)
    unknown = set(want) - set(ALL_TABLES)
    if unknown:
        raise ValueError(f"not TPC-H tables: {sorted(unknown)}")
    n = _counts(sf)
    tasks = []
    for kind in reversed(range(len(_KINDS))):
        _, writes, count = _KINDS[kind]
        if not set(writes) & set(want):
            continue
        if count is None:
            tasks.append((kind, (root, sf, seed, want, 0, 0, 0)))
            continue
        total = n[count]
        cuts = [total * i // parts for i in range(parts + 1)]
        for cid, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
            if hi > lo:
                tasks.append((kind, (root, sf, seed, want, cid, lo, hi)))
    return tasks


def _stem(name: str, sf: float, parts: int, tables: Iterable[str]) -> str:
    tabs = "all" if set(tables) == set(ALL_TABLES) else "-".join(
        sorted(tables))
    return f"{name}_sf{sf:g}_p{parts}_{tabs}_s"


def ensure_dataset(cache_root: str, name: str, sf: float, parts: int,
                   tables: Sequence[str], seed: int, workers: int) -> str:
    """The directory of this seed's data, generated if its marker is
    absent. Data of the same configuration for another seed is removed
    first: one seed's SF10 ``lineitem`` is gigabytes, a check runs many
    seeds, and a run's set-up should not depend on how many came before."""
    stem = _stem(name, sf, parts, tables)
    root = os.path.join(cache_root, f"{stem}{seed}")
    marker = os.path.join(root, "_COMPLETE")
    if os.path.exists(marker):
        return root
    os.makedirs(cache_root, exist_ok=True)
    for other in os.listdir(cache_root):
        if other.startswith(stem):
            shutil.rmtree(os.path.join(cache_root, other))
    tasks = chunk_tasks(root, sf, parts, tables, seed)
    workers = max(1, min(workers, len(tasks)))
    if workers == 1:
        for t in tasks:
            _run(t)
    else:
        ctx = multiprocessing.get_context("spawn")
        with ctx.Pool(workers) as pool:
            for _ in pool.imap_unordered(_run, tasks):
                pass
            pool.close()
            pool.join()   # every worker has ended before JAX is imported
    with open(marker, "w") as f:
        f.write("ok\n")
    return root
