"""TPC-H Q19, discounted revenue: ``lineitem`` joined to ``part`` on the
part key under a disjunction of three conjunctions over both sides; the
optimizer pushes the part common to all three (``l_shipinstruct``,
``l_shipmode``: ~3.6% of ``lineitem``) into the scan. One global sum.
Copied from ``benchmarking/tpch/queries.py`` (PR 39's tree); validation
parameters Brand#12 / Brand#23 / Brand#34, quantities 1 / 10 / 20."""

from daft_tpu import col

#: columns read, with the kind that sizes them in ``peaks.MIN_BYTES``
SCANS = {"lineitem": {"l_partkey": "int", "l_quantity": "float",
                      "l_extendedprice": "float", "l_discount": "float",
                      "l_shipinstruct": "code", "l_shipmode": "code"},
         "part": {"p_partkey": "int", "p_brand": "code", "p_size": "int",
                  "p_container": "code"}}

#: see ``q14.SELECT_SCAN``
SELECT_SCAN = {"table": "lineitem",
               "reads": ["l_partkey", "l_quantity", "l_extendedprice",
                         "l_discount", "l_shipinstruct", "l_shipmode"],
               "writes": ["l_partkey", "l_quantity", "l_extendedprice",
                          "l_discount", "l_shipinstruct", "l_shipmode"]}


def build(get_df):
    out = get_df("lineitem").join(get_df("part"), left_on="l_partkey",
                                  right_on="p_partkey")
    common = (col("l_shipinstruct") == "DELIVER IN PERSON") \
        & col("l_shipmode").is_in(["AIR", "AIR REG"])
    b1 = ((col("p_brand") == "Brand#12")
          & col("p_container").is_in(["SM CASE", "SM BOX", "SM PACK",
                                      "SM PKG"])
          & (col("l_quantity") >= 1) & (col("l_quantity") <= 11)
          & col("p_size").between(1, 5))
    b2 = ((col("p_brand") == "Brand#23")
          & col("p_container").is_in(["MED BAG", "MED BOX", "MED PKG",
                                      "MED PACK"])
          & (col("l_quantity") >= 10) & (col("l_quantity") <= 20)
          & col("p_size").between(1, 10))
    b3 = ((col("p_brand") == "Brand#34")
          & col("p_container").is_in(["LG CASE", "LG BOX", "LG PACK",
                                      "LG PKG"])
          & (col("l_quantity") >= 20) & (col("l_quantity") <= 30)
          & col("p_size").between(1, 15))
    return (out.where(common & (b1 | b2 | b3))
            .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum()
                 .alias("revenue")))
