"""TPC-H Q3 (shipping priority) in plain numpy."""

import numpy as np

from benchmark.refdata import days, iso

COLUMNS = {"lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"],
           "customer": ["c_custkey", "c_mktsegment"]}


def reference(c: dict) -> list:
    cut = days("1995-03-15")
    building = c["c_custkey"][c["c_mktsegment"] == "BUILDING"]
    osel = np.isin(c["o_custkey"], building) & (c["o_orderdate"] < cut)
    okey = c["o_orderkey"][osel]
    odate, oprio = c["o_orderdate"][osel], c["o_shippriority"][osel]
    order = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[order], odate[order], oprio[order]
    lsel = c["l_shipdate"] > cut
    lkey = c["l_orderkey"][lsel]
    pos = np.clip(np.searchsorted(okey, lkey), 0, max(len(okey) - 1, 0))
    hit = okey[pos] == lkey
    rev = (c["l_extendedprice"][lsel] * (1.0 - c["l_discount"][lsel]))[hit]
    sums = np.bincount(pos[hit], weights=rev, minlength=len(okey))
    live = np.flatnonzero(np.bincount(pos[hit], minlength=len(okey)))
    top = live[np.lexsort((odate[live], -sums[live]))][:10]
    return [(int(okey[g]), float(sums[g]), iso(odate[g]), int(oprio[g]))
            for g in top]
