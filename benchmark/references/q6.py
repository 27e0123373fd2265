"""TPC-H Q6 (forecasting revenue change) in plain numpy."""

from decimal import Decimal

from benchmark.refdata import days

COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate"]}


def reference(c: dict) -> list:
    # SQL arithmetic on the decimal literals, as TPC-H defines the bounds:
    # 0.05 and 0.07 exactly.  (IEEE doubles make 0.06 + 0.01 one ulp less
    # than 0.07, a difference below the resolution of the chip's DOUBLE;
    # the CPU engine folds the literals in IEEE f64 and misses this
    # reference, ROADMAP C0.)
    lo = float(Decimal("0.06") - Decimal("0.01"))
    hi = float(Decimal("0.06") + Decimal("0.01"))
    sd, disc = c["l_shipdate"], c["l_discount"]
    sel = ((sd >= days("1994-01-01")) & (sd < days("1995-01-01"))
           & (disc >= lo) & (disc <= hi) & (c["l_quantity"] < 24))
    return [(float((c["l_extendedprice"][sel] * disc[sel]).sum()),)]
