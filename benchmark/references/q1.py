"""TPC-H Q1 (pricing summary report) in plain numpy."""

import numpy as np

from benchmark.refdata import days

COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_returnflag", "l_linestatus",
                        "l_shipdate"]}


def reference(c: dict) -> list:
    sel = c["l_shipdate"] <= days("1998-12-01") - 90
    rf, ls = c["l_returnflag"][sel], c["l_linestatus"][sel]
    qty, price = c["l_quantity"][sel], c["l_extendedprice"][sel]
    disc, tax = c["l_discount"][sel], c["l_tax"][sel]
    disc_price = price * (1.0 - disc)
    charge = disc_price * (1.0 + tax)
    keys, inv = np.unique(np.char.add(rf, ls), return_inverse=True)
    n = np.bincount(inv, minlength=len(keys))
    sums = [np.bincount(inv, weights=w, minlength=len(keys))
            for w in (qty, price, disc_price, charge, disc)]
    return [(k[0], k[1], sums[0][g], sums[1][g], sums[2][g], sums[3][g],
             sums[0][g] / n[g], sums[1][g] / n[g], sums[4][g] / n[g],
             int(n[g])) for g, k in enumerate(keys)]
