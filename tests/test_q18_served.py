"""TPC-H Q18 over the served path (coordinator and two workers, HTTP)
against a plain numpy Q18: the large-order subquery groups every order
key, clustered in the partial step and unbounded in the final one, which
the sort tier serves."""

import json
import urllib.request

import numpy as np
import pytest

import tpch_reference
from benchmark.refdata import iso
from tpch_queries import QUERIES

SCALE = 0.05
# the standard text's 300 leaves few orders at this scale; 250 passes the
# LIMIT of 100
QUANTITIES = (300, 250)


def numpy_q18(c, quantity):
    per_order = np.bincount(c["l_orderkey"], weights=c["l_quantity"])
    osel = per_order[c["o_orderkey"]] > quantity
    okey, ocust = c["o_orderkey"][osel], c["o_custkey"][osel]
    odate, oprice = c["o_orderdate"][osel], c["o_totalprice"][osel]
    by_cust = np.argsort(c["c_custkey"], kind="stable")
    cpos = by_cust[np.searchsorted(c["c_custkey"][by_cust], ocust)]
    top = np.lexsort((odate, -oprice))[:100]
    return [(str(c["c_name"][cpos[i]]), int(ocust[i]), int(okey[i]),
             iso(odate[i]), float(oprice[i]), float(per_order[okey[i]]))
            for i in top]


@pytest.fixture(scope="module")
def served():
    """{quantity: (rows, span tree)} from one cluster."""
    from presto_tpu.server.dqr import DistributedQueryRunner

    out = {}
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=2) as dqr:
        client = dqr.new_client()
        for quantity in QUANTITIES:
            _, rows = client.execute(
                QUERIES[18].replace("> 300", f"> {quantity}"))
            with urllib.request.urlopen(
                    f"{dqr.coordinator.uri}/v1/query/"
                    f"{client.last_query_id}/spans") as resp:
                out[quantity] = rows, json.load(resp)
    return out


@pytest.fixture(scope="module")
def columns():
    return tpch_reference.host_columns(SCALE, {
        "lineitem": ["l_orderkey", "l_quantity"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                   "o_totalprice"],
        "customer": ["c_custkey", "c_name"]})


def _aggregations(tree):
    """(tier, rows in, groups out) of every HashAggregationOperator."""
    if tree["kind"] == "task":
        return [(op["kernelTier"], op["inputRows"], op["outputRows"])
                for op in tree["attributes"]["operators"]
                if op["operator"].endswith("HashAggregationOperator")]
    return [a for child in tree.get("children", ())
            for a in _aggregations(child)]


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_q18_answers_as_numpy_does_on_the_sort_tier(served, columns,
                                                    quantity):
    rows, tree = served[quantity]
    want = numpy_q18(columns, quantity)
    assert 0 < len(want) <= 100 and (quantity == 300 or len(want) == 100)
    tpch_reference.compare([tuple(r) for r in rows], want,
                           tpch_reference.RTOL)
    aggs = _aggregations(tree)
    assert {tier for tier, rows_in, _ in aggs if rows_in} == {"sort"}
    # the final step of the subquery's GROUP BY l_orderkey: every order
    # is a group of one of its two tasks, a group a row
    orders = len(columns["o_orderkey"])
    final = [(rows_in, out) for _, rows_in, out in aggs
             if out > orders // 4]
    assert len(final) == 2 and sum(out for _, out in final) == orders
    assert all(rows_in == out for rows_in, out in final)
