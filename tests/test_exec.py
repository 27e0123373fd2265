"""Execution runtime tests: hand-built operator pipelines over TPC-H data,
parity-checked against direct numpy computation (reference tier:
HandTpchQuery1/6 benchmarks + OperatorAssertion golden results)."""

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.batch import batch_from_pylist
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.aggregation import (
    AggChannel, GlobalAggregationOperatorFactory, HashAggregationOperatorFactory,
)
from presto_tpu.exec.driver import Pipeline
from presto_tpu.exec.joinop import (
    HashBuildOperatorFactory, LookupJoinOperatorFactory,
)
from presto_tpu.exec.operators import (
    FilterProjectOperatorFactory, LimitOperatorFactory, OutputCollectorFactory,
    TableScanOperatorFactory, ValuesOperatorFactory,
)
from presto_tpu.exec.runner import execute_pipelines
from presto_tpu.exec.sortop import OrderByOperatorFactory, SortSpec
from presto_tpu.expr import build as B

SCALE = 0.005


@pytest.fixture(scope="module")
def tpch():
    return TpchConnector(scale=SCALE)


def scan_numpy(conn, table, columns):
    handle = conn.get_table(table)
    from presto_tpu.batch import concat_batches

    batches = []
    for split in conn.get_splits(handle, 1):
        batches.extend(conn.page_source(split, columns))
    return concat_batches(batches)


def all_splits(conn, table, n=3):
    return conn.get_splits(conn.get_table(table), n)


def test_q6_filter_global_agg(tpch):
    """TPC-H Q6: sum(extendedprice * discount) with date/qty/discount range
    filters — the FilterAndProject + AggregationOperator slice."""
    cols = ["l_shipdate", "l_quantity", "l_discount", "l_extendedprice"]
    D, Q, DISC, EX = range(4)
    filt = B.and_(
        B.comparison(">=", B.ref(D, T.DATE), B.const("1994-01-01", T.DATE)),
        B.comparison("<", B.ref(D, T.DATE), B.const("1995-01-01", T.DATE)),
        B.between(B.ref(DISC, T.DOUBLE), B.const(0.05, T.DOUBLE),
                  B.const(0.07, T.DOUBLE)),
        B.comparison("<", B.ref(Q, T.DOUBLE), B.const(24.0, T.DOUBLE)))
    proj = [B.call("multiply", B.ref(EX, T.DOUBLE), B.ref(DISC, T.DOUBLE))]
    out = OutputCollectorFactory()
    pipeline = Pipeline([
        TableScanOperatorFactory(tpch, cols, batch_rows=4096),
        FilterProjectOperatorFactory(filt, proj, [T.DATE, T.DOUBLE,
                                                  T.DOUBLE, T.DOUBLE]),
        GlobalAggregationOperatorFactory([AggChannel("sum", 0, T.DOUBLE)],
                                         [T.DOUBLE]),
        out,
    ], splits=all_splits(tpch, "lineitem"))
    execute_pipelines([pipeline])
    (got,) = out.rows()[0]

    # numpy oracle
    b = scan_numpy(tpch, "lineitem", cols).to_numpy()
    ship = np.asarray(b.columns[0].values)
    qty = np.asarray(b.columns[1].values)
    disc = np.asarray(b.columns[2].values)
    ext = np.asarray(b.columns[3].values)
    lo = T.DATE.from_python("1994-01-01")
    hi = T.DATE.from_python("1995-01-01")
    mask = (ship >= lo) & (ship < hi) & (disc >= 0.05) & (disc <= 0.07) & \
        (qty < 24.0)
    expected = float((ext[mask] * disc[mask]).sum())
    assert got == pytest.approx(expected, rel=1e-9)
    assert expected > 0


def test_q1_grouped_agg(tpch):
    """TPC-H Q1 slice: grouped aggregation over two dictionary key columns
    with computed measures, then ORDER BY."""
    cols = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate"]
    RF, LS, Q, EP, DI, TX, SD = range(7)
    cutoff = "1998-09-02"
    filt = B.comparison("<=", B.ref(SD, T.DATE), B.const(cutoff, T.DATE))
    disc_price = B.call("multiply", B.ref(EP, T.DOUBLE),
                        B.call("subtract", B.const(1.0, T.DOUBLE),
                               B.ref(DI, T.DOUBLE)))
    charge = B.call("multiply", disc_price,
                    B.call("add", B.const(1.0, T.DOUBLE), B.ref(TX, T.DOUBLE)))
    proj = [B.ref(RF, T.VARCHAR), B.ref(LS, T.VARCHAR), B.ref(Q, T.DOUBLE),
            B.ref(EP, T.DOUBLE), disc_price, charge]
    out = OutputCollectorFactory()
    pipeline = Pipeline([
        TableScanOperatorFactory(tpch, cols, batch_rows=8192),
        FilterProjectOperatorFactory(
            filt, proj, [T.VARCHAR, T.VARCHAR] + [T.DOUBLE] * 4 + [T.DATE]),
        HashAggregationOperatorFactory(
            [0, 1],
            [AggChannel("sum", 2, T.DOUBLE), AggChannel("sum", 3, T.DOUBLE),
             AggChannel("sum", 4, T.DOUBLE), AggChannel("sum", 5, T.DOUBLE),
             AggChannel("count", None, T.BIGINT)],
            [T.VARCHAR, T.VARCHAR] + [T.DOUBLE] * 4),
        OrderByOperatorFactory([SortSpec(0), SortSpec(1)]),
        out,
    ], splits=all_splits(tpch, "lineitem"))
    execute_pipelines([pipeline])
    got = out.rows()

    b = scan_numpy(tpch, "lineitem", cols)
    rows = b.to_pylist()
    cutoff_d = __import__("datetime").date(1998, 9, 2)
    agg = {}
    for rf, ls, q, ep, di, tx, sd in rows:
        if sd <= cutoff_d:
            e = agg.setdefault((rf, ls), [0.0, 0.0, 0.0, 0.0, 0])
            e[0] += q
            e[1] += ep
            e[2] += ep * (1 - di)
            e[3] += ep * (1 - di) * (1 + tx)
            e[4] += 1
    expected = sorted((k[0], k[1], *v) for k, v in agg.items())
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        assert g[0] == e[0] and g[1] == e[1]
        for gv, ev in zip(g[2:6], e[2:6]):
            assert gv == pytest.approx(ev, rel=1e-9)
        assert g[6] == e[6]


def test_join_pipeline(tpch):
    """orders JOIN customer ON o_custkey = c_custkey (single-key streaming
    build/probe), counting matches."""
    build = HashBuildOperatorFactory([0], [T.BIGINT, T.VARCHAR])
    build_pipeline = Pipeline([
        TableScanOperatorFactory(tpch, ["c_custkey", "c_mktsegment"]),
        build,
    ], splits=all_splits(tpch, "customer"), name="build")
    out = OutputCollectorFactory()
    probe_pipeline = Pipeline([
        TableScanOperatorFactory(tpch, ["o_orderkey", "o_custkey"]),
        LookupJoinOperatorFactory(build, [1], [T.BIGINT, T.BIGINT], "inner"),
        out,
    ], splits=all_splits(tpch, "orders"), name="probe")
    execute_pipelines([build_pipeline, probe_pipeline])
    rows = out.rows()
    orders = scan_numpy(tpch, "orders", ["o_orderkey", "o_custkey"]).to_pylist()
    cust = dict(scan_numpy(tpch, "customer",
                           ["c_custkey", "c_mktsegment"]).to_pylist())
    assert len(rows) == len(orders)  # every order has exactly one customer
    for okey, ckey, ckey2, seg in rows[:500]:
        assert ckey == ckey2
        assert seg == cust[ckey]


def test_left_join_and_semi(tpch):
    """customer LEFT JOIN orders + semijoin: 1/3 of customers have no
    orders (the 2/3-customer rule)."""
    build = HashBuildOperatorFactory([0], [T.BIGINT])
    build_pipeline = Pipeline([
        TableScanOperatorFactory(tpch, ["o_custkey"]),
        build,
    ], splits=all_splits(tpch, "orders"), name="build")
    out = OutputCollectorFactory()
    probe = Pipeline([
        TableScanOperatorFactory(tpch, ["c_custkey"]),
        LookupJoinOperatorFactory(build, [0], [T.BIGINT], "semi"),
        out,
    ], splits=all_splits(tpch, "customer"), name="probe")
    execute_pipelines([build_pipeline, probe])
    with_orders = {r[0] for r in out.rows()}
    ordered_custkeys = {r[0] for r in
                        scan_numpy(tpch, "orders", ["o_custkey"]).to_pylist()}
    assert with_orders == ordered_custkeys

    # anti join: customers without orders
    build2 = HashBuildOperatorFactory([0], [T.BIGINT])
    bp2 = Pipeline([TableScanOperatorFactory(tpch, ["o_custkey"]), build2],
                   splits=all_splits(tpch, "orders"), name="b2")
    out2 = OutputCollectorFactory()
    pp2 = Pipeline([
        TableScanOperatorFactory(tpch, ["c_custkey"]),
        LookupJoinOperatorFactory(build2, [0], [T.BIGINT], "anti"),
        out2,
    ], splits=all_splits(tpch, "customer"), name="p2")
    execute_pipelines([bp2, pp2])
    n_cust = tpch.row_count("customer")
    assert {r[0] for r in out2.rows()} == \
        set(range(1, n_cust + 1)) - ordered_custkeys


def test_packed_multikey_join(tpch):
    """lineitem JOIN partsupp ON (partkey, suppkey) — the packed two-word
    id path (Q9's join shape)."""
    build = HashBuildOperatorFactory(
        [0, 1], [T.BIGINT, T.BIGINT, T.BIGINT])
    bp = Pipeline([
        TableScanOperatorFactory(tpch, ["ps_partkey", "ps_suppkey",
                                        "ps_availqty"]),
        build,
    ], splits=all_splits(tpch, "partsupp"), name="build")
    out = OutputCollectorFactory()
    pp = Pipeline([
        TableScanOperatorFactory(tpch, ["l_partkey", "l_suppkey"]),
        LookupJoinOperatorFactory(build, [0, 1],
                                  [T.BIGINT, T.BIGINT], "inner"),
        out,
    ], splits=all_splits(tpch, "lineitem"), name="probe")
    execute_pipelines([bp, pp])
    rows = out.rows()
    li = scan_numpy(tpch, "lineitem", ["l_partkey", "l_suppkey"]).to_pylist()
    assert len(rows) == len(li)  # ps (partkey,suppkey) unique -> 1 match each
    for lp, ls, bp_, bs, qty in rows[:300]:
        assert (lp, ls) == (bp_, bs)


def test_order_by_limit_values():
    b = batch_from_pylist([T.BIGINT, T.DOUBLE],
                          [(3, 1.5), (1, 9.0), (2, -4.0), (5, 0.0), (4, 2.0)])
    out = OutputCollectorFactory()
    p = Pipeline([
        ValuesOperatorFactory([b]),
        OrderByOperatorFactory([SortSpec(1, descending=True)], limit=3),
        LimitOperatorFactory(3),
        out,
    ])
    execute_pipelines([p])
    assert out.rows() == [(1, 9.0), (4, 2.0), (3, 1.5)]


def test_empty_results():
    b = batch_from_pylist([T.BIGINT], [(1,), (2,)])
    out = OutputCollectorFactory()
    p = Pipeline([
        ValuesOperatorFactory([b]),
        FilterProjectOperatorFactory(
            B.comparison(">", B.ref(0, T.BIGINT), B.const(100, T.BIGINT)),
            [B.ref(0, T.BIGINT)], [T.BIGINT]),
        HashAggregationOperatorFactory(
            [0], [AggChannel("count", None, T.BIGINT)], [T.BIGINT]),
        out,
    ])
    execute_pipelines([p])
    assert out.rows() == []  # grouped agg over empty input: no rows


def _concat_input(rng, capacity, rows, nullable, dictionary, on_device=True):
    import jax

    from presto_tpu.batch import Batch, Column

    put = jax.device_put if on_device else (lambda a: a)
    valid = put(rng.random(capacity) < 0.8) if nullable else None
    return Batch((
        Column(T.BIGINT, put(rng.integers(-1 << 40, 1 << 40, capacity))),
        Column(T.DOUBLE, put(rng.random(capacity)), valid),
        Column(T.VARCHAR, put(rng.integers(0, 3, capacity).astype(np.int32)),
               None, dictionary)), rows)


@pytest.mark.parametrize("inputs", [
    [(65536, 600, False)],                      # one sparse join output
    [(65536, 600, True), (65536, 0, False), (65536, 900, False),
     (1024, 1024, True), (2048, 1500, False)],  # mixed buckets and masks
    [(1024, 1000, False)] * 2 + [(4096, 48, True)],  # last lands at the end
    [(1024, 1024, False)] * 2,                  # exactly fills its bucket
], ids=["one-sparse", "mixed", "tail-window", "full"])
def test_device_concat_stays_on_device(inputs):
    """Device inputs are appended on the device (one program per pair of
    buckets) and equal the host concat, padding invalid."""
    from presto_tpu.batch import Dictionary, concat_batches
    from presto_tpu.exec.operator import device_concat, pad_batch

    rng = np.random.default_rng(7)
    dictionary = Dictionary(["a", "b", "c"])
    batches = [_concat_input(rng, *spec, dictionary) for spec in inputs]
    want = concat_batches(batches)
    got = device_concat(batches, 1024)
    n = want.num_rows
    assert (got.num_rows, got.capacity) == (n, pad_batch(want).capacity)
    assert got.to_pylist() == want.to_pylist()
    for g in got.columns:
        assert not isinstance(g.values, np.ndarray)
        assert g.valid is None or not np.asarray(g.valid)[n:].any()


def test_device_concat_passthrough_and_host_path():
    """A single device batch at its bucket is returned as it is; a host
    input or a second dictionary sends the concat through the host."""
    from presto_tpu.batch import Dictionary
    from presto_tpu.exec.operator import _APPEND_PROGRAMS, device_concat

    rng = np.random.default_rng(8)
    d1, d2 = Dictionary(["a", "b", "c"]), Dictionary(["c", "b", "a"])
    at_bucket = _concat_input(rng, 1024, 7, False, d1)
    assert device_concat([at_bucket], 1024) is at_bucket
    programs = len(_APPEND_PROGRAMS)
    for other in (_concat_input(rng, 1024, 9, False, d1, on_device=False),
                  _concat_input(rng, 1024, 9, False, d2)):
        got = device_concat([at_bucket, other], 1024)
        assert got.num_rows == 16 and got.capacity == 1024
        assert got.to_pylist()[:7] == at_bucket.to_pylist()
        assert got.to_pylist()[7:] == other.to_pylist()
    assert len(_APPEND_PROGRAMS) == programs
