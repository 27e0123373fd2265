"""Benchmarks: the BASELINE.md pinned configs on one TPU chip.

Three hand-built device pipelines (the presto-benchmark suite pattern —
hand-assembled operator pipelines, AbstractOperatorBenchmark.java:97,
HandTpchQuery1.java / HandTpchQuery6.java / HashBuildAndJoinBenchmark):

1. TPC-H SF1 Q1  — scan + grouped aggregation (headline metric)
2. TPC-H SF10 Q6 — predicate + projection + global aggregation
3. TPC-H SF1 Q3 core — 3-way join + aggregation + TopN, exploiting
   TPC-H's dense integer keys TPU-first: FK joins become boolean-table
   gathers, the revenue aggregation is a scatter-add over the dense
   orderkey domain, TopN is lax.top_k — no sorts, so the program is both
   compile-cheap and HBM-bound (the reference's HashBuilder/LookupJoin
   for the same query walks hash tables row-at-a-time).

Each config reports rows/s and effective input bytes/s, with parity
against a vectorized-numpy CPU implementation (the stand-in for the
reference's CPU operator pipeline — its codegen also reduces to tight
CPU loops over columnar arrays; the reference publishes no absolute
numbers, BASELINE.md).

Config 6 (``bench_engine_q1q6``) measures the SHIPPED engine: TPC-H Q1 +
Q6 SQL through LocalQueryRunner (planner + operator tier + pipeline
fusion), reported in ``extras`` next to the hand-kernel configs so the
artifact tracks what the engine executes, not just what hand-built
kernels can reach (ROADMAP #10).

Config 7 (``bench_mesh_q1q6``) pushes the same two queries through the
DISTRIBUTED tier — a real 2-worker DistributedQueryRunner cluster
(coordinator + workers on ephemeral HTTP ports, serde'd pages on the
exchange wire, partial/final aggregation split across fragments) — the
engine-path depth ROADMAP #10 still wanted.  ``vs_baseline`` is the
single-process engine wall ratio, so the line prices the distribution
overhead directly.

Config 10 (``bench_concurrent_qps``) measures the SERVING tier: N
concurrent clients (tools/qps_run.py closed loop) against a live
2-worker cluster with resource-group admission engaged — QPS and
p50/p95/p99 latency at 4 concurrency levels, per-client exact-rows
parity, plan-cache hit rate, and jit_compiles == 0 on the second
execution of a cached plan (the dispatcher + plan-cache PR).

Timing methodology: run K dependence-chained iterations INSIDE one jitted
fori_loop and take the slope between two K values, so dispatch overhead
and sync-polling granularity cancel.

Prints exactly ONE JSON line; the headline is Q1 and the other configs
ride in "extras"; the headline and every extra name the device they ran
on (platform, device_kind, device_count):
    {"metric": ..., "value": N, "unit": "rows/s", "vs_baseline": N,
     "extras": [...]}
There is no fallback: the bench runs on the backend JAX selects, and a
failed config or a crash exits non-zero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

Q6_DATE_LO, Q6_DATE_HI = 8766, 9131          # 1994-01-01 .. 1995-01-01
# BETWEEN 0.05 AND 0.07 via class midpoints: the generated discounts are
# the 11 cent classes 0.00..0.10, and the 0.05/0.07 boundaries sit on
# float knife-edges that f32-physical device doubles and host f64 round
# differently; midpoint thresholds select exactly {0.05,0.06,0.07} under
# either precision
Q6_DISC_LO, Q6_DISC_HI = 0.045, 0.075
Q3_DATE = 9204                               # 1995-03-15, epoch days


def _slope_time(make_chained, args) -> float:
    """Seconds per iteration via the two-K dependence-chained slope."""
    f5 = make_chained(5)
    np.asarray(f5(args))
    t0 = time.perf_counter()
    np.asarray(f5(args))
    rough = max((time.perf_counter() - t0) / 5, 1e-5)
    k1 = 3
    k2 = k1 + max(20, min(2000, int(4.0 / rough)))
    ts = []
    for k in (k1, k2):
        f = make_chained(k)
        np.asarray(f(args))  # compile + warm (sync via host read)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(f(args))
            best = min(best, time.perf_counter() - t0)
        ts.append(best)
    return max((ts[1] - ts[0]) / (k2 - k1), 1e-9)


def _col_bytes(arrays) -> int:
    return int(sum(np.asarray(a).nbytes if not hasattr(a, "nbytes")
                   else a.nbytes for a in arrays))


# ---------------------------------------------------------------------------
# Config 1: TPC-H Q1 (scan + grouped aggregation)
# ---------------------------------------------------------------------------

def _cpu_q1(rf, ls, qty, price, disc, tax, shipdate, n):
    sel = shipdate[:n] <= 10471
    rf, ls = rf[:n][sel], ls[:n][sel]
    qty, price = qty[:n][sel], price[:n][sel]
    disc, tax = disc[:n][sel], tax[:n][sel]
    disc_price = price * (1.0 - disc)
    charge = disc_price * (1.0 + tax)
    key = rf.astype(np.int64) * 64 + ls
    uniq, inv = np.unique(key, return_inverse=True)
    out = []
    for col in (qty, price, disc_price, charge, disc):
        out.append(np.bincount(inv, weights=col, minlength=len(uniq)))
    out.append(np.bincount(inv, minlength=len(uniq)))
    return uniq, out


def bench_q1(scale: float):
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _q1_arrays, q1_step

    args = _q1_arrays(scale)

    def chained(k):
        def body(_, carry):
            a, acc = carry
            out = q1_step(*a[:2], a[2] + (acc - acc).astype(a[2].dtype),
                          *a[3:])
            return (a, acc + out[3][0])
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, body, (a, jnp.float64(0.0)))[1])

    device_s = _slope_time(chained, args)
    n = int(args[-1])

    out = jax.jit(q1_step)(*args)
    host = [np.asarray(a) for a in args[:-1]]
    t0 = time.perf_counter()
    cpu = _cpu_q1(*host, n)
    cpu_s = time.perf_counter() - t0

    ng = int(out[2])
    dev_key = (np.asarray(out[0])[:ng].astype(np.int64) * 64
               + np.asarray(out[1])[:ng])
    order = np.argsort(dev_key)
    ok = bool(np.array_equal(dev_key[order], cpu[0]))
    for i, want in enumerate(cpu[1]):
        got = np.asarray(out[3 + i])[:ng][order]
        ok = ok and bool(np.allclose(got, want, rtol=1e-6))
    nbytes = _col_bytes(host) * n // max(host[0].shape[0], 1)
    return {
        "metric": f"tpch_sf{scale:g}_q1_rows_per_sec_per_chip",
        "value": round(n / device_s, 1), "unit": "rows/s",
        "vs_baseline": round(n / device_s / (n / cpu_s), 3),
        "bytes_per_sec": round(nbytes / device_s, 1),
        "parity": ok,
    }


# ---------------------------------------------------------------------------
# Config 2: TPC-H Q6 (filter + projection + global sum)
# ---------------------------------------------------------------------------

def _q6_arrays(scale: float):
    import jax.numpy as jnp

    from presto_tpu.batch import concat_batches, next_bucket
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=scale)
    handle = conn.get_table("lineitem")
    cols = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
    batches = []
    for split in conn.get_splits(handle, 1):
        batches.extend(conn.page_source(split, cols, 1 << 24))
    b = concat_batches(batches) if len(batches) > 1 else batches[0]
    cap = next_bucket(b.num_rows)
    b = b.pad_rows(cap)
    arrays = tuple(jnp.asarray(c.values) for c in b.columns)
    return arrays + (jnp.asarray(b.num_rows, jnp.int64),)


def q6_step(shipdate, disc, qty, price, num_rows):
    """WHERE l_shipdate in [1994, 1995) AND l_discount BETWEEN 0.05 AND
    0.07 AND l_quantity < 24 -> SUM(l_extendedprice * l_discount), fused
    into the aggregation as a live mask (HandTpchQuery6 role)."""
    import jax.numpy as jnp

    live = jnp.arange(shipdate.shape[0]) < num_rows
    sel = (live & (shipdate >= Q6_DATE_LO) & (shipdate < Q6_DATE_HI)
           & (disc > Q6_DISC_LO) & (disc < Q6_DISC_HI) & (qty < 24.0))
    return jnp.where(sel, price * disc, 0.0).sum()


def _cpu_q6(shipdate, disc, qty, price, n):
    sel = ((shipdate[:n] >= Q6_DATE_LO) & (shipdate[:n] < Q6_DATE_HI)
           & (disc[:n] > Q6_DISC_LO) & (disc[:n] < Q6_DISC_HI)
           & (qty[:n] < 24.0))
    return float((price[:n][sel] * disc[:n][sel]).sum())


def bench_q6(scale: float):
    import jax
    import jax.numpy as jnp

    args = _q6_arrays(scale)

    def chained(k):
        def body(_, carry):
            a, acc = carry
            s = q6_step(a[0] + (acc - acc).astype(a[0].dtype), *a[1:])
            return (a, acc + s)
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, body, (a, jnp.float64(0.0)))[1])

    device_s = _slope_time(chained, args)
    n = int(args[-1])
    host = [np.asarray(a) for a in args[:-1]]
    t0 = time.perf_counter()
    want = _cpu_q6(*host, n)
    cpu_s = time.perf_counter() - t0
    got = float(jax.jit(q6_step)(*args))
    ok = bool(np.isclose(got, want, rtol=1e-6))
    nbytes = _col_bytes(host) * n // max(host[0].shape[0], 1)
    return {
        "metric": f"tpch_sf{scale:g}_q6_rows_per_sec_per_chip",
        "value": round(n / device_s, 1), "unit": "rows/s",
        "vs_baseline": round(n / device_s / (n / cpu_s), 3),
        "bytes_per_sec": round(nbytes / device_s, 1),
        "parity": ok,
    }


# ---------------------------------------------------------------------------
# Config 3: TPC-H Q3 core (3-way join + aggregation + TopN)
# ---------------------------------------------------------------------------

def _q3_arrays(scale: float):
    import jax.numpy as jnp

    from presto_tpu.batch import concat_batches, next_bucket
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=scale)

    def load(table, cols):
        h = conn.get_table(table)
        batches = []
        for split in conn.get_splits(h, 1):
            batches.extend(conn.page_source(split, cols, 1 << 24))
        return concat_batches(batches) if len(batches) > 1 else batches[0]

    cust = load("customer", ["c_custkey", "c_mktsegment"])
    seg = cust.columns[1]
    building_code = seg.dictionary.code_of("BUILDING")
    n_cust = cust.num_rows
    # dense boolean membership table over the custkey domain (keys are
    # 1..N in order): the build side of join #1, as one gather table
    cust_building = np.zeros(n_cust + 1, bool)
    cust_building[np.asarray(cust.columns[0].values)[:n_cust]] = (
        np.asarray(seg.values)[:n_cust] == building_code)

    orders = load("orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    n_ord = orders.num_rows
    ocust = np.asarray(orders.columns[1].values)[:n_ord]
    odate = np.asarray(orders.columns[2].values)[:n_ord]

    li = load("lineitem", ["l_orderkey", "l_extendedprice", "l_discount",
                           "l_shipdate"])
    n_li = li.num_rows
    cap = next_bucket(n_li)
    li = li.pad_rows(cap)
    # f32/i32 on device: the v5e stores "f64" as f32 anyway (X64 rewrite)
    # and emulated 64-bit elementwise ops would dominate the runtime;
    # per-order revenue sums at most 7 f32 terms so precision holds
    okey0 = np.clip(np.asarray(li.columns[0].values) - 1, 0,
                    n_ord - 1).astype(np.int32)
    arrays = (
        jnp.asarray(cust_building),
        jnp.asarray(ocust.astype(np.int32)),
        jnp.asarray(odate.astype(np.int32)),
        jnp.asarray(okey0),
        jnp.asarray(np.asarray(li.columns[1].values,
                               dtype=np.float32)),
        jnp.asarray(np.asarray(li.columns[2].values,
                               dtype=np.float32)),
        jnp.asarray(np.asarray(li.columns[3].values, dtype=np.int32)),
        jnp.asarray(n_li, jnp.int64),
    )
    rows = n_cust + n_ord + n_li
    # 4 lineitem device arrays (okey0/price/disc/ship, 4B each)
    nbytes = (cust_building.nbytes + 2 * 4 * n_ord + 4 * 4 * n_li)
    # keep f64 copies for the CPU oracle
    host = (cust_building, ocust, odate,
            np.asarray(li.columns[0].values)[:n_li],
            np.asarray(li.columns[1].values)[:n_li],
            np.asarray(li.columns[2].values)[:n_li],
            np.asarray(li.columns[3].values)[:n_li], n_li)
    return arrays, host, rows, nbytes


def q3_step(cust_building, ocust, odate, okey0, price, disc, ship, n_li):
    """Q3's join+agg+TopN core as one XLA program over dense keys:

        sel_orders = building[o_custkey] & o_orderdate < DATE   (join #1
                     + filter: a gather and a compare)
        sel_line   = sel_orders[l_orderkey] & l_shipdate > DATE (join #2)
        revenue    = 7-tap same-key windowed sum at each order's last
                     lineitem (orders have <= 7 adjacent lineitems, so
                     no scatter and no sort)
        top 10 revenue via blocked two-stage lax.top_k

    The reference executes this as HashBuilder/LookupJoin x2 +
    HashAggregation + TopN (presto-main/.../operator/, SURVEY §3.4);
    dense TPC-H keys let the TPU do it bandwidth-bound with no hash
    table."""
    import jax
    import jax.numpy as jnp

    sel_ord = cust_building[ocust] & (odate < Q3_DATE)
    live = jnp.arange(okey0.shape[0]) < n_li
    sel_li = live & (ship > Q3_DATE) & sel_ord[okey0]
    contrib = jnp.where(sel_li, price * (1.0 - disc), jnp.float32(0))
    rev = contrib
    for j in range(1, 7):
        shifted = jnp.concatenate(
            [jnp.zeros(j, contrib.dtype), contrib[:-j]])
        same = jnp.concatenate(
            [jnp.zeros(j, bool), okey0[j:] == okey0[:-j]])
        rev = rev + jnp.where(same, shifted, 0)
    end = jnp.concatenate([okey0[1:] != okey0[:-1], jnp.ones(1, bool)])
    rev = jnp.where(end & live, rev, jnp.float32(-1.0))
    B = 1024
    pad = (-rev.shape[0]) % B
    r2 = jnp.pad(rev, (0, pad), constant_values=-1.0).reshape(B, -1)
    tv, ti = jax.lax.top_k(r2, 10)
    base = (jnp.arange(B) * r2.shape[1])[:, None]
    cv, ci = jax.lax.top_k(tv.reshape(-1), 10)
    pos = (base + ti).reshape(-1)[ci]
    return cv, okey0[jnp.clip(pos, 0, okey0.shape[0] - 1)] + 1


def _cpu_q3(cust_building, ocust, odate, l_okey, l_price, l_disc,
            l_ship, n_li):
    sel_ord = cust_building[ocust] & (odate < Q3_DATE)
    okey0 = l_okey[:n_li] - 1
    sel_li = (l_ship[:n_li] > Q3_DATE) & sel_ord[okey0]
    contrib = np.where(sel_li, l_price[:n_li] * (1.0 - l_disc[:n_li]), 0.0)
    rev = np.bincount(okey0, weights=contrib, minlength=len(ocust))
    top = np.argsort(-rev, kind="stable")[:10]
    return rev[top]


def bench_q3(scale: float):
    import jax
    import jax.numpy as jnp

    args, host, rows, nbytes = _q3_arrays(scale)

    def chained(k):
        def body(_, carry):
            a, acc = carry
            out = q3_step(a[0], a[1], a[2],
                          a[3] + (acc - acc).astype(a[3].dtype), *a[4:])
            return (a, acc + out[0][0].astype(jnp.float64))
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, body, (a, jnp.float64(0.0)))[1])

    device_s = _slope_time(chained, args)

    t0 = time.perf_counter()
    want = _cpu_q3(*host)
    cpu_s = time.perf_counter() - t0
    got = np.sort(np.asarray(jax.jit(q3_step)(*args)[0]))[::-1]
    # f32 revenue sums: ~1e-5 relative (SQL float aggregation order is
    # unspecified; the reference reorders too)
    ok = bool(np.allclose(got, np.sort(want)[::-1], rtol=1e-4))
    return {
        "metric": f"tpch_sf{scale:g}_q3_join_agg_rows_per_sec_per_chip",
        "value": round(rows / device_s, 1), "unit": "rows/s",
        "vs_baseline": round(rows / device_s / (rows / cpu_s), 3),
        "bytes_per_sec": round(nbytes / device_s, 1),
        "parity": ok,
    }


def bench_whole_query_q3(scale: float):
    """The generic one-XLA-program tier (parallel/sqlmesh) on TPC-H Q3
    text — the flagship mode's warm wall clock (cold compile amortized
    by the persistent XLA cache)."""
    from presto_tpu.connectors.api import ConnectorRegistry
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.parallel.sqlmesh import MeshQueryRunner

    sql = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""
    reg = ConnectorRegistry()
    reg.register("tpch", TpchConnector(scale=scale))
    r = MeshQueryRunner(reg, "tpch", n_devices=1)
    r.execute(sql)                         # compile + warm
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        res = r.execute(sql)
        walls.append(time.perf_counter() - t0)
    return {
        "metric": f"tpch_sf{scale:g}_q3_whole_query_warm_wall_s",
        "value": round(min(walls), 3), "unit": "s",
        "vs_baseline": 0.0,
        "note": "generic SPMD lowering, one program",
        "rows": len(res.rows),
    }


# ---------------------------------------------------------------------------
# Config 4: TPC-H Q9 (5-way join + grouped aggregation over (nation, year))
# ---------------------------------------------------------------------------

def _epoch_days_to_year(days: np.ndarray) -> np.ndarray:
    return (days.astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970).astype(np.int32)


def _q9_tables(scale: float):
    """Build-side lookup tables for Q9, laid out for dense device gathers:
    part's LIKE-'%green%' mask over the partkey domain, partsupp as four
    slot-rows per part (row (pk-1)*4+i — the generator emits them
    adjacent), supplier nation over the suppkey domain, and order year
    over the dense orderkey domain.  The reference runs this as a 6-way
    HashBuilder/LookupJoin tree (BenchmarkSuite.java:33 configs); dense
    TPC-H keys let the TPU resolve every join with one gather each."""
    from presto_tpu.connectors.tpch import COLORS, _S_PART, TpchConnector, u_int

    conn = TpchConnector(scale=scale).generator
    P, S, O = conn.n_part, conn.n_supplier, conn.n_orders
    keys = np.arange(1, P + 1, dtype=np.int64)
    gi = COLORS.index("green")
    gm = np.zeros(P, bool)
    for i in range(5):  # p_name is five color words; 'green' is exact
        gm |= u_int(_S_PART + 10 + i, keys, 0, len(COLORS) - 1) == gi
    green = np.zeros(P + 1, bool)
    green[1:] = gm

    ps = conn.gen_partsupp(["ps_suppkey", "ps_supplycost"], 1, P + 1)
    ps_sk = np.asarray(ps.columns[0].values).astype(np.int32)
    ps_cost = np.asarray(ps.columns[1].values).astype(np.float32)

    sup = conn.gen_supplier(["s_nationkey"], 1, S + 1)
    s_nat = np.zeros(S + 1, np.int32)
    s_nat[1:] = np.asarray(sup.columns[0].values)

    odate = conn._order_date(np.arange(1, O + 1, dtype=np.int64))
    o_year = (_epoch_days_to_year(odate) - 1992).astype(np.int32)  # 0..6
    return conn, green, ps_sk, ps_cost, s_nat, o_year


def q9_step(green, ps_sk, ps_cost, s_nat, o_year,
            pk, sk, okey0, qty, price, disc, n_rows):
    """Q9's join+agg stage as one XLA program: four dense-key gathers
    (part mask, partsupp 4-slot compare, supplier nation, order year)
    feed a 175-group scatter-add over (nation, year).  Role:
    presto-benchmark's hand-built pipelines (HandTpchQuery1.java:97
    pattern) over the 6-way join of BenchmarkSuite.java:33."""
    import jax.numpy as jnp

    live = jnp.arange(pk.shape[0]) < n_rows
    sel = live & green[pk]
    cand = ((pk - 1) * 4)[:, None] + jnp.arange(4, dtype=jnp.int32)[None, :]
    cand = jnp.clip(cand, 0, ps_sk.shape[0] - 1)
    hit = ps_sk[cand] == sk[:, None]
    cost = (ps_cost[cand] * hit).sum(axis=1)
    amount = price * (1.0 - disc) - cost * qty
    g = s_nat[sk] * 7 + o_year[okey0]
    sums = (jnp.zeros(176, jnp.float32)
            .at[jnp.where(sel, g, 175)]
            .add(jnp.where(sel, amount, jnp.float32(0))))
    return sums[:175]


def _cpu_q9(green, ps_sk, ps_cost, s_nat, o_year, chunks):
    out = np.zeros(175)
    for pk, sk, okey0, qty, price, disc, n in chunks:
        pk, sk = pk[:n], sk[:n]
        okey0, qty = okey0[:n], qty[:n]
        price, disc = price[:n].astype(np.float64), disc[:n].astype(np.float64)
        sel = green[pk]
        cost = np.zeros(n)
        for i in range(4):
            m = ps_sk[(pk - 1) * 4 + i] == sk
            cost = np.where(m, ps_cost[(pk - 1) * 4 + i].astype(np.float64),
                            cost)
        amount = price * (1.0 - disc) - cost * qty
        g = s_nat[sk] * 7 + o_year[okey0]
        out += np.bincount(g[sel], weights=amount[sel], minlength=175)
    return out


def _gen_lineitem_chunks(conn, cols, np_dtypes, chunk_orders):
    """Generate lineitem host arrays chunked on ORDER boundaries (each
    order's lineitems stay within one chunk), padded to one shared
    capacity so every chunk reuses the same compiled program."""
    from presto_tpu.batch import next_bucket

    O = conn.n_orders
    chunk_orders = min(chunk_orders, O)
    cap = next_bucket(int(chunk_orders * 4.3) + 16)
    chunks = []
    for lo in range(1, O + 1, chunk_orders):
        hi = min(lo + chunk_orders, O + 1)
        b = conn.gen_lineitem(cols, lo, hi)
        n = b.num_rows
        arrs = []
        for c, dt in zip(b.columns, np_dtypes):
            a = np.asarray(c.values)[:n].astype(dt)
            pad = np.zeros(cap, dt)
            pad[:n] = a
            arrs.append(pad)
        chunks.append(tuple(arrs) + (n,))
    return chunks, cap


def bench_q9(scale: float, chunk_orders: int = 1 << 24):
    import jax
    import jax.numpy as jnp

    conn, green, ps_sk, ps_cost, s_nat, o_year = _q9_tables(scale)
    cols = ["l_partkey", "l_suppkey", "l_orderkey", "l_quantity",
            "l_extendedprice", "l_discount"]
    dts = [np.int32, np.int32, np.int32, np.float32, np.float32, np.float32]
    chunks, cap = _gen_lineitem_chunks(conn, cols, dts, chunk_orders)
    for ch in chunks:
        ch[2][:ch[-1]] -= 1  # l_orderkey -> 0-based dense index
    n_li = sum(ch[-1] for ch in chunks)
    resident = tuple(jnp.asarray(a) for a in
                     (green, ps_sk, ps_cost, s_nat, o_year))

    # device-only rows/s from the dependence-chained slope on one chunk
    c0 = chunks[0]
    args = resident + tuple(jnp.asarray(a) for a in c0[:-1]) + (
        jnp.asarray(c0[-1], jnp.int64),)

    def chained(k):
        def body(_, carry):
            a, acc = carry
            out = q9_step(*a[:5], a[5] + (acc - acc).astype(a[5].dtype),
                          *a[6:])
            return (a, acc + out[0].astype(jnp.float64))
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, body, (a, jnp.float64(0.0)))[1])

    device_s_chunk = _slope_time(chained, args)
    device_s = device_s_chunk * (n_li / max(c0[-1], 1))

    # streamed pass (all chunks through the one compiled program) for the
    # grouped/chunked-dispatch wall at scales past the single-program cap
    step = jax.jit(q9_step)
    np.asarray(step(*args[:-1], args[-1]))  # compile outside the wall
    sums = np.zeros(175)
    t0 = time.perf_counter()
    for ch in chunks:
        out = step(*resident, *(jnp.asarray(a) for a in ch[:-1]),
                   jnp.asarray(ch[-1], jnp.int64))
        sums += np.asarray(out, dtype=np.float64)
    stream_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    want = _cpu_q9(green, ps_sk, ps_cost, s_nat, o_year, chunks)
    cpu_s = time.perf_counter() - t0
    ok = bool(np.allclose(sums, want, rtol=2e-3, atol=1.0))
    rows = (len(green) + len(ps_sk) + len(s_nat) + len(o_year) + n_li)
    return {
        "metric": f"tpch_sf{scale:g}_q9_join_agg_rows_per_sec_per_chip",
        "value": round(rows / device_s, 1), "unit": "rows/s",
        "vs_baseline": round((rows / device_s) / (rows / cpu_s), 3),
        "streamed_rows_per_sec": round(rows / stream_s, 1),
        "chunks": len(chunks),
        "parity": ok,
    }


# ---------------------------------------------------------------------------
# Config 5: TPC-H Q17 (part filter + correlated per-part avg + agg)
# ---------------------------------------------------------------------------

def _q17_tables(scale: float):
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=scale).generator
    P = conn.n_part
    part = conn.gen_part(["p_brand", "p_container"], 1, P + 1)
    bcol, ccol = part.columns
    bc = bcol.dictionary.code_of("Brand#23")
    cc = ccol.dictionary.code_of("MED BOX")
    mask = np.zeros(P + 1, bool)
    mask[1:] = ((np.asarray(bcol.values) == bc)
                & (np.asarray(ccol.values) == cc))
    return conn, mask


def q17_passA(sumq, cnt, pk, qty, n_rows):
    """Accumulate per-part quantity sum/count (the correlated
    avg(l_quantity) subquery's aggregation) into donated accumulators."""
    import jax.numpy as jnp

    live = jnp.arange(pk.shape[0]) < n_rows
    idx = jnp.where(live, pk, 0)
    return (sumq.at[idx].add(jnp.where(live, qty, jnp.float32(0))),
            cnt.at[idx].add(live.astype(jnp.float32)))


def q17_passB(sumq, cnt, mask, pk, qty, price, n_rows):
    import jax.numpy as jnp

    live = jnp.arange(pk.shape[0]) < n_rows
    avg = sumq[pk] / jnp.maximum(cnt[pk], jnp.float32(1))
    sel = live & mask[pk] & (qty < 0.2 * avg)
    return jnp.where(sel, price, jnp.float32(0)).sum()


def q17_step(mask, pk, qty, price, n_rows):
    """Single-program Q17 join+agg stage (fits one chunk): per-part
    avg(l_quantity) via scatter-add over the partkey domain, then the
    filtered price sum — the reference's join + correlated-subquery plan
    (BenchmarkSuite.java:33) with the hash tables replaced by the dense
    part domain."""
    import jax.numpy as jnp

    P1 = mask.shape[0]
    sumq, cnt = q17_passA(jnp.zeros(P1, jnp.float32),
                          jnp.zeros(P1, jnp.float32), pk, qty, n_rows)
    return q17_passB(sumq, cnt, mask, pk, qty, price, n_rows) / 7.0


def _cpu_q17(mask, chunks):
    P1 = len(mask)
    sumq = np.zeros(P1)
    cnt = np.zeros(P1)
    for pk, qty, price, n in chunks:
        sumq += np.bincount(pk[:n], weights=qty[:n], minlength=P1)
        cnt += np.bincount(pk[:n], minlength=P1)
    total = 0.0
    for pk, qty, price, n in chunks:
        avg = sumq[pk[:n]] / np.maximum(cnt[pk[:n]], 1)
        sel = mask[pk[:n]] & (qty[:n] < 0.2 * avg)
        total += float(price[:n][sel].astype(np.float64).sum())
    return total / 7.0


def bench_q17(scale: float, chunk_orders: int = 1 << 24):
    import jax
    import jax.numpy as jnp

    conn, mask = _q17_tables(scale)
    cols = ["l_partkey", "l_quantity", "l_extendedprice"]
    dts = [np.int32, np.float32, np.float32]
    chunks, cap = _gen_lineitem_chunks(conn, cols, dts, chunk_orders)
    n_li = sum(ch[-1] for ch in chunks)
    mask_d = jnp.asarray(mask)

    c0 = chunks[0]
    args = (mask_d,) + tuple(jnp.asarray(a) for a in c0[:-1]) + (
        jnp.asarray(c0[-1], jnp.int64),)

    def chained(k):
        def body(_, carry):
            a, acc = carry
            s = q17_step(a[0], a[1] + (acc - acc).astype(a[1].dtype),
                         *a[2:])
            return (a, acc + s.astype(jnp.float64))
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, body, (a, jnp.float64(0.0)))[1])

    device_s_chunk = _slope_time(chained, args)
    device_s = device_s_chunk * (n_li / max(c0[-1], 1))

    # streamed two-pass (device-resident accumulators, donated)
    passA = jax.jit(q17_passA, donate_argnums=(0, 1))
    passB = jax.jit(q17_passB)
    P1 = mask.shape[0]
    wa, wb = passA(jnp.zeros(P1, jnp.float32),  # compile outside the wall
                   jnp.zeros(P1, jnp.float32), args[1], args[2], args[-1])
    float(passB(wa, wb, mask_d, *args[1:]))
    del wa, wb
    t0 = time.perf_counter()
    sumq = jnp.zeros(P1, jnp.float32)
    cnt = jnp.zeros(P1, jnp.float32)
    for ch in chunks:
        sumq, cnt = passA(sumq, cnt, jnp.asarray(ch[0]),
                          jnp.asarray(ch[1]), jnp.asarray(ch[-1], jnp.int64))
    got = 0.0
    for ch in chunks:
        got += float(passB(sumq, cnt, mask_d,
                           *(jnp.asarray(a) for a in ch[:-1]),
                           jnp.asarray(ch[-1], jnp.int64)))
    got /= 7.0
    stream_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    want = _cpu_q17(mask, chunks)
    cpu_s = time.perf_counter() - t0
    ok = bool(np.isclose(got, want, rtol=1e-3))
    rows = n_li + (P1 - 1)
    return {
        "metric": f"tpch_sf{scale:g}_q17_join_agg_rows_per_sec_per_chip",
        "value": round(rows / device_s, 1), "unit": "rows/s",
        "vs_baseline": round((rows / device_s) / (rows / cpu_s), 3),
        "streamed_rows_per_sec": round(rows / stream_s, 1),
        "chunks": len(chunks),
        "parity": ok,
    }


# ---------------------------------------------------------------------------
# Config 3b: TPC-H Q3 at scales past the single-program cap, as k
# order-aligned chunk dispatches through ONE compiled program (the
# grouped-execution / P9 idea applied to the bench: each device program
# stays bounded whatever the scale)
# ---------------------------------------------------------------------------

def q3_chunk_step(sel_ord, okey0, price, disc, ship, n_rows):
    """Per-chunk Q3 core: lineitems of any order are entirely within one
    chunk (order-aligned generation), so per-order revenue and the
    chunk-local top-10 are exact; the cross-chunk merge is a host top-10
    of k*10 candidates."""
    import jax
    import jax.numpy as jnp

    live = jnp.arange(okey0.shape[0]) < n_rows
    sel_li = live & (ship > Q3_DATE) & sel_ord[okey0]
    contrib = jnp.where(sel_li, price * (1.0 - disc), jnp.float32(0))
    rev = contrib
    for j in range(1, 7):
        shifted = jnp.concatenate(
            [jnp.zeros(j, contrib.dtype), contrib[:-j]])
        same = jnp.concatenate(
            [jnp.zeros(j, bool), okey0[j:] == okey0[:-j]])
        rev = rev + jnp.where(same, shifted, 0)
    end = jnp.concatenate([okey0[1:] != okey0[:-1], jnp.ones(1, bool)])
    rev = jnp.where(end & live, rev, jnp.float32(-1.0))
    B = 1024
    pad = (-rev.shape[0]) % B
    r2 = jnp.pad(rev, (0, pad), constant_values=-1.0).reshape(B, -1)
    tv, ti = jax.lax.top_k(r2, 10)
    base = (jnp.arange(B) * r2.shape[1])[:, None]
    cv, ci = jax.lax.top_k(tv.reshape(-1), 10)
    pos = (base + ti).reshape(-1)[ci]
    return cv, okey0[jnp.clip(pos, 0, okey0.shape[0] - 1)] + 1


def bench_q3_chunked(scale: float, chunk_orders: int = 1 << 24):
    import jax
    import jax.numpy as jnp

    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=scale).generator
    n_cust, n_ord = conn.n_customer, conn.n_orders
    cust = conn.gen_customer(["c_custkey", "c_mktsegment"], 1, n_cust + 1)
    seg = cust.columns[1]
    building_code = seg.dictionary.code_of("BUILDING")
    cust_building = np.zeros(n_cust + 1, bool)
    cust_building[np.asarray(cust.columns[0].values)] = (
        np.asarray(seg.values) == building_code)
    orders = conn.gen_orders(["o_custkey", "o_orderdate"], 1, n_ord + 1)
    ocust = np.asarray(orders.columns[0].values).astype(np.int32)
    odate = np.asarray(orders.columns[1].values).astype(np.int32)

    cols = ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]
    dts = [np.int32, np.float32, np.float32, np.int32]
    chunks, cap = _gen_lineitem_chunks(conn, cols, dts, chunk_orders)
    for ch in chunks:
        ch[0][:ch[-1]] -= 1  # okey -> 0-based
    n_li = sum(ch[-1] for ch in chunks)
    rows = n_cust + n_ord + n_li

    # join #1 (customer⨝orders) once, on device, result resident
    sel_prog = jax.jit(lambda cb, oc, od: cb[oc] & (od < Q3_DATE))
    step = jax.jit(q3_chunk_step)
    # compile both programs outside the streamed wall
    sel_ord = sel_prog(jnp.asarray(cust_building), jnp.asarray(ocust),
                       jnp.asarray(odate))
    c0w = chunks[0]
    np.asarray(step(sel_ord, *(jnp.asarray(a) for a in c0w[:-1]),
                    jnp.asarray(c0w[-1], jnp.int64))[0])
    t0 = time.perf_counter()
    sel_ord = sel_prog(jnp.asarray(cust_building), jnp.asarray(ocust),
                       jnp.asarray(odate))
    cands_v, cands_k = [], []
    for ch in chunks:
        cv, ck = step(sel_ord, *(jnp.asarray(a) for a in ch[:-1]),
                      jnp.asarray(ch[-1], jnp.int64))
        cands_v.append(np.asarray(cv))
        cands_k.append(np.asarray(ck))
    stream_s = time.perf_counter() - t0
    allv = np.concatenate(cands_v)
    top = np.argsort(-allv, kind="stable")[:10]
    got = np.sort(allv[top])[::-1]

    # device-only slope on one resident chunk, scaled to the full input
    c0 = chunks[0]
    args = (sel_ord,) + tuple(jnp.asarray(a) for a in c0[:-1]) + (
        jnp.asarray(c0[-1], jnp.int64),)

    def chained(k):
        def body(_, carry):
            a, acc = carry
            out = q3_chunk_step(a[0], a[1] + (acc - acc).astype(a[1].dtype),
                                *a[2:])
            return (a, acc + out[0][0].astype(jnp.float64))
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, body, (a, jnp.float64(0.0)))[1])

    device_s = _slope_time(chained, args) * (n_li / max(c0[-1], 1))

    # CPU oracle (f64, chunked bincount over the dense orderkey domain)
    t0 = time.perf_counter()
    rev = np.zeros(n_ord)
    sel_np = cust_building[ocust] & (odate < Q3_DATE)
    for ch in chunks:
        okey0, price, disc, ship, n = ch
        s = (ship[:n] > Q3_DATE) & sel_np[okey0[:n]]
        contrib = np.where(s, price[:n].astype(np.float64)
                           * (1.0 - disc[:n].astype(np.float64)), 0.0)
        rev += np.bincount(okey0[:n], weights=contrib, minlength=n_ord)
    want = np.sort(rev[np.argsort(-rev, kind="stable")[:10]])[::-1]
    cpu_s = time.perf_counter() - t0
    ok = bool(np.allclose(got, want, rtol=1e-4))
    return {
        "metric": f"tpch_sf{scale:g}_q3_join_agg_rows_per_sec_per_chip",
        "value": round(rows / device_s, 1), "unit": "rows/s",
        "vs_baseline": round((rows / device_s) / (rows / cpu_s), 3),
        "streamed_rows_per_sec": round(rows / stream_s, 1),
        "chunks": len(chunks), "chunked": True,
        "parity": ok,
    }


# ---------------------------------------------------------------------------
# Config 6: the SHIPPED ENGINE path (SQL text -> planner -> operator tier)
# ---------------------------------------------------------------------------

ENGINE_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc, count(*) as count_order
from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus
"""

ENGINE_Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""


def bench_engine_q1q6(scale: float):
    """TPC-H Q1 + Q6 through the SHIPPED SQL runner (parser -> optimizer
    -> operator tier with pipeline fusion), so the artifact measures what
    the engine actually executes — not hand-built kernels.  Reports warm
    rows/s per query, the fused-vs-unfused wall ratio, and the jit
    dispatch counters the fusion tier halves (ROADMAP #10)."""
    import dataclasses as dc

    from presto_tpu.config import EngineConfig
    from presto_tpu.localrunner import LocalQueryRunner

    runner = LocalQueryRunner.tpch(scale=scale)
    runner_off = LocalQueryRunner.tpch(scale=scale, config=dc.replace(
        EngineConfig(), pipeline_fusion=False))
    n_rows = runner.execute(
        "select count(*) from lineitem").rows[0][0]

    def timed(r, sql):
        t0 = time.perf_counter()
        r.execute(sql)                      # compile + warm caches
        cold_s = time.perf_counter() - t0
        cold_jit = r._last_task.jit_counters()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res = r.execute(sql)
            best = min(best, time.perf_counter() - t0)
        warm = r._last_task.jit_counters()
        # compile-vs-execute split (PR 9 attribution): cold wall is
        # compile-dominated, warm wall must carry ZERO compile ns —
        # nonzero warm compile means a cache key churns per execution
        warm["cold_s"] = round(cold_s, 4)
        warm["cold_compile_ms"] = round(cold_jit["compile_ns"] / 1e6, 1)
        warm["warm_compile_ms"] = round(warm["compile_ns"] / 1e6, 3)
        return best, res, warm

    q1_s, q1_res, q1_jit = timed(runner, ENGINE_Q1)
    q6_s, q6_res, q6_jit = timed(runner, ENGINE_Q6)
    q1_off_s, q1_off_res, q1_off_jit = timed(runner_off, ENGINE_Q1)
    q6_off_s, q6_off_res, _ = timed(runner_off, ENGINE_Q6)

    def close(a, b):
        if len(a) != len(b):
            return False
        for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
            for va, vb in zip(ra, rb):
                if isinstance(va, float):
                    if not np.isclose(va, vb, rtol=1e-6):
                        return False
                elif va != vb:
                    return False
        return True

    parity = close(q1_res.rows, q1_off_res.rows) and \
        close(q6_res.rows, q6_off_res.rows)
    return {
        "metric": f"tpch_sf{scale:g}_q1_engine_rows_per_sec",
        "value": round(n_rows / q1_s, 1), "unit": "rows/s",
        # baseline for the engine path = the same engine with pipeline
        # fusion off (per-operator dispatch, the pre-fusion engine)
        "vs_baseline": round(q1_off_s / q1_s, 3),
        "engine_path": True,
        "q6_rows_per_sec": round(n_rows / q6_s, 1),
        "q6_speedup_vs_unfused": round(q6_off_s / q6_s, 3),
        "jit_dispatches": {"q1_fused": q1_jit["dispatches"],
                           "q1_unfused": q1_off_jit["dispatches"],
                           "q6_fused": q6_jit["dispatches"]},
        # compile-vs-execute attribution (jit_counters()['compile_ns']):
        # the warm number is the regression canary — it was ~400 ms/run
        # before PR 10 pinned the scan dictionaries (a fused-segment
        # cache key churned per execution)
        "compile_split": {
            "q1_cold_s": q1_jit["cold_s"],
            "q1_cold_compile_ms": q1_jit["cold_compile_ms"],
            "q1_warm_compile_ms": q1_jit["warm_compile_ms"],
            "q6_warm_compile_ms": q6_jit["warm_compile_ms"]},
        "parity": parity,
    }


def bench_engine_q3q9(scale: float):
    """Join-heavy TPC-H Q3 + Q9 through the SHIPPED LocalQueryRunner —
    the tracked number for the device-resident hash tier (PagesHash
    probe absorbed into fused segments + GroupByHash aggregation
    state).  Baseline = the same engine with every PR 10 kernel off
    (hash_groupby_enabled / device_join_probe / fusion_final_merge /
    prereduce_cost_based = false, i.e. the PR 9 lowering), so
    vs_baseline prices the hash tier directly; parity is checked
    against that baseline's rows."""
    import dataclasses as dc
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpch_queries import QUERIES

    from presto_tpu.config import EngineConfig
    from presto_tpu.localrunner import LocalQueryRunner

    runner = LocalQueryRunner.tpch(scale=scale)
    runner_off = LocalQueryRunner.tpch(scale=scale, config=dc.replace(
        EngineConfig(), hash_groupby_enabled=False,
        device_join_probe=False, fusion_final_merge=False,
        prereduce_cost_based=False))
    n_rows = runner.execute(
        "select count(*) from lineitem").rows[0][0]

    def timed(r, sql):
        t0 = time.perf_counter()
        r.execute(sql)
        cold_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res = r.execute(sql)
            best = min(best, time.perf_counter() - t0)
        jit = r._last_task.jit_counters()
        jit["cold_s"] = round(cold_s, 4)
        jit["warm_compile_ms"] = round(jit["compile_ns"] / 1e6, 3)
        return best, res, jit

    q3_s, q3_res, q3_jit = timed(runner, QUERIES[3])
    q9_s, q9_res, q9_jit = timed(runner, QUERIES[9])
    q3_off_s, q3_off_res, q3_off_jit = timed(runner_off, QUERIES[3])
    q9_off_s, q9_off_res, q9_off_jit = timed(runner_off, QUERIES[9])

    def close(a, b):
        if len(a) != len(b):
            return False
        for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
            for va, vb in zip(ra, rb):
                if isinstance(va, float) and isinstance(vb, float):
                    if not np.isclose(va, vb, rtol=1e-6):
                        return False
                elif va != vb:
                    return False
        return True

    parity = close(q3_res.rows, q3_off_res.rows) and \
        close(q9_res.rows, q9_off_res.rows)
    return {
        "metric": f"tpch_sf{scale:g}_q3_engine_rows_per_sec",
        "value": round(n_rows / q3_s, 1), "unit": "rows/s",
        "vs_baseline": round(q3_off_s / q3_s, 3),
        "engine_path": True, "join_heavy": True,
        "q9_rows_per_sec": round(n_rows / q9_s, 1),
        "q9_speedup_vs_pr9_path": round(q9_off_s / q9_s, 3),
        "jit_dispatches": {
            "q3_hash": q3_jit["dispatches"],
            "q3_pr9": q3_off_jit["dispatches"],
            "q9_hash": q9_jit["dispatches"],
            "q9_pr9": q9_off_jit["dispatches"]},
        "compile_split": {
            "q3_cold_s": q3_jit["cold_s"],
            "q3_warm_compile_ms": q3_jit["warm_compile_ms"],
            "q9_warm_compile_ms": q9_jit["warm_compile_ms"]},
        "parity": parity,
    }


def bench_mesh_q1q6(scale: float):
    """TPC-H Q1 + Q6 through the DISTRIBUTED tier: a real 2-worker
    cluster (DistributedQueryRunner — coordinator + workers over HTTP)
    vs the single-process engine on the same data.  PR 11: the cluster
    runs with ``mesh_device_exchange`` ON — co-resident fragments lower
    to ONE SPMD program with in-program collectives instead of
    serde+HTTP (ROADMAP #2 acceptance: mesh >= 1.0x the LOCAL engine
    path; PR 10 measured 0.73x on the wire tier).  A second knobs-off
    cluster keeps measuring the PR 10 HTTP plane so the wire-tier trend
    stays visible."""
    import dataclasses as _dc

    from presto_tpu.config import DEFAULT
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.server.dqr import DistributedQueryRunner

    def close(a, b):
        if len(a) != len(b):
            return False
        for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
            for va, vb in zip(ra, rb):
                if isinstance(va, float) and isinstance(vb, float):
                    if not np.isclose(va, vb, rtol=1e-6):
                        return False
                elif va != vb:
                    return False
        return True

    local = LocalQueryRunner.tpch(scale=scale)
    n_rows = local.execute("select count(*) from lineitem").rows[0][0]

    def timed_local(sql):
        local.execute(sql)
        best = float("inf")
        res = None
        for _ in range(2):
            t0 = time.perf_counter()
            res = local.execute(sql)
            best = min(best, time.perf_counter() - t0)
        return best, res

    def timed_cluster(dqr, sql, runs=2):
        """Warm, then time ``runs`` executions; returns (times, res).
        The headline keeps best-of-N; the telemetry/checkpoint extras
        damp run-to-run noise the PR 13 way — MEDIAN of 3 plus a
        ``noise_band`` annotation for perf_regress."""
        dqr.execute(sql)                  # compile + warm caches
        times, res = [], None
        for _ in range(runs):
            t0 = time.perf_counter()
            res = dqr.execute(sql)
            times.append(time.perf_counter() - t0)
        return times, res

    def median(times):
        return sorted(times)[len(times) // 2]

    dev_cfg = _dc.replace(DEFAULT, mesh_device_exchange=True)
    with DistributedQueryRunner.tpch(scale=scale, n_workers=2,
                                     config=dev_cfg) as dqr:
        q1_times, q1_res = timed_cluster(dqr, ENGINE_Q1, runs=3)
        q6_times, q6_res = timed_cluster(dqr, ENGINE_Q6, runs=3)
        q1_s, q6_s = min(q1_times), min(q6_times)
        last = list(dqr.coordinator.queries.values())[-1]
        device_engaged = set(last.exchange_modes) == {"device"}
        beacon_samples = len(last.timeseries)
    # the SAME collective tier with progress beacons traced OUT of the
    # program (PR 12 default ON): the on-vs-off delta IS the telemetry
    # overhead, tracked so perf_regress can see it drift
    nb_cfg = _dc.replace(dev_cfg, mesh_progress_beacons=False)
    with DistributedQueryRunner.tpch(scale=scale, n_workers=2,
                                     config=nb_cfg) as dqr_nb:
        q1_nb_times, _r1 = timed_cluster(dqr_nb, ENGINE_Q1, runs=3)
        q6_nb_times, _r6 = timed_cluster(dqr_nb, ENGINE_Q6, runs=3)
    # PR 17 mid-program fault tolerance: the same tier with boundary
    # checkpoints ON — each fragment group runs as its own SPMD program
    # and its output is write-through'd into the spool, so the
    # on-vs-off delta IS the checkpoint overhead a user pays for
    # partial-state resume
    ck_cfg = _dc.replace(dev_cfg, mesh_checkpoint_boundaries=True)
    with DistributedQueryRunner.tpch(scale=scale, n_workers=2,
                                     config=ck_cfg) as dqr_ck:
        q1_ck_times, c1_res = timed_cluster(dqr_ck, ENGINE_Q1, runs=3)
        q6_ck_times, c6_res = timed_cluster(dqr_ck, ENGINE_Q6, runs=3)
        last_ck = list(dqr_ck.coordinator.queries.values())[-1]
        ck_info = getattr(last_ck, "device_exchange_info", None) or {}
    with DistributedQueryRunner.tpch(scale=scale, n_workers=2) as http:
        h1_times, _h1 = timed_cluster(http, ENGINE_Q1)
        h6_times, _h6 = timed_cluster(http, ENGINE_Q6)
        h1_s, h6_s = min(h1_times), min(h6_times)
    q1_local_s, q1_local = timed_local(ENGINE_Q1)
    q6_local_s, q6_local = timed_local(ENGINE_Q6)
    parity = close(q1_res.rows, q1_local.rows) and \
        close(q6_res.rows, q6_local.rows)
    ck_parity = close(c1_res.rows, q1_local.rows) and \
        close(c6_res.rows, q6_local.rows)
    q1_med, q6_med = median(q1_times), median(q6_times)
    q1_nb_s, q6_nb_s = median(q1_nb_times), median(q6_nb_times)
    q1_ck_s, q6_ck_s = median(q1_ck_times), median(q6_ck_times)
    return {
        "metric": f"tpch_sf{scale:g}_q1_mesh_2worker_rows_per_sec",
        "value": round(n_rows / q1_s, 1), "unit": "rows/s",
        # baseline = the single-process engine on the same data: >= 1.0
        # means distribution now buys more than it costs
        "vs_baseline": round(q1_local_s / q1_s, 3),
        "engine_path": True, "distributed": True, "workers": 2,
        "device_exchange": device_engaged,
        "q6_rows_per_sec": round(n_rows / q6_s, 1),
        "q6_vs_local": round(q6_local_s / q6_s, 3),
        # the PR 10 wire tier on the same cluster shape (trend line)
        "http_plane": {
            "q1_vs_local": round(q1_local_s / h1_s, 3),
            "q6_vs_local": round(q6_local_s / h6_s, 3),
        },
        # PR 12 telemetry overhead: wall with progress beacons traced
        # into the program (the shipped default) vs the beacon-free
        # PR 11 program; ratio > 1 = beacons cost wall.  PR 17: both
        # sides are MEDIAN-of-3 with the PR 13 noise_band annotation —
        # the 1-core CI host swings single-shot overhead ratios well
        # past any real beacon cost, so perf_regress gates the trend
        "telemetry": {
            "beacons_on_q1_ms": round(q1_med * 1000, 2),
            "beacons_off_q1_ms": round(q1_nb_s * 1000, 2),
            "beacons_on_q6_ms": round(q6_med * 1000, 2),
            "beacons_off_q6_ms": round(q6_nb_s * 1000, 2),
            "overhead_q1": round(q1_med / max(q1_nb_s, 1e-9), 3),
            "overhead_q6": round(q6_med / max(q6_nb_s, 1e-9), 3),
            "beacon_samples_q6": beacon_samples,
            "runs": 3, "aggregation": "median", "noise_band": 0.6,
        },
        # PR 17 checkpoint overhead: the same tier with
        # mesh_checkpoint_boundaries ON (per-group SPMD programs +
        # spool write-through) vs the one-program default; ratio > 1 =
        # what resume-ability costs when nothing fails
        "checkpoints": {
            "ckpt_on_q1_ms": round(q1_ck_s * 1000, 2),
            "ckpt_on_q6_ms": round(q6_ck_s * 1000, 2),
            "overhead_q1": round(q1_ck_s / max(q1_med, 1e-9), 3),
            "overhead_q6": round(q6_ck_s / max(q6_med, 1e-9), 3),
            "groups_q6": ck_info.get("checkpoint_groups", 0),
            "bytes_q6": ck_info.get("checkpoint_bytes", 0),
            "parity": ck_parity,
            "runs": 3, "aggregation": "median", "noise_band": 0.6,
        },
        "parity": parity,
    }


_SHARDED_JOIN_SQL = (
    "select o_orderpriority, count(*) as c, sum(l_extendedprice) as s "
    "from lineitem, orders where l_orderkey = o_orderkey "
    "group by o_orderpriority order by o_orderpriority")


def _sharded_join_model(n_probe: int, n_build: int, ncols: int,
                        nparts: int, buckets: int):
    """Modeled per-shard peak bytes of the mesh join, mirroring the
    capacity formulas in parallel/sqlmesh.py (cap_scale=1): exchange
    receive buffers (sharded sizing when nparts > 1), the per-shard
    PagesHash table, the bucket-sequential working buffers, and the
    match-expansion output.  9 bytes/column-row (8 value + 1 valid),
    int64 index buffers.  ``nparts=buckets=1`` models the single-device
    unbucketed build the P8+P9 path exists to break past."""
    from presto_tpu.batch import next_bucket

    if nparts > 1:
        pcap = next_bucket(max(8, (2 * n_probe) // nparts))
        bcap = next_bucket(max(8, (2 * n_build) // nparts))
    else:
        pcap = next_bucket(max(8, n_probe))
        bcap = next_bucket(max(8, n_build))
    table_cap = next_bucket(2 * bcap, minimum=16)
    out_cap = next_bucket(max(pcap, bcap))
    if buckets > 1:
        wb = min(next_bucket(max(8, (2 * bcap) // buckets)), bcap)
        wp = min(next_bucket(max(8, (2 * pcap) // buckets)), pcap)
        we = min(next_bucket(max(8, (2 * max(pcap, bcap)) // buckets)),
                 out_cap)
    else:
        wb, wp, we = bcap, pcap, out_cap
    col = 9                      # value + valid bytes per row per column
    idx = 8
    exchange_bytes = (pcap + bcap) * ncols * col
    table_bytes = table_cap * (2 * idx + 8 + 1 + 1)  # words+starts+cnt..
    working_bytes = (wb + wp) * (ncols * col + idx) + we * 3 * idx
    out_bytes = out_cap * (ncols * col + 2 * idx)
    return {
        "probe_cap": pcap, "build_cap": bcap, "table_cap": table_cap,
        "bucket_caps": [wb, wp, we], "out_cap": out_cap,
        "total_bytes": exchange_bytes + table_bytes + working_bytes
        + out_bytes,
    }


def _sharded_join_inner(scale: float):
    """Runs inside the 8-virtual-device subprocess: the P8+P9
    acceptance config — lineitem JOIN orders with the build FORCED
    partitioned (join_distribution_type), the PagesHash build table
    sharded across 8 shards' HBM, probes routed by the hash-exchange
    all_to_all, and 8 hash buckets run sequentially through the sharded
    join."""
    import dataclasses as _dc

    import jax

    from presto_tpu.config import DEFAULT
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.parallel.sqlmesh import MeshQueryRunner

    P, B = 8, 8
    local = LocalQueryRunner.tpch(scale=scale)
    n_probe = local.execute("select count(*) from lineitem").rows[0][0]
    n_build = local.execute("select count(*) from orders").rows[0][0]
    want = local.execute(_SHARDED_JOIN_SQL).rows
    cfg = _dc.replace(
        DEFAULT, partitioned_join_build=True, grouped_mesh_execution=B,
        device_join_probe_max_build_rows=1,
        join_distribution_type="partitioned")
    mesh = MeshQueryRunner.tpch(scale=scale, n_devices=P, config=cfg)
    mesh.execute(_SHARDED_JOIN_SQL)          # trace + compile
    best = float("inf")
    res = None
    for _ in range(2):
        t0 = time.perf_counter()
        res = mesh.execute(_SHARDED_JOIN_SQL)
        best = min(best, time.perf_counter() - t0)
    info = mesh.last_run_info

    def close(a, b):
        if len(a) != len(b):
            return False
        for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
            for va, vb in zip(ra, rb):
                if isinstance(va, float) and isinstance(vb, float):
                    if not np.isclose(va, vb, rtol=1e-6):
                        return False
                elif va != vb:
                    return False
        return True

    # HBM overflow model (documented acceptance): capacity formulas
    # mirror parallel/sqlmesh.py; bytes scale ~linearly with the scale
    # factor, so dividing a real 16 GiB v5e HBM by the per-SF bytes
    # gives each path's maximum holdable scale factor.  The run
    # executes at a budget scaled to SF_CLAIM — a scale factor the
    # model puts PAST the single-device limit and INSIDE the sharded
    # one: the single-device build provably overflows it while the
    # 8-shard x 8-bucket partitioned+grouped path fits.
    ncols = 3                      # l_orderkey, l_extendedprice, o_* keys
    single = _sharded_join_model(n_probe, n_build, ncols, 1, 1)
    sharded = _sharded_join_model(n_probe, n_build, ncols, P, B)
    hbm = 16 * (1 << 30)
    sf_max_single = round(hbm / (single["total_bytes"] / scale), 1)
    sf_max_sharded = round(hbm / (sharded["total_bytes"] / scale), 1)
    sf_claim = 30.0
    budget = int(hbm * scale / sf_claim)
    tiers = info.get("kernel_tiers", [])
    grouped_pages = sum(1 for t in tiers
                        if t.startswith("grouped join")
                        and t.endswith("pages_hash"))
    return {
        "metric": f"tpch_sf{scale:g}_sharded_join_rows_per_sec",
        "value": round(n_probe / best, 1), "unit": "rows/s",
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "engine_path": True, "distributed": True,
        "nparts": P, "buckets": B,
        "parity": close(res.rows, want),
        "exchange_modes": info.get("exchange_modes", {}),
        "grouped_pages_hash_buckets": grouped_pages,
        "hbm_model": {
            "note": (f"16 GiB v5e budget scaled to SF{sf_claim:g}: the "
                     "single-device unbucketed build overflows it, the "
                     "8-shard x 8-bucket path fits; sf_max_* = largest "
                     "SF each path holds under a real 16 GiB HBM"),
            "budget_bytes": budget,
            "single_device_bytes": single["total_bytes"],
            "single_device_overflows": single["total_bytes"] > budget,
            "per_shard_bucketed_bytes": sharded["total_bytes"],
            "sharded_fits": sharded["total_bytes"] < budget,
            "sf_max_single_16gib": sf_max_single,
            "sf_max_sharded_16gib": sf_max_sharded,
            "single": single, "sharded": sharded,
        },
    }


def bench_mesh_sharded_join(scale: float):
    """P8 + P9 acceptance config (ROADMAP #2): the partitioned lookup
    source (PagesHash build sharded across 8 shards' HBM, probes routed
    by all_to_all) plus bucket-sequential grouped execution, at a scale
    factor where the single-device unbucketed build provably overflows
    the modeled per-device HBM budget (extras carry the model).  Runs
    in a subprocess so the 8-virtual-device XLA host platform doesn't
    perturb the other configs' device topology."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--sharded-join-inner", str(scale)],
        env=env, capture_output=True, text=True, timeout=1200)
    for ln in reversed(r.stdout.strip().splitlines()):
        try:
            return json.loads(ln)
        except ValueError:
            continue
    raise RuntimeError(f"bench_mesh_sharded_join sf{scale:g} failed: "
                       f"{(r.stderr or r.stdout)[-300:]}")


def _bench_tpcds_mesh(scale: float, spooling: bool):
    import dataclasses as _dc
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpcds_queries import QUERIES as DS

    from presto_tpu.config import DEFAULT
    from presto_tpu.connectors.api import ConnectorRegistry
    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.server.dqr import DistributedQueryRunner

    reg = ConnectorRegistry()
    reg.register("tpcds", TpcdsConnector(scale=scale))
    local = LocalQueryRunner(reg, "tpcds")
    n_rows = local.execute(
        "select count(*) from tpcds.catalog_sales").rows[0][0] + \
        local.execute("select count(*) from tpcds.web_sales").rows[0][0]

    def norm(rows):
        return sorted(tuple(round(v, 4) if isinstance(v, float) else v
                            for v in r) for r in rows)

    cfg = _dc.replace(DEFAULT, exchange_spooling_enabled=spooling)
    # the spooled config swings wildly across single-shot runs
    # (158-742 rows/s observed in the PR 12 variance investigation:
    # write-through timing vs the 0.1s stats sampler beats) — report
    # the MEDIAN of 3 mesh executions per query so perf_regress
    # --check gates on the trend, not the noise
    runs = 3 if spooling else 1
    out = {}
    with DistributedQueryRunner.tpcds(scale=scale, n_workers=2,
                                      config=cfg) as dqr:
        for qn in (72, 95):
            t0 = time.perf_counter()
            want = local.execute(DS[qn]).rows
            t_local = time.perf_counter() - t0
            mesh_times, parity = [], True
            for _ in range(runs):
                t0 = time.perf_counter()
                got = dqr.execute(DS[qn]).rows
                mesh_times.append(time.perf_counter() - t0)
                parity = parity and norm(got) == norm(want)
            t_mesh = sorted(mesh_times)[len(mesh_times) // 2]
            out[qn] = {"mesh_s": round(t_mesh, 3),
                       "local_s": round(t_local, 3),
                       "mesh_runs_s": [round(t, 3)
                                       for t in mesh_times],
                       "parity": parity}
    suffix = "_spooled" if spooling else ""
    row = {
        "metric": f"tpcds_sf{scale:g}_q72q95_mesh_2worker"
                  f"{suffix}_fact_rows_per_sec",
        "value": round(n_rows / (out[72]["mesh_s"] + out[95]["mesh_s"]),
                       1),
        "unit": "rows/s", "vs_baseline": round(
            (out[72]["local_s"] + out[95]["local_s"])
            / (out[72]["mesh_s"] + out[95]["mesh_s"]), 3),
        "engine_path": True, "distributed": True, "workers": 2,
        "exchange_spooling": spooling,
        "runs": runs, "aggregation": "median" if runs > 1 else "single",
        "q72": out[72], "q95": out[95],
        "parity": out[72]["parity"] and out[95]["parity"],
    }
    if spooling:
        # documented run-to-run spread of this config on the 1-core CI
        # host (PR 12 investigation: 158-742 rows/s across reruns of
        # one tree) — perf_regress widens its gate to this band for
        # THIS config only, so the trajectory check gates on the trend
        row["noise_band"] = 0.6
    return row


def bench_tpcds_mesh_q72q95(scale: float):
    """TPC-DS Q72 + Q95 — the BASELINE.md multi-chip configs — through
    the DISTRIBUTED tier: a real 2-worker cluster with HTTP exchanges,
    parity-checked against the single-process engine on identical data
    (ROADMAP #3: the multi-chip proof beyond TPC-H, measured).
    Exchange spooling OFF: this row keeps measuring the PR 5-era
    in-memory data plane, so its trend stays comparable."""
    return _bench_tpcds_mesh(scale, spooling=False)


def bench_tpcds_mesh_q72q95_spooled(scale: float):
    """The same mesh configs with the spooled exchange ON (write-through
    to the local-FS spool store): the delta against
    ``bench_tpcds_mesh_q72q95`` IS the spooling overhead, tracked as a
    number per round."""
    return _bench_tpcds_mesh(scale, spooling=True)


def bench_concurrent_qps(scale: float):
    """Serving-tier sustained QPS (tools/qps_run.py): N concurrent
    clients driving the mixed TPC-H/TPC-DS statement set against a live
    2-worker DistributedQueryRunner with resource-group admission
    engaged — QPS + p50/p95/p99 per concurrency level, exact-rows
    parity per client, plan-cache hit rate, and the zero-jit-compile
    proof for the second execution of a cached plan — plus the
    open-loop overload curve (bounded-pool dispatcher driven past
    saturation: goodput/shed/latency per arrival rate)."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import qps_run

    report = qps_run.run_qps(scale=scale, levels=(1, 2, 4, 8),
                             requests_per_client=6, mode="closed",
                             quiet=True)
    peak = max(lv["qps"] for lv in report["levels"])
    levels = []
    for lv in report["levels"]:
        row = {k: lv[k] for k in ("concurrency", "qps", "p50_ms",
                                  "p95_ms", "p99_ms", "parity")}
        row["plan_cache_hit_rate"] = lv["plan_cache"]["hit_rate"]
        levels.append(row)
    # hot-repeat tier (server/resultcache.py): the SAME dashboard-shape
    # worklist with the cross-query result cache on vs off — the on/off
    # ratio is the serving-tier headline a hit costs one spool lookup
    # instead of a full execution.  Parity is per request in both runs.
    hot = {}
    for label, rc in (("cache_on", True), ("cache_off", False)):
        rep = qps_run.run_qps(scale=scale, levels=(4,),
                              requests_per_client=10, mode="closed",
                              quiet=True, hot_repeat=True,
                              result_cache=rc)
        lv = rep["levels"][0]
        hot[label] = {
            "qps": lv["qps"], "p50_ms": lv["p50_ms"],
            "p95_ms": lv["p95_ms"], "parity": rep["parity"],
            "result_cache_hit_rate": rep["result_cache_hit_rate"],
            "result_cache_bytes_served":
                rep["result_cache_bytes_served"]}
    hot["speedup"] = round(
        hot["cache_on"]["qps"] / hot["cache_off"]["qps"], 2) \
        if hot["cache_off"]["qps"] else 0.0
    hot["parity"] = (hot["cache_on"]["parity"]
                     and hot["cache_off"]["parity"])
    # open-loop overload tier (server/dispatcher.py bounded pool):
    # arrivals PAST saturation must degrade to fast well-shaped
    # QUERY_QUEUE_FULL rejections with retry hints while goodput holds
    # — the graceful-degradation curve (goodput/shed/latency per rate)
    # 4s per level: at 2s the goodput ratio is dominated by queue
    # ramp/drain edge effects on the 1-core CI host (measured swings
    # 0.60-1.04 across reruns of one tree); the longer window keeps
    # the steady-state shed/goodput mix in charge of the number
    ov = qps_run.run_overload(scale=scale, pool_size=4, max_queued=8,
                              duration_s=4.0, quiet=True)
    overload = {
        "peak_qps": ov["peak_qps"],
        "dispatcher": ov["dispatcher"],
        "goodput_ratio_at_2x": ov["goodput_ratio_at_max"],
        "shed_total": ov["shed_total"],
        "graceful": ov["ok"],
        # "errors" carries samples of any non-shaped failure so a
        # parity=false artifact is diagnosable from the JSON alone
        "levels": [{k: lv[k] for k in (
            "rate_factor", "rate_per_s", "requests", "ok", "shed",
            "other", "goodput_qps", "shed_rate", "p50_ms", "p95_ms",
            "shed_p95_ms", "errors")} for lv in ov["levels"]],
    }
    return {
        "metric": f"tpcds_sf{scale:g}_concurrent_qps_peak",
        "value": peak, "unit": "qps",
        # scaling vs the single-client level: how much of the added
        # concurrency the serving tier converts into throughput
        "vs_baseline": round(peak / report["levels"][0]["qps"], 3)
        if report["levels"][0]["qps"] else 0.0,
        "engine_path": True, "distributed": True, "workers": 2,
        "levels": levels,
        "plan_cache_hit_rate": report["plan_cache_hit_rate"],
        "second_run_jit_compiles": report["second_run_jit_compiles"],
        "queries_queued": report["queries_queued"],
        "resource_groups": report["resource_groups"],
        "hot_repeat": hot,
        "overload": overload,
        # overload folds only its SHAPE requirement into parity (zero
        # non-error-shaped failures); the goodput ratio is a perf
        # property recorded in the curve, not a correctness gate
        "parity": report["parity"] and hot["parity"]
        and all(lv["other"] == 0 for lv in overload["levels"]),
    }


def bench_sqlite_baseline(scale: float):
    """External (non-self-authored) CPU baseline: the sqlite3 engine over
    IDENTICAL generated data, per BASELINE.md's measurement note — the
    'reference CPU engine' stand-in the builder did not write."""
    import sqlite3

    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=scale)
    db = sqlite3.connect(":memory:")
    for table, cols in (
        ("lineitem", ["l_orderkey", "l_quantity", "l_extendedprice",
                      "l_discount", "l_tax", "l_returnflag",
                      "l_linestatus", "l_shipdate"]),
    ):
        h = conn.get_table(table)
        schema = conn.table_schema(h)
        db.execute(f"create table {table} ("
                   + ", ".join(f"{c} NUMERIC" for c in cols) + ")")
        n = 0
        for split in conn.get_splits(h, 1):
            for b in conn.page_source(split, cols, 1 << 20):
                rows = b.to_pylist()
                db.executemany(
                    f"insert into {table} values "
                    f"({', '.join('?' * len(cols))})",
                    [[str(v) if not isinstance(v, (int, float)) else v
                      for v in r] for r in rows])
                n += b.num_rows
        db.commit()
    t0 = time.perf_counter()
    db.execute(
        "select l_returnflag, l_linestatus, sum(l_quantity), "
        "sum(l_extendedprice), sum(l_extendedprice*(1-l_discount)), "
        "sum(l_extendedprice*(1-l_discount)*(1+l_tax)), sum(l_discount), "
        "count(*) from lineitem where l_shipdate <= 10471 "
        "group by l_returnflag, l_linestatus").fetchall()
    q1_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    db.execute(
        "select sum(l_extendedprice*l_discount) from lineitem "
        f"where l_shipdate >= {Q6_DATE_LO} and l_shipdate < {Q6_DATE_HI} "
        f"and l_discount > {Q6_DISC_LO} and l_discount < {Q6_DISC_HI} "
        "and l_quantity < 24").fetchall()
    q6_s = time.perf_counter() - t0
    db.close()
    return {
        "metric": f"cpu_sqlite_sf{scale:g}_q1_rows_per_sec",
        "value": round(n / q1_s, 1), "unit": "rows/s",
        "vs_baseline": 1.0,
        "note": "external engine (sqlite3) on identical generated data",
        "q6_rows_per_sec": round(n / q6_s, 1),
    }


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


# Documented single-host run-to-run spread, the PR 12/13 way but
# measured wholesale (2026-08: three reruns of one tree on the 1-core
# CI host; the stdlib-sqlite CONTROL config — zero repo code — swung
# -31%/+50% between back-to-back runs, so the spread is host
# scheduling noise, not engine drift).  Bands are the max measured
# spread per config rounded up; perf_regress widens its gate to the
# band for these configs only, so the trajectory still gates the
# trend.  Matched by metric-name fragment (scale prefix varies).
_HOST_NOISE_BANDS = (
    ("cpu_sqlite_", 0.55),
    ("q3_engine_rows_per_sec", 0.55),
    ("concurrent_qps_peak", 0.40),
    ("q1_mesh_2worker_rows_per_sec", 0.35),
    ("q3_join_agg_rows_per_sec_per_chip", 0.30),
    ("q17_join_agg_rows_per_sec_per_chip", 0.30),
    ("sharded_join_rows_per_sec", 0.30),
    ("q1_rows_per_sec_per_chip", 0.25),
    ("q6_rows_per_sec_per_chip", 0.25),
    ("q9_join_agg_rows_per_sec_per_chip", 0.25),
    ("q1_engine_rows_per_sec", 0.25),
)


def _stamp_noise_band(row) -> None:
    m = row.get("metric", "")
    for frag, band in _HOST_NOISE_BANDS:
        if frag in m:
            # never narrow a band a config already declares (spooled
            # tpcds carries 0.6 from its own investigation)
            row["noise_band"] = max(row.get("noise_band", 0.0), band)
            return


def _run_jobs(headline, jobs, budget_s):
    extras = []
    t_start = time.perf_counter()
    for fn, scale, need_frac in jobs:
        elapsed = time.perf_counter() - t_start
        if need_frac and elapsed > budget_s * (1.0 - need_frac):
            extras.append({"metric": f"{fn.__name__}_sf{scale:g}_skipped",
                           "note": f"bench budget ({elapsed:.0f}s used)"})
            continue
        extras.append(fn(scale))
    # anchor the headline ratio externally when the sqlite baseline ran:
    # rows/s at the measured scales (sqlite rows/s is ~scale-invariant)
    for e in extras:
        if e.get("metric", "").startswith("cpu_sqlite") \
                and "value" in e and headline.get("value"):
            headline["vs_external_sqlite"] = round(
                headline["value"] / e["value"], 1)
    if not headline.pop("parity", True):
        headline = {"metric": "tpch_q1_parity_failure", "value": 0.0,
                    "unit": "rows/s", "vs_baseline": 0.0}
    import jax

    dev = jax.devices()[0]
    for row in extras + [headline]:
        _stamp_noise_band(row)
        # the sharded-join child stamps its own (forced-CPU) device
        row.setdefault("platform", dev.platform)
        row.setdefault("device_kind", dev.device_kind)
        row.setdefault("device_count", len(jax.devices()))
    headline["extras"] = extras
    return headline


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-join-inner":
        # subprocess entry for bench_mesh_sharded_join (8 virtual
        # devices forced via XLA_FLAGS by the parent)
        _emit(_sharded_join_inner(float(sys.argv[2])))
        return
    q1_scale = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
    budget_s = float(os.environ.get("PRESTO_TPU_BENCH_BUDGET_S", "1500"))
    cpu_only = os.environ.get("PRESTO_TPU_BENCH_CPU_ONLY") == "1"
    if cpu_only:
        # parity-evidence mode: small scales, every config; run it with
        # JAX_PLATFORMS=cpu (every row is stamped with the device it ran on)
        headline = bench_q1(q1_scale)
        jobs = [(bench_q6, 0.1, 0.0), (bench_q3, 0.1, 0.0),
                (bench_q9, 0.1, 0.0), (bench_q17, 0.1, 0.0),
                (bench_q3_chunked, 0.2, 0.0),
                (bench_engine_q1q6, 0.05, 0.0),
                (bench_engine_q3q9, 0.05, 0.0),
                (bench_mesh_q1q6, 0.05, 0.0),
                (bench_mesh_sharded_join, 0.2, 0.0),
                (bench_tpcds_mesh_q72q95, 0.003, 0.0),
                (bench_tpcds_mesh_q72q95_spooled, 0.003, 0.0),
                (bench_concurrent_qps, 0.003, 0.0),
                (bench_sqlite_baseline, 0.05, 0.0)]
        _emit(_run_jobs(headline, jobs, budget_s))
        return
    headline = bench_q1(q1_scale)
    # cheap configs first; the biggest scales run only with budget left.
    # bench_q3_chunked streams SF100 as order-aligned chunk dispatches
    # through one compiled program — the grouped-execution (P9) idea
    # applied to the bench.
    jobs = [(bench_q6, 10.0, 0.0), (bench_q3, 1.0, 0.0),
            (bench_q9, 1.0, 0.0), (bench_q17, 1.0, 0.0),
            (bench_engine_q1q6, 1.0, 0.0),
            (bench_engine_q3q9, 0.2, 0.0),
            (bench_mesh_q1q6, 0.2, 0.0),
            (bench_mesh_sharded_join, 1.0, 0.0),
            (bench_tpcds_mesh_q72q95, 0.003, 0.0),
            (bench_tpcds_mesh_q72q95_spooled, 0.003, 0.0),
            (bench_concurrent_qps, 0.003, 0.0),
            (bench_whole_query_q3, 0.1, 0.0),
            (bench_sqlite_baseline, 0.2, 0.0),
            (bench_q3, 10.0, 0.65),
            (bench_q9, 10.0, 0.55), (bench_q17, 10.0, 0.5),
            (bench_q3, 30.0, 0.4),
            (bench_q3_chunked, 100.0, 0.3),
            (bench_q9, 100.0, 0.2), (bench_q17, 100.0, 0.15)]
    _emit(_run_jobs(headline, jobs, budget_s))


if __name__ == "__main__":
    main()
