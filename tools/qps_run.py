#!/usr/bin/env python
"""Sustained-QPS load plane: drive a live cluster with concurrent
clients and measure latency under contention.

The CLI face of the serving tier (server/dispatcher.py +
sql/plancache.py): boots a real in-process DistributedQueryRunner
(coordinator + workers + HTTP exchanges), then drives a mixed
TPC-H/TPC-DS statement set from N concurrent clients — each with its
own StatementClient and its own user (so resource-group admission is
actually engaged) — and reports QPS, p50/p95/p99 latency, per-client
exact-rows parity against a single-threaded oracle run, and the plan
cache's hit rate:

    JAX_PLATFORMS=cpu python tools/qps_run.py --levels 1,2,4,8
    JAX_PLATFORMS=cpu python tools/qps_run.py --mode open --rate 20
    JAX_PLATFORMS=cpu python tools/qps_run.py --check

Modes:

- ``closed`` (default): each client issues its next statement the
  moment the previous one returns — N in-flight requests, throughput-
  bound (the dashboard-fleet shape);
- ``open``: statements arrive on a fixed schedule (``--rate`` per
  second) regardless of completions, and latency is measured from
  *arrival* — queueing delay under overload is visible (the
  million-users shape).

``--hot`` swaps in the hot-repeat mix (every statement repeated
verbatim — the dashboard-refresh shape) and ``--result-cache`` turns
the cross-query result cache (server/resultcache.py) on for the
cluster; result-cache hit-rate and bytes-served-from-cache are
reported per level beside the plan-cache hit rate either way.

``--check`` is the CI smoke tier: tiny scale, 2 concurrency levels,
exits nonzero unless every client saw exact rows AND the plan cache
recorded hits AND the repeated statement's second execution compiled
nothing — then a hot-repeat run with the result cache on must show
nonzero result-cache hits with exact rows and a result-cache-served
second execution.

Exit code 0 = all levels parity-clean (and --check assertions hold).
"""

import argparse
import json
import os
import queue
import sys
import threading
import time
import urllib.request

# runnable from anywhere: `python tools/qps_run.py` puts tools/ on the
# path, not the repo root (same shim as chaos_run.py)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

#: the mixed statement set: TPC-H aggregations + joins and TPC-DS
#: aggregations + joins, each cheap enough to repeat under load, plus a
#: parameter-bound prepared statement (the EXECUTE plan-cache path).
STATEMENTS = [
    ("tpch_q6ish",
     "select sum(l_extendedprice * l_discount) as revenue "
     "from tpch.lineitem "
     "where l_discount between 0.05 and 0.07 and l_quantity < 24"),
    ("tpch_q1_lite",
     "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
     "count(*) as cnt from tpch.lineitem "
     "group by l_returnflag, l_linestatus "
     "order by l_returnflag, l_linestatus"),
    ("tpch_nation_join",
     "select n_name, count(*) as c from tpch.customer, tpch.nation "
     "where c_nationkey = n_nationkey "
     "group by n_name order by c desc, n_name"),
    ("tpcds_store_agg",
     "select ss_store_sk, count(*) as c, sum(ss_net_paid) as paid "
     "from tpcds.store_sales group by ss_store_sk order by ss_store_sk"),
    ("tpcds_item_join",
     "select i_class, count(*) as c "
     "from tpcds.store_sales, tpcds.item "
     "where ss_item_sk = i_item_sk "
     "group by i_class order by c desc, i_class"),
]

PREPARE_SQL = ("prepare qps_param from select count(*) as c "
               "from tpch.lineitem where l_quantity < ?")
EXECUTE_SQL = "execute qps_param using 10"

#: the hot-repeat mix (``--hot``): two statements repeated verbatim —
#: the dashboard-refresh shape the cross-query result cache
#: (server/resultcache.py) exists for.  After each statement's first
#: execution every repeat is a cache hit served from spool pages.
HOT_STATEMENTS = ["tpch_q1_lite", "tpcds_store_agg"]


def _norm_rows(rows):
    """Order-insensitive, float-tolerant row normalization for the
    exact-rows parity check."""
    return sorted(tuple(round(v, 6) if isinstance(v, float) else v
                        for v in r) for r in rows)


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _client_worklist(n_requests, offset, hot=False):
    """The statement sequence one client walks: the shared mix, rotated
    per client so concurrent clients overlap on every statement (the
    plan-cache contention case) without issuing in lockstep.  ``hot``
    walks the tiny HOT_STATEMENTS mix instead — every statement repeats
    verbatim, the result-cache case."""
    names = (HOT_STATEMENTS if hot
             else [name for name, _ in STATEMENTS] + ["tpch_execute"])
    return [names[(offset + j) % len(names)] for j in range(n_requests)]


class _Oracle:
    """Single-threaded expected rows per statement name."""

    def __init__(self, dqr):
        client = dqr.new_client(user="oracle")
        client.execute(PREPARE_SQL)
        self.rows = {}
        for name, sql in STATEMENTS:
            self.rows[name] = _norm_rows(dqr.execute(sql).rows)
        cols, data = client.execute(EXECUTE_SQL)
        self.rows["tpch_execute"] = _norm_rows([tuple(r) for r in data])
        self.sql = dict(STATEMENTS)
        self.sql["tpch_execute"] = EXECUTE_SQL


def _run_one(client, oracle, name):
    """Issue one statement; returns (latency_s, parity_ok)."""
    t0 = time.perf_counter()
    _cols, data = client.execute(oracle.sql[name])
    lat = time.perf_counter() - t0
    ok = _norm_rows([tuple(r) for r in data]) == oracle.rows[name]
    return lat, ok


def run_closed_level(dqr, oracle, concurrency, requests_per_client,
                     n_users=2, hot=False):
    """Closed loop: N clients, each back-to-back through its worklist."""
    lock = threading.Lock()
    lats, mismatches, errors = [], [], []

    def client_loop(i):
        client = dqr.new_client(user=f"client{i % n_users}")
        try:
            client.execute(PREPARE_SQL)
            for name in _client_worklist(requests_per_client, i, hot):
                lat, ok = _run_one(client, oracle, name)
                with lock:
                    lats.append(lat)
                    if not ok:
                        mismatches.append((i, name))
        except Exception as e:  # noqa: BLE001 - reported in the result
            with lock:
                errors.append(f"client{i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client_loop, args=(i,),
                                daemon=True, name=f"qps-client-{i}")
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return _level_report(concurrency, lats, wall, mismatches, errors,
                         mode="closed")


def run_open_level(dqr, oracle, concurrency, rate_per_s, n_requests,
                   n_users=2, hot=False):
    """Open loop: arrivals on a fixed schedule; latency counts from
    scheduled arrival (queueing under overload is visible).  A pool of
    ``concurrency`` workers drains the arrival queue."""
    lock = threading.Lock()
    lats, mismatches, errors = [], [], []
    work: "queue.Queue" = queue.Queue()
    start = time.perf_counter() + 0.05
    for j, name in enumerate(_client_worklist(n_requests, 0, hot)):
        work.put((start + j / rate_per_s, name))

    def worker(i):
        client = dqr.new_client(user=f"client{i % n_users}")
        try:
            client.execute(PREPARE_SQL)
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(f"client{i}: {e}")
            return
        while True:
            try:
                arrival, name = work.get_nowait()
            except queue.Empty:
                return
            now = time.perf_counter()
            if now < arrival:
                time.sleep(arrival - now)
            try:
                _lat, ok = _run_one(client, oracle, name)
                done = time.perf_counter()
                with lock:
                    lats.append(done - arrival)   # includes queue wait
                    if not ok:
                        mismatches.append((i, name))
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"client{i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(i,), daemon=True,
                                name=f"qps-open-{i}")
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    rep = _level_report(concurrency, lats, wall, mismatches, errors,
                        mode="open")
    rep["target_rate_per_s"] = rate_per_s
    return rep


def run_overload_level(dqr, oracle, rate_per_s, n_requests, n_users=4):
    """TRUE open loop: one thread per scheduled arrival, no client-side
    gating — the arrival process never slows down when the server does,
    which is what makes shedding-not-collapse observable.  Every
    request is classified: ``ok`` (exact rows), ``shed`` (the
    dispatcher's QUERY_QUEUE_FULL shape WITH a retry hint), or
    ``other`` (anything else — a 500, a hang, a misshapen rejection —
    which overload must never produce)."""
    from presto_tpu.client import QueryFailed

    lock = threading.Lock()
    ok_lats, shed_lats, other = [], [], []
    names = [name for name, _ in STATEMENTS]
    start = time.perf_counter() + 0.1

    def issue(j, name):
        client = dqr.new_client(user=f"load{j % n_users}")
        arrival = start + j / rate_per_s
        now = time.perf_counter()
        if now < arrival:
            time.sleep(arrival - now)
        try:
            # max_retries=0: classification needs the raw rejection —
            # the retry loop is the client's own graceful-degradation
            # behavior, measured separately (tests/test_overload.py)
            _cols, data = client.execute(oracle.sql[name],
                                         max_retries=0)
            lat = time.perf_counter() - arrival
            parity = _norm_rows([tuple(r) for r in data]) \
                == oracle.rows[name]
            with lock:
                if parity:
                    ok_lats.append(lat)
                else:
                    other.append(f"req{j}: row mismatch on {name}")
        except QueryFailed as e:
            lat = time.perf_counter() - arrival
            well_shaped = (e.error_name == "QUERY_QUEUE_FULL"
                           and e.error_type == "INSUFFICIENT_RESOURCES"
                           and e.retry_after_s is not None)
            with lock:
                if well_shaped:
                    shed_lats.append(lat)
                else:
                    other.append(f"req{j}: {e.error_name}: {e}")
        except Exception as e:  # noqa: BLE001 - the unshaped bucket
            with lock:
                other.append(f"req{j}: {type(e).__name__}: {e}")

    threads = [threading.Thread(
        target=issue, args=(j, names[j % len(names)]), daemon=True,
        name=f"qps-overload-{j}") for j in range(n_requests)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = max(time.perf_counter() - t0, 1e-9)
    lats_sorted = sorted(ok_lats)
    return {
        "mode": "overload",
        "rate_per_s": round(rate_per_s, 2),
        "requests": n_requests,
        "ok": len(ok_lats),
        "shed": len(shed_lats),
        "other": len(other),
        "goodput_qps": round(len(ok_lats) / wall, 2),
        "shed_rate": round(len(shed_lats) / n_requests, 3),
        "p50_ms": round(_percentile(lats_sorted, 0.50) * 1e3, 1),
        "p95_ms": round(_percentile(lats_sorted, 0.95) * 1e3, 1),
        "shed_p95_ms": round(
            _percentile(sorted(shed_lats), 0.95) * 1e3, 1),
        "errors": other[:5],
    }


def run_overload(scale=0.003, pool_size=4, max_queued=8,
                 duration_s=3.0, factors=(0.5, 1.0, 2.0),
                 n_workers=2, quiet=False):
    """Open-loop graceful-degradation sweep over the bounded-pool
    dispatcher (``dispatcher_pool_size`` / ``dispatcher_max_queued``):
    measure peak capacity closed-loop first, then drive open-loop
    arrivals at fractions of it THROUGH saturation.  ``ok`` requires
    zero non-error-shaped failures at every rate, shedding engaged past
    saturation, and goodput at the highest rate >= 80% of peak — load
    past capacity must degrade to fast well-shaped rejections, never
    collapse."""
    import dataclasses

    from presto_tpu.config import DEFAULT
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.session import ResourceGroupManager

    cfg = dataclasses.replace(DEFAULT,
                              dispatcher_pool_size=pool_size,
                              dispatcher_max_queued=max_queued)
    # admission control for this sweep is the DISPATCHER's: keep the
    # resource-group tree wide open so every rejection is the bounded
    # pool's well-shaped shed, not a group-queue shape without a hint
    groups = ResourceGroupManager(
        hard_concurrency_limit=max(16, pool_size * 4),
        per_user_limit=max(16, pool_size * 4))
    report = {"scale": scale, "mode": "overload",
              "n_workers": n_workers,
              "dispatcher": {"pool_size": pool_size,
                             "max_queued": max_queued},
              "levels": []}
    with DistributedQueryRunner.tpcds(scale=scale, n_workers=n_workers,
                                      resource_groups=groups,
                                      config=cfg) as dqr:
        oracle = _Oracle(dqr)          # also warms scan + kernel caches
        closed = run_closed_level(dqr, oracle, pool_size, 6)
        peak = max(closed["qps"], 1.0)
        report["peak_qps"] = peak
        report["peak_parity"] = closed["parity"]
        for f in factors:
            rate = max(peak * f, 1.0)
            n = max(min(int(rate * duration_s), 150), 4)
            level = run_overload_level(dqr, oracle, rate, n)
            level["rate_factor"] = f
            report["levels"].append(level)
            if not quiet:
                print(json.dumps(level), flush=True)
        report["shed_total"] = dqr.coordinator.dispatcher.shed_total
    top = report["levels"][-1]
    # degradation is judged WITHIN the open-loop curve: goodput at the
    # top rate vs the best sustained goodput across the sweep's own
    # levels.  The closed-loop peak only sets the rate schedule — as a
    # ratio denominator it mixes two measurement windows, and on a
    # noisy single-core host the cross-window drift (not the engine)
    # ends up owning the number.  A real collapse still fails: goodput
    # that tanks past saturation tanks against its own curve too.
    crest = max(lv["goodput_qps"] for lv in report["levels"])
    report["goodput_ratio_at_max"] = round(
        top["goodput_qps"] / max(crest, 1e-9), 3)
    report["ok"] = (
        report["peak_parity"]
        and all(lv["other"] == 0 for lv in report["levels"])
        and top["shed"] > 0
        and report["goodput_ratio_at_max"] >= 0.8)
    return report


def _level_report(concurrency, lats, wall, mismatches, errors, mode):
    lats_sorted = sorted(lats)
    return {
        "mode": mode,
        "concurrency": concurrency,
        "requests": len(lats),
        "wall_s": round(wall, 3),
        "qps": round(len(lats) / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(_percentile(lats_sorted, 0.50) * 1e3, 1),
        "p95_ms": round(_percentile(lats_sorted, 0.95) * 1e3, 1),
        "p99_ms": round(_percentile(lats_sorted, 0.99) * 1e3, 1),
        "parity": not mismatches and not errors,
        "mismatches": mismatches[:5],
        "errors": errors[:5],
    }


def _second_run_jit_compiles(dqr, oracle):
    """Execute an already-cached statement once more and read its
    /v1/query detail: a warm plan-cache + kernel-cache run must show
    jit_compiles == 0 (the cross-query compiled-tier reuse proof).
    With the result cache on, the second run is served from spool
    pages instead (resultCached=true) — its jit counters are genuine
    zeros and no plan was consulted at all."""
    client = dqr.new_client(user="probe")
    name = STATEMENTS[0][0]
    client.execute(oracle.sql[name])          # belt-and-braces warm
    client.execute(oracle.sql[name])
    qid = client.last_query_id
    with urllib.request.urlopen(
            f"{dqr.coordinator.uri}/v1/query/{qid}", timeout=10) as resp:
        detail = json.loads(resp.read())
    return (int((detail.get("queryStats") or {}).get("jit_compiles", -1)),
            bool(detail.get("planCached")),
            bool(detail.get("resultCached")))


def run_qps(scale=0.003, levels=(1, 2, 4, 8), requests_per_client=4,
            mode="closed", rate_per_s=10.0, n_workers=2,
            hard_concurrency=8, per_user_limit=4, quiet=False,
            hot_repeat=False, result_cache=False):
    """Boot the cluster, run every concurrency level, return the report
    dict.  ``hot_repeat`` drives the
    repeated-verbatim statement mix; ``result_cache`` turns the
    cross-query result cache on for the cluster (hits are reported per
    level beside the plan-cache numbers either way)."""
    import dataclasses

    from presto_tpu.config import DEFAULT
    from presto_tpu.server import resultcache
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.session import ResourceGroupManager
    from presto_tpu.sql import plancache

    groups = ResourceGroupManager(
        hard_concurrency_limit=hard_concurrency,
        per_user_limit=per_user_limit)
    # the result cache is process-global (like the plan cache): start
    # each load run from a cold, unpolluted cache so hit rates and
    # bytes-served are this run's own
    resultcache.clear()
    cfg = dataclasses.replace(DEFAULT,
                              result_cache_enabled=result_cache)
    report = {"scale": scale, "mode": mode, "n_workers": n_workers,
              "hot_repeat": hot_repeat, "result_cache": result_cache,
              "resource_groups": {"hard_concurrency": hard_concurrency,
                                  "per_user_limit": per_user_limit},
              "levels": []}
    with DistributedQueryRunner.tpcds(scale=scale, n_workers=n_workers,
                                      resource_groups=groups,
                                      config=cfg) as dqr:
        oracle = _Oracle(dqr)          # also warms scan + kernel caches
        for conc in levels:
            before = plancache.stats()
            rc_before = resultcache.stats()
            if mode == "open":
                n_requests = max(requests_per_client * conc, conc)
                level = run_open_level(dqr, oracle, conc, rate_per_s,
                                       n_requests, hot=hot_repeat)
            else:
                level = run_closed_level(dqr, oracle, conc,
                                         requests_per_client,
                                         hot=hot_repeat)
            after = plancache.stats()
            rc_after = resultcache.stats()
            hits = after["hits"] - before["hits"]
            misses = after["misses"] - before["misses"]
            level["plan_cache"] = {
                "hits": hits, "misses": misses,
                "hit_rate": round(hits / (hits + misses), 3)
                if hits + misses else 0.0}
            rc_hits = rc_after["hits"] - rc_before["hits"]
            rc_misses = rc_after["misses"] - rc_before["misses"]
            level["result_cache"] = {
                "hits": rc_hits, "misses": rc_misses,
                "hit_rate": round(rc_hits / (rc_hits + rc_misses), 3)
                if rc_hits + rc_misses else 0.0,
                "bytes_served": rc_after["bytes_served"]
                - rc_before["bytes_served"]}
            report["levels"].append(level)
            if not quiet:
                print(json.dumps(level), flush=True)
        jit, cached, rcached = _second_run_jit_compiles(dqr, oracle)
        report["second_run_jit_compiles"] = jit
        report["second_run_plan_cached"] = cached
        report["second_run_result_cached"] = rcached
        # admission engagement: how many queries actually waited
        with urllib.request.urlopen(
                f"{dqr.coordinator.uri}/v1/query", timeout=10) as resp:
            qs = json.loads(resp.read())
        report["queries_total"] = len(qs)
        report["queries_queued"] = sum(
            1 for q in qs if q.get("queuedS", 0) > 0.0005)
    report["parity"] = all(lv["parity"] for lv in report["levels"])
    hits = sum(lv["plan_cache"]["hits"] for lv in report["levels"])
    misses = sum(lv["plan_cache"]["misses"] for lv in report["levels"])
    report["plan_cache_hit_rate"] = round(
        hits / (hits + misses), 3) if hits + misses else 0.0
    rc_hits = sum(lv["result_cache"]["hits"] for lv in report["levels"])
    rc_misses = sum(lv["result_cache"]["misses"]
                    for lv in report["levels"])
    report["result_cache_hit_rate"] = round(
        rc_hits / (rc_hits + rc_misses), 3) if rc_hits + rc_misses \
        else 0.0
    report["result_cache_bytes_served"] = sum(
        lv["result_cache"]["bytes_served"] for lv in report["levels"])
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=0.003)
    ap.add_argument("--levels", default="1,2,4,8",
                    help="comma-separated concurrency levels")
    ap.add_argument("--requests", type=int, default=4,
                    help="statements per client (closed) / per level "
                         "x concurrency (open)")
    ap.add_argument("--mode", choices=("closed", "open"),
                    default="closed")
    ap.add_argument("--rate", type=float, default=10.0,
                    help="open-loop arrival rate, statements/s")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--hot", action="store_true",
                    help="hot-repeat mix: repeat HOT_STATEMENTS "
                         "verbatim (the result-cache shape)")
    ap.add_argument("--result-cache", action="store_true",
                    help="enable the cross-query result cache on the "
                         "cluster")
    ap.add_argument("--open-loop", action="store_true",
                    help="overload sweep: bounded-pool dispatcher, "
                         "open-loop arrivals through saturation; "
                         "reports goodput/shed/latency per rate and "
                         "fails on any non-error-shaped rejection or "
                         "goodput collapse (with --check: a smaller "
                         "sweep with the same assertions)")
    ap.add_argument("--pool-size", type=int, default=4,
                    help="open-loop sweep: dispatcher_pool_size")
    ap.add_argument("--max-queued", type=int, default=8,
                    help="open-loop sweep: dispatcher_max_queued")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: tiny run, assert parity + plan-cache "
                         "hits + zero second-run compiles, then a "
                         "hot-repeat run asserting nonzero result-cache "
                         "hits with exact-rows parity")
    args = ap.parse_args(argv)

    if args.open_loop:
        # --check = the CI smoke: smaller pool + shorter levels, same
        # assertions — every reject past saturation must carry the
        # queue-full shape + retry hint (never a 500), and goodput must
        # hold at >= 80% of peak
        report = run_overload(
            scale=args.scale,
            pool_size=2 if args.check else args.pool_size,
            max_queued=4 if args.check else args.max_queued,
            duration_s=1.5 if args.check else 3.0,
            factors=(1.0, 2.0) if args.check else (0.5, 1.0, 2.0),
            n_workers=args.workers, quiet=args.check)
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1

    if args.check:
        report = run_qps(scale=0.003, levels=(1, 2),
                         requests_per_client=2, mode="closed",
                         n_workers=2, quiet=True)
        # hot-repeat tier: result cache ON, every statement repeated —
        # hits must happen and every row must still match the
        # single-threaded oracle exactly (a cached result is served
        # from spool pages; parity is per request)
        hot = run_qps(scale=0.003, levels=(2,),
                      requests_per_client=4, mode="closed",
                      n_workers=2, quiet=True, hot_repeat=True,
                      result_cache=True)
        checks = {
            "parity": report["parity"],
            "plan_cache_hits": report["plan_cache_hit_rate"] > 0.0,
            "zero_second_run_compiles":
                report["second_run_jit_compiles"] == 0,
            "second_run_plan_cached": report["second_run_plan_cached"],
            "hot_parity": hot["parity"],
            "result_cache_hits":
                hot["result_cache_hit_rate"] > 0.0,
            "result_cache_bytes_served":
                hot["result_cache_bytes_served"] > 0,
            "hot_second_run_result_cached":
                hot["second_run_result_cached"],
        }
        print(json.dumps({"check": checks, "report": report,
                          "hot_report": hot}))
        return 0 if all(checks.values()) else 1

    levels = tuple(int(x) for x in args.levels.split(",") if x.strip())
    report = run_qps(scale=args.scale, levels=levels,
                     requests_per_client=args.requests, mode=args.mode,
                     rate_per_s=args.rate, n_workers=args.workers,
                     hot_repeat=args.hot,
                     result_cache=args.result_cache)
    print(json.dumps(report, indent=2))
    return 0 if report["parity"] else 1


if __name__ == "__main__":
    sys.exit(main())
