"""The device-resident scan cache (presto_tpu/exec/scancache.py): an
immutable table stays on the device after its first scan.

- Q1, Q6 and Q3 at SF0.01 through coordinator and two workers, twice
  each: answers equal the plain numpy references the benchmark judges
  with, and the second execution generates, stages and builds nothing;
- the key is the data, not the statement: Q6 after Q1 hits;
- a table that can change is never kept; a budget too small keeps
  nothing; eviction frees bytes; two scans of one absent run keep one
  copy; a LIMIT that stops a scan early keeps nothing; a runner's exit
  leaves no entry;
- a run's stacked view (what a run launch of exec/fusion.py reads) is
  built once a column, counts in the budget and goes with its run.
"""

import dataclasses as dc
import gc
import json
import threading
import urllib.request

import numpy as np
import pytest

from presto_tpu.batch import Batch, Column
from presto_tpu.config import DEFAULT
from presto_tpu.exec import scancache
from presto_tpu.exec.scancache import SCAN_CACHE, ScanCache, ScanFill, ScanHit
from presto_tpu.localrunner import LocalQueryRunner
from presto_tpu.server.dqr import DistributedQueryRunner
from presto_tpu import types as T

from tpch_reference import (
    RTOL, STATEMENTS, compare, reference_answers, statement,
)

SCALE = 0.01
#: table scans by leaf tasks: two workers, each scanning its half of
#: every table the statement reads
LEAF_SCANS = {"q1": 2, "q6": 2, "q3": 6}


def _fetch(uri):
    with urllib.request.urlopen(uri, timeout=10) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def want():
    return reference_answers(SCALE)


@pytest.fixture(scope="module")
def served():
    """Q1, Q6, Q3, and the three again, on one fresh cluster: rows and
    the coordinator's detail of each execution, and what the node's
    surfaces said at the end."""
    out = {}
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=2) as dqr:
        uri = dqr.coordinator.uri
        client = dqr.new_client()
        for which in ("first", "second"):
            for name in STATEMENTS:
                _cols, rows = client.execute(statement(name))
                qid = client.last_query_id
                out[name, which] = {
                    "rows": [tuple(r) for r in rows],
                    "detail": _fetch(f"{uri}/v1/query/{qid}"),
                    "tree": _fetch(f"{uri}/v1/query/{qid}/spans")}
        _cols, rows = client.execute("explain analyze " + statement("q3"))
        out["explain"] = [r[0] for r in rows]
        out["connectors"] = [c for w in dqr.workers
                             for c in w.task_manager.registry.connectors()]
        out["kept"] = SCAN_CACHE.stats(out["connectors"])
        out["info"] = [_fetch(f"{w.uri}/v1/info")["memoryInfo"]
                       for w in dqr.workers]
        with urllib.request.urlopen(f"{dqr.workers[0].uri}/metrics",
                                    timeout=10) as resp:
            out["metrics"] = resp.read().decode()
    out["kept_after_exit"] = SCAN_CACHE.stats(out["connectors"])
    return out


def _stats(served, name, which):
    return served[name, which]["detail"]["queryStats"]


# -- served: answers, and what a repeated query no longer does --------------

@pytest.mark.parametrize("which", ["first", "second"])
@pytest.mark.parametrize("name", STATEMENTS)
def test_answers_equal_the_reference(served, want, name, which):
    got = served[name, which]
    compare(got["rows"], want[name], RTOL)
    assert not got["detail"].get("resultCached")


@pytest.mark.parametrize("name", STATEMENTS)
def test_second_execution_generates_stages_and_builds_nothing(served, name):
    qs = _stats(served, name, "second")
    assert qs["scan_cache_misses"] == 0
    assert qs["scan_cache_hits"] == LEAF_SCANS[name]
    assert qs["scan_cache_hit_bytes"] > 0
    assert not qs["host_ns"].get("generate")
    assert qs["xla_builds"] == 0


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_first_execution_of_an_absent_table_misses(served, name):
    qs = _stats(served, name, "first")
    assert qs["scan_cache_misses"] == LEAF_SCANS[name]
    assert qs["scan_cache_hits"] == 0
    assert qs["host_ns"]["generate"] > 0
    assert qs["host_ns"]["stage_h2d"] > 0


def test_q6_after_q1_hits_on_the_shared_columns(served):
    """Q6's four columns are among Q1's seven: its FIRST execution is
    handed the run Q1 filled."""
    qs = _stats(served, "q6", "first")
    assert (qs["scan_cache_hits"], qs["scan_cache_misses"]) == (2, 0)
    assert not qs["host_ns"].get("generate")
    assert 0 < qs["scan_cache_hit_bytes"] \
        < _stats(served, "q1", "second")["scan_cache_hit_bytes"]


@pytest.mark.parametrize("name", STATEMENTS)
def test_a_hit_dispatches_what_the_fill_dispatched(served, name):
    assert _stats(served, name, "first")["jit_dispatches"] \
        == _stats(served, name, "second")["jit_dispatches"] > 0


@pytest.mark.parametrize("name", STATEMENTS)
def test_task_stats_sum_to_the_query_account(served, name):
    detail = served[name, "second"]["detail"]
    tasks = [ts for lst in detail["taskStats"].values() for ts in lst]
    for field in ("scan_cache_hits", "scan_cache_misses",
                  "scan_cache_hit_bytes"):
        assert sum(ts[field] for ts in tasks) \
            == detail["queryStats"][field]


@pytest.mark.parametrize("which, state", [("first", "miss"),
                                          ("second", "hit")])
def test_span_tree_says_which_scans_hit(served, which, state):
    def walk(node):
        yield node
        for child in node.get("children", ()):
            yield from walk(child)

    ops = [op for n in walk(served["q3", which]["tree"])
           if n["kind"] == "task"
           for op in n["attributes"].get("operators", ())]
    assert [op["scanCache"] for op in ops
            if op["scanCache"]] == [state] * LEAF_SCANS["q3"]
    scans = "CachedScanOperator" if state == "hit" else "TableScanOperator"
    assert all(op["operator"].endswith(scans)
               for op in ops if op["scanCache"])


def test_explain_analyze_and_the_nodes_accounts(served):
    line = [ln for ln in served["explain"] if ln.startswith("scan cache:")]
    assert len(line) == 1 and line[0].startswith("scan cache: 6 hits (")
    assert line[0].endswith("0 misses")
    kept = served["kept"]
    # lineitem for Q1 (Q6 rides on it) and for Q3, orders, customer: a
    # task each on two workers, nothing held twice
    assert kept["entries"] == 8 and kept["resident_bytes"] > 0
    assert sum(m["scanCache"]["bytes"] for m in served["info"]) \
        == kept["resident_bytes"]
    assert [m["scanCache"]["entries"] for m in served["info"]] == [4, 4]
    metrics = served["metrics"]
    for family in ("presto_worker_scan_cache_resident_bytes",
                   "presto_worker_scan_cache_entries",
                   'presto_worker_scan_cache_total{kind="hits"}',
                   'presto_worker_scan_cache_total{kind="misses"}',
                   'presto_worker_scan_cache_total{kind="evictions"}',
                   "presto_worker_scan_cache_hit_bytes_total"):
        assert "\n" + family + " " in metrics


def test_a_runners_exit_leaves_no_entry(served):
    assert served["kept_after_exit"]["entries"] == 0
    assert served["kept_after_exit"]["resident_bytes"] == 0


def test_a_collected_runner_leaves_no_entry():
    runner = LocalQueryRunner.tpch(scale=0.001)
    runner.execute(statement("q6"))
    connector = runner.registry.get("tpch")
    assert SCAN_CACHE.stats([connector])["entries"] == 1

    class Owner:        # the token outlives the connector it named
        _scan_cache_owner = connector._scan_cache_owner

    del runner, connector
    gc.collect()
    assert SCAN_CACHE.stats([Owner])["entries"] == 0


# -- one process: dispatch counts, and what is never kept --------------------

def _account(runner):
    ts = runner._last_task.task_stats()
    return ts.scan_cache_hits, ts.scan_cache_misses


@pytest.fixture()
def local():
    return LocalQueryRunner.tpch(scale=SCALE)


@pytest.mark.parametrize("name", STATEMENTS)
def test_jit_counters_equal_on_first_and_second_execution(local, name):
    first = local.execute(statement(name)).rows
    counters = local._last_task.jit_counters()
    assert _account(local)[0] == 0
    second = local.execute(statement(name)).rows
    assert _account(local)[1] == 0 and _account(local)[0] > 0
    assert local._last_task.jit_counters()["dispatches"] \
        == counters["dispatches"] > 0
    assert local._last_task.jit_counters()["compiles"] == 0
    assert second == first


def test_a_table_that_can_change_is_never_kept(local):
    before = SCAN_CACHE.stats()
    local.execute("create table memory.t (a bigint, b double)")
    local.execute("insert into memory.t values (1, 1.5), (2, 2.5)")
    query = "select a, b * 2 from memory.t where a > 0 order by a"
    assert local.execute(query).rows == [(1, 3.0), (2, 5.0)]
    assert _account(local) == (0, 0)
    local.execute("insert into memory.t values (3, 3.5)")
    assert local.execute(query).rows == [(1, 3.0), (2, 5.0), (3, 7.0)]
    assert _account(local) == (0, 0)
    after = SCAN_CACHE.stats()
    assert (after["hits"], after["misses"], after["entries"]) \
        == (before["hits"], before["misses"], before["entries"])


def test_a_changing_tables_pipeline_runs_as_it_was_lowered(local):
    """No copy, no wrapper, no fill: the runner executes the very
    pipeline the planner lowered (the parent's operator chain)."""
    from presto_tpu.exec.runner import _through_scan_cache
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.physical import PhysicalPlanner
    from presto_tpu.sql.planner import Planner

    def pipelines(sql):
        plan = optimize(Planner(local.metadata).plan(parse_statement(sql)),
                        local.metadata, local.config)
        return PhysicalPlanner(local.registry,
                               local.config).plan(plan).pipelines

    local.execute("create table memory.u (a bigint)")
    local.execute("insert into memory.u values (1), (2)")
    for p in pipelines("select a + 1 from memory.u where a > 0"):
        assert _through_scan_cache(p) == (p, None)
    # and the immutable table's pipeline is rewritten, not mutated
    (p,) = pipelines(statement("q6"))
    factories = list(p.factories)
    rewritten, fill = _through_scan_cache(p)
    assert rewritten is not p and isinstance(fill, ScanFill)
    assert p.factories == factories
    fill.close(False)


def test_a_budget_smaller_than_one_run_keeps_nothing(local, monkeypatch):
    monkeypatch.setattr(SCAN_CACHE, "budget_bytes", 4096)
    connector = local.registry.get("tpch")
    first = local.execute(statement("q1")).rows
    assert _account(local) == (0, 1)
    second = local.execute(statement("q1")).rows
    assert _account(local) == (0, 1)
    assert second == first
    assert SCAN_CACHE.stats([connector])["entries"] == 0


def test_eviction_frees_bytes_and_a_rescan_refills(local, monkeypatch):
    connector = local.registry.get("tpch")
    orders = ("select count(*), sum(o_totalprice) from orders "
              "where o_orderdate < date '1995-01-01'")
    local.execute(statement("q6"))
    lineitem_bytes = SCAN_CACHE.stats([connector])["resident_bytes"]
    assert lineitem_bytes > 0
    # room for the larger run and not for both
    monkeypatch.setattr(SCAN_CACHE, "budget_bytes", lineitem_bytes + 1024)
    evictions = SCAN_CACHE.stats()["evictions"]
    local.execute(orders)
    kept = SCAN_CACHE.stats([connector])
    assert SCAN_CACHE.stats()["evictions"] == evictions + 1
    assert kept["entries"] == 1
    assert 1024 < kept["resident_bytes"] < lineitem_bytes
    local.execute(orders)
    assert _account(local) == (1, 0)
    local.execute(statement("q6"))            # evicted: scanned again
    assert _account(local) == (0, 1)
    kept = SCAN_CACHE.stats([connector])
    assert (kept["entries"], kept["resident_bytes"]) == (1, lineitem_bytes)
    local.execute(statement("q6"))
    assert _account(local) == (1, 0)


def test_a_limit_that_stops_a_scan_early_keeps_nothing():
    # pages of 1024 rows: the feed drivers fill the local exchange and
    # are still scanning when the LIMIT is met
    cfg = dc.replace(DEFAULT, scan_batch_rows=1024)
    runner = LocalQueryRunner.tpch(scale=SCALE, config=cfg)
    connector = runner.registry.get("tpch")
    limited = ("select l_orderkey, l_quantity from lineitem "
               "where l_quantity < 3 limit 5")
    assert len(runner.execute(limited).rows) == 5
    assert _account(runner) == (0, 1)
    assert SCAN_CACHE.stats([connector])["entries"] == 0
    whole = ("select count(*) from lineitem where l_quantity < 3")
    counted = runner.execute(whole).rows
    assert _account(runner) == (0, 1)
    assert SCAN_CACHE.stats([connector])["entries"] == 1
    assert runner.execute(whole).rows == counted
    assert _account(runner) == (1, 0)


def test_two_queries_started_together_leave_one_kept_run(want):
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=2) as dqr:
        rows, errors = {}, []

        def run(who):
            try:
                _cols, got = dqr.new_client(user=f"u{who}").execute(
                    statement("q1"))
                rows[who] = [tuple(r) for r in got]
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors and not any(t.is_alive() for t in threads)
        for who in (0, 1):
            compare(rows[who], want["q1"], RTOL)
        for w in dqr.workers:       # a task each: its half of lineitem
            assert SCAN_CACHE.stats(
                w.task_manager.registry.connectors())["entries"] == 1


# -- the cache itself -----------------------------------------------------------

class _Connector:
    immutable_data = True


def _batch(rows, names):
    return Batch(tuple(Column(T.BIGINT, np.arange(rows, dtype=np.int64))
                       for _ in names), rows)


def _fill(cache, connector, key, names, rows=(8, 4), ok=True,
          drained=True):
    fill = cache.open(connector, key, names)
    assert isinstance(fill, ScanFill)
    fill.scan_opened()
    for n in rows:
        fill.add(_batch(n, names))
    fill.scan_closed(sum(rows), drained)
    fill.close(ok)
    return fill


def test_open_twice_before_close_keeps_one_copy():
    cache, conn = ScanCache(1 << 20), _Connector()
    first = cache.open(conn, "k", ["a"])
    second = cache.open(conn, "k", ["a"])
    assert first.keeping and not second.keeping
    for fill in (second, first):
        fill.scan_opened()
        batch = _batch(8, ["a"])
        assert (fill.stage(batch) is batch) == (fill is second)
        fill.scan_closed(8, True)
        fill.close(True)
    assert cache.stats()["entries"] == 1
    assert isinstance(cache.open(conn, "k", ["a"]), ScanHit)
    # the slot was released: a new key's fill keeps again
    assert cache.open(conn, "k2", ["a"]).keeping


@pytest.mark.parametrize("ok, drained, rows_scanned, opened, kept", [
    (True, True, 12, 1, 1),      # whole
    (False, True, 12, 1, 0),     # the pipeline failed
    (True, False, 12, 1, 0),     # a scan operator stopped before its end
    (True, True, 20, 1, 0),      # rows scanned that were never staged
    (True, True, 12, 2, 0),      # a feed driver's scan never closed
])
def test_only_a_whole_scan_is_stored(ok, drained, rows_scanned, opened,
                                     kept):
    cache, conn = ScanCache(1 << 20), _Connector()
    fill = cache.open(conn, "k", ["a"])
    for _ in range(opened):
        fill.scan_opened()
    for n in (8, 4):
        fill.add(_batch(n, ["a"]))
    fill.scan_closed(rows_scanned, drained)
    fill.close(ok)
    assert cache.stats()["entries"] == kept


def test_columns_subset_hits_and_a_wider_run_replaces():
    cache, conn = ScanCache(1 << 20), _Connector()
    _fill(cache, conn, "k", ["a", "b"])
    hit = cache.open(conn, "k", ["b"])
    assert isinstance(hit, ScanHit)
    assert [b.num_columns for b in hit.batches] == [1, 1]
    assert hit.nbytes == 12 * 8
    assert isinstance(cache.open(conn, "other", ["b"]), ScanFill)
    assert isinstance(cache.open(_Connector(), "k", ["b"]), ScanFill)
    _fill(cache, conn, "k", ["a", "b", "c"])       # needs a column more
    stats = cache.stats()
    assert (stats["entries"], stats["resident_bytes"]) == (1, 12 * 8 * 3)


def test_least_recently_scanned_run_is_evicted_whole():
    run_bytes = 12 * 8
    cache, conn = ScanCache(2 * run_bytes), _Connector()
    for key in ("k1", "k2"):
        _fill(cache, conn, key, ["a"])
    assert isinstance(cache.open(conn, "k1", ["a"]), ScanHit)   # k2 is older
    _fill(cache, conn, "k3", ["a"])
    stats = cache.stats()
    assert (stats["entries"], stats["evictions"]) == (2, 1)
    assert isinstance(cache.open(conn, "k1", ["a"]), ScanHit)
    assert isinstance(cache.open(conn, "k2", ["a"]), ScanFill)


def test_a_run_that_outgrows_the_budget_is_dropped_while_it_fills():
    cache, conn = ScanCache(100), _Connector()
    fill = cache.open(conn, "k", ["a"])
    fill.scan_opened()
    fill.add(_batch(8, ["a"]))
    assert fill.keeping
    fill.add(_batch(8, ["a"]))          # 128 bytes now
    assert not fill.keeping
    fill.scan_closed(16, True)
    fill.close(True)
    assert cache.stats()["entries"] == 0


def test_drop_owner_frees_only_that_connectors_runs():
    cache, one, other = ScanCache(1 << 20), _Connector(), _Connector()
    _fill(cache, one, "k", ["a"])
    _fill(cache, other, "k", ["a"])
    cache.drop_owner(one)
    assert cache.stats([one])["entries"] == 0
    assert cache.stats([other])["entries"] == 1


def test_the_budget_is_a_fixed_figure_where_the_backend_reports_none():
    assert ScanCache().budget() == scancache.FALLBACK_BUDGET_BYTES


# -- the stacked view of a kept run ------------------------------------------

RUN_ROWS = (8, 4, 8)            # a short batch in the middle
RUN_BYTES = sum(RUN_ROWS) * 8   # a BIGINT column of the run
STACK_BYTES = 8 * 8 * 8         # a column stacked: 3 batches padded to 8
NUM_ROWS_BYTES = 8 * 4          # int32 a padded batch


def _stacked(cache, conn, key, names):
    hit = cache.open(conn, key, names)
    assert isinstance(hit, ScanHit)
    return hit.stacked()


def test_a_runs_stacked_view_is_built_once_a_column():
    cache, conn = ScanCache(1 << 20), _Connector()
    _fill(cache, conn, "k", ["a", "b"], rows=RUN_ROWS)
    cols, num_rows, launches = _stacked(cache, conn, "k", ["b"])
    ((stacked, valid),) = cols
    assert launches == 1 and valid is None
    assert stacked.shape == (8, 8)
    np.testing.assert_array_equal(np.asarray(num_rows),
                                  [8, 4, 8, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(stacked[1]),
                                  [0, 1, 2, 3, 0, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(stacked[2]), np.arange(8))
    assert not np.asarray(stacked[3:]).any()
    resident = 2 * RUN_BYTES + STACK_BYTES + NUM_ROWS_BYTES
    assert cache.stats()["resident_bytes"] == resident
    # asked again, alone or beside another column: only what is missing
    # is stacked, and a column's stack is the one kept
    assert _stacked(cache, conn, "k", ["b"])[2] == 0
    both, again, launches = _stacked(cache, conn, "k", ["a", "b"])
    assert launches == 1 and both[1][0] is stacked and again is num_rows
    assert cache.stats()["resident_bytes"] == resident + STACK_BYTES


@pytest.mark.parametrize("budget, stacked, entries", [
    (1 << 20, True, 2),                     # room for both runs and it
    (RUN_BYTES + STACK_BYTES + NUM_ROWS_BYTES, True, 1),   # evicts "old"
    (RUN_BYTES + 100, False, 1)])           # no room: none, no eviction
def test_a_stacked_view_counts_in_the_budget_and_goes_with_its_run(
        budget, stacked, entries):
    cache, conn = ScanCache(budget), _Connector()
    for key in ("old", "k"):
        _fill(cache, conn, key, ["a"], rows=RUN_ROWS)
    evictions = cache.stats()["evictions"]
    view = _stacked(cache, conn, "k", ["a"])
    assert (view is not None) == stacked
    stats = cache.stats()
    assert stats["entries"] == entries
    assert stats["evictions"] - evictions == (stacked and entries == 1)
    assert stats["resident_bytes"] == (
        RUN_BYTES * entries + stacked * (STACK_BYTES + NUM_ROWS_BYTES))
    assert isinstance(cache.open(conn, "k", ["a"]), ScanHit)
    cache.drop_owner(conn)
    assert cache.stats()["resident_bytes"] == 0
