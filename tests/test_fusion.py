"""Pipeline-fusion tier tests (exec/fusion.py).

Covers: the operator tier's answers against references that share no
engine code (the benchmark's numpy Q1/Q6/Q3, ``collections`` group-bys of
hand-built inputs), the dispatch counts the lowering produces, which
group-by tier an aggregation picks from its input, segment
formation/breaking rules, the precomputed partition-id path, dictionary
cache tokens, and the kernel-cache counters/capacity knob.
"""

import collections
import dataclasses as dc

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.batch import Dictionary, batch_from_pylist
from presto_tpu.config import EngineConfig
from presto_tpu.exec.driver import Pipeline
from presto_tpu.exec.fusion import (
    DFStage, FPStage, FusedSegmentOperatorFactory, fuse_chain,
)
from presto_tpu.exec.operators import (
    FilterProjectOperatorFactory, OutputCollectorFactory,
    TableScanOperatorFactory, ValuesOperatorFactory,
)
from presto_tpu.exec.runner import execute_pipelines
from presto_tpu.expr import build as B
from presto_tpu.localrunner import LocalQueryRunner

import tpch_reference
from tpch_queries import QUERIES


def _cfg(**kw) -> EngineConfig:
    return dc.replace(EngineConfig(), **kw)


@pytest.fixture(scope="module")
def runner_on():
    return LocalQueryRunner.tpch(scale=0.01)


@pytest.fixture(scope="module")
def want():
    return tpch_reference.reference_answers(0.01)


def _norm(rows):
    out = []
    for r in rows:
        out.append(tuple(
            round(v, max(0, 10 - int(np.log10(abs(v))) if v else 10))
            if isinstance(v, float) else v for v in r))
    return sorted(out, key=repr)


def assert_rows_close(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
        assert len(ra) == len(rb)
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                assert va == pytest.approx(vb, rel=1e-6), (ra, rb)
            else:
                assert va == vb, (ra, rb)


# ---------------------------------------------------------------------------
# hand-built chains
# ---------------------------------------------------------------------------

def _three_stage_chain():
    """values -> filter(a > 2) -> project(a+b, b) -> filter(c < 40) over
    columns a,b — a 3-deep fusable run."""
    batch = batch_from_pylist(
        [T.BIGINT, T.BIGINT],
        [(i, 10 * i) for i in range(8)] + [(None, 3)])
    t2 = (T.BIGINT, T.BIGINT)
    f1 = FilterProjectOperatorFactory(
        B.comparison(">", B.ref(0, T.BIGINT), B.const(2, T.BIGINT)),
        [B.ref(0, T.BIGINT), B.ref(1, T.BIGINT)], list(t2))
    f2 = FilterProjectOperatorFactory(
        None,
        [B.call("add", B.ref(0, T.BIGINT), B.ref(1, T.BIGINT)),
         B.ref(1, T.BIGINT)], list(t2))
    f3 = FilterProjectOperatorFactory(
        B.comparison("<", B.ref(0, T.BIGINT), B.const(40, T.BIGINT)),
        [B.ref(0, T.BIGINT), B.ref(1, T.BIGINT)], list(t2))
    return batch, [f1, f2, f3]


def test_fused_chain_parity():
    batch, fps = _three_stage_chain()
    results = {}
    for fused in (False, True):
        collector = OutputCollectorFactory()
        chain = [ValuesOperatorFactory([batch.to_device()])] + fps
        if fused:
            chain = fuse_chain(chain, _cfg())
            kinds = [type(f).__name__ for f in chain]
            assert kinds == ["ValuesOperatorFactory",
                             "FusedSegmentOperatorFactory"], kinds
        chain = chain + [collector]
        execute_pipelines([Pipeline(chain, name="t")], _cfg())
        results[fused] = sorted(collector.rows())
    assert results[True] == results[False]
    # i=3 survives a>2 and (a+b)=33 < 40; i>=4 give a+b >= 44
    assert results[True] == [(33, 30)]


def test_fuse_chain_rules():
    """Runs < 2 stay unfused unless scan- or partition-adjacent; a
    non-fusable operator breaks the segment."""
    batch, (f1, f2, f3) = _three_stage_chain()
    from presto_tpu.exec.sortop import OrderByOperatorFactory, SortSpec

    sort = OrderByOperatorFactory([SortSpec(0, False, False)])
    chain = fuse_chain([ValuesOperatorFactory([batch]), f1, sort, f2, f3],
                       _cfg())
    kinds = [type(f).__name__ for f in chain]
    # single FP before sort stays; the pair after it fuses
    assert kinds == ["ValuesOperatorFactory", "FilterProjectOperatorFactory",
                     "OrderByOperatorFactory",
                     "FusedSegmentOperatorFactory"], kinds


def test_scan_adjacent_single_stage_fuses():
    """A lone FilterProject directly after a device-staging scan fuses
    (scan coalescing: the ScanFilterAndProjectOperator role) and the scan
    flips to host hand-off."""
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=0.005)
    scan = TableScanOperatorFactory(conn, ["l_quantity"], table="lineitem")
    fp = FilterProjectOperatorFactory(
        None, [B.ref(0, T.DOUBLE)], [T.DOUBLE])
    chain = fuse_chain([scan, fp], _cfg())
    assert isinstance(chain[1], FusedSegmentOperatorFactory)
    assert chain[0].to_device is False
    assert chain[1].coalesce_rows == EngineConfig().scan_batch_rows


def test_q3_forms_multi_stage_segments(runner_on):
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.physical import PhysicalPlanner
    from presto_tpu.sql.planner import Planner

    plan = optimize(
        Planner(runner_on.metadata).plan(parse_statement(QUERIES[3])),
        runner_on.metadata, runner_on.config)
    phys = PhysicalPlanner(runner_on.registry, runner_on.config).plan(plan)
    segments = [f for p in phys.pipelines for f in p.factories
                if isinstance(f, FusedSegmentOperatorFactory)]
    assert segments
    # the probe pipeline carries a dynamic filter + filter/projects in
    # one segment, and the post-join project stack fuses too
    assert any(len(s.stages) >= 2 and isinstance(s.stages[0], DFStage)
               for s in segments)
    assert any(sum(isinstance(st, FPStage) for st in s.stages) >= 2
               for s in segments)


# ---------------------------------------------------------------------------
# SQL-level parity + the dispatch-counter regression pin
# ---------------------------------------------------------------------------

def _run_statement(runner, want, name):
    """Execute benchmark statement ``name``, hold its answer to the
    plain reference, and return the task's jit counters."""
    res = runner.execute(tpch_reference.statement(name))
    tpch_reference.compare(tpch_reference.as_served(res.rows), want[name],
                           tpch_reference.RTOL)
    return runner._last_task.jit_counters()


@pytest.mark.parametrize("name", tpch_reference.STATEMENTS)
def test_operator_tier_matches_plain_reference(runner_on, want, name):
    """The default lowering (fused segments, absorbed probes, in-segment
    pre-reduce) against the benchmark's numpy references, DOUBLE to 1e-6:
    tier-1's check that fusion computes the right answer."""
    _run_statement(runner_on, want, name)


def test_q1_dispatch_reduction(runner_on, want):
    """Q1 at SF0.01 is ONE jit launch over the scan (filter, projections
    and the per-batch aggregation in one segment over the coalesced
    scan) and one for each finish (the merge aggregation's
    ``groupby_direct``, ORDER BY's ``order_by``), with the reference's
    answer."""
    assert _run_statement(runner_on, want, "q1")["dispatches"] == 3


def test_q6_q3_parity_and_strictly_fewer(runner_on, want):
    """Q6 is one launch and its global finish, Q3 four (a segment a
    table, those over orders and lineitem each with a join probe
    absorbed, and the projection after the aggregation) and the finishes
    of its GROUP BY and its ORDER BY: a probe or an aggregation that
    left its segment shows here as a larger count."""
    assert _run_statement(runner_on, want, "q6")["dispatches"] == 2
    assert _run_statement(runner_on, want, "q3")["dispatches"] == 6


def test_explain_analyze_reports_jit_counters(runner_on):
    res = runner_on.execute(
        "explain analyze select count(*) from lineitem where l_quantity > 30")
    text = "\n".join(r[0] for r in res.rows)
    assert "jit disp" in text and "jit dispatches:" in text
    assert "kernel caches" in text


# ---------------------------------------------------------------------------
# in-segment partial-aggregation pre-reduce (Fusion II)
# ---------------------------------------------------------------------------

def _agg_chain(aggs, group_channels=(0,)):
    """values -> filter(b < 90) -> HashAgg over a dict key with nulls in
    both the key and the aggregated columns."""
    from presto_tpu.exec.aggregation import HashAggregationOperatorFactory

    rows = []
    for i in range(40):
        key = None if i % 13 == 0 else f"k{i % 3}"
        b = None if i % 7 == 0 else i
        d = None if i % 11 == 0 else float(i) * 1.5
        rows.append((key, b, d))
    batch = batch_from_pylist([T.VARCHAR, T.BIGINT, T.DOUBLE], rows)
    types = [batch.columns[0].type, T.BIGINT, T.DOUBLE]
    fp = FilterProjectOperatorFactory(
        B.comparison("<", B.ref(1, T.BIGINT), B.const(90, T.BIGINT)),
        [B.ref(0, types[0]), B.ref(1, T.BIGINT), B.ref(2, T.DOUBLE)],
        types)
    agg = HashAggregationOperatorFactory(list(group_channels), aggs, types)
    return batch, [fp, agg], [r for r in rows
                              if r[1] is not None and r[1] < 90]


def _grouped(rows, key_channel, aggs):
    """A ``collections`` GROUP BY of python rows: sorted
    ``(key, agg...)`` tuples with SQL's null rules (a null key is a
    group; aggregates skip null inputs; count(*) has ``channel`` None)."""
    groups = collections.defaultdict(list)
    for r in rows:
        groups[r[key_channel]].append(r)
    fold = {"sum": sum, "min": min, "max": max, "count": len}
    out = []
    for key, members in groups.items():
        cells = [key]
        for a in aggs:
            vals = [r if a.channel is None else r[a.channel]
                    for r in members
                    if a.channel is None or r[a.channel] is not None]
            cells.append(fold[a.prim](vals)
                         if vals or a.prim == "count" else None)
        out.append(tuple(cells))
    return sorted(out, key=repr)


def _run_chain(batch, factories, cfg):
    collector = OutputCollectorFactory()
    chain = fuse_chain(
        [ValuesOperatorFactory([batch.to_device()])] + list(factories),
        cfg)
    execute_pipelines([Pipeline(chain + [collector], name="t")], cfg)
    return chain, sorted(collector.rows(), key=repr)


def test_prereduce_hash_chain_parity():
    """Hand-built chain: the pre-reduced segment + merge aggregation
    against a ``collections`` group-by of the input rows — nullable dict
    key (null group included), sum/count/count(*)/min/max with nulls."""
    from presto_tpu.exec.aggregation import AggChannel

    aggs = [AggChannel("sum", 1, T.BIGINT),
            AggChannel("count", 1, T.BIGINT),
            AggChannel("count", None, T.BIGINT),
            AggChannel("min", 2, T.DOUBLE),
            AggChannel("max", 2, T.DOUBLE)]
    batch, factories, live = _agg_chain(aggs)
    chain, rows = _run_chain(batch, factories, _cfg())
    assert rows == _grouped(live, 0, aggs)
    segments = [f for f in chain
                if isinstance(f, FusedSegmentOperatorFactory)]
    assert segments and segments[0].agg_spec is not None


def test_prereduce_sort_path_fallback():
    """A dictionary key whose domain exceeds direct_groupby_max_domain
    still pre-reduces (sort path at batch capacity) with exact results."""
    from presto_tpu.exec.aggregation import AggChannel

    aggs = [AggChannel("sum", 1, T.BIGINT),
            AggChannel("count", None, T.BIGINT)]
    batch, factories, live = _agg_chain(aggs)
    chain, rows = _run_chain(batch, factories,
                             _cfg(direct_groupby_max_domain=1))
    assert chain[1].agg_spec is not None
    assert rows == _grouped(live, 0, aggs)


# -- bounded pre-reduce: partial states held on the device ------------------

HELD_BATCHES = 24


def _held_chain(case):
    """values (24 device batches) -> filter(x < 40) -> aggregation, and
    the config that puts the fused segment in ``case``'s form: a
    nullable dictionary key on the direct path, no key, the same key
    with its domain over ``direct_groupby_max_domain`` (sort path), and
    a key distinct in every row of a 2,048-row batch on the sort path
    (flips to raw emission after the first batch)."""
    from presto_tpu.batch import Batch, Column
    from presto_tpu.exec.aggregation import (
        AggChannel, GlobalAggregationOperatorFactory,
        HashAggregationOperatorFactory,
    )

    raw = case == "raw-emit"
    rows = 2048 if raw else 96
    n_keys = rows if raw else 5
    d = Dictionary([f"k{i}" for i in range(n_keys)])
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(HELD_BATCHES):
        codes = (np.arange(rows) if raw
                 else rng.integers(0, n_keys, rows)).astype(np.int32)
        batches.append(Batch((
            Column(T.VARCHAR, codes,
                   None if raw else rng.random(rows) > 0.1, d),
            # every row of the raw case passes the filter: a group a row
            Column(T.BIGINT, rng.integers(-50, 40 if raw else 50, rows),
                   None if raw else rng.random(rows) > 0.2),
            Column(T.DOUBLE, rng.random(rows) * 100)), rows).to_device())
    types = [batches[0].columns[0].type, T.BIGINT, T.DOUBLE]
    fp = FilterProjectOperatorFactory(
        B.comparison("<", B.ref(1, T.BIGINT), B.const(40, T.BIGINT)),
        [B.ref(0, types[0]), B.ref(1, T.BIGINT), B.ref(2, T.DOUBLE)],
        types)
    aggs = [AggChannel("sum", 1, T.BIGINT),
            AggChannel("count", 1, T.BIGINT),
            AggChannel("count", None, T.BIGINT),
            AggChannel("min", 2, T.DOUBLE),
            AggChannel("max", 2, T.DOUBLE),
            AggChannel("sum", 2, T.DOUBLE)]
    if case == "global":
        agg = GlobalAggregationOperatorFactory(aggs, types)
    else:
        agg = HashAggregationOperatorFactory([0], aggs, types)
    cfg = _cfg(**({} if case in ("grouped-direct", "global")
                  else {"direct_groupby_max_domain": 1}))
    return [ValuesOperatorFactory(batches), fp, agg], cfg


def _run_held(factories, cfg, fuse):
    collector = OutputCollectorFactory()
    chain = fuse_chain(list(factories), cfg) if fuse else list(factories)
    task = execute_pipelines([Pipeline(chain + [collector], name="t")],
                             cfg)
    segment = [s for s in task.operator_stats
               if "FusedSegment" in s.operator]
    return collector.rows(), (segment[0] if fuse else None)


@pytest.mark.parametrize("case, held, flush_bytes", [
    ("grouped-direct", True, 1000), ("global", True, 150),
    ("sort-path", False, 0), ("raw-emit", False, 0)])
def test_bounded_prereduce_holds_partials_on_the_device(case, held,
                                                        flush_bytes):
    """A segment whose pre-reduce is bounded at trace time (direct
    domain, or no key) keeps every dispatched batch's partial states on
    the device and hands ONE batch to its consumer at finish; the sort
    path and raw emission hand over a batch a dispatch, as ever.  The
    answer is the unfused chain's either way, and also when a tiny
    ``partial_agg_max_bytes`` forces the held partials out early."""
    factories, cfg = _held_chain(case)
    want, _ = _run_held(factories, cfg, fuse=False)
    got, seg = _run_held(factories, cfg, fuse=True)
    assert_rows_close(got, want)
    assert len(want) == {"global": 1, "raw-emit": 2048}.get(case, 6)
    if not held:
        assert seg.output_batches == seg.jit_dispatches == HELD_BATCHES
        assert (seg.prereduce_batches_held, seg.prereduce_flushes) == (0, 0)
        # raw emission really engaged: one batch pre-reduced, no more
        assert (seg.prereduce_rows == 2048) == (case == "raw-emit")
        return
    assert seg.output_batches == 1
    assert (seg.prereduce_batches_held, seg.prereduce_flushes) == \
        (HELD_BATCHES, 1)
    # the grouped form adds one merge launch a flush, the global none
    assert seg.jit_dispatches == HELD_BATCHES + (case != "global")
    early, seg = _run_held(
        factories, dc.replace(cfg, partial_agg_max_bytes=flush_bytes),
        fuse=True)
    assert_rows_close(early, want)
    assert seg.prereduce_batches_held == HELD_BATCHES
    assert 1 < seg.prereduce_flushes < HELD_BATCHES
    assert seg.output_batches == seg.prereduce_flushes


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_held_partials_through_a_coalescing_scan(want, name):
    """Q1 (direct domain) and Q6 (global) over a scan cut into 30
    batches: on the miss path the segment that stages the scan holds all
    30 and flushes once; on the scan cache's hit path it pre-reduces the
    kept run in one run launch and holds nothing; the reference's answer
    either way.  Beside the segment's launches: Q1's merge program on the
    miss path, the stacking of the run's columns at the first hit, and
    one launch for each operator's finish (two in Q1, one in Q6)."""
    r = LocalQueryRunner.tpch(scale=0.01, config=_cfg(
        scan_batch_rows=2048, task_concurrency=1))
    finish = 2 if name == "q1" else 1
    jc = _run_statement(r, want, name)
    ts = r._last_task.task_stats()
    assert (ts.prereduce_batches_held, ts.prereduce_flushes) == (30, 1)
    assert (ts.run_launches, ts.run_batches) == (0, 0)
    assert jc["dispatches"] == 30 + (name == "q1") + finish
    jc = _run_statement(r, want, name)
    ts = r._last_task.task_stats()
    assert (ts.prereduce_batches_held, ts.prereduce_flushes) == (0, 0)
    assert (ts.run_launches, ts.run_batches) == (1, 30)
    assert jc["dispatches"] == 2 + finish
    text = "\n".join(row[0] for row in r.execute(
        "explain analyze " + tpch_reference.statement(name)).rows)
    assert ("prereduce held: 0 batches kept on the device, 0 flushes; "
            "run launches: 1 over 30 batches") in text
    assert r._last_task.jit_counters()["dispatches"] == 1 + finish


@pytest.mark.parametrize("name, max_bytes, engages", [
    ("q1", None, True), ("q6", None, True),
    # partials over the limit: a launch a flush of the miss path
    ("q1", 2000, True), ("q6", 100, True),
    # an absorbed join probe; a table with no immutable_data (no hit)
    ("q3", None, False), ("q1.memory", None, False)])
def test_a_warm_scan_pre_reduces_its_kept_run_in_run_launches(
        want, name, max_bytes, engages):
    """The second execution of a statement whose leaf segment pre-reduces
    a cached scan into a bounded partial covers each task's kept run (30
    batches of 2,048 rows, padded to 32) in run launches, as many as the
    first execution's flushes of held partials, and answers what the
    first execution answered, bit for bit, and the reference.  A segment
    with an absorbed probe, and a scan of a table that can change, go
    batch by batch as ever."""
    cfg = _cfg(scan_batch_rows=2048, task_concurrency=1)
    if max_bytes:
        cfg = dc.replace(cfg, partial_agg_max_bytes=max_bytes)
    r = LocalQueryRunner.tpch(scale=0.01, config=cfg)
    statement, _, table = name.partition(".")
    sql = tpch_reference.statement(statement)
    if table:
        r.execute("create table memory.lineitem as select * from lineitem")
        sql = sql.replace("from lineitem", "from memory.lineitem")
    first = r.execute(sql).rows
    miss = r._last_task.task_stats()
    second = r.execute(sql).rows
    hit = r._last_task.task_stats()
    tpch_reference.compare(tpch_reference.as_served(second), want[statement],
                           tpch_reference.RTOL)
    assert second == first
    assert hit.scan_cache_hits == (0 if table else 3 if statement == "q3"
                                   else 1)
    if not engages:
        assert (hit.run_launches, hit.run_batches) == (0, 0)
        assert hit.jit_dispatches == miss.jit_dispatches
        return
    assert miss.prereduce_batches_held == 30 and miss.run_launches == 0
    assert 1 <= miss.prereduce_flushes == hit.run_launches
    assert (max_bytes is None) == (hit.run_launches == 1)
    assert (hit.run_batches, hit.prereduce_batches_held) == (30, 0)


def _two_bindings_chain():
    """values (three batches under one dictionary, two under a grown
    copy of it) -> project -> GROUP BY the dictionary key."""
    from presto_tpu.batch import Batch, Column
    from presto_tpu.exec.aggregation import (
        AggChannel, HashAggregationOperatorFactory,
    )

    small = Dictionary(["a", "b", "c"])
    grown = Dictionary(["a", "b", "c", "d", "e"])
    rng = np.random.default_rng(11)

    def mk(d):
        codes = rng.integers(0, len(d), 64).astype(np.int32)
        return Batch((Column(T.VARCHAR, codes, None, d),
                      Column(T.BIGINT, rng.integers(0, 9, 64))),
                     64).to_device()

    batches = [mk(small), mk(small), mk(small), mk(grown), mk(grown)]
    types = [batches[0].columns[0].type, T.BIGINT]
    fp = FilterProjectOperatorFactory(
        None, [B.ref(0, types[0]), B.ref(1, T.BIGINT)], types)
    agg = HashAggregationOperatorFactory(
        [0], [AggChannel("sum", 1, T.BIGINT),
              AggChannel("count", None, T.BIGINT)], types)
    return [ValuesOperatorFactory(batches), fp, agg], _cfg()


def test_held_partials_of_two_key_bindings_do_not_mix():
    """Partials merge only where their key codes mean the same: when a
    batch arrives under a grown dictionary (another domain, another
    program), what is held is merged out first and the answer stays the
    unfused chain's."""
    factories, _cfg_ = _two_bindings_chain()
    want, _ = _run_held(factories, _cfg(), fuse=False)
    got, seg = _run_held(factories, _cfg(), fuse=True)
    assert sorted(got) == sorted(want) and len(got) == 5
    assert (seg.prereduce_batches_held, seg.prereduce_flushes,
            seg.output_batches) == (5, 2, 2)


def _run_kept(factories, cfg):
    """``factories``' batches as a kept run of the scan cache, handed to
    the fused segment by a hit as ``exec/runner.py`` hands it: the
    rows, the segment's stats and the hit's stacking launches."""
    from presto_tpu.exec.operators import CachedScanOperatorFactory
    from presto_tpu.exec.scancache import ScanCache

    values, segment, *rest = fuse_chain(list(factories), cfg)
    batches = values.batches
    names = [f"c{i}" for i in range(batches[0].num_columns)]

    class Connector:
        immutable_data = True

    cache, conn = ScanCache(1 << 30), Connector()
    fill = cache.open(conn, "k", names)
    fill.scan_opened()
    for b in batches:
        fill.add(b)
    fill.scan_closed(sum(b.num_rows for b in batches), True)
    fill.close(True)
    hit = cache.open(conn, "k", names)
    collector = OutputCollectorFactory()
    chain = ([CachedScanOperatorFactory(hit),
              segment.for_cached_scan(hit=hit)] + rest + [collector])
    task = execute_pipelines([Pipeline(chain, name="t")], cfg)
    seg = [s for s in task.operator_stats if "FusedSegment" in s.operator]
    return collector.rows(), seg[0]


@pytest.mark.parametrize("case, flush_bytes, engages", [
    ("grouped-direct", 0, True), ("grouped-direct", 1000, True),
    ("global", 0, True), ("global", 150, True),
    ("sort-path", 0, False), ("raw-emit", 0, False),
    ("two-bindings", 0, False)])
def test_a_hit_covers_its_kept_run_where_the_partial_is_bounded(
        case, flush_bytes, engages):
    """The same batches fed to the segment one by one (the held path) and
    as a scan-cache hit: where the pre-reduce is bounded and the run is
    under one dictionary binding, the hit covers the run in run launches,
    one a flush of the held path (``partial_agg_max_bytes``), holds
    nothing and hands over the held path's rows bit for bit; the sort
    path, raw emission and two bindings go batch by batch as ever."""
    factories, cfg = (_two_bindings_chain() if case == "two-bindings"
                      else _held_chain(case))
    if flush_bytes:
        cfg = dc.replace(cfg, partial_agg_max_bytes=flush_bytes)
    held_rows, held = _run_held(factories, cfg, fuse=True)
    kept_rows, seg = _run_kept(factories, cfg)
    assert sorted(kept_rows, key=repr) == sorted(held_rows, key=repr)
    n = len(factories[0].batches)
    assert (seg.input_batches, seg.prereduce_rows) \
        == (n, held.prereduce_rows)
    if not engages:
        assert (seg.run_launches, seg.run_batches) == (0, 0)
        assert seg.jit_dispatches == held.jit_dispatches
        assert seg.prereduce_batches_held == held.prereduce_batches_held
        return
    assert (seg.run_batches, seg.prereduce_batches_held) == (n, 0)
    assert seg.run_launches == held.prereduce_flushes
    assert (seg.run_launches > 1) == bool(flush_bytes)
    # the run launches and the one that stacked the run's columns
    assert seg.jit_dispatches == seg.run_launches + 1
    assert seg.output_batches == held.output_batches


def test_prereduce_global_empty_scan(runner_on):
    """Global pre-reduce over a scan whose filter kills every row: the
    per-batch partial row carries count=0, and the merge produces the
    SQL empty-input defaults (count 0, sum NULL)."""
    res = runner_on.execute(
        "select count(*), sum(l_quantity), min(l_quantity) "
        "from lineitem where l_quantity < 0")
    assert res.rows == [(0, None, None)]


def test_prereduce_global_default_row(runner_on):
    """A global pre-reduce segment that never dispatched (zero input
    batches) still owes its default partial row — COUNT over an empty
    table is 0, not NULL."""
    runner_on.execute(
        "create table memory.fusion_empty_t (x bigint)")
    res = runner_on.execute(
        "select count(*), sum(x), max(x) from memory.fusion_empty_t "
        "where x > 0")
    assert res.rows == [(0, None, None)]
    jc = runner_on._last_task.jit_counters()
    assert jc["prereduce_rows"] == 0


def test_q1_prereduce_dispatch_pin(runner_on):
    """TPC-H Q1 at SF0.01 runs with fewer than 5 jit dispatches, the
    scan rows fold into in-segment partial states, and the downstream
    aggregation consumes group-sized partials instead of row batches."""
    runner_on.execute(QUERIES[1])
    task = runner_on._last_task
    jc = task.jit_counters()
    assert 0 < jc["dispatches"] < 5, jc
    assert jc["prereduce_rows"] > 50_000, jc
    agg_in = sum(s.input_rows for s in task.operator_stats
                 if "HashAggregation" in s.operator)
    assert 0 < agg_in <= 64, agg_in   # partial states, not 60k rows


def test_q6_prereduce_single_dispatch(runner_on):
    """Q6-class scan->global-agg pipelines collapse to ONE dispatch per
    coalesced batch: at SF0.01 the whole query is a single launch and
    the global aggregation's finish."""
    runner_on.execute(QUERIES[6])
    jc = runner_on._last_task.jit_counters()
    assert jc["dispatches"] == 2, jc
    assert jc["prereduce_rows"] > 50_000, jc


def test_partial_agg_on_q1_lowering(runner_on):
    """Q1's pipeline is scan -> pre-reducing segment -> merge
    aggregation with the finalize projections folded in."""
    from presto_tpu.exec.aggregation import HashAggregationOperatorFactory
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.physical import PhysicalPlanner
    from presto_tpu.sql.planner import Planner

    plan = optimize(
        Planner(runner_on.metadata).plan(parse_statement(QUERIES[1])),
        runner_on.metadata, runner_on.config)
    phys = PhysicalPlanner(runner_on.registry,
                           runner_on.config).plan(plan)
    chain = phys.pipelines[0].factories
    kinds = [type(f).__name__ for f in chain]
    assert kinds == [
        "TableScanOperatorFactory", "FusedSegmentOperatorFactory",
        "HashAggregationOperatorFactory", "OrderByOperatorFactory",
        "OutputCollectorFactory"], kinds
    seg, agg = chain[1], chain[2]
    assert seg.agg_spec is not None and not seg.agg_spec.global_
    assert "prereduce" in seg.describe()
    assert agg.post_projections and len(agg.post_projections) == 2
    # merge prims re-aggregate the partial states
    assert {a.prim for a in agg.aggs} <= {"sum", "min", "max"}


# ---------------------------------------------------------------------------
# shared dictionary interning (one compile per (table, expr))
# ---------------------------------------------------------------------------

def test_shared_interning_compiles_once():
    """Multi-split scan of one table compiles each unfused expression
    kernel exactly once: every split serves the SAME per-table interning
    dictionaries, so the kernel-cache (token, length) binding is stable
    across splits (pre-PR4: one re-trace per split)."""
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=0.01)
    handle = conn.get_table("customer")
    splits = conn.get_splits(handle, 8)
    assert len(splits) >= 4
    vt = conn.table_schema(handle).column_type("c_name")
    scan = TableScanOperatorFactory(
        conn, ["c_custkey", "c_name", "c_phone"], table="customer")
    fp = FilterProjectOperatorFactory(
        B.comparison(">", B.ref(0, T.BIGINT), B.const(5, T.BIGINT)),
        [B.ref(1, vt), B.ref(2, vt)], [T.BIGINT, vt, vt])
    collector = OutputCollectorFactory()
    cfg = _cfg(task_concurrency=1)
    task = execute_pipelines(
        [Pipeline([scan, fp, collector], splits, name="t")], cfg)
    jc = task.jit_counters()
    assert jc["dispatches"] == len(splits)
    assert jc["compiles"] == 1, jc
    assert len(collector.rows()) == 1500 - 5


def test_memory_interning_shares_table_dictionaries():
    """Inserted batches re-code dictionary columns into per-table shared
    interning tables, so a scan of many batches compiles as a scan of
    one does: the count does not grow with the batches inserted."""
    r = LocalQueryRunner.tpch(scale=0.01, config=_cfg(task_concurrency=1))
    conn = r.registry.get("memory")
    for table, inserts in (("interning_one", 1), ("interning_t", 3)):
        r.execute(f"create table memory.{table} (k bigint, s varchar)")
        for i in range(inserts):
            r.execute(f"insert into memory.{table} values "
                      f"({i}, 'v{i}'), ({i + 10}, 'w{i}')")
        batches = conn.tables[table].batches
        assert len(batches) == inserts
        assert len({id(b.columns[1].dictionary) for b in batches}) == 1
        res = r.execute(f"select s from memory.{table} where k >= 0")
        assert len(res.rows) == 2 * inserts
        assert r._last_task.jit_counters()["compiles"] == 1


# ---------------------------------------------------------------------------
# partition-id fusion (exchange sink)
# ---------------------------------------------------------------------------

def _pages(buffers, n):
    """Every partition's serialized pages, as the consumers fetch them."""
    out = {}
    for p in range(n):
        out[p], token = [], 0
        while True:
            pages, token, done = buffers.get_pages(p, token, 1 << 30)
            out[p].extend(bytes(pg) for pg in pages)
            if done:
                break
    return out


def test_precomputed_partition_matches_eager():
    """A segment feeding a hash-partitioned sink precomputes partition
    ids inside the fused program; the buffers must receive exactly the
    rows the eager hash path routes."""
    from presto_tpu.serde import deserialize_batch
    from presto_tpu.server.buffers import OutputBufferManager
    from presto_tpu.server.exchangeop import PartitionedOutputOperatorFactory

    batch = batch_from_pylist(
        [T.BIGINT, T.VARCHAR],
        [(i, f"k{i % 7}") for i in range(50)])
    fp = FilterProjectOperatorFactory(
        B.comparison("<", B.ref(0, T.BIGINT), B.const(40, T.BIGINT)),
        [B.ref(0, T.BIGINT), B.ref(1, batch.columns[1].type)],
        [T.BIGINT, batch.columns[1].type])

    def run(fuse: bool):
        buffers = OutputBufferManager(4)
        sink = PartitionedOutputOperatorFactory(buffers, [0, 1], 4)
        chain = [ValuesOperatorFactory([batch.to_device()]), fp]
        if fuse:
            chain = fuse_chain(chain + [sink], _cfg())
            assert isinstance(chain[1], FusedSegmentOperatorFactory)
            assert chain[1].partition_spec == ((0, 1), 4)
            assert sink.precomputed is True
        else:
            chain = chain + [sink]
        execute_pipelines([Pipeline(chain, name="t")], _cfg())
        return {p: sorted(r for pg in pages
                          for r in deserialize_batch(pg).to_pylist())
                for p, pages in _pages(buffers, 4).items()}

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# the compaction at the end of a segment's program: done through
# ops/filter.py, or skipped where the live rows are a prefix already or
# the partitioned sink cuts them on the host
# ---------------------------------------------------------------------------

def _segment_stats(task):
    return [s for s in task.operator_stats
            if s.operator.endswith("FusedSegmentOperator")]


_SINK_BATCHES = {
    # (key, value) rows; the filter keeps value < 1000
    "mixed": [(i * 7919 % 1013, i if i % 3 else 5000 + i)
              for i in range(700)],
    "all_dead": [(i, 2000 + i) for i in range(300)],
    # one key, so one partition takes every live row
    "one_partition": [(42, i if i % 2 else 9000) for i in range(500)],
}


@pytest.mark.parametrize("n_partitions", [2, 4])
@pytest.mark.parametrize("kind", sorted(_SINK_BATCHES))
def test_partitioned_sink_cuts_an_uncompacted_segment(kind, n_partitions):
    """A filter segment into the partitioned sink leaves its rows where
    they are and gives the dead ones the id one past the last partition:
    the pages are, byte for byte, those of the same rows compacted by
    the filter operator and hashed by the sink."""
    from presto_tpu.server.buffers import OutputBufferManager
    from presto_tpu.server.exchangeop import PartitionedOutputOperatorFactory

    rows = _SINK_BATCHES[kind]
    batch = batch_from_pylist([T.BIGINT, T.BIGINT], rows)
    live = [r for r in rows if r[1] < 1000]
    fp = FilterProjectOperatorFactory(
        B.comparison("<", B.ref(1, T.BIGINT), B.const(1000, T.BIGINT)),
        [B.ref(0, T.BIGINT), B.ref(1, T.BIGINT)], [T.BIGINT, T.BIGINT])

    def run(fuse: bool):
        buffers = OutputBufferManager(n_partitions)
        sink = PartitionedOutputOperatorFactory(buffers, [0], n_partitions)
        chain = [ValuesOperatorFactory([batch.to_device()]), fp, sink]
        if fuse:
            chain = fuse_chain(chain, _cfg())
            assert isinstance(chain[1], FusedSegmentOperatorFactory)
            assert sink.precomputed is True
        task = execute_pipelines([Pipeline(chain, name="t")], _cfg())
        return _pages(buffers, n_partitions), task

    fused, task = run(True)
    eager, _task = run(False)
    assert fused == eager
    assert sum(len(v) for v in fused.values()) == (
        0 if kind == "all_dead" else 1 if kind == "one_partition"
        else n_partitions)
    (seg,) = _segment_stats(task)
    assert (seg.compactions, seg.compactions_skipped) == (0, 1)
    assert seg.output_rows == len(live)
    (sink_stats,) = [s for s in task.operator_stats
                     if s.operator.endswith("PartitionedOutputOperator")]
    assert (sink_stats.input_rows, sink_stats.output_rows) == \
        ((len(live), len(live)) if live else (0, 0))


@pytest.mark.parametrize("consumer", ["operator", "task_output"])
def test_segment_into_anything_else_still_compacts(consumer):
    """What decides is the consumer the lowering saw: a segment that
    feeds an operator or an un-partitioned sink hands over its live rows
    at the front, through the helper."""
    from presto_tpu.serde import deserialize_batch
    from presto_tpu.server.buffers import OutputBufferManager
    from presto_tpu.server.exchangeop import TaskOutputOperatorFactory

    batch, fps = _three_stage_chain()
    collector = OutputCollectorFactory()
    buffers = OutputBufferManager(1)
    tail = (collector if consumer == "operator"
            else TaskOutputOperatorFactory(buffers))
    chain = fuse_chain([ValuesOperatorFactory([batch.to_device()])] + fps
                       + [tail], _cfg())
    assert chain[1].partition_spec is None
    task = execute_pipelines([Pipeline(chain, name="t")], _cfg())
    (seg,) = _segment_stats(task)
    assert (seg.compactions, seg.compactions_skipped) == (1, 0)
    assert seg.output_rows == 1
    if consumer == "operator":
        got = collector.rows()
    else:
        got = [r for pg in _pages(buffers, 1)[0]
               for r in deserialize_batch(pg).to_pylist()]
    assert got == [(33, 30)]


def _probe_chain(filter_after: bool):
    """(build pipeline, probe chain, probe batch): 40 probe rows, a
    filter before the probe, a build side with a duplicate key and keys
    the probe never asks for; optionally a filter after the probe."""
    from presto_tpu.exec.joinop import (
        HashBuildOperatorFactory, LookupJoinOperatorFactory,
    )

    build_rows = [(k, 100 + k) for k in range(0, 30, 2)] + [(4, 999)]
    build = HashBuildOperatorFactory([0], [T.BIGINT, T.BIGINT])
    build_pipeline = Pipeline(
        [ValuesOperatorFactory([batch_from_pylist(
            [T.BIGINT, T.BIGINT], build_rows).to_device()]), build],
        name="build")
    probe = batch_from_pylist([T.BIGINT, T.BIGINT],
                              [(i % 20, i) for i in range(40)])
    t2 = [T.BIGINT, T.BIGINT]
    chain = [
        FilterProjectOperatorFactory(
            B.comparison(">", B.ref(1, T.BIGINT), B.const(3, T.BIGINT)),
            [B.ref(0, T.BIGINT), B.ref(1, T.BIGINT)], t2),
        LookupJoinOperatorFactory(build, [0], t2, "inner"),
    ]
    if filter_after:
        t4 = t2 + t2
        chain.append(FilterProjectOperatorFactory(
            B.comparison("<", B.ref(3, T.BIGINT), B.const(900, T.BIGINT)),
            [B.ref(i, T.BIGINT) for i in range(4)], t4))
    return build_pipeline, chain, probe


@pytest.mark.parametrize("filter_after,want_counts",
                         [(False, (0, 1)), (True, (1, 0))],
                         ids=["probe_last", "filter_after_probe"])
def test_probe_segment_compacts_only_after_a_later_mask(filter_after,
                                                        want_counts):
    """An inner probe writes its rows to the front: with nothing masking
    them afterwards the program ends there (a skip); a filter after the
    probe brings the compaction back.  Either way the rows are the
    unfused operators'."""
    results = {}
    for fused in (False, True):
        build_pipeline, chain, probe = _probe_chain(filter_after)
        collector = OutputCollectorFactory()
        chain = [ValuesOperatorFactory([probe.to_device()])] + chain
        if fused:
            chain = fuse_chain(chain, _cfg())
            assert [type(f).__name__ for f in chain] == [
                "ValuesOperatorFactory", "FusedSegmentOperatorFactory"]
        task = execute_pipelines(
            [build_pipeline, Pipeline(chain + [collector], name="probe")],
            _cfg())
        results[fused] = collector.rows()
    assert results[True] == results[False]      # same rows, same order
    keep = {k: [100 + k] + ([999] if k == 4 else [])
            for k in range(0, 30, 2)}
    assert sorted(results[True]) == sorted(
        (i % 20, i, i % 20, b) for i in range(4, 40)
        for b in keep.get(i % 20, [])
        if not (filter_after and b >= 900))
    (seg,) = _segment_stats(task)
    assert (seg.compactions, seg.compactions_skipped) == want_counts
    assert seg.output_rows == len(results[True])


def test_explain_analyze_reports_compactions(runner_on):
    """Q3 on one node: the lineitem segment ends in its probe of the
    orders build and the orders segment in its probe of customer's (two
    skips a batch pair); customer's filter feeds the build operator and
    compacts."""
    text = "\n".join(r[0] for r in runner_on.execute(
        "explain analyze " + QUERIES[3]).rows)
    ts = runner_on._last_task.task_stats()
    assert ts.compactions >= 1 and ts.compactions_skipped >= 2
    assert (f"compactions: {ts.compactions} done, "
            f"{ts.compactions_skipped} skipped") in text


# ---------------------------------------------------------------------------
# dictionary tokens + kernel cache counters/capacity
# ---------------------------------------------------------------------------

def test_dictionary_tokens_monotonic_and_unique():
    a, b = Dictionary(["x"]), Dictionary(["x"])
    assert a.token != b.token
    assert b.token > a.token
    # tokens never recycle (unlike id()): a new dictionary after GC of an
    # old one still gets a fresh token
    import gc

    old = a.token
    del a
    gc.collect()
    c = Dictionary(["x"])
    assert c.token > old


def test_fp_cache_keys_use_tokens_not_ids():
    import inspect

    from presto_tpu.exec import operators as ops

    src = inspect.getsource(ops.FilterProjectOperator)
    assert "id(c.dictionary)" not in src
    assert "dictionary_binding_key" in src


def test_kernel_cache_counters_and_capacity():
    from presto_tpu import kernelcache as kc

    cache = kc.new_cache("test_cache")
    assert kc.cache_get(cache, ("a",)) is None
    kc.cache_put(cache, ("a",), 1)
    assert kc.cache_get(cache, ("a",)) == 1
    assert cache.hits == 1 and cache.misses == 1
    # explicit capacity evicts LRU-first
    for i in range(5):
        kc.cache_put(cache, ("k", i), i, cap=3)
    assert len(cache) == 3 and cache.evictions >= 2
    stats = kc.cache_stats()["test_cache"]
    assert stats["hits"] == 1 and stats["evictions"] >= 2
    # the EngineConfig knob lands as the process default
    prev = kc.default_capacity()
    try:
        execute_pipelines([], _cfg(kernel_cache_capacity=123))
        assert kc.default_capacity() == 123
    finally:
        kc.set_default_capacity(prev)


def test_task_info_reports_kernel_caches():
    from presto_tpu.kernelcache import cache_stats

    stats = cache_stats()
    assert "filter_project" in stats and "fused_segment" in stats
    for s in stats.values():
        # compiles/compile_ns: per-cache compile-time attribution
        # (kernelcache.record_compile) surfaced alongside hit/miss
        assert set(s) == {"size", "hits", "misses", "evictions",
                          "compiles", "compile_ns"}


# ---------------------------------------------------------------------------
# probe-in-segment, FINAL-merge fusion, cost-based pre-reduce, and
# which tier serves a group-by
# ---------------------------------------------------------------------------

def _plan_chains(runner, sql, cfg):
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.physical import PhysicalPlanner
    from presto_tpu.sql.planner import Planner

    plan = optimize(Planner(runner.metadata).plan(parse_statement(sql)),
                    runner.metadata, cfg)
    return PhysicalPlanner(runner.registry, cfg).plan(plan).pipelines


def test_q3_probe_absorbed_into_segment(runner_on):
    """Q3's probe pipeline runs filter -> project -> probe inside ONE
    fused segment (the filter/project/probe/partial-agg chain of the
    tentpole), and the probe stages name their join type."""
    from presto_tpu.exec.fusion import ProbeStage

    pipelines = _plan_chains(runner_on, QUERIES[3], runner_on.config)
    probes = [s for p in pipelines for f in p.factories
              if isinstance(f, FusedSegmentOperatorFactory)
              for s in f.stages if isinstance(s, ProbeStage)]
    assert len(probes) >= 2
    assert all(s.factory.join_type == "inner" for s in probes)


def _join_tiers(task):
    """operator kind -> the kernel tiers its instances report, for the
    operators that build or probe a join."""
    tiers = {}
    for s in task.operator_stats:
        kind = s.operator.split(".")[-1]
        if s.kernel_tier and kind in ("HashBuildOperator",
                                      "LookupJoinOperator",
                                      "FusedSegmentOperator"):
            tiers.setdefault(kind, []).append(s.kernel_tier)
    return tiers


def test_q3_joins_take_the_dense_index(runner_on):
    """Q3's keys are BIGINT with spans far under the bound: both builds
    publish the direct-address index and both absorbed probes read it,
    whatever the backend."""
    runner_on.execute(QUERIES[3])
    assert _join_tiers(runner_on._last_task) == {
        "HashBuildOperator": ["dense", "dense"],
        "FusedSegmentOperator": ["dense", "dense"]}


def test_explain_analyze_counts_the_join_tiers(runner_on):
    res = runner_on.execute("explain analyze " + QUERIES[3])
    text = "\n".join(r[0] for r in res.rows)
    assert ("dense: FusedSegmentOperator x2, HashBuildOperator x2"
            in text.split("kernel tiers: ")[1].splitlines()[0])


def test_final_merge_fuses_exchange_fed_grouped_merge():
    """A grouped FINAL merge directly on a remote exchange absorbs into
    an empty-stage coalescing segment with the finalize projections
    folded into the merge finish: the chain is exchange -> segment ->
    merge aggregation, the projection gone."""
    from presto_tpu.exec.aggregation import (
        AggChannel, HashAggregationOperatorFactory,
    )
    from presto_tpu.exec.fusion import fuse_chain
    from presto_tpu.server.exchangeop import ExchangeOperatorFactory

    types = [T.BIGINT, T.DOUBLE, T.BIGINT]
    agg = HashAggregationOperatorFactory(
        [0], [AggChannel("sum", 1, T.DOUBLE),
              AggChannel("sum", 2, T.BIGINT)], types)
    agg.step = "final"
    fin = FilterProjectOperatorFactory(
        None, [B.ref(0, T.BIGINT), B.ref(1, T.DOUBLE)], types)
    exch = ExchangeOperatorFactory(["http://x/v1/task/t/results/0"])
    chain = fuse_chain([exch, agg, fin], _cfg())
    assert [type(f).__name__ for f in chain] == [
        "ExchangeOperatorFactory", "FusedSegmentOperatorFactory",
        "HashAggregationOperatorFactory"]
    assert chain[1].stages == [] and chain[1].agg_spec is not None
    assert chain[1].coalesce_rows == _cfg().scan_batch_rows
    assert chain[2].post_projections


def test_final_merge_skips_global_merges():
    """Global merge aggregations stay unfused (their empty-input
    default row needs the ORIGINAL prims)."""
    from presto_tpu.exec.aggregation import (
        AggChannel, GlobalAggregationOperatorFactory,
    )
    from presto_tpu.exec.fusion import fuse_chain
    from presto_tpu.server.exchangeop import ExchangeOperatorFactory

    agg = GlobalAggregationOperatorFactory(
        [AggChannel("sum", 0, T.DOUBLE)], [T.DOUBLE])
    agg.step = "final"
    exch = ExchangeOperatorFactory(["http://x/v1/task/t/results/0"])
    chain = fuse_chain([exch, agg], _cfg())
    assert [type(f).__name__ for f in chain] == [
        "ExchangeOperatorFactory", "GlobalAggregationOperatorFactory"]


def test_cost_based_raw_emission_switch():
    """A pre-reducing segment whose observed groups/rows ratio says
    grouping is not reducing flips to raw partial-state emission after
    the first batch — results stay exact against a ``collections``
    group-by, and prereduce_rows stops accumulating once flipped."""
    from presto_tpu.exec.aggregation import AggChannel
    from presto_tpu.exec.aggregation import HashAggregationOperatorFactory

    n = 4096
    d = Dictionary([f"k{i}" for i in range(n)])
    vt = None
    rows1 = [(i, float(i)) for i in range(n)]          # all distinct
    rows2 = [(i, float(2 * i)) for i in range(n)]
    from presto_tpu import types as TT
    from presto_tpu.batch import Batch, Column
    import numpy as np

    def mk(rows):
        codes = np.asarray([r[0] for r in rows], np.int32)
        vals = np.asarray([r[1] for r in rows])
        kt = TT.VARCHAR
        return Batch((Column(kt, codes, None, d),
                      Column(TT.DOUBLE, vals)), len(rows))

    types = [mk(rows1).columns[0].type, T.DOUBLE]
    fp = FilterProjectOperatorFactory(
        None, [B.ref(0, types[0]), B.ref(1, T.DOUBLE)], types)
    agg = HashAggregationOperatorFactory(
        [0], [AggChannel("sum", 1, T.DOUBLE),
              AggChannel("count", None, T.BIGINT)], types)

    # on the sort path: a direct-domain pre-reduce is bounded, holds its
    # partials on the device and so never reads the ratio back
    cfg = _cfg(direct_groupby_max_domain=1 << 10)
    collector = OutputCollectorFactory()
    chain = fuse_chain(
        [ValuesOperatorFactory([mk(rows1).to_device(),
                                mk(rows2).to_device()]),
         fp, agg], cfg)
    assert chain[1].agg_spec is not None
    task = execute_pipelines(
        [Pipeline(chain + [collector], name="t")], cfg)
    sums = collections.Counter()
    counts = collections.Counter()
    for code, v in rows1 + rows2:
        sums[f"k{code}"] += v
        counts[f"k{code}"] += 1
    assert sorted(collector.rows()) == sorted(
        (k, sums[k], counts[k]) for k in sums)
    # only the FIRST batch pre-reduced: it emitted a group a row, and
    # the second went out raw in the partial schema
    assert task.jit_counters()["prereduce_rows"] == n


def _group_tiers(task):
    return [s.kernel_tier for s in task.operator_stats if s.kernel_tier]


def _count_by(column, scale=0.01):
    """``select column, count(*) from lineitem group by column`` by a
    ``collections.Counter`` over the generated column."""
    values = tpch_reference.host_columns(
        scale, {"lineitem": [column]})[column]
    return sorted(collections.Counter(values.tolist()).items())


# key column, the scale (lineitem has 60 K rows at 0.01), the tier the
# aggregation reports, whether it streams
GROUPBY_TIERS = {
    # dictionary codes: a bounded domain
    "direct": ("l_shipmode", 0.01, "direct", False),
    # unbounded, not clustered
    "sort": ("l_partkey", 0.01, "sort", False),
    # the same past 131,072 rows (180 K), fed a scan batch at a time:
    # the rows seen change nothing
    "sort-large": ("l_partkey", 0.03, "sort", False),
    # the scan's sort key: rows arrive clustered, no tier at all
    "streaming": ("l_orderkey", 0.01, "", True),
}


@pytest.mark.parametrize("case", sorted(GROUPBY_TIERS))
def test_groupby_tier_follows_the_input(case):
    """Both sides of every choice the aggregation makes alone: which
    tier serves a GROUP BY follows the key's type and the scan's order,
    and every tier counts as a Counter does."""
    column, scale, tier, streams = GROUPBY_TIERS[case]
    r = LocalQueryRunner.tpch(scale=scale)
    res = r.execute(
        f"select {column}, count(*) from lineitem group by {column}")
    stats = r._last_task.operator_stats
    assert any("StreamingAggregation" in s.operator
               for s in stats) == streams
    tiers = set(_group_tiers(r._last_task))
    assert tiers == ({tier} if tier else set()), tiers
    assert sorted(res.rows) == _count_by(column, scale)


def test_group_by_over_a_spill_seam_exact(tmp_path, monkeypatch):
    """An unbounded GROUP BY whose input passes the spill threshold part
    of the way: rows accumulated before and after the first spill land
    in the same groups exactly once (sum, count, min, max against
    ``collections`` over the generated columns)."""
    from presto_tpu.exec.aggregation import HashAggregationOperator

    spills = []
    spill = HashAggregationOperator._spill_accumulated
    monkeypatch.setattr(
        HashAggregationOperator, "_spill_accumulated",
        lambda op: (spills.append(len(op._batches)), spill(op))[1])
    r = LocalQueryRunner.tpch(scale=0.01, config=_cfg(
        scan_batch_rows=8192, spill_threshold_bytes=1 << 20,
        spill_partitions=4, spill_path=str(tmp_path)))
    got = r.execute(
        "select l_partkey, sum(l_extendedprice), count(*), "
        "min(l_quantity), max(l_tax) from lineitem group by l_partkey").rows
    assert set(_group_tiers(r._last_task)) == {"sort"}
    # batches were held before the first spill and more came after it
    assert len(spills) >= 2 and spills[0] > 1 and sum(spills[1:]) > 0
    c = tpch_reference.host_columns(0.01, {"lineitem": [
        "l_partkey", "l_extendedprice", "l_quantity", "l_tax"]})
    want = {}
    for k, price, qty, tax in zip(c["l_partkey"].tolist(),
                                  c["l_extendedprice"].tolist(),
                                  c["l_quantity"].tolist(),
                                  c["l_tax"].tolist()):
        s, n, lo, hi = want.get(k, (0.0, 0, np.inf, -np.inf))
        want[k] = (s + price, n + 1, min(lo, qty), max(hi, tax))
    assert_rows_close(got, [(k,) + v for k, v in want.items()])


def _key_value_batches(keys, valid, values, batch_rows):
    """Host batches of (BIGINT key, DOUBLE value, DOUBLE -value),
    ``batch_rows`` each."""
    from presto_tpu.batch import Batch, Column

    return [Batch((Column(T.BIGINT, keys[lo:lo + batch_rows],
                          None if valid is None
                          else valid[lo:lo + batch_rows]),
                   Column(T.DOUBLE, values[lo:lo + batch_rows]),
                   Column(T.DOUBLE, -values[lo:lo + batch_rows])),
                  min(batch_rows, len(keys) - lo))
            for lo in range(0, len(keys), batch_rows)]


def _sort_tier_cases():
    rng = np.random.default_rng(7)
    n = 8192
    cases = {}
    # 4,096 groups whose keys agree in their low 20 bits: nothing
    # about a key's bits matters to a sort
    cases["keys_collide_in_their_low_bits"] = (
        rng.integers(0, 4096, n) << 20, None,
        rng.uniform(-100, 100, n), n, {})
    # three rows in ten have a NULL key: one group
    cases["null_keys_form_one_group"] = (
        rng.integers(0, 64, n), rng.random(n) > 0.3, np.ones(n), 1024, {})
    # the input spills part of the way (the threshold is reached after
    # a few batches); groups 1000.. are first seen after that, all
    # values positive for the min and, negated, negative for the max:
    # a cell that started at 0 would show
    cases["minmax_identities_over_a_spill"] = (
        np.concatenate([np.arange(n) % 400, 1000 + np.arange(n) % 400]),
        None, np.arange(2 * n, dtype=np.float64) + 100.0, 1024,
        {"spill_threshold_bytes": 64 << 10, "spill_partitions": 4})
    # more than 131,072 rows of an unbounded key in 141 small batches
    big = 141_000
    cases["many_small_batches_past_131072_rows"] = (
        rng.integers(0, 50_000, big), None, rng.uniform(0, 1, big), 1000,
        {})
    return cases


SORT_TIER_CASES = _sort_tier_cases()


@pytest.mark.parametrize("case", sorted(SORT_TIER_CASES))
def test_sort_tier_serves_every_unbounded_group_by(case, tmp_path):
    """Nothing about a key's bits, its NULLs, a spill part of the way or
    the rows and batches seen changes the tier or the answer: sum,
    count, min and max of the negated value a group, against
    ``collections``; one tier, ``sort``."""
    from presto_tpu.exec.aggregation import (
        AggChannel, HashAggregationOperator,
    )
    from presto_tpu.exec.context import (
        OperatorContext, QueryContext, TaskContext,
    )

    keys, valid, values, batch_rows, knobs = SORT_TIER_CASES[case]
    cfg = _cfg(spill_path=str(tmp_path), **knobs)
    ctx = OperatorContext(TaskContext(QueryContext(cfg)), "agg")
    op = HashAggregationOperator(
        ctx, [0],
        [AggChannel("sum", 1, T.DOUBLE), AggChannel("count", None, T.BIGINT),
         AggChannel("min", 1, T.DOUBLE), AggChannel("max", 2, T.DOUBLE)],
        [T.BIGINT, T.DOUBLE, T.DOUBLE])
    for batch in _key_value_batches(keys, valid, values, batch_rows):
        op.add_input(batch)
    spilled = op._spiller is not None
    op.finish()
    got = {}
    while not op.is_finished():
        out = op.get_output()
        for k, s, c, lo, hi in out.to_pylist():
            assert k not in got
            got[k] = (s, c, lo, hi)
    want = {}
    for i, k in enumerate(keys.tolist()):
        k = k if valid is None or valid[i] else None
        v = float(values[i])
        s, c, lo, hi = want.get(k, (0.0, 0, np.inf, -np.inf))
        want[k] = (s + v, c + 1, min(lo, v), max(hi, -v))
    assert set(got) == set(want)
    for k, (s, c, lo, hi) in want.items():
        assert got[k][0] == pytest.approx(s, rel=1e-9, abs=1e-7)
        assert got[k][1:] == (c, lo, hi), k
    assert ctx.stats.kernel_tier == "sort"
    assert spilled == ("spill_threshold_bytes" in knobs)
