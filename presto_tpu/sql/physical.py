"""Physical planner: logical PlanNode tree -> executable Pipelines.

The LocalExecutionPlanner analogue (presto-main/.../sql/planner/
LocalExecutionPlanner.java:291): a bottom-up visitor mapping each PlanNode
to OperatorFactory chains, breaking pipelines at join build sides exactly
where the reference's LookupSourceFactory rendezvous sits (build pipelines
are emitted before the pipeline that probes them, matching
execute_pipelines' sequential contract).

Aggregate decomposition happens here: a PlanAggregate's AggSpec components
become primitive AggChannels (sum/count/min/max; sumsq pre-projects x*x)
and ``finalize`` becomes a post-aggregation projection (avg = sum/count,
stddev/variance from the moment components) — the role the reference's
AccumulatorCompiler + partial/final Step split plays.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from presto_tpu import types as T
from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.connectors.api import ConnectorRegistry, Split
from presto_tpu.exec.aggregation import (
    AggChannel, GlobalAggregationOperatorFactory,
    HashAggregationOperatorFactory,
)
from presto_tpu.exec.driver import Pipeline
from presto_tpu.exec.joinop import (
    HashBuildOperatorFactory, LookupJoinOperatorFactory,
)
from presto_tpu.exec.nestedloop import (
    EnforceSingleRowOperatorFactory, NestedLoopBuildOperatorFactory,
    NestedLoopJoinOperatorFactory,
)
from presto_tpu.exec.operators import (
    FilterProjectOperatorFactory, LimitOperatorFactory,
    OutputCollectorFactory, TableScanOperatorFactory, ValuesOperatorFactory,
)
from presto_tpu.exec.sortop import OrderByOperatorFactory, SortSpec
from presto_tpu.exec.unionop import (
    UnionBuffer, UnionSinkOperatorFactory, UnionSourceOperatorFactory,
)
from presto_tpu.exec.windowop import WindowOperatorFactory
from presto_tpu.expr import build as B
from presto_tpu.expr.ir import InputRef, RowExpression
from presto_tpu.sql.plan import (
    AggregationNode, EnforceSingleRowNode, FilterNode, JoinNode, LimitNode,
    OutputNode, PlanAggregate, PlanNode, ProjectNode, RemoteMergeNode,
    RemoteSourceNode, SemiJoinNode, SortNode, TableFinishNode,
    TableScanNode, TableWriterNode, UnionNode,
    UnnestNode, ValuesNode, WindowNode,
)


# process-wide count of physical plans built (PhysicalPlanner.plan
# calls) — the plan-cache physical-factory sharing pin: the SECOND
# execution of a cached statement must not bump it
PLANS_BUILT = 0

# process-wide count of worker-side fragment lowerings
# (PhysicalPlanner.plan_fragment calls) — the worker plan_fragment
# cache pin: repeat task creates of a cached statement must not bump it
FRAGMENTS_LOWERED = 0


@dataclasses.dataclass
class PhysicalPlan:
    pipelines: List[Pipeline]
    collector: OutputCollectorFactory
    column_names: List[str]
    column_types: List[T.Type]

    def reset_for_execution(self) -> None:
        """Re-arm every factory's cross-execution state (collector
        batches, union buffers, build rendezvous) so the SAME operator
        factory chains execute again — what lets the plan cache share
        the physical-planner output across repeat statements instead of
        re-planning per execution."""
        for p in self.pipelines:
            for f in p.factories:
                f.reset_for_execution()


class PhysicalPlanner:
    def __init__(self, registry: ConnectorRegistry,
                 config: EngineConfig = DEFAULT,
                 scan_shard: Optional[Tuple[int, int]] = None,
                 remote_sources: Optional[dict] = None,
                 fetch_headers: Optional[dict] = None,
                 http_client=None, task_id: Optional[str] = None,
                 exchange_register=None,
                 trace_token: Optional[str] = None,
                 spool=None):
        """``scan_shard=(task_index, task_count)`` makes scans generate only
        this task's deterministic share of splits (distributed source
        stages, P5); ``remote_sources`` maps fragment id -> producer buffer
        URLs for RemoteSourceNode lowering.  ``http_client`` (a
        RetryingHttpClient) carries the node's error-tracking/backoff
        policy into exchange fetches; ``task_id`` labels their failures;
        ``exchange_register`` receives each created ExchangeClient so the
        owning task can repoint remote sources (mid-query recovery)."""
        self.registry = registry
        self.config = config
        self.scan_shard = scan_shard
        self.remote_sources = remote_sources or {}
        # intra-cluster auth headers for exchange fetches (per cluster,
        # not process-global: one process may host several clusters)
        self.fetch_headers = fetch_headers or {}
        self.http_client = http_client
        self.task_id = task_id
        self.trace_token = trace_token
        self.exchange_register = exchange_register
        # shared SpoolStore for spool:// remote-source locations (the
        # spooled exchange tier); None when spooling is disabled
        self.spool = spool
        self._done_pipelines: List[Pipeline] = []
        self._counter = 0

    def plan(self, root: OutputNode) -> PhysicalPlan:
        global PLANS_BUILT
        PLANS_BUILT += 1
        factories, splits = self._lower(root.source)
        collector = OutputCollectorFactory()
        factories.append(collector)
        self._done_pipelines.append(
            Pipeline(factories, splits, name="output"))
        self._fuse()
        return PhysicalPlan(self._done_pipelines, collector,
                            [n for n, _ in root.columns],
                            [t for _, t in root.columns])

    def plan_fragment(self, root: PlanNode,
                      sink_factory) -> List[Pipeline]:
        """Lower a fragment root and terminate it with the given output
        sink (PartitionedOutput/TaskOutput) — the worker-task entry."""
        global FRAGMENTS_LOWERED
        FRAGMENTS_LOWERED += 1
        factories, splits = self._lower(root)
        factories.append(sink_factory)
        self._done_pipelines.append(
            Pipeline(factories, splits, name="fragment"))
        self._fuse()
        return self._done_pipelines

    def _fuse(self) -> None:
        """Pipeline-fusion post-pass (exec/fusion.py): rewrite each
        lowered chain's runs of row-local operators into fused segment
        programs.  Runs after every lowering decision that inspects the
        raw chains (streaming-agg eligibility, grouped execution,
        dynamic-filter placement)."""
        from presto_tpu.exec.fusion import fuse_pipelines

        fuse_pipelines(self._done_pipelines, self.config)

    # -- lowering -----------------------------------------------------------
    def _lower(self, node: PlanNode):
        """Returns (operator factory chain, splits) producing node's
        output batches; build-side pipelines are appended to
        self._done_pipelines in dependency order."""
        if isinstance(node, TableScanNode):
            conn = self.registry.get(node.catalog)
            handle = conn.get_table(node.table)
            if self.scan_shard is None:
                # enough splits to feed task_concurrency drivers through
                # the LocalExchange tier (4x for balance, the reference's
                # split-batch shape)
                desired = (max(4 * self.config.task_concurrency, 4)
                           if self.config.task_concurrency > 1 else 1)
                splits = conn.get_splits(handle, desired)
            else:
                # deterministic split-modulo placement: every task of a
                # source stage generates the full split list and keeps its
                # residue class (the SourcePartitionedScheduler role
                # without central placement)
                idx, count = self.scan_shard
                all_splits = conn.get_splits(handle, max(count * 4, 4))
                splits = all_splits[idx::count]
            return ([TableScanOperatorFactory(
                conn, node.column_names,
                batch_rows=self.config.scan_batch_rows,
                table=node.table)], splits)
        if isinstance(node, RemoteSourceNode):
            from presto_tpu.server.exchangeop import ExchangeOperatorFactory

            locations: List[str] = []
            for fid in node.fragment_ids:
                locations.extend(self.remote_sources.get(fid, ()))
            fac = ExchangeOperatorFactory(
                locations, headers=self.fetch_headers,
                http=self.http_client, task_id=self.task_id,
                trace_token=self.trace_token, spool=self.spool,
                spool_stall_s=self.config.exchange_spool_stall_s)
            # producer fragment ids, so the worker plan_fragment cache
            # can rebind this factory's locations per task create
            fac.source_fragment_ids = tuple(node.fragment_ids)
            if self.exchange_register is not None:
                self.exchange_register(fac)
            return ([fac], [])
        if isinstance(node, RemoteMergeNode):
            from presto_tpu.server.exchangeop import (
                MergeExchangeOperatorFactory,
            )

            locations = []
            for fid in node.fragment_ids:
                locations.extend(self.remote_sources.get(fid, ()))
            fac = MergeExchangeOperatorFactory(
                locations, node.sort_keys,
                [t for _, t in node.columns], node.limit,
                headers=self.fetch_headers, http=self.http_client,
                task_id=self.task_id, trace_token=self.trace_token,
                spool=self.spool,
                spool_stall_s=self.config.exchange_spool_stall_s)
            fac.source_fragment_ids = tuple(node.fragment_ids)
            if self.exchange_register is not None:
                self.exchange_register(fac)
            return ([fac], [])
        if isinstance(node, ValuesNode):
            from presto_tpu.batch import batch_from_pylist

            batch = batch_from_pylist(node.types, list(node.rows))
            return ([ValuesOperatorFactory([batch.to_device()])], [])
        if isinstance(node, (FilterNode, ProjectNode)):
            return self._lower_filter_project(node)
        if isinstance(node, AggregationNode):
            return self._lower_aggregation(node)
        if isinstance(node, JoinNode):
            return self._lower_join(node)
        if isinstance(node, SemiJoinNode):
            return self._lower_semijoin(node)
        if isinstance(node, SortNode):
            chain, splits = self._lower(node.source)
            specs = [SortSpec(c, not asc, bool(nf))
                     for c, asc, nf in node.sort_keys]
            chain.append(OrderByOperatorFactory(specs))
            return chain, splits
        if isinstance(node, LimitNode):
            if isinstance(node.source, SortNode):
                # TopN fusion (TopNOperator.java:35 role): sort + limit
                # becomes one truncated sort-permutation kernel
                chain, splits = self._lower(node.source.source)
                specs = [SortSpec(c, not asc, bool(nf))
                         for c, asc, nf in node.source.sort_keys]
                chain.append(OrderByOperatorFactory(specs, node.count))
                return chain, splits
            chain, splits = self._lower(node.source)
            chain.append(LimitOperatorFactory(node.count))
            return chain, splits
        if isinstance(node, EnforceSingleRowNode):
            chain, splits = self._lower(node.source)
            chain.append(EnforceSingleRowOperatorFactory(node.types))
            return chain, splits
        if isinstance(node, WindowNode):
            chain, splits = self._lower(node.source)
            chain.append(WindowOperatorFactory(
                node.partition_channels, node.order_keys, node.functions))
            return chain, splits
        if isinstance(node, UnnestNode):
            from presto_tpu.exec.unnestop import UnnestOperatorFactory

            chain, splits = self._lower(node.source)
            chain.append(UnnestOperatorFactory(
                node.replicate_channels, node.unnest_channels,
                node.ordinality, node.outer))
            return chain, splits
        if isinstance(node, TableWriterNode):
            from presto_tpu.exec.operators import (
                DistributedTableWriterOperatorFactory,
            )

            chain, splits = self._lower(node.source)
            task_tag = (str(self.scan_shard[0])
                        if self.scan_shard is not None else "0")
            chain.append(DistributedTableWriterOperatorFactory(
                self.registry, node.catalog, node.table, node.write_id,
                task_tag))
            return chain, splits
        if isinstance(node, TableFinishNode):
            from presto_tpu.exec.operators import TableFinishOperatorFactory

            chain, splits = self._lower(node.source)
            chain.append(TableFinishOperatorFactory(
                self.registry, node.catalog, node.table, node.write_id))
            return chain, splits
        if isinstance(node, UnionNode):
            buffer = UnionBuffer(len(node.inputs))
            for inp in node.inputs:
                in_chain, in_splits = self._lower(inp)
                in_chain.append(UnionSinkOperatorFactory(buffer))
                self._done_pipelines.append(
                    Pipeline(in_chain, in_splits,
                             name=self._name("union")))
            return [UnionSourceOperatorFactory(buffer)], []
        raise NotImplementedError(
            f"physical lowering for {type(node).__name__}")

    def _lower_filter_project(self, node: PlanNode):
        """Fuse adjacent Filter/Project chains into one PageProcessor-style
        operator (ScanFilterAndProjectOperator fusion)."""
        filters: List[RowExpression] = []
        projections: Optional[Tuple[RowExpression, ...]] = None
        cur = node
        # walk down: Project over (Filter*) — compose
        if isinstance(cur, ProjectNode):
            projections = cur.expressions
            cur = cur.source
        while isinstance(cur, FilterNode):
            filters.append(cur.predicate)
            cur = cur.source
        if (filters and isinstance(cur, WindowNode)
                and len(cur.functions) == 1
                and cur.functions[0].name == "row_number"):
            # TopNRowNumber fusion (TopNRowNumberOperator.java:38): a
            # row_number <= N conjunct becomes a per-partition truncation
            # inside the window sort; filtered rows never materialize
            rn_ch = len(cur.source.columns)
            limit, rest = _extract_rn_limit(filters, rn_ch)
            if limit is not None:
                from presto_tpu.exec.windowop import (
                    TopNRowNumberOperatorFactory,
                )

                chain, splits = self._lower(cur.source)
                chain.append(TopNRowNumberOperatorFactory(
                    cur.partition_channels, cur.order_keys, limit,
                    cur.columns[rn_ch][1]))
                input_types = [t for _, t in cur.columns]
                filt = None
                if rest:
                    filt = rest[-1]
                    for f in reversed(rest[:-1]):
                        filt = B.and_(filt, f)
                if projections is None:
                    projections = tuple(InputRef(i, t)
                                        for i, t in enumerate(input_types))
                chain.append(FilterProjectOperatorFactory(
                    filt, list(projections), input_types))
                return chain, splits
        if (filters and isinstance(cur, JoinNode) and cur.kind == "cross"
                and not cur.left_keys):
            spatial = _extract_spatial(filters, len(cur.left.columns))
            if spatial is not None:
                return self._lower_spatial_join(cur, spatial, projections)
        chain, splits = self._lower(cur)
        input_types = [t for _, t in cur.columns]
        if filters and isinstance(cur, TableScanNode) and splits:
            # filter-pushdown negotiation: offer TupleDomain-lite
            # conjuncts to the connector so it can drop whole splits
            # (HivePartitionManager partition-pruning role); the full
            # filter still runs on surviving rows below
            cons = _extract_constraints(filters, cur.column_names)
            if cons:
                conn = self.registry.get(cur.catalog)
                splits = conn.prune_splits(
                    conn.get_table(cur.table), splits, cons)
        filt = None
        if filters:
            filt = filters[-1]
            for f in reversed(filters[:-1]):
                filt = B.and_(filt, f)
        if projections is None:
            projections = tuple(InputRef(i, t)
                                for i, t in enumerate(input_types))
        chain.append(FilterProjectOperatorFactory(
            filt, list(projections), input_types))
        return chain, splits

    def _lower_aggregation(self, node: AggregationNode):
        if node.step == "final":
            return self._lower_final_aggregation(node)
        chain, splits = self._lower(node.source)
        input_types = [t for _, t in node.source.columns]

        pre_exprs, agg_channels, finalize_specs = decompose_aggregates(
            node.aggregates, input_types)

        needs_pre = len(pre_exprs) > len(input_types)
        if needs_pre:
            pre_types = [e.type for e in pre_exprs]
            chain.append(FilterProjectOperatorFactory(
                None, pre_exprs, input_types))
            input_types = pre_types

        ngroups = len(node.group_channels)
        if ngroups:
            if self._streaming_eligible(chain, node.group_channels,
                                        agg_channels, input_types):
                from presto_tpu.exec.streamagg import (
                    StreamingAggregationOperatorFactory,
                )

                chain.append(StreamingAggregationOperatorFactory(
                    list(node.group_channels), agg_channels, input_types))
            else:
                agg_fac = HashAggregationOperatorFactory(
                    list(node.group_channels), agg_channels, input_types)
                agg_fac.step = node.step
                agg_fac.prereduce_ratio_hint = self._group_ratio_hint(
                    node)
                chain.append(agg_fac)
        else:
            agg_fac = GlobalAggregationOperatorFactory(
                agg_channels, input_types)
            agg_fac.step = node.step
            chain.append(agg_fac)

        if node.step == "partial":
            # distributed PARTIAL: emit raw component columns (keys first);
            # the FINAL stage merges them (HashAggregationOperator.Step:61)
            return chain, splits

        # finalize projection: [keys..., finalized aggs...]
        key_types = [input_types[c] for c in node.group_channels]
        post_in = key_types + [a.out_type for a in agg_channels]
        exprs: List[RowExpression] = [InputRef(i, t)
                                      for i, t in enumerate(key_types)]
        for agg, comps in finalize_specs:
            base = [InputRef(ngroups + c, agg_channels[c].out_type)
                    for c in comps]
            exprs.append(_finalize(agg, base))
        if (len(exprs) != len(post_in)
                or any(not isinstance(e, InputRef) or e.index != i
                       for i, e in enumerate(exprs))):
            chain.append(FilterProjectOperatorFactory(
                None, exprs, post_in))
        return chain, splits

    def _group_ratio_hint(self, node: AggregationNode) -> Optional[float]:
        """Estimated groups/rows ratio for this aggregation (the
        plan-time half of the cost-based pre-reduce decision): derived
        through the same stats tier the memo's cost model uses
        (sql/stats.py NDV propagation).  None when unknown — the fusion
        pass then decides from the runtime observed ratio alone."""
        try:
            import types as _pytypes

            from presto_tpu.sql.stats import StatsCalculator

            sc = StatsCalculator(
                _pytypes.SimpleNamespace(registry=self.registry))
            src = sc.stats(node.source)
            ag = sc.stats(node)
            if (src.row_count and ag.row_count is not None
                    and src.row_count > 0):
                return float(ag.row_count) / float(src.row_count)
        except Exception:  # noqa: BLE001 - stats must never fail a plan
            return None
        return None

    def _streaming_eligible(self, chain, group_channels,
                            agg_channels, input_types) -> bool:
        """True when the group keys trace to a PREFIX of the scan's
        declared sort order (rows arrive clustered by the keys), so the
        sort-free streaming aggregation applies
        (StreamingAggregationOperator.java:38; eligibility is the
        reference's LocalProperties/StreamPropertyDerivations check)."""
        if not self.config.streaming_aggregation_enabled:
            return False
        for ch in agg_channels:
            if ch.prim not in ("sum", "count", "min", "max"):
                return False
            if (ch.prim in ("min", "max") and ch.channel is not None
                    and input_types[ch.channel].is_dictionary):
                # the carry merge would compare interning codes
                return False
        from presto_tpu.exec.grouped import scan_column_for_channel

        traced = []
        scan = None
        for g in group_channels:
            hit = scan_column_for_channel(chain, g)
            if hit is None:
                return False
            f, col = hit
            if scan is None:
                scan = f
            elif scan is not f:
                return False
            traced.append(col)
        if scan is None:
            return False
        order = scan.connector.sort_order(
            scan.connector.get_table(scan.table))
        k = len(traced)
        return bool(order) and set(traced) == set(order[:k])

    # merge prim for each partial component prim (steps.py uses the same
    # table for the SPMD in-program exchange variant)
    _FINAL_PRIM = {"count": "sum", "sum": "sum", "min": "min", "max": "max",
                   "collect": "collect_merge",  # partial arrays flatten
                   "sumln": "sum", "sumhash": "sum",
                   "hll": "hll_merge",          # partial sketches max-merge
                   "kll": "kll_merge"}          # quantile sketch union

    def _lower_final_aggregation(self, node: AggregationNode):
        """FINAL step over a partial's output: [keys..., comp0, comp1, ...].
        Re-aggregates each component with its merge primitive, then runs the
        single-step finalize projection."""
        chain, splits = self._lower(node.source)
        input_types = [t for _, t in node.source.columns]
        ngroups = len(node.group_channels)
        agg_channels, finalize_specs = merge_agg_channels(
            node.aggregates, ngroups)

        if ngroups:
            agg_fac = HashAggregationOperatorFactory(
                list(node.group_channels), agg_channels, input_types)
        else:
            agg_fac = GlobalAggregationOperatorFactory(
                agg_channels, input_types)
        agg_fac.step = "final"
        chain.append(agg_fac)

        key_types = [input_types[c] for c in node.group_channels]
        post_in = key_types + [a.out_type for a in agg_channels]
        exprs: List[RowExpression] = [InputRef(i, t)
                                      for i, t in enumerate(key_types)]
        for agg, comps in finalize_specs:
            base = [InputRef(ngroups + c, agg_channels[c].out_type)
                    for c in comps]
            exprs.append(_finalize(agg, base))
        if (len(exprs) != len(post_in)
                or any(not isinstance(e, InputRef) or e.index != i
                       for i, e in enumerate(exprs))):
            chain.append(FilterProjectOperatorFactory(None, exprs, post_in))
        return chain, splits

    def _insert_dynamic_filter(self, chain: List, dyn,
                               key_channels: List[int]) -> None:
        """Place the runtime filter as close to the scan as channel
        provenance allows (the reference pushes dynamic filters into the
        probe-side TableScan, LocalDynamicFilter.java:45): walk backwards
        over FilterProject stages remapping key channels through pure
        InputRef projections, stopping at any operator that changes row
        identity."""
        from presto_tpu.exec.dynamicfilter import (
            DynamicFilterOperatorFactory,
        )

        pos = len(chain)
        keys = list(key_channels)
        i = len(chain) - 1
        while i >= 0:
            f = chain[i]
            if isinstance(f, FilterProjectOperatorFactory):
                mapped = []
                for k in keys:
                    p = f.projections[k] if k < len(f.projections) else None
                    if isinstance(p, InputRef):
                        mapped.append(p.index)
                    else:
                        mapped = None
                        break
                if mapped is None:
                    break
                keys = mapped
                pos = i
                i -= 1
                continue
            break
        chain.insert(pos, DynamicFilterOperatorFactory(dyn, keys))

    def _lower_join(self, node: JoinNode):
        if node.kind == "cross":
            build_chain, build_splits = self._lower(node.right)
            build = NestedLoopBuildOperatorFactory(
                [t for _, t in node.right.columns])
            build_chain.append(build)
            self._done_pipelines.append(
                Pipeline(build_chain, build_splits,
                         name=self._name("xbuild")))
            chain, splits = self._lower(node.left)
            chain.append(NestedLoopJoinOperatorFactory(build))
            return chain, splits
        if node.kind in ("inner", "left"):
            # sides are lowered ONCE; the grouped-execution attempt and
            # the standard path share the chains (re-lowering would
            # duplicate nested build pipelines)
            build_chain, build_splits = self._lower(node.right)
            chain, splits = self._lower(node.left)
            grouped = self._try_grouped_join(node, chain, build_chain)
            if grouped is not None:
                return grouped
            dyn = None
            if node.kind == "inner" and self.config.dynamic_filtering_enabled:
                from presto_tpu.exec.dynamicfilter import DynamicFilter

                dyn = DynamicFilter(len(node.right_keys))
            build = HashBuildOperatorFactory(
                list(node.right_keys), [t for _, t in node.right.columns],
                dynamic_filter=dyn)
            build_chain.append(build)
            self._done_pipelines.append(
                Pipeline(build_chain, build_splits,
                         name=self._name("build")))
            if dyn is not None:
                self._insert_dynamic_filter(chain, dyn,
                                            list(node.left_keys))
            chain.append(LookupJoinOperatorFactory(
                build, list(node.left_keys),
                [t for _, t in node.left.columns],
                join_type=node.kind,
                expansion=self.config.join_expansion_factor))
            if node.residual is not None:
                if node.kind != "inner":
                    raise NotImplementedError(
                        "left-join residual not supported")
                types = [t for _, t in node.columns]
                proj = [InputRef(i, t) for i, t in enumerate(types)]
                chain.append(FilterProjectOperatorFactory(
                    node.residual, proj, types))
            return chain, splits
        raise NotImplementedError(f"{node.kind} join")

    def _lower_spatial_join(self, node: JoinNode, spatial, projections):
        """Filter(ST_pred)(cross join) -> grid-indexed spatial join
        (SpatialJoinOperator.java:42 role): the right side becomes the
        indexed build, candidates come from grid cells, and only they
        run the exact predicate — no cartesian product."""
        from presto_tpu.exec.spatialjoin import SpatialJoinOperatorFactory

        kind, flip, build_expr, probe_expr, radius, rest = spatial
        strict = False
        if isinstance(radius, tuple):
            radius, strict = radius
        build_chain, build_splits = self._lower(node.right)
        build = NestedLoopBuildOperatorFactory(
            [t for _, t in node.right.columns])
        build_chain.append(build)
        self._done_pipelines.append(
            Pipeline(build_chain, build_splits,
                     name=self._name("spatialbuild")))
        chain, splits = self._lower(node.left)
        if flip:
            # the probe side is the container: the operator's exact
            # check swaps operand roles via the 'within' kind
            kind = {"contains": "within"}.get(kind, kind)
        chain.append(SpatialJoinOperatorFactory(
            build, build_expr, probe_expr, kind, radius,
            strict=strict))
        types = [t for _, t in node.columns]
        filt = None
        if rest:
            filt = rest[-1]
            for f in reversed(rest[:-1]):
                filt = B.and_(filt, f)
        if projections is None:
            projections = tuple(InputRef(i, t)
                                for i, t in enumerate(types))
        chain.append(FilterProjectOperatorFactory(
            filt, list(projections), types))
        return chain, splits

    def _try_grouped_join(self, node: JoinNode, probe_chain,
                          build_chain):
        """Grouped execution (P9, Lifespan.java:26-38): when both join
        sides scan tables the connector co-buckets on the join key, run
        the join bucket-sequentially so only 1/k of the build side is
        resident.  Returns the (chain, splits) lowering or None when the
        shape does not qualify (caller falls through to the standard
        lowering, reusing the same chains)."""
        k = self.config.grouped_execution_buckets
        if k <= 1 or len(node.left_keys) != 1 or node.residual is not None:
            return None
        if self.scan_shard is not None:
            # distributed source stage: every task would run ALL buckets
            # over the full table and duplicate the join output — bucket
            # lifespans currently apply to single-task lowering only
            return None
        from presto_tpu.exec.grouped import (
            GroupedJoinSourceOperatorFactory, scan_column_for_channel,
        )

        probe_col = scan_column_for_channel(probe_chain, node.left_keys[0])
        build_col = scan_column_for_channel(build_chain,
                                            node.right_keys[0])
        if probe_col is None or build_col is None:
            # a side is not a pure scan chain (exchange, nested join...)
            return None
        (pscan, pname), (bscan, bname) = probe_col, build_col
        pb = pscan.connector.bucket_splits(
            pscan.connector.get_table(_scan_table(pscan)), pname, k)
        bb = bscan.connector.bucket_splits(
            bscan.connector.get_table(_scan_table(bscan)), bname, k)
        if pb is None or bb is None or pb[0] != bb[0]:
            # not bucketable, or the key domains differ (no co-partition)
            return None
        buckets = []
        for b in range(k):
            build = HashBuildOperatorFactory(
                list(node.right_keys), [t for _, t in node.right.columns])
            bfs = list(build_chain) + [build]
            pfs = list(probe_chain) + [LookupJoinOperatorFactory(
                build, list(node.left_keys),
                [t for _, t in node.left.columns],
                join_type=node.kind,
                expansion=self.config.join_expansion_factor)]
            buckets.append((bfs, bb[1][b], pfs, pb[1][b]))
        return [GroupedJoinSourceOperatorFactory(buckets)], []

    def _lower_semijoin(self, node: SemiJoinNode):
        dyn = None
        if not node.negated and self.config.dynamic_filtering_enabled:
            from presto_tpu.exec.dynamicfilter import DynamicFilter

            dyn = DynamicFilter(len(node.filtering_keys))
        build_chain, build_splits = self._lower(node.filtering)
        build = HashBuildOperatorFactory(
            list(node.filtering_keys),
            [t for _, t in node.filtering.columns],
            dynamic_filter=dyn,
            # a spilled (grace) build loses the global has-null/emptiness
            # facts a null-aware NOT IN needs; keep it resident
            allow_spill=not (node.negated and node.null_aware))
        build_chain.append(build)
        self._done_pipelines.append(
            Pipeline(build_chain, build_splits, name=self._name("sbuild")))
        chain, splits = self._lower(node.source)
        if dyn is not None:
            self._insert_dynamic_filter(chain, dyn,
                                        list(node.source_keys))
        chain.append(LookupJoinOperatorFactory(
            build, list(node.source_keys),
            [t for _, t in node.source.columns],
            join_type="anti" if node.negated else "semi",
            expansion=self.config.join_expansion_factor,
            residual=node.residual,
            null_aware=node.null_aware))
        return chain, splits

    def _name(self, prefix: str) -> str:
        self._counter += 1
        return f"{prefix}{self._counter}"


def _extract_spatial(filters, nleft: int):
    """Find one spatial conjunct over a cross join whose two geometry
    arguments come from opposite sides: ST_Contains/ST_Intersects(a, b)
    or ST_Distance(a, b) <= r.  Returns (kind, flip, build_expr,
    probe_expr, radius, remaining conjuncts) or None; expressions are
    remapped into their side's own channel space."""
    from presto_tpu.expr.ir import Call, Constant, input_channels
    from presto_tpu.sql.optimizer import remap, split_and

    conjuncts = []
    for f in filters:
        conjuncts.extend(split_and(f))

    def sides(expr):
        chans = input_channels(expr)
        if not chans:
            return None
        if all(ch < nleft for ch in chans):
            return "left"
        if all(ch >= nleft for ch in chans):
            return "right"
        return None

    def split_args(a, b):
        sa, sb = sides(a), sides(b)
        if sa == "left" and sb == "right":
            return a, b, False   # probe_expr=a(left), build=b(right)
        if sa == "right" and sb == "left":
            return b, a, True
        return None

    found = None
    rest = []
    for c in conjuncts:
        if found is None and isinstance(c, Call):
            if c.name in ("st_contains", "st_intersects") \
                    and len(c.args) == 2:
                hit = split_args(c.args[0], c.args[1])
                if hit is not None:
                    probe_e, build_e, arg0_is_right = hit
                    kind = ("intersects" if c.name == "st_intersects"
                            else "contains")
                    # contains(A, B): A is the container; flip when the
                    # container argument came from the LEFT (probe) side
                    flip = (kind == "contains") and not arg0_is_right
                    found = (kind, flip, build_e, probe_e, None)
                    continue
            if c.name in ("le", "lt", "ge", "gt") and len(c.args) == 2:
                a, b = c.args
                op = c.name
                if isinstance(a, Constant):
                    a, b = b, a
                    op = {"lt": "gt", "le": "ge",
                          "gt": "lt", "ge": "le"}[op]
                if (isinstance(a, Call) and a.name == "st_distance"
                        and op in ("le", "lt") and isinstance(b, Constant)
                        and isinstance(b.value, (int, float))):
                    hit = split_args(a.args[0], a.args[1])
                    if hit is not None:
                        probe_e, build_e, _ = hit
                        found = ("distance", False, build_e, probe_e,
                                 (float(b.value), op == "lt"))
                        continue
        rest.append(c)
    if found is None:
        return None
    kind, flip, build_e, probe_e, radius = found
    build_e = remap(build_e, {ch: ch - nleft
                              for ch in input_channels(build_e)})
    return kind, flip, build_e, probe_e, radius, rest


def _extract_rn_limit(filters, rn_channel: int):
    """Find one ``row_number <= K`` upper bound among the filter
    conjuncts; returns (K | None, remaining conjuncts)."""
    from presto_tpu.expr.ir import Call, Constant, InputRef
    from presto_tpu.sql.optimizer import split_and

    conjuncts = []
    for f in filters:
        conjuncts.extend(split_and(f))
    limit = None
    rest = []
    for c in conjuncts:
        k = None
        if (limit is None and isinstance(c, Call)
                and c.name in ("le", "lt", "eq", "ge", "gt")
                and len(c.args) == 2):
            a, b = c.args
            op = c.name
            if isinstance(b, InputRef) and isinstance(a, Constant):
                a, b = b, a
                op = {"lt": "gt", "le": "ge",
                      "gt": "lt", "ge": "le"}.get(op, op)
            if (isinstance(a, InputRef) and a.index == rn_channel
                    and isinstance(b, Constant)
                    and isinstance(b.value, int)):
                if op == "le":
                    k = b.value
                elif op == "lt":
                    k = b.value - 1
                elif op == "eq" and b.value == 1:
                    k = 1
        if k is not None and k >= 1:
            # the per-partition truncation IS the bound (le/lt/eq-1 all
            # keep exactly rows with rn <= k)
            limit = k
            continue
        rest.append(c)
    return limit, rest


def _scan_table(scan_factory) -> str:
    """Table name a TableScanOperatorFactory reads (for bucket lookup);
    scans keep a handle-producing connector but not the name directly,
    so it rides on the factory (set at construction)."""
    return scan_factory.table


def _coerce_to(expr: RowExpression, typ: T.Type) -> RowExpression:
    if expr.type == typ:
        return expr
    return B.cast(expr, typ)


def decompose_aggregates(aggregates: Sequence[PlanAggregate],
                         input_types: Sequence[T.Type]):
    """Aggregate specs -> primitive channels (the AccumulatorCompiler
    decomposition, shared by the operator and mesh lowerings).

    Returns (pre_exprs, agg_channels, finalize_specs): ``pre_exprs`` is the
    pre-projection (identity refs plus any derived channels such as x*x for
    sumsq); a pre-projection is needed iff len(pre_exprs) > len(input_types).
    """
    pre_exprs: List[RowExpression] = [
        InputRef(i, t) for i, t in enumerate(input_types)]
    agg_channels: List[AggChannel] = []
    finalize_specs: List[Tuple[PlanAggregate, List[int]]] = []
    for agg in aggregates:
        comp_channels: List[int] = []
        for prim, ctype in agg.spec.components:
            if agg.channel is None:
                agg_channels.append(AggChannel("count", None, ctype))
                comp_channels.append(len(agg_channels) - 1)
                continue
            in_ref = InputRef(agg.channel, input_types[agg.channel])
            if prim == "sumsq":
                sq = B.call("multiply", in_ref, in_ref)
                pre_exprs.append(_coerce_to(sq, ctype))
                ch = len(pre_exprs) - 1
                agg_channels.append(AggChannel("sum", ch, ctype))
            elif prim in ("sum", "min", "max", "count"):
                arg = in_ref
                if prim == "sum" and arg.type != ctype:
                    pre_exprs.append(_coerce_to(arg, ctype))
                    ch = len(pre_exprs) - 1
                else:
                    ch = agg.channel
                agg_channels.append(AggChannel(prim, ch, ctype))
            elif prim in ("collect", "hll", "kll"):
                agg_channels.append(
                    AggChannel(prim, agg.channel, ctype))
            elif prim == "sumln":
                ln = B.call("ln", _coerce_to(in_ref, T.DOUBLE))
                pre_exprs.append(ln)
                agg_channels.append(
                    AggChannel("sum", len(pre_exprs) - 1, ctype))
            elif prim == "sumhash":
                h = B.call("hash64", in_ref)
                pre_exprs.append(h)
                agg_channels.append(
                    AggChannel("sum", len(pre_exprs) - 1, ctype))
            else:
                raise NotImplementedError(f"agg component {prim}")
            comp_channels.append(len(agg_channels) - 1)
        finalize_specs.append((agg, comp_channels))
    return pre_exprs, agg_channels, finalize_specs


def merge_agg_channels(aggregates: Sequence[PlanAggregate], ngroups: int):
    """FINAL-step channels: re-aggregate each partial component with its
    merge primitive (HashAggregationOperator.Step:61 role)."""
    agg_channels: List[AggChannel] = []
    finalize_specs: List[Tuple[PlanAggregate, List[int]]] = []
    comp_ch = ngroups
    for agg in aggregates:
        comp_channels: List[int] = []
        for prim, ctype in agg.spec.components:
            merge = PhysicalPlanner._FINAL_PRIM[
                prim if prim != "sumsq" else "sum"]
            agg_channels.append(AggChannel(merge, comp_ch, ctype))
            comp_channels.append(len(agg_channels) - 1)
            comp_ch += 1
        finalize_specs.append((agg, comp_channels))
    return agg_channels, finalize_specs


def _finalize(agg: PlanAggregate, comps: List[RowExpression]
              ) -> RowExpression:
    fin = agg.spec.finalize
    if fin == "identity":
        out = comps[0]
        if out.type != agg.spec.result_type:
            out = B.cast(out, agg.spec.result_type)
        return out
    if fin == "avg":
        s, c = comps
        if agg.spec.result_type.name == "double":
            return B.call("divide", _coerce_to(s, T.DOUBLE),
                          B.cast(c, T.DOUBLE))
        return B.call("divide", s, c)
    if fin == "map_agg":
        return B.call("map_from_entries", comps[0])
    if fin in ("min_by", "max_by"):
        return B.call(f"$rows_{fin}", comps[0])
    if fin == "approx_distinct":
        return B.call("$hll_cardinality", comps[0])
    if fin.startswith("approx_percentile:"):
        from presto_tpu.expr import functions as F
        from presto_tpu.expr.ir import Call

        p = float(fin.split(":", 1)[1])
        fn = F.resolve_kll_percentile(agg.spec.result_type, p)
        return Call("$kll_percentile", (comps[0],), fn.result_type, fn)
    if fin in ("corr", "covar_samp", "covar_pop", "regr_slope",
               "regr_intercept"):
        return B.call(f"$rows_{fin}", comps[0])
    if fin in ("learn_classifier", "learn_regressor"):
        return B.call(f"$rows_{fin}", comps[0])
    if fin == "geometric_mean":
        s, n = comps
        return B.call("exp", B.call("divide", s, B.cast(n, T.DOUBLE)))
    if fin in ("stddev_samp", "stddev_pop", "var_samp", "var_pop"):
        s, sq, n = comps
        nd = B.cast(n, T.DOUBLE)
        mean_sq = B.call("divide", B.call("multiply", s, s), nd)
        num = B.call("subtract", sq, mean_sq)
        if fin.endswith("_pop"):
            var = B.call("divide", num, nd)
        else:
            var = B.call("divide", num,
                         B.call("subtract", nd, B.const(1.0, T.DOUBLE)))
        if fin.startswith("stddev"):
            return B.call("sqrt", var)
        return var
    raise NotImplementedError(f"finalize {fin}")


def _extract_constraints(filters, column_names):
    """RowExpression conjuncts -> TupleDomain-lite (col, op, literal)
    triples for Connector.prune_splits.  Only simple comparisons and IN
    over a bare input channel qualify; everything else is ignored (the
    row-level filter still applies)."""
    from presto_tpu.expr.ir import Call, Constant, SpecialForm

    def fold(e):
        """Fold literal-only subtrees (e.g. cast(1:integer) from IN-list
        coercion) to a Constant by evaluating on a zero-channel row."""
        if isinstance(e, Constant) or any(
                isinstance(x, InputRef) for x in _walk(e)):
            return e
        try:
            from presto_tpu.batch import Batch
            from presto_tpu.expr.compile import evaluate

            col = evaluate(e, Batch((), 1))
            if col.valid is not None and not bool(col.valid[0]):
                return Constant(None, e.type)
            v = col.values[0]
            if col.dictionary is not None:
                v = col.dictionary.values[int(v)]
            return Constant(v.item() if hasattr(v, "item") else v, e.type)
        except Exception:
            return e

    conjuncts = []
    stack = list(filters)
    while stack:
        e = stack.pop()
        if isinstance(e, SpecialForm) and e.form == "AND":
            stack.extend(e.args)
        else:
            conjuncts.append(e)
    flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
            "eq": "eq", "ne": "ne"}
    out = []
    for c in conjuncts:
        if isinstance(c, Call) and c.name in flip and len(c.args) == 2:
            a, b = (fold(x) for x in c.args)
            if isinstance(a, InputRef) and isinstance(b, Constant) \
                    and b.value is not None:
                out.append((column_names[a.index], c.name, b.value))
            elif isinstance(b, InputRef) and isinstance(a, Constant) \
                    and a.value is not None:
                out.append((column_names[b.index], flip[c.name], a.value))
        elif isinstance(c, SpecialForm) and c.form == "IN" and c.args:
            v = c.args[0]
            items = [fold(i) for i in c.args[1:]]
            if isinstance(v, InputRef) and all(
                    isinstance(i, Constant) and i.value is not None
                    for i in items):
                out.append((column_names[v.index], "in",
                            tuple(i.value for i in items)))
    return out


def _walk(e):
    yield e
    for a in getattr(e, "args", ()):
        yield from _walk(a)
