"""Pipeline fusion: one jitted XLA program per run of row-local operators.

The reference's performance tier is runtime code generation — its
``ExpressionCompiler``/``PageProcessor`` fuse a filter and all its
projections into one generated loop per page (survey §2.7).  The engine
already matches the intra-operator half (``FilterProjectOperator`` jits
filter+projections together), but a fragment still executed as a chain of
independently-jitted dispatches with a Python driver hop between every
adjacent operator pair, so intermediates round-tripped through HBM (and
sometimes host) at each hop.

This module is the cross-operator generalization: at fragment-lowering
time ``fuse_pipelines`` identifies maximal runs of adjacent row-local,
jit-able operator factories —

- chained ``FilterProject``s (stacked optimizer Projects, join residuals,
  aggregation finalize projections),
- dynamic-filter application (``DynamicFilterOperator``),
- the partial-aggregation input projection (an ordinary FilterProject),
- the hash/partition-id computation feeding ``PartitionedOutputOperator``

— and compiles each run into ONE jitted segment program executed once per
batch.  Inside a segment, consecutive filters combine into one
accumulated mask with a single gather at the end, projection
intermediates never materialize (XLA fuses the elementwise chains), and
the exchange sink's partition ids ride along as one extra output.

Scan-adjacent segments additionally take over the scan staging (the
``ScanFilterAndProjectOperator`` role): the scan hands over raw host
batches and the segment coalesces them up to ``scan_batch_rows`` before
staging + dispatching once, so many tiny per-split batches cost one
launch instead of one each.  Dictionary columns are re-coded into a
per-operator target dictionary so coalesced flushes share one compiled
program.  Segments fed by a remote exchange coalesce the same way
(pages arrive host-side and small), so exchange-fed probe sides stop
dispatching once per tiny page.

Fusion II — in-segment partial-aggregation pre-reduce: a segment that
feeds a partial or single-step ``HashAggregationOperator`` /
``GlobalAggregationOperator`` (device prims only, bounded-domain group
keys) absorbs the per-batch accumulate into the program itself: the
jitted kernel masks, projects, and group-accumulates (via
ops.groupby's segment kernels, no compaction — the filter rides as the
live mask) before anything materializes, emitting partial-state
batches (keys + component columns) instead of row batches.  The
reference avoids the same materialization by pushing the partial
``HashAggregationOperator.Step`` into the generated scan loop
(HashAggregationOperator.java:48).  Downstream, a single-step
aggregation is replaced by its merge form (MERGE_PRIM re-aggregation
of the tiny partials, filter-less finalize projection folded into the
aggregation finish); a partial-step aggregation is dropped outright —
the FINAL stage's merge already accepts partials at any granularity.

Segment programs are cached globally (``kernelcache``) keyed by segment
expression keys + capacity bucket + dictionary binding (token, length) +
the dynamic-filter value shape — the same keying discipline as
``_FP_KERNELS``.

The segment grammar reaches three ways further (see exec/README.md
"Device-resident hash tier"): residual-free inner/semi/anti LookupJoin
probes absorb as ``ProbeStage`` so filter -> project -> probe ->
partial-agg chains are one dispatch; grouped FINAL merges directly on a
remote exchange absorb into empty-stage coalescing segments; and the
pre-reduce decision is cost-based — plan-time NDV hints plus a runtime
observed-ratio switch to raw partial-state emission when grouping stops
reducing.

What breaks a segment: any non-row-local operator (aggregation — except
an absorbed one, join — except an absorbed probe, sort, exchange,
limit), expressions that need the host path (nested types, row-wise
string fallbacks), and nested input/output types.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, Dictionary, next_bucket
from presto_tpu.exec.aggregation import (
    MERGE_PRIM, AggChannel, GlobalAggregationOperatorFactory,
    HashAggregationOperatorFactory,
)
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.dynamicfilter import (
    DynamicFilter, DynamicFilterOperatorFactory,
)
from presto_tpu.exec.operator import Operator, OperatorFactory, column_pairs
from presto_tpu.exec.operators import (
    FilterProjectOperatorFactory, TableScanOperatorFactory,
    dictionary_binding_key,
)
from presto_tpu.expr.compile import ExprCompiler, needs_host_path
from presto_tpu.expr.ir import RowExpression
from presto_tpu import kernelcache
from presto_tpu.kernelcache import cache_get, cache_put, new_cache
from presto_tpu.spans import activity

# compiled segment programs, shared globally across queries/operators
_SEG_KERNELS = new_cache("fused_segment")

#: the program name of a segment, by what it absorbed (join probes, a
#: partial aggregation that is not passed through raw): a device trace
#: then says whether a `while` loop is a probe's or a group-by's
_SEGMENT_PROGRAM = {(False, False): "fused_segment",
                    (True, False): "fused_segment_probe",
                    (False, True): "fused_segment_agg",
                    (True, True): "fused_segment_probe_agg"}

# learned inner-probe expansion buckets, shared ACROSS queries: keyed by
# (segment expr key, probe stage index, input capacity), monotonic max.
# A fresh operator re-learning its bucket per execution would oscillate
# between capacity variants (arrival-order nondeterminism decides which
# batch overflows first) and churn one compiled program per variant per
# query; the sticky global bucket converges once and stays.
_OUT_CAPS_LEARNED: dict = {}


# ---------------------------------------------------------------------------
# segment stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FPStage:
    """One filter+projections step (a FilterProjectOperator's work)."""

    filter_expr: Optional[RowExpression]
    projections: Tuple[RowExpression, ...]
    input_types: Tuple[T.Type, ...]

    def key(self) -> tuple:
        return ("fp", self.filter_expr, self.projections, self.input_types)


@dataclasses.dataclass(frozen=True, eq=False)
class DFStage:
    """Dynamic-filter application over the current channel space.

    The filter VALUES (bounds, IN-set tables) are runtime kernel
    arguments, never trace constants; only the value *shape* (which
    channels are bounded, which have exact sets) keys the program.
    Adaptive shutoff is intentionally absent: it existed to avoid an
    extra per-batch dispatch, and inside a fused segment the filter
    costs no extra launch.
    """

    dyn: DynamicFilter
    key_channels: Tuple[int, ...]

    def key(self) -> tuple:
        return ("df", self.key_channels)


@dataclasses.dataclass(frozen=True, eq=False)
class ProbeStage:
    """An absorbed residual-free LookupJoin probe:
    the probe primitive runs INSIDE the segment program — the way
    ``segment_pre_reduce`` absorbed partial aggregation — so
    filter -> project -> probe -> partial-agg chains cost one dispatch.

    The build side's table (PagesHash layout, ops/hashtable.py) and data
    columns ride as RUNTIME kernel arguments, never trace constants;
    the program is keyed by the build's shape/binding, so identical
    queries share one executable.  semi/anti probes fold into the
    accumulated mask (no expansion); inner probes expand the row space
    (probe-gather + build-gather) under a static output capacity with
    host retry on overflow — the same policy every expansion kernel in
    ops/join.py uses.
    """

    factory: object                # LookupJoinOperatorFactory

    def key(self) -> tuple:
        f = self.factory
        return ("probe", f.join_type, tuple(f.probe_key_channels),
                f.null_aware, tuple(f.probe_types),
                tuple(f.build.input_types))


def _stage_of(factory) -> object:
    if isinstance(factory, FilterProjectOperatorFactory):
        return FPStage(factory.filter_expr, tuple(factory.projections),
                       tuple(factory.input_types))
    if isinstance(factory, DynamicFilterOperatorFactory):
        return DFStage(factory.dyn, tuple(factory.key_channels))
    from presto_tpu.exec.joinop import LookupJoinOperatorFactory

    if isinstance(factory, LookupJoinOperatorFactory):
        return ProbeStage(factory)
    raise TypeError(f"not a fusable factory: {type(factory).__name__}")


def _fp_jitable(f: FilterProjectOperatorFactory) -> bool:
    """True when the stage can run inside a jitted segment (mirrors the
    FilterProjectOperator host-path eligibility, decided statically)."""
    if needs_host_path([f.filter_expr] + list(f.projections)):
        return False
    if any(t.is_nested for t in f.input_types):
        return False
    if any(p.type.is_nested for p in f.projections):
        return False
    return True


def _probe_absorbable(f, config) -> bool:
    """May this LookupJoin probe run inside a segment?  Residual-free
    inner/semi/anti only; left-outer keeps its operator (its unmatched
    emission interacts with downstream outer-composition paths).
    Grouped execution keeps per-bucket probe operators so Lifespan
    memory retirement stays observable."""
    if config.grouped_execution_buckets > 1:
        return False
    if f.join_type not in ("inner", "semi", "anti"):
        return False
    if f.residual is not None:
        return False
    if any(t.is_nested for t in f.probe_types):
        return False
    if f.join_type == "inner" and any(t.is_nested
                                      for t in f.build.input_types):
        return False
    return True


def _fusable(f, config) -> bool:
    if isinstance(f, DynamicFilterOperatorFactory):
        return True
    if isinstance(f, FilterProjectOperatorFactory):
        return _fp_jitable(f)
    from presto_tpu.exec.joinop import LookupJoinOperatorFactory

    if isinstance(f, LookupJoinOperatorFactory):
        return _probe_absorbable(f, config)
    return False


@dataclasses.dataclass(frozen=True)
class PreReduceSpec:
    """In-segment partial-aggregation pre-reduce (Fusion II).

    ``group_channels``/``aggs`` index the SEGMENT's output channel
    space (== the absorbed aggregation's input space); the segment then
    emits the partial schema [key columns..., one state column per
    aggregation].  ``key_types`` are the group-key output types (kept
    for describe()); ``global_`` marks the ungrouped form, which emits
    exactly one partial row per dispatched batch plus a default row at
    finish when nothing was dispatched (a task must never contribute
    zero partial rows — the merge's count-sum would yield NULL where
    COUNT over empty input is 0).
    """

    group_channels: Tuple[int, ...]
    aggs: Tuple[AggChannel, ...]
    key_types: Tuple[T.Type, ...]
    global_: bool

    def key(self) -> tuple:
        return ("prereduce", self.group_channels, self.global_,
                tuple((a.prim, a.channel, a.out_type) for a in self.aggs))


def _sort_groupable(t: T.Type) -> bool:
    """Key types the in-segment sort-path pre-reduce can normalize to
    int64 words (ops/keys.py); plain varchar (no dictionary) cannot."""
    return bool(t.is_dictionary or T.is_integral(t)
                or t.name in ("boolean", "double", "real", "date",
                              "timestamp")
                or isinstance(t, T.DecimalType))


def _segment_out_types(stages) -> Optional[List[T.Type]]:
    """The segment's output channel types, walked through the stages:
    FP stages remap channels to their projection types, inner probe
    stages append the build channels, semi/anti probes keep the probe
    space (DF stages filter rows, never remap channels)."""
    types: Optional[List[T.Type]] = None
    for s in stages:
        if isinstance(s, FPStage):
            types = [p.type for p in s.projections]
        elif isinstance(s, ProbeStage):
            f = s.factory
            base = list(f.probe_types) if types is None else types
            types = (base + list(f.build.input_types)
                     if f.join_type == "inner" else base)
    return types


def _try_pre_reduce(stages, factory, config, out_types=None,
                    relax_keys=False):
    """When ``factory`` (the operator the run feeds) is an eligible
    aggregation, return ``(spec, replacement)``: the pre-reduce spec the
    segment absorbs and the downstream factory that replaces the
    aggregation — a merge-form aggregation for single/final steps, or
    None for the partial step (the FINAL stage's merge accepts partials
    at any granularity, so the partial operator is dropped outright).

    Eligibility: device prims only (sum/count/min/max — collect-style
    accumulators need the host path), no min/max over dictionary inputs
    (their partial state would be interning codes, not values), and
    every group key dictionary-coded or boolean so the per-batch
    reduction can take the bounded-domain direct path (unbounded keys
    would make per-batch pre-reduce a pessimization: as many groups as
    rows, nothing reduced) — ``relax_keys`` lifts that last rule for
    exchange-fed FINAL merges, whose input is already pre-reduced
    (duplication factor = producer count) and which the cost-based
    raw-emission switch protects at runtime.  A plan-time NDV estimate
    (``factory.prereduce_ratio_hint`` from the memo's stats tier) skips
    pre-reduce outright when estimated groups approach input rows.
    Returns (None, None) when ineligible.
    """
    is_hash = isinstance(factory, HashAggregationOperatorFactory)
    is_global = isinstance(factory, GlobalAggregationOperatorFactory)
    if not (is_hash or is_global):
        return None, None
    if out_types is None:
        out_types = _segment_out_types(stages)
    if out_types is None or len(out_types) != len(factory.input_types):
        return None, None
    if is_hash:
        hint = getattr(factory, "prereduce_ratio_hint", None)
        if (hint is not None
                and hint > config.prereduce_max_group_fraction):
            return None, None
    for a in factory.aggs:
        if a.prim not in MERGE_PRIM:
            return None, None
        if a.channel is not None:
            if a.channel >= len(out_types):
                return None, None
            if out_types[a.channel].is_nested:
                return None, None
            if a.prim in ("min", "max") \
                    and out_types[a.channel].is_dictionary:
                return None, None
    groups = tuple(factory.group_channels) if is_hash else ()
    if is_hash:
        if not groups:
            return None, None
        for g in groups:
            t = out_types[g]
            if t.is_nested:
                return None, None
            if not relax_keys and not (t.is_dictionary
                                       or t.name == "boolean"):
                return None, None
            if relax_keys and not _sort_groupable(t):
                return None, None
    spec = PreReduceSpec(groups, tuple(factory.aggs),
                         tuple(out_types[g] for g in groups), is_global)
    step = getattr(factory, "step", "single")
    if step == "partial":
        return spec, None
    k = len(groups)
    partial_types = ([out_types[g] for g in groups]
                     + [a.out_type for a in factory.aggs])
    merge_aggs = [AggChannel(MERGE_PRIM[a.prim], k + i, a.out_type)
                  for i, a in enumerate(factory.aggs)]
    if is_hash:
        replacement = HashAggregationOperatorFactory(
            list(range(k)), merge_aggs, partial_types)
    else:
        replacement = GlobalAggregationOperatorFactory(
            merge_aggs, partial_types)
    replacement.step = step
    return spec, replacement


def _exchange_adjacent(prev) -> bool:
    """True when ``prev`` is a remote-exchange source whose pages the
    segment should coalesce (they arrive host-side and page-sized)."""
    try:
        from presto_tpu.server.exchangeop import (
            ExchangeOperatorFactory, MergeExchangeOperatorFactory,
        )
    except Exception:  # noqa: BLE001 - server tier absent in slim envs
        return False
    return isinstance(prev, (ExchangeOperatorFactory,
                             MergeExchangeOperatorFactory))


def _partition_spec(sink) -> Optional[Tuple[Tuple[int, ...], int]]:
    """(channels, n_partitions) when ``sink`` is a hash-partitioned
    output whose partition ids a segment can precompute."""
    try:
        from presto_tpu.server.exchangeop import (
            PartitionedOutputOperatorFactory,
        )
    except Exception:  # noqa: BLE001 - server tier absent in slim envs
        return None
    if (isinstance(sink, PartitionedOutputOperatorFactory)
            and sink.n_partitions > 1 and sink.channels):
        return (tuple(sink.channels), sink.n_partitions)
    return None


# ---------------------------------------------------------------------------
# the fusion pass
# ---------------------------------------------------------------------------

def _try_final_merge(factory, prev, config):
    """FINAL-merge fusion: a grouped merge aggregation fed DIRECTLY by
    a remote exchange absorbs into an empty-stage coalescing segment —
    partial pages batch up to scan_batch_rows and merge-accumulate in
    ONE dispatch per flush, with the finalize projections folded into
    the downstream merge's finish.  Global merges stay unfused: their
    empty-input default row must come from the original prims, which
    the merge form no longer names.  Returns (spec, replacement) or
    (None, None)."""
    if not isinstance(factory, HashAggregationOperatorFactory):
        return None, None
    if not _exchange_adjacent(prev):
        return None, None
    return _try_pre_reduce([], factory, config,
                           out_types=list(factory.input_types),
                           relax_keys=True)


def fuse_chain(factories: List[OperatorFactory], config
               ) -> List[OperatorFactory]:
    """Replace maximal runs of fusable factories with FusedSegment
    factories.  A run fuses when it is ≥ 2 operators, rides directly on
    a device-staging TableScan (scan coalescing) or a remote exchange
    (page coalescing), feeds a hash-partitioned output (partition-id
    fusion), or feeds an eligible aggregation (partial-agg pre-reduce);
    it must contain at least one FilterProject or absorbed-probe stage
    (the segment's type anchor).  An eligible merge aggregation sitting
    DIRECTLY on a remote exchange absorbs without any run at all (the
    FINAL-merge segment)."""
    result: List[OperatorFactory] = []
    n = len(factories)
    i = 0
    while i < n:
        if not _fusable(factories[i], config):
            spec, replacement = _try_final_merge(
                factories[i], result[-1] if result else None, config)
            if spec is not None and replacement is not None:
                consumed = i + 1
                post_stages = []
                while (consumed < n
                        and isinstance(factories[consumed],
                                       FilterProjectOperatorFactory)
                        and factories[consumed].filter_expr is None):
                    post_stages.append(
                        list(factories[consumed].projections))
                    consumed += 1
                if post_stages:
                    replacement.post_projections = post_stages
                result.append(FusedSegmentOperatorFactory(
                    [], coalesce_rows=config.scan_batch_rows,
                    partition_spec=None,
                    min_batch_capacity=config.min_batch_capacity,
                    agg_spec=spec))
                result.append(replacement)
                i = consumed
                continue
            result.append(factories[i])
            i += 1
            continue
        j = i
        while j < n and _fusable(factories[j], config):
            j += 1
        run = factories[i:j]
        stages = [_stage_of(f) for f in run]
        has_fp = any(isinstance(s, (FPStage, ProbeStage))
                     for s in stages)
        scan = (result[-1] if result
                and isinstance(result[-1], TableScanOperatorFactory)
                and result[-1].to_device else None)
        exch = bool(result) and _exchange_adjacent(result[-1])
        # in-segment partial-aggregation pre-reduce: the run's output
        # feeds an eligible aggregation -> absorb its per-batch
        # accumulate; the aggregation becomes its merge form (or, for
        # the partial step, disappears — the FINAL merge takes over)
        spec = replacement = None
        consumed = j
        if has_fp and j < n:
            spec, replacement = _try_pre_reduce(stages, factories[j],
                                                config)
            if spec is not None:
                consumed = j + 1
                post_stages = []
                while (replacement is not None and consumed < n
                        and isinstance(factories[consumed],
                                       FilterProjectOperatorFactory)
                        and factories[consumed].filter_expr is None):
                    # fold the finalize projection run into the merge
                    # aggregation's finish: group-sized output, host
                    # vector math beats one more program launch per
                    # stacked projection
                    post_stages.append(
                        list(factories[consumed].projections))
                    consumed += 1
                if post_stages:
                    replacement.post_projections = post_stages
        partition = None
        if spec is None or replacement is None:
            # the segment's own output reaches the next factory (no
            # merge aggregation in between): partition-id fusion may
            # apply — including over pre-reduced partial rows feeding a
            # partial fragment's exchange sink
            partition = (_partition_spec(factories[consumed])
                         if consumed < n else None)
        if not has_fp or (len(run) < 2 and scan is None and not exch
                          and partition is None and spec is None):
            result.extend(run)
            i = j
            continue
        for s in stages:
            if isinstance(s, ProbeStage):
                # the resident build side must stay resident: a spilled
                # build would take the probe out of the segment's reach
                # mid-query (the broadcast-join stance)
                s.factory.build.allow_spill = False
        coalesce_rows = 0
        if scan is not None:
            # the segment takes over staging: the scan now hands over
            # raw host batches (ScanFilterAndProjectOperator role)
            result[-1] = TableScanOperatorFactory(
                scan.connector, scan.columns, scan.batch_rows,
                to_device=False, table=scan.table)
            coalesce_rows = config.scan_batch_rows
        elif exch:
            coalesce_rows = config.scan_batch_rows
        if partition is not None:
            factories[consumed].precomputed = True
        result.append(FusedSegmentOperatorFactory(
            stages, coalesce_rows=coalesce_rows, partition_spec=partition,
            min_batch_capacity=config.min_batch_capacity,
            agg_spec=spec))
        if replacement is not None:
            result.append(replacement)
        i = consumed if spec is not None else j
    return result


def fuse_pipelines(pipelines: Sequence, config) -> None:
    """Apply the fusion pass to every lowered pipeline, in place.  Runs
    after all lowering decisions (streaming-agg eligibility, grouped
    execution, dynamic-filter placement) were made on the unfused
    chains."""
    for p in pipelines:
        p.factories = fuse_chain(p.factories, config)


# ---------------------------------------------------------------------------
# the fused operator
# ---------------------------------------------------------------------------

class _ColView:
    """values/valid/type/dictionary holder for ops.hashing inside a
    traced segment program."""

    __slots__ = ("values", "valid", "type", "dictionary")

    def __init__(self, values, valid, typ, dictionary):
        self.values = values
        self.valid = valid
        self.type = typ
        self.dictionary = dictionary


def _partition_ids(outs, out_meta, partition):
    """The sink's partition id of every output row, inside a traced
    program: ``partition`` is ``_partition_spec``'s (channels, n)."""
    from presto_tpu.ops.hashing import (
        partition_of, row_hash, value_hash_triple,
    )

    channels, nparts = partition
    triples = []
    for ch in channels:
        v, valid = outs[ch]
        typ, d = out_meta[ch]
        triples.append(value_hash_triple(_ColView(v, valid, typ, d)))
    return partition_of(row_hash(triples), nparts)


class FusedSegmentOperator(Operator):
    """Executes a fused run of row-local stages as one jitted program per
    batch; optionally coalesces host scan batches first."""

    def __init__(self, ctx: OperatorContext, stages: Sequence,
                 coalesce_rows: int, partition_spec, min_batch_capacity,
                 agg_spec: Optional[PreReduceSpec] = None, scan_fill=None):
        super().__init__(ctx)
        # the scan cache's record of what this segment stages for its
        # scan (exec/scancache.py ScanFill); None off a cached table
        self._scan_fill = scan_fill
        self.stages = list(stages)
        self.partition_spec = partition_spec
        self.agg_spec = agg_spec
        # the bounded-domain direct-vs-sort decision is made at trace
        # time against this threshold; programs are shared globally, so
        # the threshold is part of the cache key
        self._max_domain = int(getattr(
            ctx.config, "direct_groupby_max_domain", 1 << 12))
        key_parts: tuple = tuple(s.key() for s in stages)
        if agg_spec is not None:
            key_parts = key_parts + (agg_spec.key(), self._max_domain)
        self._expr_key = key_parts
        self._coalesce = int(coalesce_rows)
        self._min_capacity = int(min_batch_capacity)
        self._pending: Optional[Batch] = None     # device-batch path
        self._emitted_any = False
        # absorbed-probe state: build-source snapshots resolve lazily at
        # first dispatch (the build pipeline has finished by then);
        # learned expansion capacities per inner probe stage persist
        # across batches (overflow bumps them once, then they stick)
        self._probe_idx = [k for k, s in enumerate(stages)
                           if isinstance(s, ProbeStage)]
        self._probe_srcs: Optional[list] = None
        self._out_caps: dict = {}
        # cost-based pre-reduce: flipped True when the observed
        # groups/rows ratio says per-batch grouping is not reducing
        self._raw_emit = False
        # bounded pre-reduce: each dispatched batch's partial states stay
        # on the device, (outs, count, parts) as the program returned
        # them, until finish or partial_agg_max_bytes; _held_form says
        # what the held partials share (None before the first)
        self._held: List[tuple] = []
        self._held_form: Optional[tuple] = None
        self._held_meta: Optional[list] = None
        self._partial_bytes = 0
        self._held_bytes = 0
        # held partials a dispatch of another form merged out of its way
        self._ready: List[Batch] = []
        # host-coalescing path state
        self._acc: List[List[tuple]] = []          # per-flush batch parts
        self._acc_rows = 0
        self._full_cap = 0     # bucket of a full flush, once one happened
        self._targets: Optional[List[Optional[Dictionary]]] = None
        self._col_types: Optional[List[T.Type]] = None

    # -- protocol --------------------------------------------------------
    def needs_input(self) -> bool:
        if self._finishing:
            return False
        if self._coalesce:
            return self._acc_rows < self._coalesce
        return self._pending is None

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_batches += 1
        self.ctx.stats.input_rows += batch.num_rows
        if not self._coalesce:
            self._pending = batch
            return
        batch = batch.to_numpy()
        with activity("stage_h2d"):     # the segment stages for its scan
            self._accumulate(batch)

    def get_output(self) -> Optional[Batch]:
        if self._ready:
            return self._emit(self._ready.pop())
        if self._coalesce:
            if self._acc_rows >= self._coalesce or (
                    self._finishing and self._acc_rows > 0):
                passthrough = self._passthrough_ok()
                with activity("stage_h2d"):
                    batch = self._flush()
                    if self._scan_fill is not None:
                        batch = self._scan_fill.stage(batch)
                if passthrough:
                    return self._emit(batch.compact())
                return self._dispatch(batch)
        elif self._pending is not None:
            batch, self._pending = self._pending, None
            return self._dispatch(batch)
        if self._finishing:
            if self._held:
                return self._emit(self._flush_held())
            if self._needs_default_row():
                return self._emit(self._default_partial_batch())
        return None

    # a FINAL-merge segment flush below this many rows skips its own
    # dispatch: the rows pass through AS partial states (identity — the
    # segment has no stages and its input/output schemas coincide) and
    # the downstream merge pays exactly what a merge aggregation fed by
    # the exchange alone pays.  Pre-reducing a tiny flush costs a full
    # program launch to save the merge almost nothing; at real exchange
    # volumes the flush crosses the bound and the in-segment
    # merge-accumulate wins.
    _PASSTHROUGH_ROWS = 8192

    def _passthrough_ok(self) -> bool:
        return (not self.stages and self.agg_spec is not None
                and not self.agg_spec.global_
                and self._acc_rows < self._PASSTHROUGH_ROWS)

    def _emit(self, out: Optional[Batch],
              rows: Optional[int] = None) -> Optional[Batch]:
        """Account for a batch that leaves: ``rows`` of it are live
        where the program left dead rows among them for the sink."""
        if out is None:
            return None
        self._emitted_any = True
        self.ctx.stats.output_batches += 1
        self.ctx.stats.output_rows += out.num_rows if rows is None else rows
        return out

    def _needs_default_row(self) -> bool:
        """A global pre-reduce segment that dispatched nothing still owes
        one default partial row (count=0, other states NULL): the merge
        aggregation's count components re-aggregate with 'sum', and SUM
        over zero partial rows is NULL where COUNT over empty is 0.  A
        held partial counts as emitted: it leaves at finish."""
        return (self.agg_spec is not None and self.agg_spec.global_
                and not self._emitted_any)

    def _default_partial_batch(self) -> Batch:
        cols = []
        for a in self.agg_spec.aggs:
            if a.prim == "count":
                cols.append(Column(a.out_type, np.zeros(1, np.int64)))
            else:
                dictionary = (Dictionary()
                              if a.out_type.is_dictionary else None)
                cols.append(Column(a.out_type,
                                   np.zeros(1, a.out_type.np_dtype),
                                   np.zeros(1, bool), dictionary))
        return Batch(tuple(cols), 1)

    def is_finished(self) -> bool:
        return self._finishing and self._pending is None \
            and self._acc_rows == 0 and not self._held \
            and not self._ready and not self._needs_default_row()

    # -- host coalescing (scan-adjacent segments) ------------------------
    def _accumulate(self, batch: Batch) -> None:
        n = batch.num_rows
        if self._targets is None:
            # adopt the first batch's dictionaries as the per-operator
            # interning targets (append-only, so codes stay stable)
            self._targets = [c.dictionary for c in batch.columns]
            self._col_types = [c.type for c in batch.columns]
        parts = []
        for ci, c in enumerate(batch.columns):
            vals = np.asarray(c.values)[:n]
            target = self._targets[ci]
            if c.dictionary is not None and c.dictionary is not target:
                remap = c.dictionary.remap_into(target)
                if len(remap):
                    vals = remap[vals]
            valid = None if c.valid is None else np.asarray(c.valid)[:n]
            parts.append((vals, valid))
        self._acc.append(parts)
        self._acc_rows += n
        self._charge_memory()

    def _charge_memory(self) -> None:
        """What the segment keeps between calls: the host rows it is
        coalescing and the partials it holds on the device."""
        self.ctx.memory.set_bytes(
            sum(v.nbytes for p in self._acc for v, _ in p)
            + self._held_bytes)

    def _flush(self) -> Batch:
        ncols = len(self._col_types)
        rows = self._acc_rows
        cols = []
        for ci in range(ncols):
            vals = np.concatenate([p[ci][0] for p in self._acc]) \
                if len(self._acc) > 1 else self._acc[0][ci][0]
            valids = [p[ci][1] for p in self._acc]
            if any(v is not None for v in valids):
                valid = np.concatenate([
                    v if v is not None
                    else np.ones(p[ci][0].shape[0], bool)
                    for p, v in zip(self._acc, valids)])
            else:
                valid = None
            cols.append(Column(self._col_types[ci], vals, valid,
                               self._targets[ci]))
        self._acc = []
        self._acc_rows = 0
        batch = Batch(tuple(cols), rows)
        cap = next_bucket(rows, self._min_capacity)
        if rows >= self._coalesce:
            # flush EXACTLY coalesce_rows and carry the rest, and pad this
            # operator's later (tail) flushes to the same bucket: one
            # program shape per segment instead of one per overshoot /
            # tail bucket.  Which bucket a tail lands in follows how
            # splits and exchange pages were dealt to this driver, so a
            # WARM TPC-H Q3 at SF1 met tail buckets (and compiled
            # programs) its cold run had not seen (PR 25).
            if rows > self._coalesce:
                cut = self._coalesce
                self._acc = [[(c.values[cut:],
                               None if c.valid is None else c.valid[cut:])
                              for c in batch.columns]]
                self._acc_rows = rows - cut
                batch = batch.head(cut)
            self._full_cap = cap = next_bucket(self._coalesce,
                                               self._min_capacity)
        self._charge_memory()
        return batch.pad_rows(max(cap, self._full_cap))

    # -- dispatch --------------------------------------------------------
    def _df_snapshot(self):
        """Per-DF-stage (shape, args): shape keys the program, args carry
        the values.  Returns None when an empty build makes the whole
        segment output empty (inner-join semantics)."""
        shapes, args = [], []
        for s in self.stages:
            if not isinstance(s, DFStage):
                continue
            dyn = s.dyn
            if not dyn.ready or dyn.disabled:
                shapes.append(("off",))
                args.append(((), ()))
                continue
            if dyn.build_empty:
                return None
            chans, has_set, bounds, tables = [], [], [], []
            for i, ch in enumerate(s.key_channels):
                if dyn.mins[i] is None:
                    continue
                chans.append(ch)
                st = dyn.sets[i]
                has_set.append(st is not None)
                bounds.append((np.asarray(dyn.mins[i]),
                               np.asarray(dyn.maxs[i])))
                if st is not None:
                    tables.append(st)
            shapes.append((tuple(chans), tuple(has_set)))
            args.append((tuple(bounds), tuple(tables)))
        return tuple(shapes), tuple(args)

    def _probe_snapshot(self):
        """Resolve (and cache) each absorbed probe's build source.  The
        program is keyed by the source's SHAPE (mode, capacities,
        dictionary binding); the arrays themselves ride as runtime
        kernel arguments, so identical queries share executables."""
        import jax.numpy as jnp

        if self._probe_srcs is None:
            srcs = []
            for k in self._probe_idx:
                src = self.stages[k].factory.build.lookup.get()
                if src.mode not in ("hash", "single", "packed"):
                    raise RuntimeError(
                        "absorbed join probe needs a streaming lookup "
                        f"source, got mode={src.mode!r}")
                srcs.append(src)
            # one tier per absorbed probe, in stage order, repeats merged
            self.ctx.stats.kernel_tier = "+".join(
                dict.fromkeys(src.kernel_tier for src in srcs))
            self._probe_srcs = srcs
        key_parts, args, metas = [], [], []
        for k, src in zip(self._probe_idx, self._probe_srcs):
            f = self.stages[k].factory
            out_cap = self._out_caps.get(k, 0)
            build_pairs = tuple(column_pairs(src.data))
            if src.mode == "hash":
                aux = (src.pages, src.perm)
                table_cap = src.pages[2].shape[0]
            else:
                if src.mode == "single":
                    ranges = (src.mins, jnp.zeros(1, jnp.int64),
                              jnp.zeros(1, jnp.int64))
                else:
                    ranges = (jnp.asarray(src.mins),
                              jnp.asarray(src.strides),
                              jnp.asarray(src.maxs))
                # a direct-address index (kernel tier "dense") rides in
                # place of the sorted ids; its slot count keys the program
                aux = (src.sorted_ids, src.perm) + ranges + (src.index,)
                table_cap = (src.index.shape[0]
                             if src.index is not None else 0)
            bstats = (jnp.asarray(src.n_build, jnp.int64),
                      src.has_null_key if src.has_null_key is not None
                      else jnp.zeros((), bool))
            key_parts.append((src.mode, src.data.capacity, table_cap,
                              dictionary_binding_key(src.data.columns),
                              out_cap))
            args.append((build_pairs, aux, bstats))
            metas.append({
                "mode": src.mode, "out_cap": out_cap,
                "join_type": f.join_type,
                "null_aware": f.null_aware,
                "key_channels": tuple(f.probe_key_channels),
                "key_types": src.key_types or (),
                "build_meta": [(c.type, c.dictionary)
                               for c in src.data.columns],
            })
        return tuple(key_parts), tuple(args), metas

    def _default_out_cap(self, capacity: int) -> int:
        """First expansion bucket for an inner probe: the probe space
        itself (exact for FK->PK joins, where every probe row matches
        at most one build row); duplicate-key builds overflow once,
        learn the bucket, and keep it."""
        return next_bucket(max(capacity, 1))

    def _dispatch(self, batch: Batch) -> Optional[Batch]:
        """One launch of the segment's program over ``batch``; what it
        returns has been accounted for by ``_emit``."""
        snap = self._df_snapshot()
        if snap is None:
            return None      # empty build: nothing can survive the join
        df_shapes, df_args = snap
        part_n = self.partition_spec[1] if self.partition_spec else 0
        cap = batch.capacity
        for k in self._probe_idx:
            if k not in self._out_caps:
                if self.stages[k].factory.join_type == "inner":
                    cap = max(self._default_out_cap(cap),
                              _OUT_CAPS_LEARNED.get(
                                  (self._expr_key, k, batch.capacity),
                                  0))
                    self._out_caps[k] = cap
                else:
                    self._out_caps[k] = 0
            else:
                cap = max(cap, self._out_caps[k] or cap)
        while True:
            probe_keys, probe_args, probe_metas = ((), (), [])
            if self._probe_idx:
                probe_keys, probe_args, probe_metas = \
                    self._probe_snapshot()
            key = (self._expr_key, batch.capacity,
                   dictionary_binding_key(batch.columns), df_shapes,
                   part_n, probe_keys, self._raw_emit)
            entry = cache_get(_SEG_KERNELS, key)
            if entry is None:
                import time as _time

                from presto_tpu.kernelcache import (
                    record_compile, timed_first_call,
                )

                _t0 = _time.perf_counter_ns()
                built_fn, *built_meta = self._compile(batch, df_shapes,
                                                      probe_metas)
                build_ns = _time.perf_counter_ns() - _t0
                self.ctx.stats.jit_compile_ns += build_ns
                record_compile(_SEG_KERNELS, build_ns)
                entry = (timed_first_call(built_fn, self.ctx.stats,
                                          _SEG_KERNELS), *built_meta)
                cache_put(_SEG_KERNELS, key, entry)
                self.ctx.stats.jit_compiles += 1
            fn, out_meta, traced = entry
            self.ctx.stats.jit_dispatches += 1
            with activity("dispatch"):
                outs, count, parts, etotals = fn(
                    tuple(column_pairs(batch)), batch.num_rows, df_args,
                    probe_args)
            # how the program that ran ends, as its trace recorded it
            compaction = traced.get("compaction")
            if compaction == "done":
                self.ctx.stats.compactions += 1
            elif compaction is not None:
                self.ctx.stats.compactions_skipped += 1
            # expansion-overflow retry: bump the learned bucket for any
            # inner probe whose exact total exceeded its capacity and
            # re-dispatch (ops/join.py's host-retry policy, in-segment)
            overflowed = False
            for k, total in zip(
                    (k for k in self._probe_idx
                     if self.stages[k].factory.join_type == "inner"),
                    etotals):
                with activity("device_wait"):
                    t = int(total)
                if t > self._out_caps[k]:
                    self._out_caps[k] = next_bucket(t)
                    lk = (self._expr_key, k, batch.capacity)
                    _OUT_CAPS_LEARNED[lk] = max(
                        _OUT_CAPS_LEARNED.get(lk, 0), self._out_caps[k])
                    overflowed = True
            if not overflowed:
                break
        form = None
        if self.agg_spec is not None and not self._raw_emit:
            self.ctx.stats.prereduce_rows += batch.num_rows
            form = self._bounded_form(out_meta, traced, outs)
        if self._held and form != self._held_form:
            merged = self._flush_held()
            if merged is not None:
                self._ready.append(merged)
        if form is not None:
            return self._emit(self._hold(form, out_meta, outs, count, parts))
        with activity("device_wait"):
            n = int(count)
        self._observe_reduction(batch.num_rows, n)
        if n == 0:
            return None
        # rows left for the sink to cut: every row of the program's
        # output goes, and the partition ids say which n are live
        rows = outs[0][0].shape[0] if compaction == "sink" else n
        return self._emit(
            self._partial_batch(out_meta, outs, parts, rows), n)

    @staticmethod
    def _partial_batch(out_meta, outs, parts, n: int) -> Batch:
        cols = tuple(Column(typ, v, valid, d)
                     for (typ, d), (v, valid) in zip(out_meta, outs))
        if parts is not None:
            cols = cols + (Column(T.INTEGER, parts),)
        return Batch(cols, n)

    # -- held partials (bounded pre-reduce) ------------------------------
    def _bounded_form(self, out_meta, traced, outs) -> Optional[tuple]:
        """What a pre-reduced dispatch's partial shares with others it
        can be held and merged with, or None where its size is not
        bounded at trace time (the sort path) and it leaves as it comes.
        Bounded are the global form (one row) and the direct path (at
        most ``direct_groupby_max_domain`` rows, ``traced["doms"]`` as
        the trace recorded them).  Two direct partials merge when their
        key codes mean the same: equal domains and nullability, equal
        dictionary content."""
        if self.agg_spec.global_:
            return ("global",)
        if "doms" not in traced:
            return None
        k = len(self.agg_spec.group_channels)
        return (traced["doms"],
                tuple(valid is not None for _v, valid in outs[:k]),
                tuple(None if d is None else (d.content_key(), len(d))
                      for _t, d in out_meta[:k]))

    def _hold(self, form, out_meta, outs, count, parts) -> Optional[Batch]:
        """Keep one dispatch's partial on the device: nothing is read
        back, so the next launch queues behind this one.  Reaching
        ``partial_agg_max_bytes`` flushes what is held."""
        if form != self._held_form:
            # partials of one form are all as large: sized once
            self._held_form, self._held_meta = form, out_meta
            self._partial_bytes = sum(
                a.nbytes for v, valid in outs for a in (v, valid)
                if a is not None)
        self._held.append((outs, count, parts))
        self._held_bytes += self._partial_bytes
        self._emitted_any = True
        self.ctx.stats.prereduce_batches_held += 1
        self._charge_memory()
        if self._held_bytes >= self.ctx.config.partial_agg_max_bytes:
            return self._flush_held()
        return None

    def _flush_held(self) -> Optional[Batch]:
        """The held partials as one batch: the only hand-over to the
        consumer, and the only reads from the device, of a task that
        stayed under the limit."""
        held, self._held = self._held, []
        out_meta = self._held_meta
        self._held_bytes = 0
        self._charge_memory()
        self.ctx.stats.prereduce_flushes += 1
        if len(held) == 1:      # as it came, no launch added
            outs, count, parts = held[0]
        elif self.agg_spec.global_:
            return self._concat_global(held, out_meta)
        else:
            outs, count, parts = self._merge_held(held, out_meta)
        with activity("device_wait"):
            n = int(count)
        if n == 0:
            return None
        return self._partial_batch(out_meta, outs, parts, n)

    def _concat_global(self, held, out_meta) -> Batch:
        """Global partials are one row each and their counts host
        constants: no merge program, the rows come to the host in one
        overlapped transfer and concatenate there."""
        import jax

        with activity("device_wait"):
            host = jax.device_get([(outs, parts)
                                   for outs, _one, parts in held])
        outs = tuple(
            (np.concatenate([o[ci][0] for o, _p in host]),
             None if host[0][0][ci][1] is None
             else np.concatenate([o[ci][1] for o, _p in host]))
            for ci in range(len(host[0][0])))
        parts = (None if host[0][1] is None
                 else np.concatenate([p for _o, p in host]))
        return self._partial_batch(out_meta, outs, parts, len(held))

    def _merge_held(self, held, out_meta):
        """One program over all held partials (ops/groupby.py
        ``merge_pre_reduced``), and the merged rows' partition ids where
        the sink takes them precomputed.  The list pads to a bucketed
        length with empty partials, so a task builds one or two shapes."""
        agg = self.agg_spec
        outs0, count0, _parts = held[0]
        k_pad = next_bucket(len(held), 8)
        key = ("merge", agg.key(), self._held_form, k_pad,
               tuple((v.dtype.str, valid is not None)
                     for v, valid in outs0),
               self.partition_spec)
        fn = cache_get(_SEG_KERNELS, key)
        if fn is None:
            from presto_tpu.kernelcache import timed_first_call

            fn = timed_first_call(
                self._compile_merge(out_meta, self._held_form[0]),
                self.ctx.stats, _SEG_KERNELS)
            cache_put(_SEG_KERNELS, key, fn)
            self.ctx.stats.jit_compiles += 1
        empty = (outs0, np.zeros((), count0.dtype))
        args = tuple((outs, count) for outs, count, _p in held) \
            + (empty,) * (k_pad - len(held))
        self.ctx.stats.jit_dispatches += 1
        with activity("dispatch"):
            return fn(args)

    def _compile_merge(self, out_meta, doms):
        agg = self.agg_spec
        k = len(agg.group_channels)
        key_types = [typ for typ, _d in out_meta[:k]]
        merge_prims = [MERGE_PRIM[a.prim] for a in agg.aggs]
        out_dtypes = [a.out_type.np_dtype for a in agg.aggs]
        partition = self.partition_spec

        def kernel(held):
            from presto_tpu.ops.groupby import merge_pre_reduced

            key_outs, agg_outs, count = merge_pre_reduced(
                held, key_types, doms, merge_prims, out_dtypes)
            # a count state is always valid, as the batch's program left it
            agg_outs = [(v, None if a.prim == "count" else valid)
                        for a, (v, valid) in zip(agg.aggs, agg_outs)]
            outs = tuple(key_outs) + tuple(agg_outs)
            parts = (None if partition is None
                     else _partition_ids(outs, out_meta, partition))
            return outs, count, parts

        return kernelcache.jit(kernel, "fused_segment_merge")

    def _observe_reduction(self, rows_in: int, groups_out: int) -> None:
        """Runtime half of the cost-based pre-reduce decision: when a
        grouped pre-reduce emits nearly one group per input row, later
        batches skip the group kernel and emit raw rows in the partial
        schema (any granularity is legal for the downstream merge)."""
        if (self.agg_spec is None or self.agg_spec.global_
                or self._raw_emit):
            return
        if rows_in < 2048:      # tiny batches prove nothing
            return
        frac = self.ctx.config.prereduce_max_group_fraction
        if groups_out > frac * rows_in:
            self._raw_emit = True

    def _compile(self, batch: Batch, df_shapes, probe_metas=()):
        # stage-by-stage expression compilation: each stage's dictionary
        # bindings are the previous stage's projection output
        # dictionaries (stage 0 binds the batch's columns)
        dicts = {i: c.dictionary for i, c in enumerate(batch.columns)
                 if c.dictionary is not None}
        progs = []
        out_meta = [(c.type, c.dictionary) for c in batch.columns]
        di = 0
        pi_meta = 0
        for stage in self.stages:
            if isinstance(stage, FPStage):
                compiler = ExprCompiler(dicts)
                cfilter = (compiler.compile(stage.filter_expr)
                           if stage.filter_expr is not None else None)
                cprojs = [compiler.compile(p) for p in stage.projections]
                progs.append(("fp", cfilter, cprojs))
                dicts = {i: cp.dictionary for i, cp in enumerate(cprojs)
                         if cp.dictionary is not None}
                out_meta = [(cp.type, cp.dictionary) for cp in cprojs]
            elif isinstance(stage, ProbeStage):
                meta = probe_metas[pi_meta]
                pi_meta += 1
                progs.append(("probe", meta))
                if meta["join_type"] == "inner":
                    out_meta = list(out_meta) + list(meta["build_meta"])
                dicts = {i: d for i, (_t, d) in enumerate(out_meta)
                         if d is not None}
            else:
                progs.append(("df", df_shapes[di]))
                di += 1
        partition = self.partition_spec
        agg = self.agg_spec
        max_domain = self._max_domain
        raw_emit = self._raw_emit
        # filled while the program traces.  "doms": the key domains
        # where the pre-reduce took the direct path (its partial is
        # bounded then).  "compaction": how a program that ends with a
        # row mask and emits rows left them: "done" (compacted through
        # ops/filter.py), "prefix" or "sink" (not moved; the two cases
        # above the branches that set them)
        traced: dict = {}
        if agg is not None:
            # partial schema: [key columns..., one state col per agg]
            key_meta = [out_meta[g] for g in agg.group_channels]
            final_meta = key_meta + [(a.out_type, None) for a in agg.aggs]
            agg_prims = [(a.prim, a.channel) for a in agg.aggs]
            out_dtypes = [a.out_type.np_dtype for a in agg.aggs]
        else:
            final_meta = out_meta

        def kernel(cols, num_rows, df_args, probe_args):
            import jax.numpy as jnp

            from presto_tpu.ops import join as J
            from presto_tpu.ops.filter import selected_positions

            mask = None
            # the mask is exactly an inner probe's row_valid, j < total:
            # the expansion wrote its rows to the front, [0, num_rows)
            prefix = False
            cur = tuple(cols)
            dfi = 0
            pri = 0
            etotals = []
            for prog in progs:
                if prog[0] == "fp":
                    _, cfilter, cprojs = prog
                    if cfilter is not None:
                        fv, fvalid = cfilter.run(cur, num_rows, jnp)
                        m = fv if fvalid is None else fv & fvalid
                        mask = m if mask is None else mask & m
                        prefix = False
                    cur = tuple(p.run(cur, num_rows, jnp) for p in cprojs)
                elif prog[0] == "probe":
                    meta = prog[1]
                    build_pairs, aux, bstats = probe_args[pri]
                    pri += 1
                    kc = meta["key_channels"]
                    cap_now = cur[0][0].shape[0]
                    if meta["mode"] == "hash":
                        from presto_tpu.ops.hashtable import (
                            pages_hash_probe,
                        )

                        pages, perm = aux
                        kcols = [(cur[c][0], cur[c][1], kt)
                                 for c, kt in zip(kc, meta["key_types"])]
                        lo, counts, live = pages_hash_probe(
                            pages, kcols, num_rows)
                    else:
                        from presto_tpu.exec.joinop import (
                            _ids_from_pairs, _index_lo_counts,
                        )

                        sorted_ids, perm, mins, strides, maxs, index = aux
                        ids = _ids_from_pairs(
                            jnp, cur, kc, meta["mode"], mins, strides,
                            maxs, num_rows)
                        lo, counts = _index_lo_counts(
                            ids, sorted_ids, perm, index)
                        live = ids >= 0
                    alive = jnp.arange(cap_now) < num_rows
                    if mask is not None:
                        alive = alive & mask
                    jt = meta["join_type"]
                    prefix = jt == "inner"
                    if jt == "semi":
                        mask = J.semi_mask(counts, live & alive,
                                           anti=False)
                    elif jt == "anti":
                        n_build, has_null = bstats
                        mask = J.anti_keep_from_parts(
                            counts, live, alive, meta["null_aware"],
                            [cur[c][1] for c in kc], n_build,
                            build_has_null=has_null)
                    else:
                        out_cap = meta["out_cap"]
                        cnts = jnp.where(alive, counts, 0)
                        p_idx, b_idx, rv, _unm, total = J.expand_matches(
                            lo, cnts, perm, out_cap)
                        p32 = p_idx.astype(jnp.int32)
                        b32 = b_idx.astype(jnp.int32)
                        new_cur = [
                            (v[p32],
                             None if valid is None else valid[p32])
                            for v, valid in cur]
                        for v, valid in build_pairs:
                            bvalid = (rv if valid is None
                                      else (valid[b32] & rv))
                            new_cur.append((v[b32], bvalid))
                        cur = tuple(new_cur)
                        mask = rv
                        num_rows = total
                        etotals.append(total)
                else:
                    shape = prog[1]
                    bounds, tables = df_args[dfi]
                    dfi += 1
                    if shape == ("off",) or not shape[0]:
                        continue
                    chans, has_set = shape
                    ti = 0
                    for k, ch in enumerate(chans):
                        v, valid = cur[ch]
                        mn, mx = bounds[k]
                        m = ((v >= mn.astype(v.dtype))
                             & (v <= mx.astype(v.dtype)))
                        if has_set[k]:
                            table = tables[ti].astype(v.dtype)
                            ti += 1
                            idx = jnp.clip(jnp.searchsorted(table, v), 0,
                                           table.shape[0] - 1)
                            m = m & (table[idx] == v)
                        if valid is not None:
                            m = m & valid
                        mask = m if mask is None else mask & m
                        prefix = False
            cap = cur[0][0].shape[0]
            live = None
            if agg is not None and raw_emit and not agg.global_:
                # cost-based raw emission: the observed groups/rows
                # ratio said grouping is not reducing — compact the
                # live rows once and emit them AS partial states (one
                # row = one group of one; the downstream merge accepts
                # any granularity)
                m = (mask if mask is not None
                     else jnp.ones(cap, bool))
                idx, count = selected_positions(m, None, num_rows, cap)
                traced["compaction"] = "done"
                outs = []
                for g in agg.group_channels:
                    v, valid = cur[g]
                    outs.append((v[idx],
                                 None if valid is None else valid[idx]))
                for (prim, ch), dtype in zip(agg_prims, out_dtypes):
                    if ch is None:
                        outs.append((jnp.ones(cap, jnp.int64)[idx],
                                     None))
                    elif prim == "count":
                        v, valid = cur[ch]
                        ones = (jnp.ones(cap, jnp.int64)
                                if valid is None
                                else valid.astype(jnp.int64))
                        outs.append((ones[idx], None))
                    else:
                        v, valid = cur[ch]
                        outs.append((v[idx].astype(dtype),
                                     None if valid is None
                                     else valid[idx]))
                outs = tuple(outs)
            elif agg is not None:
                # pre-reduce: NO compaction — the accumulated mask rides
                # into the group kernels as the live mask, and the
                # segment emits per-batch partial group states instead
                # of rows (HashAggregationOperator.java:48 partial step,
                # fused into the scan program)
                from presto_tpu.ops.groupby import (
                    global_pre_reduce, segment_pre_reduce,
                )

                agg_ins = []
                for prim, ch in agg_prims:
                    if ch is None:
                        agg_ins.append(("count", None, None))
                    else:
                        v, valid = cur[ch]
                        agg_ins.append((prim, v, valid))
                if agg.global_:
                    outs = tuple(global_pre_reduce(
                        agg_ins, out_dtypes, num_rows, mask))
                    count = 1
                else:
                    keys = []
                    doms = []
                    bounded = True
                    total = 1
                    for g, (typ, d) in zip(agg.group_channels, key_meta):
                        v, valid = cur[g]
                        keys.append((v, valid, typ))
                        if d is not None:
                            dom = len(d)
                        elif typ.name == "boolean":
                            dom = 2
                        else:
                            bounded = False
                            dom = 0
                        doms.append(dom)
                        total *= dom + (1 if valid is not None else 0)
                    # direct (bounded-domain) vs sort path, decided at
                    # trace time: the sort fallback runs at the batch
                    # capacity, so per-batch groups can never overflow
                    use_direct = bounded and 0 < total <= max_domain
                    if use_direct:
                        traced["doms"] = tuple(doms)
                    key_outs, agg_outs, count = segment_pre_reduce(
                        keys, agg_ins, out_dtypes, num_rows, mask,
                        doms if use_direct else None, cap)
                    outs = tuple(key_outs) + tuple(agg_outs)
            elif mask is None:
                outs = cur
                count = num_rows
            elif prefix:
                # nothing masked a row since the last inner probe: the
                # live rows are the first num_rows already
                outs = cur
                count = num_rows
                traced["compaction"] = "prefix"
            elif partition is not None:
                # the consumer is the sink that cuts rows by partition
                # id on the host (server/exchangeop.py): the rows stay
                # where they are and the dead ones get the id one past
                # the last partition, which no page takes
                outs = cur
                live = (jnp.arange(cap) < num_rows) & mask
                count = live.sum()
                traced["compaction"] = "sink"
            else:
                # ONE compaction for the whole segment: every stage's
                # filter landed in the accumulated mask, so unselected
                # rows were computed over (harmless, like padding rows)
                # but never gathered or materialized
                idx, count = selected_positions(mask, None, num_rows, cap)
                outs = tuple(
                    (v[idx], None if valid is None else valid[idx])
                    for v, valid in cur)
                traced["compaction"] = "done"
            parts = (None if partition is None
                     else _partition_ids(outs, final_meta, partition))
            if live is not None:
                parts = jnp.where(live, parts, partition[1])
            return outs, count, parts, tuple(etotals)

        name = _SEGMENT_PROGRAM[bool(self._probe_idx),
                                agg is not None and not raw_emit]
        return kernelcache.jit(kernel, name), list(final_meta), traced


class FusedSegmentOperatorFactory(OperatorFactory):
    parallel_safe = True

    def __init__(self, stages: Sequence, coalesce_rows: int = 0,
                 partition_spec=None, min_batch_capacity: int = 1024,
                 agg_spec: Optional[PreReduceSpec] = None, scan_fill=None):
        self.stages = list(stages)
        self.coalesce_rows = coalesce_rows
        self.partition_spec = partition_spec
        self.min_batch_capacity = min_batch_capacity
        self.agg_spec = agg_spec
        self.scan_fill = scan_fill

    def create(self, ctx: OperatorContext) -> FusedSegmentOperator:
        return FusedSegmentOperator(ctx, self.stages, self.coalesce_rows,
                                    self.partition_spec,
                                    self.min_batch_capacity,
                                    agg_spec=self.agg_spec,
                                    scan_fill=self.scan_fill)

    def for_cached_scan(self, fill=None) -> "FusedSegmentOperatorFactory":
        """This segment for one execution of a scan the scan cache
        knows (exec/scancache.py).  A miss: the same segment, recording
        what it stages into ``fill``.  A hit (no fill): it is fed the
        kept device batches, so it coalesces nothing and dispatches each
        as it comes (the ``_pending`` path)."""
        return FusedSegmentOperatorFactory(
            self.stages, self.coalesce_rows if fill is not None else 0,
            self.partition_spec, self.min_batch_capacity, self.agg_spec,
            scan_fill=fill)

    def describe(self) -> str:
        """Human-readable stage summary."""
        parts = []
        for s in self.stages:
            if isinstance(s, FPStage):
                parts.append(
                    "fp(filter=%s, %d proj)" % (
                        "yes" if s.filter_expr is not None else "no",
                        len(s.projections)))
            elif isinstance(s, ProbeStage):
                parts.append("probe(%s, keys=%s)" % (
                    s.factory.join_type,
                    list(s.factory.probe_key_channels)))
            else:
                parts.append("df(keys=%s)" % (list(s.key_channels),))
        if self.agg_spec is not None:
            parts.append("prereduce(%s, %d aggs)" % (
                "global" if self.agg_spec.global_
                else "keys=%s" % (list(self.agg_spec.group_channels),),
                len(self.agg_spec.aggs)))
        extra = []
        if self.coalesce_rows:
            extra.append(f"coalesce={self.coalesce_rows}")
        if self.partition_spec:
            extra.append("partition=%dx%s" % (
                self.partition_spec[1], list(self.partition_spec[0])))
        tail = (" [" + ", ".join(extra) + "]") if extra else ""
        return "FusedSegment{" + " -> ".join(parts) + "}" + tail


def boundary_roles(pipelines) -> List[Tuple[str, str, str]]:
    """(pipeline name, segment description, role) for every fused
    segment that touches a fragment boundary on the HTTP exchange tier:
    'feeds-exchange' when the segment computes the partition ids
    PartitionedOutput routes by (the producer side of a boundary),
    'fed-by-exchange' when it coalesces pages arriving from a remote
    exchange (the consumer side), 'feeds+fed' for both, '' for interior
    segments.  On the device-sharded exchange tier neither side exists
    — the boundary collective splices the exchange-feeding and
    exchange-fed segment programs into ONE trace — so this report names
    exactly the dispatch/serde work the collective tier removes
    (tools/exchange_report.py renders it next to the per-boundary
    exchange-mode column)."""
    out = []
    for p in pipelines:
        for i, f in enumerate(p.factories):
            if not isinstance(f, FusedSegmentOperatorFactory):
                continue
            feeds = f.partition_spec is not None
            fed = i > 0 and _exchange_adjacent(p.factories[i - 1])
            role = ("feeds+fed" if feeds and fed
                    else "feeds-exchange" if feeds
                    else "fed-by-exchange" if fed else "")
            out.append((p.name, f.describe(), role))
    return out
