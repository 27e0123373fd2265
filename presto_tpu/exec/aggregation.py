"""Aggregation operators.

Reference models: HashAggregationOperator.java:48 (grouped; partial/final
Step) and AggregationOperator.java:35 (global).  The TPU version
materializes its input (as the reference's builders do), then runs the
sort-based grouped_aggregate kernel once, retrying at the next capacity
bucket when ``num_groups`` overflows — the device-side answer to
GroupByHash's rehash-with-memory-reservation (MultiChannelGroupByHash.java:87).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, next_bucket
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import Operator, OperatorFactory, device_concat
from presto_tpu.spans import activity


@dataclasses.dataclass(frozen=True)
class AggChannel:
    """One primitive reduction: prim in {'sum','count','min','max'},
    over input channel ``channel`` (None == count(*))."""

    prim: str
    channel: Optional[int]
    out_type: T.Type


# merge primitive per partial-state component (the Step.FINAL half of
# HashAggregationOperator.Step:61 for the device prims): re-aggregating a
# pre-reduced partial state with these yields the same answer as
# aggregating the raw rows.  Shared by the fusion pass (exec/fusion.py)
# when it pushes the partial accumulate into a scan segment.
MERGE_PRIM = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}


def _apply_post_projections(batch: Batch, stages) -> Batch:
    """Apply absorbed finalize projections to an aggregation's output
    (exec/fusion.py folds the filter-less post-aggregation FilterProject
    run into the aggregation finish).  ``stages`` is a list of
    projection lists, applied in order.  Aggregation outputs are
    group-sized, so vectorized host evaluation costs less than one more
    device program launch per stage."""
    import numpy as np

    from presto_tpu.expr.compile import (
        ExprCompiler, batch_pairs, result_column,
    )

    # one read of the padded batch, cut on the host: compact() on device
    # arrays is a slice whose shape is the row count, a program a count
    batch = batch.to_numpy().compact()
    for projections in stages:
        compiler = ExprCompiler({i: c.dictionary
                                 for i, c in enumerate(batch.columns)
                                 if c.dictionary is not None})
        cprojs = [compiler.compile(p) for p in projections]
        pairs = batch_pairs(batch)
        n = batch.num_rows
        cols = tuple(result_column(p, *p.run(pairs, n, np))
                     for p in cprojs)
        batch = Batch(cols, n)
    return batch


def _agg_inputs(aggs: Sequence[AggChannel], data: Batch):
    """The finish programs' aggregation inputs (ops/groupby.py): per
    aggregate ``(prim, values, valid, tables)`` over ``data``'s columns.
    count(*) has no values; min/max over a dictionary column takes the
    dictionary's rank tables, the program reduces ranks."""
    from presto_tpu.ops.groupby import dictionary_rank_tables

    ins = []
    ranked = {}      # channel -> its tables: min and max share them
    for a in aggs:
        if a.channel is None:
            ins.append(("count", None, None, None))
            continue
        col = data.columns[a.channel]
        tables = None
        if a.prim in ("min", "max") and col.dictionary is not None:
            if a.channel not in ranked:
                ranked[a.channel] = dictionary_rank_tables(col.dictionary)
            tables = ranked[a.channel]
        ins.append((a.prim, col.values, col.valid, tables))
    return ins


def _agg_columns(aggs: Sequence[AggChannel], data: Batch, agg_outs):
    """The finish programs' ``(values, valid)`` outputs as Columns; a
    min/max over a dictionary column keeps its input's dictionary."""
    cols = []
    for a, (values, valid) in zip(aggs, agg_outs):
        dictionary = None
        if a.channel is not None and a.prim in ("min", "max"):
            dictionary = data.columns[a.channel].dictionary
        cols.append(Column(a.out_type, values, valid, dictionary))
    return cols


_HOST_PRIMS = ("collect", "collect_merge", "hll", "hll_merge",
               "kll", "kll_merge")


def _has_collect(aggs: Sequence[AggChannel]) -> bool:
    return any(a.prim in _HOST_PRIMS for a in aggs)


def host_aggregate(batches: List[Batch], group_channels: Sequence[int],
                   aggs: Sequence[AggChannel],
                   global_row: bool) -> Optional[Batch]:
    """Host-side aggregation used when a collect-style aggregate
    (array_agg/map_agg/min_by, AccumulatorCompiler's object-state
    accumulators in the reference) is present: device reductions cannot
    produce variable-length results.

    At the FINAL distributed step, collect inputs are the partial step's
    arrays and are flattened (the @CombineFunction merge role).
    """
    import numpy as np

    from presto_tpu.batch import (
        Batch, Column, column_from_pylist, concat_batches,
    )

    live = [b.compact().to_numpy() for b in batches if b.num_rows > 0]
    if not live:
        if not global_row:
            return None
        rows: List[tuple] = []
        data = None
        n = 0
    else:
        data = concat_batches(live) if len(live) > 1 else live[0]
        n = data.num_rows
    key_lists = [data.columns[c].to_pylist(n) for c in group_channels] \
        if data is not None else [[] for _ in group_channels]
    group_ids: dict = {}
    order: List[tuple] = []
    gids = np.zeros(n, np.int64)
    for i in range(n):
        k = tuple(kl[i] for kl in key_lists)
        gid = group_ids.get(k)
        if gid is None:
            gid = group_ids[k] = len(order)
            order.append(k)
        gids[i] = gid
    if global_row and not order:
        order.append(())
    ng = len(order)
    cols: List[Column] = []
    for j, c in enumerate(group_channels):
        src = None if data is None else data.columns[c]
        vals = [k[j] for k in order]
        cols.append(column_from_pylist(src.type, vals))
    for a in aggs:
        in_list = None
        if a.channel is not None and data is not None:
            in_list = data.columns[a.channel].to_pylist(n)
        if a.prim == "count":
            out = [0] * ng
            for i in range(n):
                if in_list is None or in_list[i] is not None:
                    out[int(gids[i])] += 1
            cols.append(column_from_pylist(a.out_type, out))
            continue
        if a.prim in ("collect", "collect_merge"):
            # the FINAL step's inputs are the partial step's arrays; the
            # prim says which step this is (type equality is ambiguous,
            # e.g. array_agg over varbinary-typed inputs)
            flatten = a.prim == "collect_merge"
            acc: List[Optional[list]] = [[] for _ in range(ng)]
            for i in range(n):
                v = in_list[i]
                if flatten:
                    if v is not None:
                        acc[int(gids[i])].extend(v)
                else:
                    acc[int(gids[i])].append(v)
            if n == 0 and global_row:
                acc = [None]       # array_agg over no rows is NULL
            cols.append(column_from_pylist(a.out_type, acc))
            continue
        if a.prim in ("hll", "hll_merge"):
            from presto_tpu.sketch import HyperLogLog

            merge = a.prim == "hll_merge"
            sketches = [HyperLogLog() for _ in range(ng)]
            for i in range(n):
                v = in_list[i]
                if v is None:
                    continue
                g = int(gids[i])
                if merge:
                    sketches[g].merge(HyperLogLog.deserialize(v))
                else:
                    sketches[g].add_value(v)
            cols.append(column_from_pylist(
                a.out_type, [s.serialize() for s in sketches]))
            continue
        if a.prim in ("kll", "kll_merge"):
            from presto_tpu.sketch import KllSketch

            merge = a.prim == "kll_merge"
            qsketches = [KllSketch() for _ in range(ng)]
            for i in range(n):
                v = in_list[i]
                if v is None:
                    continue
                g = int(gids[i])
                if merge:
                    qsketches[g].merge(KllSketch.deserialize(v))
                else:
                    qsketches[g].add_value(v)
            cols.append(column_from_pylist(
                a.out_type, [s.serialize() for s in qsketches]))
            continue
        # sum / min / max over non-null values
        out2: List[Optional[object]] = [None] * ng
        for i in range(n):
            v = in_list[i] if in_list is not None else None
            if v is None:
                continue
            g = int(gids[i])
            cur = out2[g]
            if cur is None:
                out2[g] = v
            elif a.prim == "sum":
                out2[g] = cur + v
            elif a.prim == "min":
                out2[g] = min(cur, v)
            elif a.prim == "max":
                out2[g] = max(cur, v)
        cols.append(column_from_pylist(a.out_type, out2))
    return Batch(tuple(cols), ng)


class HashAggregationOperator(Operator):
    def __init__(self, ctx: OperatorContext, group_channels: Sequence[int],
                 aggs: Sequence[AggChannel], input_types: Sequence[T.Type],
                 post_projections=None):
        super().__init__(ctx)
        self.group_channels = list(group_channels)
        self.aggs = list(aggs)
        self.input_types = list(input_types)
        self.post_projections = (list(post_projections)
                                 if post_projections else None)
        self._batches: List[Batch] = []
        self._outputs: List[Batch] = []
        self._done = False
        self._spiller = None
        self._accumulated_bytes = 0

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_batches += 1
        self.ctx.stats.input_rows += batch.num_rows
        self._batches.append(batch)
        self.ctx.memory.reserve(batch.size_bytes)
        self._accumulated_bytes += batch.size_bytes
        cfg = self.ctx.config
        if (cfg.spill_enabled and self.group_channels
                and self._accumulated_bytes > cfg.spill_threshold_bytes):
            self._spill_accumulated()

    def _spill_accumulated(self) -> None:
        """Revoke: hash-partition accumulated rows to the spill tier
        (SpillableHashAggregationBuilder role); each group lands wholly in
        one partition, so finish aggregates partition-by-partition."""
        from presto_tpu.exec.spill import PartitioningSpiller

        cfg = self.ctx.config
        if self._spiller is None:
            self._spiller = PartitioningSpiller(
                cfg.spill_path, cfg.spill_partitions, self.group_channels,
                tag=f"agg-{self.ctx.name}")
        for b in self._batches:
            self._spiller.spill(b.to_numpy())
        self._batches = []
        self._accumulated_bytes = 0
        self.ctx.memory.free()

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        outs: List[Batch] = []
        if self._spiller is not None:
            self._spill_accumulated()
            for p in range(self.ctx.config.spill_partitions):
                part = list(self._spiller.partition(p))
                if not part:
                    continue
                out = self._compute_batches(part)
                if out is not None:
                    outs.append(out)
            self._spiller.close()
            self._spiller = None
        else:
            out = self._compute_batches(self._batches)
            if out is not None:
                outs.append(out)
        self._outputs.extend(outs)
        self._batches = []
        self.ctx.memory.free()

    def _direct_domains(self, data: Batch) -> Optional[List[int]]:
        """Per-key domain sizes when every key column is bounded (dictionary
        codes / booleans) and the packed domain is small; else None.  A
        domain is rounded up to a power of two: the finish program is
        compiled for it, and a dictionary that grows must not make a
        program a length."""
        doms = []
        for c in self.group_channels:
            col = data.columns[c]
            if col.dictionary is not None:
                doms.append(len(col.dictionary))
            elif col.type.name == "boolean":
                doms.append(2)
            else:
                return None
        total = 1
        for d, c in zip(doms, self.group_channels):
            total *= d + (1 if data.columns[c].valid is not None else 0)
        if not doms or total > self.ctx.config.direct_groupby_max_domain:
            return None
        return [next_bucket(d, 1) for d in doms]

    def _compute_batches(self, batches: List[Batch]) -> Optional[Batch]:
        """Stage the accumulated rows once, launch one named program
        (``groupby_direct`` / ``groupby_sort``), read ``num_groups``: the
        output columns stay on the device, padded."""
        import numpy as np

        from presto_tpu.ops.groupby import grouped_finish_jit

        if _has_collect(self.aggs):
            out = host_aggregate(batches, self.group_channels, self.aggs,
                                 global_row=False)
            if out is not None:
                self.ctx.stats.output_rows += out.num_rows
            return out

        data = device_concat(batches, self.ctx.config.min_batch_capacity)
        if data is None:
            return None  # grouped aggregation of zero rows -> zero rows
        doms = self._direct_domains(data)
        self.ctx.stats.kernel_tier = "sort" if doms is None else "direct"
        key_cols = [data.columns[c] for c in self.group_channels]
        keys = [(c.values, c.valid, c.type) for c in key_cols]
        agg_ins = _agg_inputs(self.aggs, data)
        out_dtypes = [a.out_type.np_dtype for a in self.aggs]
        # the direct tier's output is its packed domain: it cannot overflow
        group_cap = next_bucket(1, min(max(data.num_rows, 1), 1 << 16))
        while True:
            self.ctx.stats.jit_dispatches += 1
            key_outs, agg_outs, ng = grouped_finish_jit(
                keys, agg_ins, out_dtypes, np.int32(data.num_rows), doms,
                group_cap)
            with activity("device_wait"):
                num_groups = int(ng)
            if doms is not None or num_groups <= group_cap:
                break
            group_cap = next_bucket(num_groups)
        cols = [Column(c.type, values, valid, c.dictionary)
                for c, (values, valid) in zip(key_cols, key_outs)]
        cols += _agg_columns(self.aggs, data, agg_outs)
        self.ctx.stats.output_rows += num_groups
        return Batch(tuple(cols), num_groups)

    def get_output(self) -> Optional[Batch]:
        if not self._outputs:
            return None
        self._done = True
        out = self._outputs.pop(0)
        if self.post_projections is not None and out.num_rows:
            out = _apply_post_projections(out, self.post_projections)
        return out

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


class HashAggregationOperatorFactory(OperatorFactory):
    def __init__(self, group_channels, aggs, input_types,
                 post_projections=None):
        self.group_channels = list(group_channels)
        self.aggs = list(aggs)
        self.input_types = list(input_types)
        # absorbed filter-less finalize projection (exec/fusion.py)
        self.post_projections = post_projections
        # aggregation step this factory lowers ("single"/"partial"/
        # "final"), set by the physical planner for the fusion pass
        self.step = "single"

    def create(self, ctx: OperatorContext) -> HashAggregationOperator:
        return HashAggregationOperator(ctx, self.group_channels, self.aggs,
                                       self.input_types,
                                       post_projections=self.post_projections)


class GlobalAggregationOperator(Operator):
    """Ungrouped aggregation: exactly one output row, even on empty input."""

    def __init__(self, ctx: OperatorContext, aggs: Sequence[AggChannel],
                 input_types: Sequence[T.Type], post_projections=None):
        super().__init__(ctx)
        self.aggs = list(aggs)
        self.input_types = list(input_types)
        self.post_projections = (list(post_projections)
                                 if post_projections else None)
        self._batches: List[Batch] = []
        self._output: Optional[Batch] = None

    def add_input(self, batch: Batch) -> None:
        self._batches.append(batch)
        self.ctx.stats.input_rows += batch.num_rows

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        import jax
        import numpy as np

        from presto_tpu.ops.groupby import global_finish_jit

        if _has_collect(self.aggs):
            self._output = host_aggregate(self._batches, [], self.aggs,
                                          global_row=True)
            self._batches = []
            return

        data = device_concat(self._batches,
                             self.ctx.config.min_batch_capacity)
        self._batches = []
        cols = []
        if data is None:
            for a in self.aggs:
                if a.prim == "count":
                    cols.append(Column(a.out_type, np.zeros(1, np.int64)))
                else:
                    from presto_tpu.batch import Dictionary

                    dictionary = (Dictionary()
                                  if a.out_type.is_dictionary else None)
                    cols.append(Column(a.out_type,
                                       np.zeros(1, a.out_type.np_dtype),
                                       np.zeros(1, bool), dictionary))
            self._output = Batch(tuple(cols), 1)
            return
        self.ctx.stats.jit_dispatches += 1
        agg_outs = global_finish_jit(
            _agg_inputs(self.aggs, data),
            [a.out_type.np_dtype for a in self.aggs],
            np.int32(data.num_rows))
        with activity("device_wait"):   # the one read: a row
            agg_outs = jax.device_get(agg_outs)
        self._output = Batch(
            tuple(_agg_columns(self.aggs, data, agg_outs)), 1)

    def get_output(self) -> Optional[Batch]:
        out, self._output = self._output, None
        if out is not None and self.post_projections is not None:
            out = _apply_post_projections(out, self.post_projections)
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._output is None


class GlobalAggregationOperatorFactory(OperatorFactory):
    def __init__(self, aggs, input_types, post_projections=None):
        self.aggs = list(aggs)
        self.input_types = list(input_types)
        self.post_projections = post_projections
        self.step = "single"

    def create(self, ctx: OperatorContext) -> GlobalAggregationOperator:
        return GlobalAggregationOperator(
            ctx, self.aggs, self.input_types,
            post_projections=self.post_projections)
