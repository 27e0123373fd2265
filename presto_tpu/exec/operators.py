"""Leaf / streaming operators: scan, values, filter+project, limit, output.

Reference models: TableScanOperator.java:46, ValuesOperator.java:27,
FilterAndProjectOperator.java:38 (+ compiled PageProcessor), LimitOperator
.java:24, TaskOutputOperator.java:33.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu import kernelcache
from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, next_bucket
from presto_tpu.connectors.api import Connector, Split
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import (
    Operator, OperatorFactory, SourceOperator, column_pairs, pad_batch,
)
from presto_tpu.expr.compile import ExprCompiler
from presto_tpu.spans import activity
from presto_tpu.expr.ir import RowExpression


class TableScanOperator(SourceOperator):
    """Pulls host batches from the connector PageSource and stages them to
    device (the LazyBlock-load + ConnectorPageSource.getNextPage path)."""

    def __init__(self, ctx: OperatorContext, connector: Connector,
                 columns: Sequence[str], batch_rows: int, to_device: bool,
                 fill=None):
        super().__init__(ctx)
        self.connector = connector
        self.columns = list(columns)
        self.batch_rows = batch_rows
        self.to_device = to_device
        self._splits: List[Split] = []
        self._no_more_splits = False
        self._iter = None
        # the scan cache's record of this pipeline's miss
        # (exec/scancache.py ScanFill); None for a table that can change
        self._fill = fill
        self._rows_out = 0
        if fill is not None and fill.scan_opened():
            ctx.stats.scan_cache_misses = 1

    def add_split(self, split: Split) -> None:
        self._splits.append(split)

    def no_more_splits(self) -> None:
        self._no_more_splits = True

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[Batch]:
        while True:
            if self._iter is None:
                if not self._splits:
                    return None
                split = self._splits.pop(0)
                self._iter = iter(self.connector.page_source(
                    split, self.columns, self.batch_rows))
            try:
                with activity("generate"):
                    batch = next(self._iter)
            except StopIteration:
                self._iter = None
                continue
            if batch.num_rows == 0:
                continue
            self.ctx.memory.set_bytes(batch.size_bytes)
            self._rows_out += batch.num_rows
            if self.to_device:
                batch = pad_batch(batch, self.ctx.config.min_batch_capacity)
                if self._fill is not None:
                    self._fill.add(batch)
            return batch

    def _drained(self) -> bool:
        return (self._no_more_splits and not self._splits
                and self._iter is None)

    def is_finished(self) -> bool:
        return self._drained() or self._finishing

    def close(self) -> None:
        fill, self._fill = self._fill, None
        if fill is not None:
            fill.scan_closed(self._rows_out, self._drained())
        super().close()


class TableScanOperatorFactory(OperatorFactory):
    parallel_safe = True

    def __init__(self, connector: Connector, columns: Sequence[str],
                 batch_rows: int = 65536, to_device: bool = True,
                 table: str = "", fill=None):
        self.connector = connector
        self.columns = list(columns)
        self.batch_rows = batch_rows
        self.to_device = to_device
        self.table = table  # for grouped-execution bucket lookup
        self.fill = fill    # one execution's ScanFill (exec/runner.py)

    def create(self, ctx: OperatorContext) -> TableScanOperator:
        return TableScanOperator(ctx, self.connector, self.columns,
                                 self.batch_rows, self.to_device,
                                 fill=self.fill)

    def filling(self, fill) -> "TableScanOperatorFactory":
        """This scan for one execution that missed the scan cache."""
        return TableScanOperatorFactory(
            self.connector, self.columns, self.batch_rows, self.to_device,
            self.table, fill=fill)


class CachedScanOperator(Operator):
    """A scan the scan cache answered (exec/scancache.py): hands over
    the device batches an earlier scan of the same splits staged."""

    def __init__(self, ctx: OperatorContext, hit):
        super().__init__(ctx)
        self._batches = collections.deque(hit.batches)
        ctx.stats.scan_cache_hits = 1
        ctx.stats.scan_cache_hit_bytes = hit.nbytes

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[Batch]:
        return self._batches.popleft() if self._batches else None

    def is_finished(self) -> bool:
        return not self._batches or self._finishing


class CachedScanOperatorFactory(OperatorFactory):
    def __init__(self, hit):
        self.hit = hit

    def create(self, ctx: OperatorContext) -> CachedScanOperator:
        return CachedScanOperator(ctx, self.hit)


class ValuesOperator(Operator):
    def __init__(self, ctx: OperatorContext, batches: Sequence[Batch]):
        super().__init__(ctx)
        self._batches = list(batches)

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[Batch]:
        if self._batches:
            return self._batches.pop(0)
        return None

    def is_finished(self) -> bool:
        return not self._batches


class ValuesOperatorFactory(OperatorFactory):
    def __init__(self, batches: Sequence[Batch]):
        self.batches = list(batches)

    def create(self, ctx: OperatorContext) -> ValuesOperator:
        return ValuesOperator(ctx, self.batches)


from presto_tpu.kernelcache import cache_get as _cache_get
from presto_tpu.kernelcache import cache_put as _cache_put
from presto_tpu.kernelcache import new_cache as _new_cache
from presto_tpu.kernelcache import record_compile as _record_compile
from presto_tpu.kernelcache import timed_first_call as _timed_first_call

# Compiled filter/project kernels shared GLOBALLY across operator
# instances and queries (the reference's ExpressionCompiler/
# PageFunctionCompiler Guava caches, JoinCompiler-style): RowExpressions
# hash structurally and dictionaries are append-only with monotonic
# tokens, so a repeated query shape reuses the jitted program instead of
# re-tracing.

_FP_KERNELS = _new_cache("filter_project")
_FP_HOST = _new_cache("filter_project_host")


def dictionary_binding_key(columns) -> tuple:
    """Per-column dictionary-binding component of a kernel cache key.

    (content fingerprint, len) per dictionary column: equal CONTENT in
    equal order implies identical code semantics, so per-execution
    rebuilt dictionaries (deserialized exchange pages, concat-merged
    build sides) share compiled programs instead of churning one
    recompile per query — ``Dictionary.token`` remains the identity
    surface (never reused, unlike id()), but programs key on what they
    actually baked: entry content (per-entry lookup tables) and length
    (append-only growth guard).
    """
    return tuple(
        None if c.dictionary is None
        else (c.dictionary.content_key(), len(c.dictionary))
        for c in columns)


class FilterProjectOperator(Operator):
    """filter -> compact -> project, fused into one jitted XLA program per
    (expressions, capacity, dictionary-binding) — the PageProcessor
    replacement.

    The compiled program returns projected columns plus the selected-row
    count; intermediate selection vectors never leave the device.
    """

    def __init__(self, ctx: OperatorContext,
                 filter_expr: Optional[RowExpression],
                 projections: Sequence[RowExpression],
                 input_types: Sequence[T.Type]):
        super().__init__(ctx)
        self.filter_expr = filter_expr
        self.projections = list(projections)
        self.input_types = list(input_types)
        self._pending: Optional[Batch] = None
        self._expr_key = (filter_expr, tuple(projections),
                          tuple(input_types))
        from presto_tpu.expr.compile import needs_host_path

        # expressions are fixed for the operator's lifetime: decide the
        # host-vs-jit route once
        self._host_exprs = needs_host_path(
            [self.filter_expr] + self.projections)

    def needs_input(self) -> bool:
        return self._pending is None and not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._pending = batch
        self.ctx.stats.input_batches += 1
        self.ctx.stats.input_rows += batch.num_rows

    def _kernel_for(self, batch: Batch):
        dict_key = dictionary_binding_key(batch.columns)
        key = (self._expr_key, batch.capacity, dict_key)
        hit = _cache_get(_FP_KERNELS, key)
        if hit is not None:
            return hit
        self.ctx.stats.jit_compiles += 1
        import time as _time

        _t0 = _time.perf_counter_ns()
        compiler = ExprCompiler({i: c.dictionary
                                 for i, c in enumerate(batch.columns)
                                 if c.dictionary is not None})
        cfilter = (compiler.compile(self.filter_expr)
                   if self.filter_expr is not None else None)
        cprojs = [compiler.compile(p) for p in self.projections]
        cap = batch.capacity

        def kernel(cols, num_rows):
            import jax.numpy as jnp

            from presto_tpu.ops.filter import selected_positions

            if cfilter is not None:
                mask, mvalid = cfilter.run(cols, num_rows, jnp)
                idx, count = selected_positions(mask, mvalid, num_rows, cap)
                gathered = tuple(
                    (v[idx], None if valid is None else valid[idx])
                    for v, valid in cols)
            else:
                gathered, count = cols, num_rows
            outs = [p.run(gathered, count, jnp) for p in cprojs]
            return outs, count

        # expression-compile time lands now; the XLA trace+compile wall
        # of the jitted program lands on its first dispatch (wrapper)
        build_ns = _time.perf_counter_ns() - _t0
        self.ctx.stats.jit_compile_ns += build_ns
        _record_compile(_FP_KERNELS, build_ns)
        entry = (_timed_first_call(kernelcache.jit(kernel, "filter_project"),
                                   self.ctx.stats, _FP_KERNELS), cprojs)
        _cache_put(_FP_KERNELS, key, entry)
        return entry

    def _host_output(self, batch: Batch) -> Optional[Batch]:
        """Un-jitted path for nested-typed expressions (host Columns)."""
        import numpy as np

        from presto_tpu.expr.compile import (
            ExprCompiler, batch_pairs, result_column,
        )

        batch = batch.compact().to_numpy()
        # cache per dictionary binding (same policy as the jit kernels);
        # dictionaries are append-only so the binding stays valid and
        # per-call-site output dictionaries keep stable codes
        key = (self._expr_key, dictionary_binding_key(batch.columns))
        hit = _cache_get(_FP_HOST, key)
        if hit is None:
            compiler = ExprCompiler({i: c.dictionary
                                     for i, c in enumerate(batch.columns)
                                     if c.dictionary is not None})
            cfilter = (compiler.compile(self.filter_expr)
                       if self.filter_expr is not None else None)
            cprojs = [compiler.compile(p) for p in self.projections]
            hit = (cfilter, cprojs)
            _cache_put(_FP_HOST, key, hit)
        cfilter, cprojs = hit
        n = batch.num_rows
        if cfilter is not None:
            mask, mvalid = cfilter.run(batch_pairs(batch), n, np)
            keep = np.asarray(mask, bool)
            if mvalid is not None:
                keep = keep & np.asarray(mvalid)
            batch = batch.take(np.nonzero(keep[:n])[0])
            n = batch.num_rows
        pairs = batch_pairs(batch)
        cols = tuple(
            result_column(p, *p.run(pairs, n, np)) for p in cprojs)
        return Batch(cols, n)

    def get_output(self) -> Optional[Batch]:
        if self._pending is None:
            return None
        batch, self._pending = self._pending, None
        if (self._host_exprs
                or any(c.type.is_nested for c in batch.columns)):
            out = self._host_output(batch)
            n = out.num_rows
        else:
            jitted, cprojs = self._kernel_for(batch)
            self.ctx.stats.jit_dispatches += 1
            with activity("dispatch"):
                outs, count = jitted(tuple(column_pairs(batch)),
                                     batch.num_rows)
            with activity("device_wait"):
                n = int(count)
            cols = tuple(
                Column(p.type, v, valid, p.dictionary)
                for p, (v, valid) in zip(cprojs, outs))
            out = Batch(cols, n)
        self.ctx.stats.output_batches += 1
        self.ctx.stats.output_rows += n
        if n == 0:
            return None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._pending is None


class FilterProjectOperatorFactory(OperatorFactory):
    parallel_safe = True

    def __init__(self, filter_expr: Optional[RowExpression],
                 projections: Sequence[RowExpression],
                 input_types: Sequence[T.Type]):
        self.filter_expr = filter_expr
        self.projections = list(projections)
        self.input_types = list(input_types)

    def create(self, ctx: OperatorContext) -> FilterProjectOperator:
        return FilterProjectOperator(ctx, self.filter_expr, self.projections,
                                     self.input_types)


class LimitOperator(Operator):
    def __init__(self, ctx: OperatorContext, limit: int):
        super().__init__(ctx)
        self.remaining = limit
        self._pending: Optional[Batch] = None

    def needs_input(self) -> bool:
        return (self._pending is None and self.remaining > 0
                and not self._finishing)

    def add_input(self, batch: Batch) -> None:
        if batch.num_rows > self.remaining:
            batch = batch.head(self.remaining)
        self.remaining -= batch.num_rows
        self._pending = batch

    def get_output(self) -> Optional[Batch]:
        out, self._pending = self._pending, None
        return out

    def is_finished(self) -> bool:
        return (self.remaining == 0 or self._finishing) and \
            self._pending is None


class LimitOperatorFactory(OperatorFactory):
    def __init__(self, limit: int):
        self.limit = limit

    def create(self, ctx: OperatorContext) -> LimitOperator:
        return LimitOperator(ctx, self.limit)


class TableWriterOperator(Operator):
    """Write path terminal: streams batches into a connector PageSink and
    emits the committed row count at finish (the TableWriterOperator +
    TableFinishOperator pair, presto-main/.../operator/TableWriter
    Operator.java:58 / TableFinishOperator.java:46, fused — the engine's
    per-query writes are single-commit)."""

    def __init__(self, ctx: OperatorContext, sink):
        super().__init__(ctx)
        self.sink = sink
        self._rows: Optional[int] = None
        self._emitted = False

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        self.sink.append(batch)

    def finish(self) -> None:
        if not self._finishing:
            super().finish()
            self._rows = self.sink.finish()

    def get_output(self) -> Optional[Batch]:
        if self._rows is None or self._emitted:
            return None
        self._emitted = True
        from presto_tpu.batch import batch_from_pylist

        return batch_from_pylist([T.BIGINT], [(self._rows,)])

    def is_finished(self) -> bool:
        # terminal operator: the driver never pulls it, so emission of the
        # row-count batch is best-effort (read via rows_written instead)
        return self._finishing

    @property
    def rows_written(self) -> Optional[int]:
        return self._rows


class TableWriterOperatorFactory(OperatorFactory):
    def __init__(self, sink):
        self.sink = sink
        self.op: Optional[TableWriterOperator] = None

    def create(self, ctx: OperatorContext) -> TableWriterOperator:
        self.op = TableWriterOperator(ctx, self.sink)
        return self.op


class DistributedTableWriterOperator(Operator):
    """Worker half of a distributed write (P6): stream input into the
    connector's per-task STAGING sink and emit one (rows, fragment) row;
    nothing is visible to readers until the TableFinish commit
    (TableWriterOperator.java:58 under SCALED_WRITER_DISTRIBUTION)."""

    def __init__(self, ctx: OperatorContext, sink):
        super().__init__(ctx)
        self.sink = sink
        self._row: Optional[tuple] = None
        self._emitted = False

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        self.sink.append(batch)

    def finish(self) -> None:
        if not self._finishing:
            super().finish()
            rows = self.sink.finish()
            self._row = (rows, self.sink.fragment())

    def get_output(self) -> Optional[Batch]:
        if self._row is None or self._emitted:
            return None
        self._emitted = True
        from presto_tpu.batch import batch_from_pylist

        self.ctx.stats.output_rows += 1
        return batch_from_pylist([T.BIGINT, T.VARCHAR], [self._row])

    def is_finished(self) -> bool:
        return self._finishing and self._emitted


class DistributedTableWriterOperatorFactory(OperatorFactory):
    def __init__(self, registry, catalog: str, table: str, write_id: str,
                 task_tag: str):
        self.registry = registry
        self.catalog = catalog
        self.table = table
        self.write_id = write_id
        self.task_tag = task_tag

    def create(self, ctx: OperatorContext
               ) -> DistributedTableWriterOperator:
        conn = self.registry.get(self.catalog)
        handle = conn.get_table(self.table)
        sink = conn.task_sink(handle, self.write_id,
                              f"{self.task_tag}.{ctx.name}")
        return DistributedTableWriterOperator(ctx, sink)


class TableFinishOperator(Operator):
    """Commit half (TableFinishOperator.java:46): collects every writer
    task's (rows, fragment) row, publishes all fragments in ONE
    connector call (all-or-nothing), and emits the total row count."""

    def __init__(self, ctx: OperatorContext, registry, catalog: str,
                 table: str, write_id: str):
        super().__init__(ctx)
        self.registry = registry
        self.catalog = catalog
        self.table = table
        self.write_id = write_id
        self._rows = 0
        self._fragments: List[str] = []
        self._emitted = False
        self._committed = False

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        for rows, frag in batch.to_pylist():
            self._rows += int(rows)
            if frag is not None:
                self._fragments.append(frag)

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        conn = self.registry.get(self.catalog)
        handle = conn.get_table(self.table)
        conn.finish_write(handle, self.write_id, self._fragments)
        self._committed = True

    def get_output(self) -> Optional[Batch]:
        if not self._committed or self._emitted:
            return None
        self._emitted = True
        from presto_tpu.batch import batch_from_pylist

        self.ctx.stats.output_rows += 1
        return batch_from_pylist([T.BIGINT], [(self._rows,)])

    def is_finished(self) -> bool:
        return self._finishing and self._emitted


class TableFinishOperatorFactory(OperatorFactory):
    def __init__(self, registry, catalog: str, table: str, write_id: str):
        self.registry = registry
        self.catalog = catalog
        self.table = table
        self.write_id = write_id

    def create(self, ctx: OperatorContext) -> TableFinishOperator:
        return TableFinishOperator(ctx, self.registry, self.catalog,
                                   self.table, self.write_id)


class OutputCollector(Operator):
    """Terminal sink gathering result batches host-side
    (TaskOutputOperator / test MaterializedResult role)."""

    def __init__(self, ctx: OperatorContext):
        super().__init__(ctx)
        self.batches: List[Batch] = []

    def add_input(self, batch: Batch) -> None:
        if batch.num_rows:
            self.batches.append(batch.compact().to_numpy())
        self.ctx.stats.input_batches += 1
        self.ctx.stats.input_rows += batch.num_rows

    def is_finished(self) -> bool:
        return self._finishing

    def rows(self) -> List[tuple]:
        out: List[tuple] = []
        for b in self.batches:
            out.extend(b.to_pylist())
        return out


class OutputCollectorFactory(OperatorFactory):
    def __init__(self):
        self.collectors: List[OutputCollector] = []

    def create(self, ctx: OperatorContext) -> OutputCollector:
        c = OutputCollector(ctx)
        self.collectors.append(c)
        return c

    def reset_for_execution(self) -> None:
        # drop the previous execution's collected batches, or rows()
        # would accumulate across runs of a cached physical plan
        self.collectors = []

    def rows(self) -> List[tuple]:
        out = []
        for c in self.collectors:
            out.extend(c.rows())
        return out
