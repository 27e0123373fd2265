"""Dynamic filtering: build-side key domains prune the probe early.

Reference model: DynamicFilterSourceOperator collects build-side join-key
values into runtime filters that LocalDynamicFilter applies on the probe
scan (presto-main/.../operator/DynamicFilterSourceOperator.java:46,
sql/planner/LocalDynamicFilter.java:45, sql/DynamicFilters.java).

Here the build side always completes before the probe pipeline starts
(the single-process rendezvous), so the filter is synchronously ready:
``HashBuildOperator`` fills a ``DynamicFilter`` with per-key min/max and —
for small builds — the exact distinct key set, and a
``DynamicFilterOperator`` inserted before the probe's LookupJoin drops
non-matching rows with one vectorized mask+gather instead of letting them
reach the join kernel.  (The reference pushes to the scan itself; applying
at the probe-join input is the same work saved for every operator above
this point — channel provenance to the scan is a later refinement.)

Dictionary-coded keys are skipped: probe and build dictionaries intern
independently, so code-domain comparisons would be meaningless.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import Operator, OperatorFactory
from presto_tpu import kernelcache
from presto_tpu.spans import activity
from presto_tpu.kernelcache import (
    cache_get, cache_put, new_cache, record_compile, timed_first_call,
)

# jitted dynamic-filter programs, shared across queries (values are
# arguments, not constants — see _kernel_for)
_DF_KERNELS = new_cache("dynamic_filter")

# exact-set filtering only below this many distinct build keys
MAX_DISTINCT_SET = 4096


class DynamicFilter:
    """Per-join runtime filter, one entry per equi-key channel."""

    def __init__(self, n_keys: int):
        self.ready = False
        self.mins: List[Optional[np.ndarray]] = [None] * n_keys
        self.maxs: List[Optional[np.ndarray]] = [None] * n_keys
        self.sets: List[Optional[np.ndarray]] = [None] * n_keys
        self.build_empty = False
        self.disabled = False    # spilled build: pass everything through

    def disable(self) -> None:
        self.disabled = True
        self.ready = True

    def fill_from_build(self, data: Optional[Batch],
                        key_channels: Sequence[int]) -> None:
        if data is None or data.num_rows == 0:
            self.build_empty = True
            self.ready = True
            return
        for i, ch in enumerate(key_channels):
            col = data.columns[ch]
            if col.type.is_dictionary or col.type.name == "boolean":
                continue  # incomparable domains / trivial
            vals = np.asarray(col.values)[:data.num_rows]
            if col.valid is not None:
                vals = vals[np.asarray(col.valid)[:data.num_rows]]
            if vals.size == 0:
                self.build_empty = True
                continue
            self.mins[i] = vals.min()
            self.maxs[i] = vals.max()
            uniq = np.unique(vals)
            if uniq.size <= MAX_DISTINCT_SET:
                self.sets[i] = uniq
        self.ready = True


class DynamicFilterOperator(Operator):
    def __init__(self, ctx: OperatorContext, dyn: DynamicFilter,
                 key_channels: Sequence[int]):
        super().__init__(ctx)
        self.dyn = dyn
        self.key_channels = list(key_channels)
        self._pending: Optional[Batch] = None
        # adaptive shutoff (the reference disables ineffective dynamic
        # filters): stop filtering once observed selectivity is poor —
        # un-pruned rows cost nothing extra in static-shape kernels, but
        # each filter application costs a device round-trip
        self._rows_seen = 0
        self._rows_kept = 0
        self._adaptive_off = False

    def needs_input(self) -> bool:
        return not self._finishing and self._pending is None

    def _filters(self):
        out = []
        for i, ch in enumerate(self.key_channels):
            if self.dyn.mins[i] is None:
                continue
            out.append((ch, np.asarray(self.dyn.mins[i]),
                        np.asarray(self.dyn.maxs[i]),
                        self.dyn.sets[i]))
        return out

    def _kernel_for(self, batch: Batch, filters):
        """One jitted mask+compact program per (capacity, filter shape),
        shared GLOBALLY across queries: the bounds and IN-set tables are
        passed as arguments, never baked in as constants, so a new
        query's dynamic-filter values reuse the compiled program (no
        eager per-batch dispatch, no retrace)."""
        cap = batch.capacity
        chans = tuple(ch for ch, _, _, _ in filters)
        has_set = tuple(st is not None for _, _, _, st in filters)
        key = (cap, chans, has_set)
        hit = cache_get(_DF_KERNELS, key)
        if hit is not None:
            return hit
        self.ctx.stats.jit_compiles += 1
        import time as _time

        _t0 = _time.perf_counter_ns()
        import jax.numpy as jnp

        from presto_tpu.ops.filter import selected_positions

        def kernel(cols, num_rows, bounds, tables):
            mask = jnp.ones(cap, bool)
            ti = 0
            for k, ch in enumerate(chans):
                v, valid = cols[ch]
                mn, mx = bounds[k]
                m = (v >= mn.astype(v.dtype)) & (v <= mx.astype(v.dtype))
                if has_set[k]:
                    table = tables[ti].astype(v.dtype)
                    ti += 1
                    idx = jnp.clip(jnp.searchsorted(table, v), 0,
                                   table.shape[0] - 1)
                    m = m & (table[idx] == v)
                if valid is not None:
                    m = m & valid
                mask = mask & m
            idx, count = selected_positions(mask, None, num_rows, cap)
            gathered = tuple(
                (v[idx], None if valid is None else valid[idx])
                for v, valid in cols)
            return gathered, count

        build_ns = _time.perf_counter_ns() - _t0
        self.ctx.stats.jit_compile_ns += build_ns
        record_compile(_DF_KERNELS, build_ns)
        jitted = timed_first_call(
            kernelcache.jit(kernel, "dynamic_filter"), self.ctx.stats,
            _DF_KERNELS)
        cache_put(_DF_KERNELS, key, jitted)
        return jitted

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        if (not self.dyn.ready or self.dyn.disabled
                or self._adaptive_off):
            self._pending = batch  # no filter info: pass through
            return
        if self.dyn.build_empty:
            return  # inner join against empty build: nothing survives
        if any(c.type.is_nested for c in batch.columns):
            self._pending = batch  # nested payloads: pass through
            return
        filters = self._filters()
        if not filters:
            self._pending = batch
            return
        kernel = self._kernel_for(batch, filters)
        from presto_tpu.exec.operator import column_pairs

        self.ctx.stats.jit_dispatches += 1
        bounds = tuple((mn, mx) for _, mn, mx, _ in filters)
        tables = tuple(st for _, _, _, st in filters if st is not None)
        with activity("dispatch"):
            outs, count = kernel(tuple(column_pairs(batch)),
                                 batch.num_rows, bounds, tables)
        with activity("device_wait"):
            n_keep = int(count)
        self._rows_seen += batch.num_rows
        self._rows_kept += n_keep
        if self._rows_seen >= 4096 and \
                self._rows_kept > 0.95 * self._rows_seen:
            self._adaptive_off = True
        if n_keep == batch.num_rows:
            self._pending = batch
        elif n_keep > 0:
            cols = tuple(
                Column(c.type, v, valid, c.dictionary)
                for c, (v, valid) in zip(batch.columns, outs))
            self._pending = Batch(cols, n_keep)
        # else: fully pruned, emit nothing
        self.ctx.stats.output_rows += n_keep

    def get_output(self) -> Optional[Batch]:
        out, self._pending = self._pending, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._pending is None


class DynamicFilterOperatorFactory(OperatorFactory):
    parallel_safe = True

    def __init__(self, dyn: DynamicFilter, key_channels: Sequence[int]):
        self.dyn = dyn
        self.key_channels = list(key_channels)

    def create(self, ctx: OperatorContext) -> DynamicFilterOperator:
        return DynamicFilterOperator(ctx, self.dyn, self.key_channels)
