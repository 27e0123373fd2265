"""WindowOperator: partition-sorted segmented-scan window evaluation.

Reference model: WindowOperator (presto-main/.../operator/
WindowOperator.java:61) sorts a PagesIndex by (partition, order) keys and
walks it row-by-row, partition-by-partition, with per-function framing
(operator/window/FrameInfo).  The TPU formulation materializes, runs the
sort-permutation kernel once over all partitions, derives partition/peer
segment ids from adjacent-row key equality, and evaluates every window
function as a data-parallel segmented scan (ops/window.py) — one XLA
program, no per-partition loop.

Output rows come out partition/order-sorted (the reference's output order
as well); the appended channels hold the function results.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import Operator, OperatorFactory, device_concat
from presto_tpu.exec.sortop import SortSpec
from presto_tpu.sql.plan import PlanWindowFunction


def eval_window_function(fn: PlanWindowFunction, columns, seg, peer):
    """Evaluate one window function over partition-sorted columns.

    ``columns`` is any sequence of column-like objects exposing
    ``values / valid / type / dictionary`` (the operator tier's Column and
    the mesh tier's MCol both do).  Returns
    ``(result_type, values, valid|None, dictionary|None)``.
    """
    import jax.numpy as jnp

    from presto_tpu.ops import window as W

    name = fn.name
    rt = fn.result_type
    if name == "row_number":
        return rt, W.row_number(seg), None, None
    if name == "rank":
        return rt, W.rank(seg, peer), None, None
    if name == "dense_rank":
        return rt, W.dense_rank(seg, peer), None, None
    if name == "percent_rank":
        return rt, W.percent_rank(seg, peer), None, None
    if name == "cume_dist":
        return rt, W.cume_dist(seg, peer), None, None
    if name == "ntile":
        return rt, W.ntile(seg, fn.offset), None, None

    if name in ("lag", "lead"):
        c = columns[fn.arg_channels[0]]
        default = (columns[fn.default_channel].values
                   if fn.default_channel is not None else None)
        off = fn.offset if name == "lag" else -fn.offset
        vals, ok = W.shift_in_partition(seg, c.values, c.valid, off,
                                        default)
        return rt, vals, ok, c.dictionary

    lo, hi = W.frame_ends(seg, peer, fn.frame_unit, fn.frame_start,
                          fn.frame_end, fn.frame_start_offset,
                          fn.frame_end_offset)
    if name in ("first_value", "nth_value"):
        c = columns[fn.arg_channels[0]]
        k = fn.offset or 1
        target = lo + (k - 1)
        in_frame = target <= hi
        tc = jnp.clip(target, 0, c.values.shape[0] - 1)
        vals = c.values[tc]
        ok = in_frame if c.valid is None else (in_frame & c.valid[tc])
        return rt, vals, ok, c.dictionary
    if name == "last_value":
        c = columns[fn.arg_channels[0]]
        vals, ok = W.value_at(c.values, c.valid, hi)
        ok = ok & (lo <= hi)
        return rt, vals, ok, c.dictionary

    # framed aggregates
    if name == "count":
        if not fn.arg_channels:
            ones = jnp.ones(seg.shape[0], jnp.int64)
            s, _ = W.framed_sum_count(seg, ones, None, lo, hi)
            return rt, s, None, None
        c = columns[fn.arg_channels[0]]
        _, cnt = W.framed_sum_count(
            seg, jnp.zeros(seg.shape[0], jnp.int64), c.valid, lo, hi)
        return rt, cnt, None, None
    if name in ("sum", "avg"):
        c = columns[fn.arg_channels[0]]
        vals = c.values
        if T.is_integral(c.type) or isinstance(c.type, T.DecimalType):
            vals = vals.astype(jnp.int64)
        s, cnt = W.framed_sum_count(seg, vals, c.valid, lo, hi)
        ok = cnt > 0
        if name == "sum":
            return rt, s.astype(rt.np_dtype), ok, None
        cnt_safe = jnp.maximum(cnt, 1)
        if isinstance(rt, T.DecimalType):
            # scaled-integer average, round half away from zero
            q = s / cnt_safe
            avg = jnp.where(q >= 0, jnp.floor(q + 0.5),
                            jnp.ceil(q - 0.5)).astype(jnp.int64)
            return rt, avg, ok, None
        avg = s.astype(jnp.float64) / cnt_safe.astype(jnp.float64)
        return rt, avg, ok, None
    if name in ("min", "max"):
        c = columns[fn.arg_channels[0]]
        vals, ok = W.framed_minmax(seg, peer, c.values, c.valid,
                                   fn.frame_unit, fn.frame_start,
                                   fn.frame_end, is_max=(name == "max"),
                                   lo=lo, hi=hi)
        return rt, vals, ok, c.dictionary
    raise NotImplementedError(f"window function {name}")


class WindowOperator(Operator):
    """Spill-capable (SURVEY §2.9: WindowOperator is a spill consumer):
    input accumulates through an embedded external sort keyed by
    (partition, order) — over the revocable threshold, sorted runs go to
    the spill tier and are k-way merged at finish — then window
    evaluation proceeds chunk-by-chunk over groups of COMPLETE
    partitions, so device memory is bounded by the chunk size (a single
    partition larger than memory still must fit, as in the reference)."""

    def __init__(self, ctx: OperatorContext,
                 partition_channels: Sequence[int],
                 order_keys: Sequence[Tuple[int, bool, Optional[bool]]],
                 functions: Sequence[PlanWindowFunction]):
        super().__init__(ctx)
        self.partition_channels = list(partition_channels)
        self.order_keys = list(order_keys)
        self.functions = list(functions)
        self._batches: List[Batch] = []
        self._sorter = None
        self._outputs: List[Batch] = []

    def _sort_specs(self):
        from presto_tpu.exec.sortop import SortSpec

        specs = [SortSpec(ch, False, False)
                 for ch in self.partition_channels]
        specs += [SortSpec(ch, not asc, bool(nf))
                  for ch, asc, nf in self.order_keys]
        return specs

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        specs = self._sort_specs()
        if not specs:
            # OVER (): one global partition — nothing to sort or chunk
            self._batches.append(batch)
            self.ctx.memory.reserve(batch.size_bytes)
            return
        if self._sorter is None:
            from presto_tpu.exec.context import OperatorContext as OC
            from presto_tpu.exec.sortop import OrderByOperator

            sub = OC(self.ctx.task, f"{self.ctx.name}.sort")
            self._sorter = OrderByOperator(sub, specs)
        self._sorter.add_input(batch)

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        if self._sorter is None:
            data = device_concat(self._batches,
                                 self.ctx.config.min_batch_capacity)
            self._batches = []
            self.ctx.memory.free()
            if data is not None:
                self._emit(self._evaluate(data, presorted=False))
            return
        self._sorter.finish()
        self._consume_sorted()
        self._sorter = None

    def close(self) -> None:
        super().close()
        # the embedded sorter is not in the driver's operator list: free
        # its reservations and spilled run files here (failure paths
        # included — the Driver close invariant)
        if self._sorter is not None:
            try:
                self._sorter.close()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass
            self._sorter = None

    def _emit(self, out: Batch) -> None:
        self._outputs.append(out)
        self.ctx.stats.output_rows += out.num_rows

    def _partition_starts(self, batch: Batch, prev_tail):
        """Host-side: bool[n] marking rows that START a new partition,
        given the previous stream row's key tuple (or None).  Returns
        (starts, this batch's last-row key tuple)."""
        import numpy as np

        n = batch.num_rows
        if not self.partition_channels:
            starts = np.zeros(n, bool)
            if prev_tail is None and n:
                starts[0] = True
            return starts, ()
        vals = []
        for ch in self.partition_channels:
            c = batch.columns[ch]
            v = np.asarray(c.values)[:n]
            if c.dictionary is not None:
                # codes are per-batch after a merge of spilled runs:
                # compare decoded values
                dic = np.asarray(list(c.dictionary.values) or [""],
                                 dtype=object)
                v = dic[np.clip(v, 0, len(dic) - 1)]
            g = (np.ones(n, bool) if c.valid is None
                 else np.asarray(c.valid)[:n])
            if c.valid is not None:
                # NULL rows may carry arbitrary buffer residue: mask
                # values so null==null (the validity bit carries the
                # distinction), matching the cross-batch tail compare
                v = v.copy()
                v[~g] = "" if v.dtype == object else v.dtype.type(0)
            if v.dtype.kind == "f":
                # NaN != NaN would split a NaN partition into per-row
                # partitions (and break the cross-batch tail compare);
                # compare bit patterns with NaN canonicalized and -0.0
                # folded into +0.0, matching the device-side segment path
                v = v.copy()
                v[v == 0.0] = 0.0
                w = v.view(np.int64 if v.dtype.itemsize == 8 else np.int32)
                w = w.copy()
                w[np.isnan(v)] = -1
                v = w
            vals.append((v, g))
        starts = np.zeros(n, bool)
        for v, g in vals:
            diff = np.zeros(n, bool)
            diff[1:] = (v[1:] != v[:-1]) | (g[1:] != g[:-1])
            starts |= diff
        if prev_tail is None:
            if n:
                starts[0] = True
        else:
            first = tuple((None if not g[0] else v[0])
                          for v, g in vals)
            if first != prev_tail:
                starts[0] = True
        tail = tuple((None if not g[-1] else v[-1]) for v, g in vals) \
            if n else prev_tail
        return starts, tail

    def _consume_sorted(self) -> None:
        """Stream the (possibly spill-merged) sorted batches, cutting
        evaluation chunks at partition boundaries."""
        import numpy as np

        from presto_tpu.batch import concat_batches

        target = max(self.ctx.config.scan_batch_rows, 1)
        pending: List[Batch] = []
        pending_rows = 0
        # global row index (within pending) of each partition start
        starts_acc: List[int] = []
        prev_tail = None

        def evaluate_rows(batches: List[Batch]) -> None:
            data = device_concat(batches,
                                 self.ctx.config.min_batch_capacity)
            if data is not None:
                self._emit(self._evaluate(data, presorted=True))

        while True:
            b = self._sorter.get_output()
            if b is None:
                break
            hb = b.compact().to_numpy() if pending else b
            starts, prev_tail = self._partition_starts(hb, prev_tail)
            starts_acc.extend((pending_rows + i)
                              for i in np.nonzero(starts)[0])
            pending.append(hb)
            pending_rows += hb.num_rows
            if pending_rows >= target:
                # split at the LAST partition start > 0 so every emitted
                # chunk holds only complete partitions
                cut = None
                for s in reversed(starts_acc):
                    if s > 0:
                        cut = s
                        break
                if cut is None:
                    continue      # one giant partition: keep growing
                merged = (concat_batches([x.compact().to_numpy()
                                          for x in pending])
                          if len(pending) > 1 else
                          pending[0].compact().to_numpy())
                head = merged.take(np.arange(0, cut))
                rest = merged.take(np.arange(cut, merged.num_rows))
                evaluate_rows([head])
                pending = [rest] if rest.num_rows else []
                pending_rows = rest.num_rows
                starts_acc = [s - cut for s in starts_acc if s >= cut]
        if pending_rows:
            evaluate_rows(pending)

    def _sort_and_segment(self, data: Batch, presorted: bool = False):
        """Sort by (partition, order) and derive partition/peer segment
        ids — shared by the window evaluation and the TopNRowNumber
        truncation (computed ONCE, not once per consumer; the cost of a
        dispatch is not measured on the chip).  ``presorted`` skips the
        sort (spill-merged chunks arrive already ordered)."""
        import jax.numpy as jnp

        from presto_tpu.ops import window as W
        from presto_tpu.ops.sort import sort_permutation

        n = data.num_rows
        cap = data.capacity

        def sort_key(channel: int, desc: bool, nulls_first: bool):
            c = data.columns[channel]
            if c.type.is_dictionary:
                ranks = c.dictionary.sort_ranks()
                return (jnp.asarray(ranks)[c.values], c.valid, T.INTEGER,
                        desc, nulls_first)
            return (c.values, c.valid, c.type, desc, nulls_first)

        keys = [sort_key(ch, False, False) for ch in self.partition_channels]
        keys += [sort_key(ch, not asc, bool(nf))
                 for ch, asc, nf in self.order_keys]
        if keys and not presorted:
            perm = sort_permutation(keys, jnp.asarray(n))
            data = Batch(tuple(
                Column(c.type, c.values[perm],
                       None if c.valid is None else c.valid[perm],
                       c.dictionary)
                for c in data.columns), n)

        # adjacent-row equality -> partition segments / peer groups.
        # liveness participates as a pseudo-key so padding rows (all
        # sorted past the live rows) can never merge into the last
        # partition.
        live = jnp.arange(cap) < n

        def eq_prev(channel: int):
            c = data.columns[channel]
            v = c.values
            same = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), v[1:] == v[:-1]])
            if c.valid is not None:
                g = c.valid
                both_null = jnp.concatenate(
                    [jnp.ones((1,), jnp.bool_), (~g[1:]) & (~g[:-1])])
                both_ok = jnp.concatenate(
                    [jnp.ones((1,), jnp.bool_), g[1:] & g[:-1]])
                same = both_null | (both_ok & same)
            return same

        part_eq = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                   live[1:] == live[:-1]])
        for ch in self.partition_channels:
            part_eq = part_eq & eq_prev(ch)
        seg = W.segment_ids(part_eq)
        peer_eq = part_eq
        for ch, _, _ in self.order_keys:
            peer_eq = peer_eq & eq_prev(ch)
        peer = W.segment_ids(peer_eq)
        return data, seg, peer, live

    def _evaluate(self, data: Batch, presorted: bool = False) -> Batch:
        data, seg, peer, _live = self._sort_and_segment(data, presorted)
        out_cols = list(data.columns)
        for fn in self.functions:
            out_cols.append(self._eval_function(fn, data, seg, peer))
        return Batch(tuple(out_cols), data.num_rows)

    def _eval_function(self, fn: PlanWindowFunction, data: Batch,
                       seg, peer) -> Column:
        rt, vals, ok, d = eval_window_function(fn, data.columns, seg, peer)
        return Column(rt, vals, ok, d)

    def get_output(self) -> Optional[Batch]:
        if self._outputs:
            return self._outputs.pop(0)
        return None

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


class TopNRowNumberOperator(WindowOperator):
    """Fused ``row_number() OVER (partition ORDER BY ...) <= N``
    (TopNRowNumberOperator.java:38 role): sorts once by (partition,
    order), keeps only each partition's first N rows, and emits the row
    number with them — the filtered rows never materialize downstream."""

    def __init__(self, ctx: OperatorContext, factory:
                 "TopNRowNumberOperatorFactory"):
        super().__init__(ctx, factory.partition_channels,
                         factory.order_keys, [])
        self.limit = factory.limit
        self.rn_type = factory.rn_type

    def _evaluate(self, data: Batch, presorted: bool = False) -> Batch:
        import jax.numpy as jnp
        import numpy as np

        from presto_tpu.ops import window as W

        full, seg, _peer, live = self._sort_and_segment(data, presorted)
        rn = W.row_number(seg)
        keep = np.asarray(live & (rn <= self.limit))
        idx = np.nonzero(keep)[0]
        out = full.take(jnp.asarray(idx))
        rn_col = Column(self.rn_type,
                        jnp.asarray(rn)[jnp.asarray(idx)]
                        .astype(self.rn_type.np_dtype))
        return Batch(tuple(out.columns) + (rn_col,), len(idx))


class TopNRowNumberOperatorFactory(OperatorFactory):
    def __init__(self, partition_channels: Sequence[int],
                 order_keys: Sequence[Tuple[int, bool, Optional[bool]]],
                 limit: int, rn_type: T.Type):
        self.partition_channels = list(partition_channels)
        self.order_keys = list(order_keys)
        self.limit = limit
        self.rn_type = rn_type

    def create(self, ctx: OperatorContext) -> TopNRowNumberOperator:
        return TopNRowNumberOperator(ctx, self)


class WindowOperatorFactory(OperatorFactory):
    def __init__(self, partition_channels: Sequence[int],
                 order_keys: Sequence[Tuple[int, bool, Optional[bool]]],
                 functions: Sequence[PlanWindowFunction]):
        self.partition_channels = list(partition_channels)
        self.order_keys = list(order_keys)
        self.functions = list(functions)

    def create(self, ctx: OperatorContext) -> WindowOperator:
        return WindowOperator(ctx, self.partition_channels,
                              self.order_keys, self.functions)
