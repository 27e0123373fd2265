"""ORDER BY / TopN operators (OrderByOperator.java:45, TopNOperator.java:35).

Both materialize (as the reference's PagesIndex does), run the device
sort-permutation kernel once, and gather.  TopN is the same kernel with a
truncated gather — a bounded-heap has no TPU advantage over a full
vectorized sort at these sizes.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from presto_tpu.batch import Batch, Column, next_bucket, padded_table
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import Operator, OperatorFactory, device_concat
from presto_tpu.spans import activity


@dataclasses.dataclass(frozen=True)
class SortSpec:
    channel: int
    descending: bool = False
    nulls_first: bool = False


class OrderByOperator(Operator):
    def __init__(self, ctx: OperatorContext, specs: Sequence[SortSpec],
                 limit: Optional[int] = None):
        super().__init__(ctx)
        self.specs = list(specs)
        self.limit = limit
        self._batches: List[Batch] = []
        self._outputs: List[Batch] = []
        self._runs = []            # spilled sorted runs (FileSpiller each)
        self._accumulated_bytes = 0

    def add_input(self, batch: Batch) -> None:
        self._batches.append(batch)
        self.ctx.stats.input_rows += batch.num_rows
        self.ctx.memory.reserve(batch.size_bytes)
        self._accumulated_bytes += batch.size_bytes
        # byte threshold OR node-pool pressure (revoke-first: shed
        # revocable state before anyone blocks on the memory pool)
        if self.ctx.should_spill(self._accumulated_bytes):
            self._spill_run()

    def _sort_batches(self, batches: List[Batch],
                      limit: Optional[int] = None) -> Optional[Batch]:
        """Device sort of the concatenated batches (one run): staged
        once, one named program (``order_by``), nothing read.  The
        columns come back padded, cut inside the program to ``limit``'s
        capacity bucket; the first ``num_rows`` are the answer."""
        import numpy as np

        from presto_tpu.ops.sort import sorted_columns

        data = device_concat(batches, self.ctx.config.min_batch_capacity)
        if data is None:
            return None
        keys = []
        for s in self.specs:
            c = data.columns[s.channel]
            # a dictionary column orders by lexicographic rank, computed
            # host-side over the dictionary (strings never sort on device)
            ranks = (padded_table(c.dictionary.sort_ranks())
                     if c.type.is_dictionary else None)
            keys.append((s.channel, c.type, s.descending, s.nulls_first,
                         ranks))
        n = data.num_rows if limit is None else min(limit, data.num_rows)
        out_capacity = data.capacity if limit is None else min(
            data.capacity,
            next_bucket(limit, self.ctx.config.min_batch_capacity))
        self.ctx.stats.jit_dispatches += 1
        outs, perm = sorted_columns(
            keys, [None if c.children else (c.values, c.valid)
                   for c in data.columns],
            np.int32(data.num_rows), out_capacity)
        cols = []
        for c, out in zip(data.columns, outs):
            if out is None:      # nested columns gather host-side
                with activity("device_wait"):
                    perm = np.asarray(perm)
                cols.append(c.to_numpy().take(perm))
            else:
                cols.append(Column(c.type, *out, c.dictionary))
        return Batch(tuple(cols), n)

    def _spill_run(self) -> None:
        """External sort: sort the accumulated chunk on device, spill it as
        one sorted run (OrderByOperator's revocable path; runs are merged
        at finish like the reference's MergeSortedPages)."""
        from presto_tpu.exec.spill import FileSpiller

        run = self._sort_batches(self._batches)
        self._batches = []
        self._accumulated_bytes = 0
        self.ctx.memory.free()
        if run is None:
            return
        import numpy as np

        spiller = FileSpiller(self.ctx.config.spill_path,
                              tag=f"sort-{self.ctx.name}")
        step = max(1, self.ctx.config.scan_batch_rows)
        run = run.to_numpy().compact()
        for lo in range(0, run.num_rows, step):
            hi = min(lo + step, run.num_rows)
            spiller.spill(run.take(np.arange(lo, hi)))
        self._runs.append(spiller)

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        if not self._runs:
            out = self._sort_batches(self._batches, self.limit)
            self._batches = []
            self.ctx.memory.free()
            if out is not None:
                self._outputs.append(out)
                self.ctx.stats.output_rows += out.num_rows
            return
        if self._batches:
            self._spill_run()
        self._merge_runs()

    def _merge_runs(self) -> None:
        """K-way merge of spilled sorted runs (MergeOperator.java:45 logic,
        host-side; output batches stream out bounded)."""
        import heapq

        import numpy as np

        from presto_tpu.batch import concat_batches
        from presto_tpu.ops.keys import to_sortable_i64

        def run_iter(spiller):
            for batch in spiller.read_all():
                yield batch.to_numpy()

        class _Rev:
            """Reverse-comparing wrapper for descending string keys."""

            __slots__ = ("v",)

            def __init__(self, v):
                self.v = v

            def __lt__(self, other):
                return other.v < self.v

            def __eq__(self, other):
                return self.v == other.v

        def batch_words(batch: Batch) -> List[np.ndarray]:
            words = []
            for s in self.specs:
                c = batch.columns[s.channel]
                if c.type.is_dictionary:
                    # Compare actual string values, not per-batch ranks:
                    # each spilled run re-codes into its own dictionary
                    # (concat_batches / per-shard scans), so equal codes or
                    # ranks from different runs denote different strings.
                    # The reference's MergeSortedPages likewise compares
                    # real values.
                    dic = np.asarray(c.dictionary.values, dtype=object)
                    w = dic[np.asarray(c.values)]
                    if s.descending:
                        w = np.array([_Rev(v) for v in w], dtype=object)
                else:
                    w = to_sortable_i64(np, np.asarray(c.values), c.type)
                    if s.descending:
                        w = ~w
                # Always emit the null word so key tuples stay structurally
                # comparable across runs (one run may have nulls in this
                # column while another does not).
                if c.valid is not None:
                    valid = np.asarray(c.valid)
                    null_word = np.where(
                        valid,
                        np.int8(1 if s.nulls_first else 0),
                        np.int8(0 if s.nulls_first else 1))
                    if w.dtype == object:
                        w = np.where(valid, w, "")
                    else:
                        w = np.where(valid, w, np.int64(0))
                else:
                    null_word = np.full(batch.num_rows,
                                        1 if s.nulls_first else 0, np.int8)
                words.append(null_word)
                words.append(w)
            return words

        iters = [run_iter(s) for s in self._runs]
        states = []  # per run: [batch, words, pos]
        heap = []
        for ri, it in enumerate(iters):
            batch = next(it, None)
            if batch is None:
                states.append(None)
                continue
            words = batch_words(batch)
            states.append([batch, words, 0])
            heap.append((tuple(w[0] for w in words), ri))
        heapq.heapify(heap)

        emitted = 0
        limit = self.limit
        # ordered emission: accumulate (batch, idx) picks in order, flush
        # as a Batch whenever the output step fills
        order: List[tuple] = []  # (batch, row_idx)
        step = max(1, self.ctx.config.scan_batch_rows)

        def flush():
            nonlocal order, emitted
            if not order:
                return
            groups: List[Batch] = []
            i = 0
            while i < len(order):
                batch = order[i][0]
                idxs = []
                while i < len(order) and order[i][0] is batch:
                    idxs.append(order[i][1])
                    i += 1
                groups.append(batch.take(np.asarray(idxs, np.int64)))
            merged = concat_batches(groups) if len(groups) > 1 else groups[0]
            if limit is not None and emitted + merged.num_rows > limit:
                merged = merged.head(limit - emitted)
            self._outputs.append(merged)
            self.ctx.stats.output_rows += merged.num_rows
            emitted += merged.num_rows
            order = []

        while heap:
            if limit is not None and emitted + len(order) >= limit:
                break
            _, ri = heapq.heappop(heap)
            batch, words, pos = states[ri]
            order.append((batch, pos))
            pos += 1
            if pos >= batch.num_rows:
                nxt = next(iters[ri], None)
                if nxt is None:
                    states[ri] = None
                else:
                    w = batch_words(nxt)
                    states[ri] = [nxt, w, 0]
                    heapq.heappush(heap, (tuple(x[0] for x in w), ri))
            else:
                states[ri][2] = pos
                heapq.heappush(heap,
                               (tuple(w[pos] for w in words), ri))
            if len(order) >= step:
                flush()
        flush()
        for s in self._runs:
            s.close()
        self._runs = []

    def close(self) -> None:
        super().close()
        for s in self._runs:
            try:
                s.close()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass
        self._runs = []

    def get_output(self) -> Optional[Batch]:
        if not self._outputs:
            return None
        return self._outputs.pop(0)

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


class OrderByOperatorFactory(OperatorFactory):
    def __init__(self, specs: Sequence[SortSpec],
                 limit: Optional[int] = None):
        self.specs = list(specs)
        self.limit = limit

    def create(self, ctx: OperatorContext) -> OrderByOperator:
        return OrderByOperator(ctx, self.specs, self.limit)
