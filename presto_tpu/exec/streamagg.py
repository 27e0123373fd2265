"""Streaming aggregation over key-clustered input.

The reference's StreamingAggregationOperator
(presto-main/.../operator/StreamingAggregationOperator.java:38) exploits
input that is already sorted/clustered on the group keys: it holds ONE
open group instead of a hash table and emits each group the moment the
next key appears.  Same contract here, TPU-shaped: each batch runs the
sort-free ``clustered_aggregate`` kernel (run-boundary detection +
segment reductions — no lexsort, no rehash), all finished groups of the
batch are emitted together, and only the last (possibly still open)
group's partial state carries to the next batch, merged by the agg
primitive's combine rule.

Chosen by the physical planner when the group channels trace to a
prefix of the scan's declared sort order (Connector.sort_order — the
LocalProperties/StreamPropertyDerivations role).  The pipeline must not
be split into concurrent feed drivers (``requires_ordered_input``):
round-robin feeds would interleave key ranges.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, next_bucket
from presto_tpu.exec.aggregation import AggChannel
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import Operator, OperatorFactory
from presto_tpu.spans import activity


class StreamingAggregationOperator(Operator):
    def __init__(self, ctx: OperatorContext,
                 group_channels: Sequence[int],
                 aggs: Sequence[AggChannel],
                 input_types: Sequence[T.Type]):
        super().__init__(ctx)
        self.group_channels = list(group_channels)
        self.aggs = list(aggs)
        self.input_types = list(input_types)
        self._outputs: List[Batch] = []
        # carried open group: (key row values tuple-of-host-scalars,
        # per-agg (value, count) host scalars, key Columns of 1 row)
        self._carry: Optional[Tuple[tuple, List[Tuple[object, int]],
                                    List[Column]]] = None

    # -- kernel ---------------------------------------------------------
    def _aggregate_batch(self, batch: Batch):
        import jax.numpy as jnp

        from presto_tpu.ops.groupby import clustered_aggregate_jit

        data = batch
        key_cols = [data.columns[c] for c in self.group_channels]
        key_triples = [(c.values, c.valid, c.type) for c in key_cols]
        agg_ins = []
        for a in self.aggs:
            if a.channel is None:
                agg_ins.append(("count", jnp.zeros(data.capacity, jnp.int8),
                                None))
            else:
                col = data.columns[a.channel]
                agg_ins.append((a.prim, col.values, col.valid))
        cap = data.capacity
        group_cap = next_bucket(min(cap, max(data.num_rows, 1)),
                                minimum=16)
        gi, ng, results = clustered_aggregate_jit(
            key_triples, agg_ins, jnp.asarray(data.num_rows), group_cap)
        with activity("device_wait"):
            ng = int(ng)
        return key_cols, gi, ng, results, group_cap

    # -- carry merge (the combine rule per primitive) --------------------
    @staticmethod
    def _combine(prim: str, a, b, cnt_a: int, cnt_b: int):
        if cnt_a == 0:
            return b
        if cnt_b == 0:
            return a
        if prim in ("sum", "count"):
            return a + b
        if prim == "min":
            return min(a, b)
        if prim == "max":
            return max(a, b)
        raise ValueError(prim)

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_batches += 1
        self.ctx.stats.input_rows += batch.num_rows
        if batch.num_rows == 0:
            return
        (key_cols, gi, ng, results,
         group_cap) = self._aggregate_batch(batch)
        if ng == 0:
            return
        # host-materialize the per-group outputs (ng rows)
        gi_h = np.asarray(gi)[:ng]
        key_out = [c.take(gi_h).to_numpy() for c in key_cols]
        vals_h = []
        cnts_h = []
        for values, cnt in results:
            vals_h.append(np.asarray(values)[:ng])
            cnts_h.append(np.asarray(cnt)[:ng])
        first_key = tuple(k.to_pylist(ng)[0] for k in key_out)

        # merge the carried open group into this batch's FIRST group
        # when the key continues; otherwise flush the carry as its own
        # finished group
        flush_rows: List[Tuple[List[Column], List[Tuple[object, int]]]] = []
        if self._carry is not None:
            ckey, cstate, ckey_cols = self._carry
            if ckey == first_key:
                for i, a in enumerate(self.aggs):
                    merged = self._combine(
                        a.prim, cstate[i][0], vals_h[i][0].item(),
                        cstate[i][1], int(cnts_h[i][0]))
                    vals_h[i] = vals_h[i].copy()
                    vals_h[i][0] = merged
                    cnts_h[i] = cnts_h[i].copy()
                    cnts_h[i][0] = cstate[i][1] + int(cnts_h[i][0])
            else:
                flush_rows.append((ckey_cols, cstate))
            self._carry = None

        # carry the LAST group (still open until a new key or finish)
        last = ng - 1
        carry_key = tuple(k.to_pylist(ng)[last] for k in key_out)
        carry_state = [(vals_h[i][last].item(), int(cnts_h[i][last]))
                       for i in range(len(self.aggs))]
        carry_cols = [Column(c.type, c.values[last:last + 1],
                             None if c.valid is None
                             else c.valid[last:last + 1],
                             c.dictionary) for c in key_out]
        self._carry = (carry_key, carry_state, carry_cols)

        emit = ng - 1  # all but the open last group
        out_batches = []
        if flush_rows:
            out_batches.append(self._state_batch(*flush_rows[0]))
        if emit > 0:
            cols = [Column(c.type, c.values[:emit],
                           None if c.valid is None else c.valid[:emit],
                           c.dictionary) for c in key_out]
            for a, v, cnt in zip(self.aggs, vals_h, cnts_h):
                cols.append(self._agg_column(a, v[:emit], cnt[:emit]))
            out_batches.append(Batch(tuple(cols), emit))
        for b in out_batches:
            self.ctx.stats.output_batches += 1
            self.ctx.stats.output_rows += b.num_rows
            self._outputs.append(b)

    def _agg_column(self, a: AggChannel, vals: np.ndarray,
                    cnts: np.ndarray) -> Column:
        vals = vals.astype(a.out_type.np_dtype)
        if a.prim == "count":
            return Column(a.out_type, vals)
        valid = cnts > 0
        return Column(a.out_type, vals,
                      None if bool(valid.all()) else valid)

    def _state_batch(self, key_cols: List[Column],
                     state: List[Tuple[object, int]]) -> Batch:
        cols = list(key_cols)
        for a, (v, cnt) in zip(self.aggs, state):
            vals = np.asarray([v if v is not None else 0],
                              dtype=a.out_type.np_dtype)
            cols.append(Column(a.out_type, vals,
                               None if (cnt > 0 or a.prim == "count")
                               else np.asarray([False])))
        return Batch(tuple(cols), 1)

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        if self._carry is not None:
            ckey, cstate, ckey_cols = self._carry
            b = self._state_batch(ckey_cols, cstate)
            self.ctx.stats.output_batches += 1
            self.ctx.stats.output_rows += 1
            self._outputs.append(b)
            self._carry = None

    def get_output(self) -> Optional[Batch]:
        if self._outputs:
            return self._outputs.pop(0)
        return None

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


class StreamingAggregationOperatorFactory(OperatorFactory):
    # concurrent feed drivers would interleave key ranges and break the
    # clustering contract — the runner must keep this pipeline serial
    requires_ordered_input = True

    def __init__(self, group_channels: Sequence[int],
                 aggs: Sequence[AggChannel],
                 input_types: Sequence[T.Type]):
        for a in aggs:
            # the planner's eligibility check guarantees this; direct
            # construction must honor it too (the carry merge would
            # compare dictionary interning codes)
            assert not (a.prim in ("min", "max") and a.channel is not None
                        and input_types[a.channel].is_dictionary), \
                "min/max over dictionary columns is not streamable"
        self.group_channels = list(group_channels)
        self.aggs = list(aggs)
        self.input_types = list(input_types)

    def create(self, ctx: OperatorContext) -> StreamingAggregationOperator:
        return StreamingAggregationOperator(
            ctx, self.group_channels, self.aggs, self.input_types)
