"""Filter compaction kernel.

The reference's compiled PageFilter produces SelectedPositions consumed by
projections (presto-main/.../operator/project/PageProcessor.java:100).  The
device equivalent turns a boolean mask into a static-capacity gather index
vector plus a live count, after which every downstream op is a plain
gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def selected_positions(mask: jax.Array, valid, num_rows: jax.Array,
                       out_capacity: int):
    """(selection indices int32 [out_capacity], count): the live
    positions ascending, then zeros.

    NULL predicate results are "not selected" (SQL WHERE semantics).
    ``count`` can exceed out_capacity only if out_capacity < capacity;
    callers size out_capacity == input capacity to make overflow
    impossible (filters never grow rows).

    What ``jnp.nonzero(live, size=out_capacity, fill_value=0)`` returns,
    in int32: each live row's rank is where its position goes.  nonzero
    is a bincount of the cumsum, with 64-bit indices an int64
    scatter-add over repeated indices: the chip's compiler spent 4-6 s
    on it a program (PR 34) and the chip 4.5 ms a 65,536-row launch,
    most of a filtering segment's (PERF.md, PR 37).
    """
    cap = mask.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    live = (pos < num_rows) & mask
    if valid is not None:
        live = live & valid
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    # dead rows write to distinct slots past both capacities, so the
    # index vector is unique as the scatter is told, and mode="drop"
    # discards them (ops/join.py _expand_probe_idx)
    dead = max(cap, out_capacity) + pos
    idx = (jnp.zeros(out_capacity, jnp.int32)
           .at[jnp.where(live, rank, dead)]
           .set(pos, mode="drop", unique_indices=True))
    return idx, live.sum()
