"""Operator protocol + shared device-batch plumbing.

The contract is the reference's Operator SPI verbatim
(presto-main/.../operator/Operator.java:20-102):

    needs_input() / add_input(batch) / get_output() / finish() /
    is_finished()

kept because the *control plane* of a pull/push pipeline is
hardware-agnostic; what changes on TPU is that each operator's data plane
is a jitted XLA program over padded static shapes.  ``accumulate``-style
operators (agg, join build, sort) materialize their input exactly like the
reference's PagesIndex-backed operators do, then run one kernel at finish.

``device_concat`` / ``pad_columns`` implement the padding-bucket policy
(SURVEY §7 hard part #1): every kernel sees power-of-two row capacities so
XLA compiles a small, reusable set of programs per query shape.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.batch import Batch, Column, next_bucket
from presto_tpu import kernelcache
from presto_tpu.exec.context import OperatorContext
from presto_tpu.kernelcache import (
    cache_get, cache_put, new_cache, timed_first_call,
)
from presto_tpu.spans import activity


class Operator:
    """One physical operator instance (single driver)."""

    def __init__(self, ctx: OperatorContext):
        self.ctx = ctx
        self._finishing = False

    # -- control protocol (reference-identical) -------------------------
    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, batch: Batch) -> None:
        raise NotImplementedError

    def get_output(self) -> Optional[Batch]:
        return None

    def finish(self) -> None:
        """No more input will arrive (Operator.finish)."""
        self._finishing = True

    def is_finished(self) -> bool:
        raise NotImplementedError

    def close(self) -> None:
        self.ctx.memory.free()


class OperatorFactory:
    """Creates per-driver Operator instances
    (reference OperatorFactory; duplicated per driver for parallelism).

    ``parallel_safe`` marks row-local factories (scan, filter/project,
    unnest, dynamic filter) whose operators may replicate into N
    concurrent feed drivers without changing results — the
    AddLocalExchanges eligibility bit."""

    parallel_safe = False

    def create(self, ctx: OperatorContext) -> Operator:
        raise NotImplementedError

    def reset_for_execution(self) -> None:
        """Clear cross-execution factory state so a cached PhysicalPlan
        can be re-executed (the plan-cache physical-factory sharing
        path).  Most factories keep all runtime state in the Operators
        they create and need nothing; factories that rendezvous ACROSS
        pipelines (output collector, union buffer, build sides) override
        to re-arm their shared state."""

    @property
    def name(self) -> str:
        return type(self).__name__.replace("Factory", "")


class SourceOperator(Operator):
    """An operator at pipeline position 0 fed by splits, not batches
    (reference SourceOperator; split delivery is the scheduler's job)."""

    def add_split(self, split) -> None:
        raise NotImplementedError

    def no_more_splits(self) -> None:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Device-batch helpers
# ---------------------------------------------------------------------------

def rebucket(batch: Batch, min_capacity: int = 1024) -> Batch:
    """Re-pad a sparsely occupied batch down to its capacity bucket.

    Expansion-sized join/filter outputs otherwise amplify capacity
    multiplicatively down an operator chain (126 live rows riding a
    67M-row padded batch after 5 joins); two static-shape device copies
    (slice + zero-pad) reset the invariant.
    """
    cap = next_bucket(batch.num_rows, min_capacity)
    if batch.capacity <= cap:
        return batch
    return batch.head(batch.num_rows).pad_rows(cap)


def pad_batch(batch: Batch, min_capacity: int = 1024) -> Batch:
    """Pad to the power-of-two bucket and move to device."""
    cap = next_bucket(batch.num_rows, min_capacity)
    with activity("stage_h2d"):
        padded = batch.pad_rows(cap)
    return padded.to_device()


def device_concat(batches: Sequence[Batch], min_capacity: int = 1024) -> Batch:
    """Concatenate batches into one padded device Batch.

    Device-resident inputs stay on the device: a single batch already at
    its capacity bucket is returned as it is, anything else is appended
    into the output bucket by ONE cached program per (output bucket,
    input bucket) pair (`_append_kernel`).  A program over all inputs at
    once would be keyed by the ordered tuple of their capacities, which
    follows how batches happened to coalesce: on TPC-H Q3 at SF1 that
    compiled a new program in warm runs (PR 25).  Host inputs (exchange
    pages), nested columns and dictionaries that differ between inputs
    take the host concat (dictionary columns are re-coded into a shared
    dictionary there) and are staged once."""
    from presto_tpu.batch import concat_batches

    live = [b for b in batches if b.num_rows > 0]
    if not live:
        return None
    out = _device_append(live, min_capacity)
    if out is not None:
        return out
    with activity("stage_h2d"):
        joined = concat_batches(live)
    return pad_batch(joined, min_capacity)


_APPEND_PROGRAMS = new_cache("device_concat")


def _device_append(live: Sequence[Batch],
                   min_capacity: int) -> Optional[Batch]:
    """The device half of device_concat; None when an input needs the
    host path."""
    first = live[0]
    if not first.columns:
        return None
    for b in live:
        for c, c0 in zip(b.columns, first.columns):
            if (c.children or isinstance(c.values, np.ndarray)
                    or c.dictionary is not c0.dictionary
                    or c.values.dtype != c0.values.dtype):
                return None
    total = sum(b.num_rows for b in live)
    out_cap = next_bucket(total, min_capacity)
    if len(live) == 1 and first.capacity == out_cap:
        return first
    has_valid = tuple(any(b.columns[ci].valid is not None for b in live)
                      for ci in range(len(first.columns)))
    outs = None      # the first append makes the zeroed bucket itself
    offset = 0
    for b in live:
        ins = tuple(
            (c.values, None if not hv else c.valid if c.valid is not None
             else np.ones(b.capacity, bool))
            for c, hv in zip(b.columns, has_valid))
        key = (out_cap, b.capacity, has_valid, outs is None,
               tuple((c.values.dtype.str, c.values.shape[1:])
                     for c in b.columns))
        program = cache_get(_APPEND_PROGRAMS, key)
        if program is None:
            kernel = (_append_kernel if outs is not None
                      else _first_append_kernel(out_cap))
            program = timed_first_call(
                kernelcache.jit(kernel, "device_append", donate_argnums=0),
                None, _APPEND_PROGRAMS)
            cache_put(_APPEND_PROGRAMS, key, program)
        with activity("dispatch"):
            outs = program(outs, ins, np.int32(offset),
                           np.int32(b.num_rows))
        offset += b.num_rows
    return Batch(tuple(Column(c.type, values, valid, c.dictionary)
                       for c, (values, valid) in zip(first.columns, outs)),
                 total)


def _append_kernel(outs, ins, offset, num_rows):
    """Write the first ``num_rows`` rows of every input array into the
    output array at ``offset``; arrays pair up leaf by leaf.  Gather- and
    scatter-free: the input (cut to the output's length, every live row
    is inside it) is rotated to its place within one window of the
    output, masked in, and the window written back, so the program
    depends on the two capacities only and never on the row counts."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def append(out, x):
        window = min(x.shape[0], out.shape[0])
        start = jnp.minimum(offset, out.shape[0] - window)
        shift = offset - start
        tail = (0,) * (out.ndim - 1)
        current = lax.dynamic_slice(out, (start,) + tail,
                                    (window,) + out.shape[1:])
        row = jnp.arange(window, dtype=jnp.int32)
        mine = (row >= shift) & (row < shift + num_rows)
        mine = mine.reshape((window,) + (1,) * (out.ndim - 1))
        merged = jnp.where(mine, jnp.roll(x[:window], shift, axis=0),
                           current)
        return lax.dynamic_update_slice(out, merged, (start,) + tail)

    return jax.tree_util.tree_map(append, outs, ins)


def _first_append_kernel(out_cap: int):
    """``_append_kernel`` for the first input: ``out_cap`` zeroed rows of
    every array are made inside the program (``jnp.zeros`` outside it is
    a program and a launch an array)."""
    import jax
    import jax.numpy as jnp

    def kernel(_no_outs, ins, offset, num_rows):
        outs = jax.tree_util.tree_map(
            lambda x: jnp.zeros((out_cap,) + x.shape[1:], x.dtype), ins)
        return _append_kernel(outs, ins, offset, num_rows)

    return kernel


def column_pairs(batch: Batch) -> List[Tuple[object, object]]:
    return [(c.values, c.valid) for c in batch.columns]
