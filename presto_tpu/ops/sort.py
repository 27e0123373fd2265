"""ORDER BY / TopN kernels.

The reference sorts via PagesIndex + codegen'd comparators
(OrderByOperator.java:45, OrderingCompiler.java:62) and keeps a bounded
heap for TopN (TopNOperator.java:35).  On TPU both are the same primitive:
a multi-word lexicographic sort over order-preserving int64 key words
(XLA's sort is a vectorized bitonic/radix network), with TopN simply
truncating the permutation.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import kernelcache
from presto_tpu import types as T
from presto_tpu.spans import activity
from presto_tpu.kernelcache import (
    cache_get, cache_put, new_cache, timed_first_call,
)
from presto_tpu.ops.keys import to_sortable_i64

_SORT_PROGRAMS = new_cache("sort")

# (values, valid|None, type, descending, nulls_first)
SortKey = Tuple[jax.Array, Optional[jax.Array], T.Type, bool, bool]


def sort_permutation(keys: Sequence[SortKey], num_rows: jax.Array) -> jax.Array:
    """Stable permutation ordering live rows by the sort spec; padding rows
    sort to the end.

    On TPU this routes to the radix passes (ops/radix.py): XLA's sort
    lowering compiles in time proportional to N there.  CPU/GPU keep the
    native sort.

    Always ONE cached jitted program per sort spec (the "sort" kernel
    cache, compile time attributed there): operators call this
    eagerly, and the radix passes are dozens of ops with a ``lax.cond``
    each — dispatched eagerly, every cond recompiles on every call (its
    branches are fresh closures), which on the chip was 32 XLA compiles
    and ~5 s per ORDER BY of four rows.  Inside a trace the nested jit
    inlines."""
    from presto_tpu.ops.radix import use_radix

    spec = tuple((typ, desc, nulls_first)
                 for _values, _valid, typ, desc, nulls_first in keys)
    radix = use_radix()
    program = cache_get(_SORT_PROGRAMS, (spec, radix))
    if program is None:
        program = timed_first_call(
            kernelcache.jit(_sort_kernel(spec, radix), "sort"), None,
            _SORT_PROGRAMS)
        cache_put(_SORT_PROGRAMS, (spec, radix), program)
    with activity("dispatch"):
        return program(tuple(values for values, *_ in keys),
                       tuple(valid for _values, valid, *_ in keys),
                       num_rows)


def _sort_kernel(spec, radix: bool):
    def kernel(values, valids, num_rows):
        keys = [(v, valid, typ, desc, nulls_first)
                for v, valid, (typ, desc, nulls_first)
                in zip(values, valids, spec)]
        return _permutation(keys, num_rows, radix)

    return kernel


def _permutation(keys: Sequence[SortKey], num_rows: jax.Array,
                 radix: bool) -> jax.Array:
    if radix:
        from presto_tpu.ops.radix import radix_sort_permutation

        return radix_sort_permutation(keys, num_rows)
    return _lexsort_permutation(keys, num_rows)


def sorted_columns(keys, columns, num_rows, out_capacity: int):
    """ORDER BY's finish as ONE cached jitted program (``order_by``): the
    permutation of ``sort_permutation`` and every column gathered
    through its first ``out_capacity`` entries (a TopN's limit, rounded
    up to a capacity bucket by the caller, gathers no more).

    ``keys``: per sort key ``(channel, type, descending, nulls_first,
    ranks)``; ``ranks`` is None, or for a dictionary column its host
    code -> lexicographic rank table, which the program gathers through
    (strings never sort on device; pad the table to a bucketed length).
    ``columns``: per channel a ``(values, valid|None)`` pair, or None for
    a column that stays behind.  Returns ``(columns, perm)``, ``perm``
    int32, for what the caller gathers itself."""
    from presto_tpu.ops.radix import use_radix

    spec = tuple(key[:4] for key in keys)
    radix = use_radix()
    key = ("columns", spec, radix, out_capacity)
    program = cache_get(_SORT_PROGRAMS, key)
    if program is None:
        program = timed_first_call(
            kernelcache.jit(_columns_kernel(spec, radix, out_capacity),
                            "order_by"), None, _SORT_PROGRAMS)
        cache_put(_SORT_PROGRAMS, key, program)
    with activity("dispatch"):
        return program(tuple(columns), tuple(key[4] for key in keys),
                       num_rows)


def _columns_kernel(spec, radix: bool, out_capacity: int):
    def kernel(columns, tables, num_rows):
        keys = []
        for (channel, typ, desc, nulls_first), ranks in zip(spec, tables):
            values, valid = columns[channel]
            if ranks is not None:
                values, typ = ranks[values], T.INTEGER
            keys.append((values, valid, typ, desc, nulls_first))
        perm = _permutation(keys, num_rows, radix)
        # i32 gather indices are ~5x cheaper on TPU
        perm = perm[:out_capacity].astype(jnp.int32)
        return jax.tree_util.tree_map(lambda x: x[perm], columns), perm

    return kernel


def _lexsort_permutation(keys: Sequence[SortKey],
                         num_rows: jax.Array) -> jax.Array:
    cap = keys[0][0].shape[0]
    pad = (jnp.arange(cap) >= num_rows).astype(jnp.int8)
    major = []  # built major-to-minor, reversed for lexsort below
    for values, valid, typ, desc, nulls_first in keys:
        w = to_sortable_i64(jnp, values, typ)
        if desc:
            w = ~w  # exact order reversal for two's-complement words
        if valid is not None:
            null_word = jnp.where(valid,
                                  jnp.int8(1 if nulls_first else 0),
                                  jnp.int8(0 if nulls_first else 1))
            w = jnp.where(valid, w, jnp.int64(0))
            major.append(null_word)
        major.append(w)
    # lexsort: last element of the tuple is the PRIMARY key
    minor_to_major = tuple(reversed(major)) + (pad,)
    return jnp.lexsort(minor_to_major)
