"""Grouped aggregation kernel.

The reference's HashAggregationOperator drives GroupByHash — open-addressing
linear probing with rehash (presto-main/.../operator/MultiChannelGroupByHash.java:273-286)
— and codegen'd accumulators (AccumulatorCompiler.java:80).

The TPU-native design is *sort-based*: scatter-free, shape-static, and
entirely made of primitives XLA schedules well on the VPU:

    normalize keys -> lexsort -> run-boundary detection -> segment reduce

- No rehash problem (hard part #1 in SURVEY §7): capacity is a static
  bucket; a ``num_groups`` scalar reports overflow so the host can re-run
  at the next bucket (the recompile-on-bucket-change policy).
- Padding rows sort to the end (pad flag is the primary sort word) and fall
  into a trailing garbage group that is simply not counted.
- Exact grouping: sorting compares full key words, so there are no hash
  collisions to resolve — the 1-byte-hash-prefix trick of PagesHash:49 has
  no analogue because there is no probe loop at all.

Aggregation primitives are sum/count/min/max (planner decomposes
avg/stddev/... into these, mirroring the partial/final Step split of
HashAggregationOperator.Step:61).

Two grouping tiers coexist here, chosen per operator (a group-by whose
keys arrive clustered streams through ``clustered_aggregate`` instead,
exec/streamagg.py):

- **direct** (``direct_grouped_aggregate``): bounded key domains
  (dictionary codes/booleans) — the BigintGroupByHash special-case role
  (GroupByHash.java:30-43); fastest where it applies.
- **sort** (``grouped_aggregate``): every other key, exact and
  rehash-free: one sort of what the operator accumulated
  (exec/aggregation.py).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import kernelcache
from presto_tpu import types as T
from presto_tpu.batch import padded_table
from presto_tpu.kernelcache import cache_get, cache_put, new_cache
from presto_tpu.ops.filter import selected_positions
from presto_tpu.ops.keys import normalize_keys
from presto_tpu.spans import activity


# One aggregation input: (prim, values, valid|None) with prim in
# {'sum','count','min','max'}; 'count' ignores values.
AggIn = Tuple[str, Optional[jax.Array], Optional[jax.Array]]


def _segment_ids(key_words: List[jax.Array], pad: jax.Array):
    """Sort rows by (pad, keys); return (perm, gid_sorted, boundaries)."""
    from presto_tpu.ops.radix import radix_argsort_i64, use_radix

    # zero pad rows' keys so they collide into one trailing run
    cleaned = [jnp.where(pad, jnp.int64(0), w) for w in key_words]
    if use_radix():
        perm = radix_argsort_i64(cleaned, pad=pad)
    else:
        # lexsort: LAST key is primary; we want pad primary, then keys.
        perm = jnp.lexsort(tuple(cleaned[::-1]) + (pad.astype(jnp.int8),))
    perm = perm.astype(jnp.int32)  # i32 gather indices are ~5x cheaper on TPU
    sorted_pad = pad[perm]
    boundary = jnp.zeros(perm.shape[0], dtype=bool).at[0].set(True)
    for w in cleaned:
        ws = w[perm]
        boundary = boundary.at[1:].set(boundary[1:] | (ws[1:] != ws[:-1]))
    boundary = boundary.at[1:].set(
        boundary[1:] | (sorted_pad[1:] != sorted_pad[:-1]))
    gid = jnp.cumsum(boundary) - 1
    return perm, gid, boundary


def _min_identity(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(True, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _max_identity(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(False, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def grouped_aggregate(
    key_columns: Sequence[Tuple[jax.Array, Optional[jax.Array], T.Type]],
    aggs: Sequence[AggIn],
    num_rows: jax.Array,
    group_capacity: int,
    live_mask: Optional[jax.Array] = None,
):
    """Aggregate ``aggs`` per distinct key tuple.

    All arrays share one (padded) row capacity; ``num_rows`` is the dynamic
    live-row count.  Returns::

        (group_index: int array [group_capacity]   # row index of each
                                                   # group's representative
         num_groups: int scalar,                   # may EXCEED capacity ->
                                                   # caller re-runs bigger
         results: [(values[group_capacity], count_nonnull[group_capacity])])

    Key/grouped-output columns are gathered by the caller via
    ``group_index`` (valid for the first ``min(num_groups, capacity)``
    entries), which keeps this kernel agnostic of output channel count.
    """
    cap = key_columns[0][0].shape[0]
    pad = jnp.arange(cap) >= num_rows
    if live_mask is not None:
        # fused upstream filter (WHERE without compaction — the mesh SQL
        # tier keeps rows in place and masks them dead)
        pad = pad | ~live_mask
    key_words, _ = normalize_keys(jnp, key_columns, nulls_equal=True)
    perm, gid, boundary = _segment_ids(key_words, pad)
    total_segments = gid[-1] + 1
    # trailing pad segment (present iff any pad row) is not a real group
    any_pad = pad.any()
    num_groups = total_segments - any_pad.astype(total_segments.dtype)

    # representative input row per group (first sorted row of the segment)
    first_sorted_pos = jnp.nonzero(boundary, size=group_capacity,
                                   fill_value=cap - 1)[0]
    group_index = perm[first_sorted_pos]

    results = []
    for prim, values, valid in aggs:
        live = ~pad
        if valid is not None:
            live = live & valid
        live_sorted = live[perm]
        cnt = jax.ops.segment_sum(live_sorted.astype(jnp.int64), gid,
                                  num_segments=group_capacity)
        if prim == "count":
            results.append((cnt, cnt))
            continue
        v = values[perm]
        if prim == "sum":
            zero = jnp.asarray(0, values.dtype)
            v = jnp.where(live_sorted, v, zero)
            out = jax.ops.segment_sum(v, gid, num_segments=group_capacity)
        elif prim == "min":
            ident = _min_identity(values.dtype)
            v = jnp.where(live_sorted, v, ident)
            out = jax.ops.segment_min(v, gid, num_segments=group_capacity)
        elif prim == "max":
            ident = _max_identity(values.dtype)
            v = jnp.where(live_sorted, v, ident)
            out = jax.ops.segment_max(v, gid, num_segments=group_capacity)
        else:
            raise ValueError(f"unknown aggregation primitive {prim}")
        results.append((out, cnt))
    return group_index, num_groups, results


def clustered_aggregate(
    key_columns: Sequence[Tuple[jax.Array, Optional[jax.Array], T.Type]],
    aggs: Sequence[AggIn],
    num_rows: jax.Array,
    group_capacity: int,
):
    """Sort-free grouped aggregation over input ALREADY clustered by the
    key columns (equal keys adjacent): run boundaries come from
    neighbor comparison, groups are segment reductions in input order.
    The StreamingAggregationOperator kernel
    (StreamingAggregationOperator.java:38 role) — emitted groups keep
    the input's key order, so the carry-across-batches merge is the
    first/last group only.

    Returns (group_index, num_groups, results) like grouped_aggregate,
    with group_index pointing at each group's FIRST input row.
    """
    cap = key_columns[0][0].shape[0]
    pad = jnp.arange(cap) >= num_rows
    key_words, _ = normalize_keys(jnp, key_columns, nulls_equal=True)
    boundary = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for w in key_words:
        w = jnp.where(pad, jnp.int64(0), w)
        boundary = boundary.at[1:].set(boundary[1:] | (w[1:] != w[:-1]))
    boundary = boundary.at[1:].set(boundary[1:] | (pad[1:] != pad[:-1]))
    boundary = boundary & ~pad  # pad rows fold into one trailing segment
    gid = jnp.cumsum(boundary) - 1
    gid = jnp.where(pad, gid[-1] + 1, gid).astype(jnp.int32)
    num_groups = jnp.where(num_rows > 0, gid[-1] + 1
                           - pad.any().astype(jnp.int32), 0)
    first_pos = jnp.nonzero(boundary, size=group_capacity,
                            fill_value=cap - 1)[0]

    results = []
    for prim, values, valid in aggs:
        live = ~pad
        if valid is not None:
            live = live & valid
        cnt = jax.ops.segment_sum(live.astype(jnp.int64), gid,
                                  num_segments=group_capacity)
        if prim == "count":
            results.append((cnt, cnt))
            continue
        if prim == "sum":
            v = jnp.where(live, values, jnp.asarray(0, values.dtype))
            out = jax.ops.segment_sum(v, gid, num_segments=group_capacity)
        elif prim == "min":
            v = jnp.where(live, values, _min_identity(values.dtype))
            out = jax.ops.segment_min(v, gid, num_segments=group_capacity)
        elif prim == "max":
            v = jnp.where(live, values, _max_identity(values.dtype))
            out = jax.ops.segment_max(v, gid, num_segments=group_capacity)
        else:
            raise ValueError(f"unknown aggregation primitive {prim}")
        results.append((out, cnt))
    return first_pos, num_groups, results


def direct_grouped_aggregate(
    key_codes: Sequence[Tuple[jax.Array, Optional[jax.Array]]],
    domain_sizes: Sequence[int],
    aggs: Sequence[AggIn],
    num_rows: jax.Array,
    live_mask: Optional[jax.Array] = None,
):
    """Small-key-space fast path: mixed-radix group id -> segment reduce.

    The reference special-cases single-BIGINT keys with BigintGroupByHash
    (GroupByHash.java:30-43); the TPU analogue special-cases *bounded* key
    domains (dictionary codes, booleans, small ints): when the product of
    key cardinalities is small, the group id is computed arithmetically and
    aggregation is a handful of segment reductions — no sort, no gather
    (not measured against the sort path on the current code).

    ``key_codes``: per key column ``(codes, valid)`` with codes already in
    ``[0, domain_size)``.  Nullable keys get slot 0 reserved by the +1 shift
    here (null is a group, SQL semantics).  ``live_mask`` fuses an upstream
    filter (WHERE) without compaction.

    Returns ``(present [D] bool, results [(values [D], cnt [D])])`` over
    the dense domain ``D = prod(shifted domains)``; key values for slot g
    decode arithmetically as ``(g // stride_j) % dom_j`` (minus the null
    shift) — no representative-row gather needed.
    """
    cap = key_codes[0][0].shape[0]
    live = jnp.arange(cap) < num_rows
    if live_mask is not None:
        live = live & live_mask
    gid = jnp.zeros(cap, jnp.int32)
    doms = []
    for (codes, valid), dom in zip(key_codes, domain_sizes):
        c = codes.astype(jnp.int32)
        if valid is not None:
            c = jnp.where(valid, c + 1, 0)  # slot 0 = NULL group
            dom = dom + 1
        gid = gid * dom + c
        doms.append(dom)
    total = 1
    for d in doms:
        total *= d
    gid = jnp.where(live, gid, total)  # dead rows -> trailing garbage slot
    n_seg = total + 1

    # --- sums & counts ---------------------------------------------------
    # Small domains ride the MXU: blocked one-hot einsum with a hi/lo f32
    # split (two f32 matmuls + f64 cross-block combine, ~1.5e-9 rel err)
    # in place of a scatter-add segment_sum, which the TPU serializes
    # (not measured against it on the current code).  Above the memory
    # threshold (one-hot is [N, G]) fall back to scatter.
    # Float sums ride the matmul; integer sums must stay exact, so they go
    # through native-dtype scatter even when the matmul path is on (a
    # hi/lo f32 einsum rounds int64 sums near 2^53 — confirmed off-by-4096
    # at (1<<53)+1).  Count columns are sums of ones: exact in either path.
    sum_cols, live_masks, int_sums = [], [], {}
    for i, (prim, values, valid) in enumerate(aggs):
        lv = live if valid is None else (live & valid)
        live_masks.append(lv)
        if prim == "sum":
            if jnp.issubdtype(values.dtype, jnp.floating):
                sum_cols.append(jnp.where(lv, values, 0.0)
                                .astype(jnp.float64))
            else:
                int_sums[i] = jax.ops.segment_sum(
                    jnp.where(lv, values, jnp.asarray(0, values.dtype)),
                    gid, num_segments=n_seg)[:total]
        sum_cols.append(lv.astype(jnp.float64))  # non-null count column
    sum_cols.append(live.astype(jnp.float64))    # group-present count

    # MXU path only on TPU: on CPU, XLA's f32 einsum accumulates worse
    # (~3e-9 rel) while f64 scatter is exact and fast; on TPU the scatter
    # serializes and the matmul does not.  Decided at trace time.
    use_matmul = (n_seg <= 32 and cap % 1024 == 0
                  and jax.default_backend() == "tpu")
    m = jnp.stack(sum_cols, 1)                   # [N, A]
    if use_matmul:
        hi = m.astype(jnp.float32)
        lo = (m - hi.astype(jnp.float64)).astype(jnp.float32)
        block = 2048 if cap % 2048 == 0 else 1024
        B = cap // block
        oh = jax.nn.one_hot(gid.reshape(B, block), n_seg,
                            dtype=jnp.float32)
        # HIGHEST: TPU matmuls default to bf16 passes (1e-4 rel
        # error); HIGHEST forces full-f32 (3-pass bf16) accumulation.
        hp = jax.lax.Precision.HIGHEST
        reduced = (
            jnp.einsum("bng,bna->bga", oh, hi.reshape(B, block, -1),
                       precision=hp).astype(jnp.float64).sum(0)
            + jnp.einsum("bng,bna->bga", oh, lo.reshape(B, block, -1),
                         precision=hp).astype(jnp.float64).sum(0))
    else:
        reduced = jax.ops.segment_sum(m, gid, num_segments=n_seg)
    reduced = reduced[:total]                    # [G, A]

    star = jnp.round(reduced[:, -1]).astype(jnp.int64)
    present = star > 0
    results = []
    col = 0
    for i, ((prim, values, valid), lv) in enumerate(zip(aggs, live_masks)):
        if prim == "sum":
            if i in int_sums:
                out = int_sums[i]
            else:
                out = reduced[:, col]
                col += 1
        cnt = jnp.round(reduced[:, col]).astype(jnp.int64)
        col += 1
        if prim == "count":
            results.append((cnt, cnt))
            continue
        if prim == "sum":
            results.append((out, cnt))
            continue
        if prim == "min":
            v = jnp.where(lv, values, _min_identity(values.dtype))
            out = jax.ops.segment_min(v, gid, num_segments=n_seg)[:total]
        elif prim == "max":
            v = jnp.where(lv, values, _max_identity(values.dtype))
            out = jax.ops.segment_max(v, gid, num_segments=n_seg)[:total]
        else:
            raise ValueError(f"unknown aggregation primitive {prim}")
        results.append((out, cnt))
    return present, results


def decode_direct_keys(slots: jax.Array,
                       key_valids: Sequence[bool],
                       domain_sizes: Sequence[int]):
    """Arithmetically decode dense slot ids back into per-column
    (codes, valid) — the inverse of direct_grouped_aggregate's packing."""
    doms = [d + 1 if nullable else d
            for d, nullable in zip(domain_sizes, key_valids)]
    out = []
    rem = slots
    for dom, nullable in zip(reversed(doms), reversed(key_valids)):
        c = rem % dom
        rem = rem // dom
        if nullable:
            out.append((jnp.maximum(c - 1, 0), c > 0))
        else:
            out.append((c, None))
    return out[::-1]


def segment_pre_reduce(
    key_columns: Sequence[Tuple[jax.Array, Optional[jax.Array], T.Type]],
    aggs: Sequence[Tuple[str, Optional[jax.Array], Optional[jax.Array]]],
    out_dtypes: Sequence,
    num_rows: jax.Array,
    live_mask: Optional[jax.Array],
    doms: Optional[Sequence[int]],
    group_capacity: int,
):
    """Per-batch partial-aggregation pre-reduce for fused scan segments
    (exec/fusion.py): the in-program analogue of the reference pushing
    the partial ``HashAggregationOperator`` step into the generated scan
    loop (HashAggregationOperator.java:48).  Runs INSIDE a traced
    segment program, after the accumulated filter mask, with no
    compaction: ``live_mask`` carries the filter.

    ``doms`` non-None selects the gather-free direct path (bounded key
    domains: dictionary codes / booleans — decided at trace time from
    the segment's output dictionaries); None falls back to the sort
    path at ``group_capacity`` (== the batch capacity, so per-batch
    group counts can never overflow and no host retry loop is needed).

    Returns ``(key_outs, agg_outs, num_groups)``: per key column a
    ``(codes, valid)`` pair in the input dtype/dictionary space, per
    aggregation a ``(values, valid)`` partial-state pair (count states
    are always-valid int64; sum/min/max states are valid iff the group
    saw a non-null input — exactly what the merge primitives of the
    FINAL step expect).
    """
    if doms is not None:
        key_codes = [(v, valid) for v, valid, _t in key_columns]
        present, results = direct_grouped_aggregate(
            key_codes, doms, aggs, num_rows, live_mask=live_mask)
        domain = present.shape[0]
        # the present slots first, ascending, then zeros, in int32: with
        # 64-bit indices the chip's compiler spent 3 s on
        # decode_direct_keys' emulated divisions in every program that
        # pre-reduces (compiled for a described v5e, PR 34)
        slots, num_groups = selected_positions(present, None, domain,
                                               domain)
        decoded = decode_direct_keys(
            slots, [valid is not None for _v, valid, _t in key_columns],
            doms)
        key_outs = []
        for (src, _valid, _t), (codes, valid) in zip(key_columns, decoded):
            key_outs.append((codes.astype(src.dtype), valid))
    else:
        group_index, num_groups, results = grouped_aggregate(
            key_columns, aggs, num_rows, group_capacity,
            live_mask=live_mask)
        key_outs = []
        for v, valid, _t in key_columns:
            key_outs.append((v[group_index],
                             None if valid is None else valid[group_index]))
        slots = None
    agg_outs = []
    for (prim, _values, _valid), dtype, (values, cnt) in zip(
            aggs, out_dtypes, results):
        if slots is not None:
            values = values[slots]
            cnt = cnt[slots]
        if prim == "count":
            agg_outs.append((values.astype(jnp.int64), None))
        else:
            agg_outs.append((values.astype(dtype), cnt > 0))
    return key_outs, agg_outs, num_groups


def merge_pre_reduced(cols, counts, key_types: Sequence[T.Type],
                      doms: Sequence[int], merge_prims: Sequence[str],
                      out_dtypes: Sequence):
    """Merge partials that ``segment_pre_reduce``'s direct path emitted
    for several batches into one partial of the same form, inside one
    program (exec/fusion.py: once a task over the partials a segment
    held on the device, or inside the program that pre-reduces a whole
    kept run).

    ``cols``: the key pairs then the state pairs, each array ``[k,
    domain]``, a partial a row as that path returned it (the live
    groups first); ``counts``: each partial's ``num_groups``, ``[k]``.
    All partials come from one key binding (same ``doms``, same
    nullability), so the key codes mean the same in each.  A partial
    whose count is 0 adds nothing: the callers pad to a bucketed ``k``
    with such.  ``merge_prims`` re-aggregate the states (count states
    sum).

    Returns ``(key_outs, agg_outs, num_groups)`` as segment_pre_reduce
    does: ``domain`` rows, the merged groups compacted to the front.
    """
    domain = cols[0][0].shape[1]
    live = (jnp.arange(domain)[None, :] < counts[:, None]).reshape(-1)
    cols = [(v.reshape(-1), None if valid is None else valid.reshape(-1))
            for v, valid in cols]
    k = len(key_types)
    keys = [(v, valid, t) for (v, valid), t in zip(cols[:k], key_types)]
    aggs = [(prim, v, valid)
            for prim, (v, valid) in zip(merge_prims, cols[k:])]
    rows = live.shape[0]
    return segment_pre_reduce(keys, aggs, out_dtypes, rows, live,
                              list(doms), rows)


def global_pre_reduce(
    aggs: Sequence[Tuple[str, Optional[jax.Array], Optional[jax.Array]]],
    out_dtypes: Sequence,
    num_rows: jax.Array,
    live_mask: Optional[jax.Array],
):
    """Ungrouped counterpart of segment_pre_reduce: one partial-state
    row per batch (AggregationOperator partial step in-program)."""
    results = global_aggregate(aggs, num_rows, live_mask=live_mask)
    agg_outs = []
    for (prim, _values, _valid), dtype, (value, cnt) in zip(
            aggs, out_dtypes, results):
        if prim == "count":
            agg_outs.append((jnp.reshape(value, (1,)).astype(jnp.int64),
                             None))
        else:
            agg_outs.append((jnp.reshape(value, (1,)).astype(dtype),
                             jnp.reshape(cnt > 0, (1,))))
    return agg_outs


def global_aggregate(aggs: Sequence[AggIn], num_rows: jax.Array,
                     live_mask: Optional[jax.Array] = None):
    """Ungrouped aggregation (AggregationOperator analogue): one output row
    always (SQL: aggregates over empty input yield count=0 / sum=NULL)."""
    results = []
    n_live = num_rows
    if live_mask is not None:
        n_live = ((jnp.arange(live_mask.shape[0]) < num_rows)
                  & live_mask).sum()
    for prim, values, valid in aggs:
        if values is not None:
            live = jnp.arange(values.shape[0]) < num_rows
            if live_mask is not None:
                live = live & live_mask
        if values is None:  # count(*)
            results.append((n_live.astype(jnp.int64),
                            n_live.astype(jnp.int64)))
            continue
        if valid is not None:
            live = live & valid
        cnt = live.sum().astype(jnp.int64)
        if prim == "count":
            results.append((cnt, cnt))
            continue
        if prim == "sum":
            out = jnp.where(live, values, jnp.asarray(0, values.dtype)).sum()
        elif prim == "min":
            out = jnp.where(live, values, _min_identity(values.dtype)).min()
        elif prim == "max":
            out = jnp.where(live, values, _max_identity(values.dtype)).max()
        else:
            raise ValueError(prim)
        results.append((out, cnt))
    return results


# ---------------------------------------------------------------------------
# An operator's finish: one cached program
# ---------------------------------------------------------------------------
# The kernels above are pure functions of traced arrays plus static
# metadata (types, prims, capacities).  An accumulating operator
# (exec/aggregation.py, exec/streamagg.py) calls one of these wrappers
# once it has its input: the whole finish, from the staged columns to the
# output columns, is one named program shared across queries (the
# AccumulatorCompiler cache role).  Nothing around the call is a jnp op:
# run eagerly, each would be an XLA program and a launch of its own.
#
# One aggregation input here: ``(prim, values|None, valid|None, tables)``;
# ``values`` None is count(*).  ``tables`` is None, or for min/max over
# a dictionary column the host pair ``(ranks, order)`` of
# ``dictionary_rank_tables``: codes are interning order, not sort order,
# so the program reduces ranks and maps the winner back to a code.

_AGG_PROGRAMS = new_cache("aggregation")

_GROUPED_PROGRAM = {True: "groupby_direct", False: "groupby_sort"}


def dictionary_rank_tables(dictionary):
    """``(ranks, order)``: code -> lexicographic rank and rank -> code,
    each a ``padded_table``."""
    ranks = dictionary.sort_ranks()
    order = np.argsort(ranks).astype(np.int32)
    return padded_table(ranks), padded_table(order)


def _dispatch(key, build, *args):
    """The one call of an aggregation program: ``build()`` on a cache
    miss, then the program over ``args``."""
    with activity("kernel_lookup"):
        fn = cache_get(_AGG_PROGRAMS, key)
    if fn is None:
        fn = build()
        cache_put(_AGG_PROGRAMS, key, fn)
    with activity("dispatch"):
        return fn(*args)


def _agg_signature(aggs, out_dtypes):
    """What of the aggregation inputs a program is compiled for."""
    return tuple(
        (prim, values is not None, valid is not None,
         None if tables is None else len(tables[0]), np.dtype(dtype).str)
        for (prim, values, valid, tables), dtype in zip(aggs, out_dtypes))


def _agg_arguments(aggs):
    return (tuple(a[1] for a in aggs), tuple(a[2] for a in aggs),
            tuple(a[3] for a in aggs))


def _ranked(prims, avals, avalids, tables):
    """The kernels' ``(prim, values, valid)`` triples, dictionary codes
    looked up as ranks."""
    return [(prim, values if table is None else table[0][values], valid)
            for prim, values, valid, table
            in zip(prims, avals, avalids, tables)]


def _coded(agg_outs, tables):
    """Winning ranks back to dictionary codes.  An empty group's rank is
    the reduction's identity: clipped, and invalid anyway."""
    return [(values, valid) if table is None else
            (table[1][jnp.clip(values, 0, table[1].shape[0] - 1)], valid)
            for (values, valid), table in zip(agg_outs, tables)]


def grouped_finish_kernel(key_types, prims, out_dtypes,
                          doms: Optional[Sequence[int]],
                          group_capacity: int):
    """The traced body of ``grouped_finish_jit``: ``segment_pre_reduce``
    over the accumulated rows, the direct tier when ``doms`` is given
    and the sort tier at ``group_capacity`` otherwise, between the two
    rank lookups."""
    def kernel(kvals, kvalids, avals, avalids, tables, n):
        key_outs, agg_outs, num_groups = segment_pre_reduce(
            list(zip(kvals, kvalids, key_types)),
            _ranked(prims, avals, avalids, tables), out_dtypes, n, None,
            doms, group_capacity)
        return key_outs, _coded(agg_outs, tables), num_groups

    return kernel


def grouped_finish_jit(key_columns, aggs, out_dtypes, num_rows,
                       doms: Optional[Sequence[int]], group_capacity: int):
    """A grouped aggregation's finish as one cached jitted program: what
    comes back is every output column, the groups first, and
    ``num_groups`` (``segment_pre_reduce``'s result).  On the sort tier
    ``num_groups`` may exceed ``group_capacity``: the caller runs it
    again at a larger one."""
    key_types = tuple(t for _, _, t in key_columns)
    prims = tuple(a[0] for a in aggs)
    out_dtypes = tuple(out_dtypes)
    doms = None if doms is None else tuple(doms)
    key = ("grouped", key_types,
           tuple(v is not None for _, v, _ in key_columns),
           _agg_signature(aggs, out_dtypes), key_columns[0][0].shape[0],
           doms, group_capacity)

    def build():
        name = _GROUPED_PROGRAM[doms is not None]
        return kernelcache.jit(
            grouped_finish_kernel(key_types, prims, out_dtypes, doms,
                                  group_capacity), name)

    return _dispatch(
        key, build, tuple(v for v, _, _ in key_columns),
        tuple(v for _, v, _ in key_columns), *_agg_arguments(aggs),
        num_rows)


def clustered_aggregate_jit(key_columns, aggs, num_rows,
                            group_capacity: int):
    """clustered_aggregate as one cached jitted program."""
    key_types = tuple(t for _, _, t in key_columns)
    kvalid = tuple(v is not None for _, v, _ in key_columns)
    prims = tuple(p for p, _, _ in aggs)
    avalid = tuple(v is not None for _, _, v in aggs)
    cap = key_columns[0][0].shape[0]
    key = ("clustered", key_types, kvalid, prims, avalid, cap,
           group_capacity)

    def build():
        def kernel(kvals, kvalids, avals, avalids, n):
            kc = [(kvals[i], kvalids[i], key_types[i])
                  for i in range(len(key_types))]
            ag = [(prims[i], avals[i], avalids[i])
                  for i in range(len(prims))]
            return clustered_aggregate(kc, ag, n, group_capacity)

        return kernelcache.jit(kernel, "groupby_clustered")

    return _dispatch(
        key, build, tuple(v for v, _, _ in key_columns),
        tuple(v for _, v, _ in key_columns),
        tuple(v for v, _ in [(a[1], a[2]) for a in aggs]),
        tuple(v for _, v in [(a[1], a[2]) for a in aggs]), num_rows)


def global_finish_jit(aggs, out_dtypes, num_rows):
    """An ungrouped aggregation's finish as one cached jitted program:
    per aggregate the one-row output column ``(values [1], valid [1])``
    of ``global_pre_reduce`` (a count's ``valid`` is None)."""
    prims = tuple(a[0] for a in aggs)
    out_dtypes = tuple(out_dtypes)
    caps = tuple(None if a[1] is None else a[1].shape[0] for a in aggs)
    key = ("global", _agg_signature(aggs, out_dtypes), caps)

    def build():
        def kernel(avals, avalids, tables, n):
            agg_outs = global_pre_reduce(
                _ranked(prims, avals, avalids, tables), out_dtypes, n, None)
            return _coded(agg_outs, tables)

        return kernelcache.jit(kernel, "aggregate_global")

    return _dispatch(key, build, *_agg_arguments(aggs), num_rows)
