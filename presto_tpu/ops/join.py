"""Hash-join kernel family (grouped-build design).

The reference's join is PagesHash — open-addressing table over PagesIndex
with synthetic addresses, probed row-at-a-time
(presto-main/.../operator/PagesHash.java:63-121, JoinProbe.java:74-80,
LookupJoinPageBuilder.java:74).  A probe loop with data-dependent chaining
is the worst possible shape for a TPU, so the design here is different:

  build:  normalize keys -> dense ids -> group the build rows by id
  probe:  (lo, counts) per probe row into that grouping -> prefix-sum
          expansion -> two gathers

Three lookups share the (lo, counts) -> ``expand_matches``/``semi_mask``
contract; the build picks one from the key types and the live key span
(exec/joinop.py HashBuildOperator.finish):

- **dense** (``build_dense_index`` / ``probe_dense``): integer ids whose
  span fits ``DENSE_INDEX_MAX_SLOTS`` address an int32 [size, 2] index
  of (start, count) directly.  A probe is one row gather: no loop, no
  search.  On one v5e at TPC-H Q3's SF1 shapes a 65536-row probe takes
  0.4-1.0 ms against 29 ms through the hash table and 33 ms by binary
  search (PERF.md, PR 30).
- **sorted** (``build_index`` / ``probe_counts``): sort the build ids,
  then per call either an in-call histogram (span fits a scratch sized
  by the batch) or a vectorized binary search.  Serves integer keys too
  sparse for the index.
- **hash** (``ops/hashtable.py pages_hash_build`` / ``pages_hash_probe``):
  the PagesHash table proper over raw normalized key words with the
  1-byte hash-prefix reject of ``PagesHash.java:49``.  It probes by EQUALITY, not order, so arbitrary
  multi-channel key types stream without the canonical union-sort
  materialization; a probe costs the longest hash chain of its batch.

Everything is a sort, a scatter, a cumsum, or a gather — all XLA-native,
all static-shape.  The expansion output is a static capacity with a
``total`` scalar; overflow means the host re-runs at the next bucket
(same policy as groupby).  Duplicate build keys need no PositionLinks
chains: they are adjacent runs in the grouped order.

Multi-channel keys that do not pack are canonicalized into dense int64
ids by sorting the UNION of build and probe keys (exact, collision-free —
no hash needed), after which matching is single-word.  Null join keys
never match (SQL semantics), encoded as distinct negative sentinels per
side.

Join variants mirror LookupJoinOperators.java:45-60: inner, probe-outer
(left), semi, anti; build-side-outer composes from ``matched_build``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.ops.keys import normalize_keys

# Dead-row sentinels as plain Python ints, NOT jnp scalars: a module
# imported lazily inside a jit trace would bake module-level jnp values
# as tracers of that trace, poisoning every later program that closes
# over them (observed: whole-query programs compiled with phantom
# parameters).  Literals promote to the operand dtype at use sites.
_BUILD_DEAD = -2   # build row excluded (null key or padding)
_PROBE_DEAD = -1   # probe row excluded (null key or padding)


def canonical_ids(
    build_keys: Sequence[Tuple[jax.Array, Optional[jax.Array], T.Type]],
    probe_keys: Sequence[Tuple[jax.Array, Optional[jax.Array], T.Type]],
    n_build: jax.Array,
    n_probe: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Map equal key tuples (across both sides) to equal dense ids >= 0.

    Returns (build_ids [cap_b], probe_ids [cap_p]) with dead rows mapped to
    the side's negative sentinel.
    """
    cap_b = build_keys[0][0].shape[0]
    cap_p = probe_keys[0][0].shape[0]
    bw, bnull = normalize_keys(jnp, build_keys, nulls_equal=False)
    pw, pnull = normalize_keys(jnp, probe_keys, nulls_equal=False)
    words = [jnp.concatenate([b, p]) for b, p in zip(bw, pw)]
    n = cap_b + cap_p
    from presto_tpu.ops.radix import radix_argsort_i64, use_radix

    if use_radix():
        perm = radix_argsort_i64(words)
        sorted_words = [w[perm] for w in words]
    elif len(words) == 1:
        combined = words[0]
        perm = jnp.argsort(combined)
        sorted_words = [combined[perm]]
    else:
        perm = jnp.lexsort(tuple(words[::-1]))
        sorted_words = [w[perm] for w in words]
    boundary = jnp.zeros(n, dtype=bool).at[0].set(True)
    for ws in sorted_words:
        boundary = boundary.at[1:].set(boundary[1:] | (ws[1:] != ws[:-1]))
    gid_sorted = jnp.cumsum(boundary) - 1
    ids = jnp.zeros(n, jnp.int64).at[perm].set(gid_sorted)
    build_ids, probe_ids = ids[:cap_b], ids[cap_b:]
    dead_b = jnp.arange(cap_b) >= n_build
    dead_p = jnp.arange(cap_p) >= n_probe
    if bnull is not None:
        dead_b = dead_b | bnull
    if pnull is not None:
        dead_p = dead_p | pnull
    build_ids = jnp.where(dead_b, _BUILD_DEAD, build_ids)
    probe_ids = jnp.where(dead_p, _PROBE_DEAD, probe_ids)
    return build_ids, probe_ids


def single_word_joinable(typ: T.Type, has_dictionary: bool = False) -> bool:
    """May this key channel take the single-word fast path (values ARE
    the ids)?  Integer-word types and dictionary codes qualify."""
    return (has_dictionary or T.is_integral(typ)
            or typ.name in ("date", "timestamp", "boolean")
            or isinstance(typ, T.DecimalType))


def single_word_span_too_big(build_key, n_build) -> jax.Array:
    """Device flag: the live build-key spread would overflow the
    (value - min + 2) id arithmetic (callers must then route to the
    canonical path, or fail over to a tier that can)."""
    values, valid, _ = build_key
    cap = values.shape[0]
    dead = jnp.arange(cap) >= n_build
    if valid is not None:
        dead = dead | ~valid
    u = values.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    umin = jnp.min(jnp.where(dead, jnp.uint64(2**64 - 1), u))
    umax = jnp.max(jnp.where(dead, jnp.uint64(0), u))
    return (~jnp.all(dead)) & ((umax - umin) >= jnp.uint64(1 << 62))


def single_word_ids(
    build_key: Tuple[jax.Array, Optional[jax.Array], T.Type],
    probe_key: Tuple[jax.Array, Optional[jax.Array], T.Type],
    n_build: jax.Array,
    n_probe: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Fast path for one integer-typed key channel: values ARE the ids.

    Requires a type whose normalized word is the value itself (ints, dates,
    decimals, dictionary codes).  Both sides shift by the build side's
    live minimum so ids are non-negative for every matchable value —
    negative keys included — leaving {-2,-1} as dead-row sentinels.
    Probe values below the build minimum cannot match any build row, so
    mapping them to the dead sentinel preserves inner/semi semantics,
    and anti joins read the separate live mask, not the id.
    """
    bvals, bvalid, btyp = build_key
    pvals, pvalid, ptyp = probe_key
    b = bvals.astype(jnp.int64)
    p = pvals.astype(jnp.int64)
    cap_b, cap_p = b.shape[0], p.shape[0]
    dead_b = jnp.arange(cap_b) >= n_build
    dead_p = jnp.arange(cap_p) >= n_probe
    if bvalid is not None:
        dead_b = dead_b | ~bvalid
    if pvalid is not None:
        dead_p = dead_p | ~pvalid
    bmin = jnp.min(jnp.where(dead_b, jnp.int64(2**62), b))
    bmin = jnp.where(jnp.all(dead_b), jnp.int64(0), bmin)
    b = b - bmin + 2
    p = p - bmin + 2
    return (jnp.where(dead_b, _BUILD_DEAD, b),
            jnp.where(dead_p | (p < 0), _PROBE_DEAD, p))


def build_index(build_ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sort the build side: the LookupSource build
    (HashBuilderOperator finish -> PagesHash ctor analogue)."""
    from presto_tpu.ops.radix import radix_argsort_i64, use_radix

    if use_radix():
        perm = radix_argsort_i64([build_ids])
    else:
        perm = jnp.argsort(build_ids)
    return build_ids[perm], perm


def _lower_bound(sorted_arr: jax.Array, queries: jax.Array,
                 inclusive: bool) -> jax.Array:
    """Vectorized binary search as a static loop of flat gathers —
    measured ~2.5x faster than XLA's searchsorted lowering on v5e
    (random gather is ~7 ms/M rows; searchsorted's per-step cost was
    ~17 ms/M).  ``inclusive=False`` -> first i with arr[i] >= q (left);
    ``inclusive=True`` -> first i with arr[i] > q (right)."""
    n = sorted_arr.shape[0]
    lo = jnp.zeros(queries.shape[0], jnp.int32)
    hi = jnp.full(queries.shape[0], n, jnp.int32)
    for _ in range(n.bit_length()):
        mid = (lo + hi) >> 1
        v = sorted_arr[jnp.minimum(mid, n - 1)]
        go_right = (v <= queries) if inclusive else (v < queries)
        # Once lo==hi the interval is empty: without this guard the
        # clamped gather rereads arr[n-1] and pushes lo past n for
        # queries equal to the build max (one duplicate row per probe).
        go_right = go_right & (lo < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


def _dense_scratch(cap_b: int, cap_p: int) -> int:
    """Static histogram size for the dense-domain probe path: large
    enough for generated-key ranges at small/medium scale, capped so the
    scratch stays tens of MB."""
    want = 4 * (cap_b + cap_p)
    size = 1 << 14
    while size < want and size < (1 << 24):
        size <<= 1
    return size


def probe_counts(sorted_build: jax.Array, perm_b: jax.Array,
                 probe_ids: jax.Array):
    """Per-probe-row match range in the sorted build order.

    Two runtime-selected strategies (one compiled program, lax.cond):
    when the live build-key span fits a static histogram, match ranges
    come from two gathers into (hist, starts) arrays — the BigintGroupByHash
    dense-path idea applied to the probe (GroupByHash.java:30-43 role);
    otherwise vectorized binary search over the sorted build."""
    cap_b = sorted_build.shape[0]
    live_b = sorted_build >= 0
    n_dead = (cap_b - live_b.sum()).astype(jnp.int32)
    live_p = probe_ids >= 0
    S = _dense_scratch(cap_b, probe_ids.shape[0])

    bmin = jnp.min(jnp.where(live_b, sorted_build, jnp.int64(2**62)))
    bmax = jnp.max(jnp.where(live_b, sorted_build, jnp.int64(-1)))
    any_b = live_b.any()
    fits = any_b & ((bmax - bmin) < (S - 1))

    def dense(_):
        off = jnp.where(live_b, sorted_build - bmin, jnp.int64(S))
        hist = (jnp.zeros(S, jnp.int32)
                .at[off.astype(jnp.int32)].add(1, mode="drop"))
        starts_d = (jnp.cumsum(hist) - hist).astype(jnp.int32)
        q = probe_ids - bmin
        in_rng = live_p & (q >= 0) & (q < S)
        qi = jnp.clip(q, 0, S - 1).astype(jnp.int32)
        cnt = jnp.where(in_rng, hist[qi], 0)
        lo_ = jnp.where(in_rng, n_dead + starts_d[qi], 0)
        return lo_.astype(jnp.int64), cnt.astype(jnp.int64)

    def search(_):
        lo_ = _lower_bound(sorted_build, probe_ids, inclusive=False)
        hi_ = _lower_bound(sorted_build, probe_ids, inclusive=True)
        cnt = jnp.where(live_p, hi_ - lo_, 0)
        return lo_.astype(jnp.int64), cnt.astype(jnp.int64)

    return jax.lax.cond(fits, dense, search, 0)


#: Largest direct-address index a build publishes, in slots: int32
#: [slots, 2] is 128 MB at this many, under 1% of a v5e's HBM.  Integer
#: keys whose live span (in id space) fits take the index; wider spans
#: keep the sorted / hash tiers.
DENSE_INDEX_MAX_SLOTS = 1 << 24


def dense_index_size(id_span: int) -> Optional[int]:
    """Static slot count for a direct-address index over ids in
    ``[0, id_span)``: the power-of-two bucket of the span (so repeated
    builds of one table share a program), or None over the bound."""
    if id_span > DENSE_INDEX_MAX_SLOTS:
        return None
    return max(1 << max(id_span - 1, 0).bit_length(), 1 << 10)


def build_dense_index(build_ids: jax.Array, size: int):
    """Direct-address lookup index over build ids in ``[0, size)`` (dead
    rows carry a negative sentinel): ``(index, perm)``, both int32.
    ``index[id]`` is ``(start, count)``, the run of that id in ``perm``,
    the build rows grouped by id with dead rows last — the (lo, counts)
    -> ``expand_matches`` contract with no sorted id array and no
    search: a scatter-add, a cumsum over the slots and one int32 sort of
    the build rows.  The two columns are stacked because one row gather
    from [size, 2] measured 0.38 ms for 65536 probes into 8 M slots on
    v5e against 0.98 ms for two gathers from [size] (PERF.md, PR 30)."""
    off = jnp.where(build_ids >= 0, build_ids, size).astype(jnp.int32)
    counts = jnp.zeros(size, jnp.int32).at[off].add(1, mode="drop")
    starts = jnp.cumsum(counts, dtype=jnp.int32) - counts
    perm = jnp.argsort(off, stable=True).astype(jnp.int32)
    return jnp.stack([starts, counts], axis=1), perm


def probe_dense(index: jax.Array, probe_ids: jax.Array):
    """Per-probe-row match range through a ``build_dense_index``: one
    row gather, no loop, no search.  Ids outside ``[0, size)`` (dead
    rows, keys past the build's maximum) match nothing."""
    size = index.shape[0]
    in_rng = (probe_ids >= 0) & (probe_ids < size)
    q = jnp.clip(probe_ids, 0, size - 1).astype(jnp.int32)
    hit = jnp.where(in_rng[:, None], index[q], 0).astype(jnp.int64)
    return hit[:, 0], hit[:, 1]


def _expand_probe_idx(emit: jax.Array, out_capacity: int):
    """Map each output slot to its source probe row, scatter-free of
    search: mark each emitting row's start slot with +1, cumsum over the
    output space, and translate emit-rank back to row via a compacted
    index.  Replaces an out_capacity-query searchsorted that measured
    2.7 s/4M slots on v5e with ~2 scatters + a cumsum (~50 ms)."""
    n = emit.shape[0]
    inclusive = jnp.cumsum(emit)
    total = inclusive[-1]
    starts = (inclusive - emit).astype(jnp.int64)
    emitting = emit > 0
    erank = (jnp.cumsum(emitting.astype(jnp.int32)) - 1).astype(jnp.int32)
    # emit-rank -> probe row (rank r is the r-th emitting row)
    # Dropped (non-emitting) writes go to distinct OOB slots n+i so the
    # index vector is genuinely unique — a shared OOB index would break
    # the unique_indices contract even though mode="drop" discards it.
    rows = (jnp.zeros(n, jnp.int32)
            .at[jnp.where(emitting, erank, n + jnp.arange(n, dtype=jnp.int32))]
            .set(jnp.arange(n, dtype=jnp.int32), mode="drop",
                 unique_indices=True))
    # +1 at each emitting row's first output slot (disjoint ranges ->
    # distinct starts among emitting rows); slots past out_capacity drop
    start_slots = jnp.where(emitting & (starts < out_capacity), starts,
                            jnp.int64(out_capacity))
    flag = (jnp.zeros(out_capacity, jnp.int32)
            .at[start_slots.astype(jnp.int32)].add(1, mode="drop"))
    dense_rank = jnp.cumsum(flag) - 1
    probe_idx = rows[jnp.clip(dense_rank, 0, n - 1)]
    return probe_idx.astype(jnp.int64), starts, total


def expand_matches(lo: jax.Array, counts: jax.Array, perm_b: jax.Array,
                   out_capacity: int):
    """Prefix-sum expansion: emit (probe_row, build_row) pairs (inner join;
    left-outer variant below).

    Returns (probe_idx [out_cap], build_idx [out_cap], row_valid [out_cap],
    unmatched [out_cap], total).  ``total`` may exceed out_capacity (host
    re-runs bigger).
    """
    probe_idx, starts, total = _expand_probe_idx(counts, out_capacity)
    j = jnp.arange(out_capacity)
    k = j - starts[probe_idx]
    build_sorted_pos = jnp.minimum(lo[probe_idx] + k, perm_b.shape[0] - 1)
    build_idx = perm_b[build_sorted_pos]
    row_valid = j < total
    unmatched = jnp.zeros(out_capacity, bool)
    return probe_idx, build_idx, row_valid, unmatched, total


def expand_matches_outer(lo: jax.Array, counts: jax.Array, live_probe: jax.Array,
                         perm_b: jax.Array, out_capacity: int):
    """Left-outer expansion: every live probe row emits max(count, 1) rows."""
    emit = jnp.where(live_probe, jnp.maximum(counts, 1), 0)
    probe_idx, starts, total = _expand_probe_idx(emit, out_capacity)
    j = jnp.arange(out_capacity)
    k = j - starts[probe_idx]
    unmatched = counts[probe_idx] == 0
    build_sorted_pos = jnp.minimum(lo[probe_idx] + k, perm_b.shape[0] - 1)
    build_idx = jnp.where(unmatched, 0, perm_b[build_sorted_pos])
    row_valid = j < total
    return probe_idx, build_idx, row_valid, unmatched, total


def semi_mask(counts: jax.Array, live_probe: jax.Array, anti: bool):
    """Semi/anti join: boolean mask over probe rows
    (HashSemiJoinOperator / anti-join analogue)."""
    if anti:
        return live_probe & (counts == 0)
    return live_probe & (counts > 0)


def anti_keep_mask(counts: jax.Array, live_ids: jax.Array,
                   key_nonnull: jax.Array, in_row: jax.Array,
                   null_aware: bool, n_build_rows=None, build_has_null=None):
    """Which probe rows survive an anti join.

    NOT EXISTS (``null_aware=False``): keep every unmatched in-range row,
    null keys included (they never match anything).

    NOT IN (``null_aware=True``) follows SQL three-valued logic
    (SemiJoinNode's nullable-output contract in the reference,
    HashSemiJoinOperator.java:47): an empty filtering side keeps every
    row; otherwise a NULL probe key or any NULL among the filtering keys
    makes the predicate UNKNOWN -> row excluded; matched rows are FALSE
    -> excluded; only non-null unmatched rows against a null-free side
    survive.  ``live_ids`` = id >= 0 (non-null AND within build range);
    ``key_nonnull`` = the key columns are actually non-null (an id can be
    dead merely for being below the build minimum).
    """
    if not null_aware:
        return in_row & ((live_ids & (counts == 0)) | ~live_ids)
    empty = n_build_rows == 0
    survive = in_row & key_nonnull & (counts == 0) & ~build_has_null
    return jnp.where(empty, in_row, survive)


def anti_keep_from_parts(counts, live_ids, in_row, null_aware: bool,
                         probe_key_valids, n_build_rows,
                         build_has_null=None, build_key_valids=(),
                         build_in_row=None):
    """anti_keep_mask with the key-nonnull / build-has-null inputs derived
    from raw validity masks — the one place the NOT IN plumbing lives
    (every execution tier calls this instead of re-rolling it).

    ``probe_key_valids``: per-probe-key-channel valid masks (None entries
    = non-nullable).  Build-side null presence comes either precomputed
    (``build_has_null``, a device scalar from the build kernel) or from
    ``build_key_valids`` + ``build_in_row``.
    """
    cap = counts.shape[0]
    key_nonnull = jnp.ones(cap, bool)
    for v in probe_key_valids:
        if v is not None:
            key_nonnull = key_nonnull & v
    if build_has_null is None:
        build_has_null = jnp.zeros((), bool)
        for bv in build_key_valids:
            if bv is not None:
                bad = ~bv if build_in_row is None else (build_in_row & ~bv)
                build_has_null = build_has_null | bad.any()
    return anti_keep_mask(counts, live_ids, key_nonnull, in_row,
                          null_aware, n_build_rows, build_has_null)


def matched_build_mask(lo: jax.Array, counts: jax.Array, cap_b: int,
                       perm_b: jax.Array) -> jax.Array:
    """Which build rows matched >= 1 probe row (for right/full outer).

    Range-mark trick: +1 at lo, -1 at lo+count per probing row, cumsum > 0
    over the sorted build domain, then permute back.
    """
    has = (counts > 0).astype(jnp.int32)
    delta = jnp.zeros(cap_b + 1, jnp.int32)
    delta = delta.at[lo].add(has)
    delta = delta.at[lo + counts].add(-has)
    matched_sorted = jnp.cumsum(delta[:-1]) > 0
    return jnp.zeros(cap_b, bool).at[perm_b].set(matched_sorted)
