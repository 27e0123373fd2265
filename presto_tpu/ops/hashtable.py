"""Device-resident open-addressing hash tables.

The reference's two hottest hash structures are
``MultiChannelGroupByHash`` (open-addressing linear probing with rehash,
presto-main/.../operator/MultiChannelGroupByHash.java:273-286) and
``PagesHash`` (the join lookup table, PagesHash.java:63-121) — both walk
a power-of-two table with a **1-byte hash-prefix reject**
(PagesHash.java:49: ``positionToHashes`` stores one hash byte per entry,
so a probe compares one byte before paying the full multi-channel key
comparison).  This module is the device analogue: tables are plain jax
arrays living in HBM **across batches**, and probing is a data-parallel
claim loop instead of a row-at-a-time walk:

- every unresolved row gathers its candidate slot's (used, prefix) and
  rejects occupied-but-different-prefix slots on the one-byte compare
  (the full key-word compare runs only where the prefix agrees);
- rows that see an empty slot CLAIM it by scatter-min of their row id;
  exactly one claimant per slot wins and installs its key, so every
  round resolves at least one row per contended slot;
- losers re-examine the same slot next round (the winner may share
  their key); rows that saw a different occupied key advance one slot
  (linear probing).

Everything is gathers, scatters, and a ``lax.while_loop`` — jit-able,
shape-static, CPU/TPU portable.  The sort-based kernels in
``ops/groupby.py`` / ``ops/join.py`` remain the fallback tier: the hash
tier's contract is that state persists on device across batches (the
GroupByHash accumulate never re-sorts seen rows) and that probe cost is
O(chain length), not O(log build).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.ops.keys import normalize_keys

# distinct seed from ops/hashing.py's partitioning hash: a key must not
# land in the same table slot pattern as its exchange partition
_SEED = 0x2545F4914F6CDD1D


def _mix64(x):
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(33))) * jnp.uint64(0xFF51AFD7ED558CCD)
    x = (x ^ (x >> jnp.uint64(33))) * jnp.uint64(0xC4CEB9FE1A85EC53)
    return x ^ (x >> jnp.uint64(33))


def hash_words(words: Sequence[jax.Array]) -> jax.Array:
    """uint64 hash per row over normalized int64 key words."""
    acc = jnp.full(words[0].shape[0], _SEED, jnp.uint64)
    for w in words:
        acc = _mix64(acc ^ w.astype(jnp.uint64))
    return acc


def slot_and_prefix(h: jax.Array, cap: int):
    """(initial slot int32, 1-byte prefix) from the row hash.  The slot
    comes from the LOW bits and the prefix from the HIGH byte, so the
    reject byte stays independent of the slot index (PagesHash.java:49).
    """
    slot = (h & jnp.uint64(cap - 1)).astype(jnp.int32)
    prefix = (h >> jnp.uint64(56)).astype(jnp.uint8)
    return slot, prefix


def probe_insert(key_words: Sequence[jax.Array], live: jax.Array,
                 t_words: Tuple[jax.Array, ...], t_prefix: jax.Array,
                 t_used: jax.Array):
    """Insert-or-find every live row's key tuple.

    Returns ``(slot [N] int32, t_words', t_prefix', t_used', ok)``:
    dead rows get slot == cap (a drop sentinel for downstream
    scatters); ``ok`` is False when the bounded probe loop could not
    place every row (table effectively full — the caller must rehash
    or fall back; nothing was accumulated by then, so the update is
    safe to retry).
    """
    cap = t_used.shape[0]
    n = key_words[0].shape[0]
    h = hash_words(key_words)
    slot0, prefix = slot_and_prefix(h, cap)
    rowid = jnp.arange(n, dtype=jnp.int32)
    # Aggressive round bound: every unresolved row makes progress each
    # round (resolves, or advances past a different occupied key), so a
    # row needs at most its probe-chain length in rounds — O(log n)
    # with the 64-bit mix at <= 1/2 load.  A FULL table would otherwise
    # spin for cap rounds of O(n) work before reporting failure;
    # tripping the bound on a legitimately long chain is harmless
    # (ok=False, nothing accumulated, the caller rehashes bigger —
    # which halves the load and shortens every chain — and retries).
    max_rounds = min(cap, 256)

    def cond(s):
        _slot, unresolved, _tw, _tp, _tu, _out, it = s
        return unresolved.any() & (it < max_rounds)

    def body(s):
        slot, unresolved, tw, tp, tu, out, it = s
        used_g = tu[slot]
        # 1-byte prefix reject: the full key-word compare below is only
        # meaningful where the stored hash byte agrees
        same_pref = used_g & (tp[slot] == prefix)
        eq = same_pref
        for w, twi in zip(key_words, tw):
            eq = eq & (twi[slot] == w)
        match = unresolved & eq
        empty = unresolved & ~used_g
        claim = (jnp.full(cap, n, jnp.int32)
                 .at[jnp.where(empty, slot, cap)]
                 .min(rowid, mode="drop"))
        winner = empty & (claim[slot] == rowid)
        wslot = jnp.where(winner, slot, cap)
        tu = tu.at[wslot].set(True, mode="drop")
        tp = tp.at[wslot].set(prefix, mode="drop")
        tw = tuple(twi.at[wslot].set(w, mode="drop")
                   for twi, w in zip(tw, key_words))
        resolved = match | winner
        out = jnp.where(resolved, slot, out)
        unresolved = unresolved & ~resolved
        # rows that saw a DIFFERENT occupied key advance (linear
        # probing); claim losers stay — their slot now holds the
        # winner's key, which may equal theirs
        advance = unresolved & used_g & ~eq
        slot = jnp.where(advance, (slot + 1) & (cap - 1), slot)
        return slot, unresolved, tw, tp, tu, out, it + 1

    init = (slot0, live, tuple(t_words), t_prefix, t_used,
            jnp.full(n, cap, jnp.int32), jnp.int32(0))
    slot, unresolved, tw, tp, tu, out, _ = jax.lax.while_loop(
        cond, body, init)
    return out, tw, tp, tu, ~unresolved.any()


def probe_find(key_words: Sequence[jax.Array], live: jax.Array,
               t_words: Tuple[jax.Array, ...], t_prefix: jax.Array,
               t_used: jax.Array):
    """Read-only probe: ``(slot [N] int32, found [N] bool)``.  A row is
    resolved when it matches an entry (found) or hits an empty slot
    (not found).  Dead rows resolve immediately as not-found."""
    cap = t_used.shape[0]
    n = key_words[0].shape[0]
    h = hash_words(key_words)
    slot0, prefix = slot_and_prefix(h, cap)
    max_rounds = cap + 1

    def cond(s):
        _slot, unresolved, _found, it = s
        return unresolved.any() & (it < max_rounds)

    def body(s):
        slot, unresolved, found, it = s
        used_g = t_used[slot]
        same_pref = used_g & (t_prefix[slot] == prefix)
        eq = same_pref
        for w, twi in zip(key_words, t_words):
            eq = eq & (twi[slot] == w)
        match = unresolved & eq
        empty = unresolved & ~used_g
        found = found | match
        unresolved = unresolved & ~(match | empty)
        slot = jnp.where(unresolved, (slot + 1) & (cap - 1), slot)
        return slot, unresolved, found, it + 1

    slot, _, found, _ = jax.lax.while_loop(
        cond, body, (slot0, live, jnp.zeros(n, bool), jnp.int32(0)))
    return slot, found


# ---------------------------------------------------------------------------
# GroupByHash: device-resident grouped-aggregation state
# ---------------------------------------------------------------------------
# State layout (all arrays [cap], the table capacity, a power of two):
#   words:   one int64 array per normalized key word (compare side)
#   prefix:  uint8 hash byte per entry (the PagesHash:49 reject byte)
#   used:    occupancy
#   keyvals: per key COLUMN, (values, valid|None) in the input dtype —
#            the representative values extract() emits (the sort path
#            gathers these from the input; resident state must carry
#            them because input batches are not retained)
#   aggs:    per aggregation, (acc, nonnull_count) with the same
#            accumulation dtypes the sort path uses
#
# The exec tier (exec/aggregation.py) owns jitting + the rehash ladder:
# these functions are pure array->array kernels.

def _min_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(True, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _max_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(False, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def groupby_init(cap: int, n_words: int,
                 key_dtypes: Sequence, key_nullable: Sequence[bool],
                 agg_specs: Sequence[Tuple[str, Optional[object]]]):
    """Fresh empty state.  ``agg_specs`` is (prim, value_dtype|None) per
    aggregation (None == count(*))."""
    words = tuple(jnp.zeros(cap, jnp.int64) for _ in range(n_words))
    prefix = jnp.zeros(cap, jnp.uint8)
    used = jnp.zeros(cap, bool)
    keyvals = []
    for dt, nullable in zip(key_dtypes, key_nullable):
        vals = jnp.zeros(cap, dt)
        keyvals.append((vals, jnp.zeros(cap, bool) if nullable else None))
    aggs = []
    for prim, dt in agg_specs:
        if prim == "count" or dt is None:
            aggs.append((jnp.zeros(cap, jnp.int64),
                         jnp.zeros(cap, jnp.int64)))
        elif prim == "sum":
            aggs.append((jnp.zeros(cap, dt), jnp.zeros(cap, jnp.int64)))
        elif prim == "min":
            aggs.append((jnp.full(cap, _min_ident(dt)),
                         jnp.zeros(cap, jnp.int64)))
        elif prim == "max":
            aggs.append((jnp.full(cap, _max_ident(dt)),
                         jnp.zeros(cap, jnp.int64)))
        else:
            raise ValueError(f"unknown aggregation primitive {prim}")
    return words, prefix, used, tuple(keyvals), tuple(aggs)


def groupby_update(state, key_columns, agg_ins, num_rows,
                   live_mask=None, prims: Sequence[str] = ()):
    """One batch's accumulate into resident state.

    ``key_columns``: [(values, valid|None, type)] like grouped_aggregate;
    ``agg_ins``: [(prim, values|None, valid|None)].  Returns
    ``(state', n_groups, ok)``.  When ``ok`` is False the table was too
    full to place this batch's keys; NOTHING was accumulated (the
    accumulate scatters are gated on ok), so the caller may rehash —
    carrying installed-but-empty keys is harmless, they re-match — and
    retry the same batch exactly once-effective.
    """
    words, prefix, used, keyvals, aggs = state
    cap_rows = key_columns[0][0].shape[0]
    live = jnp.arange(cap_rows) < num_rows
    if live_mask is not None:
        live = live & live_mask
    # key words against the STATE's nullability spec, not this batch's:
    # a batch whose column happens to arrive all-valid (valid=None) must
    # still produce the null-flag word the resident table was keyed with
    from presto_tpu.ops.keys import to_sortable_i64

    kw = []
    for (values, valid, typ), (_kv, kvalid) in zip(key_columns, keyvals):
        w = to_sortable_i64(jnp, values, typ)
        if kvalid is not None:
            vm = valid if valid is not None else jnp.ones(cap_rows, bool)
            kw.append(jnp.where(vm, w, jnp.int64(0)))
            kw.append((~vm).astype(jnp.int64))
        else:
            kw.append(w)
    slot, words, prefix, used, ok = probe_insert(kw, live, words, prefix,
                                                 used)
    cap = used.shape[0]
    # gate every accumulate on ok so a failed placement round leaves
    # state numerically untouched (retry-safe after rehash)
    sslot = jnp.where(ok, jnp.where(live, slot, cap), cap)
    new_keyvals = []
    for (values, valid, _t), (kv, kvalid) in zip(key_columns, keyvals):
        kv = kv.at[sslot].set(values.astype(kv.dtype), mode="drop")
        if kvalid is not None:
            src_valid = (valid if valid is not None
                         else jnp.ones(cap_rows, bool))
            kvalid = kvalid.at[sslot].set(src_valid, mode="drop")
        new_keyvals.append((kv, kvalid))
    new_aggs = []
    for (prim, values, valid), (acc, nn) in zip(agg_ins, aggs):
        lv = live if valid is None else (live & valid)
        aslot = jnp.where(ok & lv, slot, cap)
        nn = nn.at[aslot].add(1, mode="drop")
        if prim == "count" or values is None:
            acc = acc.at[aslot].add(1, mode="drop")
        elif prim == "sum":
            acc = acc.at[aslot].add(values.astype(acc.dtype), mode="drop")
        elif prim == "min":
            acc = acc.at[aslot].min(values.astype(acc.dtype), mode="drop")
        elif prim == "max":
            acc = acc.at[aslot].max(values.astype(acc.dtype), mode="drop")
        else:
            raise ValueError(f"unknown aggregation primitive {prim}")
        new_aggs.append((acc, nn))
    n_groups = used.sum()
    return ((words, prefix, used, tuple(new_keyvals), tuple(new_aggs)),
            n_groups, ok)


def groupby_rehash(state, new_cap: int, prims: Sequence[str] = ()):
    """Re-insert every occupied entry into a ``new_cap`` table, carrying
    key values and accumulated aggregation state by scatter (the
    MultiChannelGroupByHash ``rehash()`` role).  Entries are all
    distinct, so the claim loop converges fast; returns (state', ok).

    ``prims`` must name each aggregation's primitive: slots NOT carried
    must be re-initialized to the prim's identity (min -> +inf, max ->
    -inf), or a group first installed after the rehash would fold the
    stale zero into its running min/max."""
    words, prefix, used, keyvals, aggs = state
    old_cap = used.shape[0]
    n_words = len(words)
    key_dtypes = [kv.dtype for kv, _ in keyvals]
    key_nullable = [kvalid is not None for _, kvalid in keyvals]
    if not prims:
        prims = ["sum"] * len(aggs)
    agg_specs = []
    for prim, (acc, _nn) in zip(prims, aggs):
        agg_specs.append((prim, acc.dtype))
    nwords, nprefix, nused, nkeyvals, naggs = groupby_init(
        new_cap, n_words, key_dtypes, key_nullable, agg_specs)
    slot, nwords, nprefix, nused, ok = probe_insert(
        words, used, nwords, nprefix, nused)
    sslot = jnp.where(used, slot, new_cap)
    out_keyvals = []
    for (kv, kvalid), (nkv, nkvalid) in zip(keyvals, nkeyvals):
        nkv = nkv.at[sslot].set(kv, mode="drop")
        if nkvalid is not None:
            nkvalid = nkvalid.at[sslot].set(
                kvalid if kvalid is not None
                else jnp.ones(old_cap, bool), mode="drop")
        out_keyvals.append((nkv, nkvalid))
    out_aggs = []
    for (acc, nn), (nacc, nnn) in zip(aggs, naggs):
        nacc = nacc.at[sslot].set(acc.astype(nacc.dtype), mode="drop")
        nnn = nnn.at[sslot].set(nn, mode="drop")
        out_aggs.append((nacc, nnn))
    return (nwords, nprefix, nused,
            tuple(out_keyvals), tuple(out_aggs)), ok


def groupby_extract(state):
    """Compact occupied slots into the leading positions.

    Returns ``(n_groups, key_outs, agg_outs)`` over arrays of the TABLE
    capacity: entries past n_groups are garbage.  ``key_outs`` are
    (values, valid|None) pairs; ``agg_outs`` are (acc, nonnull_count)
    pairs — the same (values, cnt) contract grouped_aggregate returns,
    so callers share the output-building code with the sort path."""
    words, prefix, used, keyvals, aggs = state
    cap = used.shape[0]
    idx = jnp.nonzero(used, size=cap, fill_value=cap - 1)[0]
    n = used.sum()
    key_outs = []
    for kv, kvalid in keyvals:
        key_outs.append((kv[idx],
                         None if kvalid is None else kvalid[idx]))
    agg_outs = []
    for acc, nn in aggs:
        agg_outs.append((acc[idx], nn[idx]))
    return n, key_outs, agg_outs


# ---------------------------------------------------------------------------
# PagesHash: join build/probe over the same table layout
# ---------------------------------------------------------------------------

def pages_hash_build(key_columns, num_rows, cap: int):
    """Build the lookup table over the build side's raw key words.

    Unlike the sorted-index build (ops/join.py build_index), the table
    is keyed on EQUALITY of normalized words, not order — so it serves
    arbitrary multi-channel key types without the canonical union-sort
    (the reason PagesHash never needs a total order).  Duplicate keys
    need no PositionLinks chains: build rows are grouped per distinct
    key by a stable int32 sort of their slot ids, and each table slot
    carries its group's (start, count) range into that order.

    Returns ``(t_words, t_prefix, t_used, starts, counts, perm,
    has_null, ok)`` — ``starts[slot]``/``counts[slot]`` index ``perm``
    exactly like the sorted path's (lo, counts) index its build
    permutation, so the expansion kernels are shared.
    """
    cap_b = key_columns[0][0].shape[0]
    in_row = jnp.arange(cap_b) < num_rows
    kw, null_row = normalize_keys(jnp, key_columns, nulls_equal=False)
    live = in_row if null_row is None else (in_row & ~null_row)
    has_null = (jnp.zeros((), bool) if null_row is None
                else (in_row & null_row).any())
    words = tuple(jnp.zeros(cap, jnp.int64) for _ in kw)
    prefix = jnp.zeros(cap, jnp.uint8)
    used = jnp.zeros(cap, bool)
    slot, words, prefix, used, ok = probe_insert(kw, live, words, prefix,
                                                 used)
    sslot = jnp.where(live, slot, cap)
    counts = jnp.zeros(cap, jnp.int32).at[sslot].add(1, mode="drop")
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    # group build rows by slot (dead rows sort last); int32 sort keys
    perm = jnp.argsort(jnp.where(live, slot, cap).astype(jnp.int32),
                       stable=True).astype(jnp.int32)
    return words, prefix, used, starts, counts, perm, has_null, ok


def pages_hash_probe(table, probe_key_columns, num_rows):
    """(lo, counts, live) per probe row against a pages_hash_build table.

    ``lo``/``counts`` satisfy the expand_matches/semi_mask contract of
    ops/join.py (positions into the build perm); ``live`` marks probe
    rows that were eligible to match (non-null keys, in-row).
    """
    t_words, t_prefix, t_used, starts, counts_t = table
    cap_p = probe_key_columns[0][0].shape[0]
    in_row = jnp.arange(cap_p) < num_rows
    kw, null_row = normalize_keys(jnp, probe_key_columns,
                                  nulls_equal=False)
    live = in_row if null_row is None else (in_row & ~null_row)
    slot, found = probe_find(kw, live, t_words, t_prefix, t_used)
    hit = live & found
    lo = jnp.where(hit, starts[slot], 0).astype(jnp.int64)
    cnt = jnp.where(hit, counts_t[slot], 0).astype(jnp.int64)
    return lo, cnt, live
