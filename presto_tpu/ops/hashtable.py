"""Device-resident open-addressing hash table: the join lookup.

``PagesHash`` (the reference's join lookup table, PagesHash.java:63-121)
walks a power-of-two table with a **1-byte hash-prefix reject**
(PagesHash.java:49: ``positionToHashes`` stores one hash byte per entry,
so a probe compares one byte before paying the full multi-channel key
comparison).  This module is the device analogue: the table is plain jax
arrays living in HBM, and probing is a data-parallel claim loop instead
of a row-at-a-time walk:

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
shape-static, CPU/TPU portable.  It serves join keys the direct-address
index cannot (``ops/join.py``, "Lookup tiers"); a GROUP BY never builds
a table (``ops/groupby.py``).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

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
    place every row (table effectively full — the caller builds a
    larger table or falls back, exec/joinop.py).
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
    # (ok=False; the caller builds a bigger table, which lowers the
    # load and shortens every chain).
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
