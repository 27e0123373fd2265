"""Device radix sort: O(1)-in-length compile, range-adaptive runtime.

Why not XLA's sort: on TPU the sort lowering's COMPILE time scales with the
input length (how steeply is not measured on the current code), so every
new shape of a generic join/group-by/order-by program would pay for it
again.  The reference instead pays a one-time bytecode
specialization per type combination (OrderingCompiler,
presto-main/.../sql/gen/OrderingCompiler.java:62).  This module is that
idea rebuilt for XLA: a least-significant-digit radix sort made of
primitives whose compile cost is independent of N (cumsum, compare,
scatter), specialized per (shape, word-count) by the jit cache.

Design (shaped by what the chip does badly: memory-random gathers and
scatters, against one-hot cumsums and compares that vectorize; the costs
are not measured on the current code):

- Keys are normalized order-preserving int64 words (ops/keys.py), split
  into two uint32 halves after an in-program per-word min-subtraction.
  Subtracting the runtime minimum both removes the sign problem and
  shrinks the value range to the data's actual spread.
- Each digit pass is a stable counting sort.  The one-hot digit matrix
  [N, R] -> inclusive cumsum along N yields every row's same-digit rank
  AND the bucket histogram (its last row); rank and bucket offset are
  read back with one-hot weighted row-sums, NOT gathers.  The pass
  carries (order, current word) and applies the permutation with two
  int32 scatters — the only memory-random ops in the loop.
- Passes whose digits are provably all zero — ``(range >> shift) == 0``
  — are skipped at RUNTIME via ``lax.cond``: one compiled program serves
  every key range, paying only for the bits the data actually uses.
  Sorting 8-bit dictionary codes through the "64-bit" program costs two
  real passes, not sixteen.
- LSD passes are stable, so multi-key lexicographic order falls out of
  running passes minor-key-first, and ties preserve input order (the
  stable-sort contract sort_permutation promises).  The relative order
  of PADDING rows is unspecified (they all land at the end).

The pad flag (rows beyond num_rows sort last) and null-ordering words are
single 1-bit passes appended most-significant.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.ops.keys import to_sortable_i64

_RADIX_BITS = 4


def use_radix() -> bool:
    """Trace-time backend dispatch: radix on TPU (where XLA sort compile
    scales with length), XLA sort elsewhere (CPU lexsort compiles fast
    and runs faster than emulated radix passes).  Tests reach the radix
    passes on the CPU by calling ``radix_argsort_i64`` itself."""
    return jax.default_backend() == "tpu"


def stable_partition_perm(flag: jax.Array) -> jax.Array:
    """Permutation moving flag=False rows (stably) before flag=True rows —
    the 1-bit sort, e.g. compact-live-rows-first."""
    n = flag.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    if n <= 1:
        return order
    return _bit_pass(order, flag)


def _pass_dest(digits: jax.Array, R: int) -> jax.Array:
    """Stable counting-sort destinations for one digit pass."""
    iota = jnp.arange(R, dtype=jnp.int32)
    oh = (digits[:, None] == iota[None, :]).astype(jnp.int32)   # [N, R]
    C = jnp.cumsum(oh, axis=0)                                  # [N, R]
    hist = C[-1]                                                # [R]
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(hist)[:-1].astype(jnp.int32)])
    # rank within bucket (inclusive) and bucket offset, via one-hot
    # weighted sums — elementwise + row reduce, no gathers
    rank = jnp.sum(C * oh, axis=1)
    off = jnp.sum(offsets[None, :] * oh, axis=1)
    return off + rank - 1                                       # permutation


def _stable_pass(order: jax.Array, word: jax.Array, digits: jax.Array,
                 R: int):
    """One stable counting-sort pass: permute (order, word) so rows are
    grouped by ``digits`` (values in [0, R)), ties in current order."""
    dest = _pass_dest(digits, R)
    new_order = (jnp.zeros_like(order)
                 .at[dest].set(order, unique_indices=True, mode="drop"))
    new_word = (jnp.zeros_like(word)
                .at[dest].set(word, unique_indices=True, mode="drop"))
    return new_order, new_word


def _word_passes(order: jax.Array, word_u32: jax.Array, rng_u32: jax.Array,
                 max_bits: int,
                 bits_per_pass: int = _RADIX_BITS) -> jax.Array:
    """All digit passes for one uint32 word, gathered into current order
    once up front (values already min-subtracted; ``rng_u32`` is the
    runtime max).  Passes above the live range are skipped via cond —
    compiled once, executed only when needed."""
    R = 1 << bits_per_pass
    w = word_u32[order]  # the one gather per word
    carry = (order, w)
    for shift in range(0, min(max_bits, 32), bits_per_pass):
        def run(c, s=shift):
            o, wc = c
            d = ((wc >> jnp.uint32(s)) & jnp.uint32(R - 1)).astype(jnp.int32)
            return _stable_pass(o, wc, d, R)

        needed = (rng_u32 >> jnp.uint32(shift)) > 0
        carry = jax.lax.cond(needed, run, lambda c: c, carry)
    return carry[0]


def _bit_pass(order: jax.Array, flag: jax.Array) -> jax.Array:
    """One binary pass: rows with flag=False before rows with flag=True."""
    f = flag[order]
    zeros = (~f).astype(jnp.int32)
    rank0 = jnp.cumsum(zeros)
    total0 = rank0[-1]
    i = jnp.arange(order.shape[0], dtype=jnp.int32)
    # stable split: zeros keep rank among zeros, ones follow
    dest = jnp.where(f, total0 + (i + 1 - rank0) - 1, rank0 - 1)
    return (jnp.zeros_like(order)
            .at[dest].set(order, unique_indices=True, mode="drop"))


def _split_u32(shifted_u64: jax.Array):
    lo = (shifted_u64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (shifted_u64 >> jnp.uint64(32)).astype(jnp.uint32)
    return lo, hi


def _range_reduce(w64: jax.Array, dead: Optional[jax.Array]):
    """Map int64 words to min-subtracted uint64 (zeroing dead rows).

    The bias trick (x ^ 2^63 viewed unsigned) preserves int64 order while
    making the subtraction wrap-free for ANY key spread — a plain
    ``w - min(w)`` overflows int64 when the live spread exceeds 2^63 and
    the runtime pass-skipping would then silently drop needed digit
    passes.  Returns (shifted uint64, range uint64)."""
    u = w64.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    if dead is not None:
        live_min = jnp.min(jnp.where(dead, jnp.uint64(2**64 - 1), u))
        live_min = jnp.where(jnp.all(dead), jnp.uint64(0), live_min)
        shifted = jnp.where(dead, jnp.uint64(0), u - live_min)
    else:
        shifted = u - jnp.min(u)
    return shifted, jnp.max(shifted)


def _rng_lo_saturated(rng: jax.Array) -> jax.Array:
    """Low word's runtime range: saturate to full 32 bits whenever high
    bits exist (low digits are then unpredictable)."""
    return ((rng & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
            | ((rng >> jnp.uint64(32)) > 0).astype(jnp.uint32)
            * jnp.uint32(0xFFFFFFFF))


def radix_argsort_i64(words: Sequence[jax.Array],
                      pad: Optional[jax.Array] = None,
                      max_bits: Sequence[int] = ()) -> jax.Array:
    """Stable ascending argsort over int64 key ``words`` (major first,
    like sort_permutation's key order; the OPPOSITE of jnp.lexsort's
    argument order).  ``pad`` rows sort to the end.  ``max_bits[i]``
    optionally bounds word i's value spread when the caller knows it
    statically (fewer compiled passes); runtime range skipping handles
    the rest dynamically.

    Returns an int32 permutation.
    """
    n = words[0].shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    if n <= 1:
        return order
    bits = list(max_bits) + [64] * (len(words) - len(max_bits))
    # LSD: least-significant key first
    for w, b in zip(reversed(list(words)), reversed(bits)):
        shifted, rng = _range_reduce(w.astype(jnp.int64), pad)
        lo, hi = _split_u32(shifted)
        order = _word_passes(order, lo, _rng_lo_saturated(rng), min(b, 32))
        if b > 32:
            order = _word_passes(order, hi,
                                 (rng >> jnp.uint64(32)).astype(jnp.uint32),
                                 b - 32)
    if pad is not None:
        order = _bit_pass(order, pad)
    return order


# (values, valid|None, type, descending, nulls_first) — ops/sort.py SortKey
def radix_sort_permutation(keys, num_rows: jax.Array) -> jax.Array:
    """Drop-in replacement for ops.sort.sort_permutation built on the
    radix passes: stable permutation ordering live rows by the sort spec,
    padding rows last (their relative order unspecified)."""
    cap = keys[0][0].shape[0]
    order = jnp.arange(cap, dtype=jnp.int32)
    if cap <= 1:
        return order
    pad = jnp.arange(cap) >= num_rows
    # LSD: minor key's passes first
    for values, valid, typ, desc, nulls_first in reversed(list(keys)):
        w = to_sortable_i64(jnp, values, typ)
        if desc:
            w = ~w
        dead = pad if valid is None else (pad | ~valid)
        shifted, rng = _range_reduce(w, dead)
        lo, hi = _split_u32(shifted)
        order = _word_passes(order, lo, _rng_lo_saturated(rng), 32)
        order = _word_passes(order, hi,
                             (rng >> jnp.uint64(32)).astype(jnp.uint32), 32)
        if valid is not None:
            null_last = (~valid) if not nulls_first else valid
            order = _bit_pass(order, null_last)
    order = _bit_pass(order, pad)
    return order


def counting_sort_perm(codes: jax.Array, domain: int) -> jax.Array:
    """Single-pass stable sort of small-domain codes (partition ids,
    dictionary codes): the dense-domain direct path.  ``codes`` must be
    in [0, domain)."""
    n = codes.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    if n <= 1 or domain <= 1:
        return order
    dest = _pass_dest(codes.astype(jnp.int32), domain)
    return (jnp.zeros_like(order)
            .at[dest].set(order, unique_indices=True, mode="drop"))
