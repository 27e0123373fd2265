"""Window-function kernels: segmented scans over partition-sorted rows.

The reference computes window functions row-at-a-time over a sorted
PagesIndex, partition by partition (WindowOperator.java:61 +
operator/window/*, framing in FrameInfo) — an inherently sequential loop.
The TPU formulation is data-parallel: after the sort kernel orders rows by
(partition keys, order keys), every window function becomes a *segmented
scan* — an ``associative_scan`` whose combine operator resets at partition
boundaries — plus gathers at segment/peer boundary indices.  No sequential
per-partition loop exists; one fused XLA program handles all partitions at
once.

Inputs are device arrays of one capacity; only rows ``[0, num_rows)`` are
live, and callers must place padding rows *after* all live rows (the sort
kernel guarantees this).  ``seg`` is the partition id per row
(nondecreasing), ``peer`` the peer-group id (nondecreasing, refines seg).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# segment machinery
# ---------------------------------------------------------------------------

def segment_ids(key_equal_prev: Array) -> Array:
    """[n] bool "row i equals row i-1 on the keys" -> int32 segment ids."""
    starts = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                              ~key_equal_prev[1:]])
    return jnp.cumsum(starts.astype(jnp.int32)) - 1


def _seg_bounds(seg: Array) -> Tuple[Array, Array, Array, Array]:
    """Per-row (start_idx, end_idx, index_in_seg, seg_count)."""
    n = seg.shape[0]
    # int32 row indices: the chip emulates int64, and an int64
    # cummax/cummin (a reduce_window there) takes minutes to compile at
    # one 64K batch, or crashes the TPU compiler outright
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                seg[1:] != seg[:-1]])
    # start index of this row's segment: running max of start positions
    start_idx = jax.lax.cummax(jnp.where(is_start, idx, 0))
    is_end = jnp.concatenate([seg[1:] != seg[:-1],
                              jnp.ones((1,), jnp.bool_)])
    # end index: reverse running min of end positions
    end_idx = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(is_end, idx, n - 1))))
    index_in_seg = idx - start_idx
    count = end_idx - start_idx + 1
    return start_idx, end_idx, index_in_seg, count


def _segmented_scan(seg: Array, values: Array, combine):
    """Inclusive scan of ``combine`` over values, restarting per segment."""

    def op(a, b):
        sa, va = a
        sb, vb = b
        return sb, jnp.where(sa == sb, combine(va, vb), vb)

    _, out = jax.lax.associative_scan(op, (seg, values))
    return out


def _seg_cumsum(seg: Array, values: Array) -> Array:
    return _segmented_scan(seg, values, jnp.add)


def _seg_cummax(seg: Array, values: Array) -> Array:
    return _segmented_scan(seg, values, jnp.maximum)


def _seg_cummin(seg: Array, values: Array) -> Array:
    return _segmented_scan(seg, values, jnp.minimum)


def _seg_reverse_cumsum(seg: Array, values: Array) -> Array:
    return jnp.flip(_seg_cumsum(jnp.flip(seg), jnp.flip(values)))


# ---------------------------------------------------------------------------
# ranking functions (frames do not apply)
# ---------------------------------------------------------------------------

def row_number(seg: Array) -> Array:
    _, _, in_seg, _ = _seg_bounds(seg)
    return (in_seg + 1).astype(jnp.int64)


def rank(seg: Array, peer: Array) -> Array:
    seg_start, _, _, _ = _seg_bounds(seg)
    peer_start, _, _, _ = _seg_bounds(peer)
    return (peer_start - seg_start + 1).astype(jnp.int64)


def dense_rank(seg: Array, peer: Array) -> Array:
    is_peer_start = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                     peer[1:] != peer[:-1]])
    return _seg_cumsum(seg, is_peer_start.astype(jnp.int64))


def percent_rank(seg: Array, peer: Array) -> Array:
    _, _, _, count = _seg_bounds(seg)
    r = rank(seg, peer)
    return jnp.where(count > 1,
                     (r - 1).astype(jnp.float64)
                     / jnp.maximum(count - 1, 1).astype(jnp.float64),
                     0.0)


def cume_dist(seg: Array, peer: Array) -> Array:
    seg_start, _, _, count = _seg_bounds(seg)
    _, peer_end, _, _ = _seg_bounds(peer)
    return ((peer_end - seg_start + 1).astype(jnp.float64)
            / count.astype(jnp.float64))


def ntile(seg: Array, n_buckets: int) -> Array:
    """SQL ntile: remainder rows go to the leading buckets."""
    _, _, in_seg, count = _seg_bounds(seg)
    base = count // n_buckets
    rem = count % n_buckets
    big = rem * (base + 1)  # rows covered by the (base+1)-sized buckets
    in_big = in_seg < big
    bucket = jnp.where(
        in_big,
        in_seg // jnp.maximum(base + 1, 1),
        rem + (in_seg - big) // jnp.maximum(base, 1))
    return (bucket + 1).astype(jnp.int64)


# ---------------------------------------------------------------------------
# value functions
# ---------------------------------------------------------------------------

def shift_in_partition(seg: Array, values: Array, valid: Optional[Array],
                       offset: int, default_values: Optional[Array] = None,
                       ) -> Tuple[Array, Array]:
    """lag (offset>0) / lead (offset<0): value ``offset`` rows back within
    the partition, else the default (NULL when no default)."""
    n = values.shape[0]
    idx = jnp.arange(n) - offset
    idx_c = jnp.clip(idx, 0, n - 1)
    in_part = (idx >= 0) & (idx < n) & (seg[idx_c] == seg)
    out = jnp.where(in_part, values[idx_c], values)
    ok = in_part if valid is None else jnp.where(in_part, valid[idx_c], False)
    if default_values is not None:
        out = jnp.where(in_part, out, default_values)
        ok = ok | ~in_part
    return out, ok


def value_at_frame_start(seg: Array, values: Array,
                         valid: Optional[Array], k: int = 1,
                         frame_end: Optional[Array] = None,
                         ) -> Tuple[Array, Array]:
    """first_value (k=1) / nth_value(k) for frames starting at the
    partition start; NULL beyond the frame end."""
    start_idx, _, _, _ = _seg_bounds(seg)
    target = start_idx + (k - 1)
    end = _seg_bounds(seg)[1] if frame_end is None else frame_end
    in_frame = target <= end
    tc = jnp.clip(target, 0, values.shape[0] - 1)
    out = values[tc]
    ok = in_frame if valid is None else (in_frame & valid[tc])
    return out, ok


def value_at(values: Array, valid: Optional[Array], idx: Array
             ) -> Tuple[Array, Array]:
    """Gather ``values[idx]`` with validity (for last_value at frame end)."""
    idx_c = jnp.clip(idx, 0, values.shape[0] - 1)
    out = values[idx_c]
    ok = (jnp.ones_like(idx, jnp.bool_) if valid is None else valid[idx_c])
    return out, ok


# ---------------------------------------------------------------------------
# framed aggregates
# ---------------------------------------------------------------------------

def frame_ends(seg: Array, peer: Array, unit: str,
               start: str, end: str,
               start_offset: Optional[int] = None,
               end_offset: Optional[int] = None) -> Tuple[Array, Array]:
    """Per-row inclusive frame [lo, hi] as row indices.

    ``unit`` 'range' resolves CURRENT ROW to the whole peer group (SQL
    semantics); bounded offsets are supported for 'rows' only.
    """
    seg_start, seg_end, in_seg, _ = _seg_bounds(seg)
    idx = jnp.arange(seg.shape[0])
    if unit == "range":
        peer_start, peer_end, _, _ = _seg_bounds(peer)
        cur_lo, cur_hi = peer_start, peer_end
    else:
        cur_lo, cur_hi = idx, idx

    if start == "unbounded_preceding":
        lo = seg_start
    elif start == "current":
        lo = cur_lo
    elif start == "preceding":
        lo = jnp.maximum(idx - start_offset, seg_start)
    elif start == "following":
        lo = jnp.minimum(idx + start_offset, seg_end + 1)
    else:
        raise ValueError(f"bad frame start {start}")

    if end == "unbounded_following":
        hi = seg_end
    elif end == "current":
        hi = cur_hi
    elif end == "following":
        hi = jnp.minimum(idx + end_offset, seg_end)
    elif end == "preceding":
        hi = jnp.maximum(idx - end_offset, seg_start - 1)
    else:
        raise ValueError(f"bad frame end {end}")
    return lo, hi


def framed_sum_count(seg: Array, values: Array, valid: Optional[Array],
                     lo: Array, hi: Array) -> Tuple[Array, Array]:
    """(sum, count) of valid values over [lo, hi] per row, via segmented
    prefix sums differenced at the frame bounds."""
    ok = jnp.ones(values.shape[0], jnp.bool_) if valid is None else valid
    contrib = jnp.where(ok, values, jnp.zeros_like(values))
    ps = _seg_cumsum(seg, contrib)          # inclusive prefix within segment
    pc = _seg_cumsum(seg, ok.astype(jnp.int64))
    seg_start = _seg_bounds(seg)[0]
    n = values.shape[0]

    def pref(p, at):
        # prefix value at index `at` (inclusive); 0 before segment start
        atc = jnp.clip(at, 0, n - 1)
        v = p[atc]
        return jnp.where(at < seg_start, jnp.zeros_like(v), v)

    s = pref(ps, hi) - pref(ps, lo - 1)
    c = pref(pc, hi) - pref(pc, lo - 1)
    empty = lo > hi
    s = jnp.where(empty, jnp.zeros_like(s), s)
    c = jnp.where(empty, jnp.zeros_like(c), c)
    return s, c


def framed_minmax_range(values: Array, valid: Optional[Array],
                        lo: Array, hi: Array, is_max: bool
                        ) -> Tuple[Array, Array]:
    """min/max over arbitrary [lo, hi] frames (bounded ``N PRECEDING``
    starts included) via a doubling sparse table: level k holds the
    extremum of each 2^k-wide window, and a query covers [lo, hi] with
    two overlapping power-of-two windows — O(n log n) build of purely
    elementwise mins, O(1) gathers per row; the TPU shape of a
    range-extremum query (no per-row loops).

    ``lo``/``hi`` must already be clipped to partition bounds (as
    frame_ends produces), so queries never straddle partitions."""
    n = values.shape[0]
    info = (jnp.finfo if jnp.issubdtype(values.dtype, jnp.floating)
            else jnp.iinfo)
    sentinel = info(values.dtype).min if is_max else info(values.dtype).max
    ok = jnp.ones(n, jnp.bool_) if valid is None else valid
    masked = jnp.where(ok, values, jnp.asarray(sentinel, values.dtype))
    op = jnp.maximum if is_max else jnp.minimum

    levels = [masked]
    counts = [ok.astype(jnp.int32)]
    width = 1
    while width < n:
        prev = levels[-1]
        pcnt = counts[-1]
        pad = jnp.full((width,), sentinel, values.dtype)
        levels.append(op(prev, jnp.concatenate([prev[width:], pad])))
        counts.append(pcnt + jnp.concatenate(
            [pcnt[width:], jnp.zeros(width, jnp.int32)]))
        width *= 2
    table = jnp.stack(levels)            # [L, n]
    ctable = jnp.stack(counts)

    length = jnp.maximum(hi - lo + 1, 1)
    k = (jnp.ceil(jnp.log2(length.astype(jnp.float64) + 0.5))
         .astype(jnp.int32) - 1)
    k = jnp.clip(k, 0, len(levels) - 1)  # floor(log2(length))
    span = jnp.left_shift(jnp.int64(1), k.astype(jnp.int64))
    a = jnp.clip(lo, 0, n - 1)
    b = jnp.clip(hi - span + 1, 0, n - 1)
    out = op(table[k, a], table[k, b])
    any_ok = (ctable[k, a] + ctable[k, b]) > 0
    empty = lo > hi
    return out, any_ok & ~empty


def framed_minmax(seg: Array, peer: Array, values: Array,
                  valid: Optional[Array], unit: str, start: str, end: str,
                  is_max: bool, lo: Optional[Array] = None,
                  hi: Optional[Array] = None) -> Tuple[Array, Array]:
    """min/max over frames with an unbounded edge (the common shapes):
    [unbounded_preceding, current|unbounded_following].  Running extremum
    via segmented cummax/cummin; range frames gather at the peer end.
    Bounded starts (``N PRECEDING``) route to the sparse-table range
    query when the caller supplies the frame ends."""
    if start != "unbounded_preceding":
        if lo is None or hi is None:
            raise NotImplementedError(
                "bounded min/max frame requires precomputed frame ends")
        return framed_minmax_range(values, valid, lo, hi, is_max)
    info = jnp.finfo if jnp.issubdtype(values.dtype, jnp.floating) else jnp.iinfo
    sentinel = info(values.dtype).min if is_max else info(values.dtype).max
    ok = jnp.ones(values.shape[0], jnp.bool_) if valid is None else valid
    masked = jnp.where(ok, values, jnp.asarray(sentinel, values.dtype))
    scan = (_seg_cummax if is_max else _seg_cummin)(seg, masked)
    cnt = _seg_cumsum(seg, ok.astype(jnp.int64))
    if end == "unbounded_following":
        seg_end = _seg_bounds(seg)[1]
        out, any_ok = scan[seg_end], cnt[seg_end] > 0
    elif unit == "range":
        peer_end = _seg_bounds(peer)[1]
        out, any_ok = scan[peer_end], cnt[peer_end] > 0
    else:
        out, any_ok = scan, cnt > 0
    return out, any_ok
