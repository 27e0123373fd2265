"""Hash join operators: build + probe pair sharing a LookupSource.

Reference models: HashBuilderOperator.java:51 (build side ->
PartitionedLookupSourceFactory), LookupJoinOperator.java:64 (probe),
HashSemiJoinOperator/SetBuilderOperator (semi), with variants per
LookupJoinOperators.java:45-60 (inner / probe-outer / semi / anti).

TPU design (ops/join.py): the LookupSource is an index over the build
rows grouped by key, never a chained table.  Id strategies, chosen at
build finish from the key types and the live span of the build keys:

- 'single': one integer-ish key channel; values are ids directly.
- 'packed': multi-channel integer keys packed into one 63-bit word using
  build-side [min,max] ranges; probe values outside a channel's build range
  cannot match and map to the dead sentinel (keeps packing exact).
- 'hash': the PagesHash table over normalized key words (ops/hashtable.py).
- 'canonical': arbitrary keys; probe side must materialize, ids come from
  a union sort (exact, collision-free).

A 'single' / 'packed' source whose id span fits
``ops.join.DENSE_INDEX_MAX_SLOTS`` carries a direct-address ``index``
(kernel tier "dense": a probe is one row gather); wider spans carry the
sorted id array (tier "sorted": histogram or binary search per probe).

Probe is streaming for every mode but 'canonical' (one jitted program per
probe batch shape), with output-capacity retry on expansion overflow.
"""

from __future__ import annotations

import dataclasses
from functools import partial as _partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import kernelcache
from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, next_bucket
from presto_tpu.spans import activity
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import (
    Operator, OperatorFactory, column_pairs, device_concat,
)

_PACKABLE = ("bigint", "integer", "smallint", "tinyint", "date", "boolean")


def _is_single_word_type(t: T.Type) -> bool:
    from presto_tpu.ops.join import single_word_joinable

    return single_word_joinable(t, t.is_dictionary)


@dataclasses.dataclass
class LookupSource:
    """Build-side product handed to probe operators."""

    mode: str                      # 'single' | 'packed' | 'canonical'
                                   # | 'hash' (PagesHash table)
    sorted_ids: object             # int64 [cap_b] (single/packed without
                                   # an index)
    perm: object                   # [cap_b] build rows grouped by key
    data: Batch                    # padded device build batch
    n_build: int
    key_channels: List[int]
    mins: Optional[np.ndarray] = None     # packed: per-channel min;
                                          # single: build live min (device)
    strides: Optional[np.ndarray] = None  # packed: per-channel stride
    maxs: Optional[np.ndarray] = None
    has_null_key: object = None           # device bool scalar (single/packed)
    # mode 'hash' (ops/hashtable.py): the open-addressing
    # table (t_words tuple, t_prefix, t_used, starts, counts) whose
    # (starts, counts) index ``perm`` — the PagesHash role proper
    pages: Optional[tuple] = None
    key_types: Optional[tuple] = None     # probe-normalization types
    # single/packed whose id span fits DENSE_INDEX_MAX_SLOTS: the
    # direct-address index, int32 [size, 2] addressed by the id itself:
    # index[id] = (start, count) of that key's run in ``perm``
    index: object = None

    @property
    def kernel_tier(self) -> str:
        """What OperatorStats.kernel_tier reads for a build or a probe
        through this source."""
        if self.mode == "hash":
            return "hash"
        return "dense" if self.index is not None else "sorted"


class LookupSourceFactory:
    """Rendezvous between build and probe pipelines
    (PartitionedLookupSourceFactory analogue; single-partition here — the
    multi-device partitioned variant lives in parallel/)."""

    def __init__(self):
        self.source: Optional[LookupSource] = None

    def set(self, source: LookupSource) -> None:
        self.source = source

    def get(self) -> LookupSource:
        if self.source is None:
            raise RuntimeError("build side not finished before probe "
                               "(pipeline ordering bug)")
        return self.source


@dataclasses.dataclass
class SpilledLookupSource:
    """Build side went to the spill tier (HashBuilderOperator's
    INPUT_SPILLED state, HashBuilderOperator.java:155): the probe operator
    must hash-partition its input the same way and join
    partition-by-partition (grace hash join / GenericPartitioningSpiller).
    """

    spiller: object                # PartitioningSpiller over key channels
    n_partitions: int
    key_channels: List[int]
    input_types: List[T.Type]

    mode: str = "spilled"


@_partial(kernelcache.jit, name="join_build_index")
def _build_index_single(kv_pair, num_rows):
    """Single-word build: ids + sorted index + the live minimum + a
    span-overflow flag, one XLA program.  Ids are (value - min + 2) so
    NEGATIVE key values map to valid non-negative ids too (the sentinels
    own {-2,-1}); the min rides to the probe side as a device scalar.
    The caller reads only the flag (one scalar sync) and falls back to
    the canonical path when the live key spread would overflow the id
    arithmetic."""
    from presto_tpu.ops import join as J

    values, valid = kv_pair
    cap = values.shape[0]
    in_row = jnp.arange(cap) < num_rows
    dead = ~in_row
    if valid is not None:
        dead = dead | ~valid
        has_null = (in_row & ~valid).any()
    else:
        has_null = jnp.zeros((), bool)
    v = values.astype(jnp.int64)
    u = v.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    umin = jnp.min(jnp.where(dead, jnp.uint64(2**64 - 1), u))
    umax = jnp.max(jnp.where(dead, jnp.uint64(0), u))
    span_big = (~jnp.all(dead)) & ((umax - umin) >= jnp.uint64(1 << 62))
    bmin = jnp.min(jnp.where(dead, jnp.int64(2**62), v))
    bmin = jnp.where(jnp.all(dead), jnp.int64(0), bmin)
    ids = jnp.where(dead, jnp.int64(-2), v - bmin + 2)
    sb, perm = J.build_index(ids)
    return sb, perm, bmin, has_null, span_big


@_partial(kernelcache.jit, name="join_key_ranges")
def _key_ranges(pairs, num_rows):
    """Per-key-channel live [min, max] (packed-mode ranges), one program,
    one small host transfer."""
    cap = pairs[0][0].shape[0]
    base_dead = jnp.arange(cap) >= num_rows
    los, his = [], []
    for values, valid in pairs:
        dead = base_dead if valid is None else (base_dead | ~valid)
        v = values.astype(jnp.int64)
        los.append(jnp.where(dead, jnp.int64(2**62), v).min())
        his.append(jnp.where(dead, jnp.int64(-2**62), v).max())
    return jnp.stack(los), jnp.stack(his)


def _packed_ids(pairs, mins, strides, num_rows):
    """Mixed-radix build ids (dead rows -2) and the null-key flag."""
    cap = pairs[0][0].shape[0]
    in_row = jnp.arange(cap) < num_rows
    dead = ~in_row
    has_null = jnp.zeros((), bool)
    ids = jnp.zeros(cap, jnp.int64)
    for i, (values, valid) in enumerate(pairs):
        if valid is not None:
            dead = dead | ~valid
            has_null = has_null | (in_row & ~valid).any()
        ids = ids + (values.astype(jnp.int64) - mins[i]) * strides[i]
    return jnp.where(dead, jnp.int64(-2), ids), has_null


@_partial(kernelcache.jit, name="join_build_index")
def _build_index_packed(pairs, mins, strides, num_rows):
    """Packed multi-key build: mixed-radix ids + sorted index."""
    from presto_tpu.ops import join as J

    ids, has_null = _packed_ids(pairs, mins, strides, num_rows)
    sb, perm = J.build_index(ids)
    return sb, perm, has_null


@_partial(kernelcache.jit, name="join_build_index",
          static_argnames=("size", "id_base"))
def _build_index_dense(pairs, mins, strides, num_rows, *, size, id_base):
    """Direct-address build for integer keys whose id span fits ``size``
    slots: the ids of 'single' (``id_base`` 2, one channel, stride 1) or
    'packed' (``id_base`` 0) mode address the index themselves.  ``size``
    is the span's power-of-two bucket, so one table's builds share one
    program."""
    from presto_tpu.ops import join as J

    ids, has_null = _packed_ids(pairs, mins, strides, num_rows)
    ids = jnp.where(ids >= 0, ids + id_base, ids)
    index, perm = J.build_dense_index(ids, size)
    return index, perm, has_null


from presto_tpu.kernelcache import cache_get, cache_put, new_cache

_PAGES_BUILD = new_cache("pages_hash_build")


def _pages_hash_build_jit(key_pairs, key_types, num_rows, table_cap: int):
    """ops.hashtable.pages_hash_build as one cached jitted program (the
    HashBuilderOperator finish -> PagesHash ctor, PagesHash.java:63)."""
    cap_b = key_pairs[0][0].shape[0]
    kvalid = tuple(v is not None for _, v in key_pairs)
    key = ("pages_build", tuple(key_types), kvalid, cap_b, table_cap)
    hit = cache_get(_PAGES_BUILD, key)
    if hit is None:
        def kernel(kvals, kvalids, n):
            from presto_tpu.ops.hashtable import pages_hash_build

            kc = [(kvals[i], kvalids[i], key_types[i])
                  for i in range(len(key_types))]
            return pages_hash_build(kc, n, table_cap)

        hit = kernelcache.jit(kernel, "join_build_hash")
        cache_put(_PAGES_BUILD, key, hit)
    with activity("dispatch"):
        return hit(tuple(v for v, _ in key_pairs),
                   tuple(v for _, v in key_pairs), num_rows)


class HashBuildOperator(Operator):
    def __init__(self, ctx: OperatorContext, factory: "HashBuildOperatorFactory"):
        super().__init__(ctx)
        self.f = factory
        factory._build_ctxs.append(ctx)
        # backstop: if the probe pipeline never instantiates (earlier
        # pipeline failure / cancellation between pipelines) the task
        # teardown releases the build reservation instead of the probe
        ctx.task.register_cleanup(factory.release)
        self._batches: List[Batch] = []
        self._spiller = None
        self._accumulated_bytes = 0

    def close(self) -> None:
        # the LookupSource keeps the build data alive through the probe:
        # the reservation is released by the probe side
        # (LookupJoinOperator.close -> factory.release), not here
        pass

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        if self._spiller is not None:
            self._spiller.spill(batch.to_numpy())
            return
        self._batches.append(batch)
        self.ctx.memory.reserve(batch.size_bytes)
        self._accumulated_bytes += batch.size_bytes
        # byte threshold OR node-pool pressure (revoke-first: shed
        # revocable state before anyone blocks on the memory pool)
        if self.f.allow_spill and \
                self.ctx.should_spill(self._accumulated_bytes):
            self._spill_accumulated()

    def _spill_accumulated(self) -> None:
        """Revoke build-side memory: hash-partition everything seen so far
        to disk; the probe side will partition itself to match."""
        from presto_tpu.exec.spill import PartitioningSpiller

        cfg = self.ctx.config
        self._spiller = PartitioningSpiller(
            cfg.spill_path, cfg.spill_partitions, self.f.key_channels,
            tag=f"joinbuild-{self.ctx.name}")
        for b in self._batches:
            self._spiller.spill(b.to_numpy())
        self._batches = []
        self._accumulated_bytes = 0
        self.ctx.memory.free()

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        if self._spiller is not None:
            # a spilled build side cannot feed dynamic filters cheaply;
            # mark the filter as pass-through
            if self.f.dynamic_filter is not None:
                self.f.dynamic_filter.disable()
            self.f.lookup.set(SpilledLookupSource(
                self._spiller, self.ctx.config.spill_partitions,
                list(self.f.key_channels), list(self.f.input_types)))
            return
        import jax.numpy as jnp

        from presto_tpu import types as TT
        from presto_tpu.exec.operator import pad_batch
        from presto_tpu.ops import join as J

        data = device_concat(self._batches, self.ctx.config.min_batch_capacity)
        if self.f.dynamic_filter is not None:
            self.f.dynamic_filter.fill_from_build(
                None if data is None else data.to_numpy(),
                self.f.key_channels)
        if data is None:
            # empty build side: synthesize a 0-row padded batch
            from presto_tpu.batch import empty_batch

            data = pad_batch(empty_batch(self.f.input_types),
                             self.ctx.config.min_batch_capacity)
        self._batches = []
        chans = self.f.key_channels
        n_build = data.num_rows
        n = jnp.asarray(n_build)
        key_pairs = tuple(
            (data.columns[c].values, data.columns[c].valid) for c in chans)
        cfg = self.ctx.config
        packable = all(_is_single_word_type(data.columns[c].type)
                       for c in chans)
        ranges = None
        if packable:
            # integer key words: the live span decides.  One small host
            # read of the per-channel [min, max]; where the id span fits
            # the direct-address bound the build publishes the index and
            # every probe is one row gather, on every backend
            ranges = self._live_key_ranges(key_pairs, n)
            if self._set_dense_index(data, key_pairs, chans, n, n_build,
                                     ranges):
                return
        # unpackable (canonical-class) keys: the hash table is what lets
        # the probe STREAM at all (the sorted tier would materialize the
        # probe side for a union sort).  Integer keys too sparse for the
        # index: on the chip a probe of the sorted tier is a binary
        # search of 2x18 dependent int64 gathers, 33 ms a 64K batch
        # against the table's 29 ms, while claim-inserting a 128K build
        # costs 245 ms against 12 ms for the sort (v5e, PERF.md PR 30):
        # the table serves builds up to the bound.  On the CPU the
        # sorted tier stays.
        want_hash = not packable or (
            jax.default_backend() == "tpu"
            and n_build <= cfg.device_join_probe_max_build_rows)
        if want_hash and self._set_pages_hash(data, key_pairs, chans,
                                              n, n_build):
            return
        if len(chans) == 1 and packable:
            # one scalar sync guards the id arithmetic: a live key spread
            # >= 2^62 would overflow the (value - min + 2) ids, silently
            # dropping matches — such builds take the canonical path
            with activity("dispatch"):
                sb, perm, bmin, has_null, span_big = _build_index_single(
                    key_pairs[0], n)
            with activity("device_wait"):
                span_big = bool(span_big)
            if not span_big:
                self.ctx.stats.kernel_tier = "sorted"
                self.f.lookup.set(LookupSource(
                    "single", sb, perm, data, n_build, chans, mins=bmin,
                    has_null_key=has_null))
                return
        if packable:
            # pack multi-channel integer keys using build-side ranges
            los, his, strides, span_product = ranges
            if span_product < (1 << 62):
                strides_a = np.asarray(strides, np.int64)
                with activity("dispatch"):
                    sb, perm, has_null = _build_index_packed(
                        key_pairs, jnp.asarray(los),
                        jnp.asarray(strides_a), n)
                self.ctx.stats.kernel_tier = "sorted"
                self.f.lookup.set(LookupSource(
                    "packed", sb, perm, data, n_build, chans,
                    mins=los, strides=strides_a, maxs=his,
                    has_null_key=has_null))
                return
        # key spans overflowed the single/packed id arithmetic: the
        # hash table still streams such keys (equality needs no ids)
        if not want_hash and self._set_pages_hash(data, key_pairs, chans,
                                                  n, n_build):
            return
        # general path: probe side will materialize and union-sort
        self.f.lookup.set(LookupSource("canonical", None, None, data,
                                       n_build, chans))

    @staticmethod
    def _live_key_ranges(key_pairs, n):
        """Per-channel live [min, max] of integer build keys, read to
        the host (one sync), with the mixed-radix strides and the id
        span they give: ``(los, his, strides, span_product)``, the last
        two as Python ints (a product of wide spans passes int64)."""
        with activity("dispatch"):
            los, his = _key_ranges(key_pairs, n)
        with activity("device_wait"):
            los = np.asarray(los)
            his = np.asarray(his)
        if bool((los > his).any()):                     # no live rows
            los = np.zeros_like(los)
            his = np.zeros_like(his)
        strides = []
        span_product = 1
        for lo, hi in zip(los, his):
            strides.append(span_product)
            span_product *= int(hi) - int(lo) + 1
        return los, his, strides, span_product

    def _set_dense_index(self, data, key_pairs, chans, n, n_build,
                         ranges) -> bool:
        """Build + publish the direct-address index; False when the id
        span is over ``DENSE_INDEX_MAX_SLOTS`` (or the index's bytes
        cannot be reserved): the caller then takes the other tiers."""
        from presto_tpu.exec.context import MemoryReservationError
        from presto_tpu.ops import join as J

        los, his, strides, span_product = ranges
        single = len(chans) == 1
        # 'single' ids are (value - min + 2): two idle slots in front
        id_base = 2 if single else 0
        size = J.dense_index_size(span_product + id_base)
        if size is None:
            return False
        try:
            # the index and the int32 perm live through the probe
            # with the build data; HashBuildOperatorFactory.release frees
            self.ctx.memory.reserve(8 * size + 4 * data.capacity)
        except MemoryReservationError:
            return False
        strides_a = np.asarray(strides, np.int64)
        with activity("dispatch"):
            index, perm, has_null = _build_index_dense(
                key_pairs, jnp.asarray(los), jnp.asarray(strides_a), n,
                size=size, id_base=id_base)
        self.ctx.stats.kernel_tier = "dense"
        # 'single' probes read ``mins`` as the device scalar of the
        # build's live minimum and nothing else of the ranges
        self.f.lookup.set(LookupSource(
            "single" if single else "packed", None, perm, data, n_build,
            chans, mins=jnp.asarray(los[0]) if single else los,
            strides=strides_a, maxs=his, has_null_key=has_null,
            index=index))
        return True

    def _set_pages_hash(self, data, key_pairs, chans, n,
                        n_build) -> bool:
        """Build + publish the PagesHash lookup source; False when the
        bounded claim loop could not place the build keys (adversarial
        chains — one retry at 4x capacity quarters the load first).
        ok=False costs one scalar sync, the span_big guard's cost
        class."""
        table_cap = max(2 * data.capacity, 1024)
        ktypes = tuple(data.columns[c].type for c in chans)
        (tw, tp, tu, starts, counts, perm, has_null,
         ok) = _pages_hash_build_jit(key_pairs, ktypes, n, table_cap)
        with activity("device_wait"):
            placed = bool(ok)
        if not placed:
            (tw, tp, tu, starts, counts, perm, has_null,
             ok) = _pages_hash_build_jit(key_pairs, ktypes, n,
                                         4 * table_cap)
            with activity("device_wait"):
                placed = bool(ok)
        if not placed:
            return False
        self.ctx.stats.kernel_tier = "hash"
        self.f.lookup.set(LookupSource(
            "hash", None, perm, data, n_build, chans,
            has_null_key=has_null, pages=(tw, tp, tu, starts, counts),
            key_types=ktypes))
        return True

    def get_output(self) -> Optional[Batch]:
        return None

    def is_finished(self) -> bool:
        return self._finishing


class HashBuildOperatorFactory(OperatorFactory):
    def __init__(self, key_channels: Sequence[int],
                 input_types: Sequence[T.Type], dynamic_filter=None,
                 allow_spill: bool = True):
        self.key_channels = list(key_channels)
        self.input_types = list(input_types)
        self.lookup = LookupSourceFactory()
        self.dynamic_filter = dynamic_filter
        # per-partition sub-builds during a grace join must not re-spill
        self.allow_spill = allow_spill
        self._build_ctxs: List[OperatorContext] = []

    def create(self, ctx: OperatorContext) -> HashBuildOperator:
        return HashBuildOperator(ctx, self)

    def release(self) -> None:
        """Drop the lookup source and the build-side reservation.  Called
        when the probe finishes — under grouped execution this is what
        makes peak memory scale with 1/buckets (Lifespan retirement,
        execution/Lifespan.java:26-38 role).  Idempotent: contexts are
        freed once; the task-teardown backstop may call this again for a
        build whose probe pipeline never instantiated."""
        self.lookup.source = None
        ctxs, self._build_ctxs = self._build_ctxs, []
        for ctx in ctxs:
            ctx.memory.free()

    def reset_for_execution(self) -> None:
        # a cached physical plan re-runs its build pipeline; the
        # previous run's lookup source (normally released at probe
        # finish — this is the backstop for error paths) must not leak
        self.release()


def _ids_from_pairs(jnp, pairs, key_channels, mode, mins, strides, maxs,
                    num_rows):
    """Probe ids for 'single'/'packed' modes over (values, valid) pairs."""
    cap = pairs[0][0].shape[0]
    dead = jnp.arange(cap) >= num_rows
    for c in key_channels:
        if pairs[c][1] is not None:
            dead = dead | ~pairs[c][1]
    if mode == "single":
        # mins = build-side live minimum (device scalar); probe values
        # below it cannot match any build row -> dead sentinel
        ids = pairs[key_channels[0]][0].astype(jnp.int64) - mins + 2
        return jnp.where(dead | (ids < 0), jnp.int64(-1), ids)
    ids = jnp.zeros(cap, jnp.int64)
    for i, c in enumerate(key_channels):
        v = pairs[c][0].astype(jnp.int64)
        dead = dead | (v < mins[i]) | (v > maxs[i])
        ids = ids + (v - mins[i]) * strides[i]
    return jnp.where(dead, jnp.int64(-1), ids)


@dataclasses.dataclass(frozen=True)
class _StreamStatics:
    """Hashable static config for the module-level probe kernels; one jit
    cache entry per distinct value + input shapes (the JoinCompiler
    specialization key, shared GLOBALLY across operators and queries —
    closures would re-trace per operator instance)."""

    mode: str
    join_type: str
    key_channels: Tuple[int, ...]
    out_cap: int
    n_probe_cols: int
    null_aware: bool = False
    # 'hash' mode: probe-key types for word normalization inside the
    # kernel (the pages table is keyed on normalized words)
    key_types: Tuple = ()


def _hash_lo_counts(probe_pairs, pages, key_channels, key_types,
                    num_rows):
    """(lo, counts, live) through the PagesHash table (probe half of
    PagesHash.java:63-121; prefix reject before the word compare)."""
    from presto_tpu.ops.hashtable import pages_hash_probe

    kc = [(probe_pairs[c][0], probe_pairs[c][1], key_types[i])
          for i, c in enumerate(key_channels)]
    return pages_hash_probe(pages, kc, num_rows)


def _index_lo_counts(ids, sorted_ids, perm, index):
    """(lo, counts) for 'single'/'packed' ids: through the build's
    direct-address index where it published one, else the sorted ids."""
    from presto_tpu.ops import join as J

    if index is not None:
        return J.probe_dense(index, ids)
    return J.probe_counts(sorted_ids, perm, ids)


@_partial(kernelcache.jit, name="join_probe_count",
          static_argnames=("key_channels", "mode", "join_type",
                           "key_types"))
def _probe_expand_total(probe_pairs, sorted_ids, perm, mins, strides,
                        maxs, pages, index, num_rows, *, key_channels,
                        mode, join_type, key_types=()):
    """Phase 1: exact expansion size for this batch (so phase 2 compiles
    at the right capacity bucket on the first try)."""
    from presto_tpu.ops import join as J

    if mode == "hash":
        _, counts, _ = _hash_lo_counts(probe_pairs, pages, key_channels,
                                       key_types, num_rows)
    else:
        ids = _ids_from_pairs(jnp, probe_pairs, key_channels, mode, mins,
                              strides, maxs, num_rows)
        _, counts = _index_lo_counts(ids, sorted_ids, perm, index)
    if join_type == "left":
        cap = probe_pairs[0][0].shape[0]
        live_probe = jnp.arange(cap) < num_rows
        return jnp.where(live_probe, jnp.maximum(counts, 1), 0).sum()
    return counts.sum()


@_partial(kernelcache.jit, name="join_probe", static_argnames=("s",))
def _stream_probe(probe_pairs, build_pairs, sorted_ids, perm, mins,
                  strides, maxs, pages, index, num_rows, bstats, *,
                  s: _StreamStatics):
    """Phase 2: the streaming probe kernel (inner/left expansion or
    semi/anti masks) as one XLA program.  All build-side data arrives as
    traced arguments: nothing is baked into the executable, so the
    compile caches by shape + statics only."""
    from presto_tpu.ops import join as J
    from presto_tpu.ops.filter import selected_positions

    cap = probe_pairs[0][0].shape[0]
    if s.mode == "hash":
        lo, counts, live = _hash_lo_counts(
            probe_pairs, pages, s.key_channels, s.key_types, num_rows)
    else:
        ids = _ids_from_pairs(jnp, probe_pairs, s.key_channels, s.mode,
                              mins, strides, maxs, num_rows)
        lo, counts = _index_lo_counts(ids, sorted_ids, perm, index)
        live = ids >= 0
    if s.join_type in ("semi", "anti"):
        if s.join_type == "anti":
            n_build, has_null = bstats
            mask = J.anti_keep_from_parts(
                counts, live, jnp.arange(cap) < num_rows, s.null_aware,
                [probe_pairs[c][1] for c in s.key_channels],
                n_build, build_has_null=has_null)
        else:
            mask = J.semi_mask(counts, live, anti=False)
        idx, count = selected_positions(mask, None, num_rows, cap)
        outs = tuple(
            (v[idx], None if valid is None else valid[idx])
            for v, valid in probe_pairs)
        return outs, count, jnp.int64(0)
    if s.join_type == "left":
        pi, bi, rv, unmatched, total = J.expand_matches_outer(
            lo, counts, jnp.arange(cap) < num_rows, perm, s.out_cap)
    else:
        pi, bi, rv, unmatched, total = J.expand_matches(
            lo, counts, perm, s.out_cap)
    pi = pi.astype(jnp.int32)
    bi = bi.astype(jnp.int32)
    outs = []
    for v, valid in probe_pairs:
        outs.append((v[pi], None if valid is None else valid[pi]))
    ones = jnp.ones(s.out_cap, bool)
    for v, valid in build_pairs:
        bvalid = ones if valid is None else valid[bi]
        outs.append((v[bi], bvalid & ~unmatched))
    return tuple(outs), total, total


class LookupJoinOperator(Operator):
    """Probe side.  Output layout: all probe channels, then all build
    channels (planner projects away what it does not need).  semi/anti emit
    probe channels only."""

    def close(self) -> None:
        super().close()
        self.f.build.release()

    def __init__(self, ctx: OperatorContext, factory: "LookupJoinOperatorFactory"):
        super().__init__(ctx)
        self.f = factory
        self._pending: List[Batch] = []
        self._out: List[Batch] = []
        self._kernels: Dict[tuple, object] = {}
        self._drained = False

    # -- probe id computation -------------------------------------------
    def _probe_ids(self, jnp, src: LookupSource, batch: Batch, num_rows):
        chans = self.f.probe_key_channels
        cap = batch.capacity
        dead = jnp.arange(cap) >= num_rows
        for c in chans:
            if batch.columns[c].valid is not None:
                dead = dead | ~batch.columns[c].valid
        if src.mode == "single":
            ids = (batch.columns[chans[0]].values.astype(jnp.int64)
                   - src.mins + 2)
            return jnp.where(dead | (ids < 0), jnp.int64(-1), ids)
        assert src.mode == "packed"
        ids = jnp.zeros(cap, jnp.int64)
        for i, c in enumerate(chans):
            v = batch.columns[c].values.astype(jnp.int64)
            lo = int(src.mins[i])
            hi = int(src.maxs[i])
            dead = dead | (v < lo) | (v > hi)
            ids = ids + (v - lo) * int(src.strides[i])
        return jnp.where(dead, jnp.int64(-1), ids)

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        src = self.f.build.lookup.get()
        if src.mode == "spilled":
            # grace join: partition the probe the same way as the build
            if getattr(self, "_probe_spiller", None) is None:
                from presto_tpu.exec.spill import PartitioningSpiller

                cfg = self.ctx.config
                self._probe_spiller = PartitioningSpiller(
                    cfg.spill_path, src.n_partitions,
                    self.f.probe_key_channels,
                    tag=f"joinprobe-{self.ctx.name}")
            self._probe_spiller.spill(batch.to_numpy())
            return
        if src.mode == "canonical":
            self._pending.append(batch)
            self.ctx.memory.reserve(batch.size_bytes)
            return
        out = self._probe_streaming(src, batch)
        if out is not None and out.num_rows > 0:
            self._out.append(out)

    def _residual_compiled(self, batch: Batch, src: LookupSource):
        """Compile the residual over [probe channels..., build channels...]
        (JoinFilterFunctionCompiler role)."""
        if self.f.residual is None:
            return None
        from presto_tpu.expr.compile import ExprCompiler

        nprobe = batch.num_columns
        dicts = {i: c.dictionary for i, c in enumerate(batch.columns)
                 if c.dictionary is not None}
        for j, c in enumerate(src.data.columns):
            if c.dictionary is not None:
                dicts[nprobe + j] = c.dictionary
        return ExprCompiler(dicts).compile(self.f.residual)

    def _probe_streaming(self, src: LookupSource, batch: Batch) -> Optional[Batch]:
        import jax
        import jax.numpy as jnp

        from presto_tpu.ops import join as J

        join_type = self.f.join_type
        cap = batch.capacity
        n = jnp.asarray(batch.num_rows)
        if self.f.residual is None:
            return self._probe_streaming_global(src, batch, n)
        out_cap = next_bucket(cap * self.f.expansion)
        cres = self._residual_compiled(batch, src)
        self.ctx.stats.kernel_tier = src.kernel_tier
        while True:
            kernel = self._kernel(src, cap, out_cap, cres)
            with activity("dispatch"):
                outs, count, expand_total = kernel(
                    tuple(column_pairs(batch)),
                    tuple(column_pairs(src.data)), src.index, n)
            with activity("device_wait"):
                total = int(count)
                expand_total = int(expand_total)
            if expand_total <= out_cap:
                break
            out_cap = next_bucket(expand_total)
        cols = []
        probe_cols = [batch.columns[i] for i in range(batch.num_columns)]
        if join_type in ("semi", "anti"):
            for c, (v, valid) in zip(probe_cols, outs):
                cols.append(Column(c.type, v, valid, c.dictionary))
        else:
            nb = batch.num_columns
            for c, (v, valid) in zip(probe_cols, outs[:nb]):
                cols.append(Column(c.type, v, valid, c.dictionary))
            for c, (v, valid) in zip(src.data.columns, outs[nb:]):
                cols.append(Column(c.type, v, valid, c.dictionary))
        out = Batch(tuple(cols), min(total, out_cap))
        self.ctx.stats.output_rows += out.num_rows
        return out

    def _probe_streaming_global(self, src: LookupSource, batch: Batch,
                                n) -> Optional[Batch]:
        """Residual-free probe through the globally-cached module kernels:
        count phase picks the exact output bucket, expand phase never
        overflows, and compiles are shared across operators and queries
        with the same shapes."""
        import jax.numpy as jnp

        join_type = self.f.join_type
        cap = batch.capacity
        kc = tuple(self.f.probe_key_channels)
        if src.mode == "packed":
            mins = jnp.asarray(src.mins)
            strides = jnp.asarray(src.strides)
            maxs = jnp.asarray(src.maxs)
        elif src.mode == "single":
            # build-side live minimum (device scalar from the build kernel)
            mins = src.mins
            strides = maxs = jnp.zeros(1, jnp.int64)
        else:
            mins = strides = maxs = jnp.zeros(1, jnp.int64)
        key_types = src.key_types if src.mode == "hash" else ()
        self.ctx.stats.kernel_tier = src.kernel_tier
        probe_pairs = tuple(column_pairs(batch))
        build_pairs = tuple(column_pairs(src.data))
        if join_type in ("semi", "anti"):
            out_cap = 0
        else:
            with activity("dispatch"):
                etotal = _probe_expand_total(
                    probe_pairs, src.sorted_ids, src.perm, mins, strides,
                    maxs, src.pages, src.index, n, key_channels=kc,
                    mode=src.mode,
                    join_type=join_type, key_types=key_types)
            with activity("device_wait"):
                etotal = int(etotal)
            out_cap = next_bucket(max(etotal, 1))
        s = _StreamStatics(src.mode, join_type, kc, out_cap,
                           batch.num_columns, self.f.null_aware,
                           key_types)
        bstats = (jnp.asarray(src.n_build, jnp.int64),
                  src.has_null_key if src.has_null_key is not None
                  else jnp.zeros((), bool))
        with activity("dispatch"):
            outs, count, _ = _stream_probe(
                probe_pairs, build_pairs, src.sorted_ids, src.perm, mins,
                strides, maxs, src.pages, src.index, n, bstats, s=s)
        # expansion joins already synced the exact total in phase 1; only
        # semi/anti need to read the selected count (every host read is
        # a device sync)
        if join_type in ("semi", "anti"):
            with activity("device_wait"):
                total = int(count)
        else:
            total = etotal
        cols = []
        probe_cols = [batch.columns[i] for i in range(batch.num_columns)]
        if join_type in ("semi", "anti"):
            for c, (v, valid) in zip(probe_cols, outs):
                cols.append(Column(c.type, v, valid, c.dictionary))
        else:
            nb = batch.num_columns
            for c, (v, valid) in zip(probe_cols, outs[:nb]):
                cols.append(Column(c.type, v, valid, c.dictionary))
            for c, (v, valid) in zip(src.data.columns, outs[nb:]):
                cols.append(Column(c.type, v, valid, c.dictionary))
        out = Batch(tuple(cols), total if out_cap == 0
                    else min(total, out_cap))
        self.ctx.stats.output_rows += out.num_rows
        return out

    def _kernel(self, src: LookupSource, cap: int, out_cap: int,
                cres=None):
        import jax
        import jax.numpy as jnp

        from presto_tpu.ops import join as J
        from presto_tpu.ops.filter import selected_positions

        key = (src.mode, cap, out_cap, self.f.join_type, id(src))
        hit = self._kernels.get(key)
        if hit is not None:
            return hit
        join_type = self.f.join_type
        probe_op = self
        residual = None if cres is None else cres.run

        def kernel(probe_cols_pairs, build_cols_pairs, index, num_rows):
            # the index rides as an argument: at up to 128 MB it must
            # not be baked into the executable as a constant
            if src.mode == "hash":
                lo, counts, live = _hash_lo_counts(
                    probe_cols_pairs, src.pages,
                    tuple(probe_op.f.probe_key_channels),
                    src.key_types, num_rows)
            else:
                pb = _RebuiltBatch(probe_cols_pairs)
                ids = probe_op._probe_ids(jnp, src, pb, num_rows)
                lo, counts = _index_lo_counts(ids, src.sorted_ids,
                                              src.perm, index)
                live = ids >= 0
            zero = jnp.int64(0)
            if join_type in ("semi", "anti"):
                if residual is not None:
                    pi, bi, rv, _, etotal = J.expand_matches(
                        lo, counts, src.perm, out_cap)
                    pairs = tuple(
                        (v[pi], None if g is None else g[pi])
                        for v, g in probe_cols_pairs) + tuple(
                        (v[bi], None if g is None else g[bi])
                        for v, g in build_cols_pairs)
                    rmask, rvalid = residual(pairs, etotal, jnp)
                    ok = rv & rmask
                    if rvalid is not None:
                        ok = ok & rvalid
                    any_pass = jnp.zeros(cap, bool).at[pi].max(
                        ok, mode="drop")
                    mask = live & any_pass
                    if join_type == "anti":
                        pad = jnp.arange(cap) >= num_rows
                        mask = (live & ~any_pass) | ((~live) & (~pad))
                else:
                    etotal = zero
                    if join_type == "anti":
                        bcap = build_cols_pairs[0][0].shape[0]
                        mask = J.anti_keep_from_parts(
                            counts, live, jnp.arange(cap) < num_rows,
                            probe_op.f.null_aware,
                            [probe_cols_pairs[c][1]
                             for c in probe_op.f.probe_key_channels],
                            jnp.int64(src.n_build),
                            build_key_valids=[
                                build_cols_pairs[c][1]
                                for c in probe_op.f.build.key_channels],
                            build_in_row=jnp.arange(bcap) < src.n_build)
                    else:
                        mask = J.semi_mask(counts, live, anti=False)
                idx, count = selected_positions(mask, None, num_rows,
                                                cap)
                outs = tuple(
                    (v[idx], None if valid is None else valid[idx])
                    for v, valid in probe_cols_pairs)
                return outs, count, etotal
            if join_type == "left":
                # every real probe row emits >=1 row (null-key rows emit the
                # unmatched form); padding rows emit nothing
                pi, bi, rv, unmatched, total = J.expand_matches_outer(
                    lo, counts, jnp.arange(cap) < num_rows,
                    src.perm, out_cap)
            else:
                pi, bi, rv, unmatched, total = J.expand_matches(
                    lo, counts, src.perm, out_cap)
            pi = pi.astype(jnp.int32)
            bi = bi.astype(jnp.int32)
            outs = []
            for v, valid in probe_cols_pairs:
                outs.append((v[pi], None if valid is None else valid[pi]))
            ones = jnp.ones(out_cap, bool)
            for v, valid in build_cols_pairs:
                bvalid = ones if valid is None else valid[bi]
                bvalid = bvalid & ~unmatched
                outs.append((v[bi], bvalid))
            return tuple(outs), total, total

        jitted = kernelcache.jit(kernel, "join_probe_residual")
        self._kernels[key] = jitted
        return jitted

    def _probe_canonical(self) -> None:
        import jax.numpy as jnp

        from presto_tpu.ops import join as J
        from presto_tpu.ops.filter import selected_positions

        src = self.f.build.lookup.get()
        probe = device_concat(self._pending,
                              self.ctx.config.min_batch_capacity)
        self._pending = []
        if probe is None:
            return
        bcols = [(src.data.columns[c].values, src.data.columns[c].valid,
                  src.data.columns[c].type) for c in self.f.build.key_channels]
        pcols = [(probe.columns[c].values, probe.columns[c].valid,
                  probe.columns[c].type) for c in self.f.probe_key_channels]
        bids, pids = J.canonical_ids(bcols, pcols,
                                     jnp.asarray(src.data.num_rows),
                                     jnp.asarray(probe.num_rows))
        sb, perm = J.build_index(bids)
        lo, counts = J.probe_counts(sb, perm, pids)
        live = pids >= 0
        cap = probe.capacity
        n = jnp.asarray(probe.num_rows)
        join_type = self.f.join_type
        if join_type in ("semi", "anti"):
            cres = self._residual_compiled(probe, src)
            if cres is None:
                if join_type == "anti":
                    bcap = src.data.capacity
                    mask = J.anti_keep_from_parts(
                        counts, live, jnp.arange(cap) < n,
                        self.f.null_aware,
                        [probe.columns[c].valid
                         for c in self.f.probe_key_channels],
                        jnp.int64(src.data.num_rows),
                        build_key_valids=[
                            src.data.columns[c].valid
                            for c in self.f.build.key_channels],
                        build_in_row=(jnp.arange(bcap)
                                      < src.data.num_rows))
                else:
                    mask = J.semi_mask(counts, live, anti=False)
            else:
                out_cap = next_bucket(cap * self.f.expansion)
                while True:
                    pi, bi, rv, _, etotal = J.expand_matches(
                        lo, counts, perm, out_cap)
                    if int(etotal) <= out_cap:
                        break
                    out_cap = next_bucket(int(etotal))
                pi = pi.astype(jnp.int32)
                bi = bi.astype(jnp.int32)
                pairs = tuple(
                    (c.values[pi], None if c.valid is None else c.valid[pi])
                    for c in probe.columns) + tuple(
                    (c.values[bi], None if c.valid is None else c.valid[bi])
                    for c in src.data.columns)
                rmask, rvalid = cres.run(pairs, etotal, jnp)
                ok = rv & rmask
                if rvalid is not None:
                    ok = ok & rvalid
                any_pass = jnp.zeros(cap, bool).at[pi].max(ok, mode="drop")
                mask = (live & ~any_pass if join_type == "anti"
                        else live & any_pass)
                if join_type == "anti":
                    # residual anti = correlated NOT EXISTS: null-key
                    # rows never match, keep them
                    pad = jnp.arange(cap) >= n
                    mask = mask | ((~live) & (~pad))
            idx, count = selected_positions(mask, None, n, cap)
            cols = tuple(
                Column(c.type, c.values[idx],
                       None if c.valid is None else c.valid[idx],
                       c.dictionary)
                for c in probe.columns)
            self._out.append(Batch(cols, int(count)))
            return
        out_cap = next_bucket(cap * self.f.expansion)
        while True:
            if join_type == "left":
                pi, bi, rv, unmatched, total = J.expand_matches_outer(
                    lo, counts, jnp.arange(cap) < n, perm, out_cap)
            else:
                pi, bi, rv, unmatched, total = J.expand_matches(
                    lo, counts, perm, out_cap)
            if int(total) <= out_cap:
                break
            out_cap = next_bucket(int(total))
        cols = []
        for c in probe.columns:
            cols.append(Column(c.type, c.values[pi],
                               None if c.valid is None else c.valid[pi],
                               c.dictionary))
        ones = jnp.ones(out_cap, bool)
        for c in src.data.columns:
            bvalid = ones if c.valid is None else c.valid[bi]
            cols.append(Column(c.type, c.values[bi], bvalid & ~unmatched,
                               c.dictionary))
        self._out.append(Batch(tuple(cols), int(total)))

    # -- protocol --------------------------------------------------------
    def get_output(self) -> Optional[Batch]:
        if self._out:
            return self._out.pop(0)
        return None

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        src = self.f.build.lookup.get()
        if src.mode == "spilled":
            self._join_spilled_partitions(src)
            return
        if self._pending:
            self._probe_canonical()

    def _join_spilled_partitions(self, src: "SpilledLookupSource") -> None:
        """Grace hash join: per hash partition, rebuild a resident lookup
        source from the spilled build rows and replay the probe rows
        through a fresh build/probe operator pair (the reference's
        unspill-and-join path; partitions are disjoint in keys so inner/
        left/semi/anti all compose per partition)."""
        probe_spiller = getattr(self, "_probe_spiller", None)
        for p in range(src.n_partitions):
            build_batches = list(src.spiller.partition(p))
            probe_batches = (list(probe_spiller.partition(p))
                             if probe_spiller is not None else [])
            if not probe_batches:
                continue
            if not build_batches and self.f.join_type == "inner":
                continue
            sub_build_f = HashBuildOperatorFactory(
                self.f.build.key_channels, self.f.build.input_types,
                allow_spill=False)
            bctx = OperatorContext(self.ctx.task,
                                   f"{self.ctx.name}.p{p}.build")
            bop = sub_build_f.create(bctx)
            for b in build_batches:
                bop.add_input(b)
            bop.finish()
            sub_probe_f = LookupJoinOperatorFactory(
                sub_build_f, self.f.probe_key_channels, self.f.probe_types,
                self.f.join_type, self.f.expansion, self.f.residual)
            pctx = OperatorContext(self.ctx.task,
                                   f"{self.ctx.name}.p{p}.probe")
            pop = sub_probe_f.create(pctx)
            for b in probe_batches:
                pop.add_input(b)
                while (out := pop.get_output()) is not None:
                    self._out.append(out)
            pop.finish()
            while (out := pop.get_output()) is not None:
                self._out.append(out)
            bop.close()
            pop.close()
        src.spiller.close()
        if probe_spiller is not None:
            probe_spiller.close()

    def is_finished(self) -> bool:
        return self._finishing and not self._out and not self._pending


class _RebuiltBatch:
    """Adapter presenting (values, valid) pairs as Batch-ish columns for
    _probe_ids inside a jit trace."""

    def __init__(self, pairs):
        self.capacity = pairs[0][0].shape[0]
        self.columns = [_Col(v, valid) for v, valid in pairs]


class _Col:
    __slots__ = ("values", "valid")

    def __init__(self, values, valid):
        self.values = values
        self.valid = valid


class LookupJoinOperatorFactory(OperatorFactory):
    def __init__(self, build: HashBuildOperatorFactory,
                 probe_key_channels: Sequence[int],
                 probe_types: Sequence[T.Type],
                 join_type: str = "inner", expansion: int = 2,
                 residual=None, null_aware: bool = False):
        assert join_type in ("inner", "left", "semi", "anti")
        if residual is not None and join_type not in ("semi", "anti"):
            # inner-join residuals become post-join filters in the
            # optimizer; outer-join residuals are pushed into the build
            # input (planner) — only semi/anti need in-kernel residuals
            raise NotImplementedError(
                "residual filters only on semi/anti joins")
        self.build = build
        self.probe_key_channels = list(probe_key_channels)
        self.probe_types = list(probe_types)
        self.join_type = join_type
        self.expansion = expansion
        self.residual = residual
        self.null_aware = null_aware

    def create(self, ctx: OperatorContext) -> LookupJoinOperator:
        return LookupJoinOperator(ctx, self)
