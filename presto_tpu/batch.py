"""Columnar batches: the device-native Page.

The reference's unit of data flow is the ``Page`` — a horizontal batch of
immutable columnar ``Block``s (presto-spi/.../Page.java:34,
presto-spi/.../block/Block.java:25).  The TPU-native equivalent is
``Batch``: a struct of device arrays, one ``Column`` per channel, where

- fixed-width blocks (LongArrayBlock, IntArrayBlock, ...) become value
  arrays of the type's dtype,
- null flags become an optional packed validity mask (None == no nulls,
  matching ``Block.mayHaveNull``),
- VariableWidthBlock (strings) becomes dictionary codes + a host-side
  dictionary (strings never live in HBM; see types.VarcharType),
- DictionaryBlock / RunLengthEncodedBlock compression is subsumed by the
  dictionary representation plus XLA gather fusion,
- ``Page.getPositions`` (selection vectors) becomes device gather.

Batches are immutable: every transformation returns a new ``Batch`` sharing
untouched arrays (the reference relies on the same immutability for its
concurrency discipline, SURVEY §5.2).

Arrays may be padded beyond ``num_rows`` so that device kernels see a small
set of static shapes (XLA recompiles per shape; the padding bucket policy
lives in ``pad_rows``).  Logical rows always occupy positions
``[0, num_rows)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from presto_tpu import types as T
from presto_tpu.spans import activity

Array = Any  # np.ndarray | jax.Array


def _on_device(array: Array) -> bool:
    """A jax.Array, whose conversion to numpy waits for the device."""
    return hasattr(array, "block_until_ready")

_UNSET = object()  # sentinel: "keep existing validity" in Column.with_values


def next_bucket(n: int, minimum: int = 1024) -> int:
    """Smallest power-of-two >= max(n, minimum): the shape-bucket policy."""
    cap = max(int(n), int(minimum), 1)
    return 1 << (cap - 1).bit_length()


def padded_table(table: np.ndarray) -> np.ndarray:
    """A host lookup table (one entry a dictionary code) zero-padded to a
    power-of-two length: the program that gathers through it is compiled
    for that length, and a dictionary that grows must not make a program
    a length."""
    return np.pad(table, (0, next_bucket(len(table), 8) - len(table)))


import itertools as _itertools

# process-unique monotonic dictionary identities: kernel caches key
# compiled programs by the dictionary BINDING, and keying on id() is
# unsound — a GC'd dictionary's address can be reused by a new one,
# silently hitting a kernel compiled against the old dictionary's codes.
# next() on an itertools.count is atomic under the GIL.
_DICT_TOKENS = _itertools.count(1)


class Dictionary:
    """A host-side value dictionary for string-ish columns.

    Append-only interning table: code -> value and value -> code.  Shared by
    reference between columns; never mutated through a Column (codes remain
    stable), so sharing is safe.  ``token`` is a process-unique monotonic
    identity for cache keying (never reused, unlike id()).
    """

    __slots__ = ("values", "token", "_index", "_lock", "_content_key")

    def __init__(self, values: Sequence[str] = ()):  # noqa: D401
        import threading

        self.values: List[str] = list(values)
        self.token: int = next(_DICT_TOKENS)
        self._index = {v: i for i, v in enumerate(self.values)}
        # concurrent feed drivers (LocalExchange tier) may intern into a
        # shared dictionary; appends must stay code-stable
        self._lock = threading.Lock()
        # (length, fp128) cache for content_key(); recomputed on growth
        self._content_key = None

    def __len__(self) -> int:
        return len(self.values)

    def content_key(self) -> tuple:
        """A 128-bit fingerprint of the entry list (values AND order).

        Kernel caches key compiled programs on dictionary bindings;
        keying by ``token`` (object identity) churns one recompile per
        query wherever a dictionary is rebuilt per execution with
        identical content — deserialized exchange pages, per-query
        concat-merged build sides.  Equal content (same entries, same
        order) implies identical code semantics, so equal fingerprints
        may share programs.  Cached per length (append-only growth
        invalidates); two independent xxh64 seeds make silent 64-bit
        collisions a non-concern.
        """
        n = len(self.values)
        ck = self._content_key
        if ck is not None and ck[0] == n:
            return ck[1]
        from presto_tpu import native

        blob = "\x00".join(self.values[:n]).encode("utf-8",
                                                   "surrogatepass")
        fp = (n, native.xxh64(blob, 0), native.xxh64(blob, 0x9E3779B9))
        self._content_key = (n, fp)
        return fp

    def code_of(self, value: str) -> Optional[int]:
        return self._index.get(value)

    def intern(self, value: str) -> int:
        code = self._index.get(value)
        if code is None:
            with self._lock:
                code = self._index.get(value)
                if code is None:
                    code = len(self.values)
                    self.values.append(value)
                    self._index[value] = code
        return code

    def intern_many(self, values: Iterable[str]) -> np.ndarray:
        return np.fromiter((self.intern(v) for v in values), dtype=np.int32)

    def decode(self, codes: np.ndarray) -> List[str]:
        vals = self.values
        return [vals[c] for c in np.asarray(codes)]

    def sort_ranks(self) -> np.ndarray:
        """rank[code] = lexicographic rank; used to ORDER BY a dictionary
        column on device without materializing strings."""
        order = np.argsort(np.asarray(self.values, dtype=object), kind="stable")
        ranks = np.empty(len(self.values), dtype=np.int32)
        ranks[order] = np.arange(len(self.values), dtype=np.int32)
        return ranks

    def remap_into(self, target: "Dictionary") -> np.ndarray:
        """Return old-code -> target-code mapping, interning as needed."""
        return np.fromiter(
            (target.intern(v) for v in self.values), dtype=np.int32,
            count=len(self.values),
        )


@dataclasses.dataclass(frozen=True)
class Column:
    """One channel of a Batch: values + optional validity (+ dictionary).

    Nested columns (ARRAY/MAP/ROW, the reference's ArrayBlock/MapBlock/
    RowBlock) carry flattened ``children``: for ARRAY, ``values`` holds
    per-row element counts (int32 lengths; offsets are their cumsum) and
    ``children=(elements,)``; for MAP the same with ``children=(keys,
    values)``; for ROW ``values`` is a placeholder and children are
    row-aligned field columns.  Lengths-not-offsets keeps every flat-column
    invariant (shape [n], gather-based take, zero-padding) intact.
    """

    type: T.Type
    values: Array
    valid: Optional[Array] = None  # bool array; None == all valid
    dictionary: Optional[Dictionary] = None
    children: Tuple["Column", ...] = ()

    def __post_init__(self):
        if self.type.is_dictionary and self.dictionary is None:
            raise ValueError(f"{self.type} column requires a dictionary")
        if self.type.is_nested and not self.children:
            raise ValueError(f"{self.type} column requires children")

    @property
    def may_have_nulls(self) -> bool:
        return self.valid is not None

    @property
    def has_offsets(self) -> bool:
        """ARRAY/MAP: values are element counts into flattened children."""
        return isinstance(self.type, (T.ArrayType, T.MapType))

    def offsets(self) -> np.ndarray:
        lengths = np.asarray(self.values)
        return np.concatenate([np.zeros(1, np.int64),
                               np.cumsum(lengths, dtype=np.int64)])

    def with_values(self, values: Array, valid: Optional[Array] = _UNSET) -> "Column":
        return Column(self.type, values,
                      self.valid if valid is _UNSET else valid,
                      self.dictionary, self.children)

    def take(self, indices: Array) -> "Column":
        if self.has_offsets:
            indices = np.asarray(indices)
            lengths = np.asarray(self.values)
            offsets = self.offsets()
            new_lengths = lengths[indices]
            child_idx = _range_gather_indices(offsets[indices], new_lengths)
            kids = tuple(c.take(child_idx) for c in self.children)
            valid = None if self.valid is None \
                else np.asarray(self.valid)[indices]
            return Column(self.type, new_lengths.astype(np.int32), valid,
                          None, kids)
        if isinstance(self.type, T.RowType):
            indices = np.asarray(indices)
            kids = tuple(c.take(indices) for c in self.children)
            valid = None if self.valid is None \
                else np.asarray(self.valid)[indices]
            return Column(self.type, np.asarray(self.values)[indices],
                          valid, None, kids)
        xp = _xp(self.values)
        values = xp.take(self.values, indices, axis=0)
        valid = None if self.valid is None else xp.take(self.valid, indices, axis=0)
        return Column(self.type, values, valid, self.dictionary)

    def head(self, n: int) -> "Column":
        """First n rows (child columns truncated to match)."""
        if self.has_offsets:
            lengths = np.asarray(self.values)[:n]
            total = int(lengths.sum())
            kids = tuple(c.head(total) for c in self.children)
            valid = None if self.valid is None \
                else np.asarray(self.valid)[:n]
            return Column(self.type, lengths, valid, None, kids)
        kids = tuple(c.head(n) for c in self.children)
        return Column(self.type, self.values[:n],
                      None if self.valid is None else self.valid[:n],
                      self.dictionary, kids)

    def pad(self, capacity: int) -> "Column":
        """Pad to ``capacity`` rows (zero fill => empty arrays, invalid)."""
        n = int(self.values.shape[0])
        if n >= capacity:
            return self
        extra = capacity - n
        if self.has_offsets:
            lengths = np.concatenate(
                [np.asarray(self.values), np.zeros(extra, np.int32)])
            valid = self.valid
            if valid is not None:
                valid = np.concatenate([np.asarray(valid),
                                        np.zeros(extra, bool)])
            return Column(self.type, lengths, valid, None, self.children)
        xp = _xp(self.values)
        values = xp.concatenate(
            [self.values,
             xp.zeros((extra,) + self.values.shape[1:], self.values.dtype)])
        valid = self.valid
        if valid is not None:
            valid = xp.concatenate([valid, xp.zeros((extra,), bool)])
        kids = tuple(c.pad(capacity) for c in self.children)
        return Column(self.type, values, valid, self.dictionary, kids)

    def on_device(self) -> bool:
        return (_on_device(self.values) or _on_device(self.valid)
                or any(c.on_device() for c in self.children))

    def to_numpy(self) -> "Column":
        if self.on_device():
            with activity("device_wait"):   # blocks until it is computed
                return self._to_numpy()
        return self._to_numpy()

    def _to_numpy(self) -> "Column":
        valid = None if self.valid is None else np.asarray(self.valid)
        kids = tuple(c._to_numpy() for c in self.children)
        return Column(self.type, np.asarray(self.values), valid,
                      self.dictionary, kids)

    def _arrays(self):
        """The column's arrays and its children's, as one pytree."""
        return (self.values, self.valid,
                tuple(c._arrays() for c in self.children))

    def _with_arrays(self, arrays) -> "Column":
        values, valid, kids = arrays
        return Column(self.type, values, valid, self.dictionary,
                      tuple(c._with_arrays(k)
                            for c, k in zip(self.children, kids)))

    def to_pylist(self, num_rows: int) -> List[Any]:
        col = self.to_numpy()
        vals = col.values[:num_rows]
        valid = None if col.valid is None else col.valid[:num_rows]
        if self.has_offsets:
            offsets = col.offsets()
            total = int(offsets[num_rows])
            kid_lists = [c.to_pylist(total) for c in col.children]
            out: List[Any] = []
            for i in range(num_rows):
                lo, hi = int(offsets[i]), int(offsets[i + 1])
                if isinstance(self.type, T.MapType):
                    out.append(dict(zip(kid_lists[0][lo:hi],
                                        kid_lists[1][lo:hi])))
                else:
                    out.append(kid_lists[0][lo:hi])
        elif isinstance(self.type, T.RowType):
            kid_lists = [c.to_pylist(num_rows) for c in col.children]
            out = [tuple(k[i] for k in kid_lists) for i in range(num_rows)]
        elif self.type.is_dictionary:
            out = [
                self.dictionary.values[int(c)] if 0 <= int(c) < len(self.dictionary)
                else None
                for c in vals
            ]
        else:
            out = [self.type.to_python(v) for v in vals]
        if valid is not None:
            out = [v if ok else None for v, ok in zip(out, valid)]
        return out


def _range_gather_indices(starts: np.ndarray,
                          lengths: np.ndarray) -> np.ndarray:
    """Concatenate [starts[i], starts[i]+lengths[i]) ranges, vectorized."""
    lengths = np.asarray(lengths, np.int64)
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    ends = np.cumsum(lengths)
    begins = ends - lengths
    ramp = np.arange(total, dtype=np.int64) - np.repeat(begins, lengths)
    return np.repeat(np.asarray(starts, np.int64), lengths) + ramp


@dataclasses.dataclass(frozen=True)
class Batch:
    """A horizontal slice of columnar data (the Page equivalent)."""

    columns: Tuple[Column, ...]
    num_rows: int

    def __post_init__(self):
        for c in self.columns:
            if c.values.shape[0] < self.num_rows:
                raise ValueError(
                    f"column has {c.values.shape[0]} rows < num_rows={self.num_rows}")

    # -- structural ------------------------------------------------------
    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return int(self.columns[0].values.shape[0]) if self.columns else self.num_rows

    def column(self, i: int) -> Column:
        return self.columns[i]

    def select_channels(self, channels: Sequence[int]) -> "Batch":
        """Page.getColumns analogue (zero copy)."""
        return Batch(tuple(self.columns[i] for i in channels), self.num_rows)

    def append_column(self, col: Column) -> "Batch":
        return Batch(self.columns + (col,), self.num_rows)

    # -- data movement ---------------------------------------------------
    def take(self, indices: Array) -> "Batch":
        """Page.getPositions analogue: gather rows (device-friendly)."""
        n = int(indices.shape[0])
        return Batch(tuple(c.take(indices) for c in self.columns), n)

    def head(self, n: int) -> "Batch":
        n = min(n, self.num_rows)
        return Batch(tuple(c.head(n) for c in self.columns), n)

    def pad_rows(self, capacity: int) -> "Batch":
        """Pad every column to ``capacity`` rows (zero fill, invalid)."""
        if self.capacity >= capacity:
            return self
        return Batch(tuple(c.pad(capacity) for c in self.columns),
                     self.num_rows)

    def compact(self) -> "Batch":
        """Drop padding (host copy if padded)."""
        if self.capacity == self.num_rows:
            return self
        return self.head(self.num_rows)

    def to_numpy(self) -> "Batch":
        if not any(c.on_device() for c in self.columns):
            return Batch(tuple(c._to_numpy() for c in self.columns),
                         self.num_rows)
        import jax

        # one read and one device_wait for the batch: device_get starts
        # every array's copy before it waits for the first
        with activity("device_wait"):
            arrays = jax.device_get([c._arrays() for c in self.columns])
        return Batch(tuple(c._with_arrays(a)
                           for c, a in zip(self.columns, arrays)),
                     self.num_rows)

    def to_device(self) -> "Batch":
        import jax

        with activity("stage_h2d"):
            # one put for the batch, not one an array; nested columns stay
            # host-side (offsets bookkeeping): device compute operates on
            # their flattened children
            put = iter(jax.device_put([(c.values, c.valid)
                                       for c in self.columns
                                       if not c.children]))
            cols = tuple(
                c.to_numpy() if c.children
                else Column(c.type, *next(put), c.dictionary)
                for c in self.columns)
        return Batch(cols, self.num_rows)

    # -- interop ---------------------------------------------------------
    def to_pylist(self) -> List[Tuple[Any, ...]]:
        cols = [c.to_pylist(self.num_rows) for c in self.columns]
        return list(zip(*cols)) if cols else [() for _ in range(self.num_rows)]

    @property
    def size_bytes(self) -> int:
        def col_bytes(c: Column) -> int:
            total = int(np.prod(c.values.shape)) * c.values.dtype.itemsize
            if c.valid is not None:
                total += int(np.prod(c.valid.shape))
            for kid in c.children:
                total += col_bytes(kid)
            return total

        return sum(col_bytes(c) for c in self.columns)

    def __repr__(self) -> str:  # pragma: no cover
        ts = ", ".join(c.type.display() for c in self.columns)
        return f"Batch[{self.num_rows} rows; {ts}]"


def _xp(arr):
    """numpy-or-jnp dispatch for code shared by host oracle and device path."""
    if isinstance(arr, np.ndarray):
        return np
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# Builders (BlockBuilder/PageBuilder analogue, presto-spi/.../PageBuilder.java)
# ---------------------------------------------------------------------------

def column_from_pylist(typ: T.Type, values: Sequence[Any],
                       dictionary: Optional[Dictionary] = None) -> Column:
    """Build a Column from Python values (None == NULL).

    Nested values: ARRAY from lists/tuples, MAP from dicts, ROW from
    tuples (ArrayBlockBuilder/MapBlockBuilder/RowBlockBuilder analogue).
    """
    n = len(values)
    has_null = any(v is None for v in values)
    valid = None
    if has_null:
        valid = np.fromiter((v is not None for v in values), dtype=bool, count=n)
    if isinstance(typ, T.ArrayType):
        lengths = np.fromiter((0 if v is None else len(v) for v in values),
                              dtype=np.int32, count=n)
        flat = [e for v in values if v is not None for e in v]
        return Column(typ, lengths, valid, None,
                      (column_from_pylist(typ.element, flat),))
    if isinstance(typ, T.MapType):
        lengths = np.fromiter((0 if v is None else len(v) for v in values),
                              dtype=np.int32, count=n)
        keys = [k for v in values if v is not None for k in v.keys()]
        vals = [x for v in values if v is not None for x in v.values()]
        return Column(typ, lengths, valid, None,
                      (column_from_pylist(typ.key, keys),
                       column_from_pylist(typ.value, vals)))
    if isinstance(typ, T.RowType):
        kids = []
        for fi, ft in enumerate(typ.field_types):
            kids.append(column_from_pylist(
                ft, [None if v is None else v[fi] for v in values]))
        return Column(typ, np.zeros(n, np.int8), valid, None, tuple(kids))
    if typ.is_dictionary:
        dictionary = dictionary or Dictionary()
        codes = np.fromiter(
            (dictionary.intern(v) if v is not None else 0 for v in values),
            dtype=np.int32, count=n)
        return Column(typ, codes, valid, dictionary)
    storage = np.zeros(n, dtype=typ.np_dtype)
    for i, v in enumerate(values):
        if v is not None:
            storage[i] = typ.from_python(v)
    return Column(typ, storage, valid)


def batch_from_pylist(schema: Sequence[T.Type],
                      rows: Sequence[Sequence[Any]]) -> Batch:
    """RowPagesBuilder analogue (presto-main test fixture) for tests."""
    cols = []
    for i, typ in enumerate(schema):
        cols.append(column_from_pylist(typ, [r[i] for r in rows]))
    return Batch(tuple(cols), len(rows))


def _concat_columns(cols: Sequence[Column],
                    row_counts: Sequence[int]) -> Column:
    """Concatenate row-count-exact numpy columns of one channel."""
    typ = cols[0].type
    if any(c.valid is not None for c in cols):
        valid = np.concatenate([
            np.asarray(c.valid)[:n] if c.valid is not None
            else np.ones(n, bool)
            for c, n in zip(cols, row_counts)])
    else:
        valid = None
    if isinstance(typ, (T.ArrayType, T.MapType)):
        lengths = np.concatenate(
            [np.asarray(c.values)[:n] for c, n in zip(cols, row_counts)])
        kid_counts = [int(np.asarray(c.values)[:n].sum())
                      for c, n in zip(cols, row_counts)]
        kids = tuple(
            _concat_columns([c.children[ki] for c in cols], kid_counts)
            for ki in range(len(cols[0].children)))
        return Column(typ, lengths.astype(np.int32), valid, None, kids)
    if isinstance(typ, T.RowType):
        kids = tuple(
            _concat_columns([c.children[ki] for c in cols], row_counts)
            for ki in range(len(cols[0].children)))
        values = np.concatenate(
            [np.asarray(c.values)[:n] for c, n in zip(cols, row_counts)])
        return Column(typ, values, valid, None, kids)
    if typ.is_dictionary:
        target = Dictionary()
        parts = []
        for c, n in zip(cols, row_counts):
            remap = c.dictionary.remap_into(target)
            codes = np.asarray(c.values)[:n]
            parts.append(remap[codes] if len(remap) else codes)
        values = np.concatenate(parts) if parts else np.zeros(0, np.int32)
        return Column(typ, values, valid, target)
    values = np.concatenate(
        [np.asarray(c.values)[:n] for c, n in zip(cols, row_counts)])
    return Column(typ, values, valid)


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Concatenate compacted batches (dictionary columns are re-coded into a
    shared dictionary — the DictionaryBlock 'compact' analogue)."""
    # host first, then drop the padding: compact() on a device array is an
    # eager slice, one XLA program per distinct (capacity, rows) pair
    batches = [b.to_numpy().compact() for b in batches if b.num_rows > 0]
    if not batches:
        raise ValueError("concat of zero rows needs a schema; use empty_batch")
    first = batches[0]
    counts = [b.num_rows for b in batches]
    out_cols = [
        _concat_columns([b.columns[ci] for b in batches], counts)
        for ci in range(first.num_columns)]
    return Batch(tuple(out_cols), sum(counts))


def empty_column(typ: T.Type) -> Column:
    if isinstance(typ, T.ArrayType):
        return Column(typ, np.zeros(0, np.int32), None, None,
                      (empty_column(typ.element),))
    if isinstance(typ, T.MapType):
        return Column(typ, np.zeros(0, np.int32), None, None,
                      (empty_column(typ.key), empty_column(typ.value)))
    if isinstance(typ, T.RowType):
        return Column(typ, np.zeros(0, np.int8), None, None,
                      tuple(empty_column(ft) for ft in typ.field_types))
    dictionary = Dictionary() if typ.is_dictionary else None
    return Column(typ, np.zeros(0, typ.np_dtype), None, dictionary)


def empty_batch(schema: Sequence[T.Type]) -> Batch:
    return Batch(tuple(empty_column(typ) for typ in schema), 0)
