"""The generated columns the plain references read, as numpy arrays.

The data is the connector's (``presto_tpu.connectors.tpch``: fixed hash
streams, no seed, as dbgen's data is fixed by its own seeds); the
computation over it, in ``references/``, shares nothing with the engine.
"""

from __future__ import annotations

import numpy as np

EPOCH = np.datetime64("1970-01-01")


def days(iso_date: str) -> int:
    """Days since 1970-01-01, the connector's DATE encoding."""
    return int((np.datetime64(iso_date) - EPOCH).astype(int))


def iso(day) -> str:
    return str(EPOCH + np.timedelta64(int(day), "D"))


def host_columns(connector: str, scale: float, wanted: dict) -> tuple:
    """``wanted`` is {table: [column, ...]}.  Returns ({column: array},
    {column: bytes as generated}); dictionary columns come back decoded to
    their strings and are counted at the width of their codes."""
    if connector != "tpch":
        raise ValueError(f"no column source for connector {connector!r}")
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=scale)
    out, nbytes = {}, {}
    for table, cols in sorted(wanted.items()):
        cols = sorted(cols)
        handle = conn.get_table(table)
        parts = {c: [] for c in cols}
        dicts = {}
        for split in conn.get_splits(handle, 1):
            for batch in conn.page_source(split, cols, 1 << 20):
                for c, col in zip(cols, batch.columns):
                    parts[c].append(np.asarray(col.values)[:batch.num_rows])
                    if col.dictionary is not None:
                        dicts[c] = np.asarray(
                            [str(v) for v in col.dictionary.values])
        for c in cols:
            arr = np.concatenate(parts[c])
            nbytes[c] = int(arr.nbytes)
            out[c] = dicts[c][arr] if c in dicts else arr
    return out, nbytes


def must_read_bytes(columns: dict, nbytes: dict) -> int:
    """The least a statement has to read: every column it names, once, at
    the width the connector generates it (``columns`` is the reference's
    {table: [column, ...]})."""
    return sum(nbytes[c] for cols in columns.values() for c in cols)
