"""Device-resident scan cache: an immutable table stays on the device
after its first scan.

A connector that declares ``immutable_data`` (tpch, tpcds) promises that
a split yields the same rows every time.  The first scan of a table by a
task generates and stages as ever and *keeps the staged device batches*;
later scans of the same splits, from any query and any statement, are
handed the kept batches: no page source, no feed drivers, no
``np.concatenate``, no ``device_put``.  Table columns are kept, never a
result: every answer is still computed.

**The kept unit** is a task's scan of a table, a ``KeptRun``: all rows
of the splits the pipeline was dealt, once, as the device batches the
filling execution staged (``exec/fusion.py`` ``_flush`` for a scan a
segment adopted, ``pad_batch`` otherwise), in the order it staged them.
Key: the connector instance, the table handle, the ordered tuple of
``split.info``, ``scan_batch_rows`` and the batch grid (the segment's
``coalesce_rows``, 0 for a scan that stages its own pages), so a hit
dispatches programs of the capacities the fill dispatched.  A scan whose
columns are among a kept run's takes them from it; one that needs a
column the run lacks fills a run of its own, which replaces the runs it
covers.

**Bounded.**  One budget for the process, because the process has one
device: half of what the device reports as ``bytes_limit``, a fixed
figure where the backend reports none (the CPU's).  Least-recently-
scanned runs are evicted whole; a run that outgrows the budget while it
fills is dropped and that scan goes on as it would without a cache.  Of
two scans that want one absent run at once, one fills and the other
scans without keeping.  A run is stored only when every scan operator
of the pipeline drained its splits and every row they produced was
staged: a LIMIT that stops early, a cancelled or failed query keeps
nothing.  Entries belong to the connector instance and go with the
runner that mounted it (``drop_connectors`` at a server's close and when
a ``LocalQueryRunner`` is collected; a finalizer on the connector for one
used without a runner).

Nothing turns it on or off: ``exec/runner.py`` asks it for every scan
pipeline of a connector with ``immutable_data`` and for no other.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from presto_tpu.batch import Batch

#: the budget where the backend reports no ``bytes_limit`` (the CPU)
FALLBACK_BUDGET_BYTES = 512 << 20
#: share of the device's ``bytes_limit`` that kept runs may hold; the
#: rest is the operators' working memory
DEVICE_BUDGET_SHARE = 0.5

_OWNER_TOKENS = itertools.count(1)


@dataclasses.dataclass(frozen=True)
class KeptRun:
    """One task's scan of one table, resident: ``batches`` hold
    ``columns`` (connector column names) in that order."""

    owner: int
    key: tuple
    columns: Tuple[str, ...]
    batches: List[Batch]
    nbytes: int


class ScanHit:
    """What a hit hands to the pipeline: the kept batches cut to the
    requested columns, and the bytes of those columns."""

    def __init__(self, run: KeptRun, columns: Sequence[str]):
        channels = [run.columns.index(c) for c in columns]
        self.batches = [b.select_channels(channels) for b in run.batches]
        self.nbytes = sum(b.size_bytes for b in self.batches)


class ScanFill:
    """One pipeline's miss.  The operators that stage the scan record
    into it; ``close`` stores the run if the scan was whole.  A fill that
    is not ``keeping`` (another scan is filling the same run, or this
    one outgrew the budget) records nothing and changes nothing."""

    def __init__(self, cache: "ScanCache", owner: int, key: tuple,
                 columns: Tuple[str, ...], keeping: bool):
        self._cache = cache
        self._owner = owner
        self._key = key
        self._columns = columns
        self._holds_slot = keeping
        self.keeping = keeping
        self._lock = threading.Lock()
        self._batches: List[Batch] = []
        self._nbytes = 0
        self._rows_kept = 0
        self._rows_scanned = 0
        self._scans = 0         # scan operators opened
        self._closed = 0
        self._drained = 0

    def scan_opened(self) -> bool:
        """A scan operator of the pipeline was created.  True for the
        first: feed drivers are several operators and one scan of the
        table, and the first counts its miss."""
        with self._lock:
            self._scans += 1
            return self._scans == 1

    def stage(self, batch: Batch) -> Batch:
        """A flushed host batch, put on the device and recorded; handed
        back as it came where this fill keeps nothing."""
        if not self.keeping:
            return batch
        staged = batch.to_device()
        self.add(staged)
        return staged

    def add(self, batch: Batch) -> None:
        """Record one staged device batch."""
        if any(c.children for c in batch.columns):
            self._give_up()     # nested columns stay on the host
            return
        with self._lock:
            if not self.keeping:
                return
            self._batches.append(batch)
            self._rows_kept += batch.num_rows
            self._nbytes += batch.size_bytes
            fits = self._nbytes <= self._cache.budget()
        if not fits:
            self._give_up()

    def _give_up(self) -> None:
        with self._lock:
            self.keeping = False
            self._batches = []

    def scan_closed(self, rows: int, drained: bool) -> None:
        """A scan operator of the pipeline closed, having produced
        ``rows`` rows and (``drained``) every row of its splits."""
        with self._lock:
            self._closed += 1
            self._drained += bool(drained)
            self._rows_scanned += rows

    def close(self, ok: bool) -> None:
        """The pipeline ended (``ok``: without an error)."""
        with self._lock:
            whole = (ok and self.keeping and self._scans > 0
                     and self._drained == self._closed == self._scans
                     and self._rows_kept == self._rows_scanned)
            run = KeptRun(self._owner, self._key, self._columns,
                          self._batches, self._nbytes) if whole else None
            self.keeping = False
            self._batches = []
        self._cache._filled(self._owner, self._key, run, self._holds_slot)
        self._holds_slot = False


class ScanCache:
    def __init__(self, budget_bytes: Optional[int] = None):
        #: None until first needed, then what the device allows
        self.budget_bytes = budget_bytes
        self._lock = threading.Lock()
        self._runs: "OrderedDict[int, KeptRun]" = OrderedDict()  # LRU first
        self._run_ids = itertools.count(1)
        self._filling: set = set()
        self._counters = {"hits": 0, "misses": 0, "evictions": 0,
                          "hit_bytes": 0}

    def budget(self) -> int:
        if self.budget_bytes is None:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            limit = stats.get("bytes_limit")
            self.budget_bytes = (int(limit * DEVICE_BUDGET_SHARE) if limit
                                 else FALLBACK_BUDGET_BYTES)
        return self.budget_bytes

    # -- owners ----------------------------------------------------------
    def _owner(self, connector) -> int:
        token = getattr(connector, "_scan_cache_owner", None)
        if token is None:
            token = next(_OWNER_TOKENS)
            connector._scan_cache_owner = token
            weakref.finalize(connector, self._drop, token)
        return token

    def drop_owner(self, connector) -> None:
        """Free every run kept for ``connector`` (its runner closed)."""
        token = getattr(connector, "_scan_cache_owner", None)
        if token is not None:
            self._drop(token)

    def _drop(self, owner: int) -> None:
        with self._lock:
            for rid in [rid for rid, run in self._runs.items()
                        if run.owner == owner]:
                del self._runs[rid]

    # -- the protocol of one scan ------------------------------------------
    def open(self, connector, key: tuple, columns: Sequence[str]):
        """A ``ScanHit`` where a kept run holds ``columns`` for ``key``,
        else the ``ScanFill`` the scan records into."""
        owner = self._owner(connector)
        columns = tuple(columns)
        with self._lock:
            for rid, run in self._runs.items():
                if (run.owner == owner and run.key == key
                        and set(columns) <= set(run.columns)):
                    self._runs.move_to_end(rid)
                    hit = ScanHit(run, columns)
                    self._counters["hits"] += 1
                    self._counters["hit_bytes"] += hit.nbytes
                    return hit
            self._counters["misses"] += 1
            keeping = (owner, key) not in self._filling
            if keeping:
                self._filling.add((owner, key))
        return ScanFill(self, owner, key, columns, keeping)

    def _filled(self, owner: int, key: tuple, run: Optional[KeptRun],
                held_slot: bool) -> None:
        with self._lock:
            if held_slot:
                self._filling.discard((owner, key))
            budget = self.budget()
            if run is None or run.nbytes > budget:
                return
            for rid in [rid for rid, old in self._runs.items()
                        if old.owner == owner and old.key == key
                        and set(old.columns) <= set(run.columns)]:
                del self._runs[rid]     # the new run holds all of it
            while self._runs and self._resident() + run.nbytes > budget:
                self._runs.popitem(last=False)
                self._counters["evictions"] += 1
            self._runs[next(self._run_ids)] = run

    def _resident(self) -> int:
        return sum(run.nbytes for run in self._runs.values())

    # -- accounts ----------------------------------------------------------
    def stats(self, connectors=None) -> Dict[str, int]:
        """Resident bytes and entries (of ``connectors`` where given,
        else of the process) and the process's counters."""
        with self._lock:
            runs = list(self._runs.values())
            out = dict(self._counters)
        if connectors is not None:
            owners = {getattr(c, "_scan_cache_owner", None)
                      for c in connectors}
            runs = [run for run in runs if run.owner in owners]
        out["entries"] = len(runs)
        out["resident_bytes"] = sum(run.nbytes for run in runs)
        return out


#: the process's cache: one device, one budget
SCAN_CACHE = ScanCache()


def drop_connectors(registry) -> None:
    """Free what ``SCAN_CACHE`` keeps for the connectors of ``registry``:
    the node or runner that mounted them is gone."""
    for connector in registry.connectors():
        SCAN_CACHE.drop_owner(connector)
