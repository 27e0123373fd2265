"""Execution contexts: memory accounting + operator statistics.

Mirrors the reference's context tree — QueryContext -> TaskContext ->
PipelineContext -> DriverContext -> OperatorContext
(presto-main/.../memory/QueryContext.java, operator/OperatorContext.java) —
and its hierarchical memory contexts (presto-memory-context, SURVEY §2.2):
reservations roll up to the query root, which enforces a limit.

Stats mirror OperatorStats -> ...  -> QueryStats rollups (SURVEY §5.1): the
Driver records per-operator wall time and row/batch counts around every
get_output/add_input call, which is what EXPLAIN ANALYZE renders.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.spans import HostActivity


class MemoryReservationError(RuntimeError):
    pass


class MemoryContext:
    """One node in the reservation tree (LocalMemoryContext analogue).

    A ROOT context may additionally charge its deltas into a per-node
    ``MemoryPool`` (server/memorypool.py): growth charges the pool
    BEFORE the tree applies (a full pool blocks the calling driver, and
    a failed charge leaves the tree untouched), shrink frees the pool
    after.  Cross-query frees arrive from other trees, so a driver
    blocked here — holding this tree's lock — is still unblockable.
    """

    def __init__(self, parent: Optional["MemoryContext"], name: str,
                 limit: Optional[int] = None, pool=None,
                 pool_query_id: str = "query"):
        self.parent = parent
        self.name = name
        self.limit = limit
        self.reserved = 0
        self.peak = 0
        self._tree_lock = (parent._tree_lock if parent is not None
                           else threading.Lock())
        if parent is None:
            self.pool = pool
            self.pool_query_id = pool_query_id
            self._pool_charged = 0

    def reserve(self, bytes_: int) -> None:
        self.set_bytes(self.reserved + bytes_)

    def set_bytes(self, bytes_: int) -> None:
        # one lock per reservation TREE (root-owned): concurrent feed
        # drivers of one task serialize, unrelated queries do not
        with self._tree_lock:
            self._set_bytes_locked(bytes_)

    def root(self) -> "MemoryContext":
        node = self
        while node.parent is not None:
            node = node.parent
        return node

    def _set_bytes_locked(self, bytes_: int) -> None:
        delta = bytes_ - self.reserved
        node = self
        root = node
        while node is not None:
            new = node.reserved + delta
            if delta > 0 and node.limit is not None and new > node.limit:
                raise MemoryReservationError(
                    f"memory limit exceeded at {node.name}: "
                    f"{new} > {node.limit}")
            root = node
            node = node.parent
        pool = root.pool
        if pool is not None and delta > 0:
            pool.reserve(root.pool_query_id, delta)
            root._pool_charged += delta
        node = self
        while node is not None:
            node.reserved += delta
            node.peak = max(node.peak, node.reserved)
            node = node.parent
        if pool is not None and delta < 0:
            freed = min(-delta, root._pool_charged)
            if freed > 0:
                root._pool_charged -= freed
                pool.free(root.pool_query_id, freed)

    def release_pool(self) -> None:
        """Detach from the pool, returning any remaining charge: the
        end-of-task backstop for reservations a failure path never freed
        (a leak in a SHARED pool would block other queries forever)."""
        with self._tree_lock:
            root = self.root()
            pool = root.pool
            if pool is not None and root._pool_charged > 0:
                pool.free(root.pool_query_id, root._pool_charged)
                root._pool_charged = 0
            root.pool = None

    def free(self) -> None:
        self.set_bytes(0)


@dataclasses.dataclass
class OperatorStats:
    operator: str = ""
    input_batches: int = 0
    input_rows: int = 0
    output_batches: int = 0
    output_rows: int = 0
    wall_ns: int = 0
    finish_wall_ns: int = 0
    # row-pipeline-tier device program accounting (FilterProject,
    # DynamicFilter, FusedSegment): one dispatch per jitted-program
    # launch.  Tests assert pipeline fusion's launch-count reduction on
    # it instead of eyeballing traces.
    jit_dispatches: int = 0
    # kernel-cache MISSES of those three operator families, not XLA
    # programs built: a miss can build one program or, through eager
    # helpers, dozens, and every other family builds without counting
    # here.  Superseded by TaskStats.xla_builds; kept because warm-run
    # checks pin it at 0.
    jit_compiles: int = 0
    # wall nanoseconds of those misses: expression compile plus the
    # WHOLE first call of the fresh kernel (trace, lower, compile or
    # load, and the run itself; kernelcache.timed_first_call).
    # Superseded by TaskStats.xla_build_ns + xla_trace_lower_ns.
    jit_compile_ns: int = 0
    # rows folded into in-segment partial-aggregation pre-reduce
    # (exec/fusion.py Fusion II): nonzero proves the scan->agg pipeline
    # emitted partial states, not row batches — tests pin on this
    # instead of eyeballing operator chains.
    prereduce_rows: int = 0
    # bounded pre-reduce (exec/fusion.py): dispatched batches whose
    # partial states stayed on the device, and hand-overs of held
    # partials to the consumer.  A task under partial_agg_max_bytes
    # flushes once a segment; both 0 for the sort path and raw emission,
    # which emit a batch a dispatch
    prereduce_batches_held: int = 0
    prereduce_flushes: int = 0
    # a fused segment's dispatches by how the program that ran ends
    # (exec/fusion.py _compile, decided at trace time): it compacted its
    # live rows to the front through ops/filter.py, or it ended with a
    # row mask and moved nothing, because the live rows were a prefix
    # already (an inner probe with no filter after it) or because the
    # partitioned sink cuts them by id on the host.  Neither for a
    # program with no mask at its end or one that pre-reduces
    compactions: int = 0
    compactions_skipped: int = 0
    # which kernel tier served this operator's group-by/join hot loop.
    # Group-by: "direct" (bounded-domain) or "sort"; a streaming
    # aggregation (clustered keys) reports none.
    # Join build and probe (absorbed or stand-alone): "dense"
    # (direct-address index, ops/join.py), "sorted", "hash"; a segment
    # that absorbed probes of two tiers reads "dense+hash".  Surfaced by
    # the span tree (kernelTier), tools/query_profile.py and EXPLAIN
    # ANALYZE's "kernel tiers" line
    kernel_tier: str = ""
    # the device-resident scan cache (exec/scancache.py), on the scan
    # operator of a connector with ``immutable_data``: 1 hit where the
    # pipeline's scan of its table was handed kept device batches (and
    # the bytes of the columns handed over), 1 miss where it generated
    # and staged (feed drivers are one scan: the first counts it).
    # Both 0 for a table that can change: the cache never saw the scan
    scan_cache_hits: int = 0
    scan_cache_misses: int = 0
    scan_cache_hit_bytes: int = 0

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DriverStats:
    """One driver run (one instantiated pipeline) — the DriverStats
    rollup level between OperatorStats and TaskStats (SURVEY §5.1)."""

    pipeline: str = ""
    operators: int = 0
    input_rows: int = 0
    output_rows: int = 0
    wall_ns: int = 0

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TaskStats:
    """Task-level rollup of every operator the task ran, plus the
    memory/exchange/buffer counters the worker owns.  This is the shape
    serialized into the ``/v1/task/{id}`` info payload (``taskStats``)
    and aggregated into StageStats by the coordinator."""

    task_id: str = ""
    state: str = ""
    # wall-clock span (epoch seconds) of the task's execution — the
    # span-timeline surface tools/query_profile.py renders
    start_time: float = 0.0
    end_time: float = 0.0
    elapsed_s: float = 0.0
    # sums over operator stats
    wall_ns: int = 0
    input_rows: int = 0
    input_batches: int = 0
    output_rows: int = 0
    output_batches: int = 0
    jit_dispatches: int = 0
    jit_compiles: int = 0
    jit_compile_ns: int = 0
    prereduce_rows: int = 0
    prereduce_batches_held: int = 0
    prereduce_flushes: int = 0
    compactions: int = 0
    compactions_skipped: int = 0
    # the scan cache's account (OperatorStats.scan_cache_*), summed
    scan_cache_hits: int = 0
    scan_cache_misses: int = 0
    scan_cache_hit_bytes: int = 0
    peak_memory_bytes: int = 0
    # attempt-aware exchange dedup counters (sums across this task's
    # remote sources) + producer-side page accounting
    exchange_fetched: int = 0
    exchange_consumed: int = 0
    exchange_purged: int = 0
    pages_enqueued: int = 0
    # cumulative wire bytes this task's output buffers enqueued — the
    # processedBytes surface of the live progress protocol
    output_bytes: int = 0
    # spooled exchange (server/spool.py): pages written through to the
    # spool, and pages/bytes evicted from the in-memory buffer under
    # max_buffer_bytes pressure (re-servable from the spool)
    pages_spooled: int = 0
    pages_evicted: int = 0
    bytes_evicted: int = 0
    # device-sharded exchange tier: bytes this shard received through
    # in-program collectives (all_to_all / all_gather / gather) at the
    # fragment boundaries it produced — read back as program outputs
    # (parallel/sqlmesh.py per-shard stats) and folded into synthetic
    # per-shard TaskStats; HTTP-plane tasks report 0
    device_exchange_bytes: int = 0
    # what the task's host threads did, nanoseconds per kind of
    # spans.ACTIVITY_KINDS (thread-seconds: feed drivers add up)
    host_ns: Dict[str, int] = dataclasses.field(default_factory=dict)
    # what XLA built on this task's threads (jax.monitoring, the
    # listener in kernelcache.py): programs compiled OR loaded from the
    # persistent cache, the seconds inside that, the seconds tracing
    # and lowering before it, and how many of the builds were loads
    xla_builds: int = 0
    xla_build_ns: int = 0
    xla_trace_lower_ns: int = 0
    xla_cache_hits: int = 0

    def take_activity(self, recorder: HostActivity) -> None:
        """The recorder's totals as this task's host and XLA accounts."""
        self.host_ns = dict(recorder.total_ns)
        xla = recorder.xla
        self.xla_builds = xla["builds"]
        self.xla_build_ns = xla["build_ns"]
        self.xla_trace_lower_ns = xla["trace_lower_ns"]
        self.xla_cache_hits = xla["cache_hits"]

    def add_operator(self, s: OperatorStats) -> None:
        self.wall_ns += s.wall_ns + s.finish_wall_ns
        self.input_rows += s.input_rows
        self.input_batches += s.input_batches
        self.output_rows += s.output_rows
        self.output_batches += s.output_batches
        self.jit_dispatches += s.jit_dispatches
        self.jit_compiles += s.jit_compiles
        self.jit_compile_ns += s.jit_compile_ns
        _add_prereduce(self, s)
        _add_scan_cache(self, s)

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "TaskStats":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in (d or {}).items() if k in known})


@dataclasses.dataclass
class StageStats:
    """Per-fragment aggregation across that stage's tasks: additive
    counters sum, wall is the slowest task (the stage critical path),
    peak memory is the largest task (StageStats rollup role)."""

    fragment_id: int = -1
    tasks: int = 0          # tasks placed
    reporting: int = 0      # tasks whose info was actually fetched
    input_rows: int = 0
    output_rows: int = 0
    wall_ns: int = 0        # max over tasks
    total_wall_ns: int = 0  # sum over tasks
    jit_dispatches: int = 0
    jit_compiles: int = 0
    jit_compile_ns: int = 0
    prereduce_rows: int = 0
    prereduce_batches_held: int = 0
    prereduce_flushes: int = 0
    compactions: int = 0
    compactions_skipped: int = 0
    # the scan cache's account (OperatorStats.scan_cache_*), summed
    scan_cache_hits: int = 0
    scan_cache_misses: int = 0
    scan_cache_hit_bytes: int = 0
    peak_memory_bytes: int = 0
    exchange_fetched: int = 0
    exchange_consumed: int = 0
    exchange_purged: int = 0
    pages_enqueued: int = 0
    output_bytes: int = 0
    pages_spooled: int = 0
    pages_evicted: int = 0
    bytes_evicted: int = 0
    device_exchange_bytes: int = 0
    host_ns: Dict[str, int] = dataclasses.field(default_factory=dict)
    xla_builds: int = 0
    xla_build_ns: int = 0
    xla_trace_lower_ns: int = 0
    xla_cache_hits: int = 0

    def add_task(self, ts: TaskStats) -> None:
        self.reporting += 1
        self.input_rows += ts.input_rows
        self.output_rows += ts.output_rows
        self.wall_ns = max(self.wall_ns, ts.wall_ns)
        self.total_wall_ns += ts.wall_ns
        self.jit_dispatches += ts.jit_dispatches
        self.jit_compiles += ts.jit_compiles
        self.jit_compile_ns += ts.jit_compile_ns
        _add_prereduce(self, ts)
        _add_scan_cache(self, ts)
        self.peak_memory_bytes = max(self.peak_memory_bytes,
                                     ts.peak_memory_bytes)
        self.exchange_fetched += ts.exchange_fetched
        self.exchange_consumed += ts.exchange_consumed
        self.exchange_purged += ts.exchange_purged
        self.pages_enqueued += ts.pages_enqueued
        self.output_bytes += ts.output_bytes
        self.pages_spooled += ts.pages_spooled
        self.pages_evicted += ts.pages_evicted
        self.bytes_evicted += ts.bytes_evicted
        self.device_exchange_bytes += ts.device_exchange_bytes
        _add_host_and_xla(self, ts)

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def _add_host_and_xla(into, other) -> None:
    """The host-activity and XLA-build accounts, summed one level up."""
    for kind, ns in other.host_ns.items():
        into.host_ns[kind] = into.host_ns.get(kind, 0) + ns
    into.xla_builds += other.xla_builds
    into.xla_build_ns += other.xla_build_ns
    into.xla_trace_lower_ns += other.xla_trace_lower_ns
    into.xla_cache_hits += other.xla_cache_hits


def _add_prereduce(into, other) -> None:
    """The fused segments' account (pre-reduce, end-of-segment
    compaction), summed one level up."""
    into.prereduce_rows += other.prereduce_rows
    into.prereduce_batches_held += other.prereduce_batches_held
    into.prereduce_flushes += other.prereduce_flushes
    into.compactions += other.compactions
    into.compactions_skipped += other.compactions_skipped


def _add_scan_cache(into, other) -> None:
    """The scan cache's account, summed one level up."""
    into.scan_cache_hits += other.scan_cache_hits
    into.scan_cache_misses += other.scan_cache_misses
    into.scan_cache_hit_bytes += other.scan_cache_hit_bytes


@dataclasses.dataclass
class QueryStats:
    """Whole-query rollup over stages (QueryStats role): the shape the
    ``/v1/query/{id}`` detail payload and QueryCompletedEvent carry."""

    query_id: str = ""
    elapsed_s: float = 0.0
    # serving-tier split (server/dispatcher.py): seconds queued for
    # resource-group admission vs executing (admission -> settled);
    # the local tier reports queued 0
    queued_s: float = 0.0
    execution_s: float = 0.0
    total_wall_ns: int = 0
    input_rows: int = 0
    output_rows: int = 0
    jit_dispatches: int = 0
    jit_compiles: int = 0
    jit_compile_ns: int = 0
    prereduce_rows: int = 0
    prereduce_batches_held: int = 0
    prereduce_flushes: int = 0
    compactions: int = 0
    compactions_skipped: int = 0
    # the scan cache's account (OperatorStats.scan_cache_*), summed
    scan_cache_hits: int = 0
    scan_cache_misses: int = 0
    scan_cache_hit_bytes: int = 0
    peak_memory_bytes: int = 0   # max single-task peak across the query
    exchange_fetched: int = 0
    exchange_consumed: int = 0
    exchange_purged: int = 0
    pages_enqueued: int = 0
    output_bytes: int = 0
    pages_spooled: int = 0
    pages_evicted: int = 0
    device_exchange_bytes: int = 0
    # cross-query result cache (server/resultcache.py): 1 when this
    # query was served ENTIRELY from cached spool pages (its jit /
    # dispatch / stage counters are then genuine zeros), and the wire
    # bytes served from the cache
    result_cached: int = 0
    result_cache_bytes: int = 0
    stages: int = 0
    host_ns: Dict[str, int] = dataclasses.field(default_factory=dict)
    xla_builds: int = 0
    xla_build_ns: int = 0
    xla_trace_lower_ns: int = 0
    xla_cache_hits: int = 0

    def add_stage(self, st: StageStats) -> None:
        self.stages += 1
        self.total_wall_ns += st.total_wall_ns
        self.input_rows += st.input_rows
        self.output_rows += st.output_rows
        self.jit_dispatches += st.jit_dispatches
        self.jit_compiles += st.jit_compiles
        self.jit_compile_ns += st.jit_compile_ns
        _add_prereduce(self, st)
        _add_scan_cache(self, st)
        self.peak_memory_bytes = max(self.peak_memory_bytes,
                                     st.peak_memory_bytes)
        self.exchange_fetched += st.exchange_fetched
        self.exchange_consumed += st.exchange_consumed
        self.exchange_purged += st.exchange_purged
        self.pages_enqueued += st.pages_enqueued
        self.output_bytes += st.output_bytes
        self.pages_spooled += st.pages_spooled
        self.pages_evicted += st.pages_evicted
        self.device_exchange_bytes += st.device_exchange_bytes
        _add_host_and_xla(self, st)

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def host_and_xla_line(stats: Dict) -> str:
    """EXPLAIN ANALYZE's line for the host-activity and XLA-build
    accounts of a TaskStats / QueryStats dict."""
    host = ", ".join(f"{kind} {ns / 1e6:.1f}" for kind, ns in
                     (stats.get("host_ns") or {}).items() if ns)
    return (f"host ms: {host or 'nothing recorded'}; xla: "
            f"{stats.get('xla_builds', 0)} built "
            f"({stats.get('xla_cache_hits', 0)} loaded) in "
            f"{stats.get('xla_build_ns', 0) / 1e6:.1f} ms, trace+lower "
            f"{stats.get('xla_trace_lower_ns', 0) / 1e6:.1f} ms")


def scan_cache_line(stats: Dict) -> str:
    """EXPLAIN ANALYZE's line for the scan cache's account of a
    TaskStats / QueryStats dict: table scans by tasks that were handed
    kept device batches, and those that generated and staged."""
    return (f"scan cache: {stats.get('scan_cache_hits', 0)} hits "
            f"({stats.get('scan_cache_hit_bytes', 0) / (1 << 20):.1f} MiB "
            f"handed over), {stats.get('scan_cache_misses', 0)} misses")


def segment_line(stats: Dict) -> str:
    """EXPLAIN ANALYZE's line for the fused segments' account of a
    TaskStats / QueryStats dict: partial states kept on the device, a
    dispatched batch each, and their hand-overs to the consumer; then
    the dispatches that compacted their rows at the end and those that
    had a row mask and moved nothing."""
    return (f"prereduce held: {stats.get('prereduce_batches_held', 0)} "
            f"batches kept on the device, "
            f"{stats.get('prereduce_flushes', 0)} flushes; "
            f"compactions: {stats.get('compactions', 0)} done, "
            f"{stats.get('compactions_skipped', 0)} skipped")


def kernel_tier_lines(ops) -> List[str]:
    """EXPLAIN ANALYZE's "kernel tiers" line: how many operator
    instances (join builds and probes, fused segments, group-bys) took
    which tier (``OperatorStats.kernel_tier``).  ``ops`` are
    operator-stats dicts, one per instance."""
    by_tier: Dict[str, Dict[str, int]] = {}
    for o in ops:
        if o.get("kernel_tier"):
            kinds = by_tier.setdefault(o["kernel_tier"], {})
            kind = o.get("operator", "?").rsplit(".", 1)[-1]
            kinds[kind] = kinds.get(kind, 0) + 1
    if not by_tier:
        return []
    return ["kernel tiers: " + "; ".join(
        f"{tier}: " + ", ".join(f"{kind} x{n}"
                                for kind, n in sorted(kinds.items()))
        for tier, kinds in sorted(by_tier.items()))]


def hot_operator_lines(ops, top_n: int = 5) -> List[str]:
    """The EXPLAIN ANALYZE "hot operators" footer: the top-N operators
    by exclusive wall (``wall_ns`` already includes finish wall for
    aggregated dicts), with the compile-vs-execute split per operator.
    ``ops`` are operator-stats dicts; shared by the local and
    distributed EXPLAIN ANALYZE renderers so the two surfaces stay
    diffable."""
    ranked = sorted((o for o in ops if o.get("wall_ns", 0) > 0),
                    key=lambda o: o.get("wall_ns", 0), reverse=True)
    if not ranked:
        return []
    lines = [f"hot operators (top {min(top_n, len(ranked))} "
             f"by exclusive wall):"]
    for o in ranked[:top_n]:
        wall = o.get("wall_ns", 0)
        compile_ns = min(o.get("jit_compile_ns", 0), wall)
        lines.append(
            f"  {o.get('operator', '?'):<36} "
            f"{wall / 1e6:>9.1f} ms wall "
            f"({compile_ns / 1e6:.1f} compile / "
            f"{(wall - compile_ns) / 1e6:.1f} execute), "
            f"{o.get('output_rows', 0)} rows out")
    return lines


class QueryContext:
    def __init__(self, config: EngineConfig = DEFAULT,
                 memory_limit: Optional[int] = None, pool=None,
                 pool_query_id: str = "query"):
        self.config = config
        self.memory = MemoryContext(None, "query", limit=memory_limit,
                                    pool=pool, pool_query_id=pool_query_id)
        self.start_time = time.time()

    def release_pool(self) -> None:
        self.memory.release_pool()


class TaskContext:
    def __init__(self, query: QueryContext, task_id: str = "task-0"):
        self.query = query
        self.task_id = task_id
        self.config = query.config
        self.memory = MemoryContext(query.memory, f"task:{task_id}")
        self.operator_stats: List[OperatorStats] = []
        self.driver_stats: List[DriverStats] = []
        # what this task's host threads do, and the XLA builds made on
        # them; every Driver of the task records into it (spans.py)
        self.activity = HostActivity()
        self.start_time = time.time()
        self._cleanups: List = []

    def task_stats(self) -> TaskStats:
        """Roll every operator's stats up into one TaskStats (exchange
        and buffer counters are merged in by the owning SqlTask, which
        owns those objects)."""
        ts = TaskStats(task_id=self.task_id, start_time=self.start_time)
        for s in list(self.operator_stats):
            ts.add_operator(s)
        ts.peak_memory_bytes = self.memory.peak
        ts.take_activity(self.activity)
        return ts

    def jit_counters(self) -> Dict[str, int]:
        """Task-level rollup of row-pipeline jit dispatch/compile counts
        (the launch-count surface the fusion tests pin)."""
        return {
            "dispatches": sum(s.jit_dispatches for s in self.operator_stats),
            "compiles": sum(s.jit_compiles for s in self.operator_stats),
            # compile-vs-execute attribution: wall spent building device
            # programs, split out of the operators' execute wall
            "compile_ns": sum(s.jit_compile_ns
                              for s in self.operator_stats),
            "prereduce_rows": sum(s.prereduce_rows
                                  for s in self.operator_stats),
        }

    def register_cleanup(self, fn) -> None:
        """Register an idempotent resource-release callback to run at task
        teardown (the SqlTask cleanup role): a backstop for reservations
        normally released by a downstream pipeline that may never run."""
        self._cleanups.append(fn)

    def close(self) -> None:
        cleanups, self._cleanups = self._cleanups, []
        for fn in cleanups:
            try:
                fn()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass


class OperatorContext:
    def __init__(self, task: TaskContext, name: str):
        self.task = task
        self.config = task.config
        self.name = name
        self.memory = MemoryContext(task.memory, f"op:{name}")
        self.stats = OperatorStats(operator=name)
        task.operator_stats.append(self.stats)

    def should_spill(self, accumulated_bytes: int) -> bool:
        """The revoke decision for accumulating operators (join build,
        sort): shed state to the spill tier past the byte threshold, OR
        as soon as the node's memory pool signals pressure — revocable
        memory is reclaimed BEFORE anyone blocks or the killer fires."""
        cfg = self.config
        if not cfg.spill_enabled:
            return False
        if accumulated_bytes > cfg.spill_threshold_bytes:
            return True
        pool = self.memory.root().pool
        return (pool is not None and accumulated_bytes > 0
                and pool.needs_revoke())
