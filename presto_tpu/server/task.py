"""Worker-side task: one fragment instance executing on one node.

SqlTask/SqlTaskExecution role (presto-main/.../execution/SqlTask.java:67,
SqlTaskExecution.java:82): a task receives a PlanFragment + its scan shard
+ upstream exchange locations + output buffer topology, lowers the
fragment to pipelines (LocalExecutionPlanner role), and runs them on an
executor thread, streaming output pages into its OutputBufferManager until
drained by consumers.

Task states mirror TaskState.java: RUNNING -> FINISHED | FAILED | CANCELED.
"""

from __future__ import annotations

import logging
import threading
import time
import traceback
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.connectors.api import ConnectorRegistry
from presto_tpu.exec.context import QueryContext, TaskContext
from presto_tpu.exec.runner import execute_pipelines
from presto_tpu.server.buffers import OutputBufferManager
from presto_tpu.server.exchangeop import (
    PartitionedOutputOperatorFactory, TaskOutputOperatorFactory,
)
from presto_tpu.server.fragmenter import PlanFragment
from presto_tpu.sql.physical import PhysicalPlanner

#: worker-side task lifecycle log; every line names the query's trace
#: token so any mesh-side event is greppable back to its query
#: (airlift TraceTokenModule role)
log = logging.getLogger("presto_tpu.worker")


class _FragmentCacheEntry:
    """One cached fragment lowering: the pipeline list plus the two
    factory groups that need per-task rebinding (remote sources get new
    producer locations, the sink gets the new task's buffer manager).
    ``in_use`` guards the factories' runtime state: a task still
    executing (or not yet reset) is never shared — a concurrent create
    of the same key lowers privately."""

    __slots__ = ("pipelines", "exchange_factories", "sink", "in_use")

    def __init__(self, pipelines, exchange_factories, sink):
        self.pipelines = pipelines
        self.exchange_factories = exchange_factories
        self.sink = sink
        self.in_use = True


class FragmentPlanCache:
    """Worker-side plan_fragment cache (the distributed half of the
    plan cache's physical-factory sharing): repeat task creates of the
    same statement — same fragment JSON, scan shard, output topology,
    session fingerprint, and coordinator stats epochs — reuse the
    lowered operator-factory chains instead of re-running
    ``PhysicalPlanner.plan_fragment``.  Keyed like ``sql/plancache.py``
    with epoch validation folded INTO the key (the coordinator ships
    its per-catalog epoch snapshot on task create, so any DML/DDL
    changes the key and stale lowered pipelines LRU out)."""

    def __init__(self, capacity: int = 32):
        from collections import OrderedDict

        self.capacity = max(capacity, 1)
        self._entries: "OrderedDict[tuple, _FragmentCacheEntry]" = \
            OrderedDict()
        self._lock = threading.Lock()
        self.stats: Dict[str, int] = {"hits": 0, "misses": 0,
                                      "evictions": 0, "bypasses": 0}

    def acquire(self, key) -> Optional[_FragmentCacheEntry]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.stats["misses"] += 1
                return None
            if entry.in_use:
                # live task still owns the factories: lower privately
                self.stats["bypasses"] += 1
                return None
            entry.in_use = True
            self._entries.move_to_end(key)
            self.stats["hits"] += 1
            return entry

    def insert(self, key, entry: _FragmentCacheEntry) -> None:
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self.stats["evictions"] += 1
            self._entries[key] = entry
            # LRU-evict idle entries past capacity (in-use ones are
            # owned by live tasks and must not vanish under them)
            while len(self._entries) > self.capacity:
                victim = next((k for k, e in self._entries.items()
                               if not e.in_use), None)
                if victim is None:
                    break
                del self._entries[victim]
                self.stats["evictions"] += 1

    def release(self, entry: _FragmentCacheEntry) -> None:
        with self._lock:
            entry.in_use = False


def _fragment_has_writer(root) -> bool:
    from presto_tpu.sql.plan import TableFinishNode, TableWriterNode

    if isinstance(root, (TableWriterNode, TableFinishNode)):
        return True
    return any(_fragment_has_writer(s) for s in root.sources)


class SqlTask:
    def __init__(self, task_id: str, fragment: PlanFragment,
                 scan_shard: Tuple[int, int],
                 remote_sources: Dict[int, List[str]],
                 n_output_partitions: int, broadcast_output: bool,
                 registry: ConnectorRegistry,
                 config: EngineConfig = DEFAULT,
                 fetch_headers: Optional[Dict[str, str]] = None,
                 http_client=None, trace_token: str = "",
                 spool=None, frag_cache: Optional[FragmentPlanCache] = None,
                 frag_cache_key=None, memory_pool=None,
                 inflate_bytes: int = 0, inflate_hold=None):
        self.task_id = task_id
        self.fragment = fragment
        self.trace_token = trace_token
        # node-wide GENERAL memory pool this task's reservation tree
        # charges into, keyed by the owning query (server/memorypool.py)
        self._pool = memory_pool
        self._pool_qid = task_id.rsplit(".", 2)[0]
        # chaos substrate: extra bytes reserved up front (the faults.py
        # memory-inflation policy — a runaway query without the wait);
        # inflate_hold is the originating FaultRule when the runaway
        # should PARK holding the bytes (hold_s) until released/killed
        self._inflate_bytes = inflate_bytes
        self._inflate_hold = inflate_hold
        self.state = "RUNNING"
        self.error: Optional[str] = None
        self.start_time = time.time()
        self.end_time: Optional[float] = None
        # coordinator HA: the coordinator currently owning this task —
        # updated by POST /v1/task/{id}/coordinator when a standby
        # adopts the query on failover (the re-attach repoint)
        self.coordinator_uri: Optional[str] = None
        self._frag_cache = frag_cache
        self._cache_entry: Optional[_FragmentCacheEntry] = None
        # spooled exchange (server/spool.py): output pages write through
        # to the shared store as they are enqueued, and remote sources
        # can read producer streams back from it (spool:// locations)
        spool = spool if config.exchange_spooling_enabled else None
        self.spool = spool
        self.buffers = OutputBufferManager(
            n_output_partitions, broadcast=broadcast_output,
            max_buffer_bytes=config.exchange_max_buffer_bytes,
            spool=spool, task_id=task_id)
        self._stats: Optional[TaskContext] = None
        self._live: Optional[TaskContext] = None  # set when execution starts
        # every exchange source factory of this task's remote sources,
        # so the coordinator can repoint them at replacement producers
        # (mid-query task recovery) whether or not fetching has started
        self.exchange_sources: List = []

        # worker->worker exchange fetches carry the query's trace token
        # alongside the intra-cluster auth header
        fetch_headers = dict(fetch_headers or {})
        if trace_token:
            fetch_headers["X-Presto-Trace-Token"] = trace_token
        reuse = None
        if frag_cache is not None and frag_cache_key is not None:
            reuse = frag_cache.acquire(frag_cache_key)
        if reuse is not None:
            # plan_fragment cache hit: the SAME lowered factory chains
            # execute again — every factory re-arms its cross-execution
            # state (the local-tier reset_for_execution contract),
            # remote sources rebind to the new query's producer
            # locations, and the sink rebinds to this task's buffers.
            # Zero fragment lowerings (sql/physical.FRAGMENTS_LOWERED).
            self._cache_entry = reuse
            for p in reuse.pipelines:
                for f in p.factories:
                    f.reset_for_execution()
            for fac in reuse.exchange_factories:
                locs: List[str] = []
                for fid in getattr(fac, "source_fragment_ids", ()):
                    locs.extend(remote_sources.get(fid, ()))
                fac.rebind(locs, task_id, trace_token or None)
                fac.headers = fetch_headers
                fac.spool = spool
                fac.spool_stall_s = config.exchange_spool_stall_s
                self.exchange_sources.append(fac)
            reuse.sink.rebind(self.buffers)
            self._pipelines = reuse.pipelines
        else:
            planner = PhysicalPlanner(registry, config,
                                      scan_shard=scan_shard,
                                      remote_sources=remote_sources,
                                      fetch_headers=fetch_headers,
                                      http_client=http_client,
                                      task_id=task_id,
                                      exchange_register=(
                                          self.exchange_sources.append),
                                      trace_token=trace_token or None,
                                      spool=spool)
            kind, channels = fragment.output_partitioning
            if kind == "hash" and n_output_partitions > 1:
                sink = PartitionedOutputOperatorFactory(
                    self.buffers, channels, n_output_partitions)
            elif kind == "arbitrary" and n_output_partitions > 1:
                from presto_tpu.server.exchangeop import (
                    RoundRobinOutputOperatorFactory,
                )

                sink = RoundRobinOutputOperatorFactory(
                    self.buffers, n_output_partitions)
            else:  # 'single', 'broadcast', or 1-consumer output
                sink = TaskOutputOperatorFactory(self.buffers)
            self._pipelines = planner.plan_fragment(fragment.root, sink)
            if frag_cache is not None and frag_cache_key is not None \
                    and not _fragment_has_writer(fragment.root):
                entry = _FragmentCacheEntry(
                    self._pipelines, list(self.exchange_sources), sink)
                frag_cache.insert(frag_cache_key, entry)
                self._cache_entry = entry
        self._thread = threading.Thread(
            target=self._run, name=f"task-{task_id}", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        def observe(task_ctx):
            self._live = task_ctx
            if self._inflate_bytes > 0:
                # injected memory pressure: a child reservation held for
                # the task's lifetime (freed by task-context cleanup;
                # the pool backstop covers every failure path)
                from presto_tpu.exec.context import MemoryContext

                mem = MemoryContext(task_ctx.memory,
                                    "fault:memory-inflation")
                task_ctx.register_cleanup(mem.free)
                mem.reserve(self._inflate_bytes)
                rule = self._inflate_hold
                if rule is not None and rule.delay_s > 0:
                    # park holding the injected bytes: the runaway
                    # stays resident until the test releases it, the
                    # hold cap elapses, or the killer's cancel fan-out
                    # aborts this query in the pool
                    deadline = time.monotonic() + rule.delay_s
                    while not rule.released.is_set() \
                            and time.monotonic() < deadline:
                        if self._pool is not None and \
                                self._pool.is_aborted(self._pool_qid):
                            break
                        time.sleep(0.02)

        trace = f" [trace:{self.trace_token}]" if self.trace_token else ""
        log.info("task %s%s started", self.task_id, trace)
        try:
            self._stats = execute_pipelines(self._pipelines,
                                            on_task_context=observe,
                                            pool=self._pool,
                                            pool_query_id=self._pool_qid)
            self.state = "FINISHED"
            log.info("task %s%s finished", self.task_id, trace)
        except Exception as e:  # noqa: BLE001 - task failure surface
            # the trace token rides the stored error AND the buffer
            # failure, so a consumer-side 500 body and the client-facing
            # query error both name the query
            self.error = f"{e}{trace}\n{traceback.format_exc()}"
            self.state = "FAILED"
            log.warning("task %s%s failed: %s", self.task_id, trace, e)
            self.buffers.fail(RuntimeError(
                f"task {self.task_id}{trace}: {e}"))
        finally:
            self.end_time = time.time()
            # release the cached fragment lowering only once this
            # task's thread is actually done touching the factories
            if self._frag_cache is not None and \
                    self._cache_entry is not None:
                self._frag_cache.release(self._cache_entry)

    def info(self, activity: bool = False) -> Dict:
        """TaskInfo with the per-operator stats rollup the coordinator's
        distributed EXPLAIN ANALYZE aggregates (TaskStatus + TaskStats,
        presto-main/.../execution/TaskInfo.java role).  ``activity``
        adds ``hostActivity``, the intervals behind
        ``taskStats.host_ns``: the coordinator's one final collection
        asks for it, the live sampler's polls do not."""
        ctx = self._stats or self._live
        stats = ([s.as_dict() for s in ctx.operator_stats]
                 if ctx is not None else [])
        exchange_stats: Dict[str, Dict] = {}
        for source in self.exchange_sources:
            if hasattr(source, "source_stats"):
                exchange_stats.update(source.source_stats())
        info = {"taskId": self.task_id, "state": self.state,
                "error": self.error, "operatorStats": stats,
                "traceToken": self.trace_token,
                # producer progress + drain state for the coordinator's
                # straggler detector, and the attempt-aware exchange
                # dedup counters (whole-stage retry observability)
                "pagesEnqueued": self.buffers.pages_enqueued,
                "pagesSpooled": self.buffers.pages_spooled,
                "spooledComplete": self.buffers.spooled_complete(),
                "drained": (self.state != "RUNNING"
                            and (self.buffers.is_drained()
                                 or self.buffers.is_fully_served())),
                "exchangeSources": exchange_stats,
                # the TaskStats rollup the coordinator aggregates into
                # StageStats/QueryStats (distributed EXPLAIN ANALYZE,
                # /v1/query detail, events, system.runtime)
                "taskStats": self.task_stats(),
                "peakMemory": ctx.memory.peak if ctx is not None else 0}
        if activity and ctx is not None:
            info["hostActivity"] = ctx.activity.as_dict()
        return info

    def task_stats(self) -> Dict:
        """TaskStats rollup as a JSON-ready dict: operator sums from the
        TaskContext plus the exchange/buffer counters this task owns."""
        from presto_tpu.exec.context import TaskStats

        ctx = self._stats or self._live
        ts = ctx.task_stats() if ctx is not None else TaskStats()
        ts.task_id = self.task_id
        ts.state = self.state
        ts.start_time = self.start_time
        end = self.end_time if self.end_time is not None else time.time()
        ts.end_time = end
        ts.elapsed_s = max(end - self.start_time, 0.0)
        ts.pages_enqueued = self.buffers.pages_enqueued
        ts.output_bytes = self.buffers.bytes_enqueued
        ts.pages_spooled = self.buffers.pages_spooled
        ts.pages_evicted = self.buffers.pages_evicted
        ts.bytes_evicted = self.buffers.bytes_evicted
        for source in self.exchange_sources:
            if not hasattr(source, "source_stats"):
                continue
            for s in source.source_stats().values():
                ts.exchange_fetched += s.get("fetched", 0)
                ts.exchange_consumed += s.get("consumed", 0)
                ts.exchange_purged += s.get("purged", 0)
        return ts.as_dict()

    def memory_info(self) -> Dict:
        """Live reservation/peak bytes (MemoryPool per-task view)."""
        ctx = self._stats or self._live
        if ctx is None:
            return {"reserved": 0, "peak": 0}
        # a CANCELED task's pipeline may still be running (cancellation
        # lands at the next buffer touch); report its reservations until
        # the thread actually exits so the memory manager keeps seeing
        # the pressure
        running = self._thread.is_alive()
        return {"reserved": ctx.memory.reserved if running else 0,
                "peak": ctx.memory.peak}

    def repoint_remote_source(self, old_prefix: str, new_prefix: str,
                              spool: bool = False) -> str:
        """Redirect remote-source fetches from a superseded producer
        attempt at its replacement.  'repointed' | 'delivered' (pages
        from the old attempt already entered the operator chain — this
        task must be restarted instead) | 'not-found'.

        ``spool=True`` is the same-attempt variant: the new prefix is
        the SAME task's spooled output, the fetch resumes at the current
        token, and the delivered guard does not apply (nothing can
        double-count — same stream, different backing store)."""
        status = "not-found"
        for source in self.exchange_sources:
            if spool:
                got = source.repoint_spool(old_prefix, new_prefix)
            else:
                got = source.repoint(old_prefix, new_prefix)
            if got == "delivered":
                return "delivered"
            if got == "repointed":
                status = "repointed"
        return status

    def probe_remote_source(self, old_prefix: str) -> str:
        """Read-only half of the repoint protocol: report whether pages
        from a producer under ``old_prefix`` were already consumed
        ('delivered'), merely fetched/unseen ('clean'), or unknown here
        ('not-found') — whole-stage retry uses this to size the restart
        cascade before mutating anything."""
        status = "not-found"
        for source in self.exchange_sources:
            if not hasattr(source, "delivery_state"):
                continue
            got = source.delivery_state(old_prefix)
            if got == "delivered":
                return "delivered"
            if got == "clean":
                status = "clean"
        return status

    def cancel(self) -> None:
        if self.state == "RUNNING":
            self.state = "CANCELED"
        # always release buffered output: a FINISHED task can still hold
        # pages an early-stopping consumer (TopN merge) never acked
        self.buffers.fail(RuntimeError("task canceled"))

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)


class SqlTaskManager:
    """Worker task registry (SqlTaskManager.java:84 role)."""

    def __init__(self, registry: ConnectorRegistry,
                 config: EngineConfig = DEFAULT,
                 fetch_headers: Optional[Dict[str, str]] = None,
                 http_client=None, spool=None, fault_injector=None):
        from presto_tpu.server.memorypool import MemoryPool

        self.registry = registry
        self.config = config
        # intra-cluster auth headers this node's exchange fetches carry
        self.fetch_headers = fetch_headers
        # node-wide error-tracked HTTP client for remote-source fetches
        self.http_client = http_client
        # node-wide spool store (spooled exchange tier); the per-task
        # exchange_spooling_enabled knob gates its use per query
        self.spool = spool
        # one per-node GENERAL pool all query reservation trees charge
        # into (worker_memory_pool_bytes; 0 = unlimited accounting)
        self.memory_pool = MemoryPool(
            config.worker_memory_pool_bytes,
            blocked_wait_s=config.memory_blocked_wait_s)
        # chaos substrate: consulted at task create for the MEMORY
        # inflation policy (server/faults.py)
        self.fault_injector = fault_injector
        # worker-side plan_fragment cache (lowered pipelines reused
        # across repeat task creates of the same statement)
        self.fragment_cache = (
            FragmentPlanCache(config.worker_fragment_cache_capacity)
            if config.worker_fragment_cache_enabled else None)
        self.tasks: Dict[str, SqlTask] = {}
        # query ids whose tasks this node was told to kill
        # (cancel_query fan-out): a task create that races the fan-out
        # must be refused, not admitted with the abort flag wiped —
        # bounded so ids from long-dead queries eventually age out
        self._killed_queries: "OrderedDict[str, None]" = OrderedDict()
        self._killed_queries_cap = 1024
        self._lock = threading.Lock()

    def _fragment_cache_key(self, fragment: PlanFragment,
                            scan_shard, n_out: int, broadcast: bool,
                            session_properties, plan_epochs,
                            config) -> Optional[tuple]:
        """The plancache-shaped key: coordinator epoch-domain token +
        per-catalog epoch snapshot (shipped on task create; any DML/DDL
        bumps an epoch and changes the key), the fragment's canonical
        JSON, the scan shard, output topology, and the session-property
        fingerprint.  None = bypass (no epochs shipped, or writers)."""
        if self.fragment_cache is None or not plan_epochs:
            return None
        import json as _json

        from presto_tpu.sql import plancache
        from presto_tpu.sql.planserde import fragment_to_json

        return (
            str(plan_epochs.get("token", "")),
            tuple(sorted((str(c), int(e)) for c, e in
                         (plan_epochs.get("epochs") or {}).items())),
            _json.dumps(fragment_to_json(fragment), sort_keys=True),
            tuple(scan_shard), int(n_out), bool(broadcast),
            plancache.fingerprint(session_properties),
            bool(config.exchange_spooling_enabled),
        )

    def create_task(self, task_id: str, fragment: PlanFragment,
                    scan_shard: Tuple[int, int],
                    remote_sources: Dict[int, List[str]],
                    n_output_partitions: int,
                    broadcast_output: bool,
                    session_properties: Optional[Dict[str, str]] = None,
                    trace_token: str = "",
                    plan_epochs: Optional[Dict] = None
                    ) -> SqlTask:
        config = self.config
        if session_properties:
            # fold the query's SET SESSION overrides over this node's
            # base config (validated names/values, Session role)
            from presto_tpu.session import Session

            session = Session()
            for k, v in session_properties.items():
                session.set_property(k, str(v))
            config = session.effective_config(config)
        key = None
        if config.worker_fragment_cache_enabled:
            try:
                key = self._fragment_cache_key(
                    fragment, scan_shard, n_output_partitions,
                    broadcast_output, session_properties, plan_epochs,
                    config)
            except Exception:  # noqa: BLE001 - cache keying is advisory
                key = None
        inflate, inflate_hold = 0, None
        apply_memory = getattr(self.fault_injector, "apply_memory", None)
        if apply_memory is not None:   # custom injectors may not have it
            inflate, inflate_hold = apply_memory(task_id)
        qid = task_id.rsplit(".", 2)[0]
        with self._lock:
            if task_id in self.tasks:
                return self.tasks[task_id]
            # a late placement racing the kill fan-out must not start:
            # admitting it would resurrect reservations the killer just
            # freed (and clearing the pool abort flag here would let the
            # victim's drivers ride out the full blocked-wait backstop)
            if qid in self._killed_queries:
                raise RuntimeError(
                    f"query {qid} was killed on this node; refusing "
                    f"late task {task_id}")
            task = SqlTask(task_id, fragment, scan_shard, remote_sources,
                           n_output_partitions, broadcast_output,
                           self.registry, config,
                           fetch_headers=self.fetch_headers,
                           http_client=self.http_client,
                           trace_token=trace_token,
                           spool=self.spool,
                           frag_cache=self.fragment_cache,
                           frag_cache_key=key,
                           memory_pool=self.memory_pool,
                           inflate_bytes=inflate,
                           inflate_hold=inflate_hold)
            self.tasks[task_id] = task
            return task

    def get(self, task_id: str) -> Optional[SqlTask]:
        with self._lock:
            return self.tasks.get(task_id)

    def list_infos(self) -> List[Dict]:
        with self._lock:
            return [t.info() for t in self.tasks.values()]

    def cancel_query(self, query_id: str) -> int:
        """Cancel every task belonging to ``query_id`` (task ids are
        ``{queryId}.{fragment}.{i}``); the KillQueryProcedure role."""
        # record the kill BEFORE aborting so a create_task racing this
        # fan-out either sees the id and refuses, or registered its
        # task earlier and gets cancelled by the sweep below
        with self._lock:
            self._killed_queries[query_id] = None
            self._killed_queries.move_to_end(query_id)
            while len(self._killed_queries) > self._killed_queries_cap:
                self._killed_queries.popitem(last=False)
            tasks = list(self.tasks.values())
        # wake the query's drivers blocked in pool.reserve() — a killed
        # victim stuck on a full pool must die promptly, not ride out
        # the blocked-wait backstop
        self.memory_pool.abort_query(query_id)
        n = 0
        for t in tasks:
            if t.task_id.startswith(query_id + "."):
                t.cancel()
                n += 1
        return n

    def cancel_all(self) -> None:
        with self._lock:
            for task in self.tasks.values():
                task.cancel()

    def memory_info(self) -> Dict:
        """Node MemoryInfo (presto-main/.../memory/MemoryInfo.java role):
        totals plus per-query reservations, aggregated from task memory
        contexts (task ids are {queryId}.{fragment}.{i})."""
        with self._lock:
            tasks = list(self.tasks.values())
        per_query: Dict[str, Dict[str, int]] = {}
        total_reserved = 0
        total_peak = 0
        for t in tasks:
            mi = t.memory_info()
            qid = t.task_id.rsplit(".", 2)[0]
            q = per_query.setdefault(qid, {"reserved": 0, "peak": 0})
            q["reserved"] += mi["reserved"]
            q["peak"] += mi["peak"]
            total_reserved += mi["reserved"]
            total_peak += mi["peak"]
        from presto_tpu.exec.scancache import SCAN_CACHE

        kept = SCAN_CACHE.stats(self.registry.connectors())
        return {"reserved": total_reserved, "peak": total_peak,
                "queries": per_query,
                "pool": self.memory_pool.info(),
                # device bytes this node's connectors keep resident
                # between queries (exec/scancache.py): no query's
                # reservation, so outside the totals above
                "scanCache": {"bytes": kept["resident_bytes"],
                              "entries": kept["entries"]}}

    def running_count(self) -> int:
        with self._lock:
            return sum(1 for t in self.tasks.values()
                       if t.state == "RUNNING")

    def undrained_count(self) -> int:
        """Tasks still running OR holding pages a consumer has not yet
        fetched — the set a graceful drain must wait for.  With the
        spooled exchange the coordinator RELEASES a draining worker's
        finished tasks (repoint consumers at the spool, then DELETE the
        task, which fails-and-frees its buffers), so this count reaches
        zero without consumers ever fetching the rest."""
        with self._lock:
            return sum(1 for t in self.tasks.values()
                       if t.state == "RUNNING"
                       or not t.buffers.is_drained())
