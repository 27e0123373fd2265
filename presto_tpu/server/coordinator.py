"""Coordinator: statement protocol, dispatch, scheduling, discovery.

Mirrors the reference's coordinator control plane (SURVEY §2.5, §3.2):

- **Statement protocol** (QueuedStatementResource.java:86-87 +
  ExecutingStatementResource.java:85-86): POST /v1/statement submits SQL,
  the client follows ``nextUri`` until FINISHED, receiving JSON rows.
- **Dispatch/execution** (DispatchManager.java:59, SqlQueryExecution
  .java:95): a per-query thread parses, plans, optimizes, fragments
  (server.fragmenter), schedules stage tasks onto workers bottom-up, then
  drains the root stage's output buffer into the client result queue.
- **Scheduling** (SqlQueryScheduler.java:112): task counts are a pure
  function of fragment partitioning — 'source'/'hash' stages get one task
  per live worker, 'single' one task; buffer topology and exchange
  locations are wired at task-create (HttpRemoteTask.java:100 role is
  ``_create_remote_task``).
- **Discovery + failure detection** (DiscoveryNodeManager.java:68,
  HeartbeatFailureDetector.java:77): workers announce at
  POST /v1/announcement; a heartbeat thread GETs /v1/info on every node
  and excludes nodes from scheduling after consecutive failures.
"""

from __future__ import annotations

import datetime
import json
import logging
import threading
import time
import traceback
import urllib.error
import urllib.request
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from presto_tpu import events as ev
from presto_tpu import types as T
from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.connectors.api import ConnectorRegistry
from presto_tpu.serde import deserialize_batch, frame_size
from presto_tpu.server.errortracker import (
    RemoteRequestError, RequestErrorTracker,
)
from presto_tpu.server.fragmenter import DistributedPlan, Fragmenter
from presto_tpu.spans import (
    HOST_ACTIVITY_HEADER, HostActivity, activity, set_current_activity,
)
from presto_tpu.sql import tree as t
from presto_tpu.sql.optimizer import optimize
from presto_tpu.sql.parser import parse_statement
from presto_tpu.sql.planner import Metadata, Planner

#: (errorName, errorType, errorCode) triples for the memory-arbitration
#: and administrative kill paths (StandardErrorCode layout: USER_ERROR
#: codes are based at 0x0000_0000, INSUFFICIENT_RESOURCES at
#: 0x0002_0000; the admission-layer triples live in server/dispatcher.py).
EXCEEDED_GLOBAL_MEMORY_LIMIT = ("EXCEEDED_GLOBAL_MEMORY_LIMIT",
                                "INSUFFICIENT_RESOURCES", 0x0002_0001)
CLUSTER_OUT_OF_MEMORY = ("CLUSTER_OUT_OF_MEMORY",
                         "INSUFFICIENT_RESOURCES", 0x0002_0004)
ADMINISTRATIVELY_KILLED = ("ADMINISTRATIVELY_KILLED", "USER_ERROR",
                           0x0000_0005)


def pick_low_memory_victim(policy: str, per_query: Dict[str, int],
                           per_query_blocked: Dict[str, int],
                           killable: set) -> Optional[str]:
    """The pluggable LowMemoryKiller (LowMemoryKiller.java SPI role):
    given per-query cluster-wide reservations — total, and restricted
    to nodes whose pools have blocked drivers — pick at most one victim.

    - ``total-reservation`` (TotalReservationLowMemoryKiller): the
      largest total reservation anywhere wins.
    - ``total-reservation-on-blocked-nodes``
      (TotalReservationOnBlockedNodesLowMemoryKiller, the default): the
      largest reservation counting only blocked nodes — the query
      actually holding the stuck pool hostage — falling back to total
      reservation when no killable query reserves on a blocked node.
    - ``none``: never kill (blocked drivers ride out the worker-side
      ``memory_blocked_wait_s`` backstop instead).

    Ties break on query id so repeated ticks are deterministic."""
    if policy == "none":
        return None
    candidates = {qid: b for qid, b in per_query.items()
                  if qid in killable}
    if not candidates:
        return None
    if policy == "total-reservation-on-blocked-nodes":
        on_blocked = {qid: b for qid, b in per_query_blocked.items()
                      if qid in killable and b > 0}
        if on_blocked:
            return max(sorted(on_blocked), key=on_blocked.get)
    return max(sorted(candidates), key=candidates.get)


class NodeManager:
    """Live-node registry + heartbeat failure detector."""

    def __init__(self, max_missed: int = 3, interval_s: float = 0.5):
        self.nodes: Dict[str, str] = {}       # node_id -> uri
        self.missed: Dict[str, int] = {}
        self.states: Dict[str, str] = {}      # node_id -> reported state
        self.locations: Dict[str, str] = {}   # node_id -> topology label
        # node_id -> announced device-mesh identity (None when a node
        # predates the field); the mesh_device_exchange co-residency test
        self.mesh_fps: Dict[str, Optional[str]] = {}
        self.max_missed = max_missed
        self.interval_s = interval_s
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._heartbeat_loop,
                                        daemon=True, name="failure-detector")
        self._thread.start()

    def announce(self, node_id: str, uri: str,
                 location: str = "",
                 mesh_fingerprint: Optional[str] = None) -> None:
        with self._lock:
            self.nodes[node_id] = uri
            self.missed[node_id] = 0
            if location:
                self.locations[node_id] = location
            self.mesh_fps[node_id] = mesh_fingerprint

    def common_mesh_fingerprint(self) -> Optional[str]:
        """The ONE fingerprint every schedulable node announced, or None
        when nodes span meshes / predate the field — the co-residency
        gate of the device-sharded exchange tier (a mixed cluster keeps
        the HTTP plane, which works across any topology)."""
        nodes = self.alive_nodes()
        if not nodes:
            return None
        with self._lock:
            fps = {self.mesh_fps.get(nid) for nid, _uri in nodes}
        if len(fps) == 1:
            return fps.pop()
        return None

    def topology_ordered(self, nodes: List[Tuple[str, str]]
                         ) -> List[Tuple[str, str]]:
        """Round-robin across topology locations (rack labels) so the
        i-th task of every stage lands in a different failure/bandwidth
        domain — the TopologyAwareNodeSelector placement role
        (presto-main/.../scheduler/TopologyAwareNodeSelector.java:50,
        NetworkTopology).  Nodes without a label form one domain."""
        with self._lock:
            locs = dict(self.locations)
        by_loc: Dict[str, List[Tuple[str, str]]] = {}
        for nid, uri in nodes:
            by_loc.setdefault(locs.get(nid, ""), []).append((nid, uri))
        out: List[Tuple[str, str]] = []
        queues = [by_loc[k] for k in sorted(by_loc)]
        i = 0
        while any(queues):
            q = queues[i % len(queues)]
            if q:
                out.append(q.pop(0))
            i += 1
            if i > 10_000:  # defensive
                break
        return out

    def alive_nodes(self) -> List[Tuple[str, str]]:
        """Schedulable nodes: responsive AND reporting ACTIVE (a
        SHUTTING_DOWN node finishes its tasks but gets no new ones)."""
        with self._lock:
            return [(nid, uri) for nid, uri in sorted(self.nodes.items())
                    if self.missed.get(nid, 0) < self.max_missed
                    and self.states.get(nid, "ACTIVE") == "ACTIVE"]

    def responsive_nodes(self) -> List[Tuple[str, str]]:
        """Every reachable node INCLUDING draining ones — the set for
        cancel fan-out, memory polling, and task aggregation (a
        SHUTTING_DOWN worker still runs tasks that must stay visible
        and cancellable)."""
        with self._lock:
            return [(nid, uri) for nid, uri in sorted(self.nodes.items())
                    if self.missed.get(nid, 0) < self.max_missed]

    def dead_uris(self) -> set:
        """URIs the failure detector has declared dead (consecutive
        missed heartbeats) — the excluded-node set task recovery and
        replacement placement consult."""
        with self._lock:
            return {uri for nid, uri in self.nodes.items()
                    if self.missed.get(nid, 0) >= self.max_missed}

    def draining_uris(self) -> set:
        """Responsive workers advertising SHUTTING_DOWN — the set the
        graceful-drain tick hands over to the spool."""
        with self._lock:
            return {uri for nid, uri in self.nodes.items()
                    if self.missed.get(nid, 0) < self.max_missed
                    and self.states.get(nid) == "SHUTTING_DOWN"}

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            with self._lock:
                targets = list(self.nodes.items())
            for nid, uri in targets:
                ok = False
                state = "ACTIVE"
                try:
                    with urllib.request.urlopen(f"{uri}/v1/info",
                                                timeout=2) as resp:
                        ok = resp.status == 200
                        if ok:
                            state = json.loads(resp.read()).get(
                                "state", "ACTIVE")
                except Exception:  # noqa: BLE001
                    ok = False
                with self._lock:
                    self.missed[nid] = 0 if ok else \
                        self.missed.get(nid, 0) + 1
                    if ok:
                        self.states[nid] = state

    def close(self) -> None:
        self._stop.set()


class _DrainRestart(Exception):
    """Internal drain control flow: a whole-stage restart superseded the
    location being pulled; abandon the in-flight request and re-enter
    the drain loop (which consumes the restart marker)."""


class _SpoolUnavailable(Exception):
    """Spool verification failed (missing object / read error): the
    spooled recovery path cannot proceed; fall back to PR 5 cascading
    retry."""


class _CoordinatorKilled(Exception):
    """Chaos control flow (coordinator HA): this coordinator was
    process-level killed (``CoordinatorServer.kill``) — the query
    thread must stop IMMEDIATELY with no externally visible side
    effects (no events, no cancel fan-out, no spool GC), leaving worker
    tasks producing into the spool for the standby to adopt."""


class _DeviceDegradeToHttp(Exception):
    """Device-plane resume gave up (mesh_resume_mode='http', or the
    device resume budget is spent): degrade to the HTTP plane,
    scheduling ONLY the fragments whose checkpoints are not
    spool-complete — completed fragments become spool:// leaf inputs
    with zero re-execution."""

    def __init__(self, reason: str, failed_fragment: int,
                 resumed_from: List[int]):
        super().__init__(reason)
        self.reason = reason
        self.failed_fragment = failed_fragment
        self.resumed_from = list(resumed_from)


class QueryExecution:
    """One query's lifecycle (QueryStateMachine + SqlQueryExecution)."""

    def __init__(self, query_id: str, sql: str,
                 coordinator: "CoordinatorServer", user: str = "user",
                 session_properties: Optional[Dict[str, str]] = None,
                 catalog: Optional[str] = None,
                 prepared: Optional[Dict[str, str]] = None,
                 trace_token: Optional[str] = None,
                 auto_start: bool = True):
        self.query_id = query_id
        self.sql = sql
        self.co = coordinator
        self.user = user
        # query-scoped trace token (airlift TraceTokenModule role): the
        # client may supply one on X-Presto-Trace-Token; otherwise it is
        # generated at dispatch and rides EVERY internal request of this
        # query so worker logs, task errors, and events correlate
        self.trace_token = trace_token or f"tt-{uuid.uuid4().hex[:12]}"
        self.create_time = ev.now()
        self.end_time: Optional[float] = None
        # client-session state carried on the request headers
        # (StatementClientV1 / Session roles)
        self.session_properties = dict(session_properties or {})
        self.catalog = catalog or coordinator.default_catalog
        self.prepared = dict(prepared or {})
        # session mutations this statement produced, returned in the
        # final payload for the client to apply (X-Presto-Set-Session /
        # X-Presto-Added-Prepare role)
        self.session_updates: Dict = {}
        self.state = "QUEUED"
        self.canceled = False
        self.error: Optional[str] = None
        # the reference's error shape (StandardErrorCode): set by the
        # dispatcher for admission-layer failures (queue full, user
        # cancel); None = generic failure, message-only
        self.error_name: Optional[str] = None
        self.error_type: Optional[str] = None
        self.error_code: Optional[int] = None
        # overload shedding: the dispatcher's retry hint for rejected
        # statements, surfaced as Retry-After on the POST ack and
        # ``retryAfterSeconds`` in the protocol error object
        self.retry_after_s: Optional[int] = None
        # serving-tier time split: seconds spent queued for admission
        # vs executing (planning through drain) — the queued-vs-execution
        # split QueryStats, /v1/query/{id}, and EXPLAIN ANALYZE report
        self.queued_s = 0.0
        self.execution_s = 0.0
        self.admit_time: Optional[float] = None
        self.resource_group_name = ""
        # EXECUTE-bound prepared statements cache under a derived key
        # (prepared text + bound parameters), set by _session_statement
        self._plan_key_sql: Optional[str] = None
        self.plan_cached = False      # this run reused a cached plan
        # this run was served ENTIRELY from the cross-query result
        # cache (server/resultcache.py): no tasks, no physical plans,
        # no jit dispatches — rows came straight from spool pages
        self.result_cached = False
        self.result_cache_bytes = 0   # spooled wire bytes served
        # the SpoolStore the served entry lives in (equals co.spool in
        # practice; kept per-hit so _drain_spool reads the right tier)
        self._rc_store = None
        self.plan_text: str = ""
        self._tasks_scheduled = False
        # (fragment_id, task_id, worker_uri) per scheduled task — the
        # stats-fetch targets for distributed EXPLAIN ANALYZE
        self._placements: List[Tuple[int, str, str]] = []
        # -- mid-query task recovery state --------------------------------
        self._dplan: Optional[DistributedPlan] = None
        self._consumers: Dict[int, int] = {}     # producer fid -> consumer
        self._task_specs: Dict[str, Dict] = {}   # task id -> create args
        # root-drain location rewrites after a root producer was
        # rescheduled (original location -> replacement location)
        self._relocations: Dict[str, str] = {}
        self._recovered_uris: set = set()        # workers already handled
        self._recovery_lock = threading.Lock()
        self._monitor_stop = threading.Event()
        # -- whole-stage retry / speculation state ------------------------
        # fid -> current attempt task ids by task index
        self._frag_tasks: Dict[int, List[str]] = {}
        # fid -> result-uri templates ('{part}' placeholder) by index;
        # the lists are SHARED with the remote-source dicts recorded in
        # _task_specs, so in-place updates keep every recreate recipe
        # pointing at the live attempts
        self._task_uris: Dict[int, List[str]] = {}
        self._attempts: Dict[str, int] = {}      # base task id -> attempt
        self._stage_retries: Dict[int, int] = {} # fid -> rounds consumed
        self.stage_retry_rounds = 0              # observability (tests)
        self.recovery_rounds = 0
        # root-drain whole-stage restarts: original location -> restarted
        # location; the drain DISCARDS that location's rows and re-pulls
        # from token 0 (unlike _relocations, which only follow at token 0)
        self._restarts: Dict[str, str] = {}
        self._root_orig: Dict[str, str] = {}     # orig loc -> current loc
        # -- spooled exchange state (server/spool.py) ----------------------
        # root-drain moves to the SAME attempt's spooled output: original
        # location -> spool:// location.  Unlike _relocations/_restarts
        # these resume at the CURRENT token with rows kept — the spool
        # serves the identical stream
        self._spool_moves: Dict[str, str] = {}
        # workers whose tasks were fully handed to the spool by the
        # graceful-drain tick (one WorkerDrainEvent each)
        self._drained_uris: set = set()
        # FAILED-on-live-worker tasks already restarted from the spool,
        # and the ones seen failed once (restart needs two consecutive
        # scans, so a racing worker-death is detected/recovered first)
        self._failed_handled: set = set()
        self._failed_seen: set = set()
        self._failed_scan_at = 0.0
        # producer-subtree tasks re-executed by stage retry; the spooled
        # exchange's headline: 0 with spooling on
        self.producer_reruns_total = 0
        # straggler tid -> {'fid','clone','clone_uri','orig_uri','state'}
        self._speculations: Dict[str, Dict] = {}
        self._task_seen: Dict[str, Dict] = {}    # tid -> progress polls
        self.column_names: List[str] = []
        self.column_types: List[T.Type] = []
        self.result_rows: List[tuple] = []
        self.rows_done = threading.Event()
        # -- mesh observability (stats rollup + event stream) --------------
        # fragment id -> StageStats dict, aggregated once post-drain from
        # real remote task info; query_stats is the whole-query rollup
        self.stage_stats: Dict[int, Dict] = {}
        self.query_stats: Dict = {}
        # exchange-mode counters: per fragment boundary, the transport
        # that served it — 'device' (in-program collective), 'http'
        # (wire pages, possibly spool-backed).  Folded into query_stats
        # and the /v1/query detail; the device tier also records its
        # kernel tiers + fallback reason here
        self.exchange_modes: Dict[str, int] = {}
        self.device_exchange_info: Dict = {}
        # fragment id -> [TaskStats dict] (span timeline for the
        # query_profile tool) and raw task infos (EXPLAIN ANALYZE)
        self.task_stats: Dict[int, List[Dict]] = {}
        self._task_infos: Dict[int, List[Dict]] = {}
        self._stats_collected = False
        # -- live telemetry (sampler-fed, StatementStats role) -------------
        # bounded per-query time-series ring: one sample per sampler
        # sweep while RUNNING, served at /v1/query/{id}/timeseries
        self.timeseries: List[Dict] = []
        # latest reference-shaped progress snapshot (totalSplits /
        # runningSplits / completedSplits / processedRows / ...) carried
        # on every client-protocol poll ("stats" object)
        self._progress: Dict = {}
        # serializes live-sample folds against the final post-drain
        # collection (the final rollup always wins)
        self._stats_lock = threading.Lock()
        self._sampler_started = False
        # phase marks for the timed span tree (presto_tpu.spans):
        # name -> (start, end) epoch seconds, coordinator-owned
        self._marks: Dict[str, Tuple[float, float]] = {}
        self._completed_fired = False
        # -- coordinator HA (server/statestore.py) -------------------------
        # durable-journal bookkeeping: serde'd plan cached per query,
        # root-drain consumed tokens per original location, and the
        # adopted-query flags a standby sets when it rebuilds this
        # query from a dead coordinator's journal
        self._journal_lock = threading.Lock()
        self._dplan_json: Optional[Dict] = None
        self._root_tokens: Dict[str, int] = {}
        self._plan_epochs_cache: Optional[Dict] = None
        self.adopted = False
        self.adopt_outcome: Optional[str] = None
        # -- device-plane boundary checkpoints (mesh_checkpoint_boundaries)
        # fid (str) -> {task_id, n_out, rows, bytes}: checkpoints this
        # query spooled (or adopted from the journal); device_resumes is
        # the /v1/query-visible resume log; _device_completed marks
        # spool-complete checkpointed fragments for the HTTP-degrade
        # scheduler (fid -> checkpoint task id)
        self._device_ckpts: Dict[str, Dict] = {}
        self.device_resumes: List[Dict] = []
        self._device_completed: Dict[int, str] = {}
        self.co.event_bus.query_created(ev.QueryCreatedEvent(
            self.query_id, self.user, self.sql, self.create_time,
            trace_token=self.trace_token))
        self._thread: Optional[threading.Thread] = None
        if auto_start:
            self._start()

    def _start(self) -> None:
        """Start the per-query thread (the dispatcher defers this until
        its loop picks the query up)."""
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"query-{self.query_id}")
        self._thread.start()

    def _run(self) -> None:
        from presto_tpu.session import Session

        group = self.co.resource_groups.group_for(
            Session(user=self.user, catalog=self.co.default_catalog))
        try:
            group.acquire(timeout_s=300)
        except Exception as e:  # noqa: BLE001 - admission rejection
            self.error = str(e)
            self.state = "FAILED"
            self.rows_done.set()
            self._fire_completed()
            return
        try:
            self._run_admitted()
        finally:
            group.release()
            self._fire_completed()

    # -- coordinator HA: durable journal + standby adoption ---------------
    def _journal(self, state: Optional[str] = None) -> None:
        """Write-through this query's durable state at a lifecycle
        transition (server/statestore.py).  Strictly best-effort: a
        journal problem must never fail a query the engine can run."""
        store = getattr(self.co, "statestore", None)
        if store is None:
            return
        try:
            doc = self._journal_doc(state or self.state)
            with self._journal_lock:
                store.write(doc)
        except Exception:  # noqa: BLE001 - journaling is best-effort
            pass

    def _journal_transition(self, state: str) -> None:
        """Journal + the chaos phase hook: tests install
        ``CoordinatorServer.phase_hook`` to hold a query AT a lifecycle
        phase; when the hook returns on a killed coordinator, the query
        thread stops with no side effects (the process-death shape)."""
        self._journal(state)
        hook = getattr(self.co, "phase_hook", None)
        if hook is not None:
            try:
                hook(self, state)
            except Exception:  # noqa: BLE001 - hooks never fail queries
                pass
        if getattr(self.co, "killed", False):
            raise _CoordinatorKilled()

    def _journal_doc(self, state: str):
        from presto_tpu.server.statestore import QueryJournal
        from presto_tpu.sql.planserde import dplan_to_json

        j = QueryJournal(
            query_id=self.query_id, sql=self.sql, user=self.user,
            catalog=self.catalog,
            session_properties=dict(self.session_properties),
            prepared=dict(self.prepared), trace_token=self.trace_token,
            plan_key_sql=self._plan_key_sql, state=state,
            error=self.error, create_time=self.create_time)
        # device-plane checkpoints: journaled as soon as they exist so a
        # standby (or the device resume path) can adopt mid-program
        # progress even though no HTTP tasks were ever scheduled
        if self._device_ckpts:
            j.device_checkpoints = dict(self._device_ckpts)
        if self._dplan is not None and self._tasks_scheduled:
            if self._dplan_json is None:
                self._dplan_json = dplan_to_json(self._dplan)
            j.dplan = self._dplan_json
            with self._recovery_lock:
                j.placements = list(self._placements)
                j.attempts = dict(self._attempts)
                fid_of = {tid: fid for fid, tid, _ in self._placements}
                j.task_specs = {
                    tid: {"fid": fid_of[tid], "index": spec["index"],
                          "scan_shard": list(spec["scan_shard"]),
                          "n_out": spec["n_out"],
                          "broadcast": spec["broadcast"],
                          "consumer_index": spec["consumer_index"],
                          "base": spec["base"]}
                    for tid, spec in self._task_specs.items()
                    if tid in fid_of}
                j.root_locations = list(self._root_orig)
                j.root_tokens = dict(self._root_tokens)
        return j

    def _journal_terminal(self) -> None:
        """Terminal journal write, BEFORE the query's spool GC: a
        FINISHED query's root output is adopted into a stable ``ha*``
        spool stream (outliving the query) so a standby serves its rows
        with zero re-execution; small or unspooled results journal
        their rows inline."""
        store = getattr(self.co, "statestore", None)
        if store is None or getattr(self.co, "killed", False):
            return
        try:
            j = self._journal_doc(self.state)
            j.column_names = list(self.column_names)
            j.column_types = [t.display() for t in self.column_types]
            j.row_count = len(self.result_rows)
            if self.state == "FINISHED" and \
                    not self._journal_adopt_result(j):
                cfg = getattr(self, "_cfg", None) or self.co.config
                rows = [[_json_value(v) for v in row]
                        for row in self.result_rows]
                encoded = json.dumps(rows)
                if len(encoded) <= \
                        cfg.coordinator_journal_max_result_bytes:
                    j.inline_rows = rows
            with self._journal_lock:
                store.write(j)
        except Exception:  # noqa: BLE001 - journaling is best-effort
            pass

    def _journal_adopt_result(self, j) -> bool:
        """Copy the root-output spool stream(s) into ``ha{token}.0.0``
        (partition i per root location) — the result-cache adoption
        shape, reused for the HA journal.  Returns False when the
        stream is not adoptable (spooling off, incomplete, oversized)."""
        import uuid as _uuid

        from presto_tpu.server import resultcache
        from presto_tpu.server.spool import query_id_of

        cfg = getattr(self, "_cfg", None) or self.co.config
        if not (self._tasks_scheduled and self._spool_enabled()
                and self._dplan is not None):
            return False
        with self._recovery_lock:
            root_tids = list(self._frag_tasks.get(
                self._dplan.root_fragment_id) or [])
        if not root_tids:
            return False
        store = self.co.spool
        ha_tid = f"ha{_uuid.uuid4().hex[:12]}.0.0"
        budget = cfg.coordinator_journal_max_result_bytes
        total = 0
        try:
            for i, tid in enumerate(root_tids):
                pages = resultcache.read_complete_stream(
                    store, tid, 0, max_bytes=budget - total)
                if pages is None:
                    raise ValueError("stream not adoptable")
                for tok, page in enumerate(pages):
                    store.write_page(ha_tid, i, tok, page)
                store.set_complete(ha_tid, i, len(pages))
                total += sum(len(p) for p in pages)
        except Exception:  # noqa: BLE001 - adoption is best-effort
            try:
                store.delete_query(query_id_of(ha_tid))
            except Exception:  # noqa: BLE001
                pass
            return False
        j.result_task_id = ha_tid
        j.result_locations = len(root_tids)
        j.result_bytes = total
        return True

    @classmethod
    def adopt(cls, co: "CoordinatorServer", journal) -> "QueryExecution":
        """Rebuild one journaled query on a standby coordinator that
        just won the takeover lease, and start its adoption thread."""
        q = cls(journal.query_id, journal.sql, co, user=journal.user,
                session_properties=journal.session_properties,
                catalog=journal.catalog, prepared=journal.prepared,
                trace_token=journal.trace_token, auto_start=False)
        q.adopted = True
        if journal.create_time:
            q.create_time = journal.create_time
        q._plan_key_sql = journal.plan_key_sql
        co.queries[journal.query_id] = q
        q._thread = threading.Thread(
            target=q._run_adopted, args=(journal,), daemon=True,
            name=f"adopt-{journal.query_id}")
        q._thread.start()
        return q

    def _run_adopted(self, journal) -> None:
        outcome = "failed"
        try:
            if journal.state == "FAILED":
                self.error = journal.error or "query failed"
                self.state = "FAILED"
                outcome = "served"
            elif journal.state == "FINISHED":
                self._serve_journal_result(journal)
                outcome = "served"
            else:
                outcome = self._adopt_running(journal)
        except Exception as e:  # noqa: BLE001 - adoption failure surface
            self.error = self.error or f"adoption failed: {e}"
            self.co.log(traceback.format_exc())
            self.state = "FAILED"
            outcome = "failed"
        finally:
            self.adopt_outcome = outcome
            self.co.count_adopted(outcome)
            self.co.event_bus.query_adopted(ev.QueryAdoptedEvent(
                self.query_id, self.trace_token, journal.state, outcome,
                ev.now()))
            if self._tasks_scheduled:
                try:
                    self._collect_stats()
                except Exception:  # noqa: BLE001 - stats best-effort
                    pass
            if self._tasks_scheduled:
                # only a RUNNING adoption produced fresh state worth
                # journaling; a served/failed terminal journal is
                # already correct (re-writing it would drop the ha*
                # page pointer a THIRD failover still needs)
                self._journal_terminal()
            self._fire_completed()
            self.rows_done.set()
            self._monitor_stop.set()
            if self._tasks_scheduled:
                self._cancel_worker_tasks()
            if self._tasks_scheduled and self.co.spool is not None:
                try:
                    self.co.spool.delete_query(self.query_id)
                except Exception:  # noqa: BLE001 - GC is best-effort
                    pass

    def _serve_journal_result(self, journal) -> None:
        """FINISHED query: rows straight from the adopted ``ha*`` spool
        pages (byte-exact re-drain), or the inline journal encoding."""
        self.column_names = list(journal.column_names)
        self.column_types = [T.parse_type(s)
                             for s in journal.column_types]
        if journal.result_task_id:
            locations = [
                f"spool://v1/task/{journal.result_task_id}/results/{i}"
                for i in range(journal.result_locations)]
            self.state = "RUNNING"
            with self._mark("execute"):
                self._drain(locations)
        elif journal.inline_rows is not None:
            self.result_rows = [
                tuple(_client_value(v, t) for v, t in
                      zip(row, self.column_types))
                for row in journal.inline_rows]
        else:
            raise RuntimeError(
                "journaled FINISHED query has no recoverable result "
                "(no ha pages, no inline rows)")
        self.state = "FINISHED"

    def _adopt_running(self, journal) -> str:
        """Adopt a mid-flight query: live tasks re-attach (they keep
        producing into the spool), tasks complete-in-spool get their
        consumers repointed (zero re-execution), unreachable tasks
        restart through the EXISTING spool stage-retry machinery at
        fresh attempt ids, and the root drain re-pulls the spooled root
        stream from token 0 (idempotent under the token+attempt dedup
        contract)."""
        from presto_tpu.server.spool import spool_location
        from presto_tpu.sql.planserde import dplan_from_json

        cfg = self._session().effective_config(self.co.config)
        self._cfg = cfg
        if not (cfg.exchange_spooling_enabled
                and self.co.spool is not None):
            raise RuntimeError("adopting a RUNNING query requires the "
                               "spooled exchange (its state lives in "
                               "the spool)")
        if not journal.placements or journal.dplan is None:
            # _adopt_journal routes task-less queries to re-admission
            # before building an adoption shell; reaching here means
            # the journal is inconsistent
            raise RuntimeError("RUNNING journal has no placements")
        dplan = dplan_from_json(journal.dplan)
        if any(f.partitioning == "scaled" for f in dplan.fragments):
            raise RuntimeError(
                "coordinator failed over mid-write: the write was "
                "aborted (writer fragments are not adoptable)")
        self._dplan = dplan
        self.column_names = list(dplan.column_names)
        self.column_types = list(dplan.column_types)
        frag_by_id = {f.fragment_id: f for f in dplan.fragments}
        for f in dplan.fragments:
            for pfid in f.consumed_fragments:
                self._consumers[pfid] = f.fragment_id
        # placements + per-fragment task/uri tables, index-ordered like
        # _schedule builds them (the recovery machinery's shape)
        by_fid: Dict[int, List] = {}
        for fid, tid, uri in journal.placements:
            spec = journal.task_specs.get(tid)
            if spec is None:
                raise RuntimeError(f"journal lacks a spec for {tid}")
            by_fid.setdefault(fid, []).append((spec["index"], tid, uri))
        for fid, rows in by_fid.items():
            rows.sort()
            self._frag_tasks[fid] = [tid for _, tid, _ in rows]
            self._task_uris[fid] = [
                (spool_location(tid) if uri.startswith("spool://")
                 else f"{uri}/v1/task/{tid}/results/{{part}}")
                for _, tid, uri in rows]
        self._attempts = dict(journal.attempts)
        for fid, tid, uri in journal.placements:
            spec = journal.task_specs[tid]
            frag = frag_by_id[fid]
            self._placements.append((fid, tid, uri))
            self._task_specs[tid] = {
                "frag": frag,
                "scan_shard": tuple(spec["scan_shard"]),
                "remote": {pfid: self._task_uris[pfid]
                           for pfid in frag.consumed_fragments},
                "n_out": spec["n_out"], "broadcast": spec["broadcast"],
                "consumer_index": spec["consumer_index"],
                "base": spec["base"], "index": spec["index"],
                "created_at": time.monotonic()}
        self._tasks_scheduled = True
        self.state = "RUNNING"
        self.admit_time = self.admit_time or ev.now()
        # classify every placement: alive / complete-in-spool / lost
        live = 0
        repointed = 0
        lost: List[Tuple[int, str]] = []
        for fid, tid, uri in list(self._placements):
            if uri.startswith("spool://"):
                repointed += 1
                continue
            if self._reattach_task(tid, uri) == "alive":
                live += 1
                continue
            spec = self._task_specs[tid]
            complete = False
            try:
                complete = self._spool_complete(tid, spec)
            except _SpoolUnavailable:
                complete = False
            if complete:
                self._repoint_to_spool(fid, tid, uri, spec)
                repointed += 1
            else:
                lost.append((fid, tid))
        if lost:
            self._retry_stages_spooled(
                lost, f"failed-over coordinator "
                      f"({len(lost)} unreachable task(s))")
        self._start_recovery_monitor()
        self._start_sampler()
        self._journal("RUNNING")
        # the root drain reads the spooled root stream(s) from token 0:
        # write-through spooling means a live root task's stream fills
        # progressively and a finished one is complete — zero
        # re-execution either way
        with self._recovery_lock:
            root_tids = list(self._frag_tasks[dplan.root_fragment_id])
        roots = [f"spool://v1/task/{tid}/results/0" for tid in root_tids]
        with self._recovery_lock:
            self._root_orig = {loc: loc for loc in roots}
        with self._mark("execute"):
            self._drain(roots)
        self.state = "FINISHED"
        if lost:
            return "restarted"
        if live:
            return "reattached"
        return "repointed"

    def _reattach_task(self, tid: str, uri: str) -> str:
        """The worker-side coordinator repoint: POST
        /v1/task/{id}/coordinator re-announces this coordinator as the
        task's owner.  'alive' means the worker holds the task and it
        is not FAILED/CANCELED — it keeps producing into the spool."""
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        body = json.dumps({"coordinator": self.co.uri}).encode("utf-8")
        try:
            resp = self.co.http.request(
                f"{uri}/v1/task/{tid}/coordinator", method="POST",
                data=body, headers=headers, timeout=5, task_id=tid,
                description="coordinator reattach",
                max_error_duration_s=2.0)
            info = resp.json()
        except Exception:  # noqa: BLE001 - unreachable = lost
            return "lost"
        if info.get("status") != "reattached":
            return "lost"
        return ("alive" if info.get("state") in ("RUNNING", "FINISHED")
                else "lost")

    def _fire_completed(self) -> None:
        """QueryCompletedEvent enriched with the stage-stats rollup
        (QueryMonitor.queryCompletedEvent role).  Fired exactly once."""
        if getattr(self.co, "killed", False):
            return
        if self._completed_fired:
            return
        self._completed_fired = True
        self.end_time = ev.now()
        qs = self.query_stats or {}
        try:
            spans = self.spans(activities=False)
        except Exception:  # noqa: BLE001 - observability never fails
            spans = {}
        self.co.event_bus.query_completed(ev.QueryCompletedEvent(
            self.query_id, self.user, self.sql, self.state,
            self.error, self.create_time, self.end_time,
            len(self.result_rows), int(qs.get("peak_memory_bytes", 0)),
            [], trace_token=self.trace_token,
            stage_stats=[self.stage_stats[fid]
                         for fid in sorted(self.stage_stats)],
            spans=spans))
        elapsed = max(self.end_time - self.create_time, 0.0)
        execution_s = self.execution_s or (
            max(self.end_time - self.admit_time, 0.0)
            if self.admit_time is not None else elapsed)
        # dispatcher-lifecycle latency histograms (/metrics:
        # presto_query_queued_seconds / presto_query_execution_seconds)
        hists = getattr(self.co, "latency_histograms", None)
        if hists is not None:
            hists["queued"].observe(self.queued_s)
            hists["execution"].observe(execution_s)
        # slow-query log: one structured line + one SlowQueryEvent past
        # the threshold (0 disables), naming the queued/execution split
        # and the hottest operator so the log line alone says where the
        # wall clock went
        cfg = getattr(self, "_cfg", None) or self.co.config
        threshold = cfg.slow_query_log_threshold_s
        if threshold > 0 and elapsed >= threshold:
            top = self._top_operator()
            # name the device-exchange disposition so the log line alone
            # says which data plane ran (and why the collective tier was
            # skipped, when it was)
            fb = (self.device_exchange_info or {}).get("fallback")
            plane = ("device" if "device" in self.exchange_modes
                     else "http")
            logging.getLogger("presto_tpu.coordinator").warning(
                "slow query %s [trace:%s] user=%s elapsed=%.3fs "
                "(queued=%.3fs execution=%.3fs, threshold=%.3fs) "
                "top_operator=%s exchange_plane=%s device_fallback=%s "
                "sql=%r",
                self.query_id, self.trace_token, self.user, elapsed,
                self.queued_s, execution_s, threshold, top or "?",
                plane, fb or "-", self.sql[:200])
            self.co.event_bus.slow_query(ev.SlowQueryEvent(
                self.query_id, self.trace_token, self.user,
                self.sql[:500], round(elapsed, 6),
                round(self.queued_s, 6), round(execution_s, 6),
                threshold, top, ev.now()))

    def _execute_query_dplan(self, dplan: DistributedPlan,
                             analyze: bool) -> None:
        """Schedule + drain one fragmented query plan (shared by the
        freshly-planned and plan-cache-hit paths)."""
        self.column_names = dplan.column_names
        self.column_types = dplan.column_types
        if self._try_device_exchange(dplan, analyze):
            # the whole fragment DAG ran as ONE SPMD program; no tasks,
            # no wire pages — per-shard stats read out of the program
            # fold into the same StageStats/TaskStats rollup (and the
            # device EXPLAIN ANALYZE rendering) a task-scheduled query
            # gets
            return
        self.state = "SCHEDULING"
        with self._mark("schedule"):
            root_locations = self._schedule(dplan)
        self.state = "RUNNING"
        self._start_sampler()
        with self._mark("execute"):
            self._drain(root_locations)
        self._collect_stats()
        if analyze:
            text = self._render_analyze(dplan)
            self.column_names = ["Query Plan"]
            self.column_types = [T.VARCHAR]
            self.result_rows = [(line,) for line in text.splitlines()]

    def _try_device_exchange(self, dplan: DistributedPlan,
                             analyze: bool = False) -> bool:
        """Collectives as the data plane (mesh_device_exchange): when
        every schedulable worker AND this coordinator share one device
        mesh (mesh fingerprints equal — same process/device set) and
        every fragment boundary is device-eligible, the whole fragment
        DAG lowers into one shard_map'ped SPMD program: 'hash'
        boundaries become all_to_all, 'broadcast' all_gather, 'single'
        a gather — no PartitionedOutput, no serde, no HTTP pull.  Any
        miss (mixed mesh, unsupported shape, runtime capacity
        non-convergence) falls back to the task-scheduled HTTP plane,
        which stays the elastic / fault-tolerant / cross-slice tier.
        Returns True when the query was fully answered here.

        Telemetry contract (PR 12): the per-shard counters traced into
        the program fold into synthetic per-shard TaskStats under real
        per-fragment StageStats, progress beacons feed the sampler ring
        MID-program, and EXPLAIN ANALYZE renders the device tier — a
        mesh query reads like an HTTP query on every surface."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        n_bound = sum(len(f.consumed_fragments) for f in dplan.fragments)
        if not cfg.mesh_device_exchange:
            return False
        import contextlib

        import jax

        from presto_tpu.parallel import beacons
        from presto_tpu.parallel.mesh import mesh_fingerprint
        from presto_tpu.parallel.sqlmesh import MeshUnsupported
        from presto_tpu.server.fragmenter import annotate_device_exchange

        def fallback(reason: str, kind: str) -> bool:
            self.exchange_modes = {"http": n_bound}
            self.device_exchange_info = {"fallback": reason[:200],
                                         "fallback_kind": kind}
            self.co.count_device_fallback(kind)
            return False

        sticky = getattr(dplan, "_device_fallback", None)
        if sticky is not None:
            # a previous execution of this cached plan already proved
            # the shape cannot serve from the collective tier (capacity
            # non-convergence / unsupported shape): go straight to the
            # task-scheduled plane with the ALREADY-FRAGMENTED plan —
            # no re-parse/analyze/optimize (the plan-cache hit carried
            # the fragments here) and no re-attempted lowering.  Still
            # counted under the bounded fallback-reason categories.
            return fallback(sticky[0], sticky[1])
        workers = self.co.nodes.alive_nodes()
        shared_fp = self.co.nodes.common_mesh_fingerprint()
        if not workers or shared_fp is None \
                or shared_fp != mesh_fingerprint():
            return fallback("placements not co-resident on one mesh",
                            "not_co_resident")
        try:
            if not annotate_device_exchange(dplan):
                return fallback("boundary outside the collective subset",
                                "unsupported_boundary")
        except Exception as e:  # noqa: BLE001 - annotation is advisory
            return fallback(f"annotation failed: {e}", "annotation_error")
        nparts = max(1, min(len(workers), len(jax.devices())))
        key = (f"{self.catalog}|{self._plan_key_sql or self.sql}")
        self.state = "RUNNING"
        collector = None
        if cfg.mesh_progress_beacons:
            collector = self._device_beacon_collector(n_bound, nparts, cfg)
        # this query has no task threads: what its own thread does inside
        # the execute phase (spans.ACTIVITY_KINDS, bracketed here and in
        # parallel/sqlmesh.py) is recorded for the root fragment's task
        recorder = HostActivity()
        previous = set_current_activity(recorder)
        try:
            with self._mark("execute"):
                exec_t0 = ev.now()
                with activity("lock_wait"):
                    self.co.mesh_executor_lock.acquire()
                try:
                    runner = self.co.mesh_executor(cfg, nparts)
                    ctx = (beacons.install(collector)
                           if collector is not None
                           else contextlib.nullcontext())
                    with ctx:
                        if cfg.mesh_checkpoint_boundaries:
                            result = self._run_mesh_checkpointed(
                                runner, dplan, key, cfg, nparts)
                        else:
                            result = runner.execute_dplan(dplan, key)
                    info = dict(runner.last_run_info)
                finally:
                    self.co.mesh_executor_lock.release()
                exec_t1 = ev.now()
        except _DeviceDegradeToHttp as e:
            # resume budget spent (or mesh_resume_mode='http'): degrade
            # to the task-scheduled plane.  _schedule consults
            # _device_completed and serves every spool-complete
            # checkpointed fragment as a spool:// leaf — only the
            # REMAINING fragments get tasks
            self.co.log(f"device-exchange degrading to http after "
                        f"checkpoint f{e.failed_fragment}: {e.reason}")
            self._note_device_resume("http", e.failed_fragment,
                                     e.resumed_from, e.reason)
            self._device_completed = {
                int(fid): rec["task_id"]
                for fid, rec in self._device_ckpts.items()}
            return fallback(f"device resume degraded to http: "
                            f"{e.reason}", "resume_degraded")
        except (MeshUnsupported, NotImplementedError) as e:
            # deterministic per plan (capacity non-convergence exhausts
            # every bucket scale; unsupported primitives never lower):
            # record it ON the dplan so the plan-cache hit path skips
            # the device attempt entirely on every repeat
            dplan._device_fallback = (f"mesh: {e}", "unsupported_shape")
            return fallback(f"mesh: {e}", "unsupported_shape")
        except (ValueError, _CoordinatorKilled):
            # query-semantic errors surfaced during mesh execution
            # ("scalar subquery returned more than one row") are the
            # user's answer, not a lowering failure; coordinator death
            # stops the thread with no side effects for the standby
            raise
        except Exception as e:  # noqa: BLE001 - HTTP tier can still run
            self.co.log(f"device-exchange execution failed "
                        f"({type(e).__name__}: {e}); falling back to the "
                        f"task-scheduled plane")
            return fallback(f"{type(e).__name__}: {e}", "execution_error")
        finally:
            set_current_activity(previous)
        self.result_rows = [tuple(r) for r in result.rows]
        boundaries = info.get("boundaries", [])
        self.exchange_modes = {"device": len(boundaries) or n_bound}
        self.device_exchange_info = {
            "nparts": info.get("nparts"),
            "boundaries": boundaries,
            "kernel_tiers": info.get("kernel_tiers", []),
            "cap_scale": info.get("cap_scale", 1),
            # compile attribution: XLA-compile wall this run paid (0 on
            # a cross-query program-cache hit) + cache disposition
            "compile_ns": int(info.get("compile_ns") or 0),
            "program_cached": bool(info.get("program_cached")),
            "per_shard": info.get("per_shard") or {},
        }
        # checkpoint-mode accounting: groups run, checkpoints reused,
        # fragments this execution actually lowered (the
        # never-re-lowered pin), resumes taken, and spooled bytes
        for k in ("checkpoint_groups", "checkpoints",
                  "fragments_lowered"):
            if k in info:
                self.device_exchange_info[k] = info[k]
        if self.device_resumes:
            self.device_exchange_info["resumes"] = [
                dict(r) for r in self.device_resumes]
        if self._device_ckpts:
            self.device_exchange_info["checkpoint_bytes"] = sum(
                int(r.get("bytes") or 0)
                for r in self._device_ckpts.values())
        # "lower"/"compile" span phases, only when THIS run built the
        # program (a cache hit has nothing to attribute)
        for name, window in (info.get("build_spans") or {}).items():
            self._marks[name] = (float(window[0]), float(window[1]))
        self.co.count_device_success(boundaries)
        self._fold_device_stats(dplan, info, (exec_t0, exec_t1), recorder)
        if collector is not None:
            self._settle_device_progress(collector)
        if analyze:
            text = self._render_analyze_device(dplan, info)
            self.column_names = ["Query Plan"]
            self.column_types = [T.VARCHAR]
            self.result_rows = [(line,) for line in text.splitlines()]
        return True

    # -- device-plane boundary checkpoints (mesh_checkpoint_boundaries) --
    def _run_mesh_checkpointed(self, runner, dplan: DistributedPlan,
                               key: str, cfg, nparts: int):
        """The restartable collective data plane: checkpoint groups run
        as a sequence of SPMD programs; each boundary's output is
        write-through spooled + journaled.  A device-plane failure
        resumes from the last complete boundary — up to
        ``mesh_resume_limit`` times in 'device' mode (fresh SPMD
        programs fed from the checkpointed batches), then (or
        immediately in 'http' mode) degrades to the HTTP plane via
        ``_DeviceDegradeToHttp``."""
        from presto_tpu.parallel.sqlmesh import MeshUnsupported

        completed = self._preload_checkpoints(dplan)
        if completed:
            # standby adoption / requeue after a coordinator kill: the
            # journaled checkpoints short-circuit their groups entirely
            self._note_device_resume(
                "device", -1, sorted(completed),
                "adopted checkpoint journal")
        inj = getattr(self.co, "fault_injector", None)
        current = {"fid": -1}

        def fault_hook(fid: int) -> None:
            current["fid"] = fid
            # a killed coordinator stops between groups with no side
            # effects: the journal keeps the checkpoints written so far
            # for the standby to adopt (kill() contract)
            if getattr(self.co, "killed", False):
                raise _CoordinatorKilled()
            if inj is None:
                return
            for s in range(nparts):
                inj.apply_device(f"{self.query_id}/f{fid}/s{s}")

        def on_checkpoint(fid: int, batch) -> None:
            self._device_checkpoint(dplan, fid, batch)

        resumes = 0
        while True:
            try:
                return runner.execute_dplan_checkpointed(
                    dplan, key, completed=completed,
                    on_checkpoint=on_checkpoint, fault_hook=fault_hook)
            except (MeshUnsupported, NotImplementedError, ValueError,
                    _CoordinatorKilled):
                # lowering misses, query-semantic errors and coordinator
                # death are NOT device faults: the caller's taxonomy
                # handles them
                raise
            except Exception as e:  # noqa: BLE001 - the resume seam
                reason = f"{type(e).__name__}: {e}"
                failed = current["fid"]
                resumed_from = sorted(completed)
                if cfg.mesh_resume_mode == "device" \
                        and resumes < max(int(cfg.mesh_resume_limit), 0):
                    resumes += 1
                    self.co.log(
                        f"device-plane failure at f{failed} "
                        f"({reason}); resuming from checkpoints "
                        f"{resumed_from} "
                        f"({resumes}/{cfg.mesh_resume_limit})")
                    self._note_device_resume("device", failed,
                                             resumed_from, reason)
                    continue
                raise _DeviceDegradeToHttp(reason, failed,
                                           resumed_from) from e

    def _note_device_resume(self, mode: str, failed_fragment: int,
                            resumed_from: List[int],
                            reason: str) -> None:
        """One resume decision on every surface: the process counter
        (/metrics), the event stream (query.json), and the per-query
        log served on /v1/query/{id} as ``deviceResumes``."""
        self.co.count_device_resume(mode)
        self.device_resumes.append({
            "mode": mode, "failed_fragment": failed_fragment,
            "resumed_from": list(resumed_from),
            "reason": reason[:200]})
        self.co.event_bus.device_resume(ev.DeviceResumeEvent(
            self.query_id, self.trace_token, mode, failed_fragment,
            tuple(resumed_from), reason[:200], ev.now()))

    def _device_checkpoint(self, dplan: DistributedPlan, fid: int,
                           batch) -> None:
        """Write-through one boundary checkpoint: the fragment's GLOBAL
        output rows, partitioned exactly like the HTTP plane's
        PartitionedOutput sink (same hash kernel, same LZ4 wire frame),
        spooled under this query's id — the spool contract, terminal
        GC, and the spool:// remote-source path apply unchanged — then
        journaled so a standby can adopt mid-program progress.
        Best-effort: a spool problem only costs restartability."""
        spool = getattr(self.co, "spool", None)
        if spool is None:
            return
        frag = dplan.fragments[fid]
        cons_fid = None
        for f in dplan.fragments:
            if fid in f.consumed_fragments:
                cons_fid = f.fragment_id
                break
        workers = self.co.nodes.alive_nodes()
        n_out = (self._task_count(dplan.fragments[cons_fid],
                                  max(len(workers), 1))
                 if cons_fid is not None else 1)
        # 'ckpt{fid}' keeps checkpoint task ids disjoint from the HTTP
        # plane's '{qid}.{fid}.{i}' ids while query_id_of still maps
        # them to this query (terminal spool GC reaps them together)
        tid = f"{self.query_id}.ckpt{fid}.0"
        try:
            batch = self._merge_sorted_checkpoint(dplan, fid, batch)
            parts = self._partition_checkpoint(batch, frag, n_out)
            total = 0
            for p in range(n_out):
                pages = parts.get(p) or []
                for tok, page in enumerate(pages):
                    spool.write_page(tid, p, tok, page)
                    total += len(page)
                spool.set_complete(tid, p, len(pages))
        except Exception:  # noqa: BLE001 - checkpointing is best-effort
            return
        self.co.count_device_checkpoint_bytes(total)
        self._device_ckpts[str(fid)] = {
            "task_id": tid, "n_out": n_out,
            "rows": int(batch.num_rows), "bytes": total,
            "kind": frag.output_partitioning[0]}
        self._journal()

    def _merge_sorted_checkpoint(self, dplan: DistributedPlan, fid: int,
                                 batch):
        """A consumer that k-way merges (RemoteMergeNode — ORDER BY /
        distributed TopN) requires every producer STREAM pre-sorted;
        the checkpoint concatenates per-shard runs, so re-sort the
        global batch by the merge keys before spooling — one fully
        sorted stream is a valid 1-way merge input.  Other consumers
        see a plain multiset and need no order."""
        from presto_tpu.sql.plan import RemoteMergeNode

        merge = None
        for f in dplan.fragments:
            if fid not in f.consumed_fragments:
                continue
            stack = [f.root]
            while stack and merge is None:
                n = stack.pop()
                if isinstance(n, RemoteMergeNode) \
                        and fid in n.fragment_ids:
                    merge = n
                    break
                stack.extend(n.sources)
            break
        if merge is None or not merge.sort_keys or not batch.num_rows:
            return batch
        import jax.numpy as jnp

        from presto_tpu.ops.sort import sort_permutation

        b = batch.compact()
        keys = []
        for ch, asc, nulls_first in merge.sort_keys:
            c = b.columns[ch]
            vals, typ = c.values, c.type
            if c.dictionary is not None:
                # strings order by lexicographic rank over the
                # dictionary, never by code (exec/sortop.py contract)
                ranks = c.dictionary.sort_ranks()
                vals = jnp.asarray(ranks)[vals]
                typ = T.INTEGER
            keys.append((vals, c.valid, typ, not asc,
                         bool(nulls_first)))
        perm = sort_permutation(keys, jnp.asarray(b.num_rows))
        return b.take(perm)

    def _partition_checkpoint(self, batch, frag,
                              n_out: int) -> Dict[int, List[bytes]]:
        """Partition a checkpoint batch for its consumer's fan-out,
        mirroring PartitionedOutputOperator: hash output routes by the
        shared value-hash kernel (co-partitioning with every other
        producer), broadcast copies the whole batch per partition,
        anything else lands in partition 0 (valid for 'single' and
        'arbitrary' — consumers merge partitions without key
        semantics)."""
        from presto_tpu.serde import serialize_batch

        kind, channels = frag.output_partitioning
        if n_out == 1 or kind not in ("hash", "broadcast"):
            return {0: [serialize_batch(batch)]}
        if kind == "broadcast":
            page = serialize_batch(batch)
            return {p: [page] for p in range(n_out)}
        import jax.numpy as jnp
        import numpy as np

        from presto_tpu.ops.hashing import (
            partition_of, row_hash, value_hash_triple,
        )

        batch = batch.compact()
        key_cols = [value_hash_triple(batch.columns[c])
                    for c in channels]
        hashes = row_hash(key_cols)
        parts = np.asarray(partition_of(hashes, n_out))
        order = np.argsort(parts, kind="stable")
        bounds = np.searchsorted(parts[order], np.arange(n_out + 1))
        out: Dict[int, List[bytes]] = {}
        for p in range(n_out):
            lo, hi = int(bounds[p]), int(bounds[p + 1])
            if lo == hi:
                continue
            sub = batch.take(jnp.asarray(order[lo:hi]))
            out[p] = [serialize_batch(sub)]
        return out

    def _preload_checkpoints(self, dplan: DistributedPlan) -> Dict:
        """Recover this query id's completed boundary checkpoints: the
        in-memory record first (same-execution device resume keeps the
        batches live anyway), else the durable journal (standby
        adoption / requeue after a coordinator kill).  Every record is
        verified spool-complete before its pages are deserialized back
        into the fragment's global output batch — an unverifiable
        checkpoint is simply re-run."""
        from presto_tpu.batch import batch_from_pylist, concat_batches
        from presto_tpu.server import resultcache

        recs = dict(self._device_ckpts)
        if not recs:
            store = getattr(self.co, "statestore", None)
            if store is not None:
                try:
                    j = store.read(self.query_id)
                    if j is not None:
                        recs = dict(j.device_checkpoints)
                except Exception:  # noqa: BLE001 - journal best-effort
                    recs = {}
        completed: Dict[int, object] = {}
        spool = getattr(self.co, "spool", None)
        if spool is None or not recs:
            return completed
        frag_by_id = {f.fragment_id: f for f in dplan.fragments}
        for fid_s, rec in recs.items():
            fid = int(fid_s)
            frag = frag_by_id.get(fid)
            tid = rec.get("task_id")
            n_out = int(rec.get("n_out") or 0)
            if frag is None or not tid or n_out <= 0 \
                    or fid == dplan.root_fragment_id:
                continue
            try:
                if not spool.is_complete(tid, n_out):
                    continue
                # broadcast checkpoints hold the FULL batch in every
                # partition — read one copy; everything else unions
                read_n = 1 if rec.get("kind") == "broadcast" else n_out
                batches = []
                for p in range(read_n):
                    pages = resultcache.read_complete_stream(
                        spool, tid, p, max_bytes=1 << 31)
                    if pages is None:
                        raise ValueError("incomplete stream")
                    batches.extend(deserialize_batch(pg)
                                   for pg in pages)
            except Exception:  # noqa: BLE001 - re-run beats bad state
                continue
            if batches:
                b = (concat_batches(batches) if len(batches) > 1
                     else batches[0])
            else:
                b = batch_from_pylist(
                    [t for _, t in frag.root.columns], [])
            completed[fid] = b
            self._device_ckpts[str(fid)] = dict(rec)
        return completed

    def _fold_device_stats(self, dplan: DistributedPlan, info: Dict,
                           window: Tuple[float, float],
                           recorder: HostActivity) -> None:
        """Per-shard program counters -> synthetic TaskStats -> real
        per-fragment StageStats -> QueryStats: the SAME rollup shapes
        _rollup_stats builds from remote task info, so every downstream
        surface (EXPLAIN ANALYZE, /v1/query detail, system.runtime,
        QueryCompletedEvent, the span tree, the web UI) renders a mesh
        query without knowing which tier ran it.  'single' fragments
        fold as ONE task (their per-shard copies are replicas, exactly
        like the HTTP plane schedules one task); the program's single
        dispatch + compile attribution land on the root task, and so does
        ``recorder``, what the query thread did inside the window: its
        totals as the task's ``host_ns`` and XLA account, its intervals
        as the task's final info (``spans()`` hangs them under it)."""
        from presto_tpu.exec.context import (
            QueryStats, StageStats, TaskStats,
        )

        nparts = max(int(info.get("nparts") or 1), 1)
        per = info.get("per_shard") or {}
        frag_rows = per.get("fragments") or {}
        peak = list(per.get("peak_live_bytes") or [])
        bytes_by_frag: Dict[int, List[int]] = {}
        for b in info.get("boundaries", []):
            acc = bytes_by_frag.setdefault(b["fragment"], [0] * nparts)
            for s, v in enumerate(b.get("bytes", [])[:nparts]):
                acc[s] += int(v)
        t0, t1 = window
        root_fid = dplan.root_fragment_id
        stage_stats: Dict[int, Dict] = {}
        task_stats: Dict[int, List[Dict]] = {}
        qs = QueryStats(query_id=self.query_id,
                        elapsed_s=ev.now() - self.create_time)
        for frag in dplan.fragments:
            fid = frag.fragment_id
            fr = frag_rows.get(fid, {})
            n_tasks = 1 if frag.partitioning == "single" else nparts
            st = StageStats(fragment_id=fid, tasks=n_tasks)
            for s in range(n_tasks):
                def at(key: str) -> int:
                    vals = fr.get(key) or []
                    return int(vals[s]) if s < len(vals) else 0

                ts = TaskStats(
                    task_id=f"{self.query_id}.{fid}.{s}",
                    state="FINISHED", start_time=t0, end_time=t1,
                    elapsed_s=round(max(t1 - t0, 0.0), 6),
                    input_rows=at("input_rows"),
                    output_rows=at("output_rows"),
                    device_exchange_bytes=int(
                        bytes_by_frag.get(fid, [0] * nparts)[s]))
                # device bytes double as the processedBytes surface the
                # wire tier reports as output_bytes
                ts.output_bytes = ts.device_exchange_bytes
                if fid == root_fid and s == 0:
                    # the ONE SPMD program: one dispatch, the build
                    # attributed where it was paid
                    ts.jit_dispatches = 1
                    ts.jit_compiles = (0 if info.get("program_cached")
                                       else 1)
                    ts.jit_compile_ns = int(info.get("compile_ns") or 0)
                    ts.peak_memory_bytes = max(
                        [int(v) for v in peak] or [0])
                    ts.take_activity(recorder)
                task_stats.setdefault(fid, []).append(ts.as_dict())
                st.add_task(ts)
            stage_stats[fid] = st.as_dict()
            qs.add_stage(st)
        qs.queued_s = round(self.queued_s, 6)
        qs.execution_s = round(
            ev.now() - self.admit_time if self.admit_time is not None
            else qs.elapsed_s, 6)
        qs_dict = qs.as_dict()
        qs_dict["exchange_modes"] = dict(self.exchange_modes)
        qs_dict["device_exchange"] = dict(self.device_exchange_info)
        with self._stats_lock:
            self.stage_stats = stage_stats
            self.task_stats = task_stats
            self.query_stats = qs_dict
            self._task_infos = {root_fid: [{
                "taskId": f"{self.query_id}.{root_fid}.0",
                "hostActivity": recorder.as_dict()}]}

    def _device_beacon_collector(self, n_bound: int, nparts: int, cfg):
        """Host-side sink for the in-program beacons: each NEW
        (fragment, shard) unit appends one RUNNING sample to the PR 9
        sampler ring and refreshes the client-poll progress object —
        progress units are fragment-boundary crossings per shard, so
        completed counts and cumulative rows are monotonic by
        construction (parallel/beacons.ProgressCollector)."""
        from presto_tpu.parallel import beacons

        total_units = max(n_bound, 1) * max(nparts, 1)
        cap = max(int(cfg.stats_timeseries_capacity), 1)

        def on_progress(completed: int, total: int, rows: int) -> None:
            sample = {
                "t": round(ev.now(), 6),
                "state": "RUNNING",
                "splits_total": total,
                "splits_queued": 0,
                "splits_running": max(total - completed, 0),
                "splits_completed": completed,
                "input_rows": rows,
                "output_rows": 0,
                "output_bytes": 0,
                "peak_memory_bytes": 0,
                "exchange_backlog": 0,
                "pages_enqueued": 0,
                "pages_spooled": 0,
                "jit_dispatches": 1,
            }
            with self._stats_lock:
                self.timeseries.append(sample)
                if len(self.timeseries) > cap:
                    del self.timeseries[:len(self.timeseries) - cap]
                self._progress = {
                    "totalSplits": total,
                    "queuedSplits": 0,
                    "runningSplits": max(total - completed, 0),
                    "completedSplits": completed,
                    "processedRows": rows,
                    "processedBytes": 0,
                    "peakMemoryBytes": 0,
                    "progressPercent": round(
                        100.0 * completed / total, 2) if total else 0.0,
                }

        return beacons.ProgressCollector(
            total_units, on_progress=on_progress,
            on_beacon=getattr(self.co, "_beacon_test_hook", None))

    def _settle_device_progress(self, collector) -> None:
        """Final progress settle after the program returned (the device
        analogue of the final _collect_stats sample): every unit
        complete, processed rows from the query rollup."""
        completed, total, rows = collector.snapshot()
        qs = self.query_stats or {}
        with self._stats_lock:
            self._progress = {
                "totalSplits": total, "queuedSplits": 0,
                "runningSplits": 0, "completedSplits": total,
                "processedRows": max(rows, qs.get("output_rows", 0)),
                "processedBytes": qs.get("device_exchange_bytes", 0),
                "peakMemoryBytes": qs.get("peak_memory_bytes", 0),
                "progressPercent": 100.0,
            }

    _COLLECTIVE_OF = {"hash": "all_to_all", "arbitrary": "all_to_all",
                      "broadcast": "all_gather", "single": "gather"}

    def _boundary_footer(self, dplan: DistributedPlan,
                         boundaries: Optional[List[Dict]] = None
                         ) -> List[str]:
        """EXPLAIN ANALYZE footer naming the exchange mode per fragment
        boundary — 'via http' on the wire plane, 'via <collective>'
        with rows/bytes when the device tier served the query."""
        consumers: Dict[int, List[int]] = {}
        for f in dplan.fragments:
            for fid in f.consumed_fragments:
                consumers.setdefault(fid, []).append(f.fragment_id)
        mode = "device" if "device" in self.exchange_modes else "http"
        lines = [f"exchange boundaries ({mode}):"]
        if boundaries:
            for b in boundaries:
                fid, kind = b["fragment"], b["kind"]
                cons = consumers.get(fid) or ["?"]
                cid = cons.pop(0) if len(cons) > 1 else cons[0]
                lines.append(
                    f"  f{fid}->f{cid} {kind} via "
                    f"{self._COLLECTIVE_OF.get(kind, kind)}: "
                    f"rows={sum(b.get('rows', []))} "
                    f"bytes={sum(b.get('bytes', []))}")
            return lines
        for f in dplan.fragments:
            for fid in f.consumed_fragments:
                kind = dplan.fragments[fid].output_partitioning[0]
                lines.append(
                    f"  f{fid}->f{f.fragment_id} {kind} via http")
        return lines if len(lines) > 1 else []

    def _render_analyze_device(self, dplan: DistributedPlan,
                               info: Dict) -> str:
        """Distributed EXPLAIN ANALYZE for the collective tier: the
        fragment plan with PER-SHARD rows/bytes tables from the
        program's own counters — the operator-stats table of the HTTP
        renderer collapses to shard granularity because the whole DAG
        is one fused program (there are no per-operator dispatches to
        time), but the fragment structure, stage lines, hot totals, and
        serving footer keep the same shape so the two tiers stay
        diffable."""
        from presto_tpu.exec.context import host_and_xla_line
        from presto_tpu.sql.plan import format_plan

        nparts = max(int(info.get("nparts") or 1), 1)
        per = info.get("per_shard") or {}
        frag_rows = per.get("fragments") or {}
        boundaries = info.get("boundaries", [])
        bytes_by_frag: Dict[int, List[int]] = {}
        rows_by_frag: Dict[int, List[int]] = {}
        for b in boundaries:
            acc = bytes_by_frag.setdefault(b["fragment"], [0] * nparts)
            racc = rows_by_frag.setdefault(b["fragment"], [0] * nparts)
            for s in range(min(nparts, len(b.get("bytes", [])))):
                acc[s] += int(b["bytes"][s])
                racc[s] = max(racc[s], int(b["rows"][s]))
        lines: List[str] = []
        header = (f"{'shard':<8} {'in rows':>11} {'out rows':>11} "
                  f"{'exchanged rows':>15} {'exchanged bytes':>16}")
        for f in dplan.fragments:
            fid = f.fragment_id
            out_kind, out_ch = f.output_partitioning
            lines.append(
                f"Fragment {fid} [{f.partitioning}] x{nparts} shards "
                f"=> output {out_kind}{list(out_ch) if out_ch else ''} "
                f"(device)")
            for ln in format_plan(f.root).splitlines():
                lines.append("    " + ln)
            fr = frag_rows.get(fid, {})
            lines.append("    " + header)
            lines.append("    " + "-" * len(header))
            for s in range(nparts):
                def at(key: str, table=fr) -> int:
                    vals = table.get(key) or []
                    return int(vals[s]) if s < len(vals) else 0

                xb = bytes_by_frag.get(fid, [0] * nparts)[s]
                xr = rows_by_frag.get(fid, [0] * nparts)[s]
                lines.append(
                    f"    {s:<8} {at('input_rows'):>11} "
                    f"{at('output_rows'):>11} {xr:>15} {xb:>16}")
            lines.append(
                f"    stage: input {sum(fr.get('input_rows') or [0])} "
                f"rows, output {sum(fr.get('output_rows') or [0])} rows, "
                f"exchanged {sum(bytes_by_frag.get(fid, [0]))} bytes")
        lines.extend(self._boundary_footer(dplan, boundaries))
        lines.extend(self._device_resume_footer())
        peak = max([int(v) for v in per.get("peak_live_bytes") or []]
                   or [0])
        compile_ns = int(info.get("compile_ns") or 0)
        lines.append(
            f"device program: 1 SPMD dispatch over {nparts} shards, "
            f"compiles: {0 if info.get('program_cached') else 1} "
            f"({compile_ns / 1e6:.1f} ms compile"
            + (", program cache hit" if info.get("program_cached")
               else "")
            + f"), cap_scale={info.get('cap_scale', 1)}, "
            f"peak live-intermediate ~{peak / (1 << 20):.2f} MiB/shard")
        if info.get("kernel_tiers"):
            lines.append("kernel tiers: "
                         + ", ".join(info["kernel_tiers"]))
        qs = self.query_stats or {}
        lines.append(
            f"query: jit dispatches: {qs.get('jit_dispatches', 1)}, "
            f"compiles: {qs.get('jit_compiles', 0)} "
            f"({qs.get('jit_compile_ns', 0) / 1e6:.1f} ms compile); "
            f"trace token: {self.trace_token}")
        lines.append(host_and_xla_line(qs))
        lines.append(
            f"serving: queued {qs.get('queued_s', 0.0):.3f} s, "
            f"execution {qs.get('execution_s', 0.0):.3f} s"
            + (", plan cache hit" if self.plan_cached else ""))
        return "\n".join(lines)

    def _device_resume_footer(self) -> List[str]:
        """Checkpoint/resume lines shared by BOTH EXPLAIN ANALYZE
        footers (device and HTTP-degraded renders), next to the
        exchange-boundary lines: boundaries checkpointed + bytes
        spooled, and one line per resume decision."""
        lines: List[str] = []
        if self._device_ckpts:
            total = sum(int(r.get("bytes") or 0)
                        for r in self._device_ckpts.values())
            fids = sorted(int(f) for f in self._device_ckpts)
            lines.append(
                f"device checkpoints: {len(fids)} boundaries "
                f"({', '.join(f'f{f}' for f in fids)}), "
                f"{total} bytes spooled")
        for r in self.device_resumes:
            frm = ", ".join(f"f{f}" for f in r.get("resumed_from", []))
            failed = r.get("failed_fragment", -1)
            lines.append(
                f"device resume ({r.get('mode')}): "
                + (f"failed f{failed}, " if failed >= 0 else "")
                + f"resumed from [{frm or 'none'}] — "
                f"{r.get('reason', '')}")
        return lines

    # -- cross-query result cache (server/resultcache.py) ---------------
    def _result_cache_key(self, key_sql: str):
        from presto_tpu.server import resultcache
        from presto_tpu.sql import plancache

        epochs = plancache.epochs_for(self.co.registry)
        return resultcache.cache_key(
            epochs, key_sql, self.catalog, None,
            self.session_properties), epochs

    def _serve_result_cache(self, key_sql: str) -> bool:
        """Probe the cross-query result cache; a hit serves the rows
        straight from the entry's spool pages through the existing
        spool drain — zero tasks scheduled, zero physical plans built,
        zero jit dispatches.  The query still reports as a normal
        FINISHED query (stats rollup, events, /v1/query, web UI) with
        ``resultCached=true``."""
        from presto_tpu.exec.context import QueryStats
        from presto_tpu.server import resultcache
        from presto_tpu.sql import plancache

        cfg = self._session().effective_config(self.co.config)
        if not cfg.result_cache_enabled:
            return False
        self._cfg = cfg
        if plancache.has_nondeterministic_functions(key_sql):
            # now()/current_timestamp/random()-family: two executions
            # legitimately differ — never admitted, so never probed
            return False
        key, epochs = self._result_cache_key(key_sql)
        hit = resultcache.get(key, epochs)
        if hit is None:
            return False
        self.result_cached = True
        self.plan_text = hit.plan_text
        self.column_names = list(hit.column_names)
        self.column_types = list(hit.column_types)
        self._rc_store = hit.store
        self.state = "RUNNING"
        locations = [f"spool://v1/task/{hit.task_id}/results/{i}"
                     for i in range(hit.n_locations)]
        try:
            with self._mark("execute"):
                self._drain(locations)
        except Exception:  # noqa: BLE001 - entry unreadable
            # the entry's pages vanished under us (eviction raced the
            # lookup, or the store errored past its budget): drop the
            # entry and fall through to a NORMAL execution — a cache
            # problem must never fail a query the engine can run
            if self.canceled:
                raise
            resultcache.invalidate(key)
            self.result_cached = False
            self._rc_store = None
            self.result_rows = []
            self.state = "PLANNING"
            return False
        resultcache.record_served(hit.bytes)
        self.result_cache_bytes = hit.bytes
        # the rollup a hit reports: the serving truth (rows/bytes out,
        # nothing executed).  jit/dispatch counters are genuine zeros —
        # the "zero work" pin tests and qps_run read them from here.
        qs = QueryStats(query_id=self.query_id,
                        elapsed_s=ev.now() - self.create_time)
        qs.queued_s = round(self.queued_s, 6)
        qs.execution_s = round(
            ev.now() - self.admit_time
            if self.admit_time is not None else qs.elapsed_s, 6)
        qs.output_rows = len(self.result_rows)
        qs.output_bytes = hit.bytes
        qs.result_cached = 1
        qs.result_cache_bytes = hit.bytes
        with self._stats_lock:
            self.query_stats = qs.as_dict()
            self._progress = {
                "totalSplits": 0, "queuedSplits": 0,
                "runningSplits": 0, "completedSplits": 0,
                "processedRows": len(self.result_rows),
                "processedBytes": hit.bytes,
                "peakMemoryBytes": 0,
                "progressPercent": 100.0,
            }
        return True

    def _maybe_admit_result_cache(self, dplan) -> None:
        """Admit this (successful, task-scheduled, spooled) execution's
        root-output pages into the result cache.  Strictly best-effort
        and post-drain: adoption copies the root stream(s) out of the
        query's spool directory into a stable ``rc*`` id BEFORE the
        query's own spool GC, so the entry outlives the query."""
        from presto_tpu.server import resultcache
        from presto_tpu.server.spool import query_id_of
        from presto_tpu.sql import plancache

        cfg = getattr(self, "_cfg", None) or self.co.config
        if not (cfg.result_cache_enabled and self._spool_enabled()):
            return
        if (not self._tasks_scheduled or self.canceled
                or self.error is not None):
            return
        if plancache.has_nondeterministic_functions(
                self._plan_key_sql or self.sql):
            # the ROADMAP 4i non-determinism guard: a result over
            # now()/random() is only true for THIS execution — the
            # statement re-executes on every repeat
            return
        cats = {self.catalog}
        for f in dplan.fragments:
            cats |= plancache.scan_catalogs(f.root)
        if any(c in resultcache.UNCACHEABLE_CATALOGS for c in cats):
            # live engine state (system.runtime...) has no stats epoch
            # to invalidate on — rows over it must never be replayed
            return
        with self._recovery_lock:
            root_tids = list(self._frag_tasks.get(
                dplan.root_fragment_id) or [])
        if not root_tids:
            return
        store = self.co.spool
        rc_tid = resultcache.new_task_id()
        total = 0
        try:
            for i, tid in enumerate(root_tids):
                pages = resultcache.read_complete_stream(
                    store, tid, 0,
                    max_bytes=cfg.result_cache_max_entry_bytes
                    - total)
                if pages is None:
                    raise ValueError("stream not adoptable")
                for tok, page in enumerate(pages):
                    store.write_page(rc_tid, i, tok, page)
                store.set_complete(rc_tid, i, len(pages))
                total += sum(len(p) for p in pages)
        except Exception:  # noqa: BLE001 - admission never fails a query
            try:
                store.delete_query(query_id_of(rc_tid))
            except Exception:  # noqa: BLE001
                pass
            return
        key, epochs = self._result_cache_key(
            self._plan_key_sql or self.sql)
        resultcache.put(
            key,
            resultcache.CachedResult(
                rc_tid, len(root_tids), list(self.column_names),
                list(self.column_types), len(self.result_rows), total,
                store, self.plan_text),
            epochs, cats, cfg.result_cache_capacity,
            cfg.result_cache_max_total_bytes)

    def _lookup_plan_cache(self, key_sql: str):
        """Plan-cache probe (sql/plancache.py): a hit returns
        (DistributedPlan, plan text) and means parse/analyze/optimize
        are skipped entirely for this execution."""
        from presto_tpu.sql import plancache

        cfg = self._session().effective_config(self.co.config)
        if not cfg.plan_cache_enabled:
            return None
        self._cfg = cfg
        epochs = plancache.epochs_for(self.co.registry)
        key = plancache.cache_key(epochs, key_sql, self.catalog, None,
                                  self.session_properties)
        return plancache.get(key, epochs)

    def _plan_query(self, stmt, metadata, cfg, cacheable: bool):
        """parse-tree -> DistributedPlan, consulting/filling the plan
        cache.  EXECUTE-bound statements key on (prepared text, bound
        parameters) via ``_plan_key_sql``; plain statements key on their
        raw SQL (so the pre-parse probe can hit next time)."""
        from presto_tpu.sql import plancache

        key = epochs = None
        if cacheable and cfg.plan_cache_enabled:
            epochs = plancache.epochs_for(self.co.registry)
            key = plancache.cache_key(
                epochs, self._plan_key_sql or self.sql, self.catalog,
                None, self.session_properties)
            hit = plancache.get(key, epochs)
            if hit is not None:
                dplan, self.plan_text = hit
                self.plan_cached = True
                return dplan
        with self._mark("analyze"):
            logical = Planner(metadata).plan(stmt)
        with self._mark("optimize"):
            optimized = optimize(logical, metadata, cfg)
        with self._mark("fragment"):
            dplan = Fragmenter(metadata=metadata,
                               config=cfg).fragment(optimized)
        self.plan_text = self._format_dplan(dplan)
        if key is not None:
            cats = {self.catalog}
            for f in dplan.fragments:
                cats |= plancache.scan_catalogs(f.root)
            plancache.put(key, (dplan, self.plan_text), epochs, cats,
                          cfg.plan_cache_capacity)
        return dplan

    def _run_admitted(self) -> None:
        try:
            self.state = "PLANNING"
            self._journal_transition("PLANNING")
            # pre-parse plan-cache probe: a repeated statement (same raw
            # SQL, catalog, session fingerprint, live stats epochs) goes
            # straight to scheduling — parse/analyze/optimize all
            # skipped.  Only plain queries are inserted under their raw
            # text (EXECUTE keys include the prepared text + parameters,
            # so a re-PREPARE under the same name can never alias).
            # result-cache probe first (server/resultcache.py): a hit
            # serves the repeated statement's rows straight from spool
            # pages — parse, planning, scheduling, and execution are
            # ALL skipped (the plan cache is not even consulted)
            if self._serve_result_cache(self.sql):
                self.state = "FINISHED"
                return
            cached = self._lookup_plan_cache(self.sql)
            if cached is not None:
                dplan, self.plan_text = cached
                self.plan_cached = True
                self._execute_query_dplan(dplan, analyze=False)
                self._maybe_admit_result_cache(dplan)
                self.state = "FINISHED"
                return
            with self._mark("parse"):
                stmt = parse_statement(self.sql)
            stmt = self._session_statement(stmt)
            if stmt is None:
                self.state = "FINISHED"
                return
            if self._plan_key_sql is not None and \
                    self._serve_result_cache(self._plan_key_sql):
                # EXECUTE-bound statements key on (prepared text +
                # bound parameters), so the probe runs after binding —
                # a re-PREPARE under the same name can never alias
                self.state = "FINISHED"
                return
            if isinstance(stmt, t.CallProcedure):
                self._run_procedure(stmt)
                self.state = "FINISHED"
                return
            analyze = False
            if (isinstance(stmt, t.Explain) and stmt.analyze
                    and isinstance(stmt.statement,
                                   (t.Query, t.SetOperation))):
                # distributed EXPLAIN ANALYZE: run the inner query across
                # the cluster, then roll task-level operator stats up
                # into the fragment plan (ExplainAnalyzeOperator.java:34
                # + stage-stats rollup role)
                analyze = True
                stmt = stmt.statement
            if isinstance(stmt, (t.Insert, t.CreateTableAs)):
                dwrite = self._plan_distributed_write(stmt)
                if dwrite == "done":
                    self.state = "FINISHED"
                    return
                if dwrite is not None:
                    # distributed DML: writer fragments on workers,
                    # atomic TableFinish commit (P6)
                    dplan, abort = dwrite
                    self.column_names = dplan.column_names
                    self.column_types = dplan.column_types
                    self.plan_text = self._format_dplan(dplan)
                    self.state = "SCHEDULING"
                    try:
                        with self._mark("schedule"):
                            root_locations = self._schedule(dplan)
                        self.state = "RUNNING"
                        self._start_sampler()
                        with self._mark("execute"):
                            self._drain(root_locations)
                        self._collect_stats()
                    except Exception:
                        abort()
                        raise
                    # the write changed the target catalog's data: bump
                    # its stats epoch so cached plans over it re-plan
                    if getattr(self, "_write_catalog", None):
                        from presto_tpu.sql import plancache

                        plancache.epochs_for(self.co.registry).bump(
                            self._write_catalog)
                    self.state = "FINISHED"
                    return
            if not isinstance(stmt, (t.Query, t.SetOperation)):
                # DDL/DML/metadata statements run coordinator-side
                # (the reference's DataDefinitionExecution path,
                # presto-main/.../execution/DataDefinitionExecution.java)
                self._run_utility(stmt)
                self.state = "FINISHED"
                return
            metadata = Metadata(self.co.registry, self.catalog)
            cfg = self._session().effective_config(self.co.config)
            self._cfg = cfg
            dplan = self._plan_query(stmt, metadata, cfg,
                                     cacheable=not analyze)
            self._execute_query_dplan(dplan, analyze)
            if not analyze:
                self._maybe_admit_result_cache(dplan)
            self.state = "FINISHED"
        except _CoordinatorKilled:
            # chaos: this coordinator was process-level killed mid-query
            # — stop with NO side effects (the finally's killed guard
            # skips events, cancel fan-out, and spool GC); the standby
            # adopts this query from the journal
            pass
        except Exception as e:  # noqa: BLE001 - query failure surface
            # keep a more specific error set by a killer (low-memory,
            # kill_query) over the generic drain abort
            self.error = self.error or f"{e}"
            self.co.log(traceback.format_exc())
            self.state = "FAILED"
        finally:
            if getattr(self.co, "killed", False):
                self._monitor_stop.set()
                return
            # release worker-side state the drain did not consume: a
            # TopN merge stops early, and failed queries strand tasks
            # mid-run — cancel fans out DELETE /v1/query/{id} so output
            # buffers are freed and blocked producers unblock
            # (SqlQueryScheduler abort/cancel role).  The client is
            # unblocked first and the fan-out only runs when worker
            # tasks were actually created.
            # observability settles BEFORE the client is unblocked: the
            # stats rollup is grabbed while worker-side state still
            # exists (failed queries report too) and the completion
            # event hits every listener, so anything that observed the
            # query finish can read its stats/events immediately
            if self._tasks_scheduled:
                try:
                    self._collect_stats()
                except Exception:  # noqa: BLE001 - stats are best-effort
                    pass
            # terminal journal write (coordinator HA) runs BEFORE the
            # spool GC below so a FINISHED query's root pages can be
            # adopted into their durable ha* stream first
            self._journal_terminal()
            self._fire_completed()
            self.rows_done.set()
            self._monitor_stop.set()
            if self._tasks_scheduled:
                self._cancel_worker_tasks()
            # spool GC: this query's pages are dead weight the moment
            # the drain settled (completion, failure, and cancel alike);
            # leftovers from unreachable workers fall to the
            # coordinator-start orphan sweep
            if self._tasks_scheduled and self.co.spool is not None:
                try:
                    self.co.spool.delete_query(self.query_id)
                except Exception:  # noqa: BLE001 - GC is best-effort
                    pass

    @staticmethod
    def _format_dplan(dplan: DistributedPlan) -> str:
        """Fragment-by-fragment plan rendering (the webapp plan.html /
        EXPLAIN (TYPE DISTRIBUTED) view)."""
        from presto_tpu.sql.plan import format_plan

        lines = []
        for f in dplan.fragments:
            out_kind, out_ch = f.output_partitioning
            lines.append(
                f"Fragment {f.fragment_id} [{f.partitioning}] "
                f"=> output {out_kind}{list(out_ch) if out_ch else ''}")
            for ln in format_plan(f.root).splitlines():
                lines.append("    " + ln)
        return "\n".join(lines)

    def _fetch_task_info(self, task_id: str, wuri: str,
                         max_error_duration_s: Optional[float] = None
                         ) -> Dict:
        resp = self.co.http.request(
            f"{wuri}/v1/task/{task_id}", headers=self._internal_headers(),
            timeout=10, task_id=task_id, description="task status",
            trace_token=self.trace_token,
            max_error_duration_s=max_error_duration_s)
        return resp.json()

    def _fetch_task_infos(self, placements,
                          join_timeout_s: float = 15.0,
                          request_timeout_s: float = 10.0,
                          activity: bool = False
                          ) -> Dict[int, List[Dict]]:
        """Fetch task info for every placement, one thread per worker so
        one hung worker costs exactly one timeout (never the whole
        sweep); budget 0 per request, best-effort per task.  Shared by
        the final post-drain collection and the live sampler (which
        passes a tighter timeout so one hung worker costs one sample).
        ``activity`` asks for each task's host-activity intervals too
        (the final collection only: they can be 4096 to a task).
        spool:// placements have no task to report."""
        headers = self._internal_headers()
        if activity:
            headers[HOST_ACTIVITY_HEADER] = "1"
        by_uri: Dict[str, List[Tuple[int, str]]] = {}
        for fid, tid, uri in placements:
            if uri.startswith("spool://"):
                continue
            by_uri.setdefault(uri, []).append((fid, tid))
        results: List[Tuple[int, Dict]] = []
        results_lock = threading.Lock()

        def fetch_worker(uri: str, tasks) -> None:
            for fid, tid in tasks:
                try:
                    resp = self.co.http.request(
                        f"{uri}/v1/task/{tid}", headers=dict(headers),
                        timeout=request_timeout_s, task_id=tid,
                        description="task status",
                        trace_token=self.trace_token,
                        max_error_duration_s=0.0)
                    info = resp.json()
                except Exception:  # noqa: BLE001 - worker may be gone
                    return   # same host: further fetches will hang too
                with results_lock:
                    results.append((fid, info))

        threads = [threading.Thread(target=fetch_worker, args=(u, ts),
                                    daemon=True,
                                    name=f"stats-{self.query_id}")
                   for u, ts in by_uri.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=join_timeout_s)
        infos: Dict[int, List[Dict]] = {}
        with results_lock:
            for fid, info in results:
                infos.setdefault(fid, []).append(info)
        return infos

    def _rollup_stats(self, infos: Dict[int, List[Dict]], placements
                      ) -> Tuple[Dict, Dict, Dict]:
        """TaskStats -> StageStats (per fragment) -> QueryStats from one
        sweep of task infos; pure aggregation, shared by the final
        collection and every live-sampler fold."""
        from presto_tpu.exec.context import (
            QueryStats, StageStats, TaskStats,
        )

        n_tasks: Dict[int, int] = {}
        for fid, _tid, _uri in placements:
            n_tasks[fid] = n_tasks.get(fid, 0) + 1
        stage_stats: Dict[int, Dict] = {}
        task_stats: Dict[int, List[Dict]] = {}
        qs = QueryStats(query_id=self.query_id,
                        elapsed_s=ev.now() - self.create_time)
        for fid in sorted(infos):
            st = StageStats(fragment_id=fid, tasks=n_tasks.get(fid, 0))
            for info in infos[fid]:
                ts_dict = info.get("taskStats") or {}
                task_stats.setdefault(fid, []).append(ts_dict)
                st.add_task(TaskStats.from_dict(ts_dict))
            stage_stats[fid] = st.as_dict()
            qs.add_stage(st)
        # serving-tier split: time spent queued for admission vs
        # executing (admission -> now); a non-dispatched query reports
        # queued 0 and elapsed as execution
        qs.queued_s = round(self.queued_s, 6)
        qs.execution_s = round(
            ev.now() - self.admit_time if self.admit_time is not None
            else qs.elapsed_s, 6)
        qs_dict = qs.as_dict()
        if self.exchange_modes:
            qs_dict["exchange_modes"] = dict(self.exchange_modes)
        return stage_stats, task_stats, qs_dict

    def _collect_stats(self) -> None:
        """Fetch every placement's task info ONCE and roll it up:
        TaskStats -> StageStats (per fragment) -> QueryStats.  Runs
        right after the drain, before the cancel fan-out can tear the
        tasks down; best-effort per task (a dead worker's tasks simply
        do not report).  Feeds distributed EXPLAIN ANALYZE, the
        /v1/query detail payload, QueryCompletedEvent, system.runtime,
        and tools/query_profile.py.  The live sampler folds the same
        rollup mid-query; this final collection supersedes it."""
        if self._stats_collected or not self._tasks_scheduled:
            return
        self._stats_collected = True
        with self._recovery_lock:
            placements = list(self._placements)
        infos = self._fetch_task_infos(placements, activity=True)
        cfg = getattr(self, "_cfg", None) or self.co.config
        with self._stats_lock:
            self._task_infos = infos
            (self.stage_stats, self.task_stats,
             self.query_stats) = self._rollup_stats(infos, placements)
            if cfg.stats_sampling_enabled:
                # settle the progress surfaces on the final rollup: the
                # last mid-query sample can predate the root task's
                # finish, and a fast query may never have been sampled
                self._append_sample(infos, placements,
                                    self.query_stats, cfg)

    # -- live stats sampling (StatementStats/QueryProgressStats role) ---
    def _start_sampler(self) -> None:
        """Poll every placement's task info at ``stats_sample_interval_s``
        while the query is RUNNING, folding each sweep into the live
        StageStats/QueryStats rollup and appending one sample to the
        bounded time-series ring — progress becomes observable
        MID-query (timeseries endpoint, client-protocol stats object,
        system.runtime, web UI).  Disabled =
        PR 8's single post-drain collection, exactly."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        if (not cfg.stats_sampling_enabled or self._sampler_started
                or not self._tasks_scheduled):
            return
        self._sampler_started = True
        threading.Thread(
            target=self._sampler_loop,
            args=(max(cfg.stats_sample_interval_s, 0.02), cfg),
            daemon=True,
            name=f"stats-sampler-{self.query_id}").start()

    def _sampler_loop(self, interval_s: float, cfg) -> None:
        while not self._monitor_stop.wait(interval_s):
            if getattr(self.co, "killed", False):
                return
            if self._stats_collected or self.state != "RUNNING":
                return
            try:
                self._sample_tick(cfg)
            except Exception:  # noqa: BLE001 - sampling is advisory
                pass

    def _sample_tick(self, cfg) -> None:
        with self._recovery_lock:
            placements = list(self._placements)
        if not placements:
            return
        # per-worker bounded timeout: one hung worker costs one sample,
        # never the sampler cadence of every other worker
        infos = self._fetch_task_infos(placements, join_timeout_s=2.5,
                                       request_timeout_s=2.0)
        if not infos:
            return
        stage_stats, task_stats, qs = self._rollup_stats(infos,
                                                         placements)
        with self._stats_lock:
            if self._stats_collected:
                return   # final collection already superseded sampling
            self.stage_stats = stage_stats
            self.task_stats = task_stats
            self.query_stats = qs
            self._task_infos = infos
            self._append_sample(infos, placements, qs, cfg)

    def _append_sample(self, infos, placements, qs: Dict, cfg) -> None:
        """One time-series sample + the latest client-protocol progress
        snapshot.  Cumulative counters are clamped monotonic against the
        previous sample: a worker missing one sweep must read as stale,
        never as regressing progress."""
        flat = [i for lst in infos.values() for i in lst]
        total = len(placements)
        completed = sum(1 for i in flat
                        if i.get("state") == "FINISHED")
        running = sum(1 for i in flat if i.get("state") == "RUNNING")
        in_rows = qs.get("input_rows", 0)
        out_rows = qs.get("output_rows", 0)
        out_bytes = qs.get("output_bytes", 0)
        prev = self.timeseries[-1] if self.timeseries else None
        if prev is not None:
            completed = max(completed, prev["splits_completed"])
            in_rows = max(in_rows, prev["input_rows"])
            out_rows = max(out_rows, prev["output_rows"])
            out_bytes = max(out_bytes, prev["output_bytes"])
        sample = {
            "t": round(ev.now(), 6),
            "state": self.state,
            "splits_total": total,
            "splits_queued": max(total - running - completed, 0),
            "splits_running": running,
            "splits_completed": completed,
            "input_rows": in_rows,
            "output_rows": out_rows,
            "output_bytes": out_bytes,
            "peak_memory_bytes": qs.get("peak_memory_bytes", 0),
            "exchange_backlog": max(
                qs.get("exchange_fetched", 0)
                - qs.get("exchange_consumed", 0), 0),
            "pages_enqueued": qs.get("pages_enqueued", 0),
            "pages_spooled": qs.get("pages_spooled", 0),
            "jit_dispatches": qs.get("jit_dispatches", 0),
        }
        self.timeseries.append(sample)
        cap = max(int(cfg.stats_timeseries_capacity), 1)
        if len(self.timeseries) > cap:
            del self.timeseries[:len(self.timeseries) - cap]
        self._progress = {
            "totalSplits": total,
            "queuedSplits": sample["splits_queued"],
            "runningSplits": running,
            "completedSplits": completed,
            "processedRows": out_rows,
            "processedBytes": out_bytes,
            "peakMemoryBytes": sample["peak_memory_bytes"],
            "progressPercent": (round(100.0 * completed / total, 2)
                                if total else 0.0),
        }

    def _mark(self, name: str):
        """Record one coordinator phase span (presto_tpu.spans) around a
        ``with`` block; marks feed the /v1/query/{id}/spans tree."""
        import contextlib

        @contextlib.contextmanager
        def cm():
            t0 = ev.now()
            try:
                yield
            finally:
                self._marks[name] = (t0, ev.now())

        return cm()

    def spans(self, activities: bool = True) -> Dict:
        """The timed span tree: query -> coordinator phases -> per-stage
        -> per-task-attempt -> host activity, from coordinator-owned
        timestamps plus the task infos (start/end lifecycle, operator
        stats, and a finished task's activity intervals; live sampler
        mid-query, final rollup after).  ``activities=False`` leaves the
        intervals out and keeps their totals: the completed event's
        copy."""
        from presto_tpu.spans import build_span_tree

        with self._stats_lock:
            task_stats = {fid: [dict(ts) for ts in lst]
                          for fid, lst in self.task_stats.items()}
            task_extras = {
                info.get("taskId"): {
                    "operators": info.get("operatorStats"),
                    "activity": info.get("hostActivity")}
                for infos in self._task_infos.values() for info in infos}
            marks = dict(self._marks)
        return build_span_tree(
            self.query_id, self.trace_token, self.create_time,
            self.end_time, marks, task_stats,
            admit_time=self.admit_time, task_extras=task_extras,
            activities=activities)

    def _top_operator(self) -> str:
        """Name of the hottest operator by exclusive wall across every
        reporting task (the slow-query log's one-line attribution)."""
        best, best_wall = "", -1
        with self._stats_lock:
            infos = [i for lst in self._task_infos.values() for i in lst]
        for info in infos:
            for s in info.get("operatorStats") or []:
                wall = s.get("wall_ns", 0) + s.get("finish_wall_ns", 0)
                if wall > best_wall:
                    best, best_wall = s.get("operator", ""), wall
        return best

    def _render_analyze(self, dplan: DistributedPlan) -> str:
        """Fragment plan + per-operator stats aggregated across each
        fragment's tasks from the collected rollup: rows summed, wall =
        slowest task (the StageStats / PlanPrinter
        textDistributedPlan-with-stats role).  Renders the SAME counter
        set as the local tier's explain_analyze_text — jit dispatches/
        compiles, pre-reduce rows, peak memory — so the two tiers stay
        diffable."""
        from presto_tpu.exec.context import (
            host_and_xla_line as _host_and_xla_line,
            hot_operator_lines as _hot_operator_lines,
            kernel_tier_lines as _kernel_tier_lines,
            segment_line as _segment_line,
            scan_cache_line as _scan_cache_line,
        )
        from presto_tpu.sql.plan import format_plan

        self._collect_stats()
        lines: List[str] = []
        # every aggregated operator across fragments, for the
        # hot-operator footer (ranked by exclusive wall)
        hot: List[Dict] = []
        header = (f"{'operator':<36} {'tasks':>5} {'in rows':>11} "
                  f"{'out rows':>11} {'wall ms':>9} {'compile ms':>10} "
                  f"{'jit disp':>8} {'jit comp':>8} {'prereduce':>9}")
        for f in dplan.fragments:
            fid = f.fragment_id
            with self._recovery_lock:
                n_tasks = sum(1 for pf, _, _ in self._placements
                              if pf == fid)
            out_kind, out_ch = f.output_partitioning
            lines.append(
                f"Fragment {fid} [{f.partitioning}] "
                f"x{n_tasks} tasks => output "
                f"{out_kind}{list(out_ch) if out_ch else ''}")
            for ln in format_plan(f.root).splitlines():
                lines.append("    " + ln)
            # aggregate operator stats by operator NAME: concurrent
            # feed drivers append stats in nondeterministic order, so
            # list position is not comparable across tasks
            agg: Dict[str, Dict] = {}
            n_reporting = 0
            for info in self._task_infos.get(fid, []):
                stats = info.get("operatorStats") or []
                if stats:
                    n_reporting += 1
                for s in stats:
                    wall = s["wall_ns"] + s["finish_wall_ns"]
                    a = agg.get(s["operator"])
                    if a is None:
                        a = dict(s)
                        a["wall_ns"] = wall
                        a.setdefault("jit_compile_ns", 0)
                        agg[s["operator"]] = a
                    else:
                        a["input_rows"] += s["input_rows"]
                        a["output_rows"] += s["output_rows"]
                        a["wall_ns"] = max(a["wall_ns"], wall)
                        a["jit_dispatches"] += s.get("jit_dispatches", 0)
                        a["jit_compiles"] += s.get("jit_compiles", 0)
                        a["jit_compile_ns"] += s.get("jit_compile_ns", 0)
                        a["prereduce_rows"] += s.get("prereduce_rows", 0)
            lines.append("    " + header)
            lines.append("    " + "-" * len(header))
            for a in agg.values():
                wall_ms = a["wall_ns"] / 1e6
                lines.append(
                    f"    {a['operator']:<36} {n_reporting:>5} "
                    f"{a['input_rows']:>11} {a['output_rows']:>11} "
                    f"{wall_ms:>9.1f} "
                    f"{a.get('jit_compile_ns', 0) / 1e6:>10.1f} "
                    f"{a.get('jit_dispatches', 0):>8} "
                    f"{a.get('jit_compiles', 0):>8} "
                    f"{a.get('prereduce_rows', 0):>9}")
                hot.append(a)
            st = self.stage_stats.get(fid)
            if st:
                lines.append(
                    f"    stage: wall {st['wall_ns'] / 1e6:.1f} ms "
                    f"(sum {st['total_wall_ns'] / 1e6:.1f}), peak memory "
                    f"{st['peak_memory_bytes'] / (1 << 20):.1f} MiB, "
                    f"jit dispatches: {st['jit_dispatches']}, "
                    f"compiles: {st['jit_compiles']}, "
                    f"prereduce rows: {st['prereduce_rows']}, "
                    f"exchange pages "
                    f"{st['exchange_fetched']}f/"
                    f"{st['exchange_consumed']}c/"
                    f"{st['exchange_purged']}p")
        lines.extend(self._boundary_footer(dplan))
        lines.extend(self._device_resume_footer())
        lines.extend(_hot_operator_lines(hot))
        lines.extend(_kernel_tier_lines(
            s for infos in self._task_infos.values() for info in infos
            for s in info.get("operatorStats") or []))
        qs = self.query_stats
        if qs:
            lines.append(
                f"query: peak memory "
                f"{qs['peak_memory_bytes'] / (1 << 20):.1f} MiB; "
                f"jit dispatches: {qs['jit_dispatches']}, "
                f"compiles: {qs['jit_compiles']} "
                f"({qs.get('jit_compile_ns', 0) / 1e6:.1f} ms compile, "
                f"{max(qs.get('total_wall_ns', 0) - qs.get('jit_compile_ns', 0), 0) / 1e6:.1f}"
                f" ms execute); "
                f"prereduce rows: {qs['prereduce_rows']}; "
                f"trace token: {self.trace_token}")
            lines.append(_host_and_xla_line(qs))
            lines.append(_segment_line(qs))
            lines.append(_scan_cache_line(qs))
            lines.append(
                f"serving: queued {qs.get('queued_s', 0.0):.3f} s, "
                f"execution {qs.get('execution_s', 0.0):.3f} s"
                + (", plan cache hit" if self.plan_cached else ""))
        return "\n".join(lines)

    def _wait_for_workers(self) -> List[Tuple[str, str]]:
        """Block until the minimum cluster size is present or the wait
        expires (ClusterSizeMonitor.java role)."""
        need = max(1, self.co.min_workers)
        deadline = time.monotonic() + self.co.min_workers_wait_s
        while True:
            if self.canceled:
                raise RuntimeError("Query killed")
            workers = self.co.nodes.alive_nodes()
            if len(workers) >= need:
                # spread consecutive tasks across topology domains
                return self.co.nodes.topology_ordered(workers)
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"Insufficient active worker nodes: have "
                    f"{len(workers)}, need {need}")
            time.sleep(0.05)

    def _internal_headers(self) -> Dict[str, str]:
        h = (dict(self.co.internal_auth.header())
             if self.co.internal_auth is not None else {})
        h["X-Presto-Trace-Token"] = self.trace_token
        return h

    def _cancel_worker_tasks(self) -> None:
        """DELETE fan-out over every responsive node.  Best-effort, but
        no longer silent: per-endpoint failures are logged through the
        error tracker, and retries are bounded by the
        ``cancel_fanout_budget_s`` error budget (config/session knob) so
        one hung worker cannot stall the fan-out for the full transport
        budget."""
        if getattr(self.co, "killed", False):
            # a killed coordinator must not reach out: worker tasks
            # keep producing into the spool for the standby to adopt
            return
        cfg = getattr(self, "_cfg", None) or self.co.config
        budget = min(cfg.cancel_fanout_budget_s,
                     cfg.remote_request_max_error_duration_s)
        for _nid, uri in self.co.nodes.responsive_nodes():
            try:
                self.co.http.request(
                    f"{uri}/v1/query/{self.query_id}", method="DELETE",
                    headers=self._internal_headers(), timeout=5,
                    description="cancel fan-out",
                    max_error_duration_s=budget)
            except Exception as e:  # noqa: BLE001 - best-effort cleanup
                self.co.log(f"cancel fan-out for {self.query_id} to "
                            f"{uri} failed: {e}")

    # -- scheduling -----------------------------------------------------
    def _task_count(self, frag, n_workers: int) -> int:
        cfg = getattr(self, "_cfg", None) or self.co.config
        if frag.partitioning == "single":
            return 1
        if frag.partitioning == "scaled":
            # scaled writers (P6): size the writer-task count to the
            # estimated volume — small INSERTs get one writer, bulk CTAS
            # scales to every worker (writerMinSize role, row-based;
            # scaled_writer_rows_per_task session property)
            rows = frag.scale_rows
            if rows is None:
                return max(1, n_workers)
            need = int(rows // max(cfg.scaled_writer_rows_per_task, 1)) + 1
            return max(1, min(n_workers, need))
        if frag.partitioning == "hash" and cfg.hash_partition_count > 0:
            return cfg.hash_partition_count
        return max(1, n_workers)

    def _schedule(self, dplan: DistributedPlan) -> List[str]:
        workers = self._wait_for_workers()
        n_workers = len(workers)
        if not self.exchange_modes:
            # every boundary of a task-scheduled plan rides the HTTP
            # data plane (spool-backed when spooling is on)
            self.exchange_modes = {"http": sum(
                len(f.consumed_fragments) for f in dplan.fragments)}
        counts = {f.fragment_id: self._task_count(f, n_workers)
                  for f in dplan.fragments}
        consumers: Dict[int, int] = {}  # producer fid -> consumer fid
        for f in dplan.fragments:
            for fid in f.consumed_fragments:
                consumers[fid] = f.fragment_id
        self._dplan = dplan
        self._consumers = consumers

        # HTTP degrade of a checkpointed mesh query: every
        # spool-complete checkpointed fragment becomes a spool:// leaf
        # (zero re-execution), and nothing beneath it is scheduled
        ckpt_leaves, ckpt_shadowed = self._degrade_schedule_skips(
            dplan, counts, consumers)
        # producers first (fragments list is already topological)
        task_uris: Dict[int, List[str]] = {}
        for frag in dplan.fragments:
            if frag.fragment_id in ckpt_shadowed:
                task_uris[frag.fragment_id] = []
                continue
            if frag.fragment_id in ckpt_leaves:
                from presto_tpu.server.spool import spool_location

                tid = self._device_completed[frag.fragment_id]
                uris = [spool_location(tid)]
                task_uris[frag.fragment_id] = uris
                self._frag_tasks[frag.fragment_id] = [tid]
                self._task_uris[frag.fragment_id] = uris
                continue
            n_tasks = counts[frag.fragment_id]
            cons_fid = consumers.get(frag.fragment_id)
            if cons_fid is None:
                n_out = 1          # root: coordinator drains partition 0
                broadcast = False
            else:
                n_out = counts[cons_fid]
                broadcast = frag.output_partitioning[0] == "broadcast"
            remote: Dict[int, List[str]] = {}
            for fid in frag.consumed_fragments:
                remote[fid] = task_uris[fid]
            uris = []
            for i in range(n_tasks):
                task_id = f"{self.query_id}.{frag.fragment_id}.{i}"
                # each consumer task i polls ITS OWN partition i on every
                # producer task; producer URIs carry a {part} placeholder
                # the consumer's index resolves.  A worker that started
                # draining between the snapshot and now answers 503 —
                # fall over to the next worker instead of failing the
                # query (the graceful-shutdown race).
                last_error = None
                for attempt in range(n_workers):
                    _, wuri = workers[(i + attempt) % n_workers]
                    try:
                        self._create_remote_task(
                            wuri, task_id, frag, (i, n_tasks), remote,
                            n_out, broadcast, consumer_index=i)
                        break
                    except RemoteRequestError as e:
                        if e.retryable:
                            # draining worker (503) or node died between
                            # heartbeat and now: fall over to the next
                            # worker instead of failing the query
                            last_error = e
                            continue
                        body = ""
                        if isinstance(e.cause, urllib.error.HTTPError):
                            body = e.cause.read().decode(
                                "utf-8", "replace")[:500]
                        raise RuntimeError(
                            f"task create failed on {wuri}: "
                            f"{e}{' ' + body if body else ''}") from e
                else:
                    raise RuntimeError(
                        "no worker accepted task "
                        f"{task_id}: {last_error}")
                uris.append(
                    f"{wuri}/v1/task/{task_id}/results/{{part}}")
                self._placements.append(
                    (frag.fragment_id, task_id, wuri))
                # the recreate recipe for mid-query recovery — leaf
                # reschedule, whole-stage retry, and speculation all
                # re-create from this
                self._task_specs[task_id] = {
                    "frag": frag, "scan_shard": (i, n_tasks),
                    "remote": remote, "n_out": n_out,
                    "broadcast": broadcast, "consumer_index": i,
                    "base": task_id, "index": i,
                    "created_at": time.monotonic()}
                self._attempts[task_id] = 0
            task_uris[frag.fragment_id] = uris
            self._frag_tasks[frag.fragment_id] = [
                t for f, t, _ in self._placements
                if f == frag.fragment_id]
            self._task_uris[frag.fragment_id] = uris
        roots = [u.format(part=0)
                 for u in task_uris[dplan.root_fragment_id]]
        self._root_orig = {loc: loc for loc in roots}
        self._start_recovery_monitor()
        # placements are final: journal the RUNNING snapshot (plan +
        # placements + attempts) so a standby can adopt mid-flight
        self._journal_transition("RUNNING")
        return roots

    def _degrade_schedule_skips(self, dplan: DistributedPlan,
                                counts: Dict[int, int],
                                consumers: Dict[int, int]
                                ) -> Tuple[set, set]:
        """(spool-leaf fids, shadowed fids) for the HTTP-degrade
        scheduler.  A checkpointed fragment qualifies as a leaf only
        when its spooled partition fan-out matches what THIS schedule
        would give its consumer (worker count may have changed since
        the checkpoint) and the spool verifies complete; its entire
        producer subtree is then shadowed — not scheduled at all."""
        if not self._device_completed:
            return set(), set()
        frag_by_id = {f.fragment_id: f for f in dplan.fragments}
        leaves: set = set()
        for fid, tid in self._device_completed.items():
            if fid == dplan.root_fragment_id or fid not in frag_by_id:
                continue
            rec = self._device_ckpts.get(str(fid)) or {}
            cons = consumers.get(fid)
            n_out = counts[cons] if cons is not None else 1
            if int(rec.get("n_out") or -1) != n_out:
                continue
            try:
                if not self.co.spool.is_complete(tid, n_out):
                    continue
            except Exception:  # noqa: BLE001 - schedule normally
                continue
            leaves.add(fid)
        shadowed: set = set()
        stack = list(leaves)
        while stack:
            fid = stack.pop()
            for p in frag_by_id[fid].consumed_fragments:
                if p not in shadowed and p not in leaves:
                    shadowed.add(p)
                    stack.append(p)
        return leaves, shadowed

    # -- mid-query task recovery ----------------------------------------
    def _start_recovery_monitor(self) -> None:
        """Watch the failure detector for workers hosting this query's
        tasks, and per-stage task progress for stragglers.  A dead
        worker's leaf tasks are rescheduled in place; its non-leaf tasks
        trigger whole-stage retry (the producer subtree is re-created
        under fresh attempt ids); stragglers get speculative clones."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        if not (cfg.task_recovery_enabled
                or cfg.speculative_execution_enabled):
            return
        threading.Thread(
            target=self._monitor_loop,
            args=(max(cfg.task_recovery_interval_s, 0.05),),
            daemon=True, name=f"recovery-{self.query_id}").start()

    def _spool_enabled(self) -> bool:
        cfg = getattr(self, "_cfg", None) or self.co.config
        return cfg.exchange_spooling_enabled and self.co.spool is not None

    def _monitor_loop(self, interval_s: float) -> None:
        cfg = getattr(self, "_cfg", None) or self.co.config
        while not self._monitor_stop.wait(interval_s):
            if getattr(self.co, "killed", False):
                return
            if self.state not in ("SCHEDULING", "RUNNING"):
                return
            try:
                if cfg.task_recovery_enabled:
                    self._recovery_tick()
                    if self._spool_enabled():
                        self._drain_worker_tick()
                        self._failed_task_tick()
                if cfg.speculative_execution_enabled:
                    self._speculation_tick()
            except Exception as e:  # noqa: BLE001 - fail fast
                self.error = self.error or f"{e}"
                self.co.log(f"task recovery for {self.query_id} "
                            f"failed: {e}")
                self.cancel()   # unblocks the drain
                return

    def _probe_alive(self, uri: str) -> bool:
        """One direct health probe, outside the failure detector."""
        try:
            with urllib.request.urlopen(f"{uri}/v1/info",
                                        timeout=1.5) as resp:
                return resp.status == 200
        except Exception:  # noqa: BLE001 - probe is the question
            return False

    def _recovery_tick(self) -> None:
        dead = self.co.nodes.dead_uris()
        with self._recovery_lock:
            targets = sorted(
                {uri for _, _, uri in self._placements
                 if uri in dead and uri not in self._recovered_uris})
        for uri in targets:
            # flap guard: heartbeats blip on an overloaded host without
            # the worker being gone.  Recovery cancels and re-creates
            # whole subtrees, so it only starts once a direct probe
            # confirms the node is really unreachable; a worker whose
            # heartbeat resumes leaves dead_uris() on the next beat and
            # is never recovered at all.
            if self._probe_alive(uri):
                continue
            self._recover_worker(uri)

    def _recover_worker(self, dead_uri: str) -> None:
        """Reschedule every task this query had on ``dead_uri``.

        **Spooled exchange** (exchange_spooling_enabled): output buffers
        survive their task in the spool, so nothing upstream re-runs —
        a lost task whose output is complete in the spool is replaced by
        repointing its consumers at the spool (zero re-execution), and a
        task lost mid-production re-runs ALONE, reading its producers
        back from the spool.  Spool verification failures fall back to
        the cascading path below.

        **Cascading** (spooling off, the PR 5 stance): leaf fragments
        (no remote sources) whose consumers have not yet consumed their
        pages are re-created in place — the replacement regenerates the
        same deterministic output from its scan shard.  Everything else
        — non-leaf tasks, and leaf tasks whose consumers already
        consumed pages — goes through whole-stage retry of the producer
        subtree."""
        with self._recovery_lock:
            if dead_uri in self._recovered_uris:
                return
            self._recovered_uris.add(dead_uri)
            affected = [(fid, tid) for fid, tid, uri in self._placements
                        if uri == dead_uri]
        if not affected or self._dplan is None:
            return
        self.recovery_rounds += 1
        self.co.event_bus.task_recovery(ev.TaskRecoveryEvent(
            self.query_id, self.trace_token, dead_uri,
            tuple(tid for _, tid in affected), ev.now()))
        if self._spool_enabled():
            try:
                self._recover_worker_spooled(dead_uri, affected)
                self._journal("RUNNING")
                return
            except _SpoolUnavailable as e:
                # spool verification failed (missing object, read
                # error): the durable copy cannot be trusted, so fall
                # back to PR 5 cascading retry — correctness over the
                # zero-re-run guarantee
                self.co.log(f"spool recovery for {dead_uri} failed "
                            f"({e}); falling back to cascading retry")
        self._recover_worker_cascading(dead_uri, affected)
        self._journal("RUNNING")

    def _recover_worker_cascading(self, dead_uri: str,
                                  affected) -> None:
        frag_by_id = {f.fragment_id: f for f in self._dplan.fragments}
        retry_fids = sorted({fid for fid, _ in affected
                             if frag_by_id[fid].consumed_fragments})
        # root-fragment leaves also go through stage retry: the drain can
        # discard and re-pull a restarted location from token 0, which
        # the token-0-only relocation path cannot once pages flowed
        for fid, _tid in affected:
            if frag_by_id[fid].consumed_fragments:
                continue
            if self._consumers.get(fid) is None:
                retry_fids.append(fid)
        restarted: set = set()
        if retry_fids:
            restarted = self._retry_stages(set(retry_fids), dead_uri)
        leaf = [(fid, tid) for fid, tid in affected
                if not frag_by_id[fid].consumed_fragments
                and fid not in restarted]
        if leaf:
            self._reschedule_leaf_tasks(leaf, dead_uri)

    def _reschedule_leaf_tasks(self, affected, dead_uri: str) -> None:
        dead = self.co.nodes.dead_uris() | {dead_uri}
        survivors = [uri for _, uri in self.co.nodes.alive_nodes()
                     if uri not in dead]
        if not survivors:
            raise RuntimeError(
                f"Worker {dead_uri} died mid-query and no surviving "
                f"worker remains to reschedule its tasks")
        for k, (fid, tid) in enumerate(affected):
            spec = self._task_specs[tid]
            new_uri = survivors[k % len(survivors)]
            self._create_remote_task(
                new_uri, tid, spec["frag"], spec["scan_shard"],
                spec["remote"], spec["n_out"], spec["broadcast"],
                consumer_index=spec["consumer_index"])
            old_prefix = f"{dead_uri}/v1/task/{tid}/results/"
            new_prefix = f"{new_uri}/v1/task/{tid}/results/"
            with self._recovery_lock:
                self._placements = [
                    (f, t, new_uri if t == tid else u)
                    for f, t, u in self._placements]
                self._task_uris[fid][spec["index"]] = \
                    new_prefix + "{part}"
            self.co.log(f"recovery: rescheduled {tid} from {dead_uri} "
                        f"to {new_uri}")
            self._repoint_consumers(fid, tid, dead_uri,
                                    old_prefix, new_prefix)

    def _repoint_consumers(self, fid: int, tid: str, dead_uri: str,
                           old_prefix: str, new_prefix: str) -> None:
        cons_fid = self._consumers.get(fid)
        if cons_fid is None:
            # root fragment: the coordinator's own drain is the consumer
            with self._recovery_lock:
                self._relocations[old_prefix + "0"] = new_prefix + "0"
                for orig, cur in self._root_orig.items():
                    if cur == old_prefix + "0":
                        self._root_orig[orig] = new_prefix + "0"
            return
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        body = json.dumps({"old_prefix": old_prefix,
                           "new_prefix": new_prefix}).encode("utf-8")
        with self._recovery_lock:
            consumers = [(t, u) for f, t, u in self._placements
                         if f == cons_fid and u != dead_uri]
        for ctid, curi in consumers:
            resp = self.co.http.request(
                f"{curi}/v1/task/{ctid}/remote-sources", method="POST",
                data=body, headers=headers, timeout=10, task_id=ctid,
                description="remote-source repoint")
            status = resp.json().get("status")
            if status == "delivered":
                # the consumer already consumed the dead producer's
                # pages: an in-place replacement would double-count, so
                # restart the consumer stage (whole-stage retry) — its
                # new attempt re-pulls every producer from token 0
                self.co.log(
                    f"recovery: consumer {ctid} already consumed pages "
                    f"from {tid}; escalating stage {cons_fid} to "
                    f"whole-stage retry")
                self._retry_stages({cons_fid}, dead_uri)
                return

    # -- spooled recovery (cascade-free: output outlives the task) ------
    def _spool_remote(self, spec: Dict) -> Dict[int, List[str]]:
        """Remote-source templates reading every producer stream from
        the spool.  Always safe under write-through spooling: a live
        producer's stream fills progressively, a finished producer's is
        complete, and an already-acked page is still there — so a fresh
        attempt can re-pull from token 0 with zero producer re-runs."""
        from presto_tpu.server.spool import spool_location

        return {pfid: [spool_location(ptid)
                       for ptid in self._frag_tasks[pfid]]
                for pfid in spec["remote"]}

    def _spool_complete(self, tid: str, spec: Dict) -> bool:
        """Completeness proof before any spool repoint; verification
        errors (injected or real) abort the spooled path."""
        try:
            return self.co.spool.is_complete(tid, spec["n_out"])
        except Exception as e:  # noqa: BLE001 - store-specific errors
            raise _SpoolUnavailable(f"verifying {tid}: {e}") from e

    def _recover_worker_spooled(self, dead_uri: str, affected) -> None:
        """Cascade-free recovery: tasks whose output is complete in the
        spool are 'replaced' by the spool itself (consumers repoint,
        token preserved, NOTHING re-runs); tasks lost mid-production
        re-run alone with spool-backed remote sources."""
        incomplete: List[Tuple[int, str]] = []
        for fid, tid in affected:
            spec = self._task_specs[tid]
            if self._spool_complete(tid, spec):
                self._repoint_to_spool(fid, tid, dead_uri, spec)
            else:
                incomplete.append((fid, tid))
        if incomplete:
            self._retry_stages_spooled(incomplete, dead_uri)

    def _repoint_to_spool(self, fid: int, tid: str, old_uri: str,
                          spec: Dict) -> bool:
        """Swap a finished task's result location for its spooled
        output: same attempt, same tokens, different backing store.
        Consumers resume at their current token — no delivered guard,
        no restart, no re-execution anywhere.  Returns True when every
        reachable consumer acknowledged the repoint (the graceful-drain
        tick only releases the worker then)."""
        from presto_tpu.server.spool import spool_location, spool_prefix

        old_prefix = f"{old_uri}/v1/task/{tid}/results/"
        new_prefix = spool_prefix(tid)
        with self._recovery_lock:
            self._placements = [
                (f, t, new_prefix.rstrip("/") if t == tid else u)
                for f, t, u in self._placements]
            self._task_uris[fid][spec["index"]] = spool_location(tid)
        # the task's full output exists: it IS done for straggler
        # ranking and must never be cloned
        self._task_seen.setdefault(tid, {})["done_at"] = time.monotonic()
        cons_fid = self._consumers.get(fid)
        if cons_fid is None:
            # root fragment: the coordinator drain follows the move at
            # its current token (rows kept — same attempt's stream)
            with self._recovery_lock:
                old_loc, new_loc = old_prefix + "0", new_prefix + "0"
                for orig, cur in self._root_orig.items():
                    if cur == old_loc:
                        self._root_orig[orig] = new_loc
                        self._spool_moves[orig] = new_loc
            self.co.log(f"spool: root task {tid} now drains from spool")
            return True
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        body = json.dumps({"old_prefix": old_prefix,
                           "new_prefix": new_prefix,
                           "spool": True}).encode()
        # consumers on dead nodes are being recovered themselves; a
        # DRAINING (alive) old_uri still gets its consumers repointed
        dead_now = self.co.nodes.dead_uris()
        with self._recovery_lock:
            consumers = [(t, u) for f, t, u in self._placements
                         if f == cons_fid and u not in dead_now
                         and not u.startswith("spool://")]
        ok = True
        for ctid, curi in consumers:
            try:
                self.co.http.request(
                    f"{curi}/v1/task/{ctid}/remote-sources",
                    method="POST", data=body, headers=headers,
                    timeout=10, task_id=ctid,
                    description="spool repoint",
                    max_error_duration_s=min(
                        5.0, (getattr(self, "_cfg", None)
                              or self.co.config)
                        .remote_request_max_error_duration_s))
            except Exception as e:  # noqa: BLE001 - consumer may be dead
                # an unreachable consumer is handled by its own
                # recovery round (which re-creates it reading from the
                # spool); nothing to escalate here
                self.co.log(f"spool repoint of {ctid} on {curi} "
                            f"failed: {e}")
                ok = False
        self.co.log(f"spool: consumers of {tid} repointed at its "
                    f"spooled output (zero re-runs)")
        return ok

    def _retry_stages_spooled(self, incomplete, dead_uri: str) -> None:
        """Re-run ONLY the tasks that died mid-production, each under a
        fresh attempt id with spool-backed remote sources — the producer
        subtree is never touched.  A consumer that already consumed the
        dead attempt's partial output restarts the same way (its own
        producers come from the spool), cascading up to the root drain's
        DISCARD/re-pull.  Bounded by stage_retry_limit per stage with
        the errortracker backoff, exactly like the cascading path."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        frags0 = sorted({fid for fid, _ in incomplete})
        if cfg.stage_retry_limit <= 0:
            tids = [tid for _, tid in incomplete]
            raise RuntimeError(
                f"Worker {dead_uri} died mid-query owning unfinished "
                f"task(s) {tids} of stage(s) {frags0} and "
                f"stage_retry_limit=0: whole-stage retry disabled, "
                f"query is not recoverable")
        rounds = []
        for f in frags0:
            n = self._stage_retries.get(f, 0) + 1
            if n > cfg.stage_retry_limit:
                raise RuntimeError(
                    f"stage {f} of query {self.query_id} exhausted "
                    f"stage_retry_limit={cfg.stage_retry_limit} after "
                    f"{n - 1} spooled stage retr"
                    f"{'y' if n - 1 == 1 else 'ies'}; last trigger: "
                    f"worker {dead_uri} lost task(s) of stage(s) "
                    f"{frags0}")
            self._stage_retries[f] = n
            rounds.append(n)
        round_n = max(rounds)
        self.stage_retry_rounds += 1
        backoff = RequestErrorTracker(
            f"stage-retry:{self.query_id}", description="stage retry",
            min_backoff_s=cfg.remote_request_min_backoff_s,
            max_backoff_s=cfg.remote_request_max_backoff_s)
        backoff.error_count = round_n - 1
        if backoff.backoff_delay() > 0:
            time.sleep(backoff.backoff_delay())
        superseded: List[Tuple[str, str]] = []
        # topological (producer-first) restart order: a consumer's new
        # attempt must read the spool of its producer's NEW attempt
        # when both died (fragment ids are assigned producers-first)
        queue: List[Tuple[int, str]] = sorted(incomplete)
        restarted: set = set()
        touched_fids: set = set(frags0)
        charged: set = set(frags0)
        # each restart can escalate its consumers; the chain is bounded
        # by the fragment count (a consumer restarts at most once here —
        # further rounds come back through _recover_worker)
        guard = 0
        while queue:
            guard += 1
            if guard > 10 * len(self._dplan.fragments) + 16:
                raise RuntimeError(
                    f"spooled stage retry of {frags0} did not converge")
            fid, old_tid = queue.pop(0)
            if old_tid in restarted:
                continue
            if fid not in charged:
                # escalated consumer stage: one retry charge per stage
                # per round, same budget as the cascading path
                n = self._stage_retries.get(fid, 0) + 1
                if n > cfg.stage_retry_limit:
                    raise RuntimeError(
                        f"stage {fid} of query {self.query_id} "
                        f"exhausted stage_retry_limit="
                        f"{cfg.stage_retry_limit} escalating from the "
                        f"spooled restart of stage(s) {frags0}")
                self._stage_retries[fid] = n
                charged.add(fid)
            restarted.add(old_tid)
            touched_fids.add(fid)
            esc = self._restart_task_spooled(fid, old_tid, dead_uri,
                                             superseded)
            queue.extend(esc)
        self._cancel_tasks(superseded)
        self.co.event_bus.stage_retry(ev.StageRetryEvent(
            self.query_id, self.trace_token,
            tuple(sorted(touched_fids)), round_n,
            f"lost worker {dead_uri}", ev.now(),
            producer_reruns=0, spooled=True))
        self.co.log(f"spooled stage retry: re-ran {len(restarted)} "
                    f"task(s) of stage(s) {sorted(touched_fids)} "
                    f"(round {round_n}, zero producer re-runs) after "
                    f"losing {dead_uri}")

    def _restart_task_spooled(self, fid: int, old_tid: str,
                              dead_uri: str, superseded
                              ) -> List[Tuple[int, str]]:
        """One fresh attempt of one task, remote sources on the spool.
        Returns consumer (fid, tid) pairs that must restart too because
        they already consumed the superseded attempt's pages."""
        spec = self._task_specs[old_tid]
        base = spec["base"]
        attempt = self._attempts.get(base, 0) + 1
        new_tid = f"{base}a{attempt}"
        with self._recovery_lock:
            old_uri = next(u for _f, t, u in self._placements
                           if t == old_tid)
        # genuinely dead nodes are excluded; ``dead_uri`` itself is NOT
        # singled out — the failed-task tick restarts tasks that failed
        # on a perfectly healthy worker (their producer died, their
        # budget drained), and on a 2-node cluster that worker is the
        # only host left
        dead = self.co.nodes.dead_uris()
        workers = [uri for _, uri in self.co.nodes.topology_ordered(
            self.co.nodes.alive_nodes()) if uri not in dead]
        if not workers:
            raise RuntimeError(
                f"Worker {dead_uri} died mid-query and no surviving "
                f"worker remains for spooled stage retry")
        remote = self._spool_remote(spec)
        last_error = None
        new_host = None
        for shift in range(len(workers)):
            w = workers[(spec["index"] + attempt + shift) % len(workers)]
            try:
                self._create_remote_task(
                    w, new_tid, spec["frag"], spec["scan_shard"],
                    remote, spec["n_out"], spec["broadcast"],
                    consumer_index=spec["consumer_index"])
                new_host = w
                break
            except RemoteRequestError as e:
                if e.retryable:
                    last_error = e
                    continue
                raise
        if new_host is None:
            raise RuntimeError(
                f"no worker accepted spooled stage-retry task "
                f"{new_tid}: {last_error}")
        new_spec = dict(spec)
        new_spec["remote"] = remote
        new_spec["created_at"] = time.monotonic()
        self._task_specs[new_tid] = new_spec
        self._attempts[base] = attempt
        old_prefix = f"{old_uri}/v1/task/{old_tid}/results/"
        new_prefix = f"{new_host}/v1/task/{new_tid}/results/"
        with self._recovery_lock:
            self._placements = [
                (f, new_tid if t == old_tid else t,
                 new_host if t == old_tid else u)
                for f, t, u in self._placements]
            self._frag_tasks[fid][spec["index"]] = new_tid
            self._task_uris[fid][spec["index"]] = new_prefix + "{part}"
        superseded.append((old_tid, old_uri))
        self._drop_speculations(fid)
        # repoint consumers at the new attempt; 'delivered' consumers
        # restart themselves (their producers read from the spool)
        esc: List[Tuple[int, str]] = []
        cons_fid = self._consumers.get(fid)
        if cons_fid is None:
            from presto_tpu.server.spool import spool_prefix as _sp

            with self._recovery_lock:
                old_loc, new_loc = old_prefix + "0", new_prefix + "0"
                # an adopted root drain reads spool://…{old_tid}…/0 —
                # that location shape moves to the fresh attempt too
                old_locs = {old_loc, _sp(old_tid) + "0"}
                for orig, cur in self._root_orig.items():
                    if cur in old_locs:
                        self._root_orig[orig] = new_loc
                        self._restarts[orig] = new_loc
                        self._spool_moves.pop(orig, None)
            return esc
        cfg = getattr(self, "_cfg", None) or self.co.config
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        from presto_tpu.server.spool import spool_prefix

        # a consumer may be fetching the old attempt over HTTP *or*
        # reading its spool stream (it was itself restarted earlier):
        # both source shapes must move to the new attempt, or the
        # spool reader stalls forever on a stream that will never
        # complete.  Both are attempt changes (delivered guard applies).
        old_prefixes = [old_prefix, spool_prefix(old_tid)]
        # skip consumers on GENUINELY dead nodes only (they are being
        # restarted by this same recovery) — ``dead_uri`` may be a live
        # worker when the failed-task tick triggered this restart, and
        # its consumers absolutely need the repoint
        dead_now = self.co.nodes.dead_uris()
        with self._recovery_lock:
            ctasks = [(t, u) for f, t, u in self._placements
                      if f == cons_fid]
        for ctid, curi in ctasks:
            if curi.startswith("spool://"):
                continue   # already served wholly from the spool
            if curi in dead_now:
                continue
            for old_p in old_prefixes:
                body = json.dumps({"old_prefix": old_p,
                                   "new_prefix": new_prefix}).encode()
                try:
                    resp = self.co.http.request(
                        f"{curi}/v1/task/{ctid}/remote-sources",
                        method="POST", data=body, headers=headers,
                        timeout=10, task_id=ctid,
                        description="remote-source repoint",
                        max_error_duration_s=min(
                            5.0,
                            cfg.remote_request_max_error_duration_s))
                    status = resp.json().get("status")
                except Exception as e:  # noqa: BLE001 - escalate
                    self.co.log(f"spooled retry: repoint of {ctid} on "
                                f"{curi} failed ({e}); restarting it")
                    status = "delivered"
                if status == "delivered":
                    esc.append((cons_fid, ctid))
                    break
        return esc

    def _failed_task_tick(self) -> None:
        """Spool-enabled second line of defense: a task that FAILED on
        a live worker (e.g. its exchange budget drained against a dead
        producer before recovery repointed it) is itself restartable —
        its new attempt reads every producer from the spool.  PR 5 had
        no answer to consumer-task failure; the spool makes it just
        another restart.  Scanned at ~1s cadence to keep the status-poll
        load off the workers."""
        now = time.monotonic()
        if now - self._failed_scan_at < 1.0:
            return
        self._failed_scan_at = now
        with self._recovery_lock:
            placements = list(self._placements)
        # a worker death explains (and fixes) most consumer failures:
        # let the dead-worker recovery settle before restarting anyone
        dead = self.co.nodes.dead_uris()
        if any(u in dead and u not in self._recovered_uris
               for _, _, u in placements):
            return
        for fid, tid, uri in placements:
            if uri.startswith("spool://") or tid in self._failed_handled:
                continue
            info = self._poll_task(tid, uri)
            if info is None or info.get("state") != "FAILED":
                continue
            # only transport-shaped failures restart (a drained error
            # budget against a lost producer); genuine application
            # errors — bad data, resource limits — keep failing fast
            # with their original message
            if "exchange" not in (info.get("error") or ""):
                continue
            if tid not in self._failed_seen:
                # confirm across two scans: a failure observed the
                # instant a worker dies must wait for the failure
                # detector to catch up, or the restart races onto the
                # dying node
                self._failed_seen.add(tid)
                continue
            self._failed_handled.add(tid)
            self.co.log(f"spool: task {tid} FAILED on live worker "
                        f"{uri}; restarting it from the spool")
            self._retry_stages_spooled([(fid, tid)], uri)

    def _drain_worker_tick(self) -> None:
        """Graceful worker drain (the elasticity story): a worker
        advertising SHUTTING_DOWN finishes its running tasks, their
        output is already write-through in the spool, and this tick
        repoints consumers at the spool so the worker can leave the
        cluster mid-query — no kill, no retry, no re-run."""
        draining = self.co.nodes.draining_uris()
        if not draining:
            return
        with self._recovery_lock:
            by_uri: Dict[str, List[Tuple[int, str]]] = {}
            for fid, tid, uri in self._placements:
                if uri in draining:
                    by_uri.setdefault(uri, []).append((fid, tid))
        for uri, tasks in by_uri.items():
            moved = []
            for fid, tid in tasks:
                spec = self._task_specs[tid]
                info = self._poll_task(tid, uri)
                if info is None or info.get("state") != "FINISHED":
                    continue   # still running: let it finish
                try:
                    if not self._spool_complete(tid, spec):
                        continue
                except _SpoolUnavailable:
                    continue   # dead-worker recovery will handle it
                if not self._repoint_to_spool(fid, tid, uri, spec):
                    continue   # retry any failed repoint next tick
                # release: cancel the task on the draining worker so
                # its buffers free and the worker's drain completes —
                # every consumer is already reading from the spool
                self._cancel_tasks([(tid, uri)])
                moved.append(tid)
            with self._recovery_lock:
                remaining = [t for _, t, u in self._placements
                             if u == uri]
            if moved and not remaining and uri not in self._drained_uris:
                self._drained_uris.add(uri)
                self.co.event_bus.worker_drain(ev.WorkerDrainEvent(
                    self.query_id, self.trace_token, uri,
                    tuple(moved), ev.now()))
                self.co.log(f"drain: worker {uri} released from query "
                            f"{self.query_id} ({len(moved)} task(s) "
                            f"now served from spool)")

    # -- whole-stage retry (Presto-on-Spark stance) ---------------------
    def _retry_stages(self, frags0: set, dead_uri: str) -> set:
        """Cancel and re-create the minimal producer subtree of the lost
        stage(s) under fresh attempt ids, repoint consumers, and escalate
        (restart the consumer too) wherever a consumer already consumed
        superseded pages — the attempt-aware dedup in the exchange layer
        guarantees every consumed stream comes wholly from one attempt,
        so nothing double-counts.  Returns the re-created fragment set.
        Bounded by ``stage_retry_limit`` per stage, with the
        deterministic errortracker backoff schedule between rounds."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        dplan = self._dplan
        frag_by_id = {f.fragment_id: f for f in dplan.fragments}
        if cfg.stage_retry_limit <= 0:
            tids = [tid for fid, tid, _ in self._placements
                    if fid in frags0]
            raise RuntimeError(
                f"Worker {dead_uri} died mid-query owning task(s) "
                f"{tids} of non-leaf stage(s) {sorted(frags0)} and "
                f"stage_retry_limit=0: whole-stage retry disabled, "
                f"query is not recoverable")
        S: set = set()
        for f in frags0:
            S.add(f)
            S.update(frag_by_id[f].producer_subtree)
        self.stage_retry_rounds += 1

        def charge(fids) -> int:
            worst = 0
            for f in sorted(fids):
                n = self._stage_retries.get(f, 0) + 1
                if n > cfg.stage_retry_limit:
                    raise RuntimeError(
                        f"stage {f} of query {self.query_id} exhausted "
                        f"stage_retry_limit={cfg.stage_retry_limit} "
                        f"after {n - 1} whole-stage retr"
                        f"{'y' if n - 1 == 1 else 'ies'}; last trigger: "
                        f"worker {dead_uri} lost stage(s) "
                        f"{sorted(frags0)}")
                self._stage_retries[f] = n
                worst = max(worst, n)
            return worst

        round_n = charge(S)
        # deterministic backoff between retry rounds — the errortracker
        # schedule (min * 2^(n-1), capped), same knobs as transport
        backoff = RequestErrorTracker(
            f"stage-retry:{self.query_id}", description="stage retry",
            min_backoff_s=cfg.remote_request_min_backoff_s,
            max_backoff_s=cfg.remote_request_max_backoff_s)
        backoff.error_count = round_n - 1
        if backoff.backoff_delay() > 0:
            time.sleep(backoff.backoff_delay())
        superseded: List[Tuple[str, str]] = []
        rerun_counts: Dict[int, int] = {}
        for _ in range(len(dplan.fragments) + 1):
            moves = self._recreate_fragments(S, dead_uri, superseded,
                                             rerun_counts)
            esc = self._repoint_after_retry(S, moves, dead_uri)
            if not esc:
                break
            grown = set()
            for c in esc:
                for f in (c,) + frag_by_id[c].producer_subtree:
                    if f not in S:
                        grown.add(f)
            charge(grown)
            S.update(grown)
            # escalated consumers force yet another attempt of their
            # whole producer subtrees: the attempts just created may
            # already be partially acked by the consumers' old tasks
            S.update(esc)
        self._cancel_tasks(superseded)
        # producer re-runs: re-executed tasks strictly BELOW a triggering
        # stage (the cascade cost the spooled exchange eliminates).
        # Escalated consumers are consumer-side restarts, not re-runs of
        # producer work, so only each consumer's producer subtree counts.
        producer_fids: set = set()
        for f in frags0:
            producer_fids.update(frag_by_id[f].producer_subtree)
        for c in S - set(frags0):
            producer_fids.update(frag_by_id[c].producer_subtree)
        producer_fids -= set(frags0)
        reruns = sum(n for fid, n in rerun_counts.items()
                     if fid in producer_fids)
        self.producer_reruns_total += reruns
        self.co.event_bus.stage_retry(ev.StageRetryEvent(
            self.query_id, self.trace_token, tuple(sorted(S)),
            round_n, f"lost worker {dead_uri}", ev.now(),
            producer_reruns=reruns, spooled=False))
        self.co.log(f"stage retry: re-created stages {sorted(S)} "
                    f"(round {round_n}, {reruns} producer re-runs) "
                    f"after losing {dead_uri}")
        return S

    def _recreate_fragments(self, S: set, dead_uri: str, superseded,
                            rerun_counts: Optional[Dict[int, int]] = None
                            ) -> Dict[int, List[Tuple[str, str]]]:
        """Create fresh attempts (new task ids, fresh output buffers)
        for every task of every fragment in ``S``, bottom-up.  Returns
        per-fragment (old_prefix, new_prefix) result-location moves;
        ``rerun_counts`` accumulates re-created task counts per fragment
        (the producer-re-run accounting)."""
        dead = self.co.nodes.dead_uris() | {dead_uri}
        workers = [uri for _, uri in self.co.nodes.topology_ordered(
            self.co.nodes.alive_nodes()) if uri not in dead]
        if not workers:
            raise RuntimeError(
                f"Worker {dead_uri} died mid-query and no surviving "
                f"worker remains for whole-stage retry")
        moves: Dict[int, List[Tuple[str, str]]] = {}
        for frag in self._dplan.fragments:   # topological: producers 1st
            fid = frag.fragment_id
            if fid not in S:
                continue
            self._drop_speculations(fid)
            frag_moves: List[Tuple[str, str]] = []
            tids = self._frag_tasks[fid]
            for i, old_tid in enumerate(list(tids)):
                spec = self._task_specs[old_tid]
                base = spec["base"]
                attempt = self._attempts.get(base, 0) + 1
                new_tid = f"{base}a{attempt}"
                with self._recovery_lock:
                    old_uri = next(u for _f, t, u in self._placements
                                   if t == old_tid)
                # producers of this fragment re-created earlier in this
                # topological pass are already current in _task_uris
                remote = {pfid: list(self._task_uris[pfid])
                          for pfid in spec["remote"]}
                last_error = None
                new_host = None
                for shift in range(len(workers)):
                    w = workers[(i + attempt + shift) % len(workers)]
                    try:
                        self._create_remote_task(
                            w, new_tid, spec["frag"], spec["scan_shard"],
                            remote, spec["n_out"], spec["broadcast"],
                            consumer_index=spec["consumer_index"])
                        new_host = w
                        break
                    except RemoteRequestError as e:
                        if e.retryable:
                            last_error = e
                            continue
                        raise
                if new_host is None:
                    raise RuntimeError(
                        f"no worker accepted stage-retry task "
                        f"{new_tid}: {last_error}")
                new_spec = dict(spec)
                new_spec["remote"] = remote
                new_spec["created_at"] = time.monotonic()
                self._task_specs[new_tid] = new_spec
                self._attempts[base] = attempt
                old_prefix = f"{old_uri}/v1/task/{old_tid}/results/"
                new_prefix = f"{new_host}/v1/task/{new_tid}/results/"
                frag_moves.append((old_prefix, new_prefix))
                with self._recovery_lock:
                    self._placements = [
                        (f, new_tid if t == old_tid else t,
                         new_host if t == old_tid else u)
                        for f, t, u in self._placements]
                    tids[i] = new_tid
                    self._task_uris[fid][i] = new_prefix + "{part}"
                superseded.append((old_tid, old_uri))
                if rerun_counts is not None:
                    rerun_counts[fid] = rerun_counts.get(fid, 0) + 1
            moves[fid] = frag_moves
        return moves

    def _repoint_after_retry(self, S: set, moves, dead_uri: str) -> set:
        """Point every consumer OUTSIDE the restart set at the fresh
        attempts.  Returns consumer fragment ids that must escalate into
        the restart set ('delivered': they already consumed superseded
        pages, or they are unreachable)."""
        esc: set = set()
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        for fid in sorted(S):
            cons_fid = self._consumers.get(fid)
            if cons_fid is None:
                # root stage restarted: the coordinator drain discards
                # that location's rows and re-pulls the new attempt
                with self._recovery_lock:
                    for old_p, new_p in moves[fid]:
                        old_loc, new_loc = old_p + "0", new_p + "0"
                        for orig, cur in self._root_orig.items():
                            if cur == old_loc:
                                self._root_orig[orig] = new_loc
                                self._restarts[orig] = new_loc
                continue
            if cons_fid in S or cons_fid in esc:
                continue   # restarted itself; its create saw fresh uris
            with self._recovery_lock:
                ctasks = [(t, u) for f, t, u in self._placements
                          if f == cons_fid]
            for ctid, curi in ctasks:
                for old_p, new_p in moves[fid]:
                    # with spooling, the consumer may be reading the
                    # superseded attempt's SPOOL stream (a fallback
                    # after partial spooled recovery): move that source
                    # shape too, or it stalls on a dead stream
                    olds = [old_p]
                    if self._spool_enabled():
                        i = old_p.find("/v1/task/")
                        if i >= 0:
                            olds.append("spool://" + old_p[i + 1:])
                    status = "not-found"
                    for one_old in olds:
                        body = json.dumps(
                            {"old_prefix": one_old,
                             "new_prefix": new_p}).encode()
                        try:
                            resp = self.co.http.request(
                                f"{curi}/v1/task/{ctid}/remote-sources",
                                method="POST", data=body,
                                headers=headers,
                                timeout=10, task_id=ctid,
                                description="remote-source repoint",
                                max_error_duration_s=min(
                                    5.0,
                                    (getattr(self, "_cfg", None)
                                     or self.co.config)
                                    .remote_request_max_error_duration_s))
                            status = resp.json().get("status")
                        except Exception as e:  # noqa: BLE001
                            self.co.log(
                                f"stage retry: repoint of {ctid} on "
                                f"{curi} failed ({e}); restarting "
                                f"consumer stage {cons_fid}")
                            status = "delivered"
                        if status == "delivered":
                            break
                    if status == "delivered":
                        esc.add(cons_fid)
                        break
                if cons_fid in esc:
                    break
        return esc

    def _cancel_tasks(self, pairs) -> None:
        """Best-effort DELETE of superseded/losing task attempts."""
        for tid, uri in pairs:
            try:
                self.co.http.request(
                    f"{uri}/v1/task/{tid}", method="DELETE",
                    headers=self._internal_headers(), timeout=5,
                    description="superseded-task cancel",
                    max_error_duration_s=0.0)
            except Exception as e:  # noqa: BLE001 - best effort
                self.co.log(f"cancel of superseded task {tid} on "
                            f"{uri} failed: {e}")

    # -- speculative re-execution of stragglers -------------------------
    def _poll_task(self, tid: str, uri: str) -> Optional[Dict]:
        try:
            resp = self.co.http.request(
                f"{uri}/v1/task/{tid}",
                headers=self._internal_headers(), timeout=5,
                task_id=tid, description="progress poll",
                max_error_duration_s=0.0)
            return resp.json()
        except Exception:  # noqa: BLE001 - progress polls are advisory
            return None

    def _speculation_tick(self) -> None:
        """Track per-stage task progress from status polls; clone a
        straggler onto another worker; the attempt the consumer drains
        first wins (the exchange's attempt-aware dedup arbitrates), the
        loser is cancelled."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        if self._dplan is None:
            return
        now = time.monotonic()
        frag_by_id = {f.fragment_id: f for f in self._dplan.fragments}
        with self._recovery_lock:
            placements = list(self._placements)
        for fid, tid, uri in placements:
            seen = self._task_seen.setdefault(tid, {"done_at": None})
            if seen["done_at"] is not None:
                continue
            info = self._poll_task(tid, uri)
            if info is None:
                continue
            seen["state"] = info.get("state")
            seen["pages"] = info.get("pagesEnqueued", 0)
            if info.get("state") == "FINISHED" and info.get("drained"):
                seen["done_at"] = now
        self._resolve_speculations()
        by_stage: Dict[int, List[Tuple[str, str]]] = {}
        for fid, tid, uri in placements:
            by_stage.setdefault(fid, []).append((tid, uri))
        for fid, tasks in by_stage.items():
            frag = frag_by_id[fid]
            if frag.consumed_fragments and not self._spool_enabled():
                # without spooling only leaf tasks speculate: a clone
                # re-derives its whole output from the deterministic
                # scan shard, while a non-leaf clone would race the
                # original for the same producer buffer tokens.  With
                # the spooled exchange, a non-leaf clone reads its
                # producers from the spool (token 0, no buffer race) —
                # non-leaf speculation becomes legal
                continue
            if fid == self._dplan.root_fragment_id or len(tasks) < 2:
                continue
            done_elapsed = []
            for tid, _u in tasks:
                seen = self._task_seen.get(tid) or {}
                if seen.get("done_at") is None:
                    continue
                created = self._task_specs[tid].get(
                    "created_at", seen["done_at"])
                done_elapsed.append(max(seen["done_at"] - created, 1e-3))
            need = max(1, int(round(cfg.speculation_quantile
                                    * len(tasks))))
            if len(done_elapsed) < need:
                continue
            done_elapsed.sort()
            median = done_elapsed[len(done_elapsed) // 2]
            for tid, uri in tasks:
                seen = self._task_seen.get(tid) or {}
                if seen.get("done_at") is not None \
                        or tid in self._speculations:
                    continue
                created = self._task_specs[tid].get("created_at")
                if created is None:
                    continue
                lag = now - created
                if lag < max(cfg.speculation_min_runtime_s,
                             cfg.speculation_lag_factor * median):
                    continue
                self._spawn_clone(fid, tid, uri)

    def _spawn_clone(self, fid: int, tid: str, uri: str) -> None:
        spec = self._task_specs[tid]
        base = spec["base"]
        attempt = self._attempts.get(base, 0) + 1
        clone_tid = f"{base}a{attempt}"
        dead = self.co.nodes.dead_uris()
        workers = [u for _, u in self.co.nodes.topology_ordered(
            self.co.nodes.alive_nodes())
            if u not in dead and u != uri]
        if not workers:   # nowhere else to run: keep waiting
            return
        w = workers[spec["index"] % len(workers)]
        if spec["remote"] and self._spool_enabled():
            # non-leaf clone: read every producer stream back from the
            # spool so the clone never races the original for buffer
            # tokens (the legality condition for non-leaf speculation)
            remote = self._spool_remote(spec)
        else:
            remote = {pfid: list(self._task_uris[pfid])
                      for pfid in spec["remote"]}
        try:
            self._create_remote_task(
                w, clone_tid, spec["frag"], spec["scan_shard"], remote,
                spec["n_out"], spec["broadcast"],
                consumer_index=spec["consumer_index"])
        except Exception as e:  # noqa: BLE001 - speculation is optional
            self.co.log(f"speculation: clone create for {tid} "
                        f"failed: {e}")
            return
        self._attempts[base] = attempt
        new_spec = dict(spec)
        new_spec["remote"] = remote
        new_spec["created_at"] = time.monotonic()
        self._task_specs[clone_tid] = new_spec
        self._speculations[tid] = {
            "fid": fid, "clone": clone_tid, "clone_uri": w,
            "orig_uri": uri, "state": "racing"}
        self.co.event_bus.speculation(ev.SpeculationEvent(
            self.query_id, self.trace_token, tid, clone_tid, "cloned",
            ev.now()))
        self.co.log(f"speculation: straggler {tid} cloned as "
                    f"{clone_tid} on {w}")

    def _resolve_speculations(self) -> None:
        """First-finisher-wins: when the clone finishes, repoint each
        consumer that has not yet consumed original pages; consumers
        that already did keep the original (attempt-aware dedup — a
        partition never mixes attempts).  The fully-unused attempt is
        cancelled."""
        for orig_tid, sp in list(self._speculations.items()):
            if sp["state"] != "racing":
                continue
            if (self._task_seen.get(orig_tid) or {}).get("done_at") \
                    is not None:
                # original finished AND was drained first: clone lost
                sp["state"] = "lost"
                self._cancel_tasks([(sp["clone"], sp["clone_uri"])])
                self._fire_speculation(orig_tid, sp)
                self.co.log(f"speculation: original {orig_tid} won; "
                            f"cancelled clone {sp['clone']}")
                continue
            info = self._poll_task(sp["clone"], sp["clone_uri"])
            if info is None:
                continue
            if info.get("state") == "FAILED":
                sp["state"] = "lost"
                self._fire_speculation(orig_tid, sp)
                continue
            if info.get("state") != "FINISHED":
                continue
            self._finish_speculation(orig_tid, sp)

    def _fire_speculation(self, orig_tid: str, sp: Dict) -> None:
        """One SpeculationEvent per race resolution (won/lost/split)."""
        self.co.event_bus.speculation(ev.SpeculationEvent(
            self.query_id, self.trace_token, orig_tid, sp["clone"],
            sp["state"], ev.now()))

    def _finish_speculation(self, orig_tid: str, sp: Dict) -> None:
        spec = self._task_specs[orig_tid]
        fid = sp["fid"]
        cons_fid = self._consumers.get(fid)
        old_prefix = f"{sp['orig_uri']}/v1/task/{orig_tid}/results/"
        new_prefix = f"{sp['clone_uri']}/v1/task/{sp['clone']}/results/"
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        body = json.dumps({"old_prefix": old_prefix,
                           "new_prefix": new_prefix}).encode()
        with self._recovery_lock:
            ctasks = [(t, u) for f, t, u in self._placements
                      if f == cons_fid]
        delivered = 0
        repointed = 0
        for ctid, curi in ctasks:
            try:
                resp = self.co.http.request(
                    f"{curi}/v1/task/{ctid}/remote-sources",
                    method="POST", data=body, headers=headers,
                    timeout=10, task_id=ctid,
                    description="speculation repoint",
                    max_error_duration_s=0.0)
                status = resp.json().get("status")
            except Exception:  # noqa: BLE001 - keep the original
                status = "delivered"
            if status == "delivered":
                delivered += 1
            elif status == "repointed":
                repointed += 1
        if delivered == 0 and repointed > 0:
            sp["state"] = "won"
            with self._recovery_lock:
                self._placements = [
                    (f, sp["clone"] if t == orig_tid else t,
                     sp["clone_uri"] if t == orig_tid else u)
                    for f, t, u in self._placements]
                self._frag_tasks[fid][spec["index"]] = sp["clone"]
                self._task_uris[fid][spec["index"]] = \
                    new_prefix + "{part}"
            self._cancel_tasks([(orig_tid, sp["orig_uri"])])
            self._fire_speculation(orig_tid, sp)
            self.co.log(f"speculation: clone {sp['clone']} won over "
                        f"straggler {orig_tid}")
        elif repointed == 0:
            sp["state"] = "lost"
            self._cancel_tasks([(sp["clone"], sp["clone_uri"])])
            self._fire_speculation(orig_tid, sp)
            self.co.log(f"speculation: clone {sp['clone']} lost "
                        f"(original pages already consumed)")
        else:
            # split decision: some consumers drained the original first,
            # others switched — each partition sticks with exactly one
            # attempt (exact either way); both attempts stay alive until
            # the end-of-query cancel fan-out
            sp["state"] = "split"
            self._fire_speculation(orig_tid, sp)
            self.co.log(f"speculation: {orig_tid} split across attempts "
                        f"({repointed} repointed, {delivered} kept)")

    def _drop_speculations(self, fid: int) -> None:
        """Whole-stage retry supersedes any in-flight clone race."""
        for tid, sp in list(self._speculations.items()):
            if sp.get("fid") == fid and sp.get("state") == "racing":
                sp["state"] = "lost"
                self._cancel_tasks([(sp["clone"], sp["clone_uri"])])
                self._fire_speculation(tid, sp)

    def _plan_epochs(self) -> Optional[Dict]:
        """The coordinator's per-catalog stats-epoch snapshot for this
        plan, shipped on task create so the worker-side plan_fragment
        cache is keyed like the plan cache: any DML/DDL bumps an epoch,
        the key changes, and stale lowered pipelines LRU out."""
        if self._dplan is None:
            return None
        if self._plan_epochs_cache is None:
            from presto_tpu.sql import plancache

            epochs = plancache.epochs_for(self.co.registry)
            cats = {self.catalog}
            for f in self._dplan.fragments:
                cats |= plancache.scan_catalogs(f.root)
            self._plan_epochs_cache = {
                "token": epochs.token,
                "epochs": epochs.snapshot(sorted(cats))}
        return self._plan_epochs_cache

    def _create_remote_task(self, worker_uri: str, task_id: str, frag,
                            scan_shard, remote, n_out, broadcast,
                            consumer_index: int) -> None:
        from presto_tpu.sql.planserde import fragment_to_json

        resolved = {fid: [u.format(part=consumer_index) for u in us]
                    for fid, us in remote.items()}
        # JSON task update (the reference's TaskUpdateRequest is JSON,
        # presto-main/.../server/TaskUpdateRequest.java) — never a pickled
        # object: the worker must not execute untrusted request bodies.
        body = json.dumps({
            "fragment": fragment_to_json(frag),
            "scan_shard": list(scan_shard),
            "remote_sources": {str(fid): us
                               for fid, us in resolved.items()},
            "n_output_partitions": n_out,
            "broadcast_output": broadcast,
            # per-query session property overrides; the worker folds
            # them over its base EngineConfig (SET SESSION reaching
            # distributed execution, SystemSessionProperties role)
            "session_properties": self.session_properties,
            # the query's trace token: the worker stamps it into its
            # log lines, task errors, and worker->worker fetches
            "trace_token": self.trace_token,
            # stats-epoch snapshot keying the worker-side plan_fragment
            # cache (absent for plans without a coordinator epoch
            # domain, which simply bypass that cache)
            "plan_epochs": self._plan_epochs(),
        }).encode("utf-8")
        headers = {"Content-Type": "application/json"}
        headers.update(self._internal_headers())
        self._tasks_scheduled = True
        # budget 0: a single classified attempt — transport failures
        # surface as retryable RemoteRequestError so the scheduler falls
        # over to the NEXT worker immediately instead of backing off
        # against a node the failure detector may not have excluded yet
        resp = self.co.http.request(
            f"{worker_uri}/v1/task/{task_id}", method="POST", data=body,
            headers=headers, timeout=30, task_id=task_id,
            description="task create", max_error_duration_s=0.0,
            trace_token=self.trace_token)
        info = resp.json()
        if info.get("state") == "FAILED":
            raise RuntimeError(f"task create failed: {info}")

    # -- result drain ---------------------------------------------------
    def _session(self):
        """Session built from the request's header state."""
        from presto_tpu.session import Session

        session = Session(user=self.user, catalog=self.catalog)
        if self.co.session_property_manager is not None:
            self.co.session_property_manager.apply(session)
        for k, v in self.session_properties.items():
            session.set_property(k, v)   # validates names and values
        for name, sql in self.prepared.items():
            try:
                session.prepared[name] = parse_statement(sql)
            except Exception:  # noqa: BLE001 - stale client entry
                pass
        return session

    def _ok_result(self) -> None:
        self.column_names = ["result"]
        self.column_types = [T.BOOLEAN]
        self.result_rows = [(True,)]

    def _session_statement(self, stmt: t.Node):
        """Handle statements that mutate client-session state: execute
        them coordinator-side (validation) and emit the session-update
        fields the client applies to its own state.  Returns None when
        fully handled, a (possibly rewritten) statement otherwise."""
        if isinstance(stmt, t.SetSession):
            self._session().set_property(stmt.name, stmt.value)  # validate
            self.session_updates["setSession"] = {stmt.name: stmt.value}
            self._ok_result()
            return None
        if isinstance(stmt, t.ResetSession):
            self.session_updates["resetSession"] = [stmt.name]
            self._ok_result()
            return None
        if isinstance(stmt, t.Use):
            self.co.registry.get(stmt.catalog)   # raises for unknown
            self.session_updates["setCatalog"] = stmt.catalog
            if stmt.schema:
                self.session_updates["setSchema"] = stmt.schema
            self._ok_result()
            return None
        if isinstance(stmt, t.Prepare):
            self.session_updates["addedPrepare"] = {
                stmt.name: stmt.original_sql}
            self._ok_result()
            return None
        if isinstance(stmt, t.Deallocate):
            if stmt.name not in self.prepared:
                raise ValueError(
                    f"prepared statement not found: {stmt.name}")
            self.session_updates["deallocatedPrepare"] = [stmt.name]
            self._ok_result()
            return None
        if isinstance(stmt, t.ExecutePrepared):
            sql = self.prepared.get(stmt.name)
            if sql is None:
                raise ValueError(
                    f"prepared statement not found: {stmt.name}")
            bound = t.substitute_parameters(parse_statement(sql),
                                            stmt.parameters)
            # plan-cache key for the bound statement: the prepared TEXT
            # plus the literal parameters — re-preparing the same name
            # with different SQL can never alias, and each distinct
            # binding gets its own (cacheable) plan
            self._plan_key_sql = (sql + "\0execute\0"
                                  + repr(stmt.parameters))
            return bound
        return stmt

    def _plan_distributed_write(self, stmt):
        """INSERT/CTAS against a connector with two-phase write support
        becomes a distributed plan: query fragments -> round-robin
        exchange -> 'scaled' writer fragment -> single TableFinish commit
        fragment (P6).  Returns (DistributedPlan, abort_fn) or None to
        fall back to the coordinator-side write."""
        from presto_tpu.localrunner import LocalQueryRunner
        from presto_tpu.sql.plan import (
            OutputNode, TableFinishNode, TableWriterNode,
        )

        runner = LocalQueryRunner(
            self.co.registry, self.catalog, self.co.config,
            session=self._session())
        runner.grants = self.co.grants
        # cheap gates FIRST: the CTAS prepare creates the target table, so
        # a later fallback must not have run it (the coordinator-side path
        # would then see "table already exists")
        if runner.session.txn is not None:
            return None               # explicit txn needs session affinity
        try:
            target_catalog, _ = runner._resolve_write_target(stmt.table)
            conn0 = self.co.registry.get(target_catalog)
        except Exception:  # noqa: BLE001 - let the utility path report it
            return None
        # remembered for the post-commit stats-epoch bump (plan cache
        # invalidation on INSERT/CTAS)
        self._write_catalog = target_catalog
        if not getattr(conn0, "supports_distributed_write", False):
            return None
        if isinstance(stmt, t.Insert):
            logical, conn, handle, catalog, name = \
                runner.prepare_insert(stmt)
        else:
            logical, conn, handle, catalog, name = \
                runner.prepare_ctas(stmt)
            if logical is None:       # IF NOT EXISTS, table present
                return self._empty_write_result()
        is_ctas = isinstance(stmt, t.CreateTableAs)
        write_id = None

        def abort():
            try:
                if write_id is not None:
                    conn.abort_write(handle, write_id)
                if is_ctas:
                    # CTAS is all-or-nothing: no empty table left behind
                    conn.drop_table(name)
            except Exception:  # noqa: BLE001 - best effort
                pass

        try:
            metadata = Metadata(self.co.registry, self.catalog)
            cfg = runner.session.effective_config(self.co.config)
            self._cfg = cfg
            optimized = optimize(logical, metadata, cfg)
            write_id = conn.begin_write(handle)
            wcols = (("rows", T.BIGINT), ("fragment", T.VARCHAR))
            fcols = (("rows", T.BIGINT),)
            writer = TableWriterNode(optimized.source, catalog, name,
                                     write_id, wcols)
            finish = TableFinishNode(writer, catalog, name, write_id,
                                     fcols)
            root = OutputNode(finish, fcols)
            dplan = Fragmenter(metadata=metadata,
                               config=cfg).fragment(root)
        except Exception:
            abort()
            raise
        return dplan, abort

    def _empty_write_result(self):
        """CTAS IF NOT EXISTS with the table already present: done, wrote
        0 rows; no plan to run and nothing to fall back to."""
        self.column_names = ["rows"]
        self.column_types = [T.BIGINT]
        self.result_rows = [(0,)]
        return "done"

    def _run_utility(self, stmt: t.Node) -> None:
        """Execute a non-query statement against the shared registry via
        an embedded single-process runner.  Views/grants persist on the
        coordinator (registry.views / co.grants); explicit transactions
        still need a session-affine connection."""
        from presto_tpu.localrunner import LocalQueryRunner

        if isinstance(stmt, (t.StartTransaction, t.Commit, t.Rollback)):
            raise ValueError(
                f"{type(stmt).__name__} requires a session-affine "
                "connection; use the single-process runner")
        runner = LocalQueryRunner(
            self.co.registry, self.catalog, self.co.config,
            session=self._session())
        runner.grants = self.co.grants
        res = runner._execute_parsed(stmt)
        self.column_names = res.column_names
        self.column_types = res.column_types
        self.result_rows = list(res.rows)

    def _run_procedure(self, stmt: t.CallProcedure) -> None:
        """system.runtime.kill_query (KillQueryProcedure.java role).
        Shares the low-memory killer's fail path: the error + shape are
        stamped BEFORE the cancel fan-out, so the client sees the kill
        message with the ADMINISTRATIVELY_KILLED triple rather than a
        generic drain abort."""
        name = ".".join(stmt.name)
        if name not in ("system.runtime.kill_query", "kill_query"):
            raise ValueError(f"unknown procedure {name}")
        if len(stmt.args) < 1 or not isinstance(stmt.args[0],
                                                t.StringLiteral):
            raise ValueError("kill_query(query_id) requires a string id")
        qid = stmt.args[0].value
        message = "Query killed via kill_query"
        if len(stmt.args) > 1:
            if not isinstance(stmt.args[1], t.StringLiteral):
                raise ValueError(
                    "kill_query(query_id, message) requires a string "
                    "message")
            if stmt.args[1].value:
                message = f"Query killed via kill_query: " \
                          f"{stmt.args[1].value}"
        if qid == self.query_id:
            raise ValueError("a query cannot kill itself")
        target = self.co.queries.get(qid)
        if target is None:
            raise ValueError(f"no such query {qid!r}")
        target.kill(message, ADMINISTRATIVELY_KILLED, reason="kill_query")
        self.column_names = ["result"]
        self.column_types = [T.VARCHAR]
        self.result_rows = [("killed",)]

    def cancel(self) -> None:
        """Kill this query (KillQueryProcedure role): flag the drain loop
        and cancel every worker task."""
        self.canceled = True
        self._cancel_worker_tasks()

    def kill(self, message: str, shape: Tuple[str, str, int],
             reason: str) -> None:
        """Administratively fail this query (the low-memory killer and
        CALL system.runtime.kill_query both land here): stamp the error
        message + reference shape BEFORE cancelling so the drain abort
        and dispatcher terminal paths preserve them, fire
        ``QueryKilledEvent``, then run the normal cancel fan-out (which
        also aborts the query's blocked pool reservations on every
        worker).  Terminal queries are left untouched."""
        if self.state in ("FINISHED", "FAILED"):
            return
        self.error = message
        self.error_name, self.error_type, self.error_code = shape
        counters = getattr(self.co, "kill_counters", None)
        if counters is not None:
            counters[reason] = counters.get(reason, 0) + 1
        self.co.event_bus.query_killed(ev.QueryKilledEvent(
            self.query_id, self.trace_token, self.user, reason,
            shape[0], message, ev.now()))
        self.cancel()

    def _drain(self, locations: List[str]) -> None:
        """Pull the root stage's pages, one location at a time.

        Transport errors retry through the error tracker (the token only
        advances on success, so a retried GET re-fetches unacked pages).
        Two recovery shapes reach the drain:

        - ``_relocations`` (leaf task recovery): follow the replacement,
          but only from token 0 — a same-task replacement regenerates
          its stream from scratch;
        - ``_restarts`` (whole-stage retry of the root stage): DISCARD
          the rows collected from that location and re-pull the fresh
          attempt from token 0 — the coordinator is the consumer, so it
          applies the attempt-aware dedup itself (a location's rows come
          wholly from one attempt).  Restarts posted after a location
          completed re-queue it."""
        cfg = getattr(self, "_cfg", None) or self.co.config
        deadline = (time.monotonic() + cfg.query_max_run_time_s
                    if cfg.query_max_run_time_s > 0 else None)
        rows_by_loc: Dict[str, List[tuple]] = {}
        pending = list(locations)
        done: set = set()
        while pending:
            orig = pending.pop(0)
            rows_by_loc[orig] = self._drain_location(orig, deadline, cfg)
            done.add(orig)
            with self._recovery_lock:
                redo = [o for o in self._restarts if o in done]
            for o in redo:
                done.discard(o)
                if o not in pending:
                    pending.append(o)
        for orig in locations:
            self.result_rows.extend(rows_by_loc[orig])

    def _drain_spool(self, loc: str, token: int):
        """One spool poll for the root drain: the coordinator is the
        consumer, reading the root task's spooled stream directly."""
        from presto_tpu.server.spool import parse_spool_url

        tid, part = parse_spool_url(loc)
        store = self._rc_store or self.co.spool
        return store.get_pages(tid, part, token, wait_s=1.0)

    def _drain_location(self, orig: str, deadline, cfg) -> List[tuple]:
        loc = orig
        token = 0
        rows: List[tuple] = []
        spool_errors = 0
        spool_stall_at: Optional[float] = None
        while True:
            if getattr(self, "canceled", False):
                raise RuntimeError("Query killed")
            if getattr(self.co, "killed", False):
                raise _CoordinatorKilled()
            self._root_tokens[orig] = token
            if deadline is not None and time.monotonic() > deadline:
                raise RuntimeError(
                    "Query exceeded maximum run time "
                    f"({cfg.query_max_run_time_s:g}s)")
            with self._recovery_lock:
                moved = self._restarts.pop(orig, None)
                spool_loc = self._spool_moves.get(orig)
            if moved is not None:
                # whole-stage retry re-created the root producer: this
                # location restarts from scratch on the fresh attempt
                loc, token = moved, 0
                rows = []
            elif spool_loc is not None and loc != spool_loc:
                # the root producer's output moved to the spool (dead or
                # drained worker, output complete): SAME attempt, same
                # stream — resume at the current token, rows kept
                loc = spool_loc
            if loc.startswith("spool://"):
                try:
                    pages, token, complete = self._drain_spool(loc,
                                                               token)
                except Exception as e:  # noqa: BLE001 - store errors
                    # transient spool errors retry on the same budget
                    # discipline as transport errors
                    spool_errors += 1
                    if spool_errors * 0.1 > \
                            cfg.remote_request_max_error_duration_s:
                        raise RuntimeError(
                            f"result drain from spool {loc} failed "
                            f"past the error budget: {e}") from e
                    time.sleep(0.1)
                    continue
                spool_errors = 0
                # stall guard (the root-drain analogue of the
                # HttpPageClient one): a stream making no progress and
                # never completing — pages deleted under us, or a
                # producer lost without a failure channel — must not
                # hang the drain forever
                if not pages and not complete:
                    now = time.monotonic()
                    if spool_stall_at is None:
                        spool_stall_at = now
                    elif now - spool_stall_at > \
                            cfg.exchange_spool_stall_s:
                        raise RuntimeError(
                            f"spool stream at {loc} stalled for "
                            f"{cfg.exchange_spool_stall_s:g}s with no "
                            "pages and no COMPLETE marker")
                else:
                    spool_stall_at = None
                for page in pages:
                    rows.extend(deserialize_batch(page).to_pylist())
                if complete:
                    with self._recovery_lock:
                        if orig in self._restarts:
                            continue
                    return rows
                continue

            def _on_retry(exc, _loc=loc, _token=token, _orig=orig):
                if getattr(self, "canceled", False):
                    raise RuntimeError("Query killed")
                with self._recovery_lock:
                    if _orig in self._restarts or \
                            _orig in self._spool_moves:
                        raise _DrainRestart() from exc
                moved2 = self._relocations.get(_loc)
                if moved2 is None:
                    return None
                if _token != 0:
                    raise RuntimeError(
                        f"root task output at {_loc} lost mid-drain "
                        f"after {_token} page(s); replacement at "
                        f"{moved2} cannot resume") from exc
                return f"{moved2}/{_token}"
            try:
                resp = self.co.http.request(
                    f"{loc}/{token}", headers=self._internal_headers(),
                    timeout=120, description="result drain",
                    endpoint=loc, retry_cb=_on_retry,
                    trace_token=self.trace_token)
            except _DrainRestart:
                continue
            except RemoteRequestError:
                # a fatal answer (e.g. 500 from a just-superseded
                # attempt) with a restart or spool move pending is part
                # of the retry choreography, not a query failure
                with self._recovery_lock:
                    pending = (orig in self._restarts
                               or orig in self._spool_moves)
                if not pending and self._spool_enabled() \
                        and not self.canceled:
                    # spooled tier: a dying root worker can answer one
                    # fatal 500 before the failure detector sees it —
                    # give recovery a beat to post the spool move or
                    # restart before declaring the query dead
                    grace = time.monotonic() + 3.0
                    while time.monotonic() < grace:
                        time.sleep(0.05)
                        with self._recovery_lock:
                            if orig in self._restarts or \
                                    orig in self._spool_moves:
                                pending = True
                                break
                if pending:
                    continue
                raise
            loc = self._relocations.get(orig, loc)
            complete = resp.headers.get(
                "X-Presto-Buffer-Complete") == "true"
            token = int(resp.headers.get("X-Presto-Next-Token", token))
            body = resp.body
            off = 0
            while off < len(body):
                size = frame_size(body, off)
                batch = deserialize_batch(body[off:off + size])
                rows.extend(batch.to_pylist())
                off += size
            if complete:
                with self._recovery_lock:
                    if orig in self._restarts:
                        continue   # restarted right at the finish line
                return rows

    # -- client protocol ------------------------------------------------
    def protocol_stats(self) -> Dict:
        """The reference-shaped ``stats`` object carried on every
        client-protocol poll (StatementStats role): state plus — once
        the live sampler has swept — split accounting and cumulative
        progress, so a client observes progress MID-query instead of a
        bare state string."""
        end = self.end_time if self.end_time is not None else ev.now()
        stats: Dict = {
            "state": self.state,
            "queued": self.state in ("QUEUED", "WAITING_FOR_RESOURCES"),
            "scheduled": self._tasks_scheduled,
            "queuedTimeMillis": int(self.queued_s * 1000),
            "elapsedTimeMillis": int(
                max(end - self.create_time, 0.0) * 1000),
        }
        stats.update(self._progress)
        return stats

    def results_payload(self, base_uri: str) -> Dict:
        out: Dict = {"id": self.query_id, "stats": self.protocol_stats(),
                     "traceToken": self.trace_token}
        if self.state == "FAILED":
            err: Dict = {"message": self.error or "query failed"}
            if self.error_name is not None:
                # the reference's error shape (QueryError):
                # name + type + numeric StandardErrorCode
                err["errorName"] = self.error_name
                err["errorType"] = self.error_type
                err["errorCode"] = self.error_code
            if self.retry_after_s is not None:
                # overload shedding: the client may retry this statement
                # after the hinted delay (StatementClient honors it)
                err["retryAfterSeconds"] = self.retry_after_s
            out["error"] = err
            return out
        if self.state != "FINISHED":
            out["nextUri"] = f"{base_uri}/v1/statement/executing/" \
                             f"{self.query_id}/0"
            return out
        out["columns"] = [
            {"name": n, "type": typ.display()}
            for n, typ in zip(self.column_names, self.column_types)]
        out["data"] = [[_json_value(v) for v in row]
                       for row in self.result_rows]
        out.update(self.session_updates)
        return out


def _client_value(v, typ: T.Type):
    """Invert ``_json_value`` for one cell (the journal's inline-row
    encoding round-trip; same contract as the client protocol)."""
    if v is None:
        return None
    if typ.name == "date" and isinstance(v, str):
        return datetime.date.fromisoformat(v)
    if typ.name == "timestamp" and isinstance(v, str):
        return datetime.datetime.fromisoformat(v)
    if isinstance(v, list):
        return [x for x in v]
    return v


def _json_value(v):
    if isinstance(v, (datetime.date, datetime.datetime)):
        return v.isoformat()
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [_json_value(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _json_value(x) for k, x in v.items()}
    return str(v)


# Minimal cluster/query status page (the reference ships a static SPA at
# presto-main/src/main/resources/webapp — query list/details views; this
# is the same role at observability-dashboard fidelity).
_UI_HTML = """<!doctype html>
<html><head><title>tpu-sql</title><style>
body { font-family: monospace; margin: 2em; background: #111; color: #eee }
h1 { color: #7fd4ff } table { border-collapse: collapse; margin: 1em 0 }
td, th { border: 1px solid #444; padding: 4px 10px; text-align: left }
th { background: #222 } .FINISHED { color: #7fff7f }
.FAILED { color: #ff7f7f } .RUNNING, .PLANNING { color: #ffff7f }
.QUEUED, .WAITING_FOR_RESOURCES { color: #7fd4ff }
</style></head><body>
<h1>tpu-sql cluster</h1>
<h2>Nodes</h2><table id="nodes"><tr><th>node</th><th>uri</th></tr></table>
<h2>Queries</h2><table id="queries">
<tr><th>id</th><th>user</th><th>state</th><th>query</th></tr></table>
<h2 id="dtitle" style="display:none">Query detail</h2>
<pre id="detail" style="white-space:pre-wrap"></pre>
<script>
// Cells are populated via textContent, never innerHTML: query SQL, the
// X-Presto-User header, and announced node ids/URIs are all untrusted.
const STATES = ['FINISHED', 'FAILED', 'RUNNING', 'PLANNING',
                'QUEUED', 'WAITING_FOR_RESOURCES'];
function header(table, names) {
  table.textContent = '';
  const tr = document.createElement('tr');
  for (const n of names) {
    const th = document.createElement('th');
    th.textContent = n;
    tr.appendChild(th);
  }
  table.appendChild(tr);
}
function row(table, cells, stateCol) {
  const tr = document.createElement('tr');
  cells.forEach((c, i) => {
    const td = document.createElement('td');
    td.textContent = c === null || c === undefined ? '' : String(c);
    if (i === stateCol && STATES.includes(c)) td.className = c;
    tr.appendChild(td);
  });
  table.appendChild(tr);
}
async function refresh() {
  const info = await (await fetch('/v1/info')).json();
  const nodes = document.getElementById('nodes');
  header(nodes, ['node', 'uri']);
  for (const n of info.nodes) row(nodes, [n[0], n[1]]);
  const qs = await (await fetch('/v1/query')).json();
  const table = document.getElementById('queries');
  header(table, ['id', 'user', 'state', 'query']);
  for (const q of qs) {
    row(table, [q.queryId, q.user, q.state, q.query], 2);
    // clicking a query id loads the plan/detail view (plan.html role)
    const td = table.lastChild.firstChild;
    td.style.cursor = 'pointer';
    td.style.textDecoration = 'underline';
    td.onclick = () => showDetail(q.queryId);
  }
}
async function showDetail(id) {
  const q = await (await fetch('/v1/query/' + id)).json();
  document.getElementById('dtitle').style.display = '';
  const qs = q.queryStats || {};
  const mib = b => ((b || 0) / 1048576).toFixed(1) + ' MiB';
  let stages = '';
  for (const [fid, st] of Object.entries(q.stageStats || {})) {
    stages += 'stage ' + fid + ': tasks=' + st.tasks +
      ' rows ' + st.input_rows + '->' + st.output_rows +
      ' wall=' + (st.wall_ns / 1e6).toFixed(1) + 'ms' +
      ' jit=' + st.jit_dispatches + '/' + st.jit_compiles +
      ' prereduce=' + st.prereduce_rows +
      ' peak=' + mib(st.peak_memory_bytes) +
      ' xchg=' + st.exchange_fetched + 'f/' +
      st.exchange_consumed + 'c/' + st.exchange_purged + 'p\n';
  }
  let spec = (q.speculations || []).map(
    s => s.task + ' -> ' + s.clone + ' [' + s.state + ']').join(', ');
  // textContent only: SQL/plan/error are untrusted
  const prog = q.progress || {};
  document.getElementById('detail').textContent =
    'query: ' + (q.query || '') + '\n' +
    'state: ' + q.state + (q.error ? '\nerror: ' + q.error : '') +
    '\nprogress: ' + (prog.completedSplits || 0) + '/' +
    (prog.totalSplits || 0) + ' splits (' +
    (prog.progressPercent || 0) + '%), rows ' +
    (prog.processedRows || 0) +
    '  [' + (q.timeseriesSamples || 0) + ' samples]' +
    '\nresource group: ' + (q.resourceGroup || '(none)') +
    '  queued: ' + (q.queuedS || 0).toFixed(3) + 's' +
    '  execution: ' + (q.executionS || 0).toFixed(3) + 's' +
    '  plan cache: ' + (q.planCached ? 'hit' : 'miss') +
    '  result cache: ' + (q.resultCached ?
        'hit (' + mib(q.resultCacheBytes) + ' served)' : 'miss') +
    '\ntrace token: ' + (q.traceToken || '') +
    '\noutput rows: ' + q.outputRows +
    '\npeak memory: ' + mib(qs.peak_memory_bytes) +
    '  jit dispatches: ' + (qs.jit_dispatches || 0) +
    '\nstage retry rounds: ' + (q.stageRetryRounds || 0) +
    '  recovery rounds: ' + (q.recoveryRounds || 0) +
    '\nproducer re-runs: ' + (q.producerReruns || 0) +
    '  spooled pages: ' + ((q.queryStats || {}).pages_spooled || 0) +
    '  drained workers: ' + ((q.drainedWorkers || []).join(', ') ||
                             '(none)') +
    '\nspeculations: ' + (spec || '(none)') +
    '\n\n-- stage stats --\n' + (stages || '(none)\n') +
    '\n-- distributed plan --\n' + (q.plan || '(none)');
}
refresh(); setInterval(refresh, 2000);
</script></body></html>
"""


class CoordinatorServer:
    def __init__(self, registry: ConnectorRegistry, default_catalog: str,
                 config: EngineConfig = DEFAULT, port: int = 0,
                 verbose: bool = False, authenticator=None,
                 internal_secret: Optional[str] = None,
                 session_property_manager=None,
                 cluster_memory_limit_bytes: Optional[int] = None,
                 min_workers: int = 0,
                 min_workers_wait_s: float = 10.0,
                 http_client=None, fault_injector=None,
                 heartbeat_interval_s: float = 0.5,
                 heartbeat_max_missed: int = 3,
                 event_log_path: Optional[str] = None,
                 resource_groups=None,
                 standby_of: Optional[str] = None):
        from presto_tpu.server.errortracker import RetryingHttpClient
        from presto_tpu.server.security import InternalAuthenticator
        from presto_tpu.session import ResourceGroupManager

        self.registry = registry
        self.default_catalog = default_catalog
        self.config = config
        self.verbose = verbose
        from presto_tpu.session import GrantStore

        # every coordinator->worker request (task create, status poll,
        # result drain, cancel fan-out) goes through the error-tracked
        # client; ``fault_injector`` simulates transport failures on
        # this path in chaos tests
        self.http = http_client or RetryingHttpClient(
            max_error_duration_s=config.remote_request_max_error_duration_s,
            min_backoff_s=config.remote_request_min_backoff_s,
            max_backoff_s=config.remote_request_max_backoff_s,
            injector=fault_injector)
        self.nodes = NodeManager(max_missed=heartbeat_max_missed,
                                 interval_s=heartbeat_interval_s)
        # spooled exchange tier (server/spool.py): the coordinator reads
        # the spool for root-drain moves and completeness verification,
        # GCs each query's spool directory, and sweeps orphans left by a
        # crashed predecessor at start.  Always constructed (dirs are
        # lazy) so per-session toggles work; exchange_spooling_enabled
        # gates every use.
        from presto_tpu.server.spool import make_spool_store

        self.spool = make_spool_store(config, injector=fault_injector)
        # kept for the device-plane chaos seam: checkpoint groups
        # consult apply_device before dispatch (faults.add_device_rule)
        self.fault_injector = fault_injector
        # -- coordinator HA (server/statestore.py) -------------------------
        # ``standby_of`` names the active coordinator this node shadows:
        # a standby journals nothing, sweeps nothing, and serves no
        # statements until it wins the takeover lease and ADOPTS the
        # journal.  With no state path configured (the default) every
        # HA code path is inert.
        from presto_tpu.server.statestore import make_state_store

        self.statestore = make_state_store(config)
        self.standby_of = standby_of
        self.killed = False
        self.is_active = standby_of is None
        # chaos/test hook: called (query, phase) at journaled lifecycle
        # transitions — tests hold a query AT a phase to kill the
        # coordinator there deterministically
        self.phase_hook = None
        self.ha_counters: Dict = {"failovers": 0, "adopted": {}}
        self._ha_lock = threading.Lock()
        self._ha_stop = threading.Event()
        self._lease_generation = 0
        self._owner_id = f"co-{uuid.uuid4().hex[:8]}"
        if config.exchange_spooling_enabled and standby_of is None:
            try:
                self.spool.sweep_orphans(
                    config.exchange_spool_orphan_age_s)
            except Exception:  # noqa: BLE001 - sweep is best-effort
                pass
        self.queries: Dict[str, QueryExecution] = {}
        # dispatcher-lifecycle latency histograms (/metrics:
        # presto_query_queued_seconds / presto_query_execution_seconds),
        # observed once per query at completion
        from presto_tpu.server.metrics import Histogram

        self.latency_histograms = {"queued": Histogram(),
                                   "execution": Histogram()}
        # mesh-wide event stream (EventListener SPI / QueryMonitor role):
        # the coordinator fires query lifecycle + fault-tolerance events;
        # ``event_log_path`` bundles the query.json JSON-lines listener
        self.event_bus = ev.EventBus()
        if event_log_path:
            self.event_bus.register(
                ev.JsonLinesEventListener(event_log_path))
        # admission control tree; callers may hand in a configured
        # manager (per-group limits/weights/policies) — the serving
        # tier's dispatch loop arbitrates every statement through it
        self.resource_groups = resource_groups or ResourceGroupManager()
        from presto_tpu.server.dispatcher import DispatchManager

        self.dispatcher = DispatchManager(self)
        # device-sharded exchange executors (mesh_device_exchange): one
        # MeshQueryRunner per (shard count, lowering-knob fingerprint),
        # shared across queries so compiled SPMD programs amortize like
        # the plan cache amortizes plans.  The lock serializes runs: the
        # runner's per-run counters (last_run_info) are read back under
        # it, and concurrent collective programs on one device set gain
        # nothing anyway.
        self._mesh_executors: Dict[Tuple, object] = {}
        self.mesh_executor_lock = threading.Lock()
        # device-exchange observability counters (/metrics:
        # presto_device_exchange_{queries,bytes,fallback}_total) —
        # queries served, bytes moved per boundary mode, and fallbacks
        # to the HTTP plane by reason category
        self.device_exchange_counters: Dict = {
            "queries": 0, "bytes": {}, "fallbacks": {},
            # mid-program fault tolerance: resumes by mode
            # (device re-lower vs http degrade) and boundary-checkpoint
            # bytes spooled (presto_device_exchange_resume_total /
            # presto_device_checkpoint_bytes_total)
            "resumes": {}, "checkpoint_bytes": 0}
        self._dx_lock = threading.Lock()
        # test hook: called (fragment, shard, rows) on EVERY progress
        # beacon (the slow-task-style hold for mid-query progress tests)
        self._beacon_test_hook = None
        self.grants = GrantStore()
        self.authenticator = authenticator
        self.internal_auth = (InternalAuthenticator(internal_secret)
                              if internal_secret else None)
        self.session_property_manager = session_property_manager
        # ClusterSizeMonitor role: queries wait for this many schedulable
        # workers before dispatching (0 = no requirement)
        self.min_workers = min_workers
        self.min_workers_wait_s = min_workers_wait_s
        # ClusterMemoryManager + pluggable LowMemoryKiller role
        # (server/README.md "Memory model & overload").  The tick always
        # runs: it folds worker MemoryInfo and feeds resource-group
        # soft-memory accounting even with every kill knob off; killing
        # only happens when a limit is configured or a worker pool has
        # been blocked past the grace delay.
        self.cluster_memory_limit_bytes = cluster_memory_limit_bytes
        self.memory_info: Dict[str, Dict] = {}   # node_id -> MemoryInfo
        self._memory_stop = threading.Event()
        # node_id -> monotonic first-seen time with blocked pool drivers
        # (the killer arms when any age exceeds low_memory_killer_delay_s)
        self._blocked_seen: Dict[str, float] = {}
        # reason -> administrative kills (/metrics:
        # presto_cluster_killed_queries_total)
        self.kill_counters: Dict[str, int] = {}
        self._memory_thread = threading.Thread(
            target=self._memory_loop, daemon=True,
            name="cluster-memory-manager")
        self._memory_thread.start()
        co = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def _json(self, code: int, payload,
                      extra_headers=None) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, str(v))
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _has_internal_token(self) -> bool:
                from presto_tpu.server.security import (
                    InternalAuthenticator,
                )

                return (co.internal_auth is not None
                        and co.internal_auth.verify(self.headers.get(
                            InternalAuthenticator.HEADER)))

            def _authenticated_user(self):
                """Authenticated principal, or None after sending 401.
                Applies to every query-facing endpoint when an
                authenticator is configured; a peer holding the cluster
                token may vouch for the user it stamps (trusted proxy
                / internal fetches)."""
                user = self.headers.get("X-Presto-User", "user")
                if co.authenticator is None:
                    return user
                if self._has_internal_token():
                    return user
                # authenticator may be a single mechanism or an
                # AuthenticatorStack (Basic password, Bearer JWT, ...)
                if hasattr(co.authenticator, "authenticate_header"):
                    auth_user = co.authenticator.authenticate_header(
                        self.headers)
                else:
                    auth_user = co.authenticator.authenticate_basic(
                        self.headers.get("Authorization"))
                if auth_user is not None:
                    return auth_user
                self.send_response(401)
                self.send_header("WWW-Authenticate",
                                 'Basic realm="presto-tpu"')
                self.send_header("Content-Length", "0")
                self.send_header("Connection", "close")
                self.end_headers()
                self.close_connection = True
                return None

            def do_POST(self):  # noqa: N802
                parts = self.path.strip("/").split("/")
                if parts == ["v1", "statement"]:
                    n = int(self.headers.get("Content-Length", 0))
                    sql = self.rfile.read(n).decode("utf-8")
                    if not co.is_active:
                        # a standby serves nothing until it wins the
                        # takeover lease; clients fail over by address
                        self._json(503, {"error": "standby coordinator "
                                                  "is not active"})
                        return
                    user = self._authenticated_user()
                    if user is None:
                        return
                    import urllib.parse as _up

                    def _kv_header(name):
                        raw = self.headers.get(name, "")
                        out = {}
                        for part in raw.split(","):
                            if "=" in part:
                                k, _, v = part.partition("=")
                                out[k.strip()] = _up.unquote(v)
                        return out

                    # serving tier (server/dispatcher.py): the handler
                    # only enqueues — admission, planning, and execution
                    # all happen off this thread (QUEUED ->
                    # WAITING_FOR_RESOURCES -> RUNNING lifecycle)
                    q = co.dispatcher.submit(
                        sql, user=user,
                        session_properties=_kv_header("X-Presto-Session"),
                        catalog=self.headers.get("X-Presto-Catalog"),
                        prepared=_kv_header(
                            "X-Presto-Prepared-Statements"),
                        trace_token=self.headers.get(
                            "X-Presto-Trace-Token"))
                    hdrs = {}
                    if q.retry_after_s is not None:
                        # shed at submit: the ack itself tells clients
                        # (and proxies) when to come back
                        hdrs["Retry-After"] = max(1, int(q.retry_after_s))
                    self._json(200, {
                        "id": q.query_id,
                        "nextUri": f"{co.uri}/v1/statement/executing/"
                                   f"{q.query_id}/0",
                        "stats": {"state": q.state}}, extra_headers=hdrs)
                    return
                if parts == ["v1", "announcement"]:
                    # when a cluster secret exists, only peers holding
                    # it may join: an unauthenticated announcement would
                    # otherwise register an attacker URI that later
                    # receives the internal token on task create
                    if co.internal_auth is not None and \
                            not self._has_internal_token():
                        self._json(401, {"error": "unauthenticated "
                                                  "announcement"})
                        return
                    n = int(self.headers.get("Content-Length", 0))
                    ann = json.loads(self.rfile.read(n))
                    co.nodes.announce(ann["nodeId"], ann["uri"],
                                      ann.get("location", ""),
                                      ann.get("meshFingerprint"))
                    if ann.get("memoryInfo") is not None:
                        # announcements push MemoryInfo so the cluster
                        # memory manager sees fresh pool state even
                        # between its own /v1/memory polls
                        co.memory_info[ann["nodeId"]] = ann["memoryInfo"]
                    self._json(200, {"ok": True})
                    return
                self._json(404, {"error": f"bad path {self.path}"})

            def do_DELETE(self):  # noqa: N802
                parts = self.path.strip("/").split("/")
                if parts[:2] == ["v1", "query"] and len(parts) == 3:
                    if self._authenticated_user() is None:
                        return
                    q = co.queries.get(parts[2])
                    if q is None:
                        self._json(404, {"error": "no such query"})
                        return
                    q.cancel()
                    self._json(200, {"killed": parts[2]})
                    return
                self._json(404, {"error": f"bad path {self.path}"})

            def do_GET(self):  # noqa: N802
                parts = self.path.strip("/").split("/")
                # /v1/info stays open (health probe); everything that
                # exposes SQL text, plans, or result rows authenticates
                if parts != ["v1", "info"] and parts[:1] == ["v1"]:
                    if self._authenticated_user() is None:
                        return
                if parts[:3] == ["v1", "statement", "executing"] \
                        and len(parts) == 5:
                    q = co.queries.get(parts[3])
                    if q is None:
                        self._json(404, {"error": "no such query"})
                        return
                    # block briefly for long-poll semantics
                    q.rows_done.wait(timeout=0.5)
                    self._json(200, q.results_payload(co.uri))
                    return
                if parts == ["v1", "info"]:
                    self._json(200, {"coordinator": True,
                                     "nodes": co.nodes.alive_nodes()})
                    return
                if parts == ["metrics"]:
                    from presto_tpu.server.metrics import (
                        coordinator_metrics,
                    )

                    body = coordinator_metrics(co).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if parts == ["ui"] or parts == [""]:
                    body = _UI_HTML.encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/html; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                # QueryResource observability (SURVEY §5.5):
                if parts == ["v1", "query"]:
                    self._json(200, [
                        {"queryId": q.query_id, "state": q.state,
                         "user": q.user,
                         "query": q.sql[:200],
                         "traceToken": q.trace_token,
                         "errorName": q.error_name,
                         "outputRows": len(q.result_rows),
                         "wallS": round((q.query_stats or {}).get(
                             "elapsed_s",
                             (q.end_time or ev.now()) - q.create_time),
                             3),
                         "peakMemoryBytes": (q.query_stats or {}).get(
                             "peak_memory_bytes", 0),
                         "stageRetryRounds": q.stage_retry_rounds,
                         "recoveryRounds": q.recovery_rounds,
                         "producerReruns": q.producer_reruns_total,
                         "spooledPages": (q.query_stats or {}).get(
                             "pages_spooled", 0),
                         "queuedS": round(q.queued_s, 3),
                         "resourceGroup": q.resource_group_name,
                         "planCached": q.plan_cached,
                         "resultCached": q.result_cached,
                         "resultCacheBytes": q.result_cache_bytes,
                         # live progress (sampler-fed, mid-query)
                         "totalSplits": q._progress.get(
                             "totalSplits", 0),
                         "completedSplits": q._progress.get(
                             "completedSplits", 0),
                         "progressPercent": q._progress.get(
                             "progressPercent", 0.0)}
                        for q in co.queries.values()])
                    return
                if parts == ["v1", "tasks"]:
                    # live task state for system.runtime.tasks, fed
                    # from each query's sampler rollup (updated
                    # mid-query at the sample cadence; the final
                    # post-drain collection supersedes it) so a hung
                    # worker costs bounded staleness, never a dropped
                    # listing.  Tasks the rollup has not seen yet —
                    # sampling disabled, or polled before the first
                    # sweep — still come from the worker fan-out.
                    out = []
                    seen = set()
                    for q in list(co.queries.values()):
                        with q._stats_lock:
                            tss = [dict(ts)
                                   for lst in q.task_stats.values()
                                   for ts in lst]
                        for ts in tss:
                            tid = ts.get("task_id")
                            if not tid:
                                continue
                            seen.add(tid)
                            out.append({"taskId": tid,
                                        "state": ts.get("state", ""),
                                        "nodeId": "",
                                        "taskStats": ts})
                    for nid, uri in co.nodes.responsive_nodes():
                        try:
                            hdrs = (co.internal_auth.header()
                                    if co.internal_auth is not None
                                    else {})
                            resp = co.http.request(
                                f"{uri}/v1/task", headers=hdrs,
                                timeout=5, description="task listing",
                                max_error_duration_s=0.0)
                            for t in resp.json():
                                if t.get("taskId") in seen:
                                    continue
                                t["nodeId"] = nid
                                out.append(t)
                        except Exception:  # noqa: BLE001 - node flaky
                            pass
                    self._json(200, out)
                    return
                if parts[:2] == ["v1", "query"] and len(parts) == 4 \
                        and parts[3] == "timeseries":
                    # the live sampler's bounded per-query ring: one
                    # sample per sweep while the query was RUNNING
                    q = co.queries.get(parts[2])
                    if q is None:
                        self._json(404, {"error": "no such query"})
                        return
                    with q._stats_lock:
                        samples = list(q.timeseries)
                    self._json(200, {"queryId": q.query_id,
                                     "state": q.state,
                                     "traceToken": q.trace_token,
                                     "samples": samples})
                    return
                if parts[:2] == ["v1", "query"] and len(parts) == 4 \
                        and parts[3] == "spans":
                    # the timed span tree (same shape query.json carries
                    # on QueryCompletedEvent — the two must round-trip)
                    q = co.queries.get(parts[2])
                    if q is None:
                        self._json(404, {"error": "no such query"})
                        return
                    self._json(200, q.spans())
                    return
                if parts[:2] == ["v1", "query"] and len(parts) == 3:
                    q = co.queries.get(parts[2])
                    if q is None:
                        self._json(404, {"error": "no such query"})
                        return
                    with q._recovery_lock:
                        speculations = [
                            {"task": tid, "clone": sp.get("clone"),
                             "state": sp.get("state")}
                            for tid, sp in q._speculations.items()]
                    self._json(200, {
                        "queryId": q.query_id, "state": q.state,
                        "user": q.user, "query": q.sql,
                        "error": q.error,
                        "errorName": q.error_name,
                        "errorType": q.error_type,
                        "errorCode": q.error_code,
                        # serving tier: admission group, queued-vs-
                        # execution split, plan-cache disposition
                        "resourceGroup": q.resource_group_name,
                        "queuedS": round(q.queued_s, 6),
                        "executionS": round(q.execution_s, 6),
                        "planCached": q.plan_cached,
                        # result-cache disposition: true = this run was
                        # served from spool pages with zero execution
                        "resultCached": q.result_cached,
                        "resultCacheBytes": q.result_cache_bytes,
                        "plan": q.plan_text,
                        "columns": q.column_names,
                        "outputRows": len(q.result_rows),
                        "traceToken": q.trace_token,
                        # PR 5 recovery machinery, previously visible
                        # only as test-probed coordinator attributes
                        "stageRetryRounds": q.stage_retry_rounds,
                        "recoveryRounds": q.recovery_rounds,
                        # spooled-exchange observability: producer
                        # re-runs (0 with spooling on) and workers
                        # gracefully drained out of this query
                        "producerReruns": q.producer_reruns_total,
                        "drainedWorkers": sorted(q._drained_uris),
                        "speculations": speculations,
                        "stageStats": {str(fid): st for fid, st
                                       in q.stage_stats.items()},
                        "taskStats": {str(fid): ts for fid, ts
                                      in q.task_stats.items()},
                        "queryStats": q.query_stats,
                        # device-sharded exchange tier: per-boundary
                        # transport counters + collective-tier detail
                        # (or the fallback reason)
                        "exchangeModes": dict(q.exchange_modes),
                        "deviceExchange": dict(q.device_exchange_info),
                        # mid-program fault tolerance: boundary
                        # checkpoints spooled and resume decisions
                        "deviceCheckpoints": dict(q._device_ckpts),
                        "deviceResumes": [dict(r)
                                          for r in q.device_resumes],
                        # live progress + time-series depth (the web UI
                        # detail page shows mid-query movement)
                        "progress": dict(q._progress),
                        "timeseriesSamples": len(q.timeseries)})
                    return
                self._json(404, {"error": f"bad path {self.path}"})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self.uri = f"http://127.0.0.1:{self.port}"
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="coordinator-http")
        self._thread.start()
        # HA: the active coordinator acquires + renews the takeover
        # lease (heartbeat object with TTL); a standby watches it and
        # claims the next generation on expiry, then adopts the journal
        if self.statestore is not None:
            if self.is_active:
                try:
                    gen = self.statestore.try_claim_lease(
                        self._owner_id, config.coordinator_lease_ttl_s,
                        force=True)
                    self._lease_generation = gen or 0
                except Exception:  # noqa: BLE001 - HA is best-effort
                    pass
            self._ha_thread = threading.Thread(
                target=self._ha_loop, daemon=True, name="coordinator-ha")
            self._ha_thread.start()

    # -- coordinator HA ----------------------------------------------------
    def kill(self) -> None:
        """Chaos: process-level coordinator death (faults.py
        ``kill_coordinator``).  Listeners stop, the lease stops
        renewing (so a standby can claim it), and every query thread
        aborts with NO external side effects — worker tasks keep
        producing into the spool, the journal stays as written, and
        nothing is GC'd.  This is NOT close(): close is a clean
        shutdown, kill is the failure the standby exists for."""
        self.killed = True
        self._ha_stop.set()
        self._memory_stop.set()
        self.dispatcher.close()
        self.nodes.close()
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:  # noqa: BLE001 - already down
            pass

    def _ha_loop(self) -> None:
        """One loop, both roles: the active coordinator renews the
        lease every ttl/3; a standby watches for expiry and claims via
        the compare-and-swap marker — exactly one of N racing standbys
        wins the generation, adopts the journal, and activates."""
        ttl = self.config.coordinator_lease_ttl_s
        tick = max(ttl / 3.0, 0.05)
        while not self._ha_stop.wait(tick):
            if self.killed:
                return
            try:
                if self.is_active:
                    if self._lease_generation and not \
                            self.statestore.renew_lease(
                                self._owner_id, self._lease_generation,
                                ttl):
                        # superseded: another coordinator claimed a
                        # newer generation — stop acting as primary
                        self.log("coordinator lease superseded; "
                                 "standing down")
                        self.is_active = False
                    self._journal_gc_tick()
                    continue
                lease = self.statestore.read_lease()
                gen = self.statestore.try_claim_lease(self._owner_id,
                                                      ttl)
                if gen is None:
                    continue
                self._lease_generation = gen
                self.is_active = True
                prev = (lease or {}).get("owner", "")
                self.log(f"standby won takeover lease generation {gen} "
                         f"(previous owner {prev or '?'})")
                self._adopt_journal(prev, gen)
            except Exception as e:  # noqa: BLE001 - HA must keep trying
                self.log(f"HA loop error: {e}")

    def _adopt_journal(self, previous_owner: str, generation: int
                       ) -> None:
        """Failover adoption: every journaled query the dead
        coordinator owned is re-served (FINISHED: rows from adopted
        spool pages), re-attached/repointed/restarted (RUNNING, through
        the existing spool-recovery machinery), or re-queued
        (QUEUED/PLANNING: back into admission) — then this coordinator
        is open for business."""
        adopted = 0
        for qid in self.statestore.list_queries():
            if qid in self.queries:
                continue
            try:
                journal = self.statestore.read(qid)
            except Exception:  # noqa: BLE001 - torn/unreadable doc
                continue
            if journal is None:
                continue
            adopted += 1
            if journal.state in ("QUEUED", "PLANNING") or (
                    journal.state not in ("FINISHED", "FAILED")
                    and not journal.placements):
                # never scheduled anything: plain re-admission under
                # the SAME query id (client polls find it here)
                self.dispatcher.submit(
                    journal.sql, user=journal.user, query_id=qid,
                    session_properties=journal.session_properties,
                    catalog=journal.catalog, prepared=journal.prepared,
                    trace_token=journal.trace_token,
                    device_checkpoints=journal.device_checkpoints)
                self.count_adopted("requeued")
                self.event_bus.query_adopted(ev.QueryAdoptedEvent(
                    qid, journal.trace_token, journal.state, "requeued",
                    ev.now()))
                continue
            QueryExecution.adopt(self, journal)
        with self._ha_lock:
            self.ha_counters["failovers"] += 1
        self.event_bus.coordinator_failover(ev.CoordinatorFailoverEvent(
            self.uri, previous_owner, generation, adopted, ev.now()))

    def count_adopted(self, outcome: str) -> None:
        with self._ha_lock:
            a = self.ha_counters["adopted"]
            a[outcome] = a.get(outcome, 0) + 1

    def _journal_gc_tick(self) -> None:
        """Journal GC, ridden on the active coordinator's lease
        heartbeat: TERMINAL ``queries/{id}`` entries older than the
        retention window — or beyond the retention count — are reaped;
        in-flight entries are never touched (a standby must always be
        able to adopt them).  Runs at most once per retention_s/4."""
        cfg = self.config
        retention = float(
            getattr(cfg, "coordinator_journal_retention_s", 0) or 0)
        if retention <= 0 or self.statestore is None:
            return
        now = time.monotonic()
        nxt = getattr(self, "_next_journal_gc", 0.0)
        if now < nxt:
            return
        self._next_journal_gc = now + max(retention / 4.0, 0.05)
        try:
            deleted = self.statestore.gc_terminal(
                retention, int(cfg.coordinator_journal_retention_count))
            if deleted:
                self.log(f"journal GC reaped {len(deleted)} terminal "
                         f"entries")
        except Exception:  # noqa: BLE001 - GC is best-effort
            pass

    def count_device_fallback(self, kind: str) -> None:
        """One query fell back from the collective tier to the HTTP
        plane for this reason category (bounded label set)."""
        with self._dx_lock:
            fb = self.device_exchange_counters["fallbacks"]
            fb[kind] = fb.get(kind, 0) + 1

    def count_device_success(self, boundaries: List[Dict]) -> None:
        """One query was served by the collective tier: count it and
        the bytes each boundary mode moved (per-shard sums)."""
        with self._dx_lock:
            self.device_exchange_counters["queries"] += 1
            by_mode = self.device_exchange_counters["bytes"]
            for b in boundaries:
                kind = b.get("kind", "?")
                by_mode[kind] = by_mode.get(kind, 0) + \
                    sum(int(v) for v in b.get("bytes", []))

    def count_device_resume(self, mode: str) -> None:
        """One mid-program resume decision on the collective tier:
        'device' (re-lowered remaining checkpoint groups) or 'http'
        (degraded to the task-scheduled plane)."""
        with self._dx_lock:
            rs = self.device_exchange_counters["resumes"]
            rs[mode] = rs.get(mode, 0) + 1

    def count_device_checkpoint_bytes(self, n: int) -> None:
        """Boundary-checkpoint wire bytes write-through spooled."""
        with self._dx_lock:
            self.device_exchange_counters["checkpoint_bytes"] += int(n)

    def mesh_executor(self, cfg, nparts: int):
        """The shared mesh runner for one (shard count, lowering knobs)
        shape.  Callers hold ``mesh_executor_lock`` around execute +
        last_run_info readback.  ``mesh_progress_beacons`` keys the
        runner too: beacons are traced INTO the program, so on/off must
        compile distinct programs."""
        from presto_tpu.parallel.sqlmesh import MeshQueryRunner

        key = (nparts, cfg.partitioned_join_build,
               cfg.grouped_mesh_execution, cfg.direct_groupby_max_domain,
               cfg.device_join_probe_max_build_rows,
               cfg.mesh_progress_beacons)
        runner = self._mesh_executors.get(key)
        if runner is None:
            runner = MeshQueryRunner(self.registry, self.default_catalog,
                                     n_devices=nparts, config=cfg)
            self._mesh_executors[key] = runner
        return runner

    def _memory_loop(self, interval_s: float = 0.5) -> None:
        """The ClusterMemoryManager loop (ClusterMemoryManager.java:
        173-347): every tick polls worker MemoryInfo, feeds the
        resource-group soft-memory gate, enforces the per-query and
        cluster-wide memory limits, and — when a worker pool has had
        blocked drivers past ``low_memory_killer_delay_s`` — runs the
        configured LowMemoryKiller policy to fail exactly one victim."""
        while not self._memory_stop.wait(interval_s):
            if not self.is_active:
                continue   # a standby arbitrates nothing until takeover
            try:
                self._memory_tick()
            except Exception as e:  # noqa: BLE001 - the tick must survive
                self.log(f"memory tick error: {e}")

    def _poll_worker_memory(self) -> None:
        """GET /v1/memory on every responsive node into
        ``self.memory_info`` (announcements push the same MemoryInfo in
        between polls)."""
        hdrs = (self.internal_auth.header()
                if self.internal_auth is not None else {})
        for nid, uri in self.nodes.responsive_nodes():
            try:
                req = urllib.request.Request(f"{uri}/v1/memory",
                                             headers=dict(hdrs))
                with urllib.request.urlopen(req, timeout=2) as resp:
                    info = json.loads(resp.read())
            except Exception:  # noqa: BLE001 - node flaky
                continue
            self.memory_info[nid] = info

    def _memory_tick(self) -> None:
        """One arbitration pass.  Kills at most ONE victim per tick (the
        reference's one-kill-per-run posture: freeing one query's memory
        unblocks pools cluster-wide; the next tick re-evaluates)."""
        self._poll_worker_memory()
        now = time.monotonic()
        # drop MemoryInfo for nodes the failure detector no longer
        # considers responsive: a worker that dies while its pool
        # reports blocked drivers would otherwise pin blocked_nodes
        # forever (one healthy victim killed per grace period) and its
        # stale reservations would permanently inflate the cluster and
        # per-query totals the limits act on
        live = {nid for nid, _uri in self.nodes.responsive_nodes()}
        for nid in list(self.memory_info):
            if nid not in live:
                self.memory_info.pop(nid, None)
                self._blocked_seen.pop(nid, None)
        total = 0
        per_query: Dict[str, int] = {}
        per_query_blocked: Dict[str, int] = {}   # reservation on blocked
        blocked_nodes = set()
        for nid, info in list(self.memory_info.items()):
            total += int(info.get("reserved", 0))
            pool = info.get("pool") or {}
            node_blocked = int(pool.get("blockedDrivers", 0)) > 0
            if node_blocked:
                blocked_nodes.add(nid)
                self._blocked_seen.setdefault(nid, now)
            else:
                self._blocked_seen.pop(nid, None)
            for qid, q in info.get("queries", {}).items():
                used = int(q.get("reserved", 0))
                per_query[qid] = per_query.get(qid, 0) + used
                if node_blocked:
                    per_query_blocked[qid] = \
                        per_query_blocked.get(qid, 0) + used
        # mesh-executed queries create no worker tasks; fold their live
        # sampler peak (synthetic device TaskStats rollup) so the
        # per-query total limit sees them too.  The sampler exposes no
        # current-usage gauge, so mesh queries are judged on their
        # LIFETIME PEAK: a mesh query whose usage already dropped back
        # under query_max_total_memory_bytes can still be killed.
        # Documented in server/README.md "Memory model & overload".
        for qid, q in list(self.queries.items()):
            if qid in per_query or q.state not in ("RUNNING",
                                                   "SCHEDULING"):
                continue
            peak = int((getattr(q, "_progress", None) or {})
                       .get("peakMemoryBytes", 0) or 0)
            if peak > 0:
                per_query[qid] = peak
        # feed group memory usage so soft limits gate new admissions
        # (InternalResourceGroup soft_memory_limit role) — this ALWAYS
        # runs, independent of any kill knob
        per_user: Dict[str, int] = {}
        for qid, used in per_query.items():
            q = self.queries.get(qid)
            if q is not None:
                per_user[q.user] = per_user.get(q.user, 0) + used
        self.resource_groups.update_memory_usage(per_user)

        def _killable(qid):
            q = self.queries.get(qid)
            return (q if q is not None
                    and q.state in ("RUNNING", "SCHEDULING") else None)

        # 1) per-query cluster-wide total limit (the session-scoped
        #    query_max_total_memory_bytes knob; reference
        #    EXCEEDED_GLOBAL_MEMORY_LIMIT shape)
        for qid in sorted(per_query):
            q = _killable(qid)
            if q is None:
                continue
            qcfg = getattr(q, "_cfg", None) or self.config
            limit = int(getattr(qcfg, "query_max_total_memory_bytes",
                                0) or 0)
            if limit > 0 and per_query[qid] > limit:
                self.log(f"killing {qid}: total reservation "
                         f"{per_query[qid]} > per-query limit {limit}")
                q.kill(
                    f"Query exceeded distributed total memory limit of "
                    f"{limit} bytes (reserved {per_query[qid]})",
                    EXCEEDED_GLOBAL_MEMORY_LIMIT,
                    reason="per-query-total-limit")
                return
        # 2) legacy cluster-wide total limit (kept message: tests and
        #    operators match on "out of memory")
        if (self.cluster_memory_limit_bytes is not None and per_query
                and total > self.cluster_memory_limit_bytes):
            victim = max(sorted(per_query), key=per_query.get)
            q = _killable(victim)
            if q is not None:
                self.log(f"low-memory killer: killing {victim} "
                         f"(cluster {total} > "
                         f"{self.cluster_memory_limit_bytes})")
                q.kill("Query killed because the cluster is out of "
                       "memory. Please try again in a few minutes.",
                       CLUSTER_OUT_OF_MEMORY, reason="cluster-limit")
                return
        # 3) the low-memory killer proper: a pool with drivers blocked
        #    past the grace delay means memory cannot free itself —
        #    select one victim by policy and fail it
        delay = float(self.config.low_memory_killer_delay_s)
        stuck = [nid for nid in blocked_nodes
                 if now - self._blocked_seen.get(nid, now) >= delay]
        if not stuck or not per_query:
            return
        victim = pick_low_memory_victim(
            self.config.low_memory_killer_policy, per_query,
            per_query_blocked,
            {qid for qid in per_query if _killable(qid) is not None})
        q = _killable(victim) if victim is not None else None
        if q is None:
            return
        self.log(f"low-memory killer "
                 f"({self.config.low_memory_killer_policy}): killing "
                 f"{victim} (pools blocked {sorted(stuck)})")
        q.kill("Query killed because the cluster is out of memory "
               f"(worker pools blocked on nodes {sorted(stuck)}). "
               "Please try again in a few minutes.",
               CLUSTER_OUT_OF_MEMORY,
               reason=self.config.low_memory_killer_policy)
        # fresh grace period before the next kill: give the cancel
        # fan-out time to actually free the victim's reservations
        for nid in stuck:
            self._blocked_seen.pop(nid, None)

    def log(self, msg: str) -> None:
        if self.verbose:
            print(msg)

    def close(self) -> None:
        self._ha_stop.set()
        self._memory_stop.set()
        self.dispatcher.close()
        # tables kept on the device by coordinator-side execution
        from presto_tpu.exec.scancache import drop_connectors

        drop_connectors(self.registry)
        self.nodes.close()
        self.spool.close()
        self._httpd.shutdown()
        self._httpd.server_close()
