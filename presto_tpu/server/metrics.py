"""Prometheus-text ``/metrics`` plane for coordinator and workers.

The reference exposes JMX beans scraped via jmx_exporter / the
``system.jmx`` catalog; here the same operational surface renders
directly in the Prometheus text exposition format (version 0.0.4) so a
scrape target needs nothing but HTTP GET /metrics:

- coordinator: query-state counts, whole-stage retry / leaf recovery /
  speculation counters (the PR 5 fault-tolerance machinery, previously
  test-private attributes), cluster memory, kernel caches, node counts;
- worker: task-state counts, memory reserved/peak, output pages,
  exchange dedup page counters (fetched/consumed/purged), jit
  dispatch/compile counters, kernel caches.

Families are built as plain (name, type, help, samples) tuples so the
renderer stays dependency-free and the builders are unit-testable
without HTTP.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Sequence, Tuple

#: one family: (name, 'gauge'|'counter', help, [(labels, value), ...])
Family = Tuple[str, str, str, List[Tuple[Dict[str, str], float]]]

#: fixed latency buckets (seconds) for the query-lifecycle histograms —
#: stable across scrapes so rate()/histogram_quantile() work
LATENCY_BUCKETS_S = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                     5.0, 10.0, 30.0, 60.0, 120.0, 300.0)


class Histogram:
    """A fixed-bucket Prometheus histogram (cumulative bucket counts +
    _sum + _count).  Observations come from the dispatcher lifecycle
    (queued / execution seconds per query); thread-safe because queries
    complete on their own threads."""

    def __init__(self, buckets: Sequence[float] = LATENCY_BUCKETS_S):
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * len(self.buckets)
        self.total = 0
        self.sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = max(float(value), 0.0)
        with self._lock:
            self.total += 1
            self.sum += v
            for i, le in enumerate(self.buckets):
                if v <= le:
                    self.counts[i] += 1

    def snapshot(self) -> Tuple[List[Tuple[float, int]], int, float]:
        """(cumulative (le, count) pairs, count, sum) — cumulative
        counts as the exposition format requires."""
        with self._lock:
            return (list(zip(self.buckets, self.counts)), self.total,
                    self.sum)


def histogram_text(name: str, help_: str, hist: Histogram) -> str:
    """Render one histogram family in the text exposition format."""
    pairs, total, sum_ = hist.snapshot()
    lines = [f"# HELP {name} {help_}", f"# TYPE {name} histogram"]
    for le, n in pairs:
        lines.append(f'{name}_bucket{{le="{_fmt(le)}"}} {n}')
    lines.append(f'{name}_bucket{{le="+Inf"}} {total}')
    lines.append(f"{name}_sum {repr(float(sum_))}")
    lines.append(f"{name}_count {total}")
    return "\n".join(lines) + "\n"


def _escape(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(v) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def prometheus_text(families: Sequence[Family]) -> str:
    lines: List[str] = []
    for name, mtype, help_, samples in families:
        lines.append(f"# HELP {name} {help_}")
        lines.append(f"# TYPE {name} {mtype}")
        for labels, value in samples:
            if labels:
                lab = ",".join(f'{k}="{_escape(v)}"'
                               for k, v in sorted(labels.items()))
                lines.append(f"{name}{{{lab}}} {_fmt(value)}")
            else:
                lines.append(f"{name} {_fmt(value)}")
    return "\n".join(lines) + "\n"


def _http_client_family(prefix: str, http) -> Family:
    stats = getattr(http, "stats", None) or {}
    return (f"{prefix}_http_client_total", "counter",
            "error-tracked transport requests by disposition "
            "(retries = transient errors retried with backoff; "
            "budget_exhausted/fatal = RemoteRequestError raised)",
            [({"kind": k}, v) for k, v in sorted(stats.items())])


def _kernel_cache_families(prefix: str) -> List[Family]:
    from presto_tpu.kernelcache import cache_stats, process_xla_stats

    stats = cache_stats()
    fams: List[Family] = []
    for key in ("size", "hits", "misses", "evictions", "compiles"):
        fams.append((
            f"{prefix}_kernel_cache_{key}",
            "gauge" if key == "size" else "counter",
            f"compiled-kernel cache {key} per named cache",
            [({"cache": name}, s.get(key, 0))
             for name, s in sorted(stats.items())]))
    # per-cache compile-time attribution (kernelcache.record_compile)
    fams.append((
        f"{prefix}_kernel_cache_compile_seconds_total", "counter",
        "wall seconds spent building entries per named cache",
        [({"cache": name}, s.get("compile_ns", 0) / 1e9)
         for name, s in sorted(stats.items())]))
    # what XLA built on threads that work for no task (a task's own
    # builds are in its taskStats: xla_builds ...)
    xla = process_xla_stats()
    fams.append((
        f"{prefix}_xla_untasked_total", "counter",
        "XLA programs built (compiled or loaded) outside any task, "
        "and how many of them were persistent-cache loads",
        [({"kind": "builds"}, xla["builds"]),
         ({"kind": "cache_hits"}, xla["cache_hits"])]))
    fams.append((
        f"{prefix}_xla_untasked_seconds_total", "counter",
        "seconds outside any task inside XLA's compile-or-load, and "
        "tracing and lowering before it",
        [({"kind": "build"}, xla["build_ns"] / 1e9),
         ({"kind": "trace_lower"}, xla["trace_lower_ns"] / 1e9)]))
    return fams


def _scan_cache_families(prefix: str) -> List[Family]:
    """The device-resident scan cache (exec/scancache.py), process-wide
    like the kernel caches: what is resident, and how often a task's
    scan of a table was handed kept batches."""
    from presto_tpu.exec.scancache import SCAN_CACHE

    s = SCAN_CACHE.stats()
    return [
        (f"{prefix}_scan_cache_resident_bytes", "gauge",
         "device bytes of table columns kept by the scan cache",
         [({}, s["resident_bytes"])]),
        (f"{prefix}_scan_cache_entries", "gauge",
         "kept runs (one task's scan of one table)",
         [({}, s["entries"])]),
        (f"{prefix}_scan_cache_total", "counter",
         "scans of a cacheable table by a task: handed kept batches "
         "(hits), generated and staged (misses); runs evicted for the "
         "byte budget (evictions)",
         [({"kind": k}, s[k]) for k in ("hits", "misses", "evictions")]),
        (f"{prefix}_scan_cache_hit_bytes_total", "counter",
         "device bytes handed to scans by the scan cache",
         [({}, s["hit_bytes"])]),
    ]


def _spool_families(prefix: str, spool, bytes_evicted: int = 0
                    ) -> List[Family]:
    """presto_spool_bytes_written/read/evicted_total: the spooled
    exchange's write-through volume, spool-read volume, and in-memory
    buffer bytes evicted under pressure (re-served from the spool)."""
    stats = getattr(spool, "stats", None) or {}
    return [
        (f"{prefix}_spool_bytes_written_total", "counter",
         "exchange pages written through to the spool store, bytes",
         [({}, stats.get("bytes_written", 0))]),
        (f"{prefix}_spool_bytes_read_total", "counter",
         "exchange pages read back from the spool store, bytes",
         [({}, stats.get("bytes_read", 0))]),
        (f"{prefix}_spool_bytes_evicted_total", "counter",
         "spooled pages evicted from in-memory output buffers, bytes",
         [({}, bytes_evicted)]),
    ]


def _plan_cache_families(prefix: str) -> List[Family]:
    """presto_plan_cache_{hits,misses,evictions}_total + size: the
    serving tier's plan cache (sql/plancache.py)."""
    from presto_tpu.sql import plancache

    s = plancache.stats()
    fams: List[Family] = [
        (f"{prefix}_plan_cache_size", "gauge",
         "cached plans currently held", [({}, s.get("size", 0))])]
    for key in ("hits", "misses", "evictions"):
        fams.append((
            f"{prefix}_plan_cache_{key}_total", "counter",
            f"plan cache {key} (evictions include stats-epoch "
            "invalidations)",
            [({}, s.get(key, 0))]))
    return fams


def _result_cache_families(prefix: str) -> List[Family]:
    """presto_result_cache_{hits,misses,evictions,bytes_served}_total
    + size/bytes gauges: the cross-query result cache
    (server/resultcache.py) — a hit serves a repeated statement from
    spool pages with zero execution."""
    from presto_tpu.server import resultcache

    s = resultcache.stats()
    fams: List[Family] = [
        (f"{prefix}_result_cache_size", "gauge",
         "cached results currently held", [({}, s.get("size", 0))]),
        (f"{prefix}_result_cache_bytes", "gauge",
         "spooled wire bytes currently held by the result cache",
         [({}, s.get("bytes", 0))])]
    for key in ("hits", "misses", "evictions", "bytes_served"):
        fams.append((
            f"{prefix}_result_cache_{key}_total", "counter",
            f"result cache {key} (evictions include stats-epoch "
            "invalidations; bytes_served = wire bytes drained to "
            "clients from cached spool pages)",
            [({}, s.get(key, 0))]))
    return fams


def _resource_group_families(manager) -> List[Family]:
    """Per-group admission gauges (queue depth + running count), the
    serving tier's contention surface."""
    stats = manager.stats() if manager is not None else []
    return [
        ("presto_resource_group_queued", "gauge",
         "queries waiting for admission per resource group",
         [({"group": s["name"]}, s["queued"]) for s in stats]),
        ("presto_resource_group_running", "gauge",
         "admitted (running) queries per resource group",
         [({"group": s["name"]}, s["running"]) for s in stats]),
        ("presto_resource_group_cpu_usage_seconds", "gauge",
         "charged CPU seconds per resource group (regenerating)",
         [({"group": s["name"]}, s["cpu_usage_s"]) for s in stats]),
    ]


def _device_exchange_families(co) -> List[Family]:
    """presto_device_exchange_{queries,bytes,fallback}_total: the
    collective data plane's scrape surface — queries served as ONE SPMD
    program, bytes moved per boundary mode (from the program's own
    per-shard counters), and HTTP-plane fallbacks by reason category
    (the bounded-label form of QueryExecution.device_exchange_info)."""
    dx = getattr(co, "device_exchange_counters", None) or {}
    with getattr(co, "_dx_lock", threading.Lock()):
        queries = dx.get("queries", 0)
        by_mode = dict(dx.get("bytes", {}))
        fallbacks = dict(dx.get("fallbacks", {}))
        resumes = dict(dx.get("resumes", {}))
        ckpt_bytes = dx.get("checkpoint_bytes", 0)
    return [
        ("presto_device_exchange_queries_total", "counter",
         "queries served by the device-sharded exchange tier "
         "(whole fragment DAG as one SPMD program)",
         [({}, queries)]),
        ("presto_device_exchange_bytes_total", "counter",
         "bytes moved through in-program collectives per boundary mode",
         [({"mode": m}, v) for m, v in sorted(by_mode.items())]
         or [({"mode": "hash"}, 0)]),
        ("presto_device_exchange_fallback_total", "counter",
         "collective-tier queries that fell back to the HTTP plane, "
         "by reason category",
         [({"reason": r}, v) for r, v in sorted(fallbacks.items())]
         or [({"reason": "none"}, 0)]),
        ("presto_device_exchange_resume_total", "counter",
         "mid-program resumes from boundary checkpoints, by mode "
         "(device: remaining groups re-run on the mesh; http: degraded "
         "to the HTTP plane scheduling only remaining fragments)",
         [({"mode": m}, v) for m, v in sorted(resumes.items())]
         or [({"mode": "device"}, 0)]),
        ("presto_device_checkpoint_bytes_total", "counter",
         "boundary-checkpoint bytes write-through'd into the spool "
         "between checkpoint groups (mesh_checkpoint_boundaries)",
         [({}, ckpt_bytes)]),
    ]


def _ha_families(co) -> List[Family]:
    """presto_coordinator_failover_total + presto_queries_adopted_total:
    the coordinator-HA plane — standby takeovers won (lease claims) and
    journaled queries adopted by outcome category (served / repointed /
    reattached / restarted / requeued / failed)."""
    ha = getattr(co, "ha_counters", None) or {}
    with getattr(co, "_ha_lock", threading.Lock()):
        failovers = ha.get("failovers", 0)
        adopted = dict(ha.get("adopted", {}))
    return [
        ("presto_coordinator_failover_total", "counter",
         "takeover leases won by this coordinator (journal adoptions)",
         [({}, failovers)]),
        ("presto_queries_adopted_total", "counter",
         "journaled queries adopted on failover, by outcome",
         [({"outcome": o}, v) for o, v in sorted(adopted.items())]
         or [({"outcome": "served"}, 0)]),
    ]


def coordinator_metrics(co) -> str:
    """Render the coordinator's /metrics payload from live state."""
    by_state: Dict[str, int] = {}
    retry_rounds = 0
    recovery_rounds = 0
    producer_reruns = 0
    spec_outcomes: Dict[str, int] = {}
    for q in list(co.queries.values()):
        by_state[q.state] = by_state.get(q.state, 0) + 1
        retry_rounds += q.stage_retry_rounds
        recovery_rounds += q.recovery_rounds
        producer_reruns += getattr(q, "producer_reruns_total", 0)
        for sp in list(getattr(q, "_speculations", {}).values()):
            state = sp.get("state", "racing")
            spec_outcomes[state] = spec_outcomes.get(state, 0) + 1
    mem_infos = list(co.memory_info.values())   # snapshot vs heartbeat
    mem_reserved = sum(int(i.get("reserved", 0)) for i in mem_infos)
    mem_peak = sum(int(i.get("peak", 0)) for i in mem_infos)
    fams: List[Family] = [
        ("presto_queries", "gauge",
         "queries known to this coordinator by state",
         [({"state": s}, n) for s, n in sorted(by_state.items())]),
        ("presto_stage_retry_rounds_total", "counter",
         "whole-stage retry rounds across all queries",
         [({}, retry_rounds)]),
        ("presto_task_recovery_rounds_total", "counter",
         "leaf task recovery rounds across all queries",
         [({}, recovery_rounds)]),
        ("presto_producer_reruns_total", "counter",
         "producer-subtree tasks re-executed by stage retry "
         "(0 with the spooled exchange on)",
         [({}, producer_reruns)]),
        ("presto_speculation_total", "counter",
         "speculative straggler clones by race outcome",
         [({"outcome": o}, n) for o, n in sorted(spec_outcomes.items())]
         or [({"outcome": "racing"}, 0)]),
        ("presto_cluster_nodes", "gauge",
         "workers by scheduling eligibility",
         [({"state": "active"}, len(co.nodes.alive_nodes())),
          ({"state": "responsive"}, len(co.nodes.responsive_nodes()))]),
        ("presto_cluster_memory_bytes", "gauge",
         "sum of worker-reported reservation bytes",
         [({"kind": "reserved"}, mem_reserved),
          ({"kind": "peak"}, mem_peak)]),
        ("presto_cluster_pool_blocked_drivers", "gauge",
         "drivers currently blocked on full worker memory pools, "
         "summed over worker-reported MemoryInfo",
         [({}, sum(int((i.get("pool") or {}).get("blockedDrivers", 0))
                   for i in mem_infos))]),
        ("presto_cluster_killed_queries_total", "counter",
         "queries administratively failed, by kill reason (low-memory "
         "killer policy / cluster-limit / per-query-total-limit / "
         "kill_query)",
         [({"reason": r}, v) for r, v in
          sorted((getattr(co, "kill_counters", None) or {}).items())]
         or [({"reason": "none"}, 0)]),
        ("presto_dispatcher_shed_queries_total", "counter",
         "statements rejected at submit because the dispatch backlog "
         "was full (overload shedding)",
         [({}, getattr(co.dispatcher, "shed_total", 0))]),
        _http_client_family("presto", co.http),
    ]
    fams.extend(_resource_group_families(
        getattr(co, "resource_groups", None)))
    fams.extend(_device_exchange_families(co))
    fams.extend(_ha_families(co))
    fams.extend(_plan_cache_families("presto"))
    fams.extend(_result_cache_families("presto"))
    fams.extend(_spool_families("presto", getattr(co, "spool", None)))
    fams.extend(_kernel_cache_families("presto"))
    fams.extend(_scan_cache_families("presto"))
    text = prometheus_text(fams)
    # dispatcher-lifecycle latency histograms: the scrape-side
    # cross-check for tools/qps_run.py's client-side latency numbers
    hists = getattr(co, "latency_histograms", None)
    if hists is not None:
        text += histogram_text(
            "presto_query_queued_seconds",
            "seconds queries spent queued for admission",
            hists["queued"])
        text += histogram_text(
            "presto_query_execution_seconds",
            "seconds queries spent executing (admission to settled)",
            hists["execution"])
    return text


def worker_metrics(worker) -> str:
    """Render one worker's /metrics payload from its task manager."""
    tm = worker.task_manager
    with tm._lock:
        tasks = list(tm.tasks.values())
    by_state: Dict[str, int] = {}
    pages = 0
    exchange = {"fetched": 0, "consumed": 0, "purged": 0}
    jit = {"dispatches": 0, "compiles": 0}
    prereduce = 0
    reserved = 0
    peak = 0
    bytes_evicted = 0
    for t in tasks:
        by_state[t.state] = by_state.get(t.state, 0) + 1
        # one source of truth for per-task counters: the same TaskStats
        # rollup the coordinator aggregates (server/task.py)
        ts = t.task_stats()
        pages += ts["pages_enqueued"]
        bytes_evicted += ts["bytes_evicted"]
        for k in exchange:
            exchange[k] += ts[f"exchange_{k}"]
        jit["dispatches"] += ts["jit_dispatches"]
        jit["compiles"] += ts["jit_compiles"]
        prereduce += ts["prereduce_rows"]
        mi = t.memory_info()
        reserved += mi["reserved"]
        peak = max(peak, mi["peak"])
    pool_info = tm.memory_pool.info()
    fams: List[Family] = [
        ("presto_worker_tasks", "gauge", "tasks on this worker by state",
         [({"state": s}, n) for s, n in sorted(by_state.items())]),
        ("presto_worker_memory_bytes", "gauge",
         "task memory on this worker",
         [({"kind": "reserved"}, reserved),
          ({"kind": "peak_task"}, peak)]),
        ("presto_worker_pool_bytes", "gauge",
         "the worker GENERAL memory pool (0 max = unlimited)",
         [({"kind": "max"}, pool_info["maxBytes"]),
          ({"kind": "reserved"}, pool_info["reservedBytes"]),
          ({"kind": "peak"}, pool_info["peakBytes"])]),
        ("presto_worker_pool_blocked_drivers", "gauge",
         "drivers blocked in reserve() on the full pool right now",
         [({}, pool_info["blockedDrivers"])]),
        ("presto_worker_output_pages_total", "counter",
         "pages enqueued into output buffers", [({}, pages)]),
        ("presto_worker_exchange_pages_total", "counter",
         "exchange pages by attempt-dedup disposition",
         [({"kind": k}, v) for k, v in sorted(exchange.items())]),
        ("presto_worker_jit_total", "counter",
         "jitted-program launches and kernel-cache-miss compiles",
         [({"kind": k}, v) for k, v in sorted(jit.items())]),
        ("presto_worker_prereduce_rows_total", "counter",
         "rows folded by in-segment partial-aggregation pre-reduce",
         [({}, prereduce)]),
        ("presto_worker_draining", "gauge",
         "1 while the worker is shutting down gracefully",
         [({}, 1 if worker.draining else 0)]),
        _http_client_family("presto_worker", worker.http),
    ]
    fams.extend(_spool_families("presto_worker",
                                getattr(worker, "spool", None),
                                bytes_evicted=bytes_evicted))
    fams.extend(_kernel_cache_families("presto_worker"))
    fams.extend(_scan_cache_families("presto_worker"))
    return prometheus_text(fams)
