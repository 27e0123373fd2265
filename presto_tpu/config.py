"""Global engine configuration.

The reference splits configuration into static host config (airlift
``@Config`` beans, e.g. presto-main/.../sql/analyzer/FeaturesConfig.java:61)
and per-query session properties
(presto-main/.../SystemSessionProperties.java:51).  We keep the same split:
``EngineConfig`` is the static host config; ``Session`` (session.py) carries
per-query overrides.

SQL semantics require 64-bit integers (BIGINT, short DECIMAL as scaled
int64), so x64 is enabled at import.  TPUs execute int64 element-wise ops as
pairs of int32 ops; the MXU-bound paths in this engine are int32/float32 by
construction, so enabling x64 does not put float64 on the hot path.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compile cache: a cold query compiles every kernel it
# dispatches, the disk cache makes every later process reuse the
# executables (the reference's generated-class cache role, at the XLA
# level).  Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and
# nothing is set here.  Otherwise the cache lives at ONE fixed path inside
# the checkout — the path is part of the cache key, so a directory derived
# from the host, the user or the time never hits across processes.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
# The engine is hundreds of small programs (one per operator shape), most
# under JAX's default 1 s admission threshold; cache them all unless the
# user has set the threshold.
if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@dataclasses.dataclass
class EngineConfig:
    """Static engine configuration (the FeaturesConfig/TaskManagerConfig role).

    Defaults are chosen for a single v5e chip; tests override freely.
    """

    # Capacity buckets: device arrays are padded to the next power of two at
    # least this size, bounding the number of distinct compiled shapes
    # (the reference instead recompiles nothing because the JVM tolerates
    # dynamic sizes; XLA does not).
    min_batch_capacity: int = 1024
    # Rows per Batch produced by scans (the Page-size analogue,
    # reference default 1024 positions / 1MB).
    scan_batch_rows: int = 65536
    # Default hash-aggregation group capacity per kernel invocation.
    group_capacity: int = 1 << 20
    # Largest packed key domain for the gather-free direct GROUP BY path
    # (mixed-radix ids + segment reduce: no sort, no gather, and the
    # group keys decode arithmetically).  Above this the dense per-batch
    # state outgrows what a batch amortizes and the hash / sort tiers
    # take over.  Not measured on the current code against either.
    direct_groupby_max_domain: int = 1 << 12
    # Default join match-expansion capacity multiplier (output rows per
    # probe batch before chunked re-probe kicks in).
    join_expansion_factor: int = 4
    # Number of drivers per pipeline on one host (the task.concurrency
    # analogue); device kernels are internally parallel so this mostly
    # governs host-side feed parallelism.
    task_concurrency: int = 4
    # Maximum partial-aggregation memory before flush, bytes.
    partial_agg_max_bytes: int = 256 << 20
    # Spill directory (host-RAM/disk tier below HBM).
    spill_path: str = os.environ.get("PRESTO_TPU_SPILL", "/tmp/presto_tpu_spill")
    spill_enabled: bool = True
    # Accumulated-input bytes above which an accumulating operator sheds
    # state to the spill tier (the revocable-memory trigger, SURVEY §2.9).
    spill_threshold_bytes: int = 1 << 30
    # Hash-partition fan-out for partitioned spill (peak memory ~ 1/K).
    spill_partitions: int = 8
    # Build-side key domains prune probe rows before the join kernel
    # (DynamicFilterSourceOperator role, SURVEY §2.6).
    dynamic_filtering_enabled: bool = True
    # LRU capacity for the shared compiled-kernel caches (filter/project,
    # fused segments, dynamic filter, aggregation...).  Caches are
    # process-global; this is applied as the process default when a query
    # starts (kernelcache.set_default_capacity).
    kernel_cache_capacity: int = 256
    # Whole-query execution: compile supported queries into ONE XLA
    # program (the parallel/sqlmesh lowering on a single-device mesh)
    # instead of per-operator dispatches — repeat executions are a
    # single device dispatch.  Falls back to the operator tier for
    # unsupported shapes.  Off by default: the operator tier remains
    # the reference path.
    whole_query_execution: bool = False
    # Sorted/clustered-input aggregation (StreamingAggregationOperator
    # role): group keys tracing to a prefix of the scan's sort order
    # aggregate run-by-run with no sort and one open group carried.
    streaming_aggregation_enabled: bool = True
    # Grouped execution (P9, Lifespan role): joins whose sides co-bucket
    # on the join key run bucket-by-bucket with only 1/k of the build
    # side resident.  1 = off.
    grouped_execution_buckets: int = 1
    # --- distributed-planning knobs (FeaturesConfig /
    # SystemSessionProperties surface) -----------------------------------
    # automatic = CBO decides per join; broadcast / partitioned force the
    # distribution (join_distribution_type session property,
    # DetermineJoinDistributionType role).
    join_distribution_type: str = "automatic"
    # estimated build rows below which AUTOMATIC picks broadcast
    broadcast_join_row_limit: int = 100_000
    # automatic = cost-based join reordering; none = keep syntactic order
    # (ReorderJoins / join_reordering_strategy role)
    join_reordering_strategy: str = "automatic"
    # Memo-based cost exploration (sql/memo.py — the Cascades-style
    # Memo/ReorderJoins/DetermineJoinDistribution tier): ON explores join
    # orders and exchange placement by cost; it falls back to the greedy
    # orderer per join graph when leaf stats are unavailable or the graph
    # exceeds memo_max_reorder_relations.  OFF restores the pre-memo
    # greedy path exactly.
    optimizer_use_memo: bool = True
    # largest join graph the memo enumerates exhaustively (the reference's
    # max_reorder_joins, ReorderJoins.java getMaxReorderedJoins; 9 there)
    memo_max_reorder_relations: int = 9
    # split grouped aggregation into partial (producer fragment) + final;
    # off = aggregate once at the consumer (push_partial_aggregation role)
    partial_aggregation_enabled: bool = True
    # scaled writers (P6): rows one writer task absorbs before another is
    # warranted (writerMinSize role, row-denominated)
    scaled_writer_rows_per_task: int = 200_000
    # tasks per hash-partitioned fragment; 0 = one per worker
    # (hash_partition_count session property)
    hash_partition_count: int = 0
    # per-query memory ceiling enforced by the reservation tree;
    # 0 = unlimited (query_max_memory role)
    query_max_memory_bytes: int = 0
    # wall-clock ceiling for one query; 0 = unlimited
    # (query_max_run_time role)
    query_max_run_time_s: float = 0.0
    # --- distributed fault-tolerance knobs (RequestErrorTracker /
    # remote-task error budget, server/errortracker.py) ------------------
    # first backoff step after a retryable transport error; doubles per
    # consecutive error up to the max (query.remote-task.min-error-duration
    # neighborhood in the reference's RequestErrorTracker)
    remote_request_min_backoff_s: float = 0.05
    remote_request_max_backoff_s: float = 2.0
    # error budget: consecutive-transport-failure window per endpoint
    # before the request (and with it the task/query) is failed with the
    # task id + endpoint attached (max-error-duration role)
    remote_request_max_error_duration_s: float = 30.0
    # mid-query task recovery: reschedule leaf (no-remote-source) tasks
    # of a dead worker onto a survivor and repoint their consumers
    task_recovery_enabled: bool = True
    # how often the per-query monitor checks the failure detector's view
    # of the workers hosting this query's tasks
    task_recovery_interval_s: float = 0.25
    # whole-stage retry (the Presto-on-Spark stance): when a dead worker
    # owned a NON-leaf task, the minimal producer subtree is cancelled and
    # re-created under fresh attempt ids instead of failing the query.
    # This is the maximum number of re-creation rounds any single stage
    # may consume before the query fails with the retry history attached;
    # rounds back off on the errortracker schedule
    # (remote_request_min/max_backoff_s).  0 = fail fast (PR 2 behavior).
    stage_retry_limit: int = 2
    # wall-clock bound for the cancel/DELETE fan-out at query end: each
    # endpoint gets at most this error budget so one hung worker cannot
    # stall cleanup (was a hardcoded ~2s)
    cancel_fanout_budget_s: float = 2.0
    # speculative re-execution of stragglers: a leaf task whose stage has
    # >= speculation_quantile of its peers already finished-and-drained,
    # and whose elapsed time exceeds speculation_lag_factor x the median
    # finished elapsed (and speculation_min_runtime_s), gets a clone on
    # another worker under a new attempt id; whichever attempt the
    # consumer first drains from wins, the loser is cancelled (exactness
    # via the attempt-aware exchange dedup).  Off by default, like the
    # reference's speculative execution.
    speculative_execution_enabled: bool = False
    speculation_quantile: float = 0.5
    speculation_lag_factor: float = 4.0
    speculation_min_runtime_s: float = 1.0
    # --- spooled exchange (server/spool.py, SURVEY §2.8 Presto-on-Spark
    # / Tardigrade stance) ------------------------------------------------
    # Write exchange output through to a shared spool store as pages are
    # enqueued, making every producer stream durably re-pullable: stage
    # retry repoints consumers at the spool instead of re-running the
    # producer subtree, non-leaf stages may speculate (clones read their
    # producers from the spool), and workers can drain out of a running
    # query.  OFF restores the PR 5 cascading retry exactly.
    exchange_spooling_enabled: bool = True
    # shared spool root (every node of a cluster must see the same
    # storage; the local-FS tier assumes one host or shared mounts)
    exchange_spool_path: str = os.environ.get(
        "PRESTO_TPU_EXCHANGE_SPOOL",
        os.path.join(tempfile.gettempdir(), "presto_tpu_exchange"))
    # output-buffer memory ceiling per task; with spooling on, acked or
    # spooled pages are EVICTED from memory (re-served from the spool on
    # a late re-fetch) instead of blocking the producer
    exchange_max_buffer_bytes: int = 256 << 20
    # a spool stream with no new pages and no COMPLETE marker for this
    # long is declared stalled (the producer died without a failure
    # channel through the spool); consumers raise instead of hanging
    exchange_spool_stall_s: float = 60.0
    # coordinator-start orphan sweep: spool query dirs older than this
    # are removed (crashed-coordinator leftovers); the age guard keeps a
    # shared spool root safe across concurrent clusters
    exchange_spool_orphan_age_s: float = 3600.0
    # spool backing tier: 'fs' = one file per page on the shared
    # filesystem (the PR 7 tier, restored exactly); 'object' = the
    # S3/GCS-role ObjectStoreSpoolStore — pages batch in memory and
    # flush ASYNCHRONOUSLY as multi-page segment objects (compaction
    # replaces one-file-per-page), with read-through to the FS tier for
    # pages the object tier does not hold.  Every node of a cluster
    # must run the same tier (§2.8/§2.9 tiering stance: exchange
    # durability and result-cache capacity become independent of
    # worker disks).
    exchange_spool_tier: str = "fs"
    # object tier: pending bytes per partition that force a segment
    # flush ahead of the interval tick
    exchange_spool_segment_bytes: int = 4 << 20
    # object tier: background flush cadence for pending pages (writes
    # are batched + async; set_complete always flushes synchronously so
    # the COMPLETE marker never precedes its pages)
    exchange_spool_flush_interval_s: float = 0.05
    # --- serving tier (server/dispatcher.py + sql/plancache.py) ----------
    # plan cache: repeated statements (same normalized SQL, catalog,
    # session-property fingerprint, current per-catalog stats epochs)
    # reuse the fragmented plan and skip parse/analyze/optimize; any
    # DDL/DML against a catalog bumps its epoch and invalidates plans
    # scanning it.  OFF restores inline planning exactly.
    plan_cache_enabled: bool = True
    # entries kept in the shared plan cache (LRU)
    plan_cache_capacity: int = 128
    # --- cross-query result cache (server/resultcache.py) ----------------
    # Serve a REPEATED statement's rows straight from its first
    # execution's root-output spool pages: zero task scheduling, zero
    # physical plans, zero jit dispatches — admission/lifecycle still
    # run through the dispatcher, so resource groups, events, stats,
    # and the web UI see a FINISHED query with resultCached=true.
    # Keyed exactly like the plan cache (normalized SQL + catalog +
    # session fingerprint + per-catalog stats epochs), so any
    # DML/DDL/ANALYZE invalidates correctly.  Requires
    # exchange_spooling_enabled (the cache's values ARE spool pages).
    # Off by default for the same reason mesh_device_exchange is: the
    # execute-every-statement path stays the reference path the
    # observability/retry planes instrument, and repeat-statement
    # stats change shape under a hit; serving deployments (and the
    # qps/bench hot-repeat configs) turn it on.
    result_cache_enabled: bool = False
    # entries kept in the result cache (LRU; eviction deletes the
    # entry's spool pages)
    result_cache_capacity: int = 64
    # largest single result admitted, bytes of spooled wire pages
    result_cache_max_entry_bytes: int = 16 << 20
    # total spooled bytes the cache may hold before LRU eviction
    result_cache_max_total_bytes: int = 256 << 20
    # how long a dispatched query may wait for a resource-group slot
    # before failing with the queue-timeout error (the reference's
    # query.max-queued-time role)
    query_queue_timeout_s: float = 300.0
    # --- cluster memory arbitration (server/memorypool.py + the
    # coordinator's ClusterMemoryManager tick, SURVEY §2.2/§5) ------------
    # per-node GENERAL pool: every query reservation on a worker charges
    # this pool; a reservation past the cap BLOCKS the driver (condition
    # wait) until another query frees bytes or the killer acts.
    # 0 = unlimited — pure accounting, restores pre-pool behavior exactly.
    worker_memory_pool_bytes: int = 0
    # backstop behind the killer: how long one driver may stay blocked on
    # a full pool before its reservation fails worker-side
    memory_blocked_wait_s: float = 60.0
    # cluster-wide ceiling on ONE query's summed worker reservations
    # (the query_max_total_memory role); 0 = off
    query_max_total_memory_bytes: int = 0
    # a node pool continuously blocked for longer than this arms the
    # coordinator's low-memory killer
    low_memory_killer_delay_s: float = 5.0
    # victim policy: 'total-reservation' (biggest query cluster-wide),
    # 'total-reservation-on-blocked-nodes' (biggest query measured on
    # the blocked nodes only — the reference default), or 'none'
    low_memory_killer_policy: str = "total-reservation-on-blocked-nodes"
    # --- bounded-pool admission (server/dispatcher.py) -------------------
    # dispatch worker threads running admission + execution; 0 restores
    # thread-per-query dispatch exactly
    dispatcher_pool_size: int = 0
    # dispatch queue depth past which submits are shed with the
    # queue-full error shape + a Retry-After hint; 0 = never shed
    dispatcher_max_queued: int = 0
    # --- coordinator HA (server/statestore.py) ---------------------------
    # Durable query-state journal + takeover lease root (an object-API
    # directory; primary and standby coordinators must see the same
    # storage, like the spool path).  Empty = HA journaling disabled —
    # the default, which leaves every existing code path untouched.
    coordinator_state_path: str = ""
    # takeover lease TTL: the active coordinator renews every ttl/3; a
    # standby that observes the lease expired claims the next
    # generation (compare-and-swap) and adopts the journal
    coordinator_lease_ttl_s: float = 2.0
    # largest FINISHED-query result adopted into a durable ha* spool
    # stream at terminal journaling (bigger results journal without
    # rows and re-enter admission on adoption)
    coordinator_journal_max_result_bytes: int = 16 << 20
    # journal GC: terminal (FINISHED/FAILED) ``queries/{id}`` entries
    # older than this are deleted by the active coordinator's lease
    # tick instead of accumulating until the orphan sweep; in-flight
    # entries are NEVER reaped.  0 disables age-based reaping.
    coordinator_journal_retention_s: float = 3600.0
    # journal GC count bound: at most this many terminal entries are
    # retained (oldest reaped first); 0 = unbounded
    coordinator_journal_retention_count: int = 1024
    # --- worker-side plan_fragment cache (server/task.py) ----------------
    # Repeat task creates of the same statement (same fragment JSON,
    # scan shard, output topology, session fingerprint, and coordinator
    # stats epochs) reuse the lowered pipeline factories instead of
    # re-running plan_fragment — the distributed half of the plan
    # cache's physical-factory sharing.  Entries re-arm via
    # reset_for_execution and rebind exchange sources + output buffers
    # per task; an entry in use by a live task is never shared.
    worker_fragment_cache_enabled: bool = True
    worker_fragment_cache_capacity: int = 32
    # --- live query telemetry (the StatementStats/QueryProgressStats
    # role: progress observable MID-query, not just post-mortem) --------
    # coordinator sampler: while a query is RUNNING, poll every
    # placement's task info at this cadence, fold each sweep into the
    # live StageStats/QueryStats rollup, and append one sample to the
    # bounded per-query time-series ring (/v1/query/{id}/timeseries).
    # OFF restores the single post-drain stats collection exactly.
    stats_sampling_enabled: bool = True
    stats_sample_interval_s: float = 0.1
    # samples kept in the per-query time-series ring (oldest dropped)
    stats_timeseries_capacity: int = 512
    # slow-query log: a query whose wall clock exceeds this threshold
    # emits one structured log line + a SlowQueryEvent through the
    # event bus (trace token, queued/execution split, top hot
    # operator).  0 disables.
    slow_query_log_threshold_s: float = 60.0
    # Join lookup source (exec/joinop.py HashBuildOperator.finish):
    # integer keys whose live span fits the direct-address index take it;
    # unpackable (VARCHAR, wide multi-channel) keys always build the
    # PagesHash open-addressing table, which is what lets them stream.
    # Integer keys too sparse for the index, on the chip: build sides up
    # to this many rows take the hash table (a probe measured 29 ms
    # against the binary search's 33 ms a 64K batch on v5e, PERF.md
    # PR 30), larger ones keep the sorted index (claim-inserting a build
    # measured 245 ms per 128K rows against 12 ms for the sort).
    device_join_probe_max_build_rows: int = 1 << 17
    # Pre-reduce inside a fused segment (exec/fusion.py) is skipped, and
    # raw rows emitted in partial-state schema, when the estimated or the
    # observed groups/rows ratio of a hash-path aggregation passes this:
    # per-batch grouping that does not reduce is pure overhead.
    prereduce_max_group_fraction: float = 0.9
    # --- collectives as the data plane (parallel/, SURVEY §5.8 / §2.13,
    # roles P1/P2/P8/P9) -------------------------------------------------
    # Device-sharded exchange: when every fragment of a query is
    # co-resident on ONE jax.sharding.Mesh (all placements share a mesh
    # fingerprint — same process, same device set), the whole fragment
    # DAG lowers into a single shard_map'ped SPMD program and every
    # fragment boundary becomes an in-program ICI collective
    # (all_to_all for 'hash', all_gather for 'broadcast', gather for
    # 'single') instead of PartitionedOutputOperator -> serde -> HTTP ->
    # ExchangeOperator.  The HTTP plane stays the cross-slice / elastic
    # / spool tier and the fallback for unsupported shapes.  OFF
    # restores the PR 10 task-scheduled lowering exactly.  Off by
    # default for the same reason whole_query_execution is: the
    # task-scheduled operator tier remains the reference path (it is
    # what the retry/spool/speculation/live-stats planes instrument);
    # the mesh bench configs and the device-exchange parity tests turn
    # it on per cluster/session.
    mesh_device_exchange: bool = False
    # Partitioned lookup source (P8): inside the mesh program, equi-join
    # build sides use the PR 10 open-addressing PagesHash table built
    # PER SHARD over the shard's key partition — the global build table
    # is sharded across device HBM (probes were routed to the owning
    # shard by the hash-exchange all_to_all), so a build exceeding one
    # device's HBM is legal.  OFF restores the sorted-index mesh join
    # exactly.
    partitioned_join_build: bool = True
    # Bucket-sequential grouped execution (P9, §5.7): mesh equi-joins
    # hash-bucket both sides and run the buckets SEQUENTIALLY through
    # the sharded join, so per-shard peak intermediate memory is ~1/K of
    # the unbucketed join (SF10-100 builds fit HBM).  Value = bucket
    # count; 1 = off (the PR 10 single-pass join exactly).  The
    # capacity-bucket overflow/rerun policy applies per bucket.
    grouped_mesh_execution: int = 1
    # Mid-program progress beacons (parallel/beacons.py): a
    # jax.debug.callback at every fragment boundary inside the SPMD
    # program reports (fragment, shard, rows) to a host-side collector,
    # which feeds the PR 9 sampler ring / client-poll progress object /
    # progressPercent MID-program — the collective tier's analogue of
    # the task-info sampler the HTTP plane already has.  Default on
    # (only engages together with mesh_device_exchange); OFF traces a
    # program with no callbacks and restores the PR 11 sampling
    # behavior for device-exchange queries exactly (no mid-run samples,
    # no progress object until the final rollup).
    mesh_progress_beacons: bool = True
    # Boundary checkpoints for the collective tier (PR 17): instead of
    # ONE all-or-nothing SPMD program, the fragment DAG executes as a
    # SEQUENCE of per-fragment SPMD programs; after each group the
    # coordinator write-throughs the boundary's output pages into the
    # SpoolStore (same LZ4 wire frames, spooled under the query's task
    # ids) and journals a device-plane checkpoint record.  A mid-program
    # failure then resumes from the last complete boundary instead of
    # re-running the whole query.  OFF (default) restores the PR 14
    # all-or-nothing lowering + fallback exactly.
    mesh_checkpoint_boundaries: bool = False
    # Recovery mode after a device-plane failure under checkpointing:
    # 'device' re-runs ONLY the remaining checkpoint groups as fresh
    # SPMD programs fed from the checkpointed boundary batches; 'http'
    # degrades to the task-scheduled plane, scheduling ONLY the
    # fragments whose producers are not spool-complete (completed
    # fragments become zero-re-execution spool:// leaf inputs).
    mesh_resume_mode: str = "device"
    # Consecutive device-resume attempts before a checkpointed query
    # degrades to the HTTP plane anyway (the device plane may be
    # persistently broken; the spooled checkpoints are still honored).
    mesh_resume_limit: int = 3


DEFAULT = EngineConfig()
