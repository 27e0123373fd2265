"""Shared locked-LRU cache for compiled device programs.

One implementation for every kernel cache in the engine (filter/project,
dynamic filter, fused pipeline segments, aggregation, concat): the
reference keeps its generated classes in Guava caches the same way
(ExpressionCompiler / AccumulatorCompiler / JoinCompiler caches).

Caches are *named* and registered so operators and EXPLAIN ANALYZE can
surface hit/miss/eviction counters (the CacheStatsMBean role), and the
default capacity is configurable through ``EngineConfig
.kernel_cache_capacity`` (applied by ``execute_pipelines`` at query
start; caches are process-global so the knob is a process default, not a
per-query isolation boundary).

Two more things every compiled program passes through here.  ``jit`` is
the one way a function under ``presto_tpu/`` becomes a jitted program,
under a name from ``PROGRAM_NAMES``, so a device trace reads
``jit_join_probe(...)`` and not ``jit_kernel(...)``.  And the module
listens to JAX's own compile events (``jax.monitoring``) and charges each
to the task whose thread built the program (``spans.current_activity``):
the account that sees what XLA sees (``TaskStats.xla_builds`` ...).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict

from presto_tpu.spans import current_activity

_LOCK = threading.Lock()

# process default for cache_put(cap=None); EngineConfig.kernel_cache_capacity
# lands here via set_default_capacity()
_DEFAULT_CAPACITY = 256

_REGISTRY: Dict[str, "KernelCache"] = {}

#: the name of every kind of jitted program, one per kind and never per
#: query: XLA names the module ``jit_<name>``, and that is what a device
#: trace and the benchmark's ``breakdown.device_ops`` show.  Whatever a
#: trace still names ``jit_<primitive>`` (``jit_scatter-add`` ...) is an
#: eager dispatch outside any of these.
PROGRAM_NAMES = (
    "fused_segment",        # exec/fusion.py: one program per segment,
    "fused_segment_probe",  #   named by what it absorbed: join probes,
    "fused_segment_agg",    #   a partial aggregation,
    "fused_segment_probe_agg",  # or both
    "fused_segment_merge",  #   a task's held partials merged at finish
    "filter_project",       # exec/operators.py
    "dynamic_filter",       # exec/dynamicfilter.py
    "join_build_index",     # exec/joinop.py: sorted build-side key index
    "join_key_ranges",      #   build-side [min, max] per key channel
    "join_build_hash",      #   open-addressing table over the build pages
    "join_probe_count",     #   match total before an expanding probe
    "join_probe",           #   the streaming probe, every tier
    "join_probe_residual",  #   probe with a residual filter fused in
    "groupby_direct",       # ops/groupby.py: a GROUP BY's finish over
    "groupby_sort",         #   bounded key domains; over any keys
    "aggregate_global",     #   an ungrouped aggregation's finish
    "groupby_clustered",    #   clustered (streaming) keys, a batch
    "sort",                 # ops/sort.py: a sort's permutation
    "order_by",             #   ORDER BY's finish: every column sorted
    "device_append",        # exec/operator.py: device_concat
    "mesh_step",            # parallel/steps.py
    "mesh_program",         # parallel/sqlmesh.py: the SPMD query program
    "mesh_slice",           #   cut of a device-resident scan input
)


def jit(fn, name: str, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under a stable program name."""
    import jax

    if name not in PROGRAM_NAMES:
        raise ValueError(f"{name!r} is not in kernelcache.PROGRAM_NAMES")
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


class KernelCache(OrderedDict):
    """An OrderedDict with hit/miss/eviction counters and a name.

    Plain OrderedDicts also work with cache_get/cache_put (stats are
    skipped) so hand-built caches in tests keep functioning.
    """

    def __init__(self, name: str):
        super().__init__()
        self.name = name
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # cumulative wall nanoseconds spent building entries for this
        # cache (trace + lower + XLA compile): the compile-time
        # attribution surface EXPLAIN ANALYZE and /metrics report
        self.compile_ns = 0
        self.compiles = 0


def new_cache(name: str = "") -> "KernelCache":
    cache = KernelCache(name or f"cache{len(_REGISTRY)}")
    with _LOCK:
        # last creation wins the registry slot (module reloads in tests)
        _REGISTRY[cache.name] = cache
    return cache


def set_default_capacity(cap: int) -> None:
    """Set the process-wide default capacity for caches that do not pass
    an explicit cap (EngineConfig.kernel_cache_capacity)."""
    global _DEFAULT_CAPACITY
    if cap and cap > 0:
        _DEFAULT_CAPACITY = int(cap)


def default_capacity() -> int:
    return _DEFAULT_CAPACITY


def cache_get(cache: "OrderedDict[tuple, object]", key):
    with _LOCK:
        hit = cache.get(key)
        if hit is not None:
            cache.move_to_end(key)
            if isinstance(cache, KernelCache):
                cache.hits += 1
        elif isinstance(cache, KernelCache):
            cache.misses += 1
        return hit


def cache_put(cache: "OrderedDict[tuple, object]", key, val,
              cap: int = None):
    with _LOCK:
        cache[key] = val
        limit = cap if cap is not None else _DEFAULT_CAPACITY
        while len(cache) > limit:
            cache.popitem(last=False)
            if isinstance(cache, KernelCache):
                cache.evictions += 1


def cache_pop(cache: "OrderedDict[tuple, object]", key) -> None:
    """Drop one entry (no eviction counted: callers pop entries they
    know are invalid — e.g. a mesh program whose capacity bucket
    overflowed — which is correctness, not capacity pressure)."""
    with _LOCK:
        cache.pop(key, None)


def record_compile(cache, duration_ns: int) -> None:
    """Attribute one kernel build's wall time to its named cache (the
    compile-time-attribution half of the CacheStatsMBean role); plain
    OrderedDicts are silently skipped."""
    if isinstance(cache, KernelCache):
        with _LOCK:
            cache.compile_ns += int(duration_ns)
            cache.compiles += 1


def timed_first_call(fn, stats, cache=None):
    """Wrap a freshly jitted callable so its FIRST invocation — where
    jax traces, lowers, and XLA-compiles before running — is timed and
    attributed as compile time: to ``stats.jit_compile_ns`` (the
    OperatorStats of the operator that built it) and to the named
    cache's registry entry.  Later invocations (including cache hits
    from other operators) pass straight through."""
    import time

    state = {"first": True}

    def wrapper(*args, **kwargs):
        if not state["first"]:
            return fn(*args, **kwargs)
        state["first"] = False
        t0 = time.perf_counter_ns()
        out = fn(*args, **kwargs)
        dt = time.perf_counter_ns() - t0
        if stats is not None:
            stats.jit_compile_ns += dt
        record_compile(cache, dt)
        return out

    return wrapper


def cache_stats() -> Dict[str, Dict[str, int]]:
    """Hit/miss/size counters for every registered cache (task info /
    EXPLAIN ANALYZE surface)."""
    with _LOCK:
        return {name: {"size": len(c), "hits": c.hits, "misses": c.misses,
                       "evictions": c.evictions,
                       "compiles": c.compiles,
                       "compile_ns": c.compile_ns}
                for name, c in sorted(_REGISTRY.items())}


# ---------------------------------------------------------------------------
# The compile account: what XLA built, charged to the task that built it
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: builds on threads that work for no task (planner constant folding,
#: the collective plane's program, tests calling kernels bare):
#: /v1/metrics reports them beside the kernel-cache families
_PROCESS_XLA = {"builds": 0, "build_ns": 0, "trace_lower_ns": 0,
                "cache_hits": 0}


def _xla_account() -> Dict[str, int]:
    recorder = current_activity()
    return _PROCESS_XLA if recorder is None else recorder.xla


def _on_xla_duration(event: str, secs: float, **_kw) -> None:
    # JAX calls this on the thread that builds.  A "build" is
    # compile_or_get_cached: a load from the persistent cache counts,
    # with the seconds the load took.
    if event == _BUILD_EVENT:
        with _LOCK:
            account = _xla_account()
            account["builds"] += 1
            account["build_ns"] += int(secs * 1e9)
    elif event == _TRACE_EVENT or event == _LOWER_EVENT:
        with _LOCK:
            _xla_account()["trace_lower_ns"] += int(secs * 1e9)


def _on_xla_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        with _LOCK:
            _xla_account()["cache_hits"] += 1


def process_xla_stats() -> Dict[str, int]:
    """XLA builds charged to no task, since the process started."""
    with _LOCK:
        return dict(_PROCESS_XLA)


def _listen_to_xla() -> None:
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_xla_duration)
    jax.monitoring.register_event_listener(_on_xla_event)


_listen_to_xla()     # once per process: at import
