"""Single-process pipeline runner (LocalQueryRunner's execution half).

The reference's LocalQueryRunner plans SQL then hand-pumps drivers in one
process (presto-main/.../testing/LocalQueryRunner.java:214,616-665).  This
module is the pumping half: it executes a DAG of Pipelines in dependency
order.  The SQL half (sql/ package) lowers plans into these pipelines.

Multi-split pipelines whose leading operators are parallel-safe run as
``config.task_concurrency`` concurrent feed drivers stitched to the rest
of the chain through a LocalExchange (the reference's
AddLocalExchanges.java:95 + LocalExchange.java:53 shape) — host-side scan
decode overlaps the consumer's device work.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence

from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.exec.context import QueryContext, TaskContext
from presto_tpu.exec.driver import Pipeline


def _parallel_prefix(p: Pipeline, config: EngineConfig) -> int:
    """Length of the leading factory run that may replicate into N
    drivers (0 = run the pipeline single-driver)."""
    if config.task_concurrency <= 1 or len(p.splits) <= 1:
        return 0
    if any(getattr(f, "requires_ordered_input", False)
           for f in p.factories):
        # round-robin feeds would interleave the clustered key order a
        # streaming aggregation depends on
        return 0
    k = 0
    for f in p.factories:
        if getattr(f, "parallel_safe", False):
            k += 1
        else:
            break
    # the whole chain being safe means there is no consumer stage left
    # to protect — still split before the terminal sink
    k = min(k, len(p.factories) - 1)
    if k > 1:
        from presto_tpu.exec.fusion import FusedSegmentOperatorFactory

        last = p.factories[k - 1]
        if isinstance(last, FusedSegmentOperatorFactory) \
                and last.coalesce_rows:
            # a coalescing segment batches everything it sees anyway, so
            # place it CONSUMER-side: one operator coalesces across all
            # feed drivers and dispatches once per coalesced batch,
            # instead of one flush per feeder.  Feeders keep the
            # parallel half that actually scales on the host (split
            # decode); the device program was serialized regardless.
            k -= 1
    return k


def _through_scan_cache(p: Pipeline):
    """``p`` as this execution runs it, and the scan cache's fill to
    close when it ended (exec/scancache.py).  A pipeline that scans a
    table of a connector with ``immutable_data`` either takes the device
    batches an earlier scan of the same splits staged (a hit: the scan
    prefix, its splits and so its feed drivers are gone, and the segment
    that adopted the scan is fed device batches) or runs as it is and
    records what it stages (a miss).  Every other pipeline comes back
    untouched: a table that can change is never kept, and where the plan
    needs the scan's order the cache stands aside, a kept run being in
    the order its fill staged it."""
    from presto_tpu.exec.fusion import FusedSegmentOperatorFactory
    from presto_tpu.exec.operators import (
        CachedScanOperatorFactory, TableScanOperatorFactory,
    )
    from presto_tpu.exec.scancache import SCAN_CACHE, ScanHit

    scan = p.factories[0] if p.factories else None
    if not (isinstance(scan, TableScanOperatorFactory) and p.splits
            and scan.columns
            and getattr(scan.connector, "immutable_data", False)):
        return p, None
    if any(getattr(f, "requires_ordered_input", False)
           for f in p.factories):
        return p, None
    rest = p.factories[1:]
    segment = None          # the segment that stages for this scan
    if not scan.to_device:
        if not (rest and isinstance(rest[0], FusedSegmentOperatorFactory)
                and rest[0].coalesce_rows):
            return p, None  # nothing here puts the scan on the device
        segment, rest = rest[0], rest[1:]
    key = (p.splits[0].handle, tuple(s.info for s in p.splits),
           scan.batch_rows, segment.coalesce_rows if segment else 0)
    try:
        hash(key)
    except TypeError:       # a split descriptor that does not hash
        return p, None
    found = SCAN_CACHE.open(scan.connector, key, scan.columns)
    if isinstance(found, ScanHit):
        head = [CachedScanOperatorFactory(found)]
        if segment is not None:
            head.append(segment.for_cached_scan())
        return Pipeline(head + rest, name=p.name), None
    head = [scan.filling(found)]
    if segment is not None:
        head.append(segment.for_cached_scan(found))
    return Pipeline(head + rest, p.splits, name=p.name), found


def _run_parallel(p: Pipeline, task: TaskContext, prefix: int,
                  width: int, deadline=None) -> None:
    from presto_tpu.exec.localexchange import (
        ConsumerFinished, LocalExchange, LocalExchangeSinkOperatorFactory,
        LocalExchangeSourceOperatorFactory,
    )

    exchange = LocalExchange(width)
    errors: List[BaseException] = []

    def feed(i: int) -> None:
        feeder = Pipeline(
            p.factories[:prefix]
            + [LocalExchangeSinkOperatorFactory(exchange, producer=i)],
            p.splits[i::width], name=f"{p.name}.feed{i}")
        try:
            feeder.instantiate(task).run_to_completion(deadline=deadline)
        except ConsumerFinished:
            pass    # a LIMIT downstream was met: the rest is not wanted
        except BaseException as e:  # noqa: BLE001 - crossed to consumer
            errors.append(e)
            exchange.fail(e)

    threads = [threading.Thread(target=feed, args=(i,), daemon=True,
                                name=f"{p.name}.feed{i}")
               for i in range(width)]
    for t in threads:
        t.start()
    consumer = Pipeline(
        [LocalExchangeSourceOperatorFactory(exchange)]
        + p.factories[prefix:], name=p.name)
    try:
        consumer.instantiate(task).run_to_completion(deadline=deadline)
        exchange.consumer_finished()
    except BaseException as e:
        # unblock feeders stuck in put() backpressure, then re-raise
        exchange.fail(e)
        raise
    finally:
        for t in threads:
            t.join(timeout=30)
    if errors:
        raise errors[0]


def execute_pipelines(pipelines: Sequence[Pipeline],
                      config: EngineConfig = DEFAULT,
                      memory_limit: Optional[int] = None,
                      on_task_context=None, pool=None,
                      pool_query_id: str = "query") -> TaskContext:
    """Run pipelines sequentially in the given (dependency) order.

    Build pipelines come before their probe pipelines — the planner emits
    them in that order, mirroring how the reference sequences via
    LookupSourceFactory futures.  Returns the TaskContext (stats).
    ``on_task_context`` receives the TaskContext before execution starts
    so callers (worker memory reporting) can observe live reservations.
    ``pool`` is the worker's shared MemoryPool; the reservation tree's
    root charges it under ``pool_query_id`` (server/memorypool.py).
    """
    import time as _time

    from presto_tpu import kernelcache

    # apply the configured compiled-kernel cache capacity (caches are
    # process-global; this sets the process default, cheap + idempotent)
    kernelcache.set_default_capacity(
        getattr(config, "kernel_cache_capacity", 0))
    query = QueryContext(config, memory_limit, pool=pool,
                         pool_query_id=pool_query_id)
    task = TaskContext(query)
    deadline = (_time.monotonic() + config.query_max_run_time_s
                if getattr(config, "query_max_run_time_s", 0) > 0 else None)
    try:
        if on_task_context is not None:
            on_task_context(task)
        for p in pipelines:
            if deadline is not None and _time.monotonic() > deadline:
                raise RuntimeError(
                    "Query exceeded maximum run time "
                    f"({config.query_max_run_time_s:g}s)")
            p, fill = _through_scan_cache(p)
            ok = False
            try:
                prefix = _parallel_prefix(p, config)
                width = min(config.task_concurrency, len(p.splits))
                if prefix > 0 and width > 1:
                    _run_parallel(p, task, prefix, width,
                                  deadline=deadline)
                else:
                    driver = p.instantiate(task)
                    driver.run_to_completion(deadline=deadline)
                ok = True
            finally:
                if fill is not None:
                    fill.close(ok)
    finally:
        task.close()
        # return any charge a failure path never freed — a leak in the
        # SHARED node pool would block every other query on this node
        query.release_pool()
    return task
