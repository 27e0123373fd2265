"""Exchange operators: partitioned/broadcast sinks and the remote source.

Reference models:
- PartitionedOutputOperator (presto-main/.../operator/PartitionedOutput
  Operator.java:48): hash-partitions pages, serializes, enqueues into the
  output buffer.  The reference appends row-at-a-time (appendRow:414); the
  TPU formulation computes one partition id vector with the device hash
  kernel and emits per-partition sub-batches by gather — no row loop.
- TaskOutputOperator (TaskOutputOperator.java:33): single-buffer output.
- ExchangeOperator + ExchangeClient + HttpPageBufferClient
  (ExchangeOperator.java:36, ExchangeClient.java:55,
  HttpPageBufferClient.java:297): pull-based page fetch over HTTP with
  token ack, merged across producer tasks.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.batch import Batch
from presto_tpu.exec.context import OperatorContext
from presto_tpu.exec.operator import Operator, OperatorFactory
from presto_tpu.serde import deserialize_batch, frame_size, serialize_batch
from presto_tpu.server.buffers import OutputBufferManager
from presto_tpu.spans import activity
from presto_tpu.server.errortracker import (
    RemoteRequestError, RetryingHttpClient,
)


class PartitionedOutputOperator(Operator):
    """Hash-partition rows on ``channels`` into n output partitions.

    When a fused upstream segment precomputed the partition ids
    (``precomputed``, exec/fusion.py), the ids arrive as an extra final
    int32 column and the per-batch hash dispatches are skipped — the
    segment program already fused them.  Such a segment may leave the
    rows its filter dropped where they were, under the id ``n``: they
    fall past the last partition's bound and into no page.
    """

    def __init__(self, ctx: OperatorContext, buffers: OutputBufferManager,
                 channels: Sequence[int], n_partitions: int,
                 precomputed: bool = False):
        super().__init__(ctx)
        self.buffers = buffers
        self.channels = list(channels)
        self.n = n_partitions
        self.precomputed = precomputed

    def add_input(self, batch: Batch) -> None:
        from presto_tpu.ops.hashing import (
            partition_of, row_hash, value_hash_triple,
        )

        if self.precomputed and self.n > 1:
            # strip the segment-computed partition-id column first so
            # row accounting and serialization see the logical schema
            parts_col = batch.columns[-1]
            batch = Batch(batch.columns[:-1], batch.num_rows)
        if self.n == 1:
            self.ctx.stats.input_rows += batch.num_rows
            self.buffers.enqueue(0, serialize_batch(batch))
            self.ctx.stats.output_rows += batch.num_rows
            return
        if self.precomputed:
            parts = parts_col.values
        else:
            # hash at the padded capacity (bucketed shapes, so a bounded
            # set of programs) and cut the ids on the host
            key_cols = [value_hash_triple(batch.columns[c])
                        for c in self.channels]
            parts = partition_of(row_hash(key_cols), self.n)
        if isinstance(parts, np.ndarray):
            parts = parts[:batch.num_rows]
        else:
            with activity("device_wait"):
                parts = np.asarray(parts)[:batch.num_rows]
        # the rows leave through the wire: stage them to the host ONCE
        # and cut the pages there.  take() on device arrays dispatches one
        # eager XLA program per distinct (rows, page rows) pair, and row
        # counts are data (see serde._encode_payload)
        batch = batch.to_numpy().compact()
        # one stable argsort-by-partition + boundary slicing instead of
        # one np.nonzero pass per partition: a single O(n log n) pass
        # regardless of fan-out, and rows stay in input order within a
        # partition (stable sort), exactly like the nonzero loop
        # (16-bit keys: numpy's stable sort is then a radix sort)
        if self.n < 1 << 16:
            parts = parts.astype(np.uint16)
        order = np.argsort(parts, kind="stable")
        bounds = np.searchsorted(parts[order], np.arange(self.n + 1))
        # the rows with a partition to go to: all of them, but for a
        # precomputing segment's dead rows
        self.ctx.stats.input_rows += int(bounds[self.n])
        for p in range(self.n):
            lo, hi = int(bounds[p]), int(bounds[p + 1])
            if lo == hi:
                continue
            sub = batch.take(order[lo:hi])
            self.buffers.enqueue(p, serialize_batch(sub))
            self.ctx.stats.output_rows += sub.num_rows

    def finish(self) -> None:
        if not self._finishing:
            super().finish()
            self.buffers.set_no_more_pages()

    def is_finished(self) -> bool:
        return self._finishing


class TaskOutputOperator(Operator):
    """Un-partitioned output: everything into partition 0 (or broadcast —
    the buffer topology decides)."""

    def __init__(self, ctx: OperatorContext, buffers: OutputBufferManager):
        super().__init__(ctx)
        self.buffers = buffers

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        self.buffers.enqueue(0, serialize_batch(batch))
        self.ctx.stats.output_rows += batch.num_rows

    def finish(self) -> None:
        if not self._finishing:
            super().finish()
            self.buffers.set_no_more_pages()

    def is_finished(self) -> bool:
        return self._finishing


class RoundRobinOutputOperator(Operator):
    """P3 (FIXED_ARBITRARY_DISTRIBUTION): whole batches rotate across the
    consumer partitions for load balance without key semantics — the
    ArbitraryOutputBuffer/RandomExchanger role
    (presto-main/.../execution/buffer/ArbitraryOutputBuffer.java:60,
    operator/exchange/LocalExchange.java:112)."""

    def __init__(self, ctx: OperatorContext, buffers: OutputBufferManager,
                 n_partitions: int):
        super().__init__(ctx)
        self.buffers = buffers
        self.n = n_partitions
        self._next = 0

    def add_input(self, batch: Batch) -> None:
        self.ctx.stats.input_rows += batch.num_rows
        self.buffers.enqueue(self._next % self.n,
                             serialize_batch(batch))
        self._next += 1
        self.ctx.stats.output_rows += batch.num_rows

    def finish(self) -> None:
        if not self._finishing:
            super().finish()
            self.buffers.set_no_more_pages()

    def is_finished(self) -> bool:
        return self._finishing


class PartitionedOutputOperatorFactory(OperatorFactory):
    def __init__(self, buffers: OutputBufferManager,
                 channels: Sequence[int], n_partitions: int):
        self.buffers = buffers
        self.channels = list(channels)
        self.n_partitions = n_partitions
        # set by the fusion pass when an upstream segment appends the
        # partition-id column (exec/fusion.py)
        self.precomputed = False

    def rebind(self, buffers: OutputBufferManager) -> None:
        """Point this (cached) sink at a new task's buffer manager —
        the worker plan_fragment cache reuses the lowered factory chain
        across task creates; topology (channels, fan-out, the fusion
        ``precomputed`` flag) is part of the cache key and unchanged."""
        self.buffers = buffers

    def create(self, ctx: OperatorContext):
        return PartitionedOutputOperator(ctx, self.buffers, self.channels,
                                         self.n_partitions,
                                         precomputed=self.precomputed)


class RoundRobinOutputOperatorFactory(OperatorFactory):
    def __init__(self, buffers: OutputBufferManager, n_partitions: int):
        self.buffers = buffers
        self.n_partitions = n_partitions

    def rebind(self, buffers: OutputBufferManager) -> None:
        self.buffers = buffers

    def create(self, ctx: OperatorContext):
        return RoundRobinOutputOperator(ctx, self.buffers,
                                        self.n_partitions)


class TaskOutputOperatorFactory(OperatorFactory):
    def __init__(self, buffers: OutputBufferManager):
        self.buffers = buffers

    def rebind(self, buffers: OutputBufferManager) -> None:
        self.buffers = buffers

    def create(self, ctx: OperatorContext):
        return TaskOutputOperator(ctx, self.buffers)


# ---------------------------------------------------------------------------
# consumer side
# ---------------------------------------------------------------------------

class HttpPageClient(threading.Thread):
    """Long-polls one producer buffer, acking by token advance.

    Transport errors retry through a ``RequestErrorTracker``: because
    the token only advances on success, a retried GET simply re-fetches
    the unacked pages (at-least-once delivery with token dedup — the
    HttpPageBufferClient.java:297 semantics).  The owning
    ``ExchangeClient`` may redirect the poll at a replacement task
    attempt mid-stream (whole-stage retry / speculative re-execution):
    ``epoch`` increments on every repoint so a response in flight from
    the previous attempt is discarded, and the ``base_url`` — which
    carries the producer's attempt-qualified task id — keys the
    attempt-aware page accounting.

    Second source kind — **spool-read**: a ``spool://v1/task/{id}/
    results/{part}`` base url pulls the same token-addressed stream from
    the shared ``SpoolStore`` instead of the producer's HTTP buffer.
    Identical contract (pages, next token, complete), so a fetcher can
    be repointed from a dead producer's HTTP buffer at its spooled
    output MID-STREAM and resume at the current token: the spool is the
    same attempt, just a different backing store.
    """

    def __init__(self, base_url: str, client: "ExchangeClient",
                 headers: Optional[dict] = None,
                 http: Optional[RetryingHttpClient] = None,
                 task_id: Optional[str] = None,
                 trace_token: Optional[str] = None):
        super().__init__(daemon=True)
        self.base_url = base_url.rstrip("/")
        self.client = client
        self.token = 0
        self.epoch = 0
        # set once the stream's final page arrived (complete=true) — a
        # finished fetcher needs no replacement on repoint
        self.finished_stream = False
        # per-cluster intra-auth headers (one process can host clusters
        # with different secrets; never process-global state)
        self.headers = dict(headers or {})
        self.http = http or RetryingHttpClient()
        self.task_id = task_id
        self.trace_token = trace_token
        self._lock = threading.Lock()
        self._stall_started: Optional[float] = None
        self._tracker = self.http.new_tracker(
            self.base_url, task_id=task_id, description="exchange fetch",
            trace_token=trace_token)

    def _fetch_spool(self, base: str, token: int):
        """One spool poll: (pages, next_token, complete).  A stream with
        no progress for ``spool_stall_s`` raises — the producer died
        without a failure channel through the store."""
        from presto_tpu.server.spool import parse_spool_url

        spool = self.client.spool
        if spool is None:
            raise RuntimeError(
                f"spool source {base} but no spool store configured")
        tid, part = parse_spool_url(base)
        pages, next_token, complete = spool.get_pages(
            tid, part, token, wait_s=1.0)
        if not pages and not complete:
            if self._stall_started is None:
                self._stall_started = time.monotonic()
            elif (time.monotonic() - self._stall_started
                    > self.client.spool_stall_s):
                raise RuntimeError(
                    f"spool stream {base} stalled: no pages and no "
                    f"COMPLETE marker for {self.client.spool_stall_s:g}s "
                    f"(producer lost before finishing?)")
        else:
            self._stall_started = None
        return pages, next_token, complete

    def run(self) -> None:
        try:
            while True:
                with self._lock:
                    base, token, epoch = (self.base_url, self.token,
                                          self.epoch)
                try:
                    if base.startswith("spool://"):
                        pages, next_token, complete = \
                            self._fetch_spool(base, token)
                    else:
                        resp = self.http.request_once(
                            f"{base}/{token}",
                            headers=dict(self.headers), timeout=120)
                        complete = resp.headers.get(
                            "X-Presto-Buffer-Complete") == "true"
                        next_token = int(resp.headers.get(
                            "X-Presto-Next-Token", token))
                        body = resp.body
                        pages = []
                        off = 0
                        while off < len(body):
                            size = frame_size(body, off)
                            pages.append(body[off:off + size])
                            off += size
                except Exception as e:  # noqa: BLE001 - classified
                    with self._lock:
                        if self.epoch != epoch:
                            continue   # repointed mid-flight: new source
                    # raises RemoteRequestError when fatal or the error
                    # budget is exhausted; else backs off and we retry
                    # (possibly against a repointed base_url)
                    self._tracker.failed(e)
                    continue
                self._tracker.succeeded()
                for page in pages:
                    # the exchange drops the page if this epoch is stale
                    # (repointed while the response was in flight)
                    self.client.on_page(page, self, epoch, base)
                with self._lock:
                    if self.epoch == epoch:
                        self.token = next_token
                    else:
                        continue
                if complete:
                    with self._lock:
                        self.finished_stream = True
                    break
        except Exception as e:  # noqa: BLE001 - surfaces to the driver
            self.client.on_source_error(self, e)
            return
        self.client.on_client_finished()


class ExchangeClient:
    """Merges pages from N producer buffers (ExchangeClient.java:55).

    Buffering is bounded (the reference's maxBufferedBytes): when the
    consumer falls behind, ``on_page`` blocks the fetching thread, which
    delays its next token-advancing GET — so backpressure propagates to
    the producer's output buffer instead of growing this list unboundedly.
    """

    def __init__(self, locations: Sequence[str],
                 max_buffered_bytes: int = 64 << 20,
                 headers: Optional[dict] = None,
                 http: Optional[RetryingHttpClient] = None,
                 task_id: Optional[str] = None,
                 trace_token: Optional[str] = None,
                 spool=None, spool_stall_s: float = 60.0):
        # shared SpoolStore for spool:// source urls (the spooled
        # exchange's consumer half); None when spooling is disabled
        self.spool = spool
        self.spool_stall_s = spool_stall_s
        self._lock = threading.Lock()
        self._drained = threading.Condition(self._lock)
        # signaled on page arrival / stream completion / error so an
        # exchange-bound driver can park in wait_for_page instead of
        # sleep-polling (the reference blocks the driver on the
        # ExchangeClient's isBlocked future the same way)
        self._arrived = threading.Condition(self._lock)
        # buffered pages tagged with their source url — the url carries
        # the producer's attempt-qualified task id, so every page is
        # identified by (task id, attempt, token) end to end and the
        # dedup accounting below is per attempt
        self._pages: List[Tuple[str, bytes]] = []
        self._buffered_bytes = 0
        self._max_buffered_bytes = max(1, max_buffered_bytes)
        self._closed = False
        self._error: Optional[Exception] = None
        self.task_id = task_id
        self.trace_token = trace_token
        self._headers = headers
        self._http = http
        # per-source-url dedup counters: 'fetched' pages buffered here,
        # 'consumed' pages handed to the operator chain, 'purged' pages
        # dropped on a repoint before the operator saw them.  The
        # exactness invariant whole-stage retry and speculation rely on:
        # for any producer task, at most ONE attempt ever has
        # consumed > 0 — a repoint is refused ('delivered') otherwise.
        self.source_stats: Dict[str, Dict[str, int]] = {}
        self._clients = [HttpPageClient(loc, self, headers=headers,
                                        http=http, task_id=task_id,
                                        trace_token=trace_token)
                         for loc in locations]
        self._remaining = len(self._clients)
        for c in self._clients:
            c.start()

    def _stat(self, url: str) -> Dict[str, int]:
        s = self.source_stats.get(url)
        if s is None:
            s = {"fetched": 0, "consumed": 0, "purged": 0}
            self.source_stats[url] = s
        return s

    def delivery_state(self, old_prefix: str) -> str:
        """Probe (read-only): 'delivered' when pages from a source under
        ``old_prefix`` already entered the operator chain, 'clean' when
        the source matches but nothing was consumed (buffered pages can
        still be purged), 'not-found' otherwise."""
        old = old_prefix.rstrip("/")
        state = "not-found"
        with self._lock:
            for c in self._clients:
                if not c.base_url.startswith(old):
                    continue
                if self.source_stats.get(
                        c.base_url, {}).get("consumed", 0) > 0:
                    return "delivered"
                state = "clean"
        return state

    def repoint(self, old_prefix: str, new_prefix: str) -> str:
        """Redirect every fetcher polling under ``old_prefix`` at the
        replacement attempt's results under ``new_prefix`` (whole-stage
        retry / speculative re-execution / leaf task recovery).

        Exactness: allowed only while ZERO pages of the old attempt were
        consumed by the operator chain — buffered-but-unconsumed pages
        are purged and the fetch restarts at token 0 of the new attempt,
        so rows always come wholly from one attempt.  Returns
        'repointed', 'delivered' (old-attempt pages already consumed —
        the consumer itself must be restarted), or 'not-found'."""
        old = old_prefix.rstrip("/")
        new = new_prefix.rstrip("/")
        with self._lock:
            matched = [c for c in self._clients
                       if c.base_url.startswith(old)]
            if not matched:
                return "not-found"
            for c in matched:
                if self.source_stats.get(
                        c.base_url, {}).get("consumed", 0) > 0:
                    return "delivered"
            for i, c in enumerate(list(self._clients)):
                if c not in matched:
                    continue
                with c._lock:
                    url = c.base_url
                    # purge buffered pages of the superseded attempt so
                    # they can never double-count against the new stream
                    kept = []
                    for (u, p) in self._pages:
                        if u == url:
                            self._buffered_bytes -= len(p)
                            self._stat(u)["purged"] += 1
                        else:
                            kept.append((u, p))
                    self._pages = kept
                    c.base_url = new + url[len(old):]
                    c.token = 0
                    c.epoch += 1
                    c._stall_started = None
                    c._tracker.reset(endpoint=c.base_url)
                    alive = c.is_alive()
                    new_url = c.base_url
                if not alive:
                    # the old attempt's stream completed (thread exited)
                    # with nothing consumed: fetch the replacement with a
                    # fresh client — threads cannot restart
                    repl = HttpPageClient(new_url, self,
                                          headers=self._headers,
                                          http=self._http,
                                          task_id=self.task_id,
                                          trace_token=self.trace_token)
                    self._clients[self._clients.index(c)] = repl
                    self._remaining += 1
                    repl.start()
            self._drained.notify_all()
            self._arrived.notify_all()
        return "repointed"

    def repoint_spool(self, old_prefix: str, new_prefix: str) -> str:
        """Redirect fetchers under ``old_prefix`` at the SAME attempt's
        spooled output under ``new_prefix`` (a ``spool://`` prefix
        carrying the same task id).

        Unlike an attempt-change repoint there is no delivered guard and
        no restart from token 0: the spool serves the identical
        token-addressed stream, so the fetch RESUMES at exactly the
        number of pages the operator chain already consumed from this
        source — buffered-but-unconsumed pages are purged (they will be
        re-read from the spool at the same tokens) and nothing can
        double-count.  Returns 'repointed' or 'not-found'."""
        old = old_prefix.rstrip("/")
        new = new_prefix.rstrip("/")
        with self._lock:
            matched = [c for c in self._clients
                       if c.base_url.startswith(old)]
            if not matched:
                return "not-found"
            for c in matched:
                with c._lock:
                    url = c.base_url
                    if c.finished_stream:
                        continue   # fully served: nothing left to move
                    # purge buffered-unconsumed pages of this source;
                    # the resume token is then precisely the consumed
                    # count (tokens are sequential page indices)
                    kept = []
                    for (u, p) in self._pages:
                        if u == url:
                            self._buffered_bytes -= len(p)
                            self._stat(u)["purged"] += 1
                        else:
                            kept.append((u, p))
                    self._pages = kept
                    c.base_url = new + url[len(old):]
                    c.token = self.source_stats.get(
                        url, {}).get("consumed", 0)
                    c.epoch += 1
                    c._stall_started = None
                    c._tracker.reset(endpoint=c.base_url)
                    alive = c.is_alive()
                    new_url = c.base_url
                if not alive:
                    # fetcher exited on a terminal transport error but
                    # the exchange survives: resume the stream from the
                    # spool with a fresh thread
                    repl = HttpPageClient(new_url, self,
                                          headers=self._headers,
                                          http=self._http,
                                          task_id=self.task_id,
                                          trace_token=self.trace_token)
                    repl.token = c.token
                    self._clients[self._clients.index(c)] = repl
                    self._remaining += 1
                    repl.start()
            self._drained.notify_all()
            self._arrived.notify_all()
        return "repointed"

    def on_page(self, page: bytes, source: "HttpPageClient",
                epoch: int, url: str) -> None:
        with self._lock:
            if source.epoch != epoch:
                return   # stale attempt: repointed while in flight
            while (self._buffered_bytes >= self._max_buffered_bytes
                   and not self._closed and self._error is None):
                self._drained.wait(timeout=1.0)
                if source.epoch != epoch:
                    return
            if self._closed or self._error is not None:
                return
            self._pages.append((url, page))
            self._buffered_bytes += len(page)
            self._stat(url)["fetched"] += 1
            self._arrived.notify_all()

    def on_error(self, e: Exception) -> None:
        with self._lock:
            self._error = e
            self._remaining = 0
            self._drained.notify_all()
            self._arrived.notify_all()

    def on_source_error(self, source: "HttpPageClient",
                        e: Exception) -> None:
        """A fetcher gave up: attach the task + producer context so the
        failure names the exact hop instead of a bare urllib error."""
        if isinstance(e, RemoteRequestError):
            self.on_error(e)   # tracker already attached the context
            return
        who = f"task {self.task_id}" if self.task_id else "exchange"
        if self.trace_token:
            who += f" [trace:{self.trace_token}]"
        self.on_error(RuntimeError(
            f"{who}: exchange fetch from {source.base_url} failed: {e}"))

    def on_client_finished(self) -> None:
        with self._lock:
            self._remaining -= 1
            self._arrived.notify_all()

    def close(self) -> None:
        """Stop accepting pages and unblock fetcher threads."""
        with self._lock:
            self._closed = True
            self._pages = []
            self._buffered_bytes = 0
            self._drained.notify_all()
            self._arrived.notify_all()

    def wait_for_page(self, timeout_s: float = 0.05) -> None:
        """Park until a page arrives, a stream finishes, or an error
        lands — bounded by ``timeout_s``.  Replaces the driver-side
        2 ms sleep-poll: exchange-bound drivers wake ON page arrival
        instead of on a timer."""
        with self._lock:
            if (self._pages or self._error is not None or self._closed
                    or self._remaining == 0):
                return
            self._arrived.wait(timeout=timeout_s)

    def poll_page(self) -> Optional[bytes]:
        with self._lock:
            if self._error is not None:
                raise RuntimeError(
                    f"exchange failed: {self._error}") from self._error
            if self._pages:
                url, page = self._pages.pop(0)
                self._buffered_bytes -= len(page)
                self._stat(url)["consumed"] += 1
                self._drained.notify_all()
                return page
            return None

    @property
    def finished(self) -> bool:
        with self._lock:
            if self._error is not None:
                raise RuntimeError(
                    f"exchange failed: {self._error}") from self._error
            return self._remaining == 0 and not self._pages


class ExchangeOperator(Operator):
    """Source operator draining an ExchangeClient
    (ExchangeOperator.java:36)."""

    def __init__(self, ctx: OperatorContext, client: ExchangeClient):
        super().__init__(ctx)
        self.client = client

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[Batch]:
        page = self.client.poll_page()
        if page is None:
            if not self.client.finished:
                # condition-variable timed wait: wakes on page arrival
                # instead of a fixed 2 ms timer (driver re-polls after)
                with activity("exchange_wait"):
                    self.client.wait_for_page()
            return None
        batch = deserialize_batch(page)
        self.ctx.stats.input_rows += batch.num_rows
        self.ctx.stats.output_rows += batch.num_rows
        return batch

    def is_finished(self) -> bool:
        return self.client.finished

    def close(self) -> None:
        # unblock any fetcher thread parked on the buffer cap
        self.client.close()
        super().close()


def _repoint_locations(locations: List[str], old_prefix: str,
                       new_prefix: str) -> str:
    """Rewrite not-yet-fetched producer locations (the pre-create half
    of mid-query recovery: the exchange client does not exist yet, so
    nothing was delivered and a plain rewrite is always safe)."""
    old, new = old_prefix.rstrip("/"), new_prefix.rstrip("/")
    hit = False
    for i, loc in enumerate(locations):
        if loc.startswith(old):
            locations[i] = new + loc[len(old):]
            hit = True
    return "repointed" if hit else "not-found"


def _probe_locations(locations: Sequence[str], old_prefix: str) -> str:
    old = old_prefix.rstrip("/")
    return ("clean" if any(loc.startswith(old) for loc in locations)
            else "not-found")


class ExchangeOperatorFactory(OperatorFactory):
    def __init__(self, locations: Sequence[str],
                 headers: Optional[dict] = None,
                 http: Optional[RetryingHttpClient] = None,
                 task_id: Optional[str] = None,
                 trace_token: Optional[str] = None,
                 spool=None, spool_stall_s: float = 60.0):
        self.locations = list(locations)
        self.headers = headers
        self.http = http
        self.task_id = task_id
        self.trace_token = trace_token
        self.spool = spool
        self.spool_stall_s = spool_stall_s
        self._client: Optional[ExchangeClient] = None

    def rebind(self, locations: Sequence[str], task_id: Optional[str],
               trace_token: Optional[str]) -> None:
        """Re-arm this (cached) remote source for a fresh task create:
        new producer locations (they embed the new query id), fresh
        exchange client, the new task's identity on fetch failures —
        the worker plan_fragment cache's per-task rebinding."""
        self.locations = list(locations)
        self.task_id = task_id
        self.trace_token = trace_token
        self._client = None

    def repoint(self, old_prefix: str, new_prefix: str) -> str:
        if self._client is not None:
            return self._client.repoint(old_prefix, new_prefix)
        return _repoint_locations(self.locations, old_prefix, new_prefix)

    def repoint_spool(self, old_prefix: str, new_prefix: str) -> str:
        """Same-attempt spool repoint (no delivered guard, token kept)."""
        if self._client is not None:
            return self._client.repoint_spool(old_prefix, new_prefix)
        return _repoint_locations(self.locations, old_prefix, new_prefix)

    def delivery_state(self, old_prefix: str) -> str:
        """Probe half of the repoint protocol (read-only)."""
        if self._client is not None:
            return self._client.delivery_state(old_prefix)
        return _probe_locations(self.locations, old_prefix)

    def source_stats(self) -> dict:
        """Attempt-aware dedup counters per source url (for task info)."""
        if self._client is None:
            return {}
        with self._client._lock:
            return {u: dict(s)
                    for u, s in self._client.source_stats.items()}

    def create(self, ctx: OperatorContext):
        if self._client is None:
            self._client = ExchangeClient(self.locations,
                                          headers=self.headers,
                                          http=self.http,
                                          task_id=self.task_id,
                                          trace_token=self.trace_token,
                                          spool=self.spool,
                                          spool_stall_s=self.spool_stall_s)
        return ExchangeOperator(ctx, self._client)


class MergeExchangeOperator(Operator):
    """Order-preserving remote source: k-way merges pre-sorted producer
    streams row-at-a-time (MergeOperator.java:45 over MergeSortedPages).

    One ExchangeClient per producer location keeps each stream's page
    order; a head row is comparable only when every unfinished stream
    has at least one buffered row, so the merge never emits out of
    order.  ``limit`` stops the merge early (distributed TopN)."""

    def __init__(self, ctx: OperatorContext, locations: Sequence[str],
                 sort_keys, types, limit: Optional[int] = None,
                 batch_rows: int = 8192, headers: Optional[dict] = None,
                 http: Optional[RetryingHttpClient] = None,
                 task_id: Optional[str] = None,
                 trace_token: Optional[str] = None,
                 spool=None, spool_stall_s: float = 60.0):
        super().__init__(ctx)
        self.clients = [ExchangeClient([loc], headers=headers,
                                       http=http, task_id=task_id,
                                       trace_token=trace_token,
                                       spool=spool,
                                       spool_stall_s=spool_stall_s)
                        for loc in locations]
        self.sort_keys = list(sort_keys)   # (channel, ascending, nulls_first)
        self.types = list(types)
        self.limit = limit
        self.batch_rows = batch_rows
        self.rows_emitted = 0
        self.queues: List[List[tuple]] = [[] for _ in locations]
        self.positions = [0] * len(locations)
        self.done = False

    def needs_input(self) -> bool:
        return False

    def _refill(self, i: int) -> bool:
        """True if stream i has a head row or is finished."""
        q, pos = self.queues[i], self.positions[i]
        if pos < len(q):
            return True
        self.queues[i] = []
        self.positions[i] = 0
        page = self.clients[i].poll_page()
        if page is None:
            return self.clients[i].finished
        batch = deserialize_batch(page)
        self.ctx.stats.input_rows += batch.num_rows
        self.queues[i] = batch.to_pylist()
        return bool(self.queues[i]) or self._refill(i)

    def _before(self, a: tuple, b: tuple) -> bool:
        for channel, ascending, nulls_first in self.sort_keys:
            av, bv = a[channel], b[channel]
            nf = bool(nulls_first)
            if av is None or bv is None:
                if av is None and bv is None:
                    continue
                return (av is None) == nf
            # NaN sorts greatest (matching to_sortable_i64's bit order
            # on the producers); plain < would treat it as unordered
            a_nan = isinstance(av, float) and av != av
            b_nan = isinstance(bv, float) and bv != bv
            if a_nan or b_nan:
                if a_nan and b_nan:
                    continue
                return b_nan == bool(ascending)
            if av == bv:
                continue
            return (av < bv) == bool(ascending)
        return False

    def get_output(self) -> Optional[Batch]:
        from presto_tpu.batch import batch_from_pylist

        if self.done:
            return None
        ready = True
        stalled = None
        for i in range(len(self.clients)):
            if not self._refill(i):
                ready = False
                if stalled is None:
                    stalled = i
        if not ready:
            # park on the first stalled stream's arrival condition
            # instead of a fixed 2 ms sleep; driver re-polls after
            with activity("exchange_wait"):
                self.clients[stalled].wait_for_page()
            return None
        out: List[tuple] = []
        while len(out) < self.batch_rows:
            if self.limit is not None and \
                    self.rows_emitted + len(out) >= self.limit:
                self.done = True
                break
            best = -1
            best_row = None
            for i in range(len(self.clients)):
                q, pos = self.queues[i], self.positions[i]
                if pos >= len(q):
                    continue
                row = q[pos]
                if best < 0 or self._before(row, best_row):
                    best, best_row = i, row
            if best < 0:
                self.done = True  # every stream drained
                break
            out.append(best_row)
            self.positions[best] += 1
            if self.positions[best] >= len(self.queues[best]):
                # _refill: True = has a head row again OR finished;
                # False = stalled mid-merge -> emit what we have and
                # resume next get_output once it has a head row
                if not self._refill(best):
                    break
        if self.done:
            # stop fetching immediately (limit reached / streams
            # drained); the coordinator cancels producers afterwards
            for c in self.clients:
                c.close()
        if not out:
            return None
        self.rows_emitted += len(out)
        batch = batch_from_pylist(self.types, out)
        self.ctx.stats.output_rows += batch.num_rows
        return batch

    def is_finished(self) -> bool:
        if self.done:
            return True
        if all(c.finished for c in self.clients) and all(
                self.positions[i] >= len(self.queues[i])
                for i in range(len(self.clients))):
            return True
        return False

    def close(self) -> None:
        for c in self.clients:
            c.close()
        super().close()


class MergeExchangeOperatorFactory(OperatorFactory):
    def __init__(self, locations: Sequence[str], sort_keys, types,
                 limit: Optional[int] = None,
                 headers: Optional[dict] = None,
                 http: Optional[RetryingHttpClient] = None,
                 task_id: Optional[str] = None,
                 trace_token: Optional[str] = None,
                 spool=None, spool_stall_s: float = 60.0):
        self.locations = list(locations)
        self.sort_keys = list(sort_keys)
        self.types = list(types)
        self.limit = limit
        self.headers = headers
        self.http = http
        self.task_id = task_id
        self.trace_token = trace_token
        self.spool = spool
        self.spool_stall_s = spool_stall_s
        self._live_clients: List[ExchangeClient] = []

    def rebind(self, locations: Sequence[str], task_id: Optional[str],
               trace_token: Optional[str]) -> None:
        self.locations = list(locations)
        self.task_id = task_id
        self.trace_token = trace_token
        self._live_clients = []

    def repoint(self, old_prefix: str, new_prefix: str) -> str:
        # probe every stream first: a partially-consumed one anywhere
        # makes the whole repoint unsafe, and must not leave the other
        # streams half-redirected
        states = [c.delivery_state(old_prefix) for c in self._live_clients]
        if "delivered" in states:
            return "delivered"
        statuses = [c.repoint(old_prefix, new_prefix)
                    for c in self._live_clients]
        if "delivered" in statuses:
            return "delivered"
        if "repointed" in statuses:
            return "repointed"
        return _repoint_locations(self.locations, old_prefix, new_prefix)

    def repoint_spool(self, old_prefix: str, new_prefix: str) -> str:
        statuses = [c.repoint_spool(old_prefix, new_prefix)
                    for c in self._live_clients]
        if "repointed" in statuses:
            return "repointed"
        return _repoint_locations(self.locations, old_prefix, new_prefix)

    def delivery_state(self, old_prefix: str) -> str:
        states = [c.delivery_state(old_prefix) for c in self._live_clients]
        if "delivered" in states:
            return "delivered"
        if "clean" in states:
            return "clean"
        return _probe_locations(self.locations, old_prefix)

    def source_stats(self) -> dict:
        out: dict = {}
        for c in self._live_clients:
            with c._lock:
                for u, s in c.source_stats.items():
                    out[u] = dict(s)
        return out

    def create(self, ctx: OperatorContext):
        op = MergeExchangeOperator(ctx, self.locations, self.sort_keys,
                                   self.types, self.limit,
                                   headers=self.headers, http=self.http,
                                   task_id=self.task_id,
                                   trace_token=self.trace_token,
                                   spool=self.spool,
                                   spool_stall_s=self.spool_stall_s)
        self._live_clients.extend(op.clients)
        return op
