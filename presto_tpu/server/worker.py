"""Worker HTTP server: the TaskResource surface.

Routes mirror the reference's worker REST API
(presto-main/.../server/TaskResource.java:83-84,121-124,240-244):

    POST   /v1/task/{taskId}                      create/update task
    GET    /v1/task/{taskId}                      task info/status
    DELETE /v1/task/{taskId}                      cancel
    GET    /v1/task/{taskId}/results/{buffer}/{token}   page fetch + ack
    GET    /v1/info                               node info (heartbeat ping)

Control bodies are JSON fragment descriptors (TaskUpdateRequest-style; the
in-process DistributedQueryRunner pattern); data responses are raw
concatenated wire frames (presto_tpu.serde) with token bookkeeping in
headers — the PRESTO_PAGES content-type role.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.connectors.api import ConnectorRegistry
from presto_tpu.server.task import SqlTaskManager
from presto_tpu.spans import HOST_ACTIVITY_HEADER


class WorkerServer:
    def __init__(self, registry: ConnectorRegistry,
                 config: EngineConfig = DEFAULT, port: int = 0,
                 node_id: str = "worker",
                 internal_secret: Optional[str] = None,
                 location: str = "",
                 fault_injector=None, http_client=None,
                 drain_grace_s: float = 2.0,
                 announce_to: Optional[list] = None,
                 announce_interval_s: float = 1.0):
        from presto_tpu.server.errortracker import RetryingHttpClient
        from presto_tpu.server.security import InternalAuthenticator
        from presto_tpu.server.spool import make_spool_store

        self.node_id = node_id
        # topology label (rack/zone) announced to the
        # coordinator for TopologyAwareNodeSelector placement
        self.location = location
        # device-mesh identity announced to the coordinator: placements
        # sharing a fingerprint (and the coordinator's own) are
        # co-resident on one jax mesh, enabling the device-sharded
        # exchange tier (mesh_device_exchange)
        from presto_tpu.parallel.mesh import mesh_fingerprint

        self.mesh_fingerprint = mesh_fingerprint()
        self.internal_auth = (InternalAuthenticator(internal_secret)
                              if internal_secret else None)
        # chaos substrate hook (server/faults.py): consulted before every
        # request is dispatched; None in production
        self.fault_injector = fault_injector
        # node-wide error-tracked HTTP client: this worker's remote-source
        # fetches retry transient producer failures with backoff
        self.http = http_client or RetryingHttpClient(
            max_error_duration_s=config.remote_request_max_error_duration_s,
            min_backoff_s=config.remote_request_min_backoff_s,
            max_backoff_s=config.remote_request_max_backoff_s)
        # spooled exchange tier: the store is always constructed (dirs
        # are created lazily on first write) so a SET SESSION toggle can
        # enable spooling per query; exchange_spooling_enabled gates use
        self.spool = make_spool_store(config, injector=fault_injector)
        self.task_manager = SqlTaskManager(
            registry, config,
            fetch_headers=(self.internal_auth.header()
                           if self.internal_auth else None),
            http_client=self.http, spool=self.spool,
            fault_injector=fault_injector)
        # graceful shutdown (GracefulShutdownHandler.java role): once
        # draining, new tasks are refused, /v1/info advertises
        # SHUTTING_DOWN so the coordinator stops scheduling here, and
        # close() waits for running tasks to finish.  PUT /v1/info/state
        # additionally starts the drain-and-remove sequence after a
        # grace period (the reference sleeps its gracePeriod twice) —
        # with spooling on, finished tasks' output is durable in the
        # spool, so the worker exits without waiting for consumers.
        self.draining = False
        self.drain_grace_s = drain_grace_s
        self._drain_thread: Optional[threading.Thread] = None
        worker = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):  # quiet
                pass

            def _json(self, code: int, payload) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _fault(self, method: str) -> bool:
                """True when the injector consumed this request (the
                chaos hook: http-503 answered or connection dropped)."""
                inj = worker.fault_injector
                if inj is None:
                    return False
                hit = inj.apply_server(self.path, method)
                if hit is None:
                    return False
                policy, rule = hit
                if policy == "http-503":
                    self._json(rule.status, {"error": "injected fault"})
                else:  # drop-connection: no response bytes at all
                    self.close_connection = True
                return True

            def _internal_ok(self, parts) -> bool:
                """Everything under /v1/task and /v1/query (create,
                status, results, cancel) requires the cluster token when
                one is set; the /v1/info health probe stays open."""
                if worker.internal_auth is None or \
                        parts[:2] not in (["v1", "task"], ["v1", "query"]):
                    return True
                from presto_tpu.server.security import (
                    InternalAuthenticator,
                )

                if worker.internal_auth.verify(self.headers.get(
                        InternalAuthenticator.HEADER)):
                    return True
                self._json(401, {"error": "unauthenticated internal "
                                          "request"})
                return False

            def do_GET(self):  # noqa: N802
                if self._fault("GET"):
                    return
                parts = self.path.strip("/").split("/")
                if parts[:2] == ["v1", "info"]:
                    self._json(200, {
                        "nodeId": worker.node_id,
                        "state": ("SHUTTING_DOWN" if worker.draining
                                  else "ACTIVE"),
                        # live MemoryInfo rides the health surface so
                        # any poller sees pool pressure without the
                        # authenticated /v1/memory endpoint
                        "memoryInfo":
                            worker.task_manager.memory_info()})
                    return
                if parts == ["metrics"]:
                    # Prometheus text plane (server/metrics.py); open
                    # like /v1/info — it exposes counters, never SQL,
                    # plans, or rows
                    from presto_tpu.server.metrics import worker_metrics

                    body = worker_metrics(worker).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if parts == ["v1", "memory"]:
                    if not self._internal_ok(["v1", "task"]):
                        return
                    self._json(200, worker.task_manager.memory_info())
                    return
                if not self._internal_ok(parts):
                    return
                if parts == ["v1", "task"]:
                    self._json(200, worker.task_manager.list_infos())
                    return
                if parts[:2] == ["v1", "task"] and len(parts) == 3:
                    task = worker.task_manager.get(parts[2])
                    if task is None:
                        self._json(404, {"error": "no such task"})
                        return
                    self._json(200, task.info(activity=self.headers.get(
                        HOST_ACTIVITY_HEADER) == "1"))
                    return
                if (parts[:2] == ["v1", "task"] and len(parts) == 6
                        and parts[3] == "results"):
                    self._results(parts[2], int(parts[4]), int(parts[5]))
                    return
                self._json(404, {"error": f"bad path {self.path}"})

            def _results(self, task_id: str, buffer_id: int,
                         token: int) -> None:
                task = worker.task_manager.get(task_id)
                if task is None:
                    self._json(404, {"error": "no such task"})
                    return
                try:
                    pages, next_token, complete = task.buffers.get_pages(
                        buffer_id, token, wait_s=1.0)
                except Exception as e:  # noqa: BLE001
                    self._json(500, {"error": str(e)})
                    return
                body = b"".join(pages)
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/x-presto-pages")
                self.send_header("Content-Length", str(len(body)))
                self.send_header("X-Presto-Next-Token", str(next_token))
                self.send_header("X-Presto-Buffer-Complete",
                                 "true" if complete else "false")
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):  # noqa: N802
                if self._fault("POST"):
                    return
                parts = self.path.strip("/").split("/")
                # intra-cluster auth: a worker only executes plans from
                # peers holding the shared-secret token
                # (InternalAuthenticationManager role)
                if not self._internal_ok(parts):
                    return
                if (parts[:2] == ["v1", "task"] and len(parts) == 4
                        and parts[3] == "coordinator"):
                    # coordinator HA re-attach: a standby that adopted
                    # this task's query on failover announces itself as
                    # the owning coordinator.  The task is untouched —
                    # it keeps producing into the spool; the response
                    # carries enough state for the standby to decide
                    # re-attach vs spool-repoint vs restart.
                    task = worker.task_manager.get(parts[2])
                    if task is None:
                        self._json(404, {"error": "no such task"})
                        return
                    n = int(self.headers.get("Content-Length", 0))
                    try:
                        req = json.loads(self.rfile.read(n) or b"{}")
                        task.coordinator_uri = str(
                            req.get("coordinator") or "")
                    except ValueError as e:
                        self._json(400, {"error": f"bad repoint: {e}"})
                        return
                    self._json(200, {
                        "status": "reattached",
                        "state": task.state,
                        "pagesEnqueued": task.buffers.pages_enqueued,
                        "spooledComplete":
                            task.buffers.spooled_complete()})
                    return
                if (parts[:2] == ["v1", "task"] and len(parts) == 4
                        and parts[3] == "remote-sources"):
                    # mid-query task recovery: repoint this task's
                    # remote-source fetches at a replacement producer.
                    # Allowed while draining — it keeps queries already
                    # running here alive.
                    task = worker.task_manager.get(parts[2])
                    if task is None:
                        self._json(404, {"error": "no such task"})
                        return
                    n = int(self.headers.get("Content-Length", 0))
                    try:
                        req = json.loads(self.rfile.read(n))
                        old = str(req["old_prefix"])
                        probe = bool(req.get("probe", False))
                        # spool=true: same-attempt repoint at the dead
                        # producer's spooled output (token preserved,
                        # no delivered guard)
                        spool = bool(req.get("spool", False))
                        new = "" if probe else str(req["new_prefix"])
                    except (KeyError, TypeError, ValueError) as e:
                        self._json(400, {"error": f"bad repoint: {e}"})
                        return
                    if probe:
                        # read-only delivery probe: whole-stage retry
                        # sizes its restart cascade with this before
                        # mutating any source
                        status = task.probe_remote_source(old)
                    else:
                        status = task.repoint_remote_source(
                            old, new, spool=spool)
                    self._json(200, {"status": status})
                    return
                if parts[:2] == ["v1", "task"] and worker.draining:
                    self._json(503, {"error": "worker is shutting down"})
                    return
                if parts[:2] == ["v1", "task"] and len(parts) == 3:
                    from presto_tpu.sql.planserde import (
                        PlanSerdeError, fragment_from_json,
                    )

                    n = int(self.headers.get("Content-Length", 0))
                    try:
                        req = json.loads(self.rfile.read(n))
                        fragment = fragment_from_json(req["fragment"])
                        scan_shard = tuple(req["scan_shard"])
                        remote_sources = {int(fid): us for fid, us in
                                          req["remote_sources"].items()}
                        n_out = int(req["n_output_partitions"])
                        broadcast = bool(req["broadcast_output"])
                        session_props = dict(
                            req.get("session_properties") or {})
                        # query trace token: body field, with the header
                        # as fallback (TraceTokenModule role)
                        trace_token = str(
                            req.get("trace_token")
                            or self.headers.get("X-Presto-Trace-Token")
                            or "")
                        # coordinator stats-epoch snapshot keying the
                        # worker-side plan_fragment cache
                        plan_epochs = req.get("plan_epochs") or None
                    except (PlanSerdeError, KeyError, TypeError,
                            AttributeError, ValueError) as e:
                        self._json(400, {"error": f"bad task update: {e}"})
                        return
                    try:
                        task = worker.task_manager.create_task(
                            task_id=parts[2],
                            fragment=fragment,
                            scan_shard=scan_shard,
                            remote_sources=remote_sources,
                            n_output_partitions=n_out,
                            broadcast_output=broadcast,
                            session_properties=session_props,
                            trace_token=trace_token,
                            plan_epochs=plan_epochs)
                    except Exception as e:  # noqa: BLE001 - bad props
                        self._json(400, {"error": f"bad task update: {e}"})
                        return
                    self._json(200, task.info())
                    return
                self._json(404, {"error": f"bad path {self.path}"})

            def do_PUT(self):  # noqa: N802
                if self._fault("PUT"):
                    return
                parts = self.path.strip("/").split("/")
                if not self._internal_ok(["v1", "task"]):
                    return
                if parts == ["v1", "info", "state"]:
                    # PUT "SHUTTING_DOWN" starts a graceful drain
                    # (the reference's /v1/info/state shutdown trigger):
                    # refuse new tasks immediately, then — after a grace
                    # period that lets the coordinator observe the state
                    # and repoint consumers at the spool — wait out
                    # running tasks and leave the cluster
                    n = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(n).decode().strip().strip('"')
                    if body != "SHUTTING_DOWN":
                        self._json(400, {"error": f"bad state {body!r}"})
                        return
                    worker.draining = True
                    worker._start_drain()
                    self._json(200, {"state": "SHUTTING_DOWN"})
                    return
                self._json(404, {"error": f"bad path {self.path}"})

            def do_DELETE(self):  # noqa: N802
                if self._fault("DELETE"):
                    return
                parts = self.path.strip("/").split("/")
                if not self._internal_ok(parts):
                    return
                if parts[:2] == ["v1", "task"] and len(parts) == 3:
                    task = worker.task_manager.get(parts[2])
                    if task is not None:
                        task.cancel()
                    self._json(200, {"canceled": True})
                    return
                if parts[:2] == ["v1", "query"] and len(parts) == 3:
                    n = worker.task_manager.cancel_query(parts[2])
                    self._json(200, {"canceledTasks": n})
                    return
                self._json(404, {"error": f"bad path {self.path}"})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self.uri = f"http://127.0.0.1:{self.port}"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name=f"worker-http-{self.port}")
        self._thread.start()
        # stateless announcer (coordinator HA): re-announce this node
        # to EVERY configured coordinator — primary and standby alike —
        # so a standby that takes over already knows the live cluster
        self.announce_to = list(announce_to or [])
        self._announce_stop = threading.Event()
        if self.announce_to:
            threading.Thread(
                target=self._announce_loop,
                args=(max(announce_interval_s, 0.1),),
                daemon=True,
                name=f"announce-{self.node_id}").start()

    def announce_once(self) -> None:
        """One announcement round to every configured coordinator
        (best-effort per target: a dead primary must not stop the
        standby from hearing about this node)."""
        import urllib.request

        body = json.dumps({
            "nodeId": self.node_id, "uri": self.uri,
            "location": self.location,
            "meshFingerprint": self.mesh_fingerprint,
            # MemoryInfo rides announcements: the coordinator's memory
            # tick folds it without waiting for its own poll round
            "memoryInfo": self.task_manager.memory_info()}).encode()
        headers = {"Content-Type": "application/json"}
        if self.internal_auth is not None:
            headers.update(self.internal_auth.header())
        for target in self.announce_to:
            try:
                req = urllib.request.Request(
                    f"{target}/v1/announcement", data=body,
                    method="POST", headers=dict(headers))
                with urllib.request.urlopen(req, timeout=5):
                    pass
            except Exception:  # noqa: BLE001 - a target may be down
                pass

    def _announce_loop(self, interval_s: float) -> None:
        self.announce_once()
        while not self._announce_stop.wait(interval_s):
            if self.draining:
                return
            self.announce_once()

    def _start_drain(self) -> None:
        """Background drain-and-remove (the PUT /v1/info/state role):
        grace sleep, then the full graceful shutdown."""
        import time

        if self._drain_thread is not None:
            return

        def drain():
            time.sleep(self.drain_grace_s)
            self.shutdown_gracefully()

        self._drain_thread = threading.Thread(
            target=drain, daemon=True,
            name=f"drain-{self.node_id}")
        self._drain_thread.start()

    def shutdown_gracefully(self, drain_timeout_s: float = 30.0) -> None:
        """Stop accepting tasks, wait for running ones, then close
        (GracefulShutdownHandler drain sequence)."""
        import time

        self.draining = True
        deadline = time.monotonic() + drain_timeout_s
        # wait for tasks to finish AND for their output to be safe:
        # either consumers fetched it, or (spooled exchange) the whole
        # output is durable in the spool and consumers re-pull it from
        # there — closing earlier would destroy pages a downstream
        # stage still needs
        while (self.task_manager.undrained_count() > 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
        self.close()

    def close(self) -> None:
        self._announce_stop.set()
        self.task_manager.cancel_all()
        # the tables this node's connectors kept on the device go with it
        from presto_tpu.exec.scancache import drop_connectors

        drop_connectors(self.task_manager.registry)
        self.spool.close()
        self._httpd.shutdown()
        self._httpd.server_close()
