"""What the benchmark observes of the program from outside: XLA's compile
events, the compile cache directory, device memory, and the coordinator's
own account of a query (detail and span tree over HTTP)."""

from __future__ import annotations

import json
import os
import time
import urllib.request

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class XlaCompiles:
    """Every XLA program this process builds, with its seconds and the
    time it was ready (``jax.monitoring``).  JAX raises the event around
    ``compile_or_get_cached``, so a program loaded from the persistent
    cache counts too, with the seconds the load took; ``cache_hits`` says
    how many of them were loads."""

    def __init__(self):
        import jax.monitoring

        self.events: list = []   # (epoch seconds at end, name, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.events.append((time.time(), str(kw.get("fun_name", "?")),
                                float(secs)))

    def since(self, mark: int) -> list:
        return self.events[mark:]


def cache_entries(cache_dir: str | None) -> int:
    return (len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir) else 0)


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def get_json(uri: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(uri, timeout=timeout) as resp:
        return json.loads(resp.read())


def query_detail(coordinator_uri: str, query_id: str) -> dict:
    return get_json(f"{coordinator_uri}/v1/query/{query_id}")


def query_spans(coordinator_uri: str, query_id: str) -> dict:
    return get_json(f"{coordinator_uri}/v1/query/{query_id}/spans")
