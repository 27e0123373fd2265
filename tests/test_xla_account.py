"""The compile account that sees what XLA sees: kernelcache's
``jax.monitoring`` listener charges every build to the task whose thread
made it (TaskStats.xla_builds ... rolled up to queryStats)."""

import json
import threading
import urllib.request

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from presto_tpu import kernelcache, spans
from tpch_queries import QUERIES


class Independent:
    """An account of its own: backend-compile events per thread."""

    def __init__(self):
        self.by_thread = {}
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.on and event.endswith("backend_compile_duration"):
            ident = threading.get_ident()
            self.by_thread[ident] = self.by_thread.get(ident, 0) + 1

    @property
    def total(self):
        return sum(self.by_thread.values())


def _detail(dqr, qid):
    with urllib.request.urlopen(
            f"{dqr.coordinator.uri}/v1/query/{qid}", timeout=10) as resp:
        return json.loads(resp.read())


def _forget_programs():
    """Empties every kernel cache, so the next query builds its programs
    whatever this process ran before (xdist puts several files in one)."""
    with kernelcache._LOCK:
        for cache in kernelcache._REGISTRY.values():
            cache.clear()


def _fresh(x):
    # a program no other test built: the constant is part of the HLO
    return jax.jit(lambda a: a * 7919 + x)


def test_a_build_on_a_task_thread_is_charged_to_the_task():
    rec = spans.HostActivity()
    x = jnp.arange(3)                   # its iota is built before
    before = kernelcache.process_xla_stats()
    previous = spans.set_current_activity(rec)
    try:
        _fresh(101)(x)
    finally:
        spans.set_current_activity(previous)
    assert rec.xla["builds"] == 1
    assert rec.xla["build_ns"] > 0 and rec.xla["trace_lower_ns"] > 0
    assert rec.xla["cache_hits"] == 0       # the suite runs uncached
    assert kernelcache.process_xla_stats() == before


def test_a_build_on_no_tasks_thread_goes_to_the_process_total():
    x = jnp.arange(3)
    previous = spans.set_current_activity(None)
    before = kernelcache.process_xla_stats()
    try:
        _fresh(102)(x)
    finally:
        spans.set_current_activity(previous)
    after = kernelcache.process_xla_stats()
    assert after["builds"] == before["builds"] + 1
    assert after["build_ns"] > before["build_ns"]
    assert after["trace_lower_ns"] > before["trace_lower_ns"]


def test_two_threads_charge_their_own_tasks():
    recs = [spans.HostActivity(), spans.HostActivity()]
    go = threading.Barrier(2, timeout=60)
    x = jnp.arange(3)

    def work(i):
        spans.set_current_activity(recs[i])
        go.wait()
        for k in range(i + 1):          # thread 0 builds 1, thread 1: 2
            _fresh(200 + 10 * i + k)(x)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in ts)
    assert [r.xla["builds"] for r in recs] == [1, 2]


def test_process_totals_are_on_the_metrics_plane():
    from presto_tpu.server.dqr import DistributedQueryRunner

    with DistributedQueryRunner.tpch(scale=0.001, n_workers=1) as dqr:
        texts = []
        for uri in (dqr.coordinator.uri, dqr.workers[0].uri):
            with urllib.request.urlopen(f"{uri}/metrics",
                                        timeout=10) as resp:
                texts.append(resp.read().decode())
    assert 'presto_xla_untasked_total{kind="builds"}' in texts[0]
    assert ('presto_worker_xla_untasked_seconds_total'
            '{kind="trace_lower"}') in texts[1]


@pytest.mark.parametrize("number", [1, 3])
def test_cold_query_counts_what_xla_built_and_a_repeat_counts_0(number):
    from presto_tpu.server.dqr import DistributedQueryRunner

    sql = QUERIES[number]
    with DistributedQueryRunner.tpch(scale=0.01, n_workers=2) as dqr:
        client = dqr.new_client()
        _forget_programs()
        mine = Independent()
        try:
            client.execute(sql)
            cold = _detail(dqr, client.last_query_id)["queryStats"]
            built_cold = mine.total
            client.execute(sql)
            warm = _detail(dqr, client.last_query_id)["queryStats"]
            built_warm = mine.total - built_cold
        finally:
            mine.on = False
        untasked = kernelcache.process_xla_stats()["builds"]
    assert cold["xla_builds"] > 0
    # the independent listener also hears the coordinator's own threads
    # (planning builds nothing today; if it ever does, the difference is
    # in the process total, never lost)
    assert cold["xla_builds"] <= built_cold
    assert built_cold - cold["xla_builds"] <= untasked
    assert cold["xla_build_ns"] > 0 and cold["xla_trace_lower_ns"] > 0
    assert built_warm == 0
    assert warm["xla_builds"] == 0 and warm["xla_build_ns"] == 0
    assert warm["xla_trace_lower_ns"] == 0
    # the old account counts kernel-cache misses of three families
    assert cold["jit_compiles"] <= cold["xla_builds"]


def test_concurrent_queries_charge_their_own_tasks():
    from presto_tpu.server.dqr import DistributedQueryRunner

    sqls = [QUERIES[6].replace("from", f", {8100 + i} from", 1)
            for i in range(2)]
    with DistributedQueryRunner.tpch(scale=0.01, n_workers=2) as dqr:
        clients = [dqr.new_client(user=f"u{i}") for i in range(2)]
        warm = dqr.new_client()
        warm.execute(QUERIES[6])
        _forget_programs()      # both queries build, whatever ran before
        mine = Independent()
        errors = []

        def run(i):
            try:
                clients[i].execute(sqls[i])
            except Exception as e:  # noqa: BLE001 - asserted below
                errors.append(e)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        mine.on = False
        assert not errors and not any(t.is_alive() for t in ts)
        stats = [_detail(dqr, c.last_query_id)["queryStats"]
                 for c in clients]
    # every build was made for one of the two queries, and each query's
    # account holds its own: together they are what XLA built
    assert sum(s["xla_builds"] for s in stats) == mine.total
    assert all(s["xla_builds"] > 0 for s in stats)
