"""Live query telemetry (PR 9): in-flight progress sampling, the timed
span tree, compile-time attribution, the slow-query log, and the
latency-histogram /metrics plane.

The acceptance pins:

- a mid-query poll OBSERVES progress: with a fault-injected slow task
  holding the root drain, /v1/query/{id}/timeseries and the
  client-protocol ``stats`` object show monotonically increasing
  completed-split/row counts while the query is still RUNNING;
- ``stats_sampling_enabled=false`` restores PR 8's single post-drain
  collection exactly (no samples, no progress object, rollup only
  after the drain);
- the span tree round-trips: /v1/query/{id}/spans and the query.json
  QueryCompletedEvent carry the same tree, every stage/task span nests
  inside the query span with end >= start;
- EXPLAIN ANALYZE (both tiers) shows the compile-vs-execute split and
  the hot-operator footer.
"""

import json
import logging
import re
import threading
import time
import urllib.request

import pytest

from presto_tpu.config import EngineConfig
from presto_tpu.server.faults import FaultInjector


def _fetch(uri: str):
    with urllib.request.urlopen(uri, timeout=10) as resp:
        return json.loads(resp.read())


def _cfg(**kw) -> EngineConfig:
    return EngineConfig(**kw)


def _run_async(client, sql):
    out = {}

    def run():
        try:
            out["rows"] = client.execute(sql)[1]
        except Exception as e:  # noqa: BLE001
            out["err"] = e

    t = threading.Thread(target=run)
    t.start()
    return t, out


GROUP_SQL = ("select l_returnflag, count(*), sum(l_extendedprice) "
             "from lineitem group by l_returnflag")


class TestLiveSampling:
    def test_midquery_poll_observes_progress(self):
        """The headline acceptance: >= 2 RUNNING samples with
        monotonically increasing completed-split and row counts, both
        on the timeseries endpoint and the client-protocol stats
        object, BEFORE the query finishes."""
        inj = FaultInjector()
        # hold the root task's result drain: leaves finish over time,
        # the root finishes producing, but the drain cannot complete —
        # the query stays RUNNING while real progress accumulates
        rule = inj.add_slow_task(r"\.1\.0")
        from presto_tpu.server.dqr import DistributedQueryRunner

        cfg = _cfg(stats_sample_interval_s=0.05)
        with DistributedQueryRunner.tpch(
                scale=0.01, n_workers=2, config=cfg,
                worker_injectors={0: inj, 1: inj}) as dqr:
            client = dqr.new_client()
            t, out = _run_async(client, GROUP_SQL)
            co_uri = dqr.coordinator.uri
            polls = []
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                qid = client.last_query_id
                if qid:
                    ts = _fetch(f"{co_uri}/v1/query/{qid}/timeseries")
                    if ts["state"] not in ("RUNNING",):
                        if ts["state"] in ("FINISHED", "FAILED"):
                            break
                    polls.append(ts)
                    running = [s for s in ts["samples"]
                               if s["state"] == "RUNNING"]
                    # stop once progress moved while still RUNNING and
                    # the CLIENT's own polling also caught a RUNNING
                    # progress object — releasing the hold on the
                    # timeseries condition alone raced the client's
                    # poll cadence under full-suite load (the client
                    # thread may not have landed a progress-carrying
                    # poll yet).  The first samples may come before a
                    # leaf has emitted a row, so both wait for rows too
                    client_saw = any(
                        s.get("state") == "RUNNING"
                        and "completedSplits" in s
                        and s.get("processedRows", 0) > 0
                        for s in client.stats_history)
                    if (len(running) >= 2
                            and running[-1]["splits_completed"]
                            > running[0]["splits_completed"]
                            and running[-1]["output_rows"] > 0
                            and client_saw):
                        break
                time.sleep(0.05)
            rule.release()
            t.join(timeout=30)
            assert "err" not in out, out.get("err")
            assert polls, "no mid-query timeseries polls landed"
            samples = polls[-1]["samples"]
            running = [s for s in samples if s["state"] == "RUNNING"]
            # >= 2 samples observed while the query was RUNNING
            assert len(running) >= 2
            completed = [s["splits_completed"] for s in running]
            rows = [s["output_rows"] for s in running]
            # monotonic non-decreasing, strictly increasing overall
            assert completed == sorted(completed)
            assert rows == sorted(rows)
            assert completed[-1] > completed[0]
            # rows reached the samples while RUNNING (the first samples
            # may predate every leaf's first row, and read 0)
            assert rows[-1] >= rows[0] and rows[-1] > 0
            assert all(s["splits_total"] == 3 for s in running)
            # the client-protocol stats object carried the same
            # progress shape mid-query (StatementStats role)
            live = [s for s in client.stats_history
                    if s.get("state") == "RUNNING"
                    and "completedSplits" in s]
            assert live, "no RUNNING poll carried split accounting"
            assert live[-1]["totalSplits"] == 3
            assert live[-1]["processedRows"] > 0
            assert 0.0 <= live[-1]["progressPercent"] <= 100.0
            # the final payload reports 100% with every split done
            done = client.stats_history[-1]
            assert done["state"] == "FINISHED"
            assert done["completedSplits"] == done["totalSplits"] == 3
            assert done["progressPercent"] == 100.0

    def test_sampling_disabled_restores_single_collection(self):
        """stats_sampling_enabled=false: NO samples, NO progress object
        on any poll, and the stage rollup appears only after the drain
        — PR 8's single post-drain collection, exactly."""
        inj = FaultInjector()
        rule = inj.add_slow_task(r"\.1\.0")
        from presto_tpu.server.dqr import DistributedQueryRunner

        cfg = _cfg(stats_sampling_enabled=False)
        with DistributedQueryRunner.tpch(
                scale=0.01, n_workers=2, config=cfg,
                worker_injectors={0: inj, 1: inj}) as dqr:
            client = dqr.new_client()
            t, out = _run_async(client, GROUP_SQL)
            co_uri = dqr.coordinator.uri
            saw_running = False
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                qid = client.last_query_id
                if qid:
                    detail = _fetch(f"{co_uri}/v1/query/{qid}")
                    if detail["state"] == "RUNNING":
                        saw_running = True
                        # mid-query: no sampler, so no rollup yet
                        assert detail["stageStats"] == {}
                        assert detail["progress"] == {}
                        ts = _fetch(
                            f"{co_uri}/v1/query/{qid}/timeseries")
                        assert ts["samples"] == []
                        break
                time.sleep(0.05)
            rule.release()
            t.join(timeout=30)
            assert "err" not in out, out.get("err")
            assert saw_running, "never observed the query RUNNING"
            qid = client.last_query_id
            ts = _fetch(f"{co_uri}/v1/query/{qid}/timeseries")
            assert ts["samples"] == []   # still none after the drain
            # the post-drain collection still fed the rollup surfaces
            detail = _fetch(f"{co_uri}/v1/query/{qid}")
            assert detail["stageStats"]
            # and no client poll ever carried split accounting
            assert all("completedSplits" not in s
                       for s in client.stats_history)

    def test_runtime_tasks_live_midquery(self):
        """Satellite regression: a mid-query SELECT over
        system.runtime.tasks sees current (monotonically non-decreasing,
        non-zero) rows fed from the live sampler, not a frozen
        post-drain rollup."""
        inj = FaultInjector()
        rule = inj.add_slow_task(r"\.1\.0")
        from presto_tpu.server.dqr import DistributedQueryRunner

        cfg = _cfg(stats_sample_interval_s=0.05)
        with DistributedQueryRunner.tpch(
                scale=0.01, n_workers=2, config=cfg,
                worker_injectors={0: inj, 1: inj}) as dqr:
            client = dqr.new_client()
            t, out = _run_async(client, GROUP_SQL)
            poller = dqr.new_client()
            polls = []
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                qid = client.last_query_id
                if not qid:
                    time.sleep(0.02)
                    continue
                _, data = poller.execute(
                    "select task_id, state, output_rows, elapsed_s "
                    "from system.runtime.tasks")
                mine = [r for r in data if r[0].startswith(qid + ".")]
                state = _fetch(f"{dqr.coordinator.uri}/v1/query/{qid}"
                               )["state"]
                if state != "RUNNING":
                    if state in ("FINISHED", "FAILED"):
                        break
                    continue
                if mine:
                    polls.append(mine)
                    # the first polls may come before a leaf has emitted
                    # a row: poll on until the last two show rows
                    if len(polls) >= 3 and sum(r[2] for r in polls[-2]) > 0:
                        break
                time.sleep(0.1)
            rule.release()
            t.join(timeout=30)
            assert "err" not in out, out.get("err")
            assert len(polls) >= 2, "needed >= 2 mid-query polls"
            totals = [sum(r[2] for r in p) for p in polls]
            # non-zero in the last two polls, and monotonic
            # non-decreasing across polls
            assert totals[-2] > 0
            assert totals == sorted(totals)
            # elapsed_s reported and growing for the held root task
            elapsed = [max(r[3] for r in p) for p in polls]
            assert elapsed[-1] >= elapsed[0] > 0

    def test_runtime_queries_progress_columns(self):
        from presto_tpu.server.dqr import DistributedQueryRunner

        with DistributedQueryRunner.tpch(scale=0.002,
                                         n_workers=2) as dqr:
            dqr.execute("select count(*) from lineitem")
            data = dqr.execute(
                "select query_id, state, completed_splits, "
                "total_splits, progress_percent "
                "from system.runtime.queries "
                "where state = 'FINISHED'").rows
            assert data
            # finished queries report full split accounting
            assert any(r[2] == r[3] and r[3] > 0 and r[4] == 100.0
                       for r in data)


def _without_activities(node):
    return dict(node, children=[_without_activities(c)
                                for c in node["children"]
                                if c["kind"] != "activity"])


class TestSpans:
    def test_span_tree_roundtrips_and_nests(self, tmp_path):
        """/v1/query/{id}/spans == the query.json event's tree; every
        stage/task-attempt span nests inside the query span with
        end >= start; the profile tool replays it."""
        from presto_tpu.server.dqr import DistributedQueryRunner
        from presto_tpu.spans import validate_span_tree

        log = str(tmp_path / "query.json")
        with DistributedQueryRunner.tpch(scale=0.002, n_workers=2,
                                         event_log_path=log) as dqr:
            dqr.execute(GROUP_SQL)
            q = list(dqr.coordinator.queries.values())[-1]
            tree = _fetch(
                f"{dqr.coordinator.uri}/v1/query/{q.query_id}/spans")
        events = [json.loads(line) for line in
                  open(log, encoding="utf-8")]
        completed = [e for e in events
                     if e["event"] == "QueryCompletedEvent"]
        assert completed and completed[-1]["spans"]
        # round-trip: the event carries the SAME tree the endpoint
        # served (both JSON round-trips of one build), less the host
        # activity intervals, whose totals it keeps (hostSeconds)
        assert completed[-1]["spans"] == _without_activities(tree)
        assert validate_span_tree(tree) == []
        kinds = {c["kind"] for c in tree["children"]}
        assert {"phase", "stage"} <= kinds
        names = {c["name"] for c in tree["children"]}
        # coordinator phases recorded from its own timestamps
        assert {"parse", "analyze", "optimize", "fragment",
                "schedule", "execute"} <= names
        stages = [c for c in tree["children"] if c["kind"] == "stage"]
        assert len(stages) == 2   # leaf + final agg fragments
        for st in stages:
            assert st["children"], "stage span without task spans"
            for task in st["children"]:
                assert task["kind"] == "task"
                assert task["end"] >= task["start"]
                assert task["attributes"]["attempt"] == 0
        # every span carries the query's trace token as trace id
        assert tree["traceToken"] == q.trace_token
        assert all(c["traceToken"] == q.trace_token
                   for c in tree["children"])

    def test_distributed_explain_analyze_compile_split(self):
        """EXPLAIN ANALYZE shows compile vs execute per operator plus
        the top-5 hot-operator footer (acceptance pin)."""
        from presto_tpu.server.dqr import DistributedQueryRunner

        with DistributedQueryRunner.tpch(scale=0.002,
                                         n_workers=2) as dqr:
            rows = dqr.execute("explain analyze " + GROUP_SQL).rows
        text = "\n".join(r[0] for r in rows)
        assert "compile ms" in text
        assert "hot operators (top" in text
        assert "by exclusive wall" in text
        assert re.search(r"\d+\.\d+ compile / \d+\.\d+ execute", text)
        assert "ms compile" in text

    def test_local_explain_analyze_compile_split(self):
        from presto_tpu.localrunner import LocalQueryRunner

        runner = LocalQueryRunner.tpch(scale=0.002)
        res = runner.execute("explain analyze " + GROUP_SQL)
        text = "\n".join(r[0] for r in res.rows)
        assert "compile ms" in text
        assert "hot operators (top" in text
        # jit_counters grew the compile_ns attribution
        jc = runner._last_task.jit_counters()
        assert "compile_ns" in jc
        if jc["compiles"] > 0:
            assert jc["compile_ns"] > 0

    def test_kernelcache_records_compile_durations(self):
        """Fresh cache keys force a compile; the named-cache registry
        accumulates per-compile durations (record_compile)."""
        from presto_tpu.kernelcache import cache_stats
        from presto_tpu.localrunner import LocalQueryRunner

        runner = LocalQueryRunner.tpch(scale=0.001)
        runner.execute("select l_orderkey + 4242424242 from lineitem "
                       "where l_partkey > 777777 limit 3")
        stats = cache_stats()
        compiled = [s for s in stats.values() if s["compiles"] > 0]
        assert compiled, "no cache recorded a compile"
        assert any(s["compile_ns"] > 0 for s in compiled)


class TestSlowQueryLog:
    def test_slow_query_event_and_log_line(self, caplog):
        """A query past slow_query_log_threshold_s emits ONE structured
        log line + a SlowQueryEvent with the trace token, the
        queued/execution split, and the top hot operator."""
        from presto_tpu.events import EventListener
        from presto_tpu.server.dqr import DistributedQueryRunner

        class Recorder(EventListener):
            events = []

            def slow_query(self, e):
                self.events.append(e)

        cfg = _cfg(slow_query_log_threshold_s=0.005)
        with DistributedQueryRunner.tpch(scale=0.002, n_workers=2,
                                         config=cfg) as dqr:
            dqr.event_bus.register(Recorder())
            with caplog.at_level(logging.WARNING,
                                 logger="presto_tpu.coordinator"):
                dqr.execute(GROUP_SQL)
                deadline = time.monotonic() + 5.0
                while not Recorder.events \
                        and time.monotonic() < deadline:
                    time.sleep(0.02)
        assert Recorder.events
        e = Recorder.events[-1]
        assert e.trace_token.startswith("tt-")
        assert e.elapsed_s >= e.threshold_s == 0.005
        assert e.execution_s > 0 and e.queued_s >= 0
        assert e.top_operator   # hottest operator named
        lines = [r for r in caplog.records
                 if "slow query" in r.getMessage()]
        assert lines
        msg = lines[-1].getMessage()
        assert e.trace_token in msg and "top_operator=" in msg

    def test_threshold_zero_disables(self):
        from presto_tpu.events import EventListener
        from presto_tpu.server.dqr import DistributedQueryRunner

        class Recorder(EventListener):
            events = []

            def slow_query(self, e):
                self.events.append(e)

        cfg = _cfg(slow_query_log_threshold_s=0.0)
        with DistributedQueryRunner.tpch(scale=0.002, n_workers=2,
                                         config=cfg) as dqr:
            dqr.event_bus.register(Recorder())
            dqr.execute("select count(*) from nation")
            time.sleep(0.2)
        assert Recorder.events == []


def _scrape(uri: str) -> str:
    with urllib.request.urlopen(uri, timeout=10) as resp:
        assert resp.status == 200
        return resp.read().decode()


def _parse_metrics(text: str):
    """{metric name: {frozenset(label keys)}}, {sample line: value}."""
    label_keys = {}
    values = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                     r"(?:\{([^}]*)\})?\s+(\S+)$", line)
        assert m, f"unparseable metrics line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        keys = frozenset(kv.split("=", 1)[0]
                         for kv in labels.split(",") if kv)
        label_keys.setdefault(name, set()).add(keys)
        values[f"{name}{{{labels}}}"] = float(value)
    return label_keys, values


class TestMetricsHistograms:
    def test_latency_histograms_fed_from_dispatcher(self):
        """presto_query_{queued,execution}_seconds histograms: fixed
        buckets, cumulative counts, fed once per dispatched query —
        the scrape-side cross-check for qps_run latencies."""
        from presto_tpu.server.dqr import DistributedQueryRunner

        with DistributedQueryRunner.tpch(scale=0.002,
                                         n_workers=2) as dqr:
            dqr.execute("select count(*) from nation")
            dqr.execute("select count(*) from region")
            text = _scrape(f"{dqr.coordinator.uri}/metrics")
        for fam in ("presto_query_execution_seconds",
                    "presto_query_queued_seconds"):
            assert f"# TYPE {fam} histogram" in text
            counts = re.findall(
                rf'{fam}_bucket{{le="([^"]+)"}} (\d+)', text)
            assert counts and counts[-1][0] == "+Inf"
            # cumulative and capped by _count
            vals = [int(n) for _, n in counts]
            assert vals == sorted(vals)
            count = int(re.search(rf"{fam}_count (\d+)",
                                  text).group(1))
            assert vals[-1] == count
            assert count >= 2
        # executions take real time, queueing was ~instant: sums differ
        ex_sum = float(re.search(
            r"presto_query_execution_seconds_sum (\S+)", text).group(1))
        assert ex_sum > 0

    @pytest.mark.slow
    def test_concurrent_scrape_storm(self):
        """Satellite: a 3-client statement storm while scraping BOTH
        /metrics planes — counters monotonic across scrapes, label
        sets stable, and the scrape never 500s mid-query."""
        from presto_tpu.server.dqr import DistributedQueryRunner

        statements = [
            "select count(*) from lineitem",
            GROUP_SQL,
            "select o_orderpriority, count(*) from orders "
            "group by o_orderpriority",
        ]
        with DistributedQueryRunner.tpch(scale=0.005,
                                         n_workers=2) as dqr:
            results = {}

            def client_loop(i):
                c = dqr.new_client(user=f"storm-{i}")
                try:
                    for _ in range(3):
                        c.execute(statements[i % len(statements)])
                    results[i] = "ok"
                except Exception as e:  # noqa: BLE001
                    results[i] = e

            threads = [threading.Thread(target=client_loop, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            targets = [f"{dqr.coordinator.uri}/metrics"] + \
                [f"{w.uri}/metrics" for w in dqr.workers]
            scrapes = {t: [] for t in targets}
            while any(t.is_alive() for t in threads):
                for target in targets:
                    scrapes[target].append(_scrape(target))
                time.sleep(0.1)
            for t in threads:
                t.join()
            for target in targets:
                scrapes[target].append(_scrape(target))
            assert all(v == "ok" for v in results.values()), results
            monotonic_counters = (
                "presto_query_execution_seconds_count{}",
                "presto_query_queued_seconds_count{}",
                "presto_worker_output_pages_total{}",
                "presto_plan_cache_misses_total{}",
            )
            for target, texts in scrapes.items():
                assert len(texts) >= 2
                prev_keys, prev_vals = {}, {}
                for text in texts:
                    label_keys, values = _parse_metrics(text)
                    for name, keysets in prev_keys.items():
                        cur = label_keys.get(name)
                        if cur is None:
                            continue
                        # label KEY sets stay stable per family: every
                        # sample of one family uses one key set, and it
                        # never mutates across scrapes
                        assert keysets == cur, \
                            f"{target}: {name} label keys changed " \
                            f"{keysets} -> {cur}"
                    # counters the storm drives are monotonic
                    for c in monotonic_counters:
                        if c in values and c in prev_vals:
                            assert values[c] >= prev_vals[c], \
                                f"{target}: {c} regressed"
                    prev_keys = {n: set(k) for n, k
                                 in label_keys.items()}
                    prev_vals = values
