"""Verifier + benchmark-driver tools (presto-verifier /
presto-benchmark-driver roles)."""

import pytest

from presto_tpu.localrunner import LocalQueryRunner
from presto_tpu.verifier import Verifier


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch(scale=0.001)


class TestVerifier:
    def test_match(self, runner):
        other = LocalQueryRunner.tpch(scale=0.001)
        v = Verifier(control=runner, test=other)
        results = v.verify([
            "select count(*) from nation",
            "select r_name, count(*) from region, nation "
            "where r_regionkey = n_regionkey group by r_name",
        ])
        assert all(r.status == "MATCH" for r in results)
        assert "MATCH=2" in Verifier.summarize(results)

    def test_mismatch_detected(self, runner):
        class Wrong:
            def execute(self, sql):
                res = runner.execute(sql)
                import dataclasses as d

                return d.replace(res, rows=res.rows[:-1])

        v = Verifier(control=runner, test=Wrong())
        (r,) = v.verify(["select n_name from nation"])
        assert r.status == "MISMATCH"
        assert "row counts differ" in r.detail

    def test_failure_classified(self, runner):
        class Broken:
            def execute(self, sql):
                raise RuntimeError("boom")

        (r,) = Verifier(runner, Broken()).verify(["select 1"])
        assert r.status == "TEST_FAILED"

    def test_float_tolerance(self, runner):
        class Jittered:
            def execute(self, sql):
                res = runner.execute(sql)
                import dataclasses as d

                rows = [tuple(v + 1e-11 if isinstance(v, float) else v
                              for v in row) for row in res.rows]
                return d.replace(res, rows=rows)

        v = Verifier(runner, Jittered())
        (r,) = v.verify(["select sum(l_quantity) from lineitem"])
        assert r.status == "MATCH"


class TestBenchmarkDriver:
    def test_run_suite(self, runner):
        from presto_tpu.benchmark_driver import load_suite, run_suite

        queries = {k: v for k, v in load_suite("tpch").items()
                   if k in ("q1", "q6")}
        results = run_suite(runner, queries, runs=1, warmup=0)
        assert [r.name for r in results] == ["q1", "q6"]
        assert all(r.median_s > 0 for r in results)
        assert results[0].rows == 4  # Q1 groups

    def test_suite_loading(self):
        from presto_tpu.benchmark_driver import load_suite

        assert len(load_suite("tpch")) == 22
        assert "q72" in load_suite("tpcds")


class TestPlanDiff:
    def test_memo_vs_greedy_diff(self, capsys):
        """tools/plan_diff.py prints both plan shapes with cost
        estimates and reports the memo plan no costlier than greedy."""
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        plan_diff = importlib.import_module("plan_diff")
        rc = plan_diff.main(["q3", "--scale", "0.001"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "=== memo-on ===" in out
        assert "=== memo-off (greedy) ===" in out
        assert "estimated cost" in out
        assert "WARNING" not in out    # memo never costlier than greedy

    def test_query_name_parsing(self):
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        plan_diff = importlib.import_module("plan_diff")
        catalog, sql = plan_diff.load_query("tpcds/q72")
        assert catalog == "tpcds" and "inventory" in sql
        catalog, _ = plan_diff.load_query("q9")
        assert catalog == "tpch"


class TestExchangeReport:
    def test_boundary_modes_and_q3_collective_check(self, capsys):
        """tools/exchange_report.py renders one row per fragment
        boundary with its exchange mode, and --check pins TPC-H Q3's
        boundaries lowering to the collective tier."""
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        exchange_report = importlib.import_module("exchange_report")
        rc = exchange_report.main(["q3", "q6", "--scale", "0.002",
                                   "--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "data plane: collective" in out
        assert "hash" in out and "single" in out

    def test_live_per_shard_bytes_and_q3_pin(self, capsys):
        """--live executes on a real mesh and reports per-boundary
        rows/bytes from the program's per-shard telemetry; --check pins
        TPC-H Q3 reporting nonzero device-boundary bytes on EVERY
        collective boundary."""
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        exchange_report = importlib.import_module("exchange_report")
        rc = exchange_report.main(["q3", "--scale", "0.002", "--live",
                                   "--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "live mesh: 2 shards" in out
        assert "bytes/shard" in out
        assert "all_to_all" in out and "gather" in out
        # every rendered boundary row carries a nonzero byte total
        for ln in out.splitlines():
            if ln.strip().startswith("f") and "all_" in ln:
                assert ln.split()[-1].isdigit()

    def test_segments_column_names_boundary_roles(self, capsys):
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        exchange_report = importlib.import_module("exchange_report")
        rc = exchange_report.main(["q3", "--scale", "0.002",
                                   "--segments"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "fed-by-exchange" in out or "feeds-exchange" in out


class TestQpsRun:
    def test_check_mode(self, capsys):
        """tools/qps_run.py --check: the serving-tier CI smoke — a tiny
        closed-loop run at 2 concurrency levels against a live 2-worker
        DQR asserting per-client exact-rows parity, nonzero plan-cache
        hits, and zero jit compiles on the second execution of a cached
        plan — then a hot-repeat run with the result cache on asserting
        nonzero result-cache hits with exact rows."""
        import importlib
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        qps_run = importlib.import_module("qps_run")
        rc = qps_run.main(["--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        payload = json.loads(out.strip().splitlines()[-1])
        assert payload["check"] == {
            "parity": True, "plan_cache_hits": True,
            "zero_second_run_compiles": True,
            "second_run_plan_cached": True,
            "hot_parity": True, "result_cache_hits": True,
            "result_cache_bytes_served": True,
            "hot_second_run_result_cached": True}
        levels = payload["report"]["levels"]
        assert [lv["concurrency"] for lv in levels] == [1, 2]
        for lv in levels:
            assert lv["qps"] > 0 and lv["p99_ms"] >= lv["p50_ms"]
        # the hot tier really served from the cache
        hot = payload["hot_report"]
        assert hot["result_cache_hit_rate"] > 0.0
        assert hot["result_cache_bytes_served"] > 0


class TestQueryProfile:
    def test_live_profile_check_mode(self, capsys, tmp_path):
        """tools/query_profile.py --check: runs a statement on a real
        2-worker DQR and renders the per-stage stats table + task span
        timeline from the coordinator's rollup."""
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        query_profile = importlib.import_module("query_profile")
        log = str(tmp_path / "query.json")
        rc = query_profile.main(
            ["--scale", "0.002", "--check", "--live",
             "--event-log", log])
        out = capsys.readouterr().out
        assert rc == 0, out
        # the timed span tree replaced the ad-hoc task reconstruction:
        # coordinator phases + per-stage spans render in the timeline
        assert "span timeline" in out
        assert "schedule" in out and "execute" in out
        assert "stage-0" in out
        assert "profile rollup complete" in out
        assert "trace=tt-" in out
        # stage table rendered both fragments with real rows
        assert "xchg f/c/p" in out
        # --live followed the timeseries endpoint
        assert "time series (" in out
        assert "splits q/r/c" in out

        # replay mode renders the log the live run just wrote,
        # including the span tree carried on QueryCompletedEvent
        rc = query_profile.main(["--replay", log])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "QueryCreatedEvent" in out
        assert "QueryCompletedEvent" in out
        assert "stage stats for" in out
        assert "spans for" in out


class TestPerfRegress:
    """tools/perf_regress.py: the bench trajectory as an enforced gate."""

    def _tool(self):
        import importlib
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        return importlib.import_module("perf_regress")

    def _artifact(self, path, headline, extras=()):
        import json

        doc = {"metric": "tpch_sf0.1_q1_rows_per_sec_per_chip",
               "value": headline, "unit": "rows/s",
               "extras": [{"metric": m, "value": v, "unit": "rows/s"}
                          for m, v in extras]}
        path.write_text(json.dumps(doc))
        return str(path)

    def test_committed_pr7_pr8_pair_passes(self, capsys):
        """The acceptance pin: the committed BENCH_PR7 -> BENCH_PR8
        artifact pair is within tolerance (worst matched config is the
        -3.4%% headline), so --check exits 0."""
        import os

        root = os.path.join(os.path.dirname(__file__), "..")
        rc = self._tool().main(
            ["--check",
             os.path.join(root, "BENCH_PR7_20260805.json"),
             os.path.join(root, "BENCH_PR8_20260805.json")])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "no regressions past tolerance" in out
        # configs matched by name, per-config delta reported
        assert "tpch_sf0.1_q1_rows_per_sec_per_chip" in out
        assert "OK" in out

    def test_committed_pr9_pr10_pair_passes(self, capsys):
        """The PR 10 acceptance gate: the committed BENCH_PR9 -> PR10
        pair is green — the engine Q1 config improved >= 2x (the
        device-resident hash tier + scan-dictionary interning), the new
        join-heavy bench_engine_q3q9 config reports NEW (tracked from
        here on), and no matched config regressed past tolerance."""
        import json
        import os

        root = os.path.join(os.path.dirname(__file__), "..")
        rc = self._tool().main(
            ["--check",
             os.path.join(root, "BENCH_PR9_20260805.json"),
             os.path.join(root, "BENCH_PR10_20260805.json")])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "no regressions past tolerance" in out
        assert "tpch_sf0.05_q3_engine_rows_per_sec" in out   # NEW config
        with open(os.path.join(root, "BENCH_PR9_20260805.json")) as f:
            old = json.load(f)
        with open(os.path.join(root, "BENCH_PR10_20260805.json")) as f:
            new = json.load(f)

        def metric(doc, name):
            for e in doc["extras"]:
                if e.get("metric") == name:
                    return e
            return None

        o = metric(old, "tpch_sf0.05_q1_engine_rows_per_sec")
        n = metric(new, "tpch_sf0.05_q1_engine_rows_per_sec")
        assert n["value"] >= 2 * o["value"], (o["value"], n["value"])
        assert n["parity"] is True
        q3q9 = metric(new, "tpch_sf0.05_q3_engine_rows_per_sec")
        assert q3q9 is not None and q3q9["parity"] is True

    def test_injected_regression_fails_check(self, capsys, tmp_path):
        """A synthetic 2x regression on a matched config must fail
        --check; unmatched configs (NEW/DROPPED) never gate."""
        old = self._artifact(tmp_path / "old.json", 1_000_000.0,
                             [("mesh_q1", 300_000.0),
                              ("dropped_only", 42.0)])
        new = self._artifact(tmp_path / "new.json", 980_000.0,
                             [("mesh_q1", 150_000.0),   # 2x regression
                              ("new_only", 7.0)])
        rc = self._tool().main(["--check", old, new])
        out = capsys.readouterr().out
        assert rc == 1, out
        assert "REGRESSED" in out and "mesh_q1" in out
        assert "REGRESSION: 1 config(s)" in out
        assert "NEW" in out and "DROPPED" in out

    def test_within_tolerance_pair_passes(self, capsys, tmp_path):
        old = self._artifact(tmp_path / "a.json", 1_000_000.0,
                             [("mesh_q1", 300_000.0)])
        new = self._artifact(tmp_path / "b.json", 950_000.0,
                             [("mesh_q1", 295_000.0)])
        rc = self._tool().main(["--check", old, new])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "no regressions past tolerance" in out

    def test_tolerance_flag(self, capsys, tmp_path):
        """--tolerance tightens the band: a -5%% drop fails at 2%%."""
        old = self._artifact(tmp_path / "a.json", 1_000_000.0)
        new = self._artifact(tmp_path / "b.json", 950_000.0)
        rc = self._tool().main(["--check", "--tolerance", "0.02",
                                old, new])
        assert rc == 1
        capsys.readouterr()


class TestChaosRunHA:
    def test_ha_check_mode(self, capsys):
        """tools/chaos_run.py --mode ha --check: the coordinator-HA CI
        smoke — kill the PRIMARY COORDINATOR mid-drain of a TPC-DS Q72
        run on a 2-worker HA mesh, headless; nonzero on inexact rows
        through the standby or on any producer re-run for stages
        already complete in the spool."""
        import importlib
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        chaos_run = importlib.import_module("chaos_run")
        rc = chaos_run.main(["--mode", "ha", "--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        report = json.loads(out[out.index("{\n"):])
        assert report["mode"] == "ha"
        assert report["phases"] == ["RUNNING"]
        assert report["total_producer_reruns"] == 0
        stage = report["stages"][0]
        assert stage["ok"] and stage["failovers"] == 1
        assert stage["adopted_outcome"] in ("reattached", "repointed",
                                            "restarted")


class TestChaosRunOom:
    def test_oom_check_mode(self, capsys):
        """tools/chaos_run.py --mode oom --check: the memory-arbitration
        CI smoke — a runaway query parks holding ~94% of an 8 MiB worker
        pool, survivors block on the pool, and the low-memory killer
        must fail EXACTLY the runaway with the CLUSTER_OUT_OF_MEMORY
        shape; survivors return exact rows, pools drain to zero, and
        both workers stay alive."""
        import importlib
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        chaos_run = importlib.import_module("chaos_run")
        rc = chaos_run.main(["--mode", "oom", "--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        report = json.loads(out[out.index("{\n"):])
        assert report["mode"] == "oom"
        assert report["ok"]
        stages = {s["stage"]: s for s in report["stages"]}
        assert set(stages) == {"runaway-resident", "kill", "survivors",
                               "recovery"}
        kill = stages["kill"]
        assert kill["errorName"] == "CLUSTER_OUT_OF_MEMORY"
        assert kill["errorType"] == "INSUFFICIENT_RESOURCES"
        assert kill["errorCode"] == 0x0002_0004
        # exactly one policy-selected kill, attributed to the default
        # policy — nothing else died
        assert kill["kill_counters"] == {
            "total-reservation-on-blocked-nodes": 1}
        rec = stages["recovery"]
        assert rec["alive"] == 2
        assert rec["pool_reserved_after"] == 0


class TestQpsRunOverload:
    def test_open_loop_check_mode(self, capsys):
        """tools/qps_run.py --open-loop --check: the graceful-degradation
        CI smoke — an open-loop arrival sweep at 1x and 2x the measured
        saturated rate against a bounded-pool dispatcher; past
        saturation every rejection must be the hinted queue-full shape
        (zero unshaped failures) and goodput must hold >= 80% of the
        closed-loop peak."""
        import importlib
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        qps_run = importlib.import_module("qps_run")
        rc = qps_run.main(["--open-loop", "--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        report = json.loads(out[out.index("{\n"):])
        assert report["mode"] == "overload"
        assert report["ok"]
        assert report["peak_parity"]
        assert report["dispatcher"] == {"pool_size": 2, "max_queued": 4}
        top = report["levels"][-1]
        assert top["rate_factor"] == 2.0
        assert top["shed"] > 0            # overload actually shed
        assert all(lv["other"] == 0 for lv in report["levels"])
        assert report["shed_total"] >= top["shed"]
        assert report["goodput_ratio_at_max"] >= 0.8
        # sheds are FAST rejections, not queue waits
        assert top["shed_p95_ms"] < 1000.0


class TestChaosRunMesh:
    def test_mesh_check_mode(self, capsys):
        """tools/chaos_run.py --mode mesh --check: the mid-program
        fault-tolerance CI smoke — inject a device-plane fault at EVERY
        checkpoint group of a TPC-H Q3 collective run in turn,
        headless; nonzero on inexact rows, a fault that never fired, a
        kill that never resumed, or ANY re-execution of a checkpointed
        fragment (re-lowered into the resumed program or re-tasked on
        the HTTP plane)."""
        import importlib
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        chaos_run = importlib.import_module("chaos_run")
        rc = chaos_run.main(["--mode", "mesh", "--check"])
        out = capsys.readouterr().out
        assert rc == 0, out
        report = json.loads(out[out.index("{\n"):])
        assert report["mode"] == "mesh"
        assert report["resume_mode"] == "device"
        assert report["ok"]
        assert len(report["stages"]) >= 2
        assert report["total_resumes"] >= len(report["stages"])
        for stage in report["stages"]:
            assert stage["ok"], stage
            assert stage["injections"] >= 1
            assert stage["resumes"] >= 1
            assert stage["resume_modes"] == ["device"]
