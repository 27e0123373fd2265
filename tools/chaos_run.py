#!/usr/bin/env python
"""Chaos smoke run: boot a real in-process cluster, kill a worker
mid-query, assert mid-query task recovery still returns correct rows.

The CLI face of the tests/test_chaos.py tier — run it standalone to
sanity-check the fault-tolerance layer on a box (CI or dev) without the
pytest harness:

    JAX_PLATFORMS=cpu python tools/chaos_run.py --workers 3 --scale 0.01
    JAX_PLATFORMS=cpu python tools/chaos_run.py --mode stage
    JAX_PLATFORMS=cpu python tools/chaos_run.py --mode mesh --check
    JAX_PLATFORMS=cpu python tools/chaos_run.py --check

``--mode leaf`` (default) kills a worker holding leaf tasks; ``--mode
stage`` runs a broadcast-join plan and kills the worker holding the
NON-leaf probe fragment, proving whole-stage retry.  ``--check`` is the
CI smoke tier: it runs the whole ``chaos`` pytest marker headless and
exits nonzero on any inexact result.

Exit code 0 = recovery reproduced the clean run exactly; non-zero =
recovery failed.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

# runnable from anywhere: `python tools/chaos_run.py` puts tools/ on the
# path, not the repo root
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
if "--mode" in sys.argv and "mesh" in sys.argv and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the mesh sweep needs >1 virtual device for real collectives; only
    # effective before jax is imported (standalone CLI use — the test
    # suite already forces an 8-device host platform)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                                 "count=8").strip()


def run_spool_sweep(scale: float = 0.003, spooling: bool = True,
                    query_num: int = 72, fragments=None,
                    spool_path=None, quiet: bool = False) -> dict:
    """Kill-every-stage-in-turn sweep of a TPC-DS query on the 2-worker
    mesh (the spooled-exchange acceptance proof): for each fragment of
    the plan, run the query with the root drain held, kill the worker
    hosting that fragment's first task while the query is in flight,
    and record rows-exactness + producer re-runs.

    ``spooling=True`` must recover every stage with ZERO producer
    re-runs (output re-pulled from the spool); ``spooling=False``
    restores the PR 5 cascading behavior (non-leaf kills re-run the
    producer subtree)."""
    import dataclasses as _dc
    import tempfile
    import threading as _th

    from presto_tpu.config import DEFAULT
    from presto_tpu.connectors.api import ConnectorRegistry
    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.server.faults import FaultInjector
    from tests.tpcds_queries import QUERIES

    sql = QUERIES[query_num]
    reg = ConnectorRegistry()
    reg.register("tpcds", TpcdsConnector(scale=scale))
    want = sorted(LocalQueryRunner(reg, "tpcds").execute(sql).rows)
    cfg = _dc.replace(
        DEFAULT, task_recovery_interval_s=0.05,
        exchange_spooling_enabled=spooling,
        exchange_spool_path=(spool_path or os.path.join(
            tempfile.mkdtemp(prefix="spool-sweep-"), "spool")))
    # every fragment of the plan, killed in turn
    if fragments is None:
        from presto_tpu.server.fragmenter import Fragmenter
        from presto_tpu.sql.optimizer import optimize
        from presto_tpu.sql.parser import parse_statement
        from presto_tpu.sql.planner import Metadata, Planner

        md = Metadata(reg, "tpcds")
        plan = optimize(Planner(md).plan(parse_statement(sql)), md, cfg)
        fragments = [f.fragment_id for f in Fragmenter(
            metadata=md, config=cfg).fragment(plan).fragments]
    stages = []
    for fid in fragments:
        t0 = time.monotonic()
        co_inj = FaultInjector()
        hold = co_inj.add_rule(r"/results/", method="GET",
                               policy="slow-task")
        res = {}
        with DistributedQueryRunner.tpcds(
                scale=scale, n_workers=2, config=cfg,
                coordinator_injector=co_inj,
                heartbeat_interval_s=0.05,
                heartbeat_max_missed=2) as dqr:
            co = dqr.coordinator
            while len(co.nodes.alive_nodes()) != 2:
                time.sleep(0.02)

            def run():
                try:
                    res["rows"] = dqr.execute(sql).rows
                except Exception as e:  # noqa: BLE001
                    res["err"] = str(e)

            t = _th.Thread(target=run)
            t.start()
            # the victim is whichever worker hosts {fid}.0; the held
            # drain guarantees the query is still in flight at the kill
            victim_uri = None
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                qs = list(co.queries.values())
                if qs:
                    hit = [u for f, tid, u in qs[0]._placements
                           if f == fid and tid.endswith(f".{fid}.0")]
                    if hit:
                        victim_uri = hit[0]
                        break
                time.sleep(0.01)
            q = list(co.queries.values())[0]
            victim_idx = next(i for i, w in enumerate(dqr.workers)
                              if w.uri == victim_uri)
            dqr.kill_worker(victim_idx)
            # keep the drain held until the recovery monitor actually
            # handled the dead worker, so every stage kill exercises
            # recovery (not a lucky drain-first finish)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and \
                    victim_uri not in q._recovered_uris:
                time.sleep(0.02)
            hold.release()
            t.join(timeout=300)
            stage = {
                "fragment": fid, "killed_worker": victim_uri,
                "wall_s": round(time.monotonic() - t0, 2),
                "producer_reruns": q.producer_reruns_total,
                "stage_retry_rounds": q.stage_retry_rounds,
                "recovery_rounds": q.recovery_rounds,
                "spool_repoints": len(q._spool_moves) + sum(
                    1 for _, _, u in q._placements
                    if str(u).startswith("spool://")),
            }
            if t.is_alive():
                stage["ok"] = False
                stage["reason"] = "query hung"
            elif "err" in res:
                stage["ok"] = False
                stage["reason"] = res["err"][:300]
            elif sorted(res["rows"]) != want:
                stage["ok"] = False
                stage["reason"] = "row mismatch"
            elif q.recovery_rounds < 1:
                stage["ok"] = False
                stage["reason"] = "kill never triggered recovery"
            else:
                stage["ok"] = True
            stages.append(stage)
            if not quiet:
                print(json.dumps(stage))
    total_reruns = sum(s["producer_reruns"] for s in stages)
    report = {
        "mode": "spool", "query": f"tpcds q{query_num}",
        "scale": scale, "spooling": spooling,
        "stages": stages,
        "total_producer_reruns": total_reruns,
        "ok": all(s["ok"] for s in stages) and (
            total_reruns == 0 if spooling else True),
    }
    return report


def run_mesh_sweep(scale: float = 0.01, query_num: int = 3,
                   resume_mode: str = "device",
                   quiet: bool = False, smoke: bool = False) -> dict:
    """Kill-every-fragment sweep of the COLLECTIVE data plane (the
    boundary-checkpoint acceptance proof): run a TPC-H query on the
    2-worker mesh with ``mesh_checkpoint_boundaries`` on, inject a
    device-plane fault at every checkpoint group in turn, and record
    rows-exactness + resumes + re-lowered fragments per kill point.

    ``resume_mode='device'`` must recover every kill by re-running ONLY
    the remaining checkpoint groups (checkpointed fragments never
    re-lowered); ``resume_mode='http'`` must degrade to the HTTP plane
    scheduling ONLY the remaining fragments (checkpointed producers
    served as spool:// leaves, zero tasks for them)."""
    import dataclasses as _dc
    import tempfile

    from presto_tpu.config import DEFAULT
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.parallel import sqlmesh
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.server.faults import FaultInjector
    from tests.tpch_queries import QUERIES

    sql = QUERIES[query_num]
    want = sorted(LocalQueryRunner.tpch(scale=scale).execute(sql).rows)
    cfg = _dc.replace(
        DEFAULT, mesh_device_exchange=True,
        mesh_checkpoint_boundaries=True,
        mesh_resume_mode=resume_mode,
        exchange_spooling_enabled=True,
        exchange_spool_path=os.path.join(
            tempfile.mkdtemp(prefix="mesh-sweep-"), "spool"))
    # ONE cluster for the whole sweep: checkpointed executions never
    # share programs across queries, device rules are one-shot, and a
    # degrade is not sticky on the cached plan — so each kill point is
    # an independent execution on the same booted mesh (a fresh boot
    # per stage would only re-pay data gen + worker startup)
    inj = FaultInjector()
    stages = []
    with DistributedQueryRunner.tpch(scale=scale, n_workers=2,
                                     config=cfg,
                                     coordinator_injector=inj) as dqr:
        # clean run: ground truth on the mesh + the kill matrix (every
        # fragment the checkpointed execution lowers is one kill point)
        rows = sorted(dqr.execute(sql).rows)
        q0 = list(dqr.coordinator.queries.values())[-1]
        info0 = dict(q0.device_exchange_info or {})
        if rows != want:
            return {"mode": "mesh", "resume_mode": resume_mode,
                    "ok": False,
                    "reason": "clean mesh run mismatched the local "
                              "engine"}
        kill_fids = sorted(info0.get("fragments_lowered") or [])
        if not kill_fids or not info0.get("checkpoints"):
            return {"mode": "mesh", "resume_mode": resume_mode,
                    "ok": False,
                    "reason": "checkpointed collective tier never "
                              "engaged",
                    "info": info0}
        if smoke and len(kill_fids) > 3:
            # CI smoke (--check): first group (no checkpoints yet), a
            # mid-DAG boundary, and the root group — the ha-mode
            # precedent (--check = kill-at-RUNNING only); the full run
            # kills every fragment
            kill_fids = sorted({kill_fids[0],
                                kill_fids[len(kill_fids) // 2],
                                kill_fids[-1]})
        for fid in kill_fids:
            t0 = time.monotonic()
            # one-shot fault on this group's dispatch, any shard/query
            # id; exhausted rules from earlier stages are inert
            inj.add_device_rule(rf"/f{fid}/s\d+$")
            hits_before = len(inj.injections)
            lowered_before = sqlmesh.FRAGMENTS_LOWERED
            stage = {"fragment": fid, "ok": False}
            res = {}
            try:
                res["rows"] = sorted(dqr.execute(sql).rows)
            except Exception as e:  # noqa: BLE001 - per-stage verdict
                res["err"] = str(e)
            q = list(dqr.coordinator.queries.values())[-1]
            info = dict(q.device_exchange_info or {})
            resumes = list(q.device_resumes)
            resumed_from = sorted({f for r in resumes
                                   for f in r["resumed_from"]})
            stage["injections"] = len(inj.injections) - hits_before
            stage["resumes"] = len(resumes)
            stage["resume_modes"] = sorted({r["mode"] for r in resumes})
            stage["resumed_from"] = resumed_from
            stage["mesh_relowered"] = \
                sqlmesh.FRAGMENTS_LOWERED - lowered_before
            # zero re-execution of checkpointed fragments, per mode:
            # device = never re-lowered into the resumed SPMD program;
            # http = never given an HTTP task (spool:// leaves instead)
            relowered = sorted(set(resumed_from)
                               & set(info.get("fragments_lowered")
                                     or []))
            retasked = sorted({f for f, _, _ in q._placements
                               if f in resumed_from})
            stage["spool_leaves"] = sorted(
                f for f, uris in q._task_uris.items()
                if any(str(u).startswith("spool://") for u in uris))
            stage["wall_s"] = round(time.monotonic() - t0, 2)
            if "err" in res:
                stage["reason"] = res["err"][:300]
            elif res["rows"] != want:
                stage["reason"] = "row mismatch"
            elif not stage["injections"]:
                stage["reason"] = "fault never fired"
            elif not resumes:
                stage["reason"] = "kill never triggered a resume"
            elif relowered:
                stage["reason"] = (f"checkpointed fragments re-lowered: "
                                   f"{relowered}")
            elif retasked:
                stage["reason"] = (f"checkpointed fragments re-executed "
                                   f"as HTTP tasks: {retasked}")
            else:
                stage["ok"] = True
            stages.append(stage)
            if not quiet:
                print(json.dumps(stage))
    report = {
        "mode": "mesh", "resume_mode": resume_mode,
        "query": f"tpch q{query_num}", "scale": scale,
        "fragments": kill_fids,
        "checkpoint_groups": info0.get("checkpoint_groups"),
        "stages": stages,
        "total_resumes": sum(s["resumes"] for s in stages),
        "ok": all(s["ok"] for s in stages),
    }
    return report


#: the coordinator-HA kill matrix (lifecycle phases of one query)
HA_PHASES = ("QUEUED", "PLANNING", "RUNNING", "SPOOL_COMPLETE",
             "FINISHED")


def run_ha_sweep(phases=HA_PHASES, scale: float = 0.003,
                 query_num: int = 72, quiet: bool = False) -> dict:
    """Kill-the-COORDINATOR sweep (coordinator HA acceptance): run a
    TPC-DS query on a 2-worker HA mesh (primary + standby sharing the
    spool and the durable query-state journal), kill the primary at
    each lifecycle phase in turn, and assert exact rows through the
    standby — with ZERO producer re-runs for stages already complete in
    the spool (and zero task creates at all for the
    all-spool-complete kill)."""
    import dataclasses as _dc
    import tempfile
    import threading as _th
    import urllib.error
    import urllib.request

    from presto_tpu.config import DEFAULT
    from presto_tpu.connectors.api import ConnectorRegistry
    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.server.dqr import HAQueryRunner
    from presto_tpu.server.faults import FaultInjector
    from tests.tpcds_queries import QUERIES

    sql = QUERIES[query_num]
    reg = ConnectorRegistry()
    reg.register("tpcds", TpcdsConnector(scale=scale))
    want = sorted(LocalQueryRunner(reg, "tpcds").execute(sql).rows)

    def poll_standby(standby_uri, qid, timeout_s=120.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"{standby_uri}/v1/statement/executing/{qid}/0",
                        timeout=30) as resp:
                    p = json.loads(resp.read())
            except urllib.error.HTTPError as e:
                if e.code in (404, 503):
                    time.sleep(0.05)
                    continue
                raise
            if "error" in p:
                raise RuntimeError(f"standby failed: {p['error']}")
            if "data" in p:
                return p
            time.sleep(0.05)
        raise RuntimeError("standby never served the query")

    stages = []
    for phase in phases:
        t0 = time.monotonic()
        tmp = tempfile.mkdtemp(prefix="ha-sweep-")
        cfg = _dc.replace(
            DEFAULT,
            exchange_spooling_enabled=True,
            exchange_spool_path=os.path.join(tmp, "spool"),
            coordinator_state_path=os.path.join(tmp, "state"),
            coordinator_lease_ttl_s=0.4,
            task_recovery_interval_s=0.05)
        co_inj = FaultInjector()
        hold = None
        if phase in ("RUNNING", "SPOOL_COMPLETE"):
            hold = co_inj.add_rule(r"/results/", method="GET",
                                   policy="slow-task", delay_s=120.0)
        stage = {"phase": phase, "ok": False}
        res = {}
        with HAQueryRunner.tpcds(
                scale=scale, n_workers=2, config=cfg,
                coordinator_injector=co_inj,
                heartbeat_interval_s=0.05,
                heartbeat_max_missed=2) as ha:
            co = ha.coordinator
            while len(co.nodes.alive_nodes()) != 2:
                time.sleep(0.02)
            try:
                if phase == "QUEUED":
                    co.dispatcher.pause()
                    qid = _ha_submit(co.uri, sql)
                    time.sleep(0.2)
                    ha.kill_primary()
                elif phase == "PLANNING":
                    at = _th.Event()
                    release = _th.Event()

                    def hook(_q, ph):
                        if ph == "PLANNING":
                            at.set()
                            release.wait(timeout=60.0)

                    co.phase_hook = hook
                    qid = _ha_submit(co.uri, sql)
                    if not at.wait(timeout=60.0):
                        raise RuntimeError("never reached PLANNING")
                    ha.kill_primary()
                    release.set()
                elif phase == "FINISHED":
                    cols, data = ha.client.execute(sql)
                    qid = ha.client.last_query_id
                    stage["primary_rows"] = len(data)
                    ha.kill_primary()
                else:   # RUNNING / SPOOL_COMPLETE, drain held
                    def run():
                        try:
                            res["rows"] = ha.execute(sql).rows
                        except Exception as e:  # noqa: BLE001
                            res["err"] = str(e)

                    t = _th.Thread(target=run)
                    t.start()
                    q = None
                    deadline = time.monotonic() + 120.0
                    while time.monotonic() < deadline:
                        qs = list(co.queries.values())
                        if qs and qs[0]._placements and \
                                qs[0].state == "RUNNING":
                            q = qs[0]
                            break
                        time.sleep(0.02)
                    if q is None:
                        raise RuntimeError("never reached RUNNING")
                    qid = q.query_id
                    if phase == "SPOOL_COMPLETE":
                        deadline = time.monotonic() + 120.0
                        while time.monotonic() < deadline:
                            with q._recovery_lock:
                                pl = list(q._placements)
                            if pl and all(co.spool.is_complete(
                                    tid, q._task_specs[tid]["n_out"])
                                    for _, tid, _ in pl):
                                break
                            time.sleep(0.05)
                        else:
                            raise RuntimeError(
                                "stages never all spool-complete")
                    time.sleep(0.3)   # journal writes settle
                    stage["tasks_before"] = sum(
                        len(w.task_manager.tasks) for w in ha.workers)
                    ha.kill_primary()
                ha.wait_for_failover(timeout_s=30.0)
                if phase in ("RUNNING", "SPOOL_COMPLETE"):
                    t.join(timeout=240.0)
                    if t.is_alive():
                        raise RuntimeError("client never finished")
                    if "err" in res:
                        raise RuntimeError(res["err"][:300])
                    rows = sorted(res["rows"])
                else:
                    p = poll_standby(ha.standby.uri, qid)
                    # decode the JSON payload through the client codec
                    # so dates/timestamps compare against the oracle
                    from presto_tpu import types as T
                    from presto_tpu.server.dqr import _from_json

                    types = [T.parse_type(c["type"])
                             for c in p.get("columns", [])]
                    rows = sorted(
                        tuple(_from_json(v, ty)
                              for v, ty in zip(r, types))
                        for r in p["data"])
                sq = ha.standby.queries.get(qid)
                stage["adopted_outcome"] = getattr(
                    sq, "adopt_outcome", None)
                stage["producer_reruns"] = getattr(
                    sq, "producer_reruns_total", 0)
                stage["stage_retry_rounds"] = getattr(
                    sq, "stage_retry_rounds", 0)
                stage["failovers"] = \
                    ha.standby.ha_counters["failovers"]
                if phase == "FINISHED":
                    # both sides are client-protocol JSON payloads:
                    # the standby must re-serve the primary's rows
                    exact = sorted(map(tuple, p["data"])) == \
                        sorted(map(tuple, data))
                else:
                    exact = rows == want
                if phase == "SPOOL_COMPLETE":
                    stage["tasks_after"] = sum(
                        len(w.task_manager.tasks) for w in ha.workers)
                    if stage["tasks_after"] != stage["tasks_before"]:
                        raise RuntimeError(
                            "adoption created tasks for "
                            "spool-complete stages")
                    if stage["producer_reruns"] != 0:
                        raise RuntimeError(
                            "producer re-ran for a spool-complete "
                            "stage")
                if phase == "RUNNING" and \
                        stage["producer_reruns"] != 0:
                    raise RuntimeError(
                        "producer re-ran under spooled HA adoption")
                if not exact:
                    raise RuntimeError("row mismatch through standby")
                stage["ok"] = True
            except Exception as e:  # noqa: BLE001 - per-phase verdict
                stage["reason"] = str(e)[:300]
            if hold is not None:
                hold.release()
        stage["wall_s"] = round(time.monotonic() - t0, 2)
        stages.append(stage)
        if not quiet:
            print(json.dumps(stage))
    report = {
        "mode": "ha", "query": f"tpcds q{query_num}", "scale": scale,
        "phases": [s["phase"] for s in stages],
        "stages": stages,
        "total_producer_reruns": sum(
            s.get("producer_reruns", 0) for s in stages),
        "ok": all(s["ok"] for s in stages),
    }
    return report


def _ha_submit(co_uri: str, sql: str) -> str:
    import urllib.request

    req = urllib.request.Request(
        f"{co_uri}/v1/statement", data=sql.encode(),
        method="POST", headers={"Content-Type": "text/plain"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())["id"]


def run_oom_sweep(scale: float = 0.01, survivors: int = 2,
                  quiet: bool = False) -> dict:
    """Overload-survival sweep (the low-memory-killer acceptance proof):
    a held runaway task fills one worker's GENERAL pool (faults.py
    memory-inflation with a hold), concurrent survivor statements then
    BLOCK on the full pool, and the coordinator's arbitration must
    resolve the stall by failing EXACTLY the policy-selected runaway
    with the reference error shape (CLUSTER_OUT_OF_MEMORY /
    INSUFFICIENT_RESOURCES) while every survivor returns exact rows and
    ZERO workers die."""
    import dataclasses as _dc
    import threading as _th

    from presto_tpu.client import QueryFailed
    from presto_tpu.config import DEFAULT
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.server.faults import FaultInjector

    pool = 8 << 20
    runaway_sql = ("select l_returnflag, count(*) from lineitem "
                   "group by l_returnflag")
    survivor_sql = "select count(*) from lineitem"
    # clean run: the survivor ground truth the degraded cluster must
    # still reproduce exactly
    with DistributedQueryRunner.tpch(scale=scale, n_workers=2) as clean:
        want = sorted(clean.execute(survivor_sql).rows)
    cfg = _dc.replace(
        DEFAULT,
        worker_memory_pool_bytes=pool,
        memory_blocked_wait_s=30.0,
        low_memory_killer_delay_s=0.75)
    inj = FaultInjector()
    # the runaway: the first task created on worker 0 reserves ~94% of
    # the node pool and PARKS holding it until the kill aborts it
    inj.add_memory_rule(".*", int(pool * 0.94), times=1, hold_s=60.0)
    t0 = time.monotonic()
    stages = []
    report = {"mode": "oom", "scale": scale, "pool_bytes": pool,
              "survivors": survivors, "stages": stages}
    with DistributedQueryRunner.tpch(
            scale=scale, n_workers=2, config=cfg,
            worker_injectors={0: inj},
            heartbeat_interval_s=0.05,
            heartbeat_max_missed=5) as dqr:
        co = dqr.coordinator
        while len(co.nodes.alive_nodes()) != 2:
            time.sleep(0.02)

        def pool_reserved() -> int:
            return max((mi.get("pool", {}).get("reservedBytes", 0)
                        for mi in co.memory_info.values()), default=0)

        run_res: dict = {}

        def run_runaway():
            try:
                run_res["rows"] = dqr.new_client("runaway").execute(
                    runaway_sql, max_retries=0)[1]
            except QueryFailed as e:
                run_res["err"] = str(e)
                run_res["errorName"] = e.error_name
                run_res["errorType"] = e.error_type
                run_res["errorCode"] = e.error_code

        t_run = _th.Thread(target=run_runaway)
        t_run.start()
        # the runaway must be RUNNING and actually resident before the
        # survivors arrive (deterministic pressure ordering)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            qs = list(co.queries.values())
            if qs and qs[0].state == "RUNNING" and \
                    pool_reserved() >= int(pool * 0.9):
                break
            time.sleep(0.02)
        resident = pool_reserved()
        stages.append({"stage": "runaway-resident",
                       "pool_reserved": resident,
                       "ok": resident >= int(pool * 0.9)})
        runaway_qid = (list(co.queries.values())[0].query_id
                       if co.queries else None)
        # survivor tasks landing on the full node inflate a LITTLE too,
        # so their drivers genuinely BLOCK on the pool (the stall the
        # killer must resolve); no hold — they proceed once the victim's
        # memory frees, and the inflations all fit in the freed pool
        inj.add_memory_rule(".*", 1 << 20, times=4 * survivors)
        sur_res = [dict() for _ in range(survivors)]

        def run_survivor(i: int):
            try:
                sur_res[i]["rows"] = dqr.new_client(
                    f"survivor{i}").execute(survivor_sql,
                                            max_retries=0)[1]
            except QueryFailed as e:
                sur_res[i]["err"] = str(e)
                sur_res[i]["errorName"] = e.error_name

        threads = [_th.Thread(target=run_survivor, args=(i,))
                   for i in range(survivors)]
        for t in threads:
            t.start()
        t_run.join(timeout=60)
        kill_stage = {
            "stage": "kill", "victim": runaway_qid,
            "errorName": run_res.get("errorName"),
            "errorType": run_res.get("errorType"),
            "errorCode": run_res.get("errorCode"),
            "kill_counters": dict(co.kill_counters),
        }
        kill_stage["ok"] = (
            not t_run.is_alive()
            and run_res.get("errorName") == "CLUSTER_OUT_OF_MEMORY"
            and run_res.get("errorType") == "INSUFFICIENT_RESOURCES"
            and "out of memory" in run_res.get("err", ""))
        if not kill_stage["ok"]:
            kill_stage["reason"] = (
                "runaway hung" if t_run.is_alive() else
                f"unexpected runaway outcome: "
                f"{str(run_res.get('err', run_res.get('rows')))[:300]}")
        stages.append(kill_stage)
        for t in threads:
            t.join(timeout=60)
        norm = [sorted(tuple(r) for r in res.get("rows", []))
                for res in sur_res]
        want_t = sorted(tuple(r) for r in want)
        bad = [res for i, res in enumerate(sur_res)
               if threads[i].is_alive() or "err" in res
               or norm[i] != want_t]
        sur_stage = {"stage": "survivors", "n": survivors,
                     "ok": not bad}
        if bad:
            sur_stage["reason"] = f"{len(bad)} survivor(s) failed: " + \
                "; ".join(str(r.get("err", "row mismatch"))[:120]
                          for r in bad)
        stages.append(sur_stage)
        # post-chaos: clear the fault plane and prove the cluster is
        # whole — both workers alive, pool fully drained, fresh
        # statement exact (zero worker deaths is the acceptance bar)
        inj.release_all()
        inj.clear()
        rec = {"stage": "recovery",
               "alive": len(co.nodes.alive_nodes())}
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and pool_reserved() > 0:
            time.sleep(0.05)
        rec["pool_reserved_after"] = pool_reserved()
        try:
            rows = sorted(dqr.execute(survivor_sql).rows)
            rec["ok"] = (rows == want and rec["alive"] == 2
                         and rec["pool_reserved_after"] == 0)
            if not rec["ok"]:
                rec["reason"] = "cluster degraded after the kill"
        except Exception as e:  # noqa: BLE001 - report must still emit
            rec["ok"] = False
            rec["reason"] = str(e)[:300]
        stages.append(rec)
        if not quiet:
            for s in stages:
                print(json.dumps(s))
    report["wall_s"] = round(time.monotonic() - t0, 2)
    report["ok"] = all(s["ok"] for s in stages)
    return report


def run_check() -> int:
    """CI smoke: the chaos marker tier, headless (quick signal — the
    TPC-DS mesh cases are additionally marked slow and excluded)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "chaos and not slow",
         "-p", "no:cacheprovider",
         os.path.join(repo, "tests", "test_chaos.py"),
         os.path.join(repo, "tests", "test_spool_exchange.py")],
        cwd=repo, env=env)
    print(json.dumps({"check": "chaos marker tier",
                      "ok": r.returncode == 0}))
    return r.returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--query", default="select count(*) from lineitem")
    ap.add_argument("--kill-index", type=int, default=None,
                    help="worker to kill (default: last)")
    ap.add_argument("--mode",
                    choices=["leaf", "stage", "spool", "ha", "mesh",
                             "oom"],
                    default="leaf",
                    help="leaf = kill a scan-task worker; stage = kill "
                         "a worker holding a non-leaf fragment "
                         "(whole-stage retry); spool = kill EVERY "
                         "stage of TPC-DS Q72 in turn on the spooled "
                         "exchange, reporting producer re-runs per "
                         "stage (must be zero); ha = kill the "
                         "COORDINATOR at every lifecycle phase of a "
                         "TPC-DS Q72 HA mesh run and assert exact "
                         "rows through the standby (with --check: "
                         "just the kill-at-RUNNING smoke); mesh = "
                         "inject a device-plane fault at EVERY "
                         "checkpoint group of a TPC-H Q3 collective "
                         "run in turn (mesh_checkpoint_boundaries) "
                         "and assert exact rows with zero "
                         "re-execution of checkpointed fragments, in "
                         "both resume modes (with --check: the "
                         "device-resume sweep at first/middle/root "
                         "kill points only); oom = fill one worker's "
                         "memory pool with a held runaway, block "
                         "concurrent survivors on it, and assert the "
                         "low-memory killer fails exactly the runaway "
                         "(CLUSTER_OUT_OF_MEMORY) while survivors "
                         "return exact rows and zero workers die "
                         "(with --check: one survivor at a smaller "
                         "scale)")
    ap.add_argument("--resume-mode", choices=["device", "http", "both"],
                    default="both",
                    help="mesh mode only: which resume path(s) the "
                         "sweep exercises")
    ap.add_argument("--no-spooling", action="store_true",
                    help="spool mode only: run the sweep with "
                         "exchange spooling disabled (PR 5 cascading "
                         "retry) for comparison")
    ap.add_argument("--check", action="store_true",
                    help="run the chaos pytest tier headless; exit "
                         "nonzero on any inexact result")
    ap.add_argument("--event-log", default="query.json",
                    help="write the coordinator's query.json event "
                         "log here (JSON lines; '' disables)")
    args = ap.parse_args(argv)
    if args.mode == "mesh":
        # --check = the CI smoke: ONLY the device-resume sweep; the
        # full run also proves the HTTP-degrade path.  Exit is nonzero
        # on any inexact result or any re-execution of a checkpointed
        # fragment (re-lowered OR re-tasked)
        modes = (("device",) if args.check or args.resume_mode == "device"
                 else ("http",) if args.resume_mode == "http"
                 else ("device", "http"))
        reports = [run_mesh_sweep(scale=args.scale, resume_mode=m,
                                  smoke=args.check)
                   for m in modes]
        report = (reports[0] if len(reports) == 1 else
                  {"mode": "mesh", "sweeps": reports,
                   "ok": all(r["ok"] for r in reports)})
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    if args.mode == "ha":
        # --check = the CI smoke: ONLY the kill-at-RUNNING scenario,
        # nonzero on inexact rows or on any producer re-run for
        # spool-complete stages
        report = run_ha_sweep(
            phases=("RUNNING",) if args.check else HA_PHASES,
            scale=args.scale if args.scale != 0.01 else 0.003)
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    if args.mode == "oom":
        # --check = the CI smoke: one survivor at the smoke scale;
        # nonzero when the wrong query dies, any survivor fails or
        # returns inexact rows, or the cluster is degraded after
        report = run_oom_sweep(
            scale=0.003 if args.check else args.scale,
            survivors=1 if args.check else 2)
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    if args.check:
        return run_check()
    if args.mode == "spool":
        report = run_spool_sweep(
            scale=args.scale if args.scale != 0.01 else 0.003,
            spooling=not args.no_spooling)
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    if args.mode == "stage":
        args.query = ("select n_name, count(*) from nation join region "
                      "on n_regionkey = r_regionkey group by n_name")

    from presto_tpu.config import DEFAULT
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.server.faults import FaultInjector

    # clean run first: the ground truth the chaos run must reproduce
    with DistributedQueryRunner.tpch(scale=args.scale,
                                     n_workers=args.workers) as clean:
        want = clean.execute(args.query).rows

    victim_idx = (args.kill_index if args.kill_index is not None
                  else args.workers - 1)
    cfg = dataclasses.replace(DEFAULT, task_recovery_interval_s=0.05)
    inj = FaultInjector()   # victim withholds results => query in flight
    inj.add_rule(r"/results/", method="GET", policy="drop-connection")
    report = {"query": args.query, "workers": args.workers,
              "scale": args.scale, "killed_worker": victim_idx}
    t0 = time.monotonic()
    if args.event_log and os.path.exists(args.event_log):
        os.remove(args.event_log)
    with DistributedQueryRunner.tpch(
            scale=args.scale, n_workers=args.workers, config=cfg,
            worker_injectors={victim_idx: inj},
            heartbeat_interval_s=0.05,
            heartbeat_max_missed=2,
            event_log_path=args.event_log or None) as dqr:
        co = dqr.coordinator
        while len(co.nodes.alive_nodes()) != args.workers:
            time.sleep(0.02)
        res = {}

        def run():
            try:
                res["rows"] = dqr.execute(args.query).rows
            except Exception as e:  # noqa: BLE001
                res["err"] = str(e)

        t = threading.Thread(target=run)
        t.start()
        victim_uri = dqr.workers[victim_idx].uri
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            qs = list(co.queries.values())
            if qs and any(
                    u == victim_uri and (
                        args.mode == "leaf"
                        or (qs[0]._dplan is not None and qs[0]._dplan
                            .fragments[f].consumed_fragments))
                    for f, _, u in qs[0]._placements):
                break
            time.sleep(0.02)
        q = list(co.queries.values())[0]
        dqr.kill_worker(victim_idx)
        t.join(timeout=120)
        report["wall_s"] = round(time.monotonic() - t0, 3)
        report["mode"] = args.mode
        report["stage_retry_rounds"] = q.stage_retry_rounds
        report["trace_token"] = q.trace_token
        # the /metrics plane must agree with the coordinator's counters
        # (the Prometheus scrape an operator would alert on)
        try:
            import urllib.request

            with urllib.request.urlopen(f"{co.uri}/metrics",
                                        timeout=5) as resp:
                metrics = resp.read().decode()
            line = next(
                (ln for ln in metrics.splitlines()
                 if ln.startswith("presto_stage_retry_rounds_total ")),
                "presto_stage_retry_rounds_total 0")
            report["metrics_stage_retry_rounds"] = float(line.split()[-1])
        except Exception as e:  # noqa: BLE001 - report must still emit
            report["metrics_stage_retry_rounds"] = f"error: {e}"
        report["recovered_placements"] = [
            (fid, tid, uri) for fid, tid, uri in q._placements]
        if t.is_alive():
            report["ok"] = False
            report["reason"] = "query hung after worker kill"
        elif "err" in res:
            report["ok"] = False
            report["reason"] = f"query failed: {res['err'][:300]}"
        elif sorted(res["rows"]) != sorted(want):
            report["ok"] = False
            report["reason"] = (f"row mismatch: chaos={res['rows'][:3]} "
                                f"clean={want[:3]}")
        elif any(u == victim_uri for _, _, u in q._placements):
            report["ok"] = False
            report["reason"] = "placements still on the dead worker"
        else:
            report["ok"] = True
    if args.event_log:
        # summarize the event log: the StageRetryEvent (stage mode) and
        # the completion event land here with the query's trace token
        from presto_tpu.events import read_event_log

        try:
            events = read_event_log(args.event_log)
        except Exception:  # noqa: BLE001 - log may be disabled
            events = []
        report["event_log"] = args.event_log
        report["events"] = sorted({e["event"] for e in events})
        report["stage_retry_events"] = sum(
            1 for e in events if e["event"] == "StageRetryEvent")
    print(json.dumps(report, indent=2))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
