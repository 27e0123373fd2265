"""The collective plane as it is served and measured (the deployment
``tpch-sf10-mesh4w`` of BENCHMARK.json, here at SF0.01 on four of the
virtual CPU devices): StatementClient -> dispatcher -> plan cache ->
``_try_device_exchange`` -> ``MeshQueryRunner.execute_dplan``.

- answers equal the plain numpy references the benchmark judges with
  (``benchmark/references/``: no engine code), not only the HTTP plane's;
- four clients repeating one statement: equal answers, the program cache
  hit and nothing built after the first execution, no fallback, and the
  wait for ``mesh_executor_lock`` in ``queryStats.host_ns``;
- the span tree of a repeat carries the query thread's host activity under
  the root fragment's task, inside the ``execute`` phase.
"""

import dataclasses as dc
import os
import sys
import threading

import pytest

from presto_tpu.config import DEFAULT
from presto_tpu.server.dqr import DistributedQueryRunner
from presto_tpu.spans import ACTIVITY_KINDS, validate_span_tree

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, load, manifest, observe, refdata  # noqa: E402

SCALE = 0.01
WORKERS = 4
CLIENTS, REPEATS = 4, 5
RTOL = 1e-6
#: the kinds the mesh path brackets (coordinator._try_device_exchange,
#: parallel/sqlmesh.py); a repeat enters the last three
MESH_KINDS = {"lock_wait", "generate", "stage_h2d", "dispatch",
              "device_wait"}


def statement(name):
    with open(manifest.path("statements", name + ".sql")) as f:
        return f.read()


@pytest.fixture(scope="module")
def cluster():
    cfg = dc.replace(DEFAULT, mesh_device_exchange=True)
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=WORKERS,
                                     config=cfg) as runner:
        yield runner


@pytest.fixture(scope="module")
def want():
    refs = {name: manifest.load_module("references", name)
            for name in ("q1", "q3")}
    wanted = {}
    for ref in refs.values():
        for table, cols in ref.COLUMNS.items():
            wanted.setdefault(table, set()).update(cols)
    columns, _nbytes = refdata.host_columns("tpch", SCALE, wanted)
    return {name: ref.reference(columns) for name, ref in refs.items()}


def run(cluster, name, client=None, who=0):
    op = load.execute(client or cluster.new_client(user=f"mesh-{who}"),
                      name, statement(name), who)
    assert op["error"] is None, op["error"]
    return op


def detail_of(cluster, op):
    return observe.query_detail(cluster.coordinator.uri, op["query_id"])


def served_by_the_mesh(cluster, detail):
    assert set(detail["exchangeModes"]) == {"device"}, detail["exchangeModes"]
    assert "fallback" not in detail["deviceExchange"]
    assert not detail.get("resultCached")
    assert not cluster.coordinator.device_exchange_counters["fallbacks"]


def activities(tree):
    """(task span, its activity children) of the tasks that have any."""
    found = []
    for stage in tree["children"]:
        if stage["kind"] != "stage":
            continue
        for task in stage["children"]:
            acts = [c for c in task["children"] if c["kind"] == "activity"]
            if acts:
                found.append((task, acts))
    return found


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_answers_equal_the_plain_reference(cluster, want, name):
    op = run(cluster, name)
    assert len(op["rows"]) == len(want[name]) > 0
    assert check.compare(op["rows"], want[name], RTOL) <= RTOL
    detail = detail_of(cluster, op)
    served_by_the_mesh(cluster, detail)
    assert detail["deviceExchange"]["nparts"] == WORKERS


def test_four_clients_repeating_q1(cluster, want):
    first = run(cluster, "q1")     # builds the program if no test has yet
    ops, lock = [], threading.Lock()

    def walk(who):
        client = cluster.new_client(user=f"mesh-{who}")
        for _ in range(REPEATS):
            op = run(cluster, "q1", client, who)
            with lock:
                ops.append(op)

    threads = [threading.Thread(target=walk, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert len(ops) == CLIENTS * REPEATS
    lock_waits = []
    for op in ops:
        assert op["rows"] == first["rows"]
        check.compare(op["rows"], want["q1"], RTOL)
        detail = detail_of(cluster, op)
        served_by_the_mesh(cluster, detail)
        assert detail["deviceExchange"]["program_cached"] is True
        assert detail["deviceExchange"]["compile_ns"] == 0
        stats = detail["queryStats"]
        assert stats["xla_builds"] == 0
        assert stats["jit_dispatches"] == 1
        assert set(stats["host_ns"]) <= set(ACTIVITY_KINDS)
        assert stats["host_ns"]["dispatch"] > 0
        assert stats["host_ns"]["device_wait"] > 0
        # a repeat neither generates nor stages: its inputs are on the mesh
        assert stats["host_ns"]["generate"] == 0
        assert stats["host_ns"]["stage_h2d"] == 0
        lock_waits.append(stats["host_ns"]["lock_wait"])
    # four clients, one program at a time: someone waited
    assert max(lock_waits) > 0


def test_first_execution_records_generation_staging_and_builds(cluster):
    """A statement the cluster has not seen: its one execution generates
    the scan on the host, puts it on the mesh and builds the program, all
    on the query thread, and the account says so."""
    client = cluster.new_client(user="mesh-first")
    _cols, rows = client.execute(
        "select l_linestatus, count(*) from lineitem group by l_linestatus")
    assert sorted(r[0] for r in rows) == ["F", "O"]
    detail = observe.query_detail(cluster.coordinator.uri,
                                  client.last_query_id)
    served_by_the_mesh(cluster, detail)
    assert detail["deviceExchange"]["program_cached"] is False
    stats = detail["queryStats"]
    for kind in ("generate", "stage_h2d", "dispatch", "device_wait"):
        assert stats["host_ns"][kind] > 0, kind
    assert stats["xla_builds"] >= 1 and stats["xla_build_ns"] > 0
    tree = observe.query_spans(cluster.coordinator.uri, client.last_query_id)
    assert {"lower", "compile", "execute"} <= {
        c["name"] for c in tree["children"] if c["kind"] == "phase"}
    assert validate_span_tree(tree) == []


def test_span_tree_of_a_repeat_has_the_query_threads_activity(cluster):
    run(cluster, "q1")
    op = run(cluster, "q1")
    detail = detail_of(cluster, op)
    assert detail["deviceExchange"]["program_cached"] is True
    tree = observe.query_spans(cluster.coordinator.uri, op["query_id"])
    assert validate_span_tree(tree) == []
    execute = [c for c in tree["children"]
               if c["kind"] == "phase" and c["name"] == "execute"]
    assert len(execute) == 1
    lo, hi = execute[0]["start"], execute[0]["end"]
    found = activities(tree)
    # only the root fragment's one task carries them
    assert len(found) == 1
    task, acts = found[0]
    (root,) = [fid for fid, st in detail["stageStats"].items()
               if st["tasks"] == 1]      # Q1's final, 'single' fragment
    assert task["name"] == f"{op['query_id']}.{root}.0"
    assert not task["attributes"]["activityTruncated"]
    kinds = {a["name"] for a in acts}
    assert kinds <= set(ACTIVITY_KINDS) and kinds <= MESH_KINDS
    assert {"lock_wait", "dispatch", "device_wait"} <= kinds
    for a in acts:
        assert lo - 1e-6 <= a["start"] <= a["end"] <= hi + 1e-6, a
        assert a["attributes"]["count"] >= 1
    # brackets run under mesh_executor_lock, where host time counts once a
    # client: the lock, two program calls, one read of each call's outputs
    assert sum(a["attributes"]["count"] for a in acts) == 5
    # the totals on the task are the intervals' busy seconds
    host_s = task["attributes"]["hostSeconds"]
    for kind in kinds:
        busy = sum(a["attributes"]["busyS"] for a in acts
                   if a["name"] == kind)
        assert host_s[kind] == pytest.approx(busy, abs=1e-6)
    assert sum(host_s.values()) <= (hi - lo) + 1e-3
    # EXPLAIN ANALYZE carries the same account through the same line
    text = "\n".join(r[0] for r in cluster.execute(
        "EXPLAIN ANALYZE " + statement("q1")).rows)
    assert "host ms:" in text and "lock_wait" in text


def test_http_plane_tree_has_no_lock_wait():
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=2) as http:
        client = http.new_client(user="http")
        client.execute(statement("q6"))
        uri, qid = http.coordinator.uri, client.last_query_id
        tree = observe.query_spans(uri, qid)
        detail = observe.query_detail(uri, qid)
    found = activities(tree)
    assert found                      # its tasks do record activity
    kinds = {a["name"] for _task, acts in found for a in acts}
    assert kinds <= set(ACTIVITY_KINDS) and "lock_wait" not in kinds
    assert detail["queryStats"]["host_ns"].get("lock_wait", 0) == 0
    assert set(detail["exchangeModes"]) == {"http"}
