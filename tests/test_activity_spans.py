"""Host activity inside a task (presto_tpu/spans.py ``HostActivity`` /
``activity``): the recorder's arithmetic, and what a served query shows
of it in /v1/query/{id}/spans, queryStats, task info and the completed
event."""

import json
import threading
import urllib.request

import pytest

from presto_tpu import spans
from presto_tpu.spans import (
    ACTIVITY_KINDS, HOST_ACTIVITY_HEADER, MAX_INTERVALS, MERGE_GAP_NS,
    HostActivity, activity, set_current_activity,
)
from tpch_queries import QUERIES

MS = 1_000_000


# -- the recorder ----------------------------------------------------------

@pytest.mark.parametrize("gap_ns, merged", [
    (0, True), (MERGE_GAP_NS - 1, True), (MERGE_GAP_NS, False),
    (5 * MERGE_GAP_NS, False)])
def test_same_kind_merges_under_one_ms(gap_ns, merged):
    rec = HostActivity()
    rec.add("generate", 10 * MS, 12 * MS)
    rec.add("generate", 12 * MS + gap_ns, 15 * MS + gap_ns)
    assert len(rec.intervals) == (1 if merged else 2)
    assert rec.total_ns["generate"] == 5 * MS       # never the gap
    assert sum(iv[3] for iv in rec.intervals) == 2
    if merged:
        kind, start, end, count, busy = rec.intervals[0]
        assert (kind, start, end) == ("generate", 10 * MS,
                                      15 * MS + gap_ns)
        assert (count, busy) == (2, 5 * MS)


def test_other_kinds_in_between_do_not_merge_or_split():
    rec = HostActivity()
    rec.add("generate", 0, 2 * MS)
    rec.add("stage_h2d", 2 * MS, 2 * MS + 1000)
    rec.add("generate", 2 * MS + 1000, 4 * MS)
    assert [iv[0] for iv in rec.intervals] == ["generate", "stage_h2d"]
    assert rec.intervals[0][1:3] == [0, 4 * MS]
    assert rec.total_ns["generate"] == 4 * MS - 1000
    assert rec.total_ns["stage_h2d"] == 1000


def test_truncates_beyond_the_cap_and_totals_stay_exact():
    rec = HostActivity()
    n = MAX_INTERVALS + 100
    for i in range(n):      # 2 ms apart: nothing merges
        rec.add("dispatch", i * 2 * MS, i * 2 * MS + 1000)
    assert len(rec.intervals) == MAX_INTERVALS
    assert rec.truncated
    assert rec.total_ns["dispatch"] == n * 1000
    assert rec.as_dict()["truncated"] is True
    assert len(rec.as_dict()["kinds"]) == MAX_INTERVALS


def test_not_truncated_at_exactly_the_cap():
    rec = HostActivity()
    for i in range(MAX_INTERVALS):
        rec.add("dispatch", i * 2 * MS, i * 2 * MS + 1000)
    assert not rec.truncated


def test_unknown_kind_is_refused():
    with pytest.raises(KeyError):
        HostActivity().add("thinking", 0, 1)


def test_overlapping_intervals_of_two_threads_are_one_union():
    rec = HostActivity()
    rec.add("generate", 0, 10 * MS)
    rec.add("generate", 3 * MS, 6 * MS)         # another feed driver
    assert rec.intervals == [["generate", 0, 10 * MS, 2, 13 * MS]]
    assert rec.total_ns["generate"] == 13 * MS  # thread-seconds


def test_activity_without_a_task_does_nothing():
    previous = set_current_activity(None)
    try:
        with activity("generate"):
            pass
        assert spans.current_activity() is None
    finally:
        set_current_activity(previous)


def test_activity_charges_the_threads_task_and_restores():
    rec, other = HostActivity(), HostActivity()
    outer = set_current_activity(rec)
    try:
        with activity("serialize"):
            pass
        inner = set_current_activity(other)
        assert inner is rec
        with activity("dispatch"):
            pass
        set_current_activity(inner)
        with pytest.raises(ValueError):
            with activity("device_wait"):
                raise ValueError("the body's error passes through")
    finally:
        set_current_activity(outer)
    assert [iv[0] for iv in rec.intervals] == ["serialize", "device_wait"]
    assert [iv[0] for iv in other.intervals] == ["dispatch"]
    kind, start, end, _count, busy = rec.intervals[0]
    assert kind == "serialize" and end >= start and busy == end - start


def test_the_current_recorder_is_per_thread():
    rec = HostActivity()
    seen = []
    outer = set_current_activity(rec)
    try:
        t = threading.Thread(
            target=lambda: seen.append(spans.current_activity()))
        t.start()
        t.join(timeout=10)
    finally:
        set_current_activity(outer)
    assert seen == [None]


def test_concurrent_adds_lose_nothing():
    rec = HostActivity()
    per_thread, threads = 2000, 8

    def work(who):
        for i in range(per_thread):
            rec.add(ACTIVITY_KINDS[who % len(ACTIVITY_KINDS)], i, i + 7)

    ts = [threading.Thread(target=work, args=(w,)) for w in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in ts)
    assert sum(rec.total_ns.values()) == threads * per_thread * 7
    assert sum(iv[3] for iv in rec.intervals) == threads * per_thread


# -- a served query ---------------------------------------------------------

def _fetch(uri, headers=None):
    req = urllib.request.Request(uri, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


NEEDS = {
    "q1": {"generate", "stage_h2d", "dispatch", "device_wait",
           "serialize"},
    "q3": {"generate", "stage_h2d", "dispatch", "device_wait",
           "serialize", "exchange_wait"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Q1 and Q3 at SF0.01 through coordinator and two workers; what
    every surface said of each, collected once."""
    from presto_tpu.server.dqr import DistributedQueryRunner

    log = str(tmp_path_factory.mktemp("events") / "query.json")
    out = {}
    with DistributedQueryRunner.tpch(scale=0.01, n_workers=2,
                                     event_log_path=log) as dqr:
        uri = dqr.coordinator.uri
        client = dqr.new_client()
        for name, number in (("q1", 1), ("q3", 3)):
            client.execute(QUERIES[number])
            qid = client.last_query_id
            query = dqr.coordinator.queries[qid]
            with query._recovery_lock:
                placements = list(query._placements)
            tid, wuri = placements[0][1], placements[0][2]
            out[name] = {
                "id": qid,
                "tree": _fetch(f"{uri}/v1/query/{qid}/spans"),
                "detail": _fetch(f"{uri}/v1/query/{qid}"),
                # what the live sampler's sweep fetches
                "poll": query._fetch_task_infos(placements),
                "task_plain": _fetch(f"{wuri}/v1/task/{tid}"),
                "task_final": _fetch(f"{wuri}/v1/task/{tid}",
                                     {HOST_ACTIVITY_HEADER: "1"}),
            }
    events = [json.loads(line) for line in open(log, encoding="utf-8")]
    for e in events:
        if e["event"] == "QueryCompletedEvent":
            for got in out.values():
                if got["id"] == e["query_id"]:
                    got["event"] = e
    return out


def _tasks(tree):
    return [t for stage in tree["children"] if stage["kind"] == "stage"
            for t in stage["children"]]


def _walk(node):
    yield node
    for child in node["children"]:
        yield from _walk(child)


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_span_tree_has_every_kind_and_validates(served, name):
    tree = served[name]["tree"]
    assert spans.validate_span_tree(tree) == []
    acts = [n for n in _walk(tree) if n["kind"] == "activity"]
    assert NEEDS[name] <= {a["name"] for a in acts}
    assert {a["name"] for a in acts} <= set(ACTIVITY_KINDS)
    # the same epoch clock as the phases and stages
    for a in acts:
        assert tree["start"] <= a["start"] <= a["end"] <= tree["end"]
        assert a["traceToken"] == tree["traceToken"]
    # activities hang under task spans, and only there
    for t in _tasks(tree):
        assert all(c["kind"] == "activity" for c in t["children"])
    assert not [c for c in tree["children"] if c["kind"] == "activity"]


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_host_seconds_are_the_childrens_sum(served, name):
    for task in _tasks(served[name]["tree"]):
        attrs = task["attributes"]
        assert attrs["activityTruncated"] is False
        by_kind = {}
        for c in task["children"]:
            by_kind[c["name"]] = (by_kind.get(c["name"], 0.0)
                                  + c["attributes"]["busyS"])
        for kind, seconds in attrs["hostSeconds"].items():
            assert seconds == pytest.approx(by_kind.get(kind, 0.0),
                                            abs=1e-6)


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_query_stats_host_ns_is_the_tasks_sum(served, name):
    detail = served[name]["detail"]
    tasks = [ts for lst in detail["taskStats"].values() for ts in lst]
    assert tasks
    for kind in ACTIVITY_KINDS:
        assert detail["queryStats"]["host_ns"].get(kind, 0) == sum(
            ts["host_ns"].get(kind, 0) for ts in tasks)
    stages = detail["stageStats"].values()
    assert detail["queryStats"]["host_ns"]["generate"] == sum(
        st["host_ns"].get("generate", 0) for st in stages) > 0


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_task_spans_carry_operator_busy_time(served, name):
    for task in _tasks(served[name]["tree"]):
        ops = task["attributes"]["operators"]
        assert ops
        for op in ops:
            assert set(op) == {"operator", "wallS", "inputRows",
                               "outputRows", "jitDispatches", "kernelTier",
                               "prereduceHeld", "compactions",
                               "compactionsSkipped", "scanCache"}
            assert op["wallS"] >= 0
        assert "jitCompileNs" not in task["attributes"]
    dispatched = sum(op["jitDispatches"]
                     for task in _tasks(served[name]["tree"])
                     for op in task["attributes"]["operators"])
    assert dispatched == \
        served[name]["detail"]["queryStats"]["jit_dispatches"]


def test_q1_leaf_device_waits_do_not_grow_with_batches():
    """Q1's leaf segment keeps each batch's partial states on the device
    (exec/fusion.py), so a leaf task reads from the device when it hands
    the merged partial to its sink and at no other time: the
    ``device_wait`` brackets it records (the count, the partition ids,
    the columns) are as many over 19 batches as over 3.  They were three
    a batch."""
    import dataclasses as dc

    from presto_tpu.config import EngineConfig
    from presto_tpu.server.dqr import DistributedQueryRunner

    def leaf_tasks(scan_batch_rows):
        cfg = dc.replace(EngineConfig(), scan_batch_rows=scan_batch_rows)
        with DistributedQueryRunner.tpch(scale=0.05, n_workers=2,
                                         config=cfg) as dqr:
            client = dqr.new_client()
            client.execute(QUERIES[1])
            tree = _fetch(f"{dqr.coordinator.uri}/v1/query/"
                          f"{client.last_query_id}/spans")
        out = []
        for task in _tasks(tree):
            held = sum(op["prereduceHeld"]
                       for op in task["attributes"]["operators"])
            if held:
                out.append((held, sum(
                    c["attributes"]["count"] for c in task["children"]
                    if c["name"] == "device_wait")))
        return out

    few, many = leaf_tasks(65536), leaf_tasks(8192)
    assert len(few) == len(many) == 2
    assert sum(held for held, _ in many) >= 5 * sum(h for h, _ in few)
    assert [waits for _, waits in many] == [waits for _, waits in few]
    assert all(0 < waits <= 3 for _, waits in many)


def test_q3_span_tree_names_the_join_tier(served):
    """Both of Q3's joins, in every task that builds or probes one, took
    the direct-address index: the span tree says so per operator."""
    tiers = {}
    for task in _tasks(served["q3"]["tree"]):
        for op in task["attributes"]["operators"]:
            kind = op["operator"].rsplit(".", 1)[-1]
            if kind == "HashBuildOperator" or (
                    kind == "FusedSegmentOperator" and op["kernelTier"]):
                tiers.setdefault(kind, []).append(op["kernelTier"])
    assert len(tiers["HashBuildOperator"]) >= 2
    assert len(tiers["FusedSegmentOperator"]) >= 2
    assert {t for ts in tiers.values() for t in ts} == {"dense"}


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_live_poll_carries_no_intervals(served, name):
    polled = [i for lst in served[name]["poll"].values() for i in lst]
    assert polled
    for info in polled + [served[name]["task_plain"]]:
        assert "hostActivity" not in info
        # read by nobody, walked on every 0.1 s poll: gone
        for key in ("kernelCaches", "jitCounters", "driverStats"):
            assert key not in info
        assert "host_ns" in info["taskStats"]       # totals ride along
    final = served[name]["task_final"]
    assert final["hostActivity"]["truncated"] is False
    columns = [final["hostActivity"][k] for k in (
        "kinds", "startNs", "endNs", "counts", "busyNs")]
    assert columns[0] and len({len(c) for c in columns}) == 1
    for kind, start, end, count, busy in zip(*columns):
        assert kind in ACTIVITY_KINDS
        assert end >= start and count >= 1 and 0 <= busy


@pytest.mark.parametrize("name", ["q1", "q3"])
def test_completed_event_keeps_totals_not_intervals(served, name):
    tree = served[name]["event"]["spans"]
    assert not [n for n in _walk(tree) if n["kind"] == "activity"]
    live = {t["name"]: t for t in _tasks(served[name]["tree"])}
    for task in _tasks(tree):
        assert task["attributes"]["hostSeconds"] == \
            live[task["name"]]["attributes"]["hostSeconds"]
        assert task["attributes"]["operators"] == \
            live[task["name"]]["attributes"]["operators"]


def test_render_prints_one_line_per_kind_per_task(served):
    tree = served["q3"]["tree"]
    lines = spans.render_span_tree(tree)
    host = [ln for ln in lines if "host:" in ln]
    per_task = sum(len({c["name"] for c in t["children"]})
                   for t in _tasks(tree))
    assert len(host) == per_task
    assert any("host:exchange_wait" in ln for ln in host)
    assert all(" x" in ln for ln in host)
    # and, for a task whose operators took a join or group-by tier, one
    # line that counts them
    assert any("kernel tiers" in ln and "dense x" in ln for ln in lines)
    # a replayed event has the totals and no counts
    replayed = [ln for ln in
                spans.render_span_tree(served["q3"]["event"]["spans"])
                if "host:" in ln]
    assert len(replayed) == per_task
    assert not any(" x" in ln for ln in replayed)


def test_explain_analyze_shows_the_host_account():
    from presto_tpu.server.dqr import DistributedQueryRunner

    with DistributedQueryRunner.tpch(scale=0.002, n_workers=2) as dqr:
        rows = dqr.execute("explain analyze " + QUERIES[6]).rows
    text = "\n".join(r[0] for r in rows)
    assert "host ms: generate " in text
    assert "xla: " in text and "trace+lower" in text
