"""The readers of host activity and of the program's compile account: on
hand-built ``run`` dicts, on the recorded chip trace with synthetic
activities, and in a traced CPU rehearsal of ``http2w.join``."""

import json
import os

import pytest

from benchmark import activity, manifest, trace_reduce

SPAN_METRICS = {"scan.generate_s": "generate",
                "scan.stage_h2d_s": "stage_h2d",
                "ops.dispatch_s": "dispatch",
                "ops.device_wait_s": "device_wait",
                "exchange.serde_s": "serialize",
                "exchange.wait_s": "exchange_wait"}
NEW = sorted(SPAN_METRICS) + ["trace.idle_attributed",
                              "xla.trace_lower_s.setup"]


def reader(name):
    return manifest.load_module("layer_metrics", name)


def act(kind, start, end):
    return {"name": kind, "kind": "activity", "start": start, "end": end,
            "durationS": end - start, "children": [],
            "attributes": {"count": 1, "busyS": end - start}}


def task(name, children, truncated=False):
    return {"name": name, "kind": "task", "start": 0.0, "end": 100.0,
            "children": children,
            "attributes": {"activityTruncated": truncated}}


def tree(tasks):
    return {"name": "q", "kind": "query", "start": 0.0, "end": 100.0,
            "children": [
                {"name": "execute", "kind": "phase", "start": 0.0,
                 "end": 100.0, "durationS": 100.0, "children": []},
                {"name": "stage-0", "kind": "stage", "start": 0.0,
                 "end": 100.0, "children": tasks}]}


def run_of(trees, **more):
    run = {"samples": [{"query_id": f"q{i}"} for i in range(len(trees))],
           "spans": {f"q{i}": t for i, t in enumerate(trees)},
           "details": {}, "trace": None, "setup": {"warm": {}}}
    run.update(more)
    return run


@pytest.mark.parametrize("name", NEW)
def test_reader_repeats_its_manifest_entry(name):
    entry = [m for m in manifest.benchmark_json()["per_layer"]
             if m["name"] == name][0]
    module = reader(name)
    assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    # every cell, on either plane, present or added later
    assert "workloads" not in entry


@pytest.mark.parametrize("name, kind", sorted(SPAN_METRICS.items()))
def test_union_across_two_overlapping_tasks(name, kind):
    # task a: 1-3 and 10-11; task b: 2-5: the union is 1-5 and 10-11
    t = tree([task("a", [act(kind, 1.0, 3.0), act(kind, 10.0, 11.0),
                         act("other", 20.0, 90.0)]),
              task("b", [act(kind, 2.0, 5.0)])])
    assert reader(name).read(run_of([t])) == pytest.approx(5.0)


def test_median_over_the_windows_queries():
    trees = [tree([task("a", [act("generate", 0.0, s)])])
             for s in (1.0, 2.0, 9.0)]
    assert reader("scan.generate_s").read(run_of(trees)) == \
        pytest.approx(2.0)


def test_a_kind_the_query_never_entered_reads_zero():
    t = tree([task("a", [act("generate", 1.0, 2.0)])])
    assert reader("exchange.wait_s").read(run_of([t])) == 0.0


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_none_when_a_task_dropped_intervals(name):
    kind = SPAN_METRICS[name]
    t = tree([task("a", [act(kind, 1.0, 3.0)]),
              task("b", [act(kind, 2.0, 5.0)], truncated=True)])
    assert reader(name).read(run_of([t])) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_none_where_no_task_has_an_activity_child(name):
    """A commit before the recorder, on either plane."""
    bare = {"name": "t", "kind": "task", "start": 0.0, "end": 1.0,
            "children": [], "attributes": {"attempt": 0}}
    assert reader(name).read(run_of([tree([bare])])) is None
    assert reader(name).read(run_of([tree([])])) is None
    assert reader(name).read(run_of([])) is None


def test_overlap_and_complement():
    assert activity.complement([[1, 2], [4, 6]], 0, 5) == [[0, 1], [2, 4]]
    assert activity.complement([], 0, 3) == [[0, 3]]
    assert activity.complement([[0, 3]], 0, 3) == []
    assert activity.overlap([[0, 1], [2, 4]], [[0.5, 3]]) == \
        pytest.approx(1.5)
    assert activity.overlap([], [[0, 1]]) == 0.0


def test_idle_attributed_on_a_hand_made_slice():
    # 10 s slice, the one device busy 2-4 and 6-7: 7 s idle.  Activities
    # (epoch = slice + 1000) cover 0-3 and 6.5-9: idle inside them is
    # 0-2 and 7-9 = 4 s
    reduced = {"window": [1000.0, 1010.0], "window_s": 10.0,
               "busy": {"/device:TPU:0": [[2.0, 4.0], [6.0, 7.0]]}}
    t = tree([task("a", [act("generate", 1000.0, 1003.0)]),
              task("b", [act("exchange_wait", 1006.5, 1009.0)])])
    read = reader("trace.idle_attributed").read
    assert read(run_of([t], trace=reduced)) == pytest.approx(400.0 / 7.0)
    assert read(run_of([t], trace=None)) is None
    assert read(run_of([tree([])], trace=reduced)) is None


def test_idle_attributed_on_the_recorded_trace():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "fixtures", "chip_trace.json")) as f:
        fx = json.load(f)
    reduced = trace_reduce.reduce(fx["trace"], fx["start"], fx["end"],
                                  [tuple(s) for s in fx["spans"]])
    idle_s = reduced["window_s"] - reduced["busy_s"]    # one device
    read = reader("trace.idle_attributed").read

    def share(children):
        return read(run_of([tree([task("a", children)])], trace=reduced))

    # one activity over the whole slice explains all of the idle time,
    # one over its first half no more than that half holds, none at all
    # outside the slice
    whole = [act("generate", fx["start"] - 1.0, fx["end"] + 1.0)]
    assert share(whole) == pytest.approx(100.0)
    mid = (fx["start"] + fx["end"]) / 2.0
    half = share([act("dispatch", fx["start"], mid)])
    busy_first = trace_reduce.busy_within(reduced, [[fx["start"], mid]])
    assert half == pytest.approx(
        100.0 * ((mid - fx["start"]) - busy_first) / idle_s)
    assert share([act("serialize", fx["end"] + 1.0,
                      fx["end"] + 2.0)]) == 0.0


def test_trace_lower_sums_each_statements_first_execution():
    warm = {"q1": {"ops": [{"query_id": "a"}, {"query_id": "b"}]},
            "q6": {"ops": [{"query_id": "c"}]}}
    details = {"a": {"queryStats": {"xla_trace_lower_ns": 3_000_000_000}},
               "b": {"queryStats": {"xla_trace_lower_ns": 7}},
               "c": {"queryStats": {"xla_trace_lower_ns": 500_000_000}}}
    read = reader("xla.trace_lower_s.setup").read
    assert read({"setup": {"warm": warm}, "details": details}) == \
        pytest.approx(3.5)
    # a program without the account, or a detail that was not fetched
    details["c"] = {"queryStats": {"jit_compiles": 1}}
    assert read({"setup": {"warm": warm}, "details": details}) is None
    assert read({"setup": {"warm": warm}, "details": {}}) is None


def test_join_cell_traced_reads_the_new_metrics():
    from test_rehearsal import rehearse

    _cell, result = rehearse("http2w.join", trace=True)
    got = result["metrics"]
    assert result["correct"]
    for name in SPAN_METRICS:
        assert name in got and got[name]["value"] >= 0.0, name
        assert got[name]["unit"] == "s"
    # scan.generate_s is not among them: since tables stay on the device after
    # their first scan (PR 32) only set-up generates, and a window query reads 0
    for name in ("ops.dispatch_s", "exchange.serde_s", "exchange.wait_s",
                 "xla.trace_lower_s.setup"):
        assert got[name]["value"] > 0.0, name
    # the CPU has no device plane: nothing to attribute idle time of
    assert "trace.idle_attributed" not in got
    # and what the rehearsal that was there asserts still holds
    for name in ("coord.plan_s", "worker.leaf_stage_s",
                 "ops.jit_dispatches", "exchange.wire_bytes",
                 "xla.compiles.window", "xla.compile_s.setup",
                 "warmup.first_exec_s"):
        assert name in got, name
    assert got["xla.compiles.window"]["value"] == 0
    assert "device.idle_share" not in got
    assert "breakdown" not in result and "busy_s" not in result["device"]
