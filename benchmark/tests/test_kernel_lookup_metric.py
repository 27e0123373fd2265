"""The reader of the kernel-cache lookup (PR 42): ``ops.kernel_lookup_s``
over the span tree's ``kernel_lookup`` activities; on hand-built ``run``
dicts and on a recorded one (``fixtures/kernel_lookup_run.json``: a warm
Q1 and a warm Q3 at SF0.01, served on the CPU; its numbers are CPU
numbers, used here for arithmetic only)."""

import copy
import json
import os

import pytest

from benchmark import manifest

NAME = "ops.kernel_lookup_s"
HERE = os.path.dirname(os.path.abspath(__file__))


def reader():
    return manifest.load_module("layer_metrics", NAME)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "kernel_lookup_run.json")) as f:
        return dict(json.load(f), cell=HTTP)


HTTP = {"config": {"served_by": "http"}}
MESH = {"config": {"served_by": "device"}}


def run_of(trees=(), cell=HTTP):
    return {"samples": [{"query_id": f"q{i}"} for i in range(len(trees))],
            "details": {}, "cell": cell,
            "spans": {f"q{i}": t for i, t in enumerate(trees)}}


def test_reader_repeats_its_manifest_entry():
    entry = [m for m in manifest.benchmark_json()["per_layer"]
             if m["name"] == NAME][0]
    module = reader()
    assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    for name in entry["workloads"]:
        assert manifest.cell(name)["config"]["served_by"] == module.PLANE


def lookup(start, end):
    return {"name": "kernel_lookup", "kind": "activity", "start": start,
            "end": end, "durationS": end - start, "children": [],
            "attributes": {"count": 1, "busyS": end - start}}


def tree(tasks, kinds=("kernel_lookup", "dispatch")):
    return {"name": "q", "kind": "query", "start": 0.0, "end": 10.0,
            "children": [{"name": "stage-0", "kind": "stage", "start": 0.0,
                          "end": 10.0, "children": [
                              {"name": f"t{i}", "kind": "task",
                               "start": 0.0, "end": 10.0, "children": acts,
                               "attributes": {
                                   "activityTruncated": False,
                                   "hostSeconds": dict.fromkeys(kinds,
                                                                0.0)}}
                              for i, acts in enumerate(tasks)]}]}


def test_kernel_lookup_s_is_the_union_over_tasks():
    # task a: 1-2 and 5-5.5; task b: 1.5-3: the union is 1-3 and 5-5.5
    t = tree([[lookup(1.0, 2.0), lookup(5.0, 5.5)], [lookup(1.5, 3.0)]])
    read = reader().read
    assert read(run_of(trees=[t])) == pytest.approx(2.5)
    # a query whose tasks know the kind and never entered it reads 0
    dispatch = dict(lookup(1.0, 2.0), name="dispatch")
    assert read(run_of(trees=[tree([[dispatch]])])) == 0.0


def test_kernel_lookup_s_is_none_for_a_program_before_the_kind():
    """The parent's task spans total every kind it has, and not this one;
    its trees would otherwise read 0."""
    read = reader().read
    old = tree([[]], kinds=("generate", "dispatch"))
    assert read(run_of(trees=[old])) is None
    assert read(run_of(trees=[tree([])])) is None
    assert read(run_of()) is None


def test_reader_on_the_recorded_run(recorded):
    assert reader().read(recorded) > 0


def test_kept_off_the_collective_plane():
    """A mesh query makes its one kernel-cache lookup in no bracket, yet its
    root task's totals name every kind (the query thread's recorder): the
    reader reads nothing there, where the tree alone would read a false 0."""
    mesh = tree([[dict(lookup(1.0, 2.0), name="dispatch")]],
                kinds=("generate", "kernel_lookup", "dispatch", "lock_wait"))
    assert reader().read(run_of(trees=[mesh], cell=MESH)) is None
    assert reader().read(run_of(trees=[mesh])) == 0.0


def test_the_recorded_run_as_a_parent_would_give_it(recorded):
    """The same queries with what a program before PR 42 lacks taken out:
    no ``kernel_lookup`` kind."""
    old = copy.deepcopy(recorded)
    for t in old["spans"].values():
        for stage in t["children"]:
            for task in stage.get("children", []):
                if task["kind"] != "task":
                    continue
                task["attributes"]["hostSeconds"].pop("kernel_lookup", None)
                task["children"] = [c for c in task["children"]
                                    if c["name"] != "kernel_lookup"]
    assert reader().read(old) is None
