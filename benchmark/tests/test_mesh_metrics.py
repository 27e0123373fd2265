"""The readers of the collective plane's spans (``mesh.execute_s`` and the
three over the host activity that the query thread records inside the
``execute`` phase), on hand-built span trees of a mesh query: phases, one
synthetic stage a fragment, and the ``activity`` children on the root
fragment's task."""

import pytest
from test_activity_metrics import act, run_of

from benchmark import manifest

CELL = "mesh4w.repeat-q1"
KIND_METRICS = {"mesh.lock_wait_s": "lock_wait",
                "mesh.dispatch_s": "dispatch",
                "mesh.device_wait_s": "device_wait"}


def reader(name):
    return manifest.load_module("layer_metrics", name)


def mesh_tree(activities, execute=(10.0, 11.0), truncated=False):
    """A repeat Q1 on four shards: stage-1 (the partial aggregation, four
    synthetic tasks) and stage-0 (the final one, one task that carries what
    the query thread did); every task's window is the execute phase."""
    lo, hi = execute

    def task(name, children):
        return {"name": name, "kind": "task", "start": lo, "end": hi,
                "durationS": hi - lo, "children": children,
                "attributes": {"activityTruncated": truncated
                               and bool(children)}}

    def phase(name, s, e):
        return {"name": name, "kind": "phase", "start": s, "end": e,
                "durationS": e - s, "children": []}

    return {"name": "q", "kind": "query", "start": lo - 0.1, "end": hi,
            "children": [
                phase("parse", lo - 0.1, lo - 0.05),
                phase("execute", lo, hi),
                {"name": "stage-0", "kind": "stage", "start": lo, "end": hi,
                 "children": [task("q.0.0", activities)]},
                {"name": "stage-1", "kind": "stage", "start": lo, "end": hi,
                 "children": [task(f"q.1.{s}", []) for s in range(4)]}]}


# the locked section of one repeat: wait 0.6 s for three others, dispatch,
# read the control outputs, compact, read the rows
REPEAT = [act("lock_wait", 10.0, 10.6), act("dispatch", 10.6, 10.62),
          act("device_wait", 10.62, 10.8), act("dispatch", 10.8, 10.81),
          act("device_wait", 10.81, 10.85)]
WANT = {"mesh.lock_wait_s": 0.6, "mesh.dispatch_s": 0.03,
        "mesh.device_wait_s": 0.22}


@pytest.mark.parametrize("name", ["mesh.execute_s"] + sorted(KIND_METRICS))
def test_reader_repeats_its_manifest_entry(name):
    entry = [m for m in manifest.benchmark_json()["per_layer"]
             if m["name"] == name][0]
    module = reader(name)
    assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == [CELL]
    assert module.LAYER == "collective plane"


@pytest.mark.parametrize("name", sorted(KIND_METRICS))
def test_reads_its_kind_of_the_root_task(name):
    assert reader(name).read(run_of([mesh_tree(REPEAT)])) == \
        pytest.approx(WANT[name])


def test_execute_reads_the_phase_and_holds_the_kinds():
    run = run_of([mesh_tree(REPEAT)])
    execute_s = reader("mesh.execute_s").read(run)
    assert execute_s == pytest.approx(1.0)
    assert sum(reader(n).read(run) for n in KIND_METRICS) <= execute_s


@pytest.mark.parametrize("name", sorted(KIND_METRICS))
def test_median_over_the_windows_queries(name):
    kind = KIND_METRICS[name]
    trees = [mesh_tree([act(kind, 10.0, 10.0 + s)], execute=(10.0, 20.0))
             for s in (1.0, 2.0, 9.0)]
    assert reader(name).read(run_of(trees)) == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(KIND_METRICS))
def test_a_kind_the_query_never_entered_reads_zero(name):
    """One client alone never waits for the lock, yet its tree records
    activity: that reads 0, not nothing."""
    t = mesh_tree([act("generate", 10.0, 10.5)])
    assert reader(name).read(run_of([t])) == 0.0


@pytest.mark.parametrize("name", sorted(KIND_METRICS))
def test_none_for_a_tree_with_no_activity_child(name):
    """What a commit before the mesh recorder serves: phases and synthetic
    tasks, nothing under them."""
    assert reader(name).read(run_of([mesh_tree([])])) is None
    assert reader(name).read(run_of([])) is None
    assert reader(name).read(
        run_of([mesh_tree(REPEAT, truncated=True)])) is None
    # the execute phase is there all the same
    assert reader("mesh.execute_s").read(run_of([mesh_tree([])])) == \
        pytest.approx(1.0)
