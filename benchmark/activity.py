"""Helpers for the readers of host activity: what a query's tasks were
doing on the host, from the ``activity`` children of the task spans in its
span tree (``run['spans']``; recorded by ``presto_tpu/spans.py``, kinds in
``spans.ACTIVITY_KINDS``).  A query of the collective plane has no task
threads: its query thread records for the length of the ``execute`` phase
(``lock_wait``, ``dispatch``, ``device_wait``), and the intervals hang under
the root fragment's task span, so they are read here like any task's (the
``mesh.*`` readers of ``mesh4w.repeat-q1``).  A tree from a program that
records none (a commit before the recorder; a mesh query before PR 31) has
no such child, and every function here then finds nothing to read."""

from __future__ import annotations

from benchmark import accounts, trace_reduce


def task_spans(tree: dict) -> list:
    return [task for stage in tree.get("children", [])
            if stage["kind"] == "stage"
            for task in stage.get("children", []) if task["kind"] == "task"]


def intervals(tree: dict, kinds=None) -> list | None:
    """[[start, end]] (epoch seconds) of the query's activity spans of
    ``kinds`` (all kinds if None), over all its tasks.  None if no task
    has an activity child, or one of them dropped intervals."""
    tasks = task_spans(tree)
    if any(t.get("attributes", {}).get("activityTruncated")
           for t in tasks):
        return None
    spans = [c for t in tasks for c in t.get("children", [])
             if c["kind"] == "activity"]
    if not spans:
        return None
    return [[c["start"], c["end"]] for c in spans
            if kinds is None or c["name"] in kinds]


def kind_seconds(tree: dict, kinds) -> float | None:
    """Wall seconds during which at least one of the query's tasks was
    inside one of ``kinds``: the union over its tasks, so two tasks that
    generate at once count once."""
    found = intervals(tree, kinds)
    return None if found is None \
        else trace_reduce.total(trace_reduce.union(found))


def median_kind_seconds(run: dict, kinds) -> float | None:
    return accounts.median_per_query(
        run, "spans", lambda tree: kind_seconds(tree, kinds))


def overlap(a: list, b: list) -> float:
    """Seconds that lie both in ``a`` and in ``b``, each a sorted list of
    disjoint [start, end] (what ``trace_reduce.union`` returns)."""
    seconds, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            seconds += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return seconds


def complement(intervals_: list, lo: float, hi: float) -> list:
    """What [lo, hi] holds outside the sorted, disjoint ``intervals_``."""
    gaps, at = [], lo
    for s, e in intervals_:
        if s > at:
            gaps.append([at, min(s, hi)])
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append([at, hi])
    return gaps
