"""Timed query spans: where a query's wall clock went.

The airlift stage-timing role (the reference attributes wall time to
dispatch/queue/planning/scheduling phases on the coordinator and to
per-stage/task execution on the workers; the web UI renders it as the
query timeline).  Here the coordinator records a ``QuerySpan`` tree from
timestamps it already owns:

    query
    ├── queue            (create -> admission)
    ├── parse / analyze / optimize / fragment / schedule
    ├── execute          (drain span)
    └── stage-{fid}
        └── task {task_id} (attempt aN)   one span per task attempt
            └── generate / stage_h2d / dispatch / ...   host activity
                (collective plane: what the query thread did inside
                ``execute``, under the root fragment's task)

What the host did inside a task is recorded by the task's
``HostActivity`` through ``activity(kind)`` (below): intervals on the
same epoch clock as every other span, and, while a profile is being
taken, ``host:<kind>`` events in the profiler's host plane.

Every span carries the query's trace token as its trace id, wall-clock
``start``/``end`` (epoch seconds), and nests inside its parent (the
builder clamps children into the query window, so ``end >= start``
always holds).  The tree is served at ``/v1/query/{id}/spans``,
serialized into ``QueryCompletedEvent``/query.json, and rendered by
``tools/query_profile.py`` as the ASCII timeline.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class QuerySpan:
    """One timed span; ``kind`` is query | phase | stage | task |
    activity."""

    name: str
    kind: str
    start: float
    end: float
    trace_token: str = ""
    attributes: Dict = dataclasses.field(default_factory=dict)
    children: List["QuerySpan"] = dataclasses.field(default_factory=list)

    def as_dict(self) -> Dict:
        return {
            "name": self.name, "kind": self.kind,
            "start": round(self.start, 6), "end": round(self.end, 6),
            "durationS": round(max(self.end - self.start, 0.0), 6),
            "traceToken": self.trace_token,
            "attributes": dict(self.attributes),
            "children": [c.as_dict() for c in self.children],
        }


#: coordinator phase order in the rendered timeline.  "lower" and
#: "compile" exist only for device-exchange queries that BUILT their
#: SPMD program this run (trace+lower wall vs XLA-compile wall, the
#: kernelcache.timed_first_call attribution); a program-cache hit
#: records neither and its query reports compile_ns=0.
PHASES = ("queue", "parse", "analyze", "optimize", "fragment", "schedule",
          "lower", "compile", "execute")


#: what a task's host threads can be doing, each bracketed where the work
#: happens (the kind's reader is in PERF.md's table of spans and counters):
#: generate      the connector's page source produces a batch
#: stage_h2d     padding a host batch and putting it on the device
#: dispatch      the call of a jitted program, from call to return
#:               (enqueue time, unless the call traces and compiles)
#: device_wait   host code reads a device value and blocks until it is there
#: serialize     encoding + LZ4 of an exchange page, and decoding on the
#:               consumer
#: exchange_wait an exchange-fed operator parked until a page arrives
#: lock_wait     a query of the collective plane waiting for
#:               ``mesh_executor_lock`` (one SPMD program at a time)
#: A query served by the collective plane has no task threads: its query
#: thread records for the length of the ``execute`` phase, and the
#: intervals hang under the root fragment's (synthetic) task span.
ACTIVITY_KINDS = ("generate", "stage_h2d", "dispatch", "device_wait",
                  "serialize", "exchange_wait", "lock_wait")

#: GET /v1/task/{id} with this header set to 1 adds ``hostActivity``, the
#: task's intervals, to the info: the coordinator's final collection
HOST_ACTIVITY_HEADER = "X-Presto-Host-Activity"

MERGE_GAP_NS = 1_000_000     # same-kind intervals closer than this are one
MAX_INTERVALS = 4096         # per task; totals stay exact beyond it


class HostActivity:
    """One task's record of what its host threads did: per-kind
    nanosecond totals (always exact; thread-seconds, so the feed drivers
    of one task add up), the wall intervals behind them (merged when one
    kind repeats within ``MERGE_GAP_NS``, at most ``MAX_INTERVALS`` and
    ``truncated`` beyond), and the XLA builds JAX made on the task's
    threads (``kernelcache``'s listener charges ``xla``).  Lives on
    ``TaskContext``; the Driver makes it the thread's current recorder.

    The intervals are kept, sent and held by the coordinator as five
    parallel lists, not a list per interval: a finished query leaves a
    few lists behind on either side, not hundreds of objects for the
    garbage collector to walk."""

    __slots__ = ("total_ns", "kinds", "start_ns", "end_ns", "counts",
                 "busy_ns", "truncated", "xla", "_last", "_lock")

    def __init__(self):
        self.total_ns: Dict[str, int] = dict.fromkeys(ACTIVITY_KINDS, 0)
        # interval i: kinds[i] from start_ns[i] to end_ns[i] (epoch
        # clock), counts[i] brackets merged into it, busy_ns[i] inside
        self.kinds: List[str] = []
        self.start_ns: List[int] = []
        self.end_ns: List[int] = []
        self.counts: List[int] = []
        self.busy_ns: List[int] = []
        self.truncated = False
        self.xla: Dict[str, int] = {"builds": 0, "build_ns": 0,
                                    "trace_lower_ns": 0, "cache_hits": 0}
        self._last: Dict[str, int] = {}     # kind -> its newest interval
        self._lock = threading.Lock()

    def add(self, kind: str, start_ns: int, end_ns: int) -> None:
        busy = end_ns - start_ns
        with self._lock:
            self.total_ns[kind] += busy      # KeyError: not a kind
            i = self._last.get(kind)
            if i is not None and start_ns - self.end_ns[i] < MERGE_GAP_NS:
                # also another thread's overlapping interval: the union
                if start_ns < self.start_ns[i]:
                    self.start_ns[i] = start_ns
                if end_ns > self.end_ns[i]:
                    self.end_ns[i] = end_ns
                self.counts[i] += 1
                self.busy_ns[i] += busy
            elif len(self.kinds) < MAX_INTERVALS:
                self._last[kind] = len(self.kinds)
                self.kinds.append(kind)
                self.start_ns.append(start_ns)
                self.end_ns.append(end_ns)
                self.counts.append(1)
                self.busy_ns.append(busy)
            else:
                self.truncated = True

    @property
    def intervals(self) -> List[list]:
        """[[kind, start ns, end ns, merged count, busy ns], ...]."""
        with self._lock:
            return [list(iv) for iv in zip(
                self.kinds, self.start_ns, self.end_ns, self.counts,
                self.busy_ns)]

    def as_dict(self) -> Dict:
        """The task's final info payload (``hostActivity``)."""
        with self._lock:
            return {"kinds": list(self.kinds),
                    "startNs": list(self.start_ns),
                    "endNs": list(self.end_ns),
                    "counts": list(self.counts),
                    "busyNs": list(self.busy_ns),
                    "truncated": self.truncated}


_CURRENT = threading.local()
_annotation = None      # jax.profiler.TraceAnnotation, bound at first use


def set_current_activity(recorder: Optional[HostActivity]
                         ) -> Optional[HostActivity]:
    """Makes ``recorder`` this thread's; returns the one it replaces."""
    previous = getattr(_CURRENT, "recorder", None)
    _CURRENT.recorder = recorder
    return previous


def current_activity() -> Optional[HostActivity]:
    return getattr(_CURRENT, "recorder", None)


class activity:
    """``with activity(kind):`` charges the body to the thread's current
    task under ``kind`` and shows it as ``host:<kind>`` in a profile
    that is being taken.  Does nothing on a thread with no task."""

    __slots__ = ("kind", "_recorder", "_start_ns", "_trace_me")

    def __init__(self, kind: str):
        self.kind = kind

    def __enter__(self) -> "activity":
        recorder = self._recorder = getattr(_CURRENT, "recorder", None)
        if recorder is not None:
            global _annotation
            if _annotation is None:
                from jax.profiler import TraceAnnotation as _annotation
            self._trace_me = _annotation("host:" + self.kind)
            self._trace_me.__enter__()
            self._start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        recorder = self._recorder
        if recorder is not None:
            end_ns = time.time_ns()
            self._trace_me.__exit__(*exc)
            recorder.add(self.kind, self._start_ns, end_ns)
        return False


def _clamp(start: float, end: float, lo: float, hi: float
           ) -> Tuple[float, float]:
    start = min(max(start, lo), hi)
    end = min(max(end, start), hi)
    return start, end


def _attempt_of(task_id: str) -> int:
    """Attempt number from a task id (``{base}aN`` suffix; 0 if none)."""
    tail = task_id.rsplit(".", 1)[-1]
    if "a" in tail:
        try:
            return int(tail.rsplit("a", 1)[1])
        except ValueError:
            return 0
    return 0


def build_span_tree(query_id: str, trace_token: str,
                    create_time: float, end_time: Optional[float],
                    marks: Dict[str, Tuple[float, float]],
                    task_stats: Dict, admit_time: Optional[float] = None,
                    now: Optional[float] = None,
                    task_extras: Optional[Dict[str, Dict]] = None,
                    activities: bool = True) -> Dict:
    """Assemble the span tree from coordinator-owned timestamps.

    ``marks`` holds per-phase (start, end) recorded by the query thread;
    ``task_stats`` is the {fid: [TaskStats dict]} rollup (live sampler
    mid-query, final collection after) whose per-task start/end times
    become the stage/task-attempt spans.  ``task_extras`` is {task id:
    {"operators": [OperatorStats dict], "activity": a final task info's
    ``hostActivity`` or None}}: the operators become the task span's
    ``operators`` attribute, the intervals its ``activity`` children
    (left out with ``activities=False``: the completed event's copy)."""
    t_now = now if now is not None else time.time()
    task_extras = task_extras or {}
    q_end = end_time if end_time is not None else t_now
    q_end = max(q_end, create_time)
    root = QuerySpan(query_id, "query", create_time, q_end, trace_token)
    if admit_time is not None and admit_time > create_time:
        s, e = _clamp(create_time, admit_time, create_time, q_end)
        root.children.append(
            QuerySpan("queue", "phase", s, e, trace_token))
    for name in PHASES:
        if name not in marks:
            continue
        s, e = _clamp(*marks[name], create_time, q_end)
        root.children.append(QuerySpan(name, "phase", s, e, trace_token))
    for fid in sorted(task_stats, key=lambda k: int(k)):
        tss = [ts for ts in task_stats[fid] if ts.get("start_time")]
        if not tss:
            continue
        s0 = min(ts["start_time"] for ts in tss)
        e0 = max(ts.get("end_time") or t_now for ts in tss)
        s0, e0 = _clamp(s0, e0, create_time, q_end)
        stage = QuerySpan(f"stage-{fid}", "stage", s0, e0, trace_token,
                          attributes={"fragmentId": int(fid),
                                      "tasks": len(tss)})
        for ts in tss:
            s, e = _clamp(ts["start_time"],
                          ts.get("end_time") or t_now,
                          s0, e0)
            tid = ts.get("task_id", "?")
            extras = task_extras.get(tid) or {}
            recorded = extras.get("activity") or {}
            task = QuerySpan(
                tid, "task", s, e, trace_token,
                attributes={
                    "attempt": _attempt_of(tid),
                    "state": ts.get("state", ""),
                    "outputRows": ts.get("output_rows", 0),
                    "hostSeconds": {k: ns / 1e9 for k, ns in
                                    (ts.get("host_ns") or {}).items()},
                    "activityTruncated": bool(recorded.get("truncated")),
                    "operators": [_operator_entry(o) for o in
                                  extras.get("operators") or []]})
            if activities:
                for kind, a_ns, b_ns, count, busy_ns in zip(
                        *(recorded.get(k) or () for k in (
                            "kinds", "startNs", "endNs", "counts",
                            "busyNs"))):
                    a, b = _clamp(a_ns / 1e9, b_ns / 1e9, s, e)
                    task.children.append(QuerySpan(
                        kind, "activity", a, b, trace_token,
                        attributes={"count": count,
                                    "busyS": busy_ns / 1e9}))
            stage.children.append(task)
        root.children.append(stage)
    return root.as_dict()


def _operator_entry(stats: Dict) -> Dict:
    """One operator's busy time: a sum over its calls, not an interval,
    hence an attribute of the task span and not a child."""
    return {"operator": stats.get("operator", ""),
            "wallS": (stats.get("wall_ns", 0)
                      + stats.get("finish_wall_ns", 0)) / 1e9,
            "inputRows": stats.get("input_rows", 0),
            "outputRows": stats.get("output_rows", 0),
            "jitDispatches": stats.get("jit_dispatches", 0),
            "kernelTier": stats.get("kernel_tier", ""),
            # batches whose partial states a segment kept on the device
            "prereduceHeld": stats.get("prereduce_batches_held", 0),
            # a segment's dispatches that compacted their rows, and
            # those that ended with a row mask and moved nothing
            "compactions": stats.get("compactions", 0),
            "compactionsSkipped": stats.get("compactions_skipped", 0),
            # "hit" / "miss" on the scan of a cached table, else ""
            "scanCache": ("hit" if stats.get("scan_cache_hits")
                          else "miss" if stats.get("scan_cache_misses")
                          else "")}


def validate_span_tree(tree: Dict) -> List[str]:
    """Structural checks (tests + query_profile --check): every child
    nests inside its parent and every span has end >= start.  Returns a
    list of violations (empty = valid)."""
    errors: List[str] = []

    def walk(node: Dict, lo: float, hi: float) -> None:
        s, e = node["start"], node["end"]
        if e < s:
            errors.append(f"{node['name']}: end {e} < start {s}")
        if s < lo - 1e-6 or e > hi + 1e-6:
            errors.append(
                f"{node['name']}: [{s}, {e}] outside parent [{lo}, {hi}]")
        for c in node.get("children", []):
            walk(c, s, e)

    walk(tree, tree["start"], tree["end"])
    return errors


def render_span_tree(tree: Dict, width: int = 40) -> List[str]:
    """ASCII timeline of the span tree (tools/query_profile.py): one
    bar per span, positioned within the query window."""
    t0, t1 = tree["start"], tree["end"]
    total = max(t1 - t0, 1e-6)
    lines = [f"span timeline ({total * 1000:.1f} ms total, "
             f"trace={tree.get('traceToken', '')})"]

    def bar(s: float, e: float) -> str:
        lo = int((s - t0) / total * width)
        hi = max(int((e - t0) / total * width), lo + 1)
        hi = min(hi, width)
        lo = min(lo, hi - 1)
        return " " * lo + "=" * (hi - lo) + " " * (width - hi)

    def walk(node: Dict, depth: int) -> None:
        label = ("  " * depth + node["name"])[:30]
        lines.append(
            f"  {label:<30} |{bar(node['start'], node['end'])}| "
            f"{node['durationS'] * 1000:>9.1f} ms")
        by_kind: Dict[str, List[float]] = {}
        for c in node.get("children", []):
            if c["kind"] != "activity":
                walk(c, depth + 1)
                continue
            # one line per kind, not one per interval
            acc = by_kind.setdefault(c["name"], [0.0, 0])
            acc[0] += c["attributes"].get("busyS", c["durationS"])
            acc[1] += c["attributes"].get("count", 1)
        if not by_kind:     # the completed event's copy: totals only
            by_kind = {kind: [seconds, 0] for kind, seconds in
                       (node.get("attributes", {}).get("hostSeconds")
                        or {}).items() if seconds}
        for kind in ACTIVITY_KINDS:
            if kind in by_kind:
                seconds, count = by_kind[kind]
                label = ("  " * (depth + 1) + "host:" + kind)[:30]
                lines.append(f"  {label:<30}  {' ' * width}  "
                             f"{seconds * 1000:>9.1f} ms"
                             + (f"  x{count}" if count else ""))
        tiers: Dict[str, int] = {}
        for op in node.get("attributes", {}).get("operators") or []:
            if op.get("kernelTier"):
                tiers[op["kernelTier"]] = tiers.get(op["kernelTier"], 0) + 1
        if tiers:
            label = ("  " * (depth + 1) + "kernel tiers")[:30]
            lines.append(f"  {label:<30}  " + ", ".join(
                f"{tier} x{n}" for tier, n in sorted(tiers.items())))

    walk(tree, 0)
    return lines
