"""From the profiler's trace to device busy time, idle share, the device
operations that took most time and the idle gaps labelled by what the host
was doing.

Two steps, so that the arithmetic is checked on a small recorded trace
(``tests/fixtures``) without the profiler: ``load`` turns an ``.xplane.pb``
into plain lists, ``reduce`` does the rest.  Times inside a trace count
from the start of the profiling session; the benchmark writes
``bench.clock:<epoch ns>`` annotations into the host's plane, and
``clock_offset_ns`` reads them back to put device events on the host's
clock, where the coordinator's spans are.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PLANE = "/device:TPU:"     # one plane per chip
OPS_LINE = "XLA Ops"              # one event per executed HLO op
MODULES_LINE = "XLA Modules"      # one event per executed program
CLOCK_MARK = "bench.clock:"
HLO_OP = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[^\]]*\])?")


def newest_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def short_op(name: str) -> str:
    """The chip's trace names an op by its whole HLO text (up to some
    thousand characters).  Keep its name, the first array it yields and
    its fusion kind: ``fusion.293 pred[65536] kLoop``."""
    m = HLO_OP.match(name)
    if not m:
        return name[:80]
    kind = re.search(r"kind=(k\w+)", name)
    return " ".join(p for p in (m.group(1), m.group(2),
                                kind and kind.group(1)) if p)


def load(xplane_path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start ns,
    duration ns], ...]}]}]} with what ``reduce`` reads: of a device plane
    the op and module lines (op names shortened), of the host planes the
    clock marks (host threads are most of a trace's size)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if on_device or e.name.startswith(CLOCK_MARK)]
            if on_device and line.name == OPS_LINE:
                for event in events:
                    event[0] = short_op(event[0])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def heaviest(seconds_by_name: dict, top: int) -> list:
    """The ``top`` entries of {name: seconds}, as [[name, seconds], ...]."""
    return [[name, s] for name, s in
            sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:top]]


def outline(trace: dict, top: int = 8) -> list:
    """Planes, lines, event counts and the heaviest names: for reading a
    trace by hand."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            by: dict = {}
            for name, _s, d in line["events"]:
                by[name] = by.get(name, 0.0) + d / 1e9
            out.append({"plane": plane["name"], "line": line["name"],
                        "events": len(line["events"]),
                        "first_ns": min((e[1] for e in line["events"]),
                                        default=None),
                        "last_ns": max((e[1] + e[2] for e in line["events"]),
                                       default=None),
                        "heaviest": heaviest(by, top)})
    return out


def union(intervals: list) -> list:
    """Overlapping or touching [start, end] intervals merged, sorted."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def clock_offset_ns(trace: dict) -> float | None:
    """Epoch ns minus trace ns, from the ``bench.clock`` marks (median)."""
    offsets = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            for name, start, _d in line["events"]:
                if name.startswith(CLOCK_MARK):
                    offsets.append(int(name[len(CLOCK_MARK):]) - start)
    return statistics.median(offsets) if offsets else None


def flatten_spans(trees: dict) -> list:
    """[(label, start, end)] on the host's clock from {statement label:
    [span tree, ...]}: coordinator phases and stages (a stage's tasks lie
    inside it and add nothing to a label)."""
    flat = []
    for label, roots in trees.items():
        for root in roots:
            flat.append((f"{label} (other)", root["start"], root["end"]))
            for child in root.get("children", []):
                if child["kind"] in ("phase", "stage"):
                    flat.append((f"{label} {child['name']}",
                                 child["start"], child["end"]))
    return flat


def label_of(t: float, spans: list) -> str:
    """The most specific (shortest) span that covers ``t``."""
    covering = [(e - s, label) for label, s, e in spans if s <= t <= e]
    return min(covering)[1] if covering else "no query in flight"


def reduce(trace: dict, start: float, end: float, spans: list,
           top: int = 10) -> dict | None:
    """Device busy and idle over the window [start, end] (epoch seconds).

    ``busy_s`` is the union of the device-op intervals on each device,
    averaged over the devices that ran anything; ``idle_gaps`` are the
    stretches in which no device ran anything, summed by the label of the
    span that covers the gap's middle.  None when the trace has no device
    operation or no clock mark."""
    offset = clock_offset_ns(trace)
    per_device, ops = {}, {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        if not lines.get(OPS_LINE):
            continue
        # an op belongs to the program (module) that was running then
        modules = sorted((s, s + d, name)
                         for name, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        ivs = per_device.setdefault(plane["name"], [])
        for name, s, d in lines[OPS_LINE]:
            ivs.append([s, s + d])
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < modules[i][1]:
                name = f"{modules[i][2]}/{name}"
            ops[name] = ops.get(name, 0.0) + d
    if offset is None or not per_device:
        return None

    # seconds from the window's start, worked out in whole ns of the
    # trace's own clock: epoch seconds as floats resolve only 0.2 us
    zero_ns = int(start * 1e9) - int(offset)
    window_s = end - start

    def rel(ns: float) -> float:
        return (ns - zero_ns) / 1e9

    busy = {dev: clip([[rel(s), rel(e)] for s, e in union(ivs)],
                      0.0, window_s) for dev, ivs in per_device.items()}
    n = len(busy)
    busy_s = sum(total(ivs) for ivs in busy.values()) / n
    any_busy = union([list(iv) for ivs in busy.values() for iv in ivs])
    spans = [(label, s - start, e - start) for label, s, e in spans]
    gaps, at = {}, 0.0
    for s, e in any_busy + [[window_s, window_s]]:
        if s > at:
            label = label_of((at + s) / 2.0, spans)
            gaps[label] = gaps.get(label, 0.0) + (s - at)
        at = max(at, e)
    return {
        "devices": n, "window": [start, end], "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "busy": busy,
        "device_ops": heaviest({name: d / 1e9 / n
                                for name, d in ops.items()}, top),
        "idle_gaps": heaviest(gaps, top),
    }


def busy_within(reduced: dict, intervals: list) -> float:
    """Device busy seconds inside ``intervals`` (epoch seconds), averaged
    over the devices, as ``busy_s`` is."""
    inside, zero = 0.0, reduced["window"][0]
    for lo, hi in union([[s - zero, e - zero] for s, e in intervals]):
        inside += sum(total(clip(ivs, lo, hi))
                      for ivs in reduced["busy"].values())
    return inside / reduced["devices"]
