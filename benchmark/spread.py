#!/usr/bin/env python3
"""How widely a set of runs of one cell spreads, read the way the check of
a PR reads it: what a bound is measured from.

    python3 benchmark/spread.py <set directory or result files> [<second set> ...]

A set is the result lines (the last line of each run's standard output) of
runs of the same code.  A set's spread of a metric is the distance between
its runs' values as a share of their median, leaving out the run farthest
from the median where that narrows it, so that one far-off run does no harm
and two do.  Two distances are printed: ``range``, greatest less least, and
``iqr``, third less first quartile as ``statistics.quantiles(values, n=4)``
gives them.  The range is the wider and the one a bound is taken from: a
new cell is refused where the mean of its two sets' spreads is over half of
the bound.  Plain Python: no engine, no JAX.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def value_range(values: list) -> float:
    return max(values) - min(values)


def iqr(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def less_farthest(values: list) -> list:
    """``values`` without the one farthest from their median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def spread(values: list, distance=value_range) -> float | None:
    """``distance`` of the values over their median, leaving out the value
    farthest from the median where that narrows it (and at least three
    stay).  None for fewer than two values or a median of 0."""
    med = statistics.median(values) if len(values) >= 2 else 0.0
    if not med:
        return None
    d = distance(values)
    if len(values) > 3:
        d = min(d, distance(less_farthest(values)))
    return d / abs(med)


def result_line(file: str) -> dict | None:
    """The last line of a run's output, if it is a result."""
    with open(file) as f:
        lines = [line for line in f.read().splitlines() if line.strip()]
    try:
        line = json.loads(lines[-1]) if lines else None
    except ValueError:
        return None
    return line if isinstance(line, dict) and "metrics" in line else None


def read_set(where: str) -> list:
    """The result lines of one set: a directory of ``*.out`` files, or one
    file."""
    files = (sorted(glob.glob(os.path.join(where, "*.out")))
             if os.path.isdir(where) else [where])
    return [line for line in map(result_line, files) if line is not None]


def set_values(results: list) -> dict:
    """{metric: [value of each run that reports it]}"""
    out: dict = {}
    for r in results:
        for name, m in r["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def summary(sets: list) -> dict:
    """{metric: {"sets": [{n, median, range, iqr, values} or None for a set
    without the metric], "mean_range", "mean_iqr",
    "second_median_over_first" (two sets)}} for sets of result lines."""
    per_set = [set_values(s) for s in sets]
    out = {}
    for name in sorted({n for values in per_set for n in values}):
        rows = [{"n": len(v), "median": statistics.median(v),
                 "range": spread(v), "iqr": spread(v, iqr), "values": v}
                if v else None
                for v in (values.get(name) for values in per_set)]
        entry: dict = {"sets": rows}
        for key in ("range", "iqr"):
            found = [r[key] for r in rows if r and r[key] is not None]
            entry["mean_" + key] = statistics.mean(found) if found else None
        if len(rows) == 2 and all(rows):
            entry["second_median_over_first"] = (rows[1]["median"]
                                                 / rows[0]["median"])
        out[name] = entry
    return out


def main(argv=None) -> int:
    places = (sys.argv[1:] if argv is None else argv)
    if not places:
        print(__doc__, file=sys.stderr)
        return 2
    sets = [read_set(p) for p in places]
    for name, entry in summary(sets).items():
        for place, row in zip(places, entry["sets"]):
            print(json.dumps({"metric": name, "set": place, **(row or {})}))
        print(json.dumps({"metric": name,
                          **{k: v for k, v in entry.items() if k != "sets"}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
