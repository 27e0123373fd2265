"""Metric arithmetic over the window's samples (plain Python, no engine)."""

from __future__ import annotations

import math
import statistics


def median_wall_by_statement(samples: list) -> dict:
    by: dict = {}
    for s in samples:
        by.setdefault(s["statement"], []).append(s["wall_s"])
    return {name: statistics.median(walls) for name, walls in by.items()}


def geomean_of_medians(samples: list, statements: list) -> float | None:
    """Geometric mean, over ``statements``, of each one's median wall (the
    shape of TPC-H's power metric: statements that differ fourfold weigh
    the same).  None unless every statement has a sample."""
    med = median_wall_by_statement(samples)
    if not statements or any(name not in med for name in statements):
        return None
    return math.exp(sum(math.log(med[n]) for n in statements)
                    / len(statements))


def percentile(values: list, q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, as numpy's default; None for no values."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def distribution(values: list) -> dict | None:
    """How the values lie: their count, the least, the quartiles and the
    greatest.  A log then shows a grid, a second mode or a window of seven
    samples without a side script.  None for no values."""
    if not values:
        return None
    return {"n": len(values), "min": min(values),
            "q1": percentile(values, 25.0), "median": percentile(values, 50.0),
            "q3": percentile(values, 75.0), "max": max(values)}


def completed_per_hour(samples: list, window_start: float) -> float | None:
    """Statements completed per hour, over the time from the window's start
    to the last completion (all the work over all the time: the statements
    in flight when the window closes finish and count, so the rate does not
    move in steps of one query)."""
    if not samples:
        return None
    span = max(s["end"] for s in samples) - window_start
    return len(samples) * 3600.0 / span if span > 0 else None
