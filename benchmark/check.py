"""The comparison that decides ``correct``.

One operation is one statement submitted by one client.  It fails when it
raised, when its rows differ from the plain reference, when the coordinator
says the answer came from the result cache, or when the configuration
promises the collective plane and the query was not served by it.
"""

from __future__ import annotations

import math


def compare(got: list, want: list, rtol: float) -> float:
    """Row count, order, integers, keys and dates exact; DOUBLE to
    ``rtol``.  Returns the largest relative error over the DOUBLE cells;
    raises AssertionError on any miss."""
    if len(got) != len(want):
        raise AssertionError(f"{len(got)} rows, reference has {len(want)}")
    worst = 0.0
    for r, (g_row, w_row) in enumerate(zip(got, want)):
        if len(g_row) != len(w_row):
            raise AssertionError(f"row {r}: width {len(g_row)}, reference "
                                 f"has {len(w_row)}")
        for g, w in zip(g_row, w_row):
            if isinstance(w, float):
                if not isinstance(g, (int, float)) or not math.isfinite(g):
                    raise AssertionError(f"row {r}: {g!r} is not a finite "
                                         "number")
                rel = abs(g - w) / max(abs(w), 1e-300)
                worst = max(worst, rel)
                if rel > rtol:
                    raise AssertionError(
                        f"row {r}: {g!r} vs reference {w!r} "
                        f"(rel {rel:.3e} > {rtol})")
            elif g != w:
                raise AssertionError(f"row {r}: {g!r} != reference {w!r}")
    return worst


def not_served_as_promised(detail: dict, config: dict,
                           counted_fallbacks: dict) -> str | None:
    """Why this query's detail (``GET /v1/query/{id}``) breaks what the
    configuration guarantees, or None."""
    if detail.get("resultCached"):
        return "served from the result cache"
    if config["served_by"] == "device":
        modes = detail.get("exchangeModes") or {}
        info = detail.get("deviceExchange") or {}
        if set(modes) != {"device"}:
            return f"exchangeModes {modes}, want only 'device'"
        if "fallback" in info:
            return f"device exchange fell back: {info['fallback']}"
        if counted_fallbacks:
            return f"coordinator counted fallbacks {counted_fallbacks}"
    return None


def judge(op: dict, want: list, detail: dict | None, config: dict,
          counted_fallbacks: dict) -> str | None:
    """Why operation ``op`` (a sample of load.py) failed, or None.  Sets
    ``op['max_rel_err']`` when the rows were compared."""
    if op.get("error"):
        return op["error"]
    try:
        op["max_rel_err"] = compare(op["rows"], want,
                                    config["guarantees"]["double_rtol"])
    except AssertionError as e:
        return f"differs from the reference: {e}"
    if detail is None:
        return "no query detail to check how it was served"
    return not_served_as_promised(detail, config, counted_fallbacks)
