"""Helpers for readers: the coordinator's account of the window's queries
(``run['details']``: GET /v1/query/{id}; ``run['spans']``: .../spans)."""

from __future__ import annotations

import statistics

PLAN_PHASES = ("queue", "parse", "analyze", "optimize", "fragment",
               "schedule")


def per_query(run: dict, source: str, value) -> list:
    """``value(account)`` for every window query that has one, Nones
    dropped.  ``source`` is 'details' or 'spans'."""
    out = []
    for op in run["samples"]:
        account = run[source].get(op["query_id"])
        if account is not None:
            v = value(account)
            if v is not None:
                out.append(v)
    return out


def median_per_query(run: dict, source: str, value) -> float | None:
    values = per_query(run, source, value)
    return statistics.median(values) if values else None


def phase_seconds(tree: dict, names) -> float | None:
    found = [c["durationS"] for c in tree.get("children", [])
             if c["kind"] == "phase" and c["name"] in names]
    return sum(found) if found else None


def served_by(run: dict) -> str:
    """The plane that serves the cell: 'http' (the exchange through the
    host) or 'device' (the collective plane)."""
    return run["cell"]["config"]["served_by"]


def query_stat(name: str):
    return lambda detail: (detail.get("queryStats") or {}).get(name)
