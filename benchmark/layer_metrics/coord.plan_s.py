"""Coordinator phases before execution (queue through schedule), median per
window query, from the span tree."""

from benchmark import accounts

LAYER = "dispatch / plan / schedule"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"


def read(run: dict):
    return accounts.median_per_query(
        run, "spans",
        lambda tree: accounts.phase_seconds(tree, accounts.PLAN_PHASES))
