"""Wall seconds a query spends with at least one task inside the call of a
jitted program (span kind ``dispatch``: enqueue time, and the copy of host
arguments where a program is handed them), median over the window's
queries."""

from benchmark import activity

LAYER = "operators, fusion"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("dispatch",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
