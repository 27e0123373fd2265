"""Wall seconds a query served by the collective plane spends inside the
calls of its jitted programs (span kind ``dispatch``: the whole-query SPMD
program and the program that compacts its result, from call to return),
median over the window's queries."""

from benchmark import activity

LAYER = "collective plane"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("dispatch",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
