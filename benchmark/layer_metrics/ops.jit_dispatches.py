"""Jitted programs dispatched per query (``queryStats.jit_dispatches``),
median over the window's queries."""

from benchmark import accounts

LAYER = "operators, fusion"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "query_geomean_s"


def read(run: dict):
    return accounts.median_per_query(run, "details",
                                     accounts.query_stat("jit_dispatches"))
