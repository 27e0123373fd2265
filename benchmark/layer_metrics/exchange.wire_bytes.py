"""Bytes the tasks put on the exchange wire per query
(``queryStats.output_bytes``), median over the window's queries."""

from benchmark import accounts

LAYER = "exchange wire"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "query_geomean_s"


def read(run: dict):
    return accounts.median_per_query(run, "details",
                                     accounts.query_stat("output_bytes"))
