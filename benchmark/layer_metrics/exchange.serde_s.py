"""Wall seconds a query spends with at least one task encoding and compressing
an exchange page, or decoding one (span kind ``serialize``), median over the
window's queries."""

from benchmark import activity

LAYER = "exchange wire"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("serialize",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
