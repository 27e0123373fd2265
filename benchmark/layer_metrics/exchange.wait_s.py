"""Wall seconds a query spends with at least one exchange-fed task parked
until a page arrives (span kind ``exchange_wait``), median over the window's
queries."""

from benchmark import activity

LAYER = "exchange wire"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("exchange_wait",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
