"""Wall seconds a query spends with at least one task inside the connector's
page source (span kind ``generate``: the host makes the rows of a scan),
median over the window's queries."""

from benchmark import activity

LAYER = "connector scan"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("generate",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
