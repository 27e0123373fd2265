"""Wall seconds a query spends with at least one task blocked reading a value
back from the device (span kind ``device_wait``), median over the window's
queries."""

from benchmark import activity

LAYER = "operators, fusion"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("device_wait",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
