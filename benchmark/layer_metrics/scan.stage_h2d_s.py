"""Wall seconds a query spends with at least one task staging a host batch
for the device (span kind ``stage_h2d``: coalescing, padding to the bucket,
``device_put``), median over the window's queries."""

from benchmark import activity

LAYER = "staging host to device"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("stage_h2d",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
