"""Wall seconds a query served by the collective plane spends blocked reading
program outputs back (span kind ``device_wait``: the control outputs, of
which the first waits for the SPMD program, beacons' host callbacks
included, and the compacted rows), median over the window's queries."""

from benchmark import activity

LAYER = "collective plane"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("device_wait",)


def read(run: dict):
    return activity.median_kind_seconds(run, KINDS)
