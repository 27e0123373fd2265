"""Wall seconds a query served by the collective plane waits for
``mesh_executor_lock`` (span kind ``lock_wait``: one SPMD program runs at a
time, so with several clients this is the queue behind the others' locked
sections), median over the window's queries."""

from benchmark import accounts, activity

LAYER = "collective plane"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KINDS = ("lock_wait",)
PLANE = "device"


def read(run: dict):
    if accounts.served_by(run) != PLANE:
        return None
    return activity.median_kind_seconds(run, KINDS)
