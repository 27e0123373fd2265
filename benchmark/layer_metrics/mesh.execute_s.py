"""The coordinator's ``execute`` phase of a query served by the collective
plane (the SPMD program's dispatch, run and drain under
``mesh_executor_lock``), median per window query."""

from benchmark import accounts

LAYER = "collective plane"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"


def read(run: dict):
    return accounts.median_per_query(
        run, "spans",
        lambda tree: accounts.phase_seconds(tree, ("execute",)))
