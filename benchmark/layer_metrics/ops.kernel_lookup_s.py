"""Wall seconds a query spends with at least one task building a
kernel-cache key and looking it up (span kind ``kernel_lookup``, before
each program's call and outside its ``dispatch``), median over the
window's queries.  A program before the kind (its task spans' totals,
``hostSeconds``, do not name it) gives nothing, and so does the collective
plane, whose one lookup a query lies in no bracket."""

from benchmark import accounts, activity

LAYER = "operators, fusion"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"
KIND = "kernel_lookup"
PLANE = "http"


def seconds(tree: dict) -> float | None:
    if not any(KIND in (task.get("attributes", {}).get("hostSeconds") or {})
               for task in activity.task_spans(tree)):
        return None
    return activity.kind_seconds(tree, (KIND,))


def read(run: dict):
    if accounts.served_by(run) != PLANE:
        return None
    return accounts.median_per_query(run, "spans", seconds)
