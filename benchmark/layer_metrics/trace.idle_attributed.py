"""Of the seconds of the traced slice in which no device ran anything, the
share that lies inside some task's activity span of any kind: how much of
the idle chip the program's own account explains.  What is left over is
host time outside every task (coordinator, HTTP, the client's polls) or
inside a task but in no bracket."""

from benchmark import activity, trace_reduce

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "query_geomean_s"


def read(run: dict):
    reduced = run["trace"]
    if reduced is None:
        return None
    zero = reduced["window"][0]
    busy = trace_reduce.union(
        [list(iv) for ivs in reduced["busy"].values() for iv in ivs])
    idle = activity.complement(busy, 0.0, reduced["window_s"])
    recorded, found = [], False
    for tree in run["spans"].values():
        ivs = activity.intervals(tree)
        if ivs is not None:
            found = True
            recorded.extend([s - zero, e - zero] for s, e in ivs)
    idle_s = trace_reduce.total(idle)
    if not found or idle_s <= 0:
        return None
    return 100.0 * activity.overlap(
        idle, trace_reduce.union(recorded)) / idle_s
