"""Share of the memory roofline reached by all device work together.

The least time the chips need for the bytes the statements must read (every
column a statement names, once, at its generated width, over the chips' peak
bytes/s) over the device time the trace shows busy, for the queries that lie
whole inside the traced slice.  It is the share of all device work, not of
one kernel: kernels have no stable names yet.  Bound: memory (the statements
are scans; their arithmetic is a few operations a byte)."""

from benchmark import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "query_geomean_s"


def read(run: dict):
    reduced = run["trace"]
    if reduced is None:
        return None
    lo, hi = reduced["window"]
    whole = [op for op in run["samples"]
             if op["start"] >= lo and op["end"] <= hi]
    if not whole:
        return None
    must_read = sum(run["statement_bytes"][op["statement"]] for op in whole)
    busy_s = trace_reduce.busy_within(
        reduced, [[op["start"], op["end"]] for op in whole])
    if busy_s <= 0:
        return None
    least_s = must_read / (reduced["devices"]
                           * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / busy_s
