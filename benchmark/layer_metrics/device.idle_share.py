"""Share of the traced slice in which no operation ran on the device
(averaged over the cell's devices)."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "query_geomean_s"


def read(run: dict):
    return None if run["trace"] is None \
        else 100.0 * run["trace"]["idle_share"]
