"""Peak bytes in use on the fullest of the cell's devices
(``memory_stats()``)."""

LAYER = "device"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "query_geomean_s"


def read(run: dict):
    return run["memory_peak_bytes"]
