"""XLA programs built (compiled or loaded) inside the window.  Should be 0:
anything else means the warm-up missed a shape."""

LAYER = "XLA compile + cache"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "query_geomean_s"


def read(run: dict):
    return len(run["window_compile_events"])
