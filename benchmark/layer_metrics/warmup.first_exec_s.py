"""Wall of the first execution of each of the cell's statements, summed:
tracing, lowering and compiling or loading every program the statement
dispatches."""

LAYER = "XLA compile + cache"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run: dict):
    return sum(w["first_exec_s"] for w in run["setup"]["warm"].values())
