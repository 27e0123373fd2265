"""Seconds inside XLA's compile-or-load during set-up, summed over every
program built (a load from the persistent cache counts its load time)."""

LAYER = "XLA compile + cache"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run: dict):
    return sum(secs for _at, _name, secs in run["setup"]["compile_events"])
