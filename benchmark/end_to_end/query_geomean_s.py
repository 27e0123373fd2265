"""Geometric mean, over the cell's statements, of each statement's median
client-side wall in the window (one statement: its median)."""

from benchmark import metrics


def read(run: dict):
    return metrics.geomean_of_medians(run["samples"],
                                      list(run["cell"]["statements"]))
