"""Statements completed per hour, from the window's start to the last
completion."""

from benchmark import metrics


def read(run: dict):
    return metrics.completed_per_hour(run["samples"], run["window_start"])
