"""95th percentile of client-side wall over all statements of the window
(the sample count is on the run's ``window`` line)."""

from benchmark import metrics


def read(run: dict):
    return metrics.percentile([s["wall_s"] for s in run["samples"]], 95.0)
