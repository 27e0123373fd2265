"""Process start to window start: imports, device start-up, the reference,
the cluster, and every statement warmed until it builds no XLA program."""


def read(run: dict):
    return run["setup"]["seconds"]
