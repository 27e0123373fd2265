"""The one general load generator: closed-loop clients over a traffic mix.

A traffic mix is a data file (``traffic/<name>.json``): the statements, the
number of clients, the loop (``closed``: a client sends its next statement
when the last one has answered) and the order rule (``cycle``: every client
walks a permutation of the statements drawn from the seed, again and again,
so every seed runs the same statements in the same numbers).
"""

from __future__ import annotations

import random
import threading
import time

CLIENT_TIMEOUT_S = 900.0   # a statement's first execution compiles
# The benchmark's clients do not sleep between polls.  The coordinator's GET
# is a long poll (it waits up to 0.5 s for the rows), so it is the wait, as
# in Presto's own StatementClientV1: a wall is then the time the server took
# to within a round trip.  With the shipped client's default of 50 ms before
# every GET, a query that ends while the client sleeps is seen when the
# sleep ends, and walls stand on a grid (0.55-0.60 s, 1.10-1.15 s, ... are
# dead bands; Q1 at SF1 ended in the first: PERF.md section 3).
POLL_INTERVAL_S = 0.0


def orders(traffic: dict, seed: int) -> list:
    """One statement order per client, drawn from ``seed``."""
    if traffic["loop"] != "closed" or traffic["order"] != "cycle":
        raise ValueError("the generator knows loop 'closed' with order "
                         f"'cycle', not {traffic['loop']!r} / "
                         f"{traffic['order']!r}")
    rng = random.Random(seed)
    out = []
    for _ in range(traffic["clients"]):
        order = list(traffic["statements"])
        rng.shuffle(order)
        out.append(order)
    return out


def bench_client(new_client, user: str):
    """A client of the benchmark's own: the program's, polling at
    ``POLL_INTERVAL_S``.  Every client the harness makes comes from here."""
    client = new_client(user=user)
    client.poll_interval_s = POLL_INTERVAL_S
    return client


def execute(client, name: str, sql: str, who: int) -> dict:
    """One operation: the sample that every metric and check reads."""
    op = {"statement": name, "client": who, "start": time.time(),
          "rows": None, "error": None, "query_id": None}
    t0 = time.perf_counter()
    try:
        _columns, data = client.execute(sql, timeout_s=CLIENT_TIMEOUT_S)
        op["rows"] = [tuple(r) for r in data]
    except Exception as e:  # an error is a failed operation, not a crash
        op["error"] = f"{type(e).__name__}: {e}"
    op["wall_s"] = time.perf_counter() - t0
    op["end"] = op["start"] + op["wall_s"]
    op["query_id"] = client.last_query_id
    # the POST's answer and every GET's (the client keeps one entry each)
    op["responses"] = len(client.stats_history)
    return op


def closed_loop(new_client, statements: dict, traffic: dict, seed: int,
                seconds: float) -> tuple:
    """Runs the window.  New statements start until ``seconds`` have
    passed; those in flight finish.  Returns (window start, epoch seconds;
    samples in order of completion)."""
    samples: list = []
    lock = threading.Lock()
    clients = [bench_client(new_client, f"bench-{i}")
               for i in range(traffic["clients"])]
    start = time.time()
    deadline = time.perf_counter() + seconds

    def walk(who: int, order: list) -> None:
        i = 0
        while time.perf_counter() < deadline:
            name = order[i % len(order)]
            op = execute(clients[who], name, statements[name], who)
            with lock:
                samples.append(op)
            i += 1

    threads = [threading.Thread(target=walk, args=(i, order), daemon=True)
               for i, order in enumerate(orders(traffic, seed))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return start, samples
