"""Metric arithmetic on fixed samples."""

import math

import pytest

from benchmark import check, load, metrics


def sample(statement, start, wall):
    return {"statement": statement, "start": start, "wall_s": wall,
            "end": start + wall}


SAMPLES = [sample("a", 0.0, 1.0), sample("b", 1.0, 4.0),
           sample("a", 5.0, 3.0), sample("b", 8.0, 16.0),
           sample("a", 24.0, 2.0)]


def test_geomean_of_medians():
    # medians: a = 2.0, b = 10.0
    assert metrics.geomean_of_medians(SAMPLES, ["a", "b"]) == \
        pytest.approx(math.sqrt(20.0))
    assert metrics.geomean_of_medians(SAMPLES, ["a"]) == pytest.approx(2.0)


def test_geomean_needs_every_statement():
    assert metrics.geomean_of_medians(SAMPLES, ["a", "c"]) is None
    assert metrics.geomean_of_medians([], ["a"]) is None


def test_percentile_interpolates_and_counts():
    values = list(range(1, 101))            # 100 samples
    assert metrics.percentile(values, 95.0) == pytest.approx(95.05)
    assert metrics.percentile([3.0], 95.0) == 3.0
    assert metrics.percentile([], 95.0) is None
    assert metrics.percentile([1.0, 2.0], 50.0) == pytest.approx(1.5)


def test_distribution_is_count_extremes_and_quartiles():
    got = metrics.distribution([5.0, 1.0, 3.0, 2.0, 4.0])
    assert got == {"n": 5, "min": 1.0, "q1": 2.0, "median": 3.0, "q3": 4.0,
                   "max": 5.0}
    # a grid shows: two thirds of the samples on one value pull a quartile
    # and the median onto it
    grid = metrics.distribution([0.52, 0.54, 0.61, 0.61, 0.61, 0.61])
    assert grid["median"] == grid["q3"] == 0.61 and grid["n"] == 6
    assert metrics.distribution([]) is None


def test_throughput_runs_to_the_last_completion():
    # 5 statements, the last one ends 26 s after the window's start
    assert metrics.completed_per_hour(SAMPLES, 0.0) == \
        pytest.approx(5 * 3600.0 / 26.0)
    assert metrics.completed_per_hour([], 0.0) is None


def test_orders_same_work_for_every_seed():
    traffic = {"statements": ["q1", "q6", "q3"], "clients": 4,
               "loop": "closed", "order": "cycle"}
    a, b = load.orders(traffic, 1), load.orders(traffic, 2 ** 31 + 7)
    assert a == load.orders(traffic, 1)
    assert a != b
    for order in a + b:
        assert sorted(order) == ["q1", "q3", "q6"]
    with pytest.raises(ValueError):
        load.orders({**traffic, "loop": "open"}, 1)


class StubClient:
    """What ``load`` touches of a StatementClient, with the program's
    default between polls."""

    def __init__(self, user):
        self.user, self.poll_interval_s = user, 0.05
        self.last_query_id, self.stats_history = None, []
        self.polled_at = []

    def execute(self, sql, timeout_s):
        self.polled_at.append(self.poll_interval_s)
        self.last_query_id = f"{self.user}-{len(self.polled_at)}"
        self.stats_history = [{"state": "QUEUED"}, {"state": "FINISHED"}]
        return [{"name": "x"}], [[1]]


def test_the_generators_clients_do_not_sleep_between_polls():
    made = []

    def new_client(user):
        made.append(StubClient(user))
        return made[-1]

    traffic = {"statements": ["a", "b"], "clients": 3, "loop": "closed",
               "order": "cycle"}
    _start, samples = load.closed_loop(
        new_client, {"a": "select 1", "b": "select 2"}, traffic, 7, 0.05)
    assert load.POLL_INTERVAL_S == 0.0
    assert [c.user for c in made] == ["bench-0", "bench-1", "bench-2"]
    for c in made:        # set before its first statement, and left so
        assert c.polled_at and set(c.polled_at) == {0.0}
    assert {s["client"] for s in samples} == {0, 1, 2}
    assert all(s["responses"] == 2 and s["rows"] == [(1,)] for s in samples)
    # the warm-up's client comes from the same helper
    assert load.bench_client(new_client, "bench-warmup").poll_interval_s == 0.0


def test_compare_exact_keys_and_double_tolerance():
    want = [("A", 3, 100.0), ("B", 4, 200.0)]
    assert check.compare([("A", 3, 100.00001), ("B", 4, 200.0)], want,
                         1e-6) == pytest.approx(1e-7)
    for bad in ([("A", 3, 100.0)],                       # a row short
                [("A", 3, 100.0), ("B", 5, 200.0)],      # a count off
                [("A", 3, 100.1), ("B", 4, 200.0)],      # a DOUBLE off
                [("A", 3, None), ("B", 4, 200.0)],       # a NULL
                [("B", 4, 200.0), ("A", 3, 100.0)]):     # the order
        with pytest.raises(AssertionError):
            check.compare(bad, want, 1e-6)


HTTP = {"served_by": "http", "guarantees": {"double_rtol": 1e-6}}
MESH = {"served_by": "device", "guarantees": {"double_rtol": 1e-6}}
SERVED = {"resultCached": False, "exchangeModes": {"device": 2},
          "deviceExchange": {}}


@pytest.mark.parametrize("detail, config, fallbacks, failed", [
    ({"resultCached": False}, HTTP, {}, False),
    ({"resultCached": True}, HTTP, {}, True),
    (SERVED, MESH, {}, False),
    ({**SERVED, "resultCached": True}, MESH, {}, True),
    ({**SERVED, "exchangeModes": {"device": 1, "http": 1}}, MESH, {}, True),
    ({**SERVED, "exchangeModes": {}}, MESH, {}, True),
    ({**SERVED, "deviceExchange": {"fallback": "capacity"}}, MESH, {}, True),
    (SERVED, MESH, {"capacity": 1}, True),
])
def test_an_operation_served_otherwise_than_promised_fails(
        detail, config, fallbacks, failed):
    op = {"rows": [(1,)], "error": None}
    assert (check.judge(op, [(1,)], detail, config, fallbacks)
            is not None) == failed


def test_errors_wrong_answers_and_missing_details_fail():
    ok = {"resultCached": False}
    assert check.judge({"rows": None, "error": "QueryFailed: x"}, [(1,)],
                       ok, HTTP, {}) == "QueryFailed: x"
    assert "differs" in check.judge({"rows": [(2,)], "error": None}, [(1,)],
                                    ok, HTTP, {})
    assert "no query detail" in check.judge(
        {"rows": [(1,)], "error": None}, [(1,)], None, HTTP, {})
