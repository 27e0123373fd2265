"""An accumulating operator's finish is one staging, one named program and
one read (exec/aggregation.py, exec/sortop.py): the programs against
plain Python over the same rows, and what a served query builds and
counts of them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.batch import batch_from_pylist
from presto_tpu.exec.aggregation import (
    AggChannel, GlobalAggregationOperatorFactory,
    HashAggregationOperatorFactory,
)
from presto_tpu.exec.driver import Pipeline
from presto_tpu.exec.operators import (
    FilterProjectOperatorFactory, OutputCollector, OutputCollectorFactory,
    ValuesOperatorFactory,
)
from presto_tpu.exec.runner import execute_pipelines
from presto_tpu.exec.sortop import OrderByOperatorFactory, SortSpec
from presto_tpu.expr import build as B

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# key, flag, word, amount, count: a NULL key is a group, 'c' is a group
# whose every input is NULL, and the words' interning order ('pear' first)
# is not their sort order
SCHEMA = [T.VARCHAR, T.BOOLEAN, T.VARCHAR, T.DOUBLE, T.BIGINT]
ROWS = [
    ("b", True, "pear", 1.5, 7), ("a", False, "fig", 2.0, None),
    (None, True, "apple", 4.0, 1), ("b", True, "apple", None, 2),
    ("c", None, None, None, None), ("a", False, "kiwi", 8.0, 3),
    (None, True, None, 16.0, 4), ("b", False, "zebra", 32.0, 5),
    ("c", None, None, None, None), ("a", False, "fig", 64.0, 6),
]
AGGS = [AggChannel("sum", 3, T.DOUBLE), AggChannel("count", 3, T.BIGINT),
        AggChannel("min", 2, T.VARCHAR), AggChannel("max", 2, T.VARCHAR),
        AggChannel("sum", 4, T.BIGINT), AggChannel("max", 3, T.DOUBLE),
        AggChannel("count", None, T.BIGINT)]


def _reference(rows, key_channels):
    """GROUP BY in plain Python: NULL keys group together, an aggregate
    skips NULL inputs and is NULL over none, a count is never NULL."""
    def over(values, fn):
        values = [v for v in values if v is not None]
        return fn(values) if values else None

    groups = {}
    for row in rows:
        groups.setdefault(tuple(row[c] for c in key_channels),
                          []).append(row)
    out = []
    for key, members in groups.items():
        amounts = [r[3] for r in members]
        words = [r[2] for r in members]
        out.append(key + (
            over(amounts, sum), sum(a is not None for a in amounts),
            over(words, min), over(words, max),
            over([r[4] for r in members], sum), over(amounts, max),
            len(members)))
    return out


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((v is None, v) for v in r))


class _Kept(OutputCollector):
    """The batches as the operator handed them over: OutputCollector
    cuts each to its rows and brings it to the host."""

    def add_input(self, batch):
        self.batches.append(batch)


class _KeptFactory(OutputCollectorFactory):
    def create(self, ctx):
        self.collectors.append(_Kept(ctx))
        return self.collectors[-1]

    def batches(self):
        return [b for c in self.collectors for b in c.batches]


def _run(factories, batches):
    out = _KeptFactory()
    task = execute_pipelines(
        [Pipeline([ValuesOperatorFactory(batches)] + factories + [out])])
    stats = {s.operator.rsplit(".", 1)[-1]: s for s in task.operator_stats}
    return out, stats


@pytest.mark.parametrize("key_channels, tier", [
    ([0, 1], "direct"),     # dictionary + boolean keys, both nullable
    ([0], "direct"),
    ([4], "sort"),          # a BIGINT key: unbounded
    ([4, 0], "sort"),
])
def test_grouped_finish_matches_plain_python(key_channels, tier):
    batches = [batch_from_pylist(SCHEMA, ROWS[:6]),
               batch_from_pylist(SCHEMA, ROWS[6:])]
    out, stats = _run([HashAggregationOperatorFactory(
        key_channels, AGGS, SCHEMA)], batches)
    assert _sorted(out.rows()) == _sorted(_reference(ROWS, key_channels))
    agg = stats["HashAggregationOperator"]
    assert (agg.kernel_tier, agg.jit_dispatches) == (tier, 1)
    # the columns stay on the device, padded past the groups
    (batch,) = out.batches()
    assert batch.capacity > batch.num_rows == len(out.rows())
    assert not any(isinstance(c.values, np.ndarray) for c in batch.columns)


def test_sort_tier_runs_again_when_the_groups_overflow():
    """140,000 rows of 70,000 keys: the first capacity bucket (65,536) is
    exceeded once, the second launch reads ``num_groups`` and no more."""
    keys = np.arange(140_000) % 70_000
    rows = [(None, None, None, float(k), int(k)) for k in keys]
    out, stats = _run([HashAggregationOperatorFactory(
        [4], AGGS, SCHEMA)], [batch_from_pylist(SCHEMA, rows)])
    assert _sorted(out.rows()) == _sorted(_reference(rows, [4]))
    agg = stats["HashAggregationOperator"]
    assert (agg.kernel_tier, agg.jit_dispatches) == ("sort", 2)
    assert agg.output_rows == 70_000


def test_a_growing_dictionary_shares_the_direct_program():
    """Domains and rank tables are bucketed: a key dictionary of 5 words
    and one of 7 run one ``groupby_direct`` program."""
    from presto_tpu.ops.groupby import _AGG_PROGRAMS

    def run(words):
        rows = [(w, True, w, 1.0, 1) for w in words]
        out, _ = _run([HashAggregationOperatorFactory(
            [0], AGGS, SCHEMA)], [batch_from_pylist(SCHEMA, rows)])
        assert _sorted(out.rows()) == _sorted(_reference(rows, [0]))

    run(["e", "d", "c", "b", "a"])
    programs = len(_AGG_PROGRAMS)
    run(["g", "f", "e", "d", "c", "b", "a"])
    assert len(_AGG_PROGRAMS) == programs


@pytest.mark.parametrize("empty", [False, True])
def test_global_finish_matches_plain_python(empty):
    """One output row always; over zero rows a count is 0 and every other
    aggregate NULL (the filter keeps nothing)."""
    keep = B.comparison("<" if empty else ">=", B.ref(4, T.BIGINT),
                        B.const(0, T.BIGINT))
    rows = [r for r in ROWS if r[4] is not None]
    out, stats = _run([
        FilterProjectOperatorFactory(
            keep, [B.ref(i, t) for i, t in enumerate(SCHEMA)], SCHEMA),
        GlobalAggregationOperatorFactory(AGGS, SCHEMA)],
        [batch_from_pylist(SCHEMA, rows)])
    (want,) = _reference([] if empty else rows, []) or [
        (None, 0, None, None, None, None, 0)]
    assert out.rows() == [want]
    assert stats["GlobalAggregationOperator"].jit_dispatches == (not empty)


@pytest.mark.parametrize("limit", [3, len(ROWS), len(ROWS) + 5, None],
                         ids=["under", "at", "over", "none"])
def test_order_by_a_dictionary_key_descending_nulls_first(limit):
    """Strings order by the dictionary's ranks inside the program; the
    limit cuts ``num_rows`` and the columns stay padded."""
    batch = batch_from_pylist(SCHEMA, ROWS)
    out, stats = _run([OrderByOperatorFactory(
        [SortSpec(2, descending=True, nulls_first=True),
         SortSpec(4, nulls_first=False)], limit=limit)], [batch])
    want = sorted(ROWS, key=lambda r: (r[4] is None, r[4] or 0))
    want.sort(key=lambda r: r[2] or "", reverse=True)
    want.sort(key=lambda r: r[2] is not None)
    assert out.rows() == want[:limit]
    assert stats["OrderByOperator"].jit_dispatches == 1
    (got,) = out.batches()
    assert got.capacity >= 1024 and not isinstance(
        got.columns[0].values, np.ndarray)


def test_top_n_gathers_a_bucket_of_the_limit_not_the_input():
    rows = [(None, None, None, float(i), i) for i in range(5000)]
    out, _ = _run([OrderByOperatorFactory(
        [SortSpec(4, descending=True)], limit=10)],
        [batch_from_pylist(SCHEMA, rows)])
    assert [r[4] for r in out.rows()] == list(range(4999, 4989, -1))
    assert out.batches()[0].capacity == 1024      # of 8,192


# -- what a served query builds and counts ----------------------------------

_SERVED = """
import json, sys, urllib.request
sys.path[:0] = [{root!r}, {tests!r}]
import conftest                 # the suite's backend and cache settings
from presto_tpu.server.dqr import DistributedQueryRunner

def fetch(uri):
    with urllib.request.urlopen(uri, timeout=30) as resp:
        return json.loads(resp.read())

sql = open({statement!r}).read()
runs = []
with DistributedQueryRunner.tpch(scale=0.01, n_workers=2) as dqr:
    client = dqr.new_client()
    for _run in ("cold", "warm"):
        client.execute(sql)
        uri = dqr.coordinator.uri + "/v1/query/" + client.last_query_id
        tree = fetch(uri + "/spans")
        dispatches = {{}}
        for stage in tree["children"]:
            if stage["kind"] != "stage":
                continue
            for task in stage["children"]:
                for op in task["attributes"]["operators"]:
                    name = op["operator"].rsplit(".", 1)[-1]
                    dispatches[name] = (dispatches.get(name, 0)
                                        + op["jitDispatches"])
        runs.append({{"xla_builds": fetch(uri)["queryStats"]["xla_builds"],
                     "dispatches": dispatches}})
print("RESULT " + json.dumps(runs))
"""


@pytest.mark.parametrize("name, most", [("q1", 25), ("q3", 40)])
def test_a_cold_query_builds_few_programs(name, most):
    """A first Q1 / Q3 through coordinator and two workers in a fresh
    process whose compile cache is off (the suite's setting: every
    program is built) builds at most ``most`` XLA programs, where the
    eager finishes built 87 / 97; a second run builds none; and the
    final stage's operators count their launches."""
    script = _SERVED.format(
        root=ROOT, tests=os.path.join(ROOT, "tests"),
        statement=os.path.join(ROOT, "benchmark", "statements",
                               name + ".sql"))
    done = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    (line,) = [ln for ln in done.stdout.splitlines()
               if ln.startswith("RESULT ")]
    cold, warm = json.loads(line[len("RESULT "):])
    assert 0 < cold["xla_builds"] <= most
    assert warm["xla_builds"] == 0
    for run in (cold, warm):
        assert run["dispatches"]["HashAggregationOperator"] >= 1
        assert run["dispatches"]["OrderByOperator"] >= 1
