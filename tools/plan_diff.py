"""Memo-on vs memo-off plan diff for a named TPC-H / TPC-DS query.

Prints both optimized logical plan shapes plus the cost model's estimate
of each (weighted total and the cpu/memory/network split), so a CBO
change can be eyeballed per query — the PlanPrinter-diff workflow the
reference drives through EXPLAIN before/after a rule lands.

Usage:
    python tools/plan_diff.py q3            # TPC-H Q3
    python tools/plan_diff.py tpcds/q72     # TPC-DS Q72
    python tools/plan_diff.py q9 --scale 0.01
"""

import argparse
import dataclasses as dc
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def load_query(name: str):
    """'q3' / 'tpch/q3' -> TPC-H; 'tpcds/q72' -> TPC-DS.  Returns
    (catalog, sql)."""
    name = name.lower().lstrip("/")
    catalog = "tpch"
    if "/" in name:
        catalog, name = name.split("/", 1)
    num = int(name.lstrip("q"))
    if catalog == "tpch":
        from tpch_queries import QUERIES
    elif catalog == "tpcds":
        from tpcds_queries import QUERIES
    else:
        raise SystemExit(f"unknown catalog {catalog!r} (tpch or tpcds)")
    if num not in QUERIES:
        raise SystemExit(
            f"no {catalog} q{num}; have {sorted(QUERIES)}")
    return catalog, QUERIES[num]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("query", help="q3 | tpch/q9 | tpcds/q72 ...")
    ap.add_argument("--scale", type=float, default=0.01)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          file=sys.stderr)

    from presto_tpu.config import DEFAULT
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.sql.memo import CostComparator, CostModel
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.plan import format_plan
    from presto_tpu.sql.planner import Planner
    from presto_tpu.sql.stats import StatsCalculator

    catalog, sql = load_query(args.query)
    runner = LocalQueryRunner.tpch(scale=args.scale)
    runner.metadata.default_catalog = catalog
    stmt = parse_statement(sql)
    comparator = CostComparator()

    totals = {}
    for label, cfg in (("memo-on", DEFAULT),
                       ("memo-off (greedy)",
                        dc.replace(DEFAULT, optimizer_use_memo=False))):
        plan = optimize(Planner(runner.metadata).plan(stmt),
                        runner.metadata, cfg)
        model = CostModel(StatsCalculator(runner.metadata), cfg)
        cost = model.cumulative(plan)
        totals[label] = comparator.total(cost)
        print(f"=== {label} ===")
        print(f"estimated cost: total={comparator.total(cost):.4g} "
              f"(cpu={cost.cpu:.4g}, mem={cost.memory:.4g}, "
              f"net={cost.network:.4g})")
        print(format_plan(plan))
    on, off = totals["memo-on"], totals["memo-off (greedy)"]
    if on < off:
        print(f"memo plan is cheaper-estimated: {on:.4g} < {off:.4g} "
              f"({off / on:.2f}x)")
    elif on == off:
        print("memo and greedy plans cost the same estimate")
    else:
        print(f"WARNING: memo plan estimate {on:.4g} > greedy {off:.4g}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
