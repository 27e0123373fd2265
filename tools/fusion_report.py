"""Per-query pipeline-fusion report for the TPC-H / TPC-DS suites.

For each query: the lowered pipeline chains with fused segments expanded
(stage composition, scan coalescing, partition-id fusion), and — with
``--execute`` — the fused vs unfused jit dispatch/compile counters plus a
result-parity check.  Companion to tools/plan_diff.py (which diffs the
LOGICAL plan; this diffs the PHYSICAL dispatch structure).

Usage:
    python tools/fusion_report.py                  # plan-only, all TPC-H
    python tools/fusion_report.py q1 q6 tpcds/q3   # subset
    python tools/fusion_report.py --execute        # + counters/parity
    python tools/fusion_report.py --execute --check  # CI smoke: exit 1 on
        any parity miss or any query where fusion does not reduce launches

``--check --execute`` is the CI smoke mode: it fails when fused execution
loses parity with unfused, when no query fused at all, when TPC-H Q1
at the default scale regresses past the partial-agg pre-reduce pin
(PR 4: fewer than 5 jit dispatches, PR 3's count), or when TPC-H Q3
loses its probe-in-segment lowering (PR 10: the probe stages absorbed
into fused segments, with the dispatch count pinned below 10).

With ``--execute`` each query also reports the **kernel-tier column**:
which tier served every group-by/join hot loop (``hash`` =
device-resident open-addressing, ``direct`` = bounded-domain,
``sort``/``sorted`` = sorted-index, ``stream`` = clustered,
``hash+sort`` = the overflow seam crossed mid-query).
"""

import argparse
import dataclasses as dc
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def load_queries(names):
    from tpch_queries import QUERIES as TPCH
    from tpcds_queries import QUERIES as TPCDS

    if not names:
        return [("tpch", n, TPCH[n]) for n in sorted(TPCH)]
    out = []
    for name in names:
        catalog, _, q = name.lower().rpartition("/")
        catalog = catalog or "tpch"
        num = int(q.lstrip("q"))
        table = {"tpch": TPCH, "tpcds": TPCDS}[catalog]
        out.append((catalog, num, table[num]))
    return out


def plan_chains(runner, sql, config):
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.physical import PhysicalPlanner
    from presto_tpu.sql.planner import Planner

    plan = optimize(Planner(runner.metadata).plan(parse_statement(sql)),
                    runner.metadata, config)
    return PhysicalPlanner(runner.registry, config).plan(plan).pipelines


def describe(f) -> str:
    from presto_tpu.exec.fusion import FusedSegmentOperatorFactory

    if isinstance(f, FusedSegmentOperatorFactory):
        return f.describe()
    return type(f).__name__.replace("Factory", "")


def rows_close(a, b) -> bool:
    import numpy as np

    if len(a) != len(b):
        return False
    for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                if not (np.isclose(va, vb, rtol=1e-6)
                        or (np.isnan(va) and np.isnan(vb))):
                    return False
            elif va != vb:
                return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("queries", nargs="*",
                    help="q1 q6 tpcds/q3 ... (default: all TPC-H)")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--execute", action="store_true",
                    help="run each query fused + unfused; report "
                         "dispatch counters and parity")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: nonzero exit on parity miss or "
                         "zero fused segments overall")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          file=sys.stderr)

    from presto_tpu.config import EngineConfig
    from presto_tpu.exec.fusion import FusedSegmentOperatorFactory
    from presto_tpu.localrunner import LocalQueryRunner

    cfg_on = EngineConfig()
    cfg_off = dc.replace(cfg_on, pipeline_fusion=False)
    runner_on = LocalQueryRunner.tpch(scale=args.scale, config=cfg_on)
    runner_off = LocalQueryRunner.tpch(scale=args.scale, config=cfg_off)

    total_segments = 0
    failures = []
    for catalog, num, sql in load_queries(args.queries):
        label = f"{catalog}/q{num}"
        runner_on.metadata.default_catalog = catalog
        runner_off.metadata.default_catalog = catalog
        try:
            pipelines = plan_chains(runner_on, sql, cfg_on)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"=== {label}: planning failed: {e}")
            failures.append((label, "plan"))
            continue
        segs = [f for p in pipelines for f in p.factories
                if isinstance(f, FusedSegmentOperatorFactory)]
        total_segments += len(segs)
        prereduced = sum(1 for s in segs if s.agg_spec is not None)
        print(f"=== {label}: {len(pipelines)} pipelines, "
              f"{len(segs)} fused segments, {prereduced} pre-reduced")
        for p in pipelines:
            print(f"  [{p.name}] " + " -> ".join(
                describe(f) for f in p.factories))
        if not args.execute:
            continue
        try:
            res_on = runner_on.execute(sql)
            jit_on = runner_on._last_task.jit_counters()
            res_off = runner_off.execute(sql)
            jit_off = runner_off._last_task.jit_counters()
        except Exception as e:  # noqa: BLE001
            print(f"  execution failed: {e}")
            failures.append((label, "exec"))
            continue
        parity = rows_close(res_on.rows, res_off.rows)
        tiers = sorted({(s.operator.rsplit(".", 1)[-1], s.kernel_tier)
                        for s in runner_on._last_task.operator_stats
                        if s.kernel_tier})
        tier_col = ", ".join(f"{op}={t}" for op, t in tiers) or "-"
        print(f"  dispatches fused={jit_on['dispatches']} "
              f"unfused={jit_off['dispatches']} "
              f"compiles fused={jit_on['compiles']} "
              f"unfused={jit_off['compiles']} "
              f"prereduce_rows={jit_on.get('prereduce_rows', 0)} "
              f"parity={parity}")
        print(f"  kernel tiers: {tier_col}")
        if not parity:
            failures.append((label, "parity"))
        if jit_on["dispatches"] > jit_off["dispatches"]:
            print(f"  WARNING: fusion increased launches on {label}")
        if (catalog, num) == ("tpch", 1) and args.scale == 0.01 \
                and jit_on["dispatches"] >= 5:
            # the PR 4 acceptance pin: pre-reduce must keep Q1 below
            # PR 3's 5 dispatches at the default report scale
            print(f"  FAIL: Q1 dispatch pin regressed "
                  f"({jit_on['dispatches']} >= 5)")
            failures.append((label, "q1-dispatch-pin"))
        if (catalog, num) == ("tpch", 3) and args.scale == 0.01:
            # the PR 10 pin: Q3's probes run IN-SEGMENT (the
            # filter->project->probe chain is one dispatch per batch)
            if not any("probe(" in describe(f) for p in pipelines
                       for f in p.factories):
                print("  FAIL: Q3 probe-in-segment lowering lost")
                failures.append((label, "q3-probe-pin"))
            if jit_on["dispatches"] >= 10:
                print(f"  FAIL: Q3 dispatch pin regressed "
                      f"({jit_on['dispatches']} >= 10)")
                failures.append((label, "q3-dispatch-pin"))
    print(f"total fused segments: {total_segments}; "
          f"failures: {failures or 'none'}")
    if args.check and (failures or total_segments == 0):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
