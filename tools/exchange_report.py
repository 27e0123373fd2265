"""Per-fragment-boundary exchange-mode report: the DATA PLANE each
fragment boundary rides.

For each query: the fragment DAG with one row per boundary —
producer fragment -> consumer fragment, the producer's output
partitioning, and the exchange mode the boundary lowers to:

- ``collective``  — the device-sharded exchange tier (in-program
  ``all_to_all`` / ``all_gather`` / gather inside one SPMD program);
  chosen when mesh_device_exchange is on, every boundary of the query
  is device-eligible, and placements are co-resident on one mesh;
- ``http+spool``  — the task-scheduled wire tier (PartitionedOutput ->
  serde -> HTTP pull, write-through to the spool when spooling is on);
- boundaries that are individually eligible but ride HTTP because a
  SIBLING boundary is not (the program is all-or-nothing) are marked
  ``http+spool (eligible)``.

With ``--segments`` the report also lists each query's fused segments
that touch a boundary (exec/fusion.py boundary_roles): the
exchange-feeding (partition-id computing) and exchange-fed (page
coalescing) segment programs are exactly the work the collective tier
splices away.

With ``--live`` the report EXECUTES each query on a real
``MeshQueryRunner`` mesh and adds per-boundary rows/bytes columns from
the per-shard telemetry the SPMD program itself reports (PR 12): what
each shard actually received through every ``all_to_all`` /
``all_gather`` / gather, not the planning-time view.

Usage:
    python tools/exchange_report.py                 # all TPC-H
    python tools/exchange_report.py q3 tpcds/q72    # subset
    python tools/exchange_report.py --check         # CI smoke: exit 1
        unless TPC-H Q3's boundaries ALL lower to the collective tier
    python tools/exchange_report.py --live --check  # ALSO execute Q3 on
        the mesh and require nonzero device-boundary bytes on every
        collective boundary
"""

import argparse
import dataclasses as dc
import os
import sys

if "--live" in sys.argv and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # a live mesh run needs >1 virtual device for real collectives;
    # only effective when jax has not been imported yet (standalone CLI
    # use — the test suite already forces an 8-device host platform)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                                 "count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def load_queries(names):
    from tpcds_queries import QUERIES as TPCDS
    from tpch_queries import QUERIES as TPCH

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


def boundary_rows(dplan, all_eligible):
    """(producer fid, consumer fid, partitioning kind, mode) rows."""
    rows = []
    for f in dplan.fragments:
        for fid in f.consumed_fragments:
            prod = dplan.fragments[fid]
            kind = prod.output_partitioning[0]
            if all_eligible:
                mode = "collective"
            elif prod.device_exchange_eligible:
                mode = "http+spool (eligible)"
            else:
                mode = "http+spool"
            rows.append((fid, f.fragment_id, kind, mode))
    return rows


def live_boundary_report(runner, sql: str) -> list:
    """Execute ``sql`` on the mesh runner and return its per-boundary
    telemetry rows: (kind, collective, per-shard rows, per-shard
    bytes) straight from the program's own per-shard counters."""
    runner.execute(sql)
    info = runner.last_run_info
    collective = {"hash": "all_to_all", "arbitrary": "all_to_all",
                  "broadcast": "all_gather", "single": "gather"}
    return [(b["fragment"], b["kind"],
             collective.get(b["kind"], b["kind"]),
             b.get("rows", []), b.get("bytes", []))
            for b in info.get("boundaries", [])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("queries", nargs="*",
                    help="q1 q6 tpcds/q3 ... (default: all TPC-H)")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--segments", action="store_true",
                    help="also list boundary-adjacent fused segments")
    ap.add_argument("--live", action="store_true",
                    help="execute each query on a MeshQueryRunner and "
                         "report per-boundary rows/bytes from the "
                         "per-shard telemetry")
    ap.add_argument("--shards", type=int, default=2,
                    help="mesh shard count for --live (clamped to the "
                         "available devices)")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: exit 1 unless TPC-H Q3's boundaries "
                         "all lower to the collective tier (with --live: "
                         "and report nonzero device bytes on every "
                         "collective boundary)")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          file=sys.stderr)

    from presto_tpu.config import EngineConfig
    from presto_tpu.localrunner import LocalQueryRunner
    from presto_tpu.server.fragmenter import (
        Fragmenter, annotate_device_exchange,
    )
    from presto_tpu.sql.optimizer import optimize
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.planner import Planner

    cfg = dc.replace(EngineConfig(), mesh_device_exchange=True)
    runner = LocalQueryRunner.tpch(scale=args.scale, config=cfg)

    mesh = None
    if args.live:
        import jax

        from presto_tpu.parallel.sqlmesh import MeshQueryRunner

        shards = max(1, min(args.shards, len(jax.devices())))
        mesh = MeshQueryRunner.tpch(scale=args.scale, n_devices=shards,
                                    config=cfg)
        print(f"live mesh: {shards} shards "
              f"({jax.devices()[0].platform} devices)")

    failures = []
    q3_collective = None
    q3_live_bytes_ok = None
    for catalog, num, sql in load_queries(args.queries):
        label = f"{catalog}/q{num}"
        runner.metadata.default_catalog = catalog
        try:
            logical = Planner(runner.metadata).plan(parse_statement(sql))
            optimized = optimize(logical, runner.metadata, cfg)
            dplan = Fragmenter(metadata=runner.metadata,
                               config=cfg).fragment(optimized)
            all_eligible = annotate_device_exchange(dplan)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"=== {label}: planning failed: {e}")
            failures.append((label, "plan"))
            continue
        rows = boundary_rows(dplan, all_eligible)
        verdict = "collective" if all_eligible else "http+spool"
        print(f"=== {label}: {len(dplan.fragments)} fragments, "
              f"{len(rows)} boundaries, data plane: {verdict}")
        print(f"  {'boundary':<12} {'partitioning':<14} mode")
        for fid, cid, kind, mode in rows:
            print(f"  f{fid}->f{cid:<9} {kind:<14} {mode}")
        if (catalog, num) == ("tpch", 3):
            q3_collective = all_eligible and all(
                m == "collective" for _, _, _, m in rows)
        if mesh is not None and all_eligible:
            # execute on the mesh: per-boundary rows/bytes straight
            # from the program's per-shard telemetry
            mesh.metadata.default_catalog = catalog
            try:
                live = live_boundary_report(mesh, sql)
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"  live execution failed: {e}")
                failures.append((label, "live"))
                continue
            print(f"  {'boundary':<12} {'collective':<12} "
                  f"{'rows/shard':<24} {'bytes/shard':<28} total bytes")
            for fid, _kind, coll, rws, byt in live:
                print(f"  f{fid:<11} {coll:<12} {str(rws):<24} "
                      f"{str(byt):<28} {sum(byt)}")
            if (catalog, num) == ("tpch", 3):
                q3_live_bytes_ok = bool(live) and all(
                    sum(byt) > 0 for _, _, _, _, byt in live)
        if args.segments:
            # lower each fragment the way a worker task would (stub
            # producer URIs, real output sinks) so the boundary-adjacent
            # fused segments — partition-id feeders and page coalescers,
            # the work the collective tier splices away — are visible
            from presto_tpu.exec.fusion import boundary_roles
            from presto_tpu.server.buffers import OutputBufferManager
            from presto_tpu.server.exchangeop import (
                PartitionedOutputOperatorFactory,
                TaskOutputOperatorFactory,
            )
            from presto_tpu.sql.physical import PhysicalPlanner

            for f in dplan.fragments:
                remotes = {fid: ["http://stub/{part}"]
                           for fid in f.consumed_fragments}
                planner = PhysicalPlanner(runner.registry, cfg,
                                          scan_shard=(0, 2),
                                          remote_sources=remotes)
                kind, channels = f.output_partitioning
                bufs = OutputBufferManager(2)
                if kind == "hash":
                    sink = PartitionedOutputOperatorFactory(
                        bufs, channels, 2)
                else:
                    sink = TaskOutputOperatorFactory(bufs)
                try:
                    pipes = planner.plan_fragment(f.root, sink)
                except Exception as e:  # noqa: BLE001 - advisory
                    print(f"  [f{f.fragment_id}] lowering failed: {e}")
                    continue
                for pname, desc, role in boundary_roles(pipes):
                    if role:
                        print(f"  [f{f.fragment_id} {pname}] "
                              f"{role}: {desc}")
    if args.check:
        if q3_collective is None:
            # --check without q3 in the set: plan (and with --live,
            # execute) it now
            extra = (["--live", "--shards", str(args.shards)]
                     if args.live else [])
            rc = main(["q3", "--scale", str(args.scale), "--check"]
                      + extra)
            return rc if rc else 0
        if not q3_collective:
            print("FAIL: TPC-H Q3 boundaries do not lower to the "
                  "collective tier")
            return 1
        if args.live and not q3_live_bytes_ok:
            print("FAIL: TPC-H Q3 live run did not report nonzero "
                  "device-boundary bytes on every collective boundary")
            return 1
        if failures:
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
