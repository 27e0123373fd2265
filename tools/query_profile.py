#!/usr/bin/env python
"""Per-query profile: stage span timeline + stats rollup table.

Two modes, one report shape:

- **live**: boot an in-process DistributedQueryRunner, execute one
  statement through the real statement protocol, and render the
  coordinator's StageStats rollup plus the timed span tree from
  ``/v1/query/{id}/spans`` (query -> coordinator phases -> per-stage ->
  per-task-attempt, the presto_tpu.spans shape).  ``--live``
  additionally follows ``/v1/query/{id}/timeseries`` while the
  statement runs and renders the sampler's progress ring;
- **replay** (``--replay query.json``): read a JsonLinesEventListener
  log (events.py, the bundled query.json role) and render each query's
  event timeline, the stage-stats table, and the span tree carried on
  its QueryCompletedEvent.

Usage:
    JAX_PLATFORMS=cpu python tools/query_profile.py \
        --sql "select count(*) from lineitem" --workers 2
    JAX_PLATFORMS=cpu python tools/query_profile.py --live --sql "..."
    JAX_PLATFORMS=cpu python tools/query_profile.py --replay query.json
    JAX_PLATFORMS=cpu python tools/query_profile.py --check   # CI smoke
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

TIMELINE_WIDTH = 40


def _fmt_bytes(b) -> str:
    return f"{(b or 0) / (1 << 20):.1f}MiB"


def stage_table(stage_stats) -> list:
    """Render {fid: StageStats dict} as aligned text lines."""
    header = (f"{'stage':>5} {'tasks':>5} {'rep':>4} {'in rows':>11} "
              f"{'out rows':>11} {'wall ms':>9} {'jit':>9} "
              f"{'prereduce':>9} {'peak':>9} {'xchg f/c/p':>14}")
    lines = [header, "-" * len(header)]
    for fid in sorted(stage_stats, key=lambda k: int(k)):
        st = stage_stats[fid]
        jit = f"{st['jit_dispatches']}/{st['jit_compiles']}"
        xchg = (f"{st['exchange_fetched']}/{st['exchange_consumed']}/"
                f"{st['exchange_purged']}")
        lines.append(
            f"{fid:>5} {st['tasks']:>5} {st['reporting']:>4} "
            f"{st['input_rows']:>11} {st['output_rows']:>11} "
            f"{st['wall_ns'] / 1e6:>9.1f} {jit:>9} "
            f"{st['prereduce_rows']:>9} "
            f"{_fmt_bytes(st['peak_memory_bytes']):>9} {xchg:>14}")
    return lines


def _fetch_json(uri: str):
    import json
    import urllib.request

    with urllib.request.urlopen(uri, timeout=10) as resp:
        return json.loads(resp.read())


def timeseries_table(samples) -> list:
    """Render the /v1/query/{id}/timeseries ring: one line per sample
    (live progress as the sampler saw it)."""
    if not samples:
        return ["(no time-series samples — query finished before the "
                "first sweep)"]
    t0 = samples[0]["t"]
    header = (f"{'t+ms':>8} {'state':<9} {'splits q/r/c':>13} "
              f"{'out rows':>11} {'bytes':>10} {'backlog':>8} "
              f"{'peak':>9}")
    lines = [header, "-" * len(header)]
    for s in samples:
        splits = (f"{s['splits_queued']}/{s['splits_running']}/"
                  f"{s['splits_completed']}")
        lines.append(
            f"{(s['t'] - t0) * 1000:>8.0f} {s['state']:<9} "
            f"{splits:>13} {s['output_rows']:>11} "
            f"{s['output_bytes']:>10} {s['exchange_backlog']:>8} "
            f"{_fmt_bytes(s['peak_memory_bytes']):>9}")
    return lines


def profile_live(args) -> int:
    import threading
    import time

    from presto_tpu.exec.context import segment_line
    from presto_tpu.server.dqr import DistributedQueryRunner
    from presto_tpu.spans import render_span_tree, validate_span_tree

    boot = (DistributedQueryRunner.tpcds if args.catalog == "tpcds"
            else DistributedQueryRunner.tpch)
    with boot(scale=args.scale, n_workers=args.workers,
              event_log_path=args.event_log) as dqr:
        co_uri = dqr.coordinator.uri
        live_polls = []
        if args.live:
            # --live: run the statement on a thread and follow the
            # timeseries endpoint while the query is RUNNING
            out = {}

            def run():
                try:
                    out["res"] = dqr.execute(args.sql)
                except Exception as e:  # noqa: BLE001
                    out["err"] = e

            t = threading.Thread(target=run)
            t.start()
            qid = None
            while t.is_alive():
                qid = qid or dqr.client.last_query_id
                if qid:
                    try:
                        live_polls.append(_fetch_json(
                            f"{co_uri}/v1/query/{qid}/timeseries"))
                    except Exception:  # noqa: BLE001 - query racing
                        pass
                time.sleep(0.1)
            t.join()
            if "err" in out:
                raise out["err"]
            res = out["res"]
        else:
            res = dqr.execute(args.sql)
        q = list(dqr.coordinator.queries.values())[-1]
        print(f"query {q.query_id} [{q.state}] trace={q.trace_token}")
        print(f"sql: {args.sql}")
        print(f"rows: {len(res.rows)}")
        qs = q.query_stats or {}
        print(f"elapsed: {qs.get('elapsed_s', 0):.3f}s  "
              f"peak memory: {_fmt_bytes(qs.get('peak_memory_bytes'))}  "
              f"jit: {qs.get('jit_dispatches', 0)} dispatches / "
              f"{qs.get('jit_compiles', 0)} compiles "
              f"({qs.get('jit_compile_ns', 0) / 1e6:.1f} ms compile)  "
              f"retries: {q.stage_retry_rounds} stage / "
              f"{q.recovery_rounds} leaf")
        print(segment_line(qs))
        print()
        for line in stage_table(q.stage_stats):
            print(line)
        print()
        # the timed span tree from the live endpoint (the same tree
        # query.json carries on QueryCompletedEvent)
        tree = _fetch_json(f"{co_uri}/v1/query/{q.query_id}/spans")
        violations = validate_span_tree(tree)
        for line in render_span_tree(tree):
            print(line)
        if args.live:
            print()
            ring = _fetch_json(
                f"{co_uri}/v1/query/{q.query_id}/timeseries")
            mid = max((len(p.get("samples", [])) for p in live_polls),
                      default=0)
            print(f"time series ({len(ring['samples'])} samples, "
                  f"{mid} observed mid-query):")
            for line in timeseries_table(ring["samples"]):
                print(line)
        if args.check:
            ok = (q.state == "FINISHED" and q.stage_stats
                  and not violations
                  and tree.get("children")
                  and all(st["reporting"] >= 1
                          for st in q.stage_stats.values())
                  and any(st["input_rows"] > 0
                          for st in q.stage_stats.values())
                  and any(ts.get("elapsed_s", 0) > 0
                          for tss in q.task_stats.values()
                          for ts in tss))
            print(f"\ncheck: profile rollup "
                  f"{'complete' if ok else 'INCOMPLETE'}")
            return 0 if ok else 1
    return 0


def profile_replay(args) -> int:
    from presto_tpu.events import read_event_log

    events = read_event_log(args.replay)
    if not events:
        print("empty event log")
        return 1
    t0 = min(e.get("create_time") or e.get("time") or 0 for e in events)
    for e in events:
        at = (e.get("time") or e.get("end_time") or
              e.get("create_time") or t0) - t0
        kind = e["event"]
        extra = ""
        if kind == "QueryCreatedEvent":
            extra = f"sql={e.get('sql', '')[:60]!r}"
        elif kind == "QueryCompletedEvent":
            extra = (f"state={e.get('state')} rows={e.get('output_rows')} "
                     f"wall={e.get('end_time', 0) - e.get('create_time', 0):.3f}s")
        elif kind == "StageRetryEvent":
            extra = (f"fragments={e.get('fragment_ids')} "
                     f"round={e.get('round')} reason={e.get('reason')!r} "
                     f"producer_reruns={e.get('producer_reruns')} "
                     f"spooled={e.get('spooled')}")
        elif kind == "TaskRecoveryEvent":
            extra = f"dead={e.get('dead_uri')} tasks={e.get('task_ids')}"
        elif kind == "WorkerDrainEvent":
            extra = (f"worker={e.get('worker_uri')} "
                     f"tasks={e.get('task_ids')}")
        elif kind == "SpeculationEvent":
            extra = (f"{e.get('task_id')} -> {e.get('clone_id')} "
                     f"[{e.get('outcome')}]")
        print(f"+{at:8.3f}s {kind:<22} query={e.get('query_id')} "
              f"trace={e.get('trace_token')} {extra}")
    for e in events:
        if e["event"] == "QueryCompletedEvent" and e.get("stage_stats"):
            print(f"\nstage stats for {e['query_id']}:")
            for line in stage_table(
                    {str(st["fragment_id"]): st
                     for st in e["stage_stats"]}):
                print(line)
        if e["event"] == "QueryCompletedEvent" and e.get("spans"):
            # the serialized span tree round-trips: query.json carries
            # the same tree /v1/query/{id}/spans served live
            from presto_tpu.spans import render_span_tree

            print(f"\nspans for {e['query_id']}:")
            for line in render_span_tree(e["spans"]):
                print(line)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sql", default="select l_returnflag, count(*), "
                    "sum(l_extendedprice) from lineitem "
                    "group by l_returnflag")
    ap.add_argument("--catalog", choices=["tpch", "tpcds"],
                    default="tpch")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--event-log", default=None,
                    help="also write a query.json event log here")
    ap.add_argument("--replay", default=None,
                    help="render a query.json event log instead of "
                         "running a statement")
    ap.add_argument("--live", action="store_true",
                    help="follow /v1/query/{id}/timeseries while the "
                         "statement runs and render the sample ring")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: exit nonzero unless every stage "
                         "reported stats and spans")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          file=sys.stderr)
    if args.replay:
        return profile_replay(args)
    return profile_live(args)


if __name__ == "__main__":
    sys.exit(main())
