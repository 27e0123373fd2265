#!/usr/bin/env python3
"""chip_smoke.py: the served SQL path on the chip, end to end, once.

Default (one chip): a real coordinator and two workers on ephemeral HTTP
ports in this one process (``DistributedQueryRunner.tpch``), TPC-H SF1
generated from the connector's fixed hash streams, Q1 / Q6 / Q3 submitted
over HTTP through ``presto_tpu.client`` twice each (cold, then warm), every
result compared with a plain numpy computation over the same generated
columns that shares no code with the engine.  The warm run of each query
must build nothing, by the engine's own ``jit_compiles`` (kernel-cache
misses) and by XLA's backend-compile event, which JAX raises around
``compile_or_get_cached``: it fires for a load from the persistent cache
as well as for a compile, so the cold run's count is of programs built,
not of cache misses.  The warm run must also scan nothing: every table
scan of every leaf task is a hit of the device-resident scan cache
(``scan_cache_hits`` / ``scan_cache_misses`` in ``queryStats``), and a
leaf task of Q1 or Q6 must hold its pre-reduced partial states on the
device and flush them at most twice (``prereduce_batches_held`` /
``prereduce_flushes``).  In a warm Q3 the tasks of three stages (the
``lineitem`` scan, the ``orders`` scan and the join) must end every
dispatch of their segments without a compaction (``compactions_skipped``
above 0, ``compactions`` 0); only ``customer``'s filter compacts.  In a
warm Q1 no final task may spend 0.08 s in ``HashAggregationOperator`` and
``OrderByOperator`` together (``warm_final_stage_s``: each finish is one
staging, one named program and one read; 0.28 s when they ran eagerly).
A worker's scan batch must sit on a TPU device.

``--chips 4``: only the collective data plane (``mesh_device_exchange``,
four co-resident workers on one 4-device mesh), Q1 and Q3 at SF1 against
the same reference; collective exchange modes, no fallback, sharded inputs
on four distinct devices.

Exit code 0 and a last line ``{"ok": true, "device": {...}}`` only when
every phase passed on a TPU.  Without a TPU, or outside the repo, the
script fails before printing any result.  Earlier lines are one JSON
object each (smoke output for CHANGES.md, not benchmark metrics).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import urllib.request
from decimal import Decimal

import numpy as np

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.06 - 0.01 and 0.06 + 0.01
  and l_quantity < 24
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

SCALE = 1.0  # TPC-H SF1, the first pinned config of BASELINE.json
RTOL = 1e-6  # DOUBLE to 1e-6, as benchmark/check.py compares
CLIENT_TIMEOUT_S = 900.0  # a cold query compiles for minutes on the chip
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def days(iso: str) -> int:
    return int((np.datetime64(iso) - np.datetime64("1970-01-01"))
               .astype(int))


def iso(d) -> str:
    return str(np.datetime64("1970-01-01") + np.timedelta64(int(d), "D"))


# ---------------------------------------------------------------------------
# The plain reference: numpy over the generated columns, nothing of the engine
# ---------------------------------------------------------------------------

def host_columns() -> dict:
    """The generated TPC-H columns the three queries read, as numpy arrays
    (dictionary columns decoded to their strings' codes + the strings)."""
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(scale=SCALE)
    out = {}
    for table, cols in (
            ("lineitem", ["l_orderkey", "l_quantity", "l_extendedprice",
                          "l_discount", "l_tax", "l_returnflag",
                          "l_linestatus", "l_shipdate"]),
            ("orders", ["o_orderkey", "o_custkey", "o_orderdate",
                        "o_shippriority"]),
            ("customer", ["c_custkey", "c_mktsegment"])):
        handle = conn.get_table(table)
        parts = {c: [] for c in cols}
        dicts = {}
        for split in conn.get_splits(handle, 1):
            for batch in conn.page_source(split, cols, 1 << 20):
                for c, col in zip(cols, batch.columns):
                    parts[c].append(np.asarray(col.values)[:batch.num_rows])
                    if col.dictionary is not None:
                        dicts[c] = np.asarray(
                            [str(v) for v in col.dictionary.values])
        for c in cols:
            arr = np.concatenate(parts[c])
            out[c] = dicts[c][arr] if c in dicts else arr
    return out


def ref_q1(c: dict) -> list:
    sel = c["l_shipdate"] <= days("1998-12-01") - 90
    rf, ls = c["l_returnflag"][sel], c["l_linestatus"][sel]
    qty, price = c["l_quantity"][sel], c["l_extendedprice"][sel]
    disc, tax = c["l_discount"][sel], c["l_tax"][sel]
    disc_price = price * (1.0 - disc)
    charge = disc_price * (1.0 + tax)
    keys, inv = np.unique(np.char.add(rf, ls), return_inverse=True)
    n = np.bincount(inv, minlength=len(keys))
    sums = [np.bincount(inv, weights=w, minlength=len(keys))
            for w in (qty, price, disc_price, charge, disc)]
    return [(k[0], k[1], sums[0][g], sums[1][g], sums[2][g], sums[3][g],
             sums[0][g] / n[g], sums[1][g] / n[g], sums[4][g] / n[g],
             int(n[g])) for g, k in enumerate(keys)]


def ref_q6(c: dict) -> list:
    # SQL arithmetic on the decimal literals, as TPC-H defines the bounds:
    # 0.05 and 0.07 exactly.  (IEEE doubles make 0.06 + 0.01 one ulp less
    # than 0.07, a difference below the resolution of the chip's DOUBLE.)
    lo = float(Decimal("0.06") - Decimal("0.01"))
    hi = float(Decimal("0.06") + Decimal("0.01"))
    sd, disc = c["l_shipdate"], c["l_discount"]
    sel = ((sd >= days("1994-01-01")) & (sd < days("1995-01-01"))
           & (disc >= lo) & (disc <= hi) & (c["l_quantity"] < 24))
    return [(float((c["l_extendedprice"][sel] * disc[sel]).sum()),)]


def ref_q3(c: dict) -> list:
    cut = days("1995-03-15")
    building = c["c_custkey"][c["c_mktsegment"] == "BUILDING"]
    osel = np.isin(c["o_custkey"], building) & (c["o_orderdate"] < cut)
    okey = c["o_orderkey"][osel]
    odate, oprio = c["o_orderdate"][osel], c["o_shippriority"][osel]
    order = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[order], odate[order], oprio[order]
    lsel = c["l_shipdate"] > cut
    lkey = c["l_orderkey"][lsel]
    pos = np.clip(np.searchsorted(okey, lkey), 0, max(len(okey) - 1, 0))
    hit = okey[pos] == lkey
    rev = (c["l_extendedprice"][lsel] * (1.0 - c["l_discount"][lsel]))[hit]
    sums = np.bincount(pos[hit], weights=rev, minlength=len(okey))
    live = np.flatnonzero(np.bincount(pos[hit], minlength=len(okey)))
    top = live[np.lexsort((odate[live], -sums[live]))][:10]
    return [(int(okey[g]), float(sums[g]), iso(odate[g]), int(oprio[g]))
            for g in top]


def references(refs: dict) -> dict:
    """Each query's expected rows, computed once over the SF1 columns."""
    t0 = time.perf_counter()
    cols = host_columns()
    want = {name: ref(cols) for name, ref in refs.items()}
    emit({"phase": "reference", "scale": SCALE,
          "lineitem_rows": int(len(cols["l_orderkey"])),
          "orders_rows": int(len(cols["o_orderkey"])),
          "customer_rows": int(len(cols["c_custkey"])),
          "seconds": time.perf_counter() - t0})
    return want


def compare(name: str, got: list, want: list) -> float:
    """Counts, integers, keys and dates exact; DOUBLE to RTOL.  Returns
    the max relative error over the DOUBLE cells; raises on any miss."""
    if len(got) != len(want):
        raise AssertionError(f"{name}: {len(got)} rows, reference has "
                             f"{len(want)}")
    worst = 0.0
    for r, (g_row, w_row) in enumerate(zip(got, want)):
        if len(g_row) != len(w_row):
            raise AssertionError(f"{name} row {r}: width differs")
        for g, w in zip(g_row, w_row):
            if isinstance(w, float):
                if g is None or not np.isfinite(g):
                    raise AssertionError(f"{name} row {r}: {g!r} not finite")
                rel = abs(g - w) / max(abs(w), 1e-300)
                worst = max(worst, rel)
                if rel > RTOL:
                    raise AssertionError(
                        f"{name} row {r}: {g!r} vs reference {w!r} "
                        f"(rel {rel:.3e} > {RTOL})")
            elif g != w:
                raise AssertionError(
                    f"{name} row {r}: {g!r} != reference {w!r}")
    return worst


# ---------------------------------------------------------------------------
# Driving the cluster
# ---------------------------------------------------------------------------

class XlaCompiles:
    """Counts XLA programs built (compiled, or loaded from the
    persistent cache: the event is the same) through jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


def _platforms(arrays) -> set:
    return {d.platform for a in arrays if hasattr(a, "devices")
            for d in a.devices()}


def observe_scan_placement(seen: dict) -> None:
    """Observe (not steer) where a worker's scanned rows live: the output
    of every TableScanOperator and of every FusedSegmentOperator (with
    pipeline fusion, the default, the scan hands over host batches and
    the segment right behind it stages them inside its jitted program)."""
    from presto_tpu.exec.fusion import FusedSegmentOperator
    from presto_tpu.exec.operators import TableScanOperator

    def wrap(cls, label):
        inner = cls.get_output

        def get_output(self):
            batch = inner(self)
            if batch is not None:
                seen.setdefault(label, set()).update(
                    _platforms(c.values for c in batch.columns) or {"host"})
            return batch

        cls.get_output = get_output

    wrap(TableScanOperator, "scan")
    wrap(FusedSegmentOperator, "fused_segment")


def observe_mesh_inputs(devices: set) -> None:
    """Observe the devices the collective plane's sharded program inputs
    were placed on (parallel/sqlmesh._MeshProgram device_puts them with
    the mesh's row sharding)."""
    from presto_tpu.parallel.sqlmesh import _MeshProgram

    inner = _MeshProgram.run

    def run(self):
        out = inner(self)
        for a in self._args:
            devices.update(a.sharding.device_set)
        return out

    _MeshProgram.run = run


def query_detail(runner, qid: str) -> dict:
    with urllib.request.urlopen(
            f"{runner.coordinator.uri}/v1/query/{qid}", timeout=30) as resp:
        return json.loads(resp.read())


FINISH_OPERATORS = ("HashAggregationOperator", "OrderByOperator")
FINAL_STAGE_LIMIT_S = 0.08


def final_stage_s(runner, qid: str) -> float:
    """The longest a task spent in its GROUP BY's and ORDER BY's finish
    (the operators' ``wallS`` in /v1/query/{id}/spans, added a task)."""
    with urllib.request.urlopen(
            f"{runner.coordinator.uri}/v1/query/{qid}/spans",
            timeout=30) as resp:
        tree = json.loads(resp.read())
    return max(
        sum(op["wallS"] for op in task["attributes"]["operators"]
            if op["operator"].rsplit(".", 1)[-1] in FINISH_OPERATORS)
        for stage in tree["children"] if stage["kind"] == "stage"
        for task in stage["children"])


def run_query(runner, client, xla: XlaCompiles, sql: str):
    before = len(xla.names)
    t0 = time.perf_counter()
    _columns, data = client.execute(sql, timeout_s=CLIENT_TIMEOUT_S)
    wall = time.perf_counter() - t0
    detail = query_detail(runner, client.last_query_id)
    rows = [tuple(r) for r in data]
    return rows, wall, detail, xla.names[before:]


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def one_chip(xla: XlaCompiles) -> None:
    from presto_tpu.server.dqr import DistributedQueryRunner

    placement: dict = {}
    observe_scan_placement(placement)
    want = references({"q1": ref_q1, "q6": ref_q6, "q3": ref_q3})
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=2) as runner:
        client = runner.new_client(user="chip_smoke")
        for name, sql in (("q1", Q1), ("q6", Q6), ("q3", Q3)):
            line = {"phase": "query", "query": name, "workers": 2}
            for temp in ("cold", "warm"):
                rows, wall, detail, compiled = run_query(
                    runner, client, xla, sql)
                stats = detail.get("queryStats") or {}
                line[f"{temp}_wall_s"] = wall
                line[f"{temp}_jit_compiles"] = int(stats["jit_compiles"])
                line[f"{temp}_xla_compiles"] = len(compiled)
                line[f"{temp}_scan_cache"] = [
                    int(stats["scan_cache_hits"]),
                    int(stats["scan_cache_misses"])]
                # dispatched batches whose partial states stayed on the
                # device, and their hand-overs to the sink (exec/fusion.py)
                line[f"{temp}_prereduce_batches_held"] = int(
                    stats["prereduce_batches_held"])
                line[f"{temp}_prereduce_flushes"] = int(
                    stats["prereduce_flushes"])
                # dispatches whose program compacted its rows at the end,
                # and those that ended with a row mask and moved nothing
                line[f"{temp}_compactions"] = [
                    int(stats["compactions"]),
                    int(stats["compactions_skipped"])]
                line[f"{temp}_max_rel_err"] = compare(
                    f"{name} {temp}", rows, want[name])
                line["rows"] = len(rows)
                if detail.get("resultCached"):
                    raise AssertionError(f"{name}: served from the result "
                                         "cache, not from the device")
            if name == "q1":
                line["warm_final_stage_s"] = final_stage_s(
                    runner, client.last_query_id)
            emit(line)
            if line.get("warm_final_stage_s", 0.0) >= FINAL_STAGE_LIMIT_S:
                raise AssertionError(
                    f"q1: a finish is one staging, one named program and one "
                    f"read; a final task of the warm run spent "
                    f"{line['warm_final_stage_s']:.3f} s in "
                    f"{' + '.join(FINISH_OPERATORS)} (limit "
                    f"{FINAL_STAGE_LIMIT_S} s; 0.28 s when they ran eagerly)")
            if line["warm_jit_compiles"] or compiled:
                raise AssertionError(
                    f"{name}: the warm run compiled "
                    f"({line['warm_jit_compiles']} jit, {len(compiled)} "
                    f"XLA: {sorted(set(compiled))})")
            # (held, flushes) of each task that held partials on the device
            holding = [(ts["prereduce_batches_held"], ts["prereduce_flushes"])
                       for tasks in (detail.get("taskStats") or {}).values()
                       for ts in tasks if ts.get("prereduce_batches_held")]
            if name in ("q1", "q6") and (
                    not holding or any(f > 2 for _held, f in holding)):
                raise AssertionError(
                    f"{name}: a leaf task hands its pre-reduced partials to "
                    f"the sink once, at most twice; the warm run's tasks "
                    f"read (held, flushes) {holding}")
            # stage -> (compactions, skipped) of each of its tasks
            moved = {stage: [(ts["compactions"], ts["compactions_skipped"])
                             for ts in tasks]
                     for stage, tasks in
                     (detail.get("taskStats") or {}).items()}
            skipping = [stage for stage, tasks in moved.items()
                        if all(done == 0 and skipped > 0
                               for done, skipped in tasks)]
            compacting = [stage for stage, tasks in moved.items()
                          if any(done for done, _skipped in tasks)]
            # at SF1 both joins are partitioned and the second has a
            # stage of its own; a rehearsal at a smaller scale broadcasts
            # the builds and joins in lineitem's stage
            if name == "q3" and (len(compacting) != 1 or len(skipping)
                                 < (3 if SCALE >= 1.0 else 2)):
                raise AssertionError(
                    f"q3: only customer's filter compacts; the lineitem, "
                    f"orders and join stages end their segments without "
                    f"a compaction; the warm run's tasks read "
                    f"(compactions, skipped) {moved}")
            hits, misses = line["warm_scan_cache"]
            if misses or not hits:
                raise AssertionError(
                    f"{name}: the warm run scanned a table the cold run "
                    f"should have kept on the device ({hits} hits, "
                    f"{misses} misses)")
    seen = set().union(*placement.values())
    emit({"phase": "placement",
          **{k: sorted(v) for k, v in sorted(placement.items())}})
    if "tpu" not in seen or seen - {"tpu", "host"}:
        raise AssertionError(f"scanned rows sat on {sorted(seen)}: want "
                             "the TPU (host = not yet staged) only")


def four_chips(xla: XlaCompiles) -> None:
    import jax

    from presto_tpu.config import DEFAULT
    from presto_tpu.server.dqr import DistributedQueryRunner

    input_devices: set = set()
    observe_mesh_inputs(input_devices)
    want = references({"q1": ref_q1, "q3": ref_q3})
    cfg = dataclasses.replace(DEFAULT, mesh_device_exchange=True)
    with DistributedQueryRunner.tpch(scale=SCALE, n_workers=4,
                                     config=cfg) as runner:
        co = runner.coordinator
        client = runner.new_client(user="chip_smoke")
        for name, sql in (("q1", Q1), ("q3", Q3)):
            rows, wall, detail, compiled = run_query(
                runner, client, xla, sql)
            modes = detail.get("exchangeModes") or {}
            info = detail.get("deviceExchange") or {}
            line = {"phase": "mesh_query", "query": name, "workers": 4,
                    "wall_s": wall, "rows": len(rows),
                    "xla_compiles": len(compiled), "exchange_modes": modes,
                    "fallback": info.get("fallback"),
                    "max_rel_err": compare(name, rows, want[name])}
            emit(line)
            if set(modes) != {"device"} or "fallback" in info:
                raise AssertionError(
                    f"{name}: not served by the collective plane: "
                    f"modes={modes} info={info}")
        fallbacks = dict(co.device_exchange_counters["fallbacks"])
        emit({"phase": "mesh_placement", "fallbacks": fallbacks,
              "sharded_input_devices": sorted(str(d) for d in input_devices),
              "note": "the HTTP plane would put every worker on device 0"})
        if fallbacks:
            raise AssertionError(f"device fallbacks counted: {fallbacks}")
        if len(input_devices) != 4 or len(jax.devices()) != 4:
            raise AssertionError(
                f"sharded inputs on {len(input_devices)} devices, "
                f"{len(jax.devices())} visible; want 4 and 4")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the collective data plane on a 4-chip "
                         "mesh (run by the builder, never by the driver)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    import presto_tpu  # noqa: F401 - places the compile cache (config.py)
    from presto_tpu import native

    xla = XlaCompiles()
    cache_dir = jax.config.jax_compilation_cache_dir
    start_entries = cache_entries(cache_dir)
    emit({"phase": "start", "device_kind": dev.device_kind,
          "devices": len(devices), "jax": jax.__version__,
          "cache_dir": cache_dir,
          "cache_dir_from_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "cache_entries_at_start": start_entries,
          "cache_empty_at_start": start_entries == 0})
    if native.lib() is None:
        raise RuntimeError("presto_tpu.native did not build/load: the "
                           "exchange wire would run without LZ4")

    if args.chips == 4:
        four_chips(xla)
    else:
        one_chip(xla)

    stats = dev.memory_stats() or {}
    emit({"phase": "end", "xla_compiles_total": len(xla.names),
          "cache_dir": cache_dir,
          "cache_entries_at_end": cache_entries(cache_dir),
          "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "bytes_limit": stats.get("bytes_limit")})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
