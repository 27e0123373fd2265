"""SQL on the device mesh: the whole distributed query as ONE SPMD program.

This is the wiring the reference achieves with AddExchanges choosing a
partitioning per subtree (presto-main/.../sql/planner/optimizations/
AddExchanges.java:114) and NodePartitioningManager binding partitions to
nodes (sql/planner/NodePartitioningManager.java:53): here the fragmenter's
DistributedPlan is lowered onto a ``jax.sharding.Mesh`` so that

- every 'source' / 'hash' fragment runs replicated over the mesh shards,
  each shard holding its slice of the rows,
- every fragment boundary becomes an ICI collective chosen by the
  producer's ``output_partitioning`` — 'hash' -> ``all_to_all``
  repartition (P1), 'broadcast' -> ``all_gather`` (P2), 'single' ->
  gather (P4),
- and the ENTIRE fragment DAG traces into a single ``shard_map``-ped,
  jitted XLA program, so exchanges overlap with compute and no
  serialize/HTTP/deserialize hop exists inside a slice.  (The HTTP data
  plane in presto_tpu.server remains the cross-slice / elastic tier;
  this module is the intra-slice fast path.)

Row representation per shard: fixed-capacity padded columns plus a `live`
mask (no compaction on filter — dead rows are masked, the mask fuses into
the aggregation/join kernels).  Static capacities derive from host-known
row counts; joins can exceed their estimate, which sets a per-shard
overflow flag and the host re-runs at a doubled capacity bucket (the
distributed recompile-on-bucket-change policy, same as the local kernels).

Unsupported shapes (window functions, nested types, distinct aggregates,
host-evaluated string paths) raise ``MeshUnsupported`` — callers fall back
to the operator tier, mirroring how the reference falls back from grouped
to ungrouped execution when a plan shape does not qualify.

Telemetry is part of the traced program (PR 12): per-fragment, per-shard
counters — scan input rows, fragment output rows, rows/bytes received
through every boundary collective, and a peak live-intermediate estimate
— ride OUT of the SPMD program as one extra int64 vector output, so the
coordinator can fold a mesh query into the same ``TaskStats ->
StageStats -> QueryStats`` rollup an HTTP query gets (run_info()
["per_shard"]).  With ``mesh_progress_beacons`` on, every boundary also
fires a ``jax.debug.callback`` beacon (parallel/beacons.py) so progress
is observable MID-program; off traces a beacon-free program (PR 11
exactly).  Compiled whole-query programs live in the shared
``kernelcache`` registry ("mesh_program"), so cross-query hits/misses
and build wall (trace+lower vs XLA compile, via ``timed_first_call``)
surface on /metrics like every other kernel cache.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu import types as T
from presto_tpu.batch import (
    Batch, Column, Dictionary, batch_from_pylist, concat_batches,
    next_bucket,
)
from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.connectors.api import ConnectorRegistry
from presto_tpu.expr.compile import ExprCompiler, needs_host_path
from presto_tpu.expr.ir import InputRef, RowExpression
from presto_tpu.sql.plan import (
    AggregationNode, EnforceSingleRowNode, FilterNode, JoinNode, LimitNode,
    OutputNode, PlanNode, ProjectNode, RemoteMergeNode, RemoteSourceNode,
    SemiJoinNode, SortNode, TableScanNode, UnionNode, UnnestNode,
    ValuesNode, WindowNode,
)

_MESH_PRIMS = ("sum", "count", "min", "max")

# compiled whole-query SPMD programs, shared across runners and keyed
# (runner serial, sql) — a named kernelcache so program-cache hits,
# misses, and compile wall land on /metrics (the generated-class-cache
# role at whole-query granularity)
from presto_tpu import kernelcache as _kc  # noqa: E402
from presto_tpu.spans import activity  # noqa: E402

_PROGRAM_CACHE = _kc.new_cache("mesh_program")

#: rows a page of a scan's first build is generated in: the connector's
#: numpy temporaries stay in reused allocations at this size (TPC-H
#: lineitem on the chip's host: 3.7 M rows/s, against 0.56 M rows/s in
#: pages of 1 << 24, which at SF10 is 16 s against 107 s of set-up)
_SCAN_BATCH_ROWS = 1 << 20

#: fragments actually traced/lowered into SPMD programs, process-wide —
#: the mesh-tier mirror of ``sql.physical.FRAGMENTS_LOWERED``.  The
#: checkpoint-resume tests pin "completed fragments are never
#: re-lowered" against deltas of this counter (a checkpoint-fed
#: fragment does NOT bump it: its subtree is replaced by a host feed)
FRAGMENTS_LOWERED = 0


class MeshUnsupported(NotImplementedError):
    """Plan shape outside the mesh tier; caller falls back to operators."""


@dataclasses.dataclass
class MCol:
    """One column of a shard-local table inside the traced program."""

    values: object                 # traced array [cap]
    valid: object                  # traced bool array [cap] | None
    type: T.Type
    dictionary: Optional[Dictionary] = None


@dataclasses.dataclass
class MTable:
    """A shard-local row set: padded columns + live mask.

    ``est`` is the host-side estimate (upper bound where possible) of the
    TOTAL live rows across all shards — it sizes downstream capacities.
    ``compacted`` means live rows form a prefix on every shard.
    ``replicated`` means every shard holds the IDENTICAL rows (the result
    of a gather/broadcast, or anything derived from only-replicated
    inputs); exchanges must treat such a table as ONE copy, not as
    shard-distinct slices — gathering it again would multiply rows by the
    shard count (the Q15 scalar-subquery shape).
    """

    cols: List[MCol]
    live: object                   # traced bool [cap]
    cap: int
    est: int
    compacted: bool = False
    replicated: bool = False

    def pairs(self):
        return [(c.values, c.valid) for c in self.cols]

    @property
    def num_rows(self):
        import jax.numpy as jnp

        return self.live.sum().astype(jnp.int64)


def _check_supported(node: PlanNode) -> None:
    if isinstance(node, UnnestNode):
        raise MeshUnsupported(type(node).__name__)
    for _, t in node.columns:
        if t.is_nested:
            raise MeshUnsupported(f"nested type {t.display()}")
    if isinstance(node, AggregationNode):
        if any(a.distinct for a in node.aggregates):
            raise MeshUnsupported("distinct aggregate")
        for a in node.aggregates:
            for prim, _ in a.spec.components:
                if prim not in _MESH_PRIMS + ("sumsq", "sumln"):
                    raise MeshUnsupported(f"agg component {prim}")
    if isinstance(node, JoinNode):
        if node.kind not in ("inner", "left", "cross"):
            raise MeshUnsupported(f"{node.kind} join")
        if node.kind == "left" and node.residual is not None:
            raise MeshUnsupported("left-join residual")
    exprs: List[RowExpression] = []
    if isinstance(node, FilterNode):
        exprs.append(node.predicate)
    if isinstance(node, ProjectNode):
        exprs.extend(node.expressions)
    if isinstance(node, JoinNode) and node.residual is not None:
        exprs.append(node.residual)
    if exprs and needs_host_path(exprs):
        raise MeshUnsupported("host-path expression")
    for s in node.sources:
        _check_supported(s)


class MeshQueryRunner:
    """SQL in, rows out, over an n-device mesh (the distributed
    LocalQueryRunner: same front end, collective execution)."""

    _serial_counter = 0

    def __init__(self, registry: ConnectorRegistry, default_catalog: str,
                 n_devices: int = 8, config: EngineConfig = DEFAULT):
        from presto_tpu.parallel.mesh import make_mesh
        from presto_tpu.sql.planner import Metadata

        self.registry = registry
        self.metadata = Metadata(registry, default_catalog)
        self.config = config
        self.mesh = make_mesh(n_devices)
        self.nparts = n_devices
        # program-cache identity: compiled _MeshPrograms live in the
        # shared "mesh_program" kernelcache keyed (serial, sql), so the
        # registry's hit/miss/compile counters cover every runner
        MeshQueryRunner._serial_counter += 1
        self._serial = MeshQueryRunner._serial_counter
        # observability for the last successful execution: exchange-mode
        # counters per fragment boundary, per-shard stats read out of
        # the program, kernel-tier markers, and compile attribution (the
        # stats-rollup feed of the device-sharded exchange tier)
        self.last_run_info: Dict = {}

    @classmethod
    def tpch(cls, scale: float = 0.01, n_devices: int = 8,
             config: EngineConfig = DEFAULT) -> "MeshQueryRunner":
        from presto_tpu.connectors.tpcds import TpcdsConnector
        from presto_tpu.connectors.tpch import TpchConnector

        reg = ConnectorRegistry()
        reg.register("tpch", TpchConnector(scale=scale))
        reg.register("tpcds", TpcdsConnector(scale=scale))
        return cls(reg, "tpch", n_devices, config)

    @classmethod
    def tpcds(cls, scale: float = 0.003, n_devices: int = 8,
              config: EngineConfig = DEFAULT) -> "MeshQueryRunner":
        """TPC-DS default catalog — the BASELINE.md Q72/Q95 multi-chip
        configs on the SPMD mesh tier (shapes outside the mesh subset,
        e.g. Q95's COUNT(DISTINCT), raise MeshUnsupported and fall back
        to the operator tier like every other caller)."""
        from presto_tpu.connectors.tpcds import TpcdsConnector
        from presto_tpu.connectors.tpch import TpchConnector

        reg = ConnectorRegistry()
        reg.register("tpcds", TpcdsConnector(scale=scale))
        reg.register("tpch", TpchConnector(scale=scale))
        return cls(reg, "tpcds", n_devices, config)

    def plan_distributed(self, sql: str):
        from presto_tpu.sql.parser import parse_statement

        return self.plan_distributed_stmt(parse_statement(sql))

    def plan_distributed_stmt(self, stmt):
        from presto_tpu.sql import tree as t
        from presto_tpu.sql.optimizer import optimize
        from presto_tpu.sql.planner import Planner

        if not isinstance(stmt, (t.Query, t.SetOperation)):
            raise MeshUnsupported("only queries run on the mesh")
        logical = Planner(self.metadata).plan(stmt)
        return self.fragment_plan(optimize(logical, self.metadata))

    def fragment_plan(self, optimized):
        from presto_tpu.server.fragmenter import Fragmenter

        return Fragmenter(metadata=self.metadata,
                          config=self.config).fragment(optimized)

    def execute(self, sql: str):
        from presto_tpu.sql.parser import parse_statement

        return self.execute_stmt(parse_statement(sql), key=sql)

    def execute_stmt(self, stmt, key: Optional[str] = None):
        """Execute a parsed query; ``key`` caches the compiled program
        (falls back to the statement's repr — tree nodes are frozen
        dataclasses, so the repr is a stable structural key)."""
        return self._execute_planned(
            key if key is not None else repr(stmt),
            lambda: self.plan_distributed_stmt(stmt))

    def execute_plan(self, optimized, key: str):
        """Execute an ALREADY-optimized logical plan (LocalQueryRunner's
        whole-query path plans once and hands it over)."""
        return self._execute_planned(
            key, lambda: self.fragment_plan(optimized))

    def execute_dplan(self, dplan, key: str):
        """Execute an ALREADY-fragmented plan: the coordinator's
        device-sharded exchange tier hands its DistributedPlan over, so
        the collective tier and the HTTP tier run the IDENTICAL fragment
        DAG — only the boundary transport differs (in-program collective
        vs PartitionedOutput -> wire pages -> ExchangeOperator)."""
        return self._execute_planned(key, lambda: dplan)

    def execute_dplan_checkpointed(self, dplan, key: str, *,
                                   completed=None, on_checkpoint=None,
                                   fault_hook=None):
        """Execute a fragmented plan as a SEQUENCE of per-fragment SPMD
        programs (``mesh_checkpoint_boundaries``): fragments run in
        topological (producers-first) order; each group's root output is
        read back to the host — the boundary checkpoint — and handed to
        ``on_checkpoint(fid, batch)`` (the coordinator spools it); later
        groups are fed from the checkpointed batches instead of
        re-lowering their producers.  ``completed`` maps fragment id ->
        host Batch for checkpoints that already exist: on resume, those
        groups are SKIPPED entirely (zero re-execution, zero
        re-lowering).  ``fault_hook(fid)`` fires before each group, the
        chaos-injection seam.  Checkpoint-group programs are never
        program-cached: restartability is bought with per-group
        dispatch, so repeat queries should run the whole-program path."""
        from presto_tpu.localrunner import QueryResult

        completed = {} if completed is None else completed
        for frag in dplan.fragments:
            _check_supported(frag.root)
        all_info: List[Dict] = []
        lowered: List[int] = []
        for fid in self._group_order(dplan):
            if fid != dplan.root_fragment_id and fid in completed:
                continue
            if fault_hook is not None:
                fault_hook(fid)
            prog = None
            batch = None
            for attempt in range(4):
                prog = _MeshProgram(self, dplan,
                                    cap_scale=1 << attempt,
                                    prepared=prog, root_fid=fid,
                                    ckpt=completed)
                batch, overflowed = prog.run()
                if not overflowed:
                    break
                batch = None
            if batch is None:
                raise MeshUnsupported(
                    f"mesh execution did not converge on fragment {fid}"
                    + (f" ({', '.join(prog.overflow_labels)})"
                       if getattr(prog, 'overflow_labels', None)
                       else ""))
            all_info.append(dict(
                prog.run_info(), compile_ns=prog.compile_ns,
                build_spans=dict(prog.build_spans)))
            lowered.extend(prog.lowered_fids)
            if fid == dplan.root_fragment_id:
                self.last_run_info = _merge_run_info(
                    all_info,
                    checkpoints=sorted(completed),
                    lowered=sorted(set(lowered)))
                return QueryResult(dplan.column_names,
                                   dplan.column_types,
                                   batch.to_pylist())
            completed[fid] = batch
            if on_checkpoint is not None:
                on_checkpoint(fid, batch)
        raise MeshUnsupported("plan has no reachable root fragment")

    @staticmethod
    def _group_order(dplan) -> List[int]:
        """Checkpoint-group schedule: DFS postorder from the root, so
        every fragment runs after all the fragments it consumes."""
        order: List[int] = []
        seen = set()

        def visit(fid: int) -> None:
            if fid in seen:
                return
            seen.add(fid)
            stack = [dplan.fragments[fid].root]
            child: List[int] = []
            while stack:
                node = stack.pop()
                fids = getattr(node, "fragment_ids", None)
                if fids:
                    child.extend(fids)
                stack.extend(node.sources)
            for c in sorted(child):
                visit(c)
            order.append(fid)

        visit(dplan.root_fragment_id)
        return order

    def _execute_planned(self, sql: str, make_dplan):
        from presto_tpu.localrunner import QueryResult

        cache_key = (self._serial, sql)
        cached = _kc.cache_get(_PROGRAM_CACHE, cache_key)
        if cached is not None:
            # repeat query: the compiled SPMD program and device-resident
            # scan inputs are reused — one dispatch per execution (the
            # kernel-cache policy applied at whole-query granularity).
            # A cross-query cache hit reports compile_ns=0: the compile
            # was paid (and attributed) by the run that built it.
            batch, overflowed = cached.run()
            if not overflowed:
                dplan = cached.dplan
                self.last_run_info = dict(cached.run_info(),
                                          compile_ns=0,
                                          program_cached=True)
                return QueryResult(dplan.column_names, dplan.column_types,
                                   batch.to_pylist())
            _kc.cache_pop(_PROGRAM_CACHE, cache_key)
        dplan = make_dplan()
        for frag in dplan.fragments:
            _check_supported(frag.root)
        last_err = None
        prog = None
        for attempt in range(4):
            prog = _MeshProgram(self, dplan, cap_scale=1 << attempt,
                                prepared=prog)
            batch, overflowed = prog.run()
            if not overflowed:
                if prog.cacheable:
                    _kc.cache_put(_PROGRAM_CACHE, cache_key, prog)
                self.last_run_info = dict(
                    prog.run_info(), compile_ns=prog.compile_ns,
                    program_cached=False,
                    build_spans=dict(prog.build_spans))
                return QueryResult(dplan.column_names, dplan.column_types,
                                   batch.to_pylist())
            last_err = f"overflow at cap_scale={1 << attempt}"
        # the query expands beyond every capacity bucket this tier will
        # try: report it as unsupported so callers take the operator-tier
        # fallback path instead of failing the query
        raise MeshUnsupported(
            f"mesh execution did not converge: {last_err}"
            + (f" ({', '.join(prog.overflow_labels)})"
               if getattr(prog, 'overflow_labels', None) else ""))


def _merge_run_info(infos: List[Dict], checkpoints: List[int],
                    lowered: List[int]) -> Dict:
    """Fold per-checkpoint-group run_info dicts into ONE whole-query
    view shaped exactly like a whole-program run_info, plus the
    checkpoint accounting (group count, checkpointed fragment ids,
    fragments actually lowered) the resume tests and the EXPLAIN
    ANALYZE footer consume."""
    merged: Dict = {
        "exchange_modes": {}, "boundaries": [], "kernel_tiers": [],
        "nparts": infos[-1]["nparts"] if infos else 0,
        "cap_scale": max((i["cap_scale"] for i in infos), default=1),
        "per_shard": {"fragments": {}, "peak_live_bytes": []},
        "checkpoint_groups": len(infos),
        "checkpoints": list(checkpoints),
        "fragments_lowered": list(lowered),
        "compile_ns": sum(i.get("compile_ns", 0) for i in infos),
        "program_cached": False,
    }
    spans: Dict[str, Tuple[float, float]] = {}
    peak: Optional[List[int]] = None
    for info in infos:
        for k, v in info["exchange_modes"].items():
            merged["exchange_modes"][k] = \
                merged["exchange_modes"].get(k, 0) + v
        merged["boundaries"].extend(info["boundaries"])
        merged["kernel_tiers"].extend(info["kernel_tiers"])
        merged["per_shard"]["fragments"].update(
            info["per_shard"]["fragments"])
        p = info["per_shard"]["peak_live_bytes"]
        peak = list(p) if peak is None else [max(a, b)
                                            for a, b in zip(peak, p)]
        for k, (s, e) in (info.get("build_spans") or {}).items():
            cur = spans.get(k)
            spans[k] = (s, e) if cur is None else (min(cur[0], s),
                                                  max(cur[1], e))
    merged["per_shard"]["peak_live_bytes"] = peak or []
    merged["build_spans"] = spans
    return merged


class _MeshProgram:
    """One capacity-bucket attempt: host scan prep + traced lowering.

    ``root_fid``/``ckpt`` carve one CHECKPOINT GROUP out of the DAG:
    the program lowers only the subtree reachable from ``root_fid``,
    replacing every checkpointed producer fragment in ``ckpt`` (fid ->
    host Batch of that fragment's global output rows) with a sharded
    host feed staged exactly like a base-table scan.  Defaults lower
    the whole DAG from the plan root — byte-identical to PR 11."""

    def __init__(self, runner: MeshQueryRunner, dplan, cap_scale: int,
                 prepared: Optional["_MeshProgram"] = None,
                 root_fid: Optional[int] = None,
                 ckpt: Optional[Dict[int, Batch]] = None):
        self.runner = runner
        self.dplan = dplan
        self.cap_scale = cap_scale
        self.nparts = runner.nparts
        self.config = runner.config
        self.root_fid = (dplan.root_fragment_id if root_fid is None
                         else root_fid)
        self.ckpt = ckpt if ckpt is not None else {}
        # fragments THIS program actually lowered (trace-time), the
        # per-program never-re-lowered accounting
        self.lowered_fids: List[int] = []
        self._root_replicated = False
        self._jitted = None
        self._args = None
        # trace-time observability, kept across cached re-runs: one
        # (fragment id, collective kind) entry per fragment boundary and
        # one (operator label, tier) marker per hot-loop lowering
        self.exchange_log: List[Tuple[int, str]] = []
        self.kernel_tiers: List[Tuple[str, str]] = []
        # compile attribution: XLA-compile wall (timed_first_call over
        # the AOT compile) + the lower/compile wall-clock windows the
        # coordinator turns into span-tree phases; per-shard telemetry
        # values read back from the LAST run's stats output
        self.compile_ns = 0
        self.build_spans: Dict[str, Tuple[float, float]] = {}
        self._last_shard_stats: List[Tuple[tuple, List[int]]] = []
        # a retry shares the prepared scans, so it must inherit their
        # mutability verdict (scan prep is the only place it is learned)
        self.cacheable = prepared.cacheable if prepared is not None \
            else True
        if prepared is not None:
            # overflow retry: only capacities change — reuse the loaded,
            # sharded scan inputs instead of re-reading every base table
            # (and the staged checkpoint feeds alongside them)
            self.inputs = prepared.inputs
            self.scan_meta = prepared.scan_meta
            self.ckpt_meta = prepared.ckpt_meta
        else:
            self.inputs: List[np.ndarray] = []
            self.scan_meta: Dict[int, dict] = {}
            self.ckpt_meta: Dict[int, dict] = {}
            self._prepare_scans()

    # ---------------- host phase ----------------
    def _prepare_scans(self) -> None:
        if self.root_fid == self.dplan.root_fragment_id \
                and not self.ckpt:
            frags = list(self.dplan.fragments)
        else:
            # checkpoint group: stage scans only for the fragments this
            # group lowers, and a host feed per checkpointed producer
            needed, feeds = self._needed_fragments()
            frags = [self.dplan.fragments[f] for f in needed]
            for fid in sorted(feeds):
                self._prepare_checkpoint_feed(fid, self.ckpt[fid])
        for frag in frags:
            stack = [frag.root]
            while stack:
                node = stack.pop()
                if isinstance(node, TableScanNode):
                    self._prepare_scan(node, frag)
                stack.extend(node.sources)

    def _needed_fragments(self) -> Tuple[List[int], List[int]]:
        """Fragment ids this group lowers (reachable from ``root_fid``
        WITHOUT descending through checkpointed producers) and the
        checkpointed fragment ids it consumes as host feeds."""
        needed: List[int] = []
        feeds: List[int] = []
        stack = [self.root_fid]
        seen = set()
        while stack:
            fid = stack.pop()
            if fid in seen:
                continue
            seen.add(fid)
            if fid != self.root_fid and fid in self.ckpt:
                feeds.append(fid)
                continue
            needed.append(fid)
            nstack = [self.dplan.fragments[fid].root]
            while nstack:
                node = nstack.pop()
                fids = getattr(node, "fragment_ids", None)
                if fids:
                    stack.extend(fids)
                nstack.extend(node.sources)
        return needed, feeds

    def _prepare_checkpoint_feed(self, fid: int, batch: Batch) -> None:
        """Stage a checkpointed fragment's GLOBAL output rows as sharded
        program inputs, exactly like a base-table scan: contiguous
        split across shards into padded [P, cap] grids.  The consumer's
        boundary collective rehashes/gathers the feed, so the
        contiguous placement is semantically neutral — the checkpoint
        captured the fragment root's output BEFORE the exchange."""
        P = self.nparts
        b = batch.to_numpy()
        n = b.num_rows
        base, rem = divmod(n, P)
        counts = np.asarray([base + (i < rem) for i in range(P)],
                            np.int64)
        cap = next_bucket(int(counts.max()), minimum=8)
        offsets = np.concatenate([[0], np.cumsum(counts)])
        slots = []
        col_meta = []
        for col in b.columns:
            vals = np.asarray(col.values)[:n]
            g = np.zeros((P, cap), vals.dtype)
            for i in range(P):
                g[i, : counts[i]] = vals[offsets[i]:offsets[i + 1]]
            vslot = len(self.inputs)
            self.inputs.append(g.reshape(P * cap))
            gslot = None
            if col.valid is not None:
                va = np.asarray(col.valid)[:n]
                gv = np.zeros((P, cap), bool)
                for i in range(P):
                    gv[i, : counts[i]] = va[offsets[i]:offsets[i + 1]]
                gslot = len(self.inputs)
                self.inputs.append(gv.reshape(P * cap))
            slots.append((vslot, gslot))
            col_meta.append((col.type, col.dictionary))
        cslot = len(self.inputs)
        self.inputs.append(counts)
        self.ckpt_meta[fid] = {
            "slots": slots, "counts": cslot, "cap": cap, "total": n,
            "meta": col_meta,
        }

    def _prepare_scan(self, node: TableScanNode, frag) -> None:
        P = self.nparts
        conn = self.runner.registry.get(node.catalog)
        if not getattr(conn, "immutable_data", False):
            # the compiled program embeds this scan's rows; a mutable
            # table (memory connector INSERTs...) would serve stale data
            # from the cache — execute, but do not cache
            self.cacheable = False
        handle = conn.get_table(node.table)
        splits = conn.get_splits(handle, 1)
        batches = []
        with activity("generate"):
            for split in splits:
                batches.extend(conn.page_source(
                    split, list(node.column_names), _SCAN_BATCH_ROWS))
        if batches:
            b = (concat_batches(batches) if len(batches) > 1
                 else batches[0]).to_numpy()
        else:
            b = batch_from_pylist(node.types, [])
        n = b.num_rows
        single = frag.partitioning == "single"
        if single:
            counts = np.zeros(P, np.int64)
            counts[0] = n
        else:
            base, rem = divmod(n, P)
            counts = np.asarray([base + (i < rem) for i in range(P)],
                                np.int64)
        cap = next_bucket(int(counts.max()), minimum=8)
        slots = []
        col_meta = []
        offsets = np.concatenate([[0], np.cumsum(counts)])
        for ci, col in enumerate(b.columns):
            vals = np.asarray(col.values)[:n]
            g = np.zeros((P, cap), vals.dtype)
            for i in range(P):
                g[i, : counts[i]] = vals[offsets[i]:offsets[i + 1]]
            vslot = len(self.inputs)
            self.inputs.append(g.reshape(P * cap))
            gslot = None
            if col.valid is not None:
                va = np.asarray(col.valid)[:n]
                gv = np.zeros((P, cap), bool)
                for i in range(P):
                    gv[i, : counts[i]] = va[offsets[i]:offsets[i + 1]]
                gslot = len(self.inputs)
                self.inputs.append(gv.reshape(P * cap))
            slots.append((vslot, gslot))
            col_meta.append((col.type, col.dictionary))
        cslot = len(self.inputs)
        self.inputs.append(counts)
        self.scan_meta[id(node)] = {
            "slots": slots, "counts": cslot, "cap": cap, "total": n,
            "meta": col_meta,
        }

    # ---------------- run ----------------
    def run(self) -> Tuple[Batch, bool]:
        import jax
        from jax.sharding import PartitionSpec as PS

        from presto_tpu.parallel.mesh import AXIS, row_sharding

        root_frag = self.dplan.fragments[self.root_fid]
        ncols = len(root_frag.root.columns)
        if self._jitted is None:
            # _out_meta/_flag_labels are trace-time side effects; cached
            # re-runs skip the trace and must keep the recorded values
            self._out_meta: List[Tuple[T.Type, Optional[Dictionary]]] = []

        def program(*inputs):
            import jax.numpy as jnp

            self._traced = inputs
            self._cache: Dict[int, MTable] = {}
            self._overflow: List[object] = []
            self._errors: List[object] = []
            self.exchange_log = []
            self.kernel_tiers = []
            # per-shard telemetry accumulated during lowering: (key,
            # traced int64 scalar) pairs that become ONE stats vector
            # output — the program's own StageStats feed
            self._shard_stats: List[Tuple[tuple, object]] = []
            self._peak_live = jnp.zeros((), jnp.int64)
            table = self._lower_fragment(self.root_fid)
            self._root_replicated = table.replicated
            self._out_meta = [(c.type, c.dictionary) for c in table.cols]
            outs = []
            for c in table.cols:
                outs.append(c.values)
                outs.append(c.valid if c.valid is not None
                            else jnp.ones(table.cap, bool))
            of = jnp.zeros((), bool)
            flags = []
            for _, f in self._overflow:
                of = of | f
                flags.append(f)
            self._flag_labels = [lbl for lbl, _ in self._overflow]
            err = jnp.zeros((), bool)
            for f in self._errors:
                err = err | f
            self._shard_stats.append(
                (("program", "peak_live_bytes"), self._peak_live))
            self._stat_keys = [k for k, _ in self._shard_stats]
            stats = jnp.stack([jnp.asarray(v).astype(jnp.int64).reshape(())
                               for _, v in self._shard_stats])
            return (tuple(outs) + (table.live, of.reshape(1),
                                   err.reshape(1),
                                   jnp.stack(flags).reshape(-1)
                                   if flags else jnp.zeros(0, bool),
                                   stats))

        n_out = 2 * ncols + 5
        if self._jitted is None:
            import time as _time

            from presto_tpu.exec.context import OperatorStats
            from presto_tpu.kernelcache import timed_first_call

            mapped = jax.shard_map(
                program, mesh=self.runner.mesh,
                in_specs=tuple(PS(AXIS) for _ in self.inputs),
                out_specs=tuple(PS(AXIS) for _ in range(n_out)),
                check_vma=False)
            with activity("stage_h2d"):
                self._args = [
                    jax.device_put(a, row_sharding(self.runner.mesh, 1))
                    for a in self.inputs]
            # AOT-compile and keep the loaded executable: the plain
            # jit dispatch path can lose the trace-time constant buffers
            # when several whole-query programs coexist in one process
            # (observed as "supplied N buffers but expected N+consts");
            # the AOT executable binds its constants explicitly.  The
            # trace+lower and XLA-compile walls are split so the span
            # tree can attribute them separately; compile wall is
            # attributed to the shared "mesh_program" cache through
            # timed_first_call (the CacheStatsMBean role).
            t0 = _time.time()
            lowered = _kc.jit(mapped, "mesh_program").lower(*self._args)
            t1 = _time.time()
            cstats = OperatorStats(operator="mesh_program")
            self._jitted = timed_first_call(
                lowered.compile, cstats, _PROGRAM_CACHE)()
            t2 = _time.time()
            self.compile_ns += cstats.jit_compile_ns
            self.build_spans = {"lower": (t0, t1), "compile": (t1, t2)}
        with activity("dispatch"):
            out = self._jitted(*self._args)
        # Read only the control outputs eagerly: the content arrays are
        # full static capacity regardless of how few rows are live (the
        # per-transfer cost is not measured on the chip).  The first read
        # waits for the program to finish; the host blocks in one
        # ``device_wait`` bracket for all of them (a bracket costs host
        # time under the executor lock, where it counts once a client).
        with activity("device_wait"):
            of = bool(np.asarray(out[-4]).any())
            if of:
                flags = np.asarray(out[-2]).reshape(self.nparts, -1)
            else:
                err = bool(np.asarray(out[-3]).any())
                stats = np.asarray(out[-1])
                live_g = np.asarray(out[-5])
        if of:
            self.overflow_labels = [
                lbl for i, lbl in enumerate(self._flag_labels)
                if flags[:, i].any()]
            return Batch((), 0), True
        if err:
            raise ValueError(
                "scalar subquery returned more than one row")
        self._read_shard_stats(stats)
        cap = live_g.shape[0] // self.nparts
        if self.root_fid != self.dplan.root_fragment_id \
                and not self._root_replicated:
            # checkpoint-group readback of a DISTRIBUTED root: the
            # boundary checkpoint is the fragment's GLOBAL live multiset
            # (pre-exchange), so concatenate every shard's live rows.
            # The plan root stays on the shard-0 fast path below — a
            # 'single'-partitioned root gathers to shard 0 in-program.
            return self._gather_all_shards(out, live_g, cap), False
        live = live_g[:cap]
        n_live = int(live.sum())
        ncols = len(self._out_meta)
        # One extra device dispatch compacts live rows to a prefix bucket
        # and stacks same-dtype outputs, so the host reads a handful of
        # right-sized arrays instead of 2*ncols capacity-sized ones (a
        # host read costs per array AND per byte; not measured on the
        # chip).
        bucket = min(next_bucket(max(n_live, 1), minimum=8), cap)
        host = self._sliced_content(out, cap, bucket, ncols)
        cols = []
        for i, (typ, d) in enumerate(self._out_meta):
            vals = host[2 * i][:n_live]
            valid = host[2 * i + 1][:n_live]
            cols.append(Column(typ, vals,
                               None if valid.all() else valid, d))
        return Batch(tuple(cols), n_live), False

    def _gather_all_shards(self, out, live_g: np.ndarray,
                           cap: int) -> Batch:
        """Host-side concat of every shard's live rows, shard order —
        the checkpoint capture path.  Plain O(cap) transfers: checkpoint
        groups are dispatched once per boundary, not per repeat query,
        so the slicer machinery is not worth specializing here."""
        P = self.nparts
        live_pg = live_g.reshape(P, cap).astype(bool)
        n_live = int(live_pg.sum())
        cols = []
        with activity("device_wait"):
            content = [np.asarray(a) for a in out[:2 * len(self._out_meta)]]
        for i, (typ, d) in enumerate(self._out_meta):
            vals_g = content[2 * i].reshape(P, cap)
            valid_g = content[2 * i + 1].reshape(P, cap)
            vals = np.concatenate([vals_g[p][live_pg[p]]
                                   for p in range(P)])
            valid = np.concatenate([valid_g[p][live_pg[p]]
                                    for p in range(P)])
            cols.append(Column(typ, vals,
                               None if valid.all() else valid, d))
        return Batch(tuple(cols), n_live)

    def _sliced_content(self, out, cap: int, bucket: int, ncols: int):
        """Device-side stable compaction of live rows + slice to the
        ``bucket`` prefix; transfers O(live) bytes instead of O(cap)."""
        import jax
        import jax.numpy as jnp

        if not hasattr(self, "_slicers"):
            self._slicers = {}
        arrays = list(out[:2 * ncols])
        # group same-dtype outputs into one stacked transfer each: a
        # host read is charged PER ARRAY, which dominates once the
        # payloads are small (not measured on the chip)
        groups: Dict[object, List[int]] = {}
        for i, a in enumerate(arrays):
            groups.setdefault(np.dtype(a.dtype), []).append(i)
        layout = tuple(sorted((str(k), tuple(v)) for k, v in groups.items()))
        fn = self._slicers.get((bucket, layout))
        if fn is None:
            from presto_tpu.ops.radix import stable_partition_perm

            def slicer(arrs, live_full):
                perm = stable_partition_perm(~live_full[:cap])[:bucket]
                return tuple(jnp.stack([arrs[i][:cap][perm] for i in idxs])
                             for _, idxs in layout)

            fn = _kc.jit(slicer, "mesh_slice")
            self._slicers[(bucket, layout)] = fn
        with activity("dispatch"):
            compacted = fn(tuple(arrays), out[-5])
        with activity("device_wait"):
            stacked = [np.asarray(a) for a in compacted]
        host: List[Optional[np.ndarray]] = [None] * len(arrays)
        for (_, idxs), mat in zip(layout, stacked):
            for row, i in enumerate(idxs):
                host[i] = mat[row]
        return host

    def _read_shard_stats(self, stats_out: np.ndarray) -> None:
        """Parse the program's stats vector output ([P*S] -> [P, S])
        into per-key per-shard int lists; same-key entries (several
        scans in one fragment) sum."""
        raw = stats_out.reshape(self.nparts, -1)
        folded: Dict[tuple, np.ndarray] = {}
        order: List[tuple] = []
        for i, key in enumerate(self._stat_keys):
            if key not in folded:
                folded[key] = np.zeros(self.nparts, np.int64)
                order.append(key)
            folded[key] += raw[:, i]
        self._last_shard_stats = [(k, [int(v) for v in folded[k]])
                                  for k in order]

    def _note_stat(self, key: tuple, value) -> None:
        self._shard_stats.append((key, value))

    def run_info(self) -> Dict:
        """Exchange-mode + kernel-tier counters and the per-shard stats
        read back from the LAST run, for the stats rollup (structure
        recorded at trace time; cached re-runs re-read the same compiled
        program's outputs)."""
        modes: Dict[str, int] = {}
        for _fid, kind in self.exchange_log:
            modes[kind] = modes.get(kind, 0) + 1
        stats = dict(self._last_shard_stats)
        fragments: Dict[int, Dict[str, List[int]]] = {}
        boundaries = []
        peak = stats.get(("program", "peak_live_bytes"),
                         [0] * self.nparts)
        for key, vals in self._last_shard_stats:
            if key[0] == "fragment":
                fragments.setdefault(key[1], {})[key[2]] = vals
        for seq, (fid, kind) in enumerate(self.exchange_log):
            boundaries.append({
                "fragment": fid, "kind": kind,
                "rows": stats.get(("boundary", seq, fid, kind, "rows"),
                                  [0] * self.nparts),
                "bytes": stats.get(("boundary", seq, fid, kind, "bytes"),
                                   [0] * self.nparts),
            })
        return {
            "exchange_modes": modes,
            "boundaries": boundaries,
            "kernel_tiers": [f"{label}:{tier}"
                             for label, tier in self.kernel_tiers],
            "nparts": self.nparts,
            "cap_scale": self.cap_scale,
            "per_shard": {
                "fragments": {
                    fid: {"input_rows": d.get("input_rows",
                                              [0] * self.nparts),
                          "output_rows": d.get("output_rows",
                                               [0] * self.nparts)}
                    for fid, d in sorted(fragments.items())},
                "peak_live_bytes": peak,
            },
        }

    # ---------------- traced lowering ----------------
    def _lower_fragment(self, fid: int) -> MTable:
        if fid in self._cache:
            return self._cache[fid]
        if fid != self.root_fid and fid in self.ckpt:
            table = self._ckpt_table(fid)
            self._cache[fid] = table
            return table
        global FRAGMENTS_LOWERED
        FRAGMENTS_LOWERED += 1
        if fid not in self.lowered_fids:
            self.lowered_fids.append(fid)
        frag = self.dplan.fragments[fid]
        prev = getattr(self, "_cur_part", None)
        prev_fid = getattr(self, "_cur_fid", None)
        self._cur_part = frag.partitioning
        self._cur_fid = fid
        try:
            table = self._lower(frag.root)
        finally:
            self._cur_part = prev
            self._cur_fid = prev_fid
        # per-shard fragment output rows: live count of the fragment
        # root (the TaskStats.output_rows feed of the synthetic rollup)
        self._note_stat(("fragment", fid, "output_rows"), table.num_rows)
        self._cache[fid] = table
        return table

    def _exchange(self, fid: int) -> MTable:
        """Apply the fragment-boundary collective (the PartitionedOutput/
        Broadcast/TaskOutput -> ExchangeOperator hop as an in-program ICI
        collective).  The collective is chosen like the HTTP tier routes
        partitions: a 'single'-partitioned consumer has ONE task pulling
        every partition, so hash-partitioned producer output degenerates
        to a gather; multi-task consumers see the producer's routing."""
        import jax.numpy as jnp

        from presto_tpu.parallel.exchange import (
            broadcast_rows, repartition, route_by_key,
        )
        from presto_tpu.parallel.mesh import AXIS

        import jax

        frag = self.dplan.fragments[fid]
        consumer_part = self._cur_part
        table = self._lower_fragment(fid)
        self._cur_part = consumer_part
        kind, channels = frag.output_partitioning
        if consumer_part == "single":
            # root/gather consumer: all partitions flow to the one task
            kind = "single"
        if table.replicated:
            if kind in ("broadcast", "single"):
                # already the identical union on every shard — a gather
                # here would multiply rows by the shard count (the
                # boundary still counts: it lowered to an identity,
                # moving zero bytes)
                self._note_boundary(fid, kind, table.num_rows, 0)
                return table
            # hash-split of a replicated table: only ONE copy may enter
            # the exchange, so mask all but shard 0's
            on_first = jax.lax.axis_index(AXIS) == 0
            table = MTable(table.cols, table.live & on_first, table.cap,
                           table.est, compacted=False)
        if kind in ("hash", "arbitrary") \
                and self.config.partitioned_join_build and self.nparts > 1:
            # P8 sharded sizing: a key-routed receive buffer holds this
            # shard's PARTITION of the rows, not the worst-case total —
            # 2x the even share for skew head room, cap_scale doubling
            # on overflow retry.  This is what makes per-shard state
            # (and the build table sized from it) scale with 1/P, so a
            # build exceeding one device's HBM becomes legal.  Knob off
            # restores the PR 10 worst-case-total sizing exactly.
            out_cap = next_bucket(
                max(8, (2 * self.cap_scale * table.est) // self.nparts))
        else:
            out_cap = next_bucket(table.est, minimum=8)

        def col_arrays(t: MTable):
            out = []
            for c in t.cols:
                out.append(c.values)
                out.append(c.valid if c.valid is not None
                           else jnp.ones(t.cap, bool))
            return out

        if kind in ("hash", "arbitrary"):
            arrays = col_arrays(table)
            if kind == "hash":
                triples = [self._hash_triple(table.cols[ch])
                           for ch in channels]
                recv, n_recv, of = route_by_key(
                    arrays, table.live, triples,
                    slot_cap=min(table.cap, out_cap), out_cap=out_cap,
                    axis_name=AXIS)
            else:
                # P3 round-robin: rotate rows across shards for balance
                # (no key semantics downstream)
                dest = ((jnp.arange(table.cap)
                         + jax.lax.axis_index(AXIS))
                        % self.nparts).astype(jnp.int32)
                recv, n_recv, of = repartition(
                    arrays, table.live, dest,
                    slot_cap=min(table.cap, out_cap), out_cap=out_cap,
                    axis_name=AXIS)
        elif kind in ("broadcast", "single"):
            ct = _compact(table)
            recv, n_recv, of = broadcast_rows(col_arrays(ct), ct.num_rows,
                                              out_cap, AXIS)
        else:
            raise MeshUnsupported(f"output partitioning {kind}")
        self._overflow.append((f'exchange f{fid} {kind}', of))
        # per-shard boundary telemetry: rows/bytes this shard RECEIVED
        # through the collective (raw device arrays, so bytes = rows x
        # static row width — no serde framing), plus the mid-program
        # progress beacon when enabled
        from presto_tpu.parallel.exchange import row_width_bytes

        self._note_boundary(fid, kind, n_recv,
                            n_recv * row_width_bytes(recv))
        cols = []
        for i, c in enumerate(table.cols):
            cols.append(MCol(recv[2 * i], recv[2 * i + 1], c.type,
                             c.dictionary))
        live = jnp.arange(out_cap) < n_recv
        return MTable(cols, live, out_cap, table.est, compacted=True,
                      replicated=kind in ("broadcast", "single"))

    def _note_boundary(self, fid: int, kind: str, rows, bytes_) -> None:
        """Record one fragment boundary: exchange-log entry, per-shard
        rows/bytes stats keyed by boundary sequence (a fragment feeding
        two consumers crosses two boundaries), and — when
        ``mesh_progress_beacons`` is on — a ``jax.debug.callback``
        beacon reporting (fragment, shard, rows) to the host collector
        mid-program.  Beacons off traces NO callback: the program is
        byte-identical to the PR 11 lowering."""
        seq = len(self.exchange_log)
        self.exchange_log.append((fid, kind))
        self._note_stat(("boundary", seq, fid, kind, "rows"), rows)
        self._note_stat(("boundary", seq, fid, kind, "bytes"), bytes_)
        # beacons ride only the device-exchange tier: the local
        # whole_query_execution tier traces through this module too and
        # must stay callback-free (its progress plane is the operator
        # tier's — and a no-op host callback is still a host sync)
        if self.config.mesh_progress_beacons \
                and self.config.mesh_device_exchange:
            import jax
            import jax.numpy as jnp

            from presto_tpu.parallel import beacons
            from presto_tpu.parallel.mesh import AXIS

            jax.debug.callback(
                beacons.emit, jnp.int32(fid),
                jax.lax.axis_index(AXIS).astype(jnp.int32),
                jnp.asarray(rows).astype(jnp.int64), ordered=False)

    def _hash_triple(self, c: MCol):
        """(values, valid, type) for exchange hashing — the SAME per-entry
        value hash the HTTP data plane and partitioned spill use, so every
        tier routes equal keys to the same partition."""
        from presto_tpu.ops.hashing import value_hash_triple

        return value_hash_triple(c)

    def _lower(self, node: PlanNode) -> MTable:
        table = self._lower_node(node)
        # peak live-intermediate estimate: the largest live-rows x
        # row-width of any lowered table on this shard — the mesh
        # tier's peak_memory_bytes analogue (an estimate: padding and
        # kernel scratch are excluded; capacities are static and the
        # point is the LIVE working set)
        import jax.numpy as jnp

        from presto_tpu.parallel.exchange import row_width_bytes

        width = row_width_bytes(
            [c.values for c in table.cols]) + len(table.cols)
        self._peak_live = jnp.maximum(
            self._peak_live, table.num_rows * jnp.int64(max(width, 1)))
        return table

    def _lower_node(self, node: PlanNode) -> MTable:
        if isinstance(node, TableScanNode):
            return self._lower_scan(node)
        if isinstance(node, RemoteSourceNode):
            tables = [self._exchange(fid) for fid in node.fragment_ids]
            return tables[0] if len(tables) == 1 else _concat(tables)
        if isinstance(node, RemoteMergeNode):
            tables = [self._exchange(fid) for fid in node.fragment_ids]
            t0 = tables[0] if len(tables) == 1 else _concat(tables)
            t0 = self._sort(t0, node.sort_keys)
            if node.limit is not None:
                t0 = _limit(t0, node.limit, self.nparts)
            return t0
        if isinstance(node, ValuesNode):
            return self._lower_values(node)
        if isinstance(node, FilterNode):
            return self._lower_filter(node)
        if isinstance(node, ProjectNode):
            return self._lower_project(node)
        if isinstance(node, AggregationNode):
            return self._lower_agg(node)
        if isinstance(node, JoinNode):
            return self._lower_join(node)
        if isinstance(node, SemiJoinNode):
            return self._lower_semijoin(node)
        if isinstance(node, SortNode):
            return self._sort(self._lower(node.source), node.sort_keys)
        if isinstance(node, LimitNode):
            return _limit(self._lower(node.source), node.count,
                          self.nparts)
        if isinstance(node, UnionNode):
            return _concat([self._lower(s) for s in node.inputs])
        if isinstance(node, EnforceSingleRowNode):
            return self._lower_single_row(node)
        if isinstance(node, WindowNode):
            return self._lower_window(node)
        raise MeshUnsupported(f"mesh lowering for {type(node).__name__}")

    def _lower_window(self, node: WindowNode) -> MTable:
        """Window functions as segmented scans over a partition-sorted
        shard (WindowOperator.java:61 role; kernels in ops/window.py,
        shared with the operator tier via eval_window_function).

        Window fragments are single-partitioned (the fragmenter's
        _parallel_safe veto), so a sharded input is first replicated —
        every shard then holds whole partitions and computes identical
        results, which is exactly the 'single' fragment contract."""
        import jax.numpy as jnp

        from presto_tpu.exec.windowop import eval_window_function
        from presto_tpu.ops import window as W

        src = self._lower(node.source)
        if not src.replicated and self.nparts > 1:
            from presto_tpu.parallel.exchange import broadcast_rows
            from presto_tpu.parallel.mesh import AXIS

            ct = _compact(src)
            out_cap = next_bucket(self.nparts * src.est, minimum=8)
            arrays = []
            for c in ct.cols:
                arrays.append(c.values)
                arrays.append(c.valid if c.valid is not None
                              else jnp.ones(ct.cap, bool))
            recv, n_recv, of = broadcast_rows(arrays, ct.num_rows,
                                              out_cap, AXIS)
            self._overflow.append(('window gather', of))
            cols = [MCol(recv[2 * i], recv[2 * i + 1], c.type, c.dictionary)
                    for i, c in enumerate(ct.cols)]
            src = MTable(cols, jnp.arange(out_cap) < n_recv, out_cap,
                         self.nparts * src.est, compacted=True,
                         replicated=True)
        table = _compact(src)
        cap = table.cap
        n = table.num_rows

        sort_keys = [(ch, True, False) for ch in node.partition_channels]
        sort_keys += [(ch, asc, bool(nf)) for ch, asc, nf in node.order_keys]
        if sort_keys:
            table = self._sort(table, sort_keys)
        live = jnp.arange(cap) < n

        def eq_prev(ch: int):
            c = table.cols[ch]
            v = c.values
            same = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), v[1:] == v[:-1]])
            if c.valid is not None:
                g = c.valid
                both_null = jnp.concatenate(
                    [jnp.ones((1,), jnp.bool_), (~g[1:]) & (~g[:-1])])
                both_ok = jnp.concatenate(
                    [jnp.ones((1,), jnp.bool_), g[1:] & g[:-1]])
                same = both_null | (both_ok & same)
            return same

        part_eq = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                   live[1:] == live[:-1]])
        for ch in node.partition_channels:
            part_eq = part_eq & eq_prev(ch)
        seg = W.segment_ids(part_eq)
        peer_eq = part_eq
        for ch, _, _ in node.order_keys:
            peer_eq = peer_eq & eq_prev(ch)
        peer = W.segment_ids(peer_eq)

        out_cols = list(table.cols)
        for fn in node.functions:
            rt, vals, ok, d = eval_window_function(fn, table.cols, seg,
                                                   peer)
            out_cols.append(MCol(vals, ok, rt, d))
        return MTable(out_cols, live, cap, table.est, compacted=True,
                      replicated=table.replicated)

    def _ckpt_table(self, fid: int) -> MTable:
        """A checkpointed fragment as a shard-local table: the staged
        host feed read back through the traced inputs, mirroring
        ``_lower_scan`` (``counts[0]`` inside shard_map is the LOCAL
        shard's count).  NOT replicated — the feed is one global copy
        split across shards, so the consumer's collective applies."""
        import jax.numpy as jnp

        meta = self.ckpt_meta[fid]
        cap = meta["cap"]
        counts = self._traced[meta["counts"]]
        cols = []
        for (vslot, gslot), (typ, d) in zip(meta["slots"], meta["meta"]):
            cols.append(MCol(self._traced[vslot],
                             self._traced[gslot] if gslot is not None
                             else None, typ, d))
        self.kernel_tiers.append((f"f{fid}", "ckpt_feed"))
        live = jnp.arange(cap) < counts[0]
        return MTable(cols, live, cap, meta["total"], compacted=True)

    def _lower_scan(self, node: TableScanNode) -> MTable:
        import jax.numpy as jnp

        meta = self.scan_meta[id(node)]
        cap = meta["cap"]
        counts = self._traced[meta["counts"]]
        cols = []
        for (vslot, gslot), (typ, d) in zip(meta["slots"], meta["meta"]):
            cols.append(MCol(self._traced[vslot],
                             self._traced[gslot] if gslot is not None
                             else None, typ, d))
        # per-shard scan input rows, summed per fragment at readback
        # (the TaskStats.input_rows feed of the synthetic rollup)
        self._note_stat(("fragment", getattr(self, "_cur_fid", 0),
                         "input_rows"), counts[0])
        live = jnp.arange(cap) < counts[0]
        return MTable(cols, live, cap, meta["total"], compacted=True)

    def _lower_values(self, node: ValuesNode) -> MTable:
        import jax
        import jax.numpy as jnp

        from presto_tpu.parallel.mesh import AXIS

        b = batch_from_pylist(node.types, list(node.rows))
        n = b.num_rows
        cap = next_bucket(max(n, 1), minimum=8)
        b = b.pad_rows(cap)
        cols = []
        for c in b.columns:
            if c.type.is_nested:
                raise MeshUnsupported("nested VALUES")
            valid = None if c.valid is None else jnp.asarray(
                np.asarray(c.valid))
            cols.append(MCol(jnp.asarray(np.asarray(c.values)), valid,
                             c.type, c.dictionary))
        on_first = jax.lax.axis_index(AXIS) == 0
        live = (jnp.arange(cap) < n) & on_first
        return MTable(cols, live, cap, n, compacted=True)

    def _compile(self, exprs: Sequence[RowExpression], table: MTable):
        dicts = {i: c.dictionary for i, c in enumerate(table.cols)
                 if c.dictionary is not None}
        comp = ExprCompiler(dicts)
        return [comp.compile(e) for e in exprs]

    def _lower_filter(self, node: FilterNode) -> MTable:
        import jax.numpy as jnp

        src = self._lower(node.source)
        (ce,) = self._compile([node.predicate], src)
        v, valid = ce.run(src.pairs(), src.cap, jnp)
        mask = v if valid is None else (v & valid)
        return MTable(src.cols, src.live & mask, src.cap, src.est,
                      compacted=False, replicated=src.replicated)

    def _lower_project(self, node: ProjectNode) -> MTable:
        import jax.numpy as jnp

        src = self._lower(node.source)
        compiled = self._compile(list(node.expressions), src)
        cols = []
        for ce, (name, typ) in zip(compiled, node.columns):
            v, valid = ce.run(src.pairs(), src.cap, jnp)
            cols.append(MCol(v, valid, typ, ce.dictionary))
        return MTable(cols, src.live, src.cap, src.est, src.compacted,
                      replicated=src.replicated)

    def _project_table(self, table: MTable,
                       exprs: Sequence[RowExpression]) -> MTable:
        import jax.numpy as jnp

        compiled = self._compile(exprs, table)
        cols = []
        for ce in compiled:
            v, valid = ce.run(table.pairs(), table.cap, jnp)
            cols.append(MCol(v, valid, ce.type, ce.dictionary))
        return MTable(cols, table.live, table.cap, table.est,
                      table.compacted, replicated=table.replicated)

    # ---------------- aggregation ----------------
    def _lower_agg(self, node: AggregationNode) -> MTable:
        import jax.numpy as jnp

        from presto_tpu.ops.groupby import global_aggregate, grouped_aggregate
        from presto_tpu.sql.physical import (
            _finalize, decompose_aggregates, merge_agg_channels,
        )
        fin = _finalize

        if (node.step == "final" and self.nparts == 1
                and isinstance(node.source, RemoteSourceNode)
                and len(node.source.fragment_ids) == 1):
            # Single-device mesh: the partial/final split exists to ride a
            # hash exchange between fragments; with one shard the exchange
            # is an identity and the split just aggregates TWICE over the
            # full capacity.  Fuse back into one single-step aggregation
            # over the partial's source (the whole-query LocalRunner tier
            # always runs here).
            fid = node.source.fragment_ids[0]
            root = self.dplan.fragments[fid].root
            if (isinstance(root, AggregationNode) and root.step == "partial"
                    and fid not in self._cache):
                fused = AggregationNode(root.source, root.group_channels,
                                        node.aggregates, node.columns,
                                        step="single")
                return self._lower_agg(fused)

        src = self._lower(node.source)
        input_types = [t for _, t in node.source.columns]
        ngroups = len(node.group_channels)
        if node.step == "final":
            agg_channels, finalize_specs = merge_agg_channels(
                node.aggregates, ngroups)
        else:
            pre_exprs, agg_channels, finalize_specs = decompose_aggregates(
                node.aggregates, input_types)
            if len(pre_exprs) > len(input_types):
                src = self._project_table(src, pre_exprs)
                input_types = [e.type for e in pre_exprs]
        for ch in agg_channels:
            if ch.prim not in _MESH_PRIMS:
                raise MeshUnsupported(f"agg primitive {ch.prim}")

        aggs = []
        for ch in agg_channels:
            if ch.channel is None:
                # count(*): valid=None counts every live row
                aggs.append(("count", jnp.zeros(src.cap, jnp.int8), None))
                continue
            c = src.cols[ch.channel]
            vals = c.values
            if ch.prim == "sum" and vals.dtype != np.dtype(
                    ch.out_type.np_dtype):
                vals = vals.astype(ch.out_type.np_dtype)
            aggs.append((ch.prim, vals, c.valid))

        if ngroups:
            key_cols = [src.cols[c] for c in node.group_channels]
            direct = self._try_direct_agg(src, key_cols, aggs)
            if direct is not None:
                self.kernel_tiers.append(('groupby', 'direct'))
                out_cols, results, live, cap, est = direct
            else:
                self.kernel_tiers.append(('groupby', 'sort'))
                key_triples = [(c.values, c.valid, c.type) for c in key_cols]
                group_cap = src.cap
                gi, ng, results = grouped_aggregate(
                    key_triples, aggs, src.cap, group_cap,
                    live_mask=src.live)
                self._overflow.append(('groupby', ng > group_cap))
                out_cols = []
                for c in key_cols:
                    out_cols.append(MCol(
                        c.values[gi],
                        None if c.valid is None else c.valid[gi],
                        c.type, c.dictionary))
                live = jnp.arange(group_cap) < jnp.minimum(ng, group_cap)
                cap = group_cap
                est = min(src.est, self.nparts * group_cap)
        else:
            results = global_aggregate(aggs, src.cap, live_mask=src.live)
            out_cols = []
            live = jnp.ones(1, bool)
            cap = 1
            est = self.nparts
        for (vals, cnt), ch in zip(results, agg_channels):
            v = vals if vals.ndim else vals.reshape(1)
            c = cnt if cnt.ndim else cnt.reshape(1)
            valid = None if ch.prim == "count" else (c > 0)
            if v.dtype != np.dtype(ch.out_type.np_dtype):
                v = v.astype(ch.out_type.np_dtype)
            out_cols.append(MCol(v, valid, ch.out_type, None))
        # the direct dense-domain path leaves holes (absent key combos):
        # live rows are NOT a prefix there
        compacted = not (ngroups and direct is not None)
        table = MTable(out_cols, live, cap, est, compacted=compacted,
                       replicated=src.replicated)

        if node.step == "partial":
            return table
        # finalize projection: [keys..., finalized aggregates...]
        key_types = [input_types[c] for c in node.group_channels]
        exprs: List[RowExpression] = [InputRef(i, t)
                                      for i, t in enumerate(key_types)]
        for agg, comps in finalize_specs:
            base = [InputRef(ngroups + ci, agg_channels[ci].out_type)
                    for ci in comps]
            exprs.append(fin(agg, base))
        out = self._project_table(table, exprs)
        out.cols = [MCol(c.values, c.valid, typ, c.dictionary)
                    for c, (_, typ) in zip(out.cols, node.columns)]
        return out

    def _try_direct_agg(self, src: MTable, key_cols, aggs):
        """Dense-domain GROUP BY: when every key is a dictionary code /
        boolean with a trace-time-known domain whose product is small,
        aggregate arithmetically over the dense domain
        (ops.groupby.direct_grouped_aggregate — the BigintGroupByHash
        special-case analogue, ~100x the sort path and the output
        capacity collapses from src.cap to the domain size)."""
        import jax.numpy as jnp

        from presto_tpu.ops.groupby import (
            decode_direct_keys, direct_grouped_aggregate,
        )

        doms: List[int] = []
        for c in key_cols:
            if c.dictionary is not None:
                doms.append(max(1, len(c.dictionary)))
            elif c.type.name == "boolean":
                doms.append(2)
            else:
                return None
        total = 1
        for c, d in zip(key_cols, doms):
            total *= d + (1 if c.valid is not None else 0)
        if total > self.config.direct_groupby_max_domain:
            return None
        key_codes = [(c.values, c.valid) for c in key_cols]
        present, results = direct_grouped_aggregate(
            key_codes, doms, aggs, src.cap, live_mask=src.live)
        D = present.shape[0]
        decoded = decode_direct_keys(
            jnp.arange(D), [c.valid is not None for c in key_cols], doms)
        out_cols: List[MCol] = []
        for c, (codes, valid) in zip(key_cols, decoded):
            out_cols.append(MCol(codes.astype(c.values.dtype),
                                 valid, c.type, c.dictionary))
        # est feeds downstream exchange capacity: a single/gather consumer
        # receives up to nparts * D rows
        return out_cols, results, present, D, min(src.est, self.nparts * D)

    # ---------------- joins ----------------
    def _key_triples(self, table: MTable, channels, other: MTable,
                     other_channels):
        """Join-key triples with dead rows folded into validity and
        dictionary codes unified across sides."""
        import jax.numpy as jnp

        triples_a, triples_b = [], []
        for ca_ch, cb_ch in zip(channels, other_channels):
            ca, cb = table.cols[ca_ch], other.cols[cb_ch]
            va, vb = ca.values, cb.values
            if ca.dictionary is not None or cb.dictionary is not None:
                if ca.dictionary is None or cb.dictionary is None:
                    raise MeshUnsupported("join key mixes string encodings")
                if ca.dictionary is not cb.dictionary:
                    union = Dictionary()
                    ra = ca.dictionary.remap_into(union)
                    rb = cb.dictionary.remap_into(union)
                    va = jnp.asarray(ra)[jnp.clip(va, 0, len(ra) - 1)]
                    vb = jnp.asarray(rb)[jnp.clip(vb, 0, len(rb) - 1)]
            ga = table.live if ca.valid is None else (ca.valid & table.live)
            gb = other.live if cb.valid is None else (cb.valid & other.live)
            triples_a.append((va, ga, ca.type))
            triples_b.append((vb, gb, cb.type))
        return triples_a, triples_b

    def _probe_ranges(self, btrip, ptrip, bcap: int, pcap: int,
                      single: bool, use_pages: bool, label: str):
        """(lo, counts, perm) match ranges per probe row — the shared
        ``(lo, counts)`` contract of ops/join.py, produced by one of the
        three lookup tiers:

        - ``pages_hash`` (P8, ``partitioned_join_build``): the PR 10
          open-addressing table over the shard's key partition — the
          ``PartitionedLookupSource`` role, no total order and no
          key-span limit; a too-full table raises the overflow flag and
          the host re-runs at the next capacity bucket;
        - ``single``: dense ids for one packed integer word;
        - ``sorted``: canonical union-sort ids + binary search.
        """
        from presto_tpu.ops import join as J

        if use_pages:
            from presto_tpu.ops import hashtable as H

            table_cap = next_bucket(2 * self.cap_scale * bcap, minimum=16)
            (words, prefix, used, starts, cnt_t, perm, _has_null,
             ok) = H.pages_hash_build(list(btrip), bcap, table_cap)
            self._overflow.append((f'{label} build table', ~ok))
            lo, counts, _plive = H.pages_hash_probe(
                (words, prefix, used, starts, cnt_t), list(ptrip), pcap)
            self.kernel_tiers.append((label, 'pages_hash'))
            return lo, counts, perm
        if single:
            # a >=2^62 key spread would overflow the dense-id
            # arithmetic; flagging it as overflow makes the runner fail
            # over to the operator tier's canonical path
            self._overflow.append((
                f'{label} key span',
                J.single_word_span_too_big(btrip[0], bcap)))
            bids, pids = J.single_word_ids(btrip[0], ptrip[0], bcap, pcap)
            tier = 'single'
        else:
            bids, pids = J.canonical_ids(btrip, ptrip, bcap, pcap)
            tier = 'sorted'
        sorted_b, perm_b = J.build_index(bids)
        lo, counts = J.probe_counts(sorted_b, perm_b, pids)
        self.kernel_tiers.append((label, tier))
        return lo, counts, perm_b

    def _grouped_expand(self, node: JoinNode, left: MTable, right: MTable,
                        btrip, ptrip, single: bool, use_pages: bool,
                        out_cap: int, B: int):
        """Bucket-sequential grouped execution (P9, §5.7): hash-bucket
        both sides on the join key and run the buckets SEQUENTIALLY
        through the shard-local join, so the per-shard peak intermediate
        (ids / build table / expansion buffers) is ~1/B of the
        single-pass join and SF10-100 builds fit HBM.  Every row belongs
        to exactly one bucket (equal keys co-bucket), so inner and left
        joins emit exactly their single-pass rows; the capacity-bucket
        overflow/rerun policy applies PER BUCKET — a skewed bucket
        raises its flag and the host re-runs at the next cap_scale."""
        import jax.numpy as jnp

        from presto_tpu.ops import join as J
        from presto_tpu.ops.hashing import row_hash
        from presto_tpu.ops.radix import stable_partition_perm

        def bucket_of(triples):
            # a DIFFERENT mix of the key hash than the exchange
            # partition: after a hash exchange every row on this shard
            # has hash % nparts == shard_index, so h % B (both powers
            # of two) would leave most buckets empty
            h = row_hash(list(triples))
            h = ((h ^ jnp.uint64(0x94D049BB133111EB))
                 * jnp.uint64(0x2545F4914F6CDD1D))
            h = h ^ (h >> jnp.uint64(29))
            return (h % jnp.uint64(B)).astype(jnp.int32)

        bb = bucket_of(btrip)
        pb = bucket_of(ptrip)
        # per-bucket working capacities: ~2x the even share (skew head
        # room), clamped to the side capacity — a bucket can never hold
        # more rows than its side, and the clamp keeps gathered shapes
        # consistent when B approaches the side capacity
        bcap = min(next_bucket(
            max(8, (2 * self.cap_scale * right.cap) // B)), right.cap)
        pcap = min(next_bucket(
            max(8, (2 * self.cap_scale * left.cap) // B)), left.cap)
        ecap = min(next_bucket(
            max(8, (2 * self.cap_scale * max(left.cap, right.cap)) // B)),
            out_cap)
        probe_idx = jnp.zeros(out_cap, jnp.int64)
        build_idx = jnp.zeros(out_cap, jnp.int64)
        unmatched = jnp.zeros(out_cap, bool)
        offset = jnp.zeros((), jnp.int64)
        side_overflow = jnp.zeros((), bool)
        expand_overflow = jnp.zeros((), bool)
        for b in range(B):
            mb = right.live & (bb == b)
            mp = left.live & (pb == b)
            ob = stable_partition_perm(~mb)[:bcap].astype(jnp.int32)
            op = stable_partition_perm(~mp)[:pcap].astype(jnp.int32)
            nb = mb.sum()
            np_ = mp.sum()
            side_overflow = side_overflow | (nb > bcap) | (np_ > pcap)
            in_b = jnp.arange(bcap) < nb
            in_p = jnp.arange(pcap) < np_
            btr = [(v[ob], g[ob] & in_b, t) for v, g, t in btrip]
            ptr = [(v[op], g[op] & in_p, t) for v, g, t in ptrip]
            lo, counts, perm = self._probe_ranges(
                btr, ptr, bcap, pcap, single, use_pages,
                label=f'grouped join b{b}')
            if node.kind == "left":
                pi, bi, rv, um, total = J.expand_matches_outer(
                    lo, counts, in_p, perm, ecap)
            else:
                pi, bi, rv, um, total = J.expand_matches(
                    lo, counts, perm, ecap)
            expand_overflow = expand_overflow | (total > ecap)
            # translate bucket-local rows back to shard rows and append
            # this bucket's compacted prefix at the running offset
            dst = jnp.where(rv, offset + jnp.arange(ecap), out_cap)
            probe_idx = probe_idx.at[dst].set(
                op[jnp.clip(pi, 0, pcap - 1)].astype(jnp.int64),
                mode="drop")
            build_idx = build_idx.at[dst].set(
                ob[jnp.clip(bi, 0, bcap - 1)].astype(jnp.int64),
                mode="drop")
            unmatched = unmatched.at[dst].set(um, mode="drop")
            offset = offset + jnp.minimum(total, ecap)
        self._overflow.append(('grouped join side bucket', side_overflow))
        self._overflow.append(('grouped join expand', expand_overflow))
        self._overflow.append(('grouped join total', offset > out_cap))
        row_valid = jnp.arange(out_cap) < offset
        return probe_idx, build_idx, row_valid, unmatched

    def _lower_join(self, node: JoinNode) -> MTable:
        import jax.numpy as jnp

        from presto_tpu.ops import join as J

        left = self._lower(node.left)
        right = self._lower(node.right)
        if node.kind == "cross" or not node.left_keys:
            return self._cross_join(node, left, right)

        btrip, ptrip = self._key_triples(right, node.right_keys,
                                         left, node.left_keys)
        # sides: build = right, probe = left (matches operator tier).
        # Single integer-word keys (ints, dates, decimals, dictionary
        # codes) skip the canonicalization sort entirely: the values ARE
        # the ids (the operator tier's 'single' LookupSource mode).
        single = (len(btrip) == 1 and J.single_word_joinable(
            btrip[0][2],
            right.cols[node.right_keys[0]].dictionary is not None))
        # Partitioned lookup source (P8): the PR 10 open-addressing
        # PagesHash table built per shard over the shard's slice of the
        # build — together the shard tables ARE the global build table
        # sharded across device HBM (probe rows were routed to the
        # owning shard by the hash-exchange all_to_all).  Canonical
        # multi-word keys always take it (equality needs no total
        # order, so the union-sort disappears); packable single-word
        # keys keep the dense-id tier unless the build is large (the
        # hash table has no key-span limit, so big spreads stop failing
        # over to the operator tier).
        use_pages = self.config.partitioned_join_build and (
            not single
            or right.est > self.config.device_join_probe_max_build_rows)
        # Per-shard match capacity: FK-shaped joins emit ~probe-count rows,
        # so the base bucket is max(cap) and cap_scale doubles on overflow
        # retry.  A fixed expansion multiplier would COMPOUND down a join
        # chain (4^depth) — the retry policy pays the cost only when a
        # query actually expands.
        out_cap = next_bucket(
            self.cap_scale * max(left.cap, right.cap), minimum=8)
        B = max(1, int(self.config.grouped_mesh_execution))
        if B > 1:
            probe_idx, build_idx, row_valid, unmatched = \
                self._grouped_expand(node, left, right, btrip, ptrip,
                                     single, use_pages, out_cap, B)
        else:
            lo, counts, perm_b = self._probe_ranges(
                btrip, ptrip, right.cap, left.cap, single, use_pages,
                label='join')
            if node.kind == "left":
                probe_idx, build_idx, row_valid, unmatched, total = \
                    J.expand_matches_outer(lo, counts, left.live, perm_b,
                                           out_cap)
            else:
                probe_idx, build_idx, row_valid, unmatched, total = \
                    J.expand_matches(lo, counts, perm_b, out_cap)
            self._overflow.append(('join', total > out_cap))
        cols: List[MCol] = []
        for c in left.cols:
            valid = None if c.valid is None else c.valid[probe_idx]
            cols.append(MCol(c.values[probe_idx], valid, c.type,
                             c.dictionary))
        for c in right.cols:
            valid = c.valid[build_idx] if c.valid is not None else None
            if node.kind == "left":
                ok = ~unmatched
                valid = ok if valid is None else (valid & ok)
            cols.append(MCol(c.values[build_idx], valid, c.type,
                             c.dictionary))
        if node.kind == "left" and left.replicated \
                and not right.replicated:
            # unmatched probe rows would emit once PER SHARD
            raise MeshUnsupported("left join: replicated probe over "
                                  "sharded build")
        est = max(1, self.cap_scale * max(left.est, right.est))
        table = MTable(cols, row_valid, out_cap, est, compacted=True,
                       replicated=left.replicated and right.replicated)
        if node.residual is not None:
            (ce,) = self._compile([node.residual], table)
            v, valid = ce.run(table.pairs(), table.cap, jnp)
            mask = v if valid is None else (v & valid)
            table = MTable(table.cols, table.live & mask, table.cap,
                           table.est, compacted=False,
                           replicated=table.replicated)
        return table

    def _cross_join(self, node: JoinNode, left: MTable,
                    right: MTable) -> MTable:
        import jax.numpy as jnp

        right = _compact(right)
        if left.cap * right.cap > (1 << 22):
            raise MeshUnsupported("cross join too large for the mesh tier")
        out_cap = left.cap * right.cap
        j = jnp.arange(out_cap)
        li = (j // right.cap).astype(jnp.int32)
        ri = (j % right.cap).astype(jnp.int32)
        live = left.live[li] & right.live[ri]
        cols: List[MCol] = []
        for c in left.cols:
            cols.append(MCol(c.values[li],
                             None if c.valid is None else c.valid[li],
                             c.type, c.dictionary))
        for c in right.cols:
            cols.append(MCol(c.values[ri],
                             None if c.valid is None else c.valid[ri],
                             c.type, c.dictionary))
        est = max(1, left.est * max(right.est, 1))
        table = MTable(cols, live, out_cap, est, compacted=False,
                       replicated=left.replicated and right.replicated)
        if node.residual is not None:
            (ce,) = self._compile([node.residual], table)
            v, valid = ce.run(table.pairs(), table.cap, jnp)
            mask = v if valid is None else (v & valid)
            table.live = table.live & mask
        return table

    def _lower_semijoin(self, node: SemiJoinNode) -> MTable:
        from presto_tpu.ops import join as J

        src = self._lower(node.source)
        filt = self._lower(node.filtering)
        btrip, strip = self._key_triples(filt, node.filtering_keys,
                                         src, node.source_keys)
        if len(btrip) == 1 and J.single_word_joinable(
                btrip[0][2],
                filt.cols[node.filtering_keys[0]].dictionary is not None):
            self._overflow.append((
                'semijoin key span',
                J.single_word_span_too_big(btrip[0], filt.cap)))
            bids, sids = J.single_word_ids(btrip[0], strip[0],
                                           filt.cap, src.cap)
        else:
            bids, sids = J.canonical_ids(btrip, strip, filt.cap, src.cap)
        sorted_b, perm_b = J.build_index(bids)
        lo, counts = J.probe_counts(sorted_b, perm_b, sids)
        if src.replicated and not filt.replicated:
            # each shard would apply only ITS slice of the filtering set
            raise MeshUnsupported("semi join: replicated source over "
                                  "sharded filtering side")
        if node.residual is not None:
            # correlated EXISTS residual (TPC-H Q21 shape): expand key
            # matches, evaluate the residual over [source cols, filtering
            # cols] per candidate pair, reduce any-pass per source row —
            # the operator tier's canonical semi/anti kernel, in-trace
            import jax.numpy as jnp

            out_cap = next_bucket(
                self.cap_scale * max(src.cap, filt.cap), minimum=8)
            pi, bi, rv, _, total = J.expand_matches(lo, counts, perm_b,
                                                    out_cap)
            self._overflow.append(('semijoin residual expand',
                                   total > out_cap))
            pi = pi.astype(jnp.int32)
            bi = bi.astype(jnp.int32)
            pair_cols = []
            for c in src.cols:
                pair_cols.append(MCol(
                    c.values[pi],
                    None if c.valid is None else c.valid[pi],
                    c.type, c.dictionary))
            for c in filt.cols:
                pair_cols.append(MCol(
                    c.values[bi],
                    None if c.valid is None else c.valid[bi],
                    c.type, c.dictionary))
            pairs = MTable(pair_cols, rv, out_cap, src.est,
                           compacted=True, replicated=src.replicated)
            (ce,) = self._compile([node.residual], pairs)
            v, valid = ce.run(pairs.pairs(), out_cap, jnp)
            ok = rv & v
            if valid is not None:
                ok = ok & valid
            matched = (jnp.zeros(src.cap, bool)
                       .at[pi].max(ok, mode="drop"))
            keep = (~matched) if node.negated else matched
            return MTable(src.cols, src.live & keep, src.cap, src.est,
                          compacted=False, replicated=src.replicated)
        if node.negated and node.null_aware:
            import jax.numpy as jnp

            # NOT IN three-valued logic (see ops.join.anti_keep_mask)
            bhn = jnp.zeros((), bool)
            for ch in node.filtering_keys:
                fc = filt.cols[ch]
                if fc.valid is not None:
                    bhn = bhn | (filt.live & ~fc.valid).any()
            if not filt.replicated:
                # filtering rows are sharded: null presence / emptiness
                # are global facts
                import jax

                from presto_tpu.parallel.mesh import AXIS
                bhn = jax.lax.pmax(bhn.astype(jnp.int32), AXIS) > 0
                n_filt = jax.lax.psum(filt.live.sum(), AXIS)
            else:
                n_filt = filt.live.sum()
            mask = J.anti_keep_from_parts(
                counts, sids >= 0, src.live, True,
                [src.cols[ch].valid for ch in node.source_keys],
                n_filt, build_has_null=bhn)
        else:
            mask = J.semi_mask(counts, src.live, node.negated)
        return MTable(src.cols, src.live & mask, src.cap, src.est,
                      compacted=False, replicated=src.replicated)

    # ---------------- order / limit / misc ----------------
    def _sort(self, table: MTable, sort_keys) -> MTable:
        import jax.numpy as jnp

        from presto_tpu.ops.sort import sort_permutation

        table = _compact(table)
        keys = []
        for ch, asc, nulls_first in sort_keys:
            c = table.cols[ch]
            vals = c.values
            if c.dictionary is not None:
                ranks = c.dictionary.sort_ranks()
                if len(ranks) == 0:
                    ranks = np.zeros(1, np.int32)
                vals = jnp.asarray(ranks)[jnp.clip(vals, 0, len(ranks) - 1)]
                typ = T.INTEGER
            else:
                typ = c.type
            keys.append((vals, c.valid, typ, not asc, bool(nulls_first)))
        perm = sort_permutation(keys, table.num_rows).astype(jnp.int32)
        cols = [MCol(c.values[perm],
                     None if c.valid is None else c.valid[perm],
                     c.type, c.dictionary) for c in table.cols]
        return MTable(cols, table.live, table.cap, table.est,
                      compacted=True, replicated=table.replicated)

    def _lower_single_row(self, node: EnforceSingleRowNode) -> MTable:
        import jax.numpy as jnp

        src = _compact(self._lower(node.source))
        n = src.num_rows
        self._errors.append(n > 1)
        cols = []
        for c in src.cols:
            v = c.values[:1]
            ok = (n >= 1)
            valid = (jnp.ones(1, bool) & ok if c.valid is None
                     else c.valid[:1] & ok)
            cols.append(MCol(v, valid, c.type, c.dictionary))
        return MTable(cols, jnp.ones(1, bool), 1, self.nparts,
                      compacted=True, replicated=src.replicated)


def _compact(table: MTable) -> MTable:
    """Move live rows to the front of every shard (stable)."""
    import jax.numpy as jnp

    if table.compacted:
        return table
    from presto_tpu.ops.radix import stable_partition_perm, use_radix

    if use_radix():
        order = stable_partition_perm(~table.live)
    else:
        order = jnp.argsort((~table.live).astype(jnp.int8)).astype(jnp.int32)
    n = table.live.sum()
    cols = [MCol(c.values[order],
                 None if c.valid is None else c.valid[order],
                 c.type, c.dictionary) for c in table.cols]
    live = jnp.arange(table.cap) < n
    return MTable(cols, live, table.cap, table.est, compacted=True,
                  replicated=table.replicated)


def _limit(table: MTable, count: int, nparts: int) -> MTable:
    """Per-shard LIMIT: each shard keeps its first ``count`` live rows,
    so the table may still hold count*nparts rows globally (the consumer
    re-limits after the gather, the reference's partial-limit shape)."""
    import jax.numpy as jnp

    table = _compact(table)
    live = jnp.arange(table.cap) < jnp.minimum(table.num_rows, count)
    return MTable(table.cols, live, table.cap,
                  min(table.est, count * nparts), compacted=True,
                  replicated=table.replicated)


def _concat(tables: List[MTable]) -> MTable:
    """Shard-local UNION ALL: stack padded columns; dictionaries unify."""
    import jax.numpy as jnp

    ncols = len(tables[0].cols)
    cols: List[MCol] = []
    for i in range(ncols):
        parts = [t.cols[i] for t in tables]
        d = None
        if any(p.dictionary is not None for p in parts):
            if not all(p.dictionary is not None for p in parts):
                raise MeshUnsupported("union mixes string encodings")
            d = Dictionary()
            remaps = [p.dictionary.remap_into(d) for p in parts]
            vals = jnp.concatenate([
                jnp.asarray(r)[jnp.clip(p.values, 0, len(r) - 1)]
                for p, r in zip(parts, remaps)])
        else:
            dtype = parts[0].values.dtype
            vals = jnp.concatenate([p.values.astype(dtype) for p in parts])
        if any(p.valid is not None for p in parts):
            valid = jnp.concatenate([
                p.valid if p.valid is not None
                else jnp.ones(t.cap, bool)
                for p, t in zip(parts, tables)])
        else:
            valid = None
        cols.append(MCol(vals, valid, parts[0].type, d))
    live = jnp.concatenate([t.live for t in tables])
    cap = sum(t.cap for t in tables)
    est = sum(t.est for t in tables)
    return MTable(cols, live, cap, est, compacted=False,
                  replicated=all(t.replicated for t in tables))
