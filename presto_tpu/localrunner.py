"""LocalQueryRunner: SQL in, rows out, one process.

The reference's LocalQueryRunner (presto-main/.../testing/LocalQueryRunner
.java:214,577) runs the full stack — parser, analyzer, planner, operators —
in one process with hand-pumped drivers; it is the backbone of the test
pyramid and the in-process benchmark harness.  Same role here:

    runner = LocalQueryRunner.tpch(scale=0.01)
    result = runner.execute("select count(*) from lineitem")
    result.rows  # [(60175,)]
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import List, Optional, Sequence, Tuple

from presto_tpu import types as T
from presto_tpu.config import DEFAULT, EngineConfig
from presto_tpu.connectors.api import Connector, ConnectorRegistry
from presto_tpu.exec.runner import execute_pipelines
from presto_tpu.exec.scancache import drop_connectors
from presto_tpu.sql import tree as t
from presto_tpu.sql.optimizer import optimize
from presto_tpu.sql.parser import parse_statement
from presto_tpu.sql.physical import PhysicalPlanner
from presto_tpu.sql.plan import format_plan
from presto_tpu.sql.planner import Metadata, Planner


@dataclasses.dataclass
class QueryResult:
    column_names: List[str]
    column_types: List[T.Type]
    rows: List[Tuple]


class _StagingSink:
    """PageSink wrapper that buffers until the enclosing explicit
    transaction commits (TransactionManager commit action)."""

    def __init__(self, inner):
        self.inner = inner
        self.batches = []
        self._rows = 0

    def append(self, batch) -> None:
        self.batches.append(batch)
        self._rows += batch.num_rows

    def finish(self) -> int:
        return self._rows

    def publish(self) -> None:
        for b in self.batches:
            self.inner.append(b)
        self.inner.finish()
        self.batches = []


def _like(value: str, pattern: Optional[str]) -> bool:
    """SQL LIKE for SHOW ... LIKE filters (% and _ wildcards)."""
    if pattern is None:
        return True
    import re

    rx = "".join(".*" if c == "%" else "." if c == "_" else re.escape(c)
                 for c in pattern)
    return re.fullmatch(rx, value) is not None


class LocalQueryRunner:
    def __init__(self, registry: ConnectorRegistry, default_catalog: str,
                 config: EngineConfig = DEFAULT, session=None,
                 access_control=None, session_property_manager=None):
        from presto_tpu.session import (
            AllowAllAccessControl, GrantStore, Session, TransactionManager,
        )

        self.registry = registry
        self.metadata = Metadata(registry, default_catalog)
        self.config = config
        # the tables this runner's connectors keep on the device
        # (exec/scancache.py) go when it is collected
        weakref.finalize(self, drop_connectors, registry)
        from presto_tpu.events import EventBus

        self.session = session or Session(catalog=default_catalog)
        if session_property_manager is not None:
            # rule-based session defaults (SET SESSION still overrides)
            session_property_manager.apply(self.session)
        self.access_control = access_control or AllowAllAccessControl()
        self.grants = GrantStore()
        if hasattr(self.access_control, "grants") and \
                self.access_control.grants is None:
            self.access_control.grants = self.grants
        self.transaction_manager = TransactionManager()
        self.event_bus = EventBus()
        self._last_task = None
        self._query_seq = 0
        self._whole_query = None   # lazy MeshQueryRunner (1-device)
        # (key, epochs) while the in-flight statement is plan-cacheable
        self._plan_cache_key = None
        # kill_query surface parity with the coordinator: ids this
        # runner has executed (all terminal — execution is synchronous)
        # and the statement currently on the caller's thread
        self._query_ids: set = set()
        self._current_query_id: Optional[str] = None

    @classmethod
    def tpch(cls, scale: float = 0.01,
             config: EngineConfig = DEFAULT, session=None,
             access_control=None) -> "LocalQueryRunner":
        from presto_tpu.connectors.memory import (
            BlackHoleConnector, MemoryConnector,
        )
        from presto_tpu.connectors.system import (
            InformationSchemaConnector, SystemConnector,
        )
        from presto_tpu.connectors.tpch import TpchConnector

        from presto_tpu.connectors.tpcds import TpcdsConnector

        reg = ConnectorRegistry()
        reg.register("tpch", TpchConnector(scale=scale))
        reg.register("tpcds", TpcdsConnector(scale=scale))
        reg.register("memory", MemoryConnector())
        reg.register("blackhole", BlackHoleConnector())
        reg.register("system", SystemConnector(
            nodes_fn=lambda: [("local", "local://", "dev", True,
                               "ACTIVE")]))
        reg.register("information_schema", InformationSchemaConnector(reg))
        return cls(reg, "tpch", config, session=session,
                   access_control=access_control)

    def register(self, catalog: str, connector: Connector) -> None:
        self.registry.register(catalog, connector)

    # --- statements --------------------------------------------------------
    def execute(self, sql: str) -> QueryResult:
        import uuid

        from presto_tpu import events as ev

        self._query_seq += 1
        qid = f"local-{self._query_seq}"
        self._current_query_id = qid
        self._query_ids.add(qid)
        trace = f"tt-{uuid.uuid4().hex[:12]}"
        created = ev.now()
        self.event_bus.query_created(ev.QueryCreatedEvent(
            qid, self.session.user, sql, created, trace_token=trace))
        self._last_task = None
        try:
            result = self._execute_statement(sql)
        except Exception as e:
            self.event_bus.query_completed(ev.QueryCompletedEvent(
                qid, self.session.user, sql, "FAILED", str(e), created,
                ev.now(), 0, 0, [], trace_token=trace))
            raise
        task = self._last_task
        # the single-process tier reports its one task as one stage, so
        # local and distributed QueryCompletedEvents share a shape
        stage_stats = []
        if task is not None:
            from presto_tpu.exec.context import StageStats

            st = StageStats(fragment_id=0, tasks=1)
            ts = task.task_stats()
            ts.elapsed_s = ev.now() - created
            st.add_task(ts)
            stage_stats = [st.as_dict()]
        self.event_bus.query_completed(ev.QueryCompletedEvent(
            qid, self.session.user, sql, "FINISHED", None, created,
            ev.now(), len(result.rows),
            task.memory.peak if task is not None else 0,
            [s.as_dict() for s in task.operator_stats]
            if task is not None else [],
            trace_token=trace, stage_stats=stage_stats))
        return result

    def _execute_statement(self, sql: str) -> QueryResult:
        from presto_tpu.sql import plancache

        cfg = self.session.effective_config(self.config)
        self._plan_cache_key = None
        if cfg.plan_cache_enabled:
            # serving-tier plan cache (sql/plancache.py): a repeated
            # statement under the same catalog/schema/session
            # fingerprint and live stats epochs reuses its optimized
            # plan — parse/analyze/optimize all skipped
            epochs = plancache.epochs_for(self.registry)
            key = plancache.cache_key(
                epochs, sql, self.metadata.default_catalog,
                self.session.schema, self.session.properties)
            hit = plancache.get(key, epochs)
            if hit is not None:
                return self._execute_optimized(hit.optimized, cfg,
                                               hit.label, cache_entry=hit)
            self._plan_cache_key = (key, epochs)
        try:
            stmt = parse_statement(sql)
            return self._execute_parsed(stmt)
        finally:
            self._plan_cache_key = None

    def _execute_parsed(self, stmt: t.Node) -> QueryResult:
        # per-catalog stats-epoch bump: any statement that changes a
        # catalog's data or metadata invalidates cached plans scanning
        # it (bumped up front — a failed write costs one spurious miss,
        # never a stale plan)
        if isinstance(stmt, (t.CreateTable, t.CreateTableAs, t.Insert,
                             t.Delete, t.DropTable, t.RenameTable,
                             t.CreateView, t.DropView, t.Analyze)):
            from presto_tpu.sql import plancache

            name = getattr(stmt, "table", None) or \
                getattr(stmt, "view", None)
            try:
                cat = (self.metadata.split_name(tuple(name))[0]
                       if name else self.metadata.default_catalog)
            except Exception:  # noqa: BLE001 - bad name errors later
                cat = self.metadata.default_catalog
            plancache.epochs_for(self.registry).bump(cat)
        if isinstance(stmt, t.CallProcedure):
            return self._run_kill_query(stmt)
        if isinstance(stmt, t.Explain):
            if stmt.analyze:
                text = self.explain_analyze_text(stmt.statement)
            elif stmt.plan_type == "distributed":
                text = self.explain_distributed_text(stmt.statement)
            elif stmt.plan_type == "validate":
                self._validate(stmt.statement)
                return QueryResult(["Valid"], [T.BOOLEAN], [(True,)])
            elif stmt.plan_type == "io":
                return self._explain_io(stmt.statement)
            else:
                text = self.explain_text(stmt.statement)
            return QueryResult(["Query Plan"], [T.VARCHAR],
                               [(line,) for line in text.splitlines()])
        if isinstance(stmt, t.ShowTables):
            cat = stmt.catalog or self.metadata.default_catalog
            conn = self.registry.get(cat)
            names = set(conn.list_tables())
            names.update(n for c, n in self.registry.views if c == cat)
            return QueryResult(["Table"], [T.VARCHAR],
                               [(n,) for n in sorted(names)
                                if _like(n, stmt.like)])
        if isinstance(stmt, t.ShowColumns):
            _, _, conn, schema = self.metadata.resolve_table(stmt.table)
            return QueryResult(
                ["Column", "Type"], [T.VARCHAR, T.VARCHAR],
                [(n, schema.column_type(n).display())
                 for n in schema.column_names()])
        if isinstance(stmt, t.SetSession):
            self.session.set_property(stmt.name, stmt.value)
            return QueryResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, t.ResetSession):
            self.session.reset_property(stmt.name)
            return QueryResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, t.ShowSession):
            return QueryResult(
                ["Name", "Value", "Default"],
                [T.VARCHAR, T.VARCHAR, T.VARCHAR],
                self.session.show_properties(self.config))
        if isinstance(stmt, t.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, t.CreateTableAs):
            return self._create_table_as(stmt)
        if isinstance(stmt, t.Insert):
            return self._insert(stmt)
        if isinstance(stmt, t.DropTable):
            # unknown catalog is an error even under IF EXISTS; only a
            # missing table is forgiven
            cat, _tbl = self.metadata.split_name(stmt.table)
            self.registry.get(cat)
            try:
                catalog, name, conn, _ = self.metadata.resolve_table(
                    stmt.table)
            except Exception:
                if stmt.if_exists:
                    return self._ok()
                raise
            self.access_control.check_can_drop_table(
                self.session.user, catalog, name)
            conn.drop_table(name)
            return self._ok()
        if isinstance(stmt, t.Delete):
            return self._delete(stmt)
        if isinstance(stmt, t.RenameTable):
            catalog, name, conn, _ = self.metadata.resolve_table(stmt.table)
            if len(stmt.new_name) == 1:
                new_cat, new_name = catalog, stmt.new_name[0]
            else:
                new_cat, new_name = self.metadata.split_name(stmt.new_name)
            if new_cat != catalog:
                raise ValueError("RENAME cannot move between catalogs")
            self.access_control.check_can_rename_table(
                self.session.user, catalog, name)
            conn.rename_table(name, new_name)
            self.grants.rename_table(catalog, name, new_name)
            self.access_control.notify_table_renamed(catalog, name,
                                                     new_name)
            return self._ok()
        if isinstance(stmt, t.CreateView):
            self.metadata.create_view(stmt.view, stmt.original_sql,
                                      stmt.replace)
            return self._ok()
        if isinstance(stmt, t.DropView):
            self.metadata.drop_view(stmt.view, stmt.if_exists)
            return self._ok()
        if isinstance(stmt, t.Prepare):
            self.session.prepared[stmt.name] = stmt.statement
            return self._ok()
        if isinstance(stmt, t.ExecutePrepared):
            prepared = self._get_prepared(stmt.name)
            bound = t.substitute_parameters(prepared, stmt.parameters)
            # never cache under the raw EXECUTE text: a re-PREPARE of
            # the same name would alias a stale plan (the coordinator
            # tier keys EXECUTE on prepared text + bound parameters)
            self._plan_cache_key = None
            return self._execute_parsed(bound)
        if isinstance(stmt, t.Deallocate):
            self._get_prepared(stmt.name)
            del self.session.prepared[stmt.name]
            return self._ok()
        if isinstance(stmt, t.DescribeInput):
            prepared = self._get_prepared(stmt.name)
            n = t.parameter_count(prepared)
            return QueryResult(
                ["Position", "Type"], [T.BIGINT, T.VARCHAR],
                [(i, "unknown") for i in range(n)])
        if isinstance(stmt, t.DescribeOutput):
            return self._describe_output(self._get_prepared(stmt.name))
        if isinstance(stmt, t.ShowCatalogs):
            rows = [(c,) for c in self.registry.catalogs()
                    if _like(c, stmt.like)]
            return QueryResult(["Catalog"], [T.VARCHAR], rows)
        if isinstance(stmt, t.ShowSchemas):
            cat = stmt.catalog or self.metadata.default_catalog
            self.registry.get(cat)  # raises for unknown catalog
            rows = [(s,) for s in ("default", "information_schema")
                    if _like(s, stmt.like)]
            return QueryResult(["Schema"], [T.VARCHAR], rows)
        if isinstance(stmt, t.ShowFunctions):
            from presto_tpu.expr.functions import function_names

            rows = [(n, kind) for n, kind in function_names()
                    if _like(n, stmt.like)]
            return QueryResult(["Function", "Function Type"],
                               [T.VARCHAR, T.VARCHAR], rows)
        if isinstance(stmt, t.ShowStats):
            return self._show_stats(stmt)
        if isinstance(stmt, t.ShowCreateTable):
            _, name, _, schema = self.metadata.resolve_table(stmt.table)
            cols = ",\n".join(
                f"   {n} {schema.column_type(n).display()}"
                for n in schema.column_names())
            ddl = f"CREATE TABLE {'.'.join(stmt.table)} (\n{cols}\n)"
            return QueryResult(["Create Table"], [T.VARCHAR], [(ddl,)])
        if isinstance(stmt, t.ShowCreateView):
            sql = self.metadata.get_view(stmt.view)
            if sql is None:
                raise ValueError(
                    f"view {'.'.join(stmt.view)} does not exist")
            ddl = f"CREATE VIEW {'.'.join(stmt.view)} AS\n{sql}"
            return QueryResult(["Create View"], [T.VARCHAR], [(ddl,)])
        if isinstance(stmt, t.Use):
            self.registry.get(stmt.catalog)  # raises for unknown catalog
            self.session.catalog = stmt.catalog
            self.session.schema = stmt.schema
            self.metadata.default_catalog = stmt.catalog
            return self._ok()
        if isinstance(stmt, t.StartTransaction):
            if self.session.txn is not None:
                raise ValueError("transaction already in progress")
            self.session.txn = self.transaction_manager.begin(
                auto_commit=False)
            return self._ok()
        if isinstance(stmt, t.Commit):
            if self.session.txn is None:
                raise ValueError("no transaction in progress")
            self.transaction_manager.commit(self.session.txn)
            self.session.txn = None
            return self._ok()
        if isinstance(stmt, t.Rollback):
            if self.session.txn is None:
                raise ValueError("no transaction in progress")
            self.transaction_manager.abort(self.session.txn)
            self.session.txn = None
            return self._ok()
        if isinstance(stmt, t.Analyze):
            _, name, conn, _ = self.metadata.resolve_table(stmt.table)
            conn.collect_statistics(conn.get_table(name))
            return self._ok()
        if isinstance(stmt, t.Grant):
            catalog, name = self.metadata.split_name(stmt.table)
            self.access_control.check_can_grant(
                self.session.user, catalog, name)
            self.grants.grant(stmt.grantee, catalog, name, stmt.privileges)
            return self._ok()
        if isinstance(stmt, t.Revoke):
            catalog, name = self.metadata.split_name(stmt.table)
            self.access_control.check_can_grant(
                self.session.user, catalog, name)
            self.grants.revoke(stmt.grantee, catalog, name, stmt.privileges)
            return self._ok()
        if not isinstance(stmt, (t.Query, t.SetOperation)):
            raise ValueError(f"unsupported statement {type(stmt).__name__}")
        return self._execute_query(stmt)

    @staticmethod
    def _ok() -> QueryResult:
        return QueryResult(["result"], [T.BOOLEAN], [(True,)])

    def _get_prepared(self, name: str) -> t.Node:
        stmt = self.session.prepared.get(name)
        if stmt is None:
            raise ValueError(f"prepared statement not found: {name}")
        return stmt

    def _describe_output(self, stmt: t.Node) -> QueryResult:
        cols = [("Column Name", T.VARCHAR), ("Type", T.VARCHAR)]
        if isinstance(stmt, (t.Query, t.SetOperation)):
            n_params = t.parameter_count(stmt)
            bound = t.substitute_parameters(
                stmt, tuple(t.NullLiteral() for _ in range(n_params)))
            logical = Planner(self.metadata).plan(bound)
            rows = [(cn, ty.display()) for cn, ty in logical.columns]
        elif isinstance(stmt, (t.Insert, t.CreateTableAs, t.Delete)):
            rows = [("rows", "bigint")]
        else:
            rows = [("result", "boolean")]
        return QueryResult([c for c, _ in cols], [ty for _, ty in cols],
                           rows)

    def _show_stats(self, stmt: t.ShowStats) -> QueryResult:
        _, name, conn, schema = self.metadata.resolve_table(stmt.table)
        stats = conn.table_statistics(conn.get_table(name))
        names = ["column_name", "data_size", "distinct_values_count",
                 "nulls_fraction", "row_count", "low_value", "high_value"]
        types = [T.VARCHAR, T.DOUBLE, T.DOUBLE, T.DOUBLE, T.DOUBLE,
                 T.VARCHAR, T.VARCHAR]
        rows: List[Tuple] = []
        if stats is not None:
            for cn in schema.column_names():
                rows.append((
                    cn,
                    stats.data_size.get(cn),
                    stats.ndv.get(cn),
                    stats.nulls_fraction.get(cn),
                    None,
                    str(stats.low[cn]) if cn in stats.low else None,
                    str(stats.high[cn]) if cn in stats.high else None))
            rows.append((None, None, None, None, float(stats.row_count),
                         None, None))
        return QueryResult(names, types, rows)

    def _delete(self, stmt: t.Delete) -> QueryResult:
        """DELETE FROM t WHERE pred: the predicate is evaluated
        connector-side per stored batch via the numpy oracle backend
        (the reference's beginDelete + DeleteOperator + rowId path,
        presto-main/.../operator/DeleteOperator.java:39, collapsed to a
        mask-rewrite since storage is engine-local)."""
        import numpy as np

        from presto_tpu.expr.compile import evaluate
        from presto_tpu.sql.planner import Field, Scope, Translator

        catalog, name, conn, schema = self.metadata.resolve_table(
            stmt.table)
        self.access_control.check_can_delete(
            self.session.user, catalog, name)
        handle = conn.get_table(name)
        if stmt.where is None:
            mask_fn = lambda b: np.ones(b.num_rows, bool)  # noqa: E731
        else:
            scope = Scope([Field(n, name, schema.column_type(n))
                           for n in schema.column_names()], None)
            pred = Translator(scope).translate(stmt.where)
            if pred.type != T.BOOLEAN:
                raise ValueError("DELETE predicate must be boolean")

            def mask_fn(b):
                col = evaluate(pred, b.to_numpy())
                vals = np.asarray(col.values)[:b.num_rows].astype(bool)
                if col.valid is not None:
                    vals &= np.asarray(col.valid)[:b.num_rows].astype(bool)
                return vals

        deleted = conn.delete_rows(handle, mask_fn)
        return QueryResult(["rows"], [T.BIGINT], [(deleted,)])

    # --- DML (TableWriter path, SURVEY §2.6 write operators) ---------------
    def _resolve_write_target(self, table):
        """catalog + bare table name for CREATE/INSERT targets."""
        parts = tuple(table)
        if len(parts) == 1:
            return self.metadata.default_catalog, parts[0]
        if len(parts) == 2:
            return parts[0], parts[1]
        raise ValueError(f"bad table name {'.'.join(parts)}")

    def _run_kill_query(self, stmt: t.CallProcedure) -> QueryResult:
        """CALL system.runtime.kill_query — the coordinator procedure's
        single-process twin (KillQueryProcedure.java role): identical
        name/argument validation and error messages, and the SAME
        ADMINISTRATIVELY_KILLED shape in the fired ``QueryKilledEvent``.
        Local statements execute synchronously on the caller's thread,
        so any valid target is already terminal and the kill itself is
        the same no-op the coordinator applies to terminal queries."""
        from presto_tpu import events as ev
        from presto_tpu.server.coordinator import ADMINISTRATIVELY_KILLED

        name = ".".join(stmt.name)
        if name not in ("system.runtime.kill_query", "kill_query"):
            raise ValueError(f"unknown procedure {name}")
        if len(stmt.args) < 1 or not isinstance(stmt.args[0],
                                                t.StringLiteral):
            raise ValueError("kill_query(query_id) requires a string id")
        qid = stmt.args[0].value
        message = "Query killed via kill_query"
        if len(stmt.args) > 1:
            if not isinstance(stmt.args[1], t.StringLiteral):
                raise ValueError(
                    "kill_query(query_id, message) requires a string "
                    "message")
            if stmt.args[1].value:
                message = f"Query killed via kill_query: " \
                          f"{stmt.args[1].value}"
        if qid == self._current_query_id:
            raise ValueError("a query cannot kill itself")
        if qid not in self._query_ids:
            raise ValueError(f"no such query {qid!r}")
        self.event_bus.query_killed(ev.QueryKilledEvent(
            qid, "", self.session.user, "kill_query",
            ADMINISTRATIVELY_KILLED[0], message, ev.now()))
        return QueryResult(["result"], [T.VARCHAR], [("killed",)])

    def _create_table(self, stmt: t.CreateTable) -> QueryResult:
        from presto_tpu.connectors.api import ColumnMetadata, TableSchema

        catalog, name = self._resolve_write_target(stmt.table)
        self.access_control.check_can_create_table(
            self.session.user, catalog, name)
        conn = self.registry.get(catalog)
        if stmt.if_not_exists and self._table_exists(conn, name):
            return self._ok()
        schema = TableSchema(name, tuple(
            ColumnMetadata(cn, T.parse_type(ct))
            for cn, ct in stmt.columns))
        conn.create_table(name, schema, dict(stmt.properties) or None)
        return QueryResult(["result"], [T.BOOLEAN], [(True,)])

    @staticmethod
    def _table_exists(conn, name: str) -> bool:
        try:
            return conn.get_table(name) is not None
        except Exception:
            return False

    def prepare_ctas(self, stmt: t.CreateTableAs):
        """Plan CTAS: returns (logical OutputNode | None-if-exists, conn,
        handle, catalog, name).  Shared by the local write path and the
        coordinator's distributed writer planning."""
        from presto_tpu.connectors.api import ColumnMetadata, TableSchema

        logical = Planner(self.metadata).plan(stmt.query)
        catalog, name = self._resolve_write_target(stmt.table)
        self.access_control.check_can_create_table(
            self.session.user, catalog, name)
        conn = self.registry.get(catalog)
        if stmt.if_not_exists and self._table_exists(conn, name):
            return None, conn, None, catalog, name
        schema = TableSchema(name, tuple(
            ColumnMetadata(cn, typ) for cn, typ in logical.columns))
        handle = conn.create_table(name, schema,
                                   dict(stmt.properties) or None)
        return logical, conn, handle, catalog, name

    def _create_table_as(self, stmt: t.CreateTableAs) -> QueryResult:
        logical, conn, handle, _, _ = self.prepare_ctas(stmt)
        if logical is None:
            return QueryResult(["rows"], [T.BIGINT], [(0,)])
        return self._write(logical, conn, handle)

    def prepare_insert(self, stmt: t.Insert):
        """Plan INSERT with column alignment/coercion: returns
        (logical OutputNode, conn, handle, catalog, name)."""
        from presto_tpu.expr import build as B
        from presto_tpu.sql.plan import OutputNode, ProjectNode

        catalog, name = self._resolve_write_target(stmt.table)
        self.access_control.check_can_insert(
            self.session.user, catalog, name)
        conn = self.registry.get(catalog)
        handle = conn.get_table(name)
        schema = conn.table_schema(handle)

        if isinstance(stmt.source, t.InlineValues):
            query: t.Node = t.Query(
                (t.SelectItem(t.Star()),), (stmt.source,))
        else:
            query = stmt.source
        logical = Planner(self.metadata).plan(query)

        src_cols = stmt.columns or tuple(schema.column_names())
        if len(logical.columns) != len(src_cols):
            raise ValueError(
                f"INSERT has {len(logical.columns)} columns, expected "
                f"{len(src_cols)}")
        # align + coerce to the table's column order and types; unnamed
        # target columns get NULL
        by_name = dict(zip(src_cols, range(len(src_cols))))
        exprs = []
        for cn in schema.column_names():
            typ = schema.column_type(cn)
            if cn in by_name:
                i = by_name[cn]
                ref = B.ref(i, logical.columns[i][1])
                exprs.append(ref if ref.type == typ else B.cast(ref, typ))
            else:
                exprs.append(B.null(typ))
        cols = tuple((cn, schema.column_type(cn))
                     for cn in schema.column_names())
        project = ProjectNode(logical.source, tuple(exprs), cols)
        return OutputNode(project, cols), conn, handle, catalog, name

    def _insert(self, stmt: t.Insert) -> QueryResult:
        logical, conn, handle, _, _ = self.prepare_insert(stmt)
        return self._write(logical, conn, handle)

    def _write(self, logical, conn, handle) -> QueryResult:
        from presto_tpu.exec.operators import TableWriterOperatorFactory

        cfg = self.session.effective_config(self.config)
        optimized = optimize(logical, self.metadata)
        self._check_scans(optimized)
        planner = PhysicalPlanner(self.registry, cfg)
        sink = conn.page_sink(handle)
        explicit = self.session.txn
        if explicit is not None:
            # START TRANSACTION write: stage pages; publish at COMMIT
            # (ROLLBACK discards).  DDL stays non-transactional, matching
            # most reference connectors.
            sink = _StagingSink(sink)
            explicit.commit_actions.append(sink.publish)
        writer = TableWriterOperatorFactory(sink)
        pipelines = planner.plan_fragment(optimized.source, writer)
        # auto-commit: the PageSink's finish IS the commit point; failures
        # before it leave the table untouched
        txn = explicit or self.transaction_manager.begin()
        try:
            execute_pipelines(pipelines, cfg)
        except Exception:
            self.transaction_manager.abort(txn)
            if explicit is not None:
                self.session.txn = None
            raise
        if explicit is None:
            self.transaction_manager.commit(txn)
        return QueryResult(["rows"], [T.BIGINT],
                           [(writer.op.rows_written,)])

    def explain(self, sql: str) -> str:
        stmt = parse_statement(sql)
        if isinstance(stmt, t.Explain):
            stmt = stmt.statement
        return self.explain_text(stmt)

    def explain_text(self, stmt: t.Node) -> str:
        if not isinstance(stmt, (t.Query, t.SetOperation)):
            raise ValueError("EXPLAIN requires a query")
        cfg = self.session.effective_config(self.config)
        logical = Planner(self.metadata).plan(stmt)
        optimized = optimize(logical, self.metadata, cfg)
        # surface the optimizer's estimates alongside the plan (the
        # PlanPrinter stats/cost annotation role); rows/cost render only
        # where the stats derivation produced estimates
        annotator = None
        if cfg.optimizer_use_memo:
            from presto_tpu.sql.memo import cost_annotator

            annotator = cost_annotator(self.metadata, cfg)
        return format_plan(optimized, annotator=annotator)

    def _validate(self, stmt: t.Node) -> None:
        """EXPLAIN (TYPE VALIDATE): analyze/plan without executing.
        Queries plan fully; DML validates its target and source; DDL
        validates names/types — errors raise instead of reporting
        Valid."""
        if isinstance(stmt, (t.Query, t.SetOperation)):
            optimize(Planner(self.metadata).plan(stmt), self.metadata)
            return
        if isinstance(stmt, t.Insert):
            catalog, name = self._resolve_write_target(stmt.table)
            conn = self.registry.get(catalog)
            conn.table_schema(conn.get_table(name))
            source = (t.Query((t.SelectItem(t.Star()),), (stmt.source,))
                      if isinstance(stmt.source, t.InlineValues)
                      else stmt.source)
            Planner(self.metadata).plan(source)
            return
        if isinstance(stmt, t.CreateTableAs):
            Planner(self.metadata).plan(stmt.query)
            return
        if isinstance(stmt, t.CreateTable):
            for _cn, ct in stmt.columns:
                T.parse_type(ct)
            return
        if isinstance(stmt, (t.Delete, t.ShowStats, t.Analyze)):
            self.metadata.resolve_table(stmt.table)
            return
        if isinstance(stmt, (t.DropTable, t.RenameTable)):
            if not getattr(stmt, "if_exists", False):
                self.metadata.resolve_table(stmt.table)
            return
        # session/metadata statements: parsing was the validation

    def explain_distributed_text(self, stmt: t.Node) -> str:
        """EXPLAIN (TYPE DISTRIBUTED): the fragmented plan
        (PlanPrinter.textDistributedPlan role)."""
        from presto_tpu.server.fragmenter import Fragmenter

        if not isinstance(stmt, (t.Query, t.SetOperation)):
            raise ValueError("EXPLAIN requires a query")
        cfg = self.session.effective_config(self.config)
        logical = Planner(self.metadata).plan(stmt)
        optimized = optimize(logical, self.metadata, cfg)
        dplan = Fragmenter(metadata=self.metadata,
                           config=cfg).fragment(optimized)
        lines = []
        for f in dplan.fragments:
            out_kind, out_ch = f.output_partitioning
            lines.append(
                f"Fragment {f.fragment_id} [{f.partitioning}] "
                f"=> output {out_kind}{list(out_ch) if out_ch else ''}")
            for ln in format_plan(f.root).splitlines():
                lines.append("    " + ln)
        return "\n".join(lines)

    def _explain_io(self, stmt: t.Node) -> QueryResult:
        """EXPLAIN (TYPE IO): the tables the query reads
        (IoPlanPrinter role), as one JSON row."""
        import json as _json

        from presto_tpu.sql.plan import TableScanNode

        if not isinstance(stmt, (t.Query, t.SetOperation)):
            raise ValueError("EXPLAIN requires a query")
        logical = Planner(self.metadata).plan(stmt)
        optimized = optimize(logical, self.metadata)
        tables = []

        def walk(node):
            if isinstance(node, TableScanNode):
                entry = {"catalog": node.catalog, "table": node.table,
                         "columns": list(node.column_names)}
                if entry not in tables:
                    tables.append(entry)
            for s in node.sources:
                walk(s)

        walk(optimized)
        return QueryResult(
            ["Query Input"], [T.VARCHAR],
            [(_json.dumps({"inputTables": tables}),)])

    def explain_analyze_text(self, stmt: t.Node) -> str:
        """EXPLAIN ANALYZE: run the query, render the plan plus the
        per-operator wall/row rollup the Driver recorded
        (ExplainAnalyzeOperator.java:34 + planPrinter role)."""
        import time as _time

        if not isinstance(stmt, (t.Query, t.SetOperation)):
            raise ValueError("EXPLAIN ANALYZE requires a query")
        t0 = _time.perf_counter()
        logical = Planner(self.metadata).plan(stmt)
        optimized = optimize(logical, self.metadata)
        phys = PhysicalPlanner(self.registry, self.config).plan(optimized)
        task = execute_pipelines(phys.pipelines, self.config)
        self._last_task = task   # EA ran a real task: report its stats
        execution_s = _time.perf_counter() - t0
        lines = [format_plan(optimized).rstrip(), "", "Operator stats:"]
        # same counter set as the distributed tier's _render_analyze
        # (jit dispatch/compile, pre-reduce rows, peak memory) so the
        # two EXPLAIN ANALYZE surfaces stay diffable
        header = (f"{'operator':<40} {'in rows':>10} {'out rows':>10} "
                  f"{'wall ms':>9} {'finish ms':>9} {'compile ms':>10} "
                  f"{'jit disp':>8} {'jit comp':>8} {'prereduce':>9}")
        lines += [header, "-" * len(header)]
        for s in task.operator_stats:
            lines.append(
                f"{s.operator:<40} {s.input_rows:>10} {s.output_rows:>10} "
                f"{s.wall_ns / 1e6:>9.1f} {s.finish_wall_ns / 1e6:>9.1f} "
                f"{s.jit_compile_ns / 1e6:>10.1f} "
                f"{s.jit_dispatches:>8} {s.jit_compiles:>8} "
                f"{s.prereduce_rows:>9}")
        from presto_tpu.exec.context import (
            host_and_xla_line, hot_operator_lines, kernel_tier_lines,
            segment_line, scan_cache_line,
        )

        op_dicts = [dict(s.as_dict(), wall_ns=s.wall_ns + s.finish_wall_ns)
                    for s in task.operator_stats]
        lines.extend(hot_operator_lines(op_dicts))
        lines.extend(kernel_tier_lines(op_dicts))
        jc = task.jit_counters()
        lines.append(
            f"peak memory: {task.memory.peak / (1 << 20):.1f} MiB; "
            f"jit dispatches: {jc['dispatches']}, "
            f"compiles: {jc['compiles']} "
            f"({jc['compile_ns'] / 1e6:.1f} ms compile); "
            f"prereduce rows: {jc['prereduce_rows']}")
        task_stats = task.task_stats().as_dict()
        lines.append(host_and_xla_line(task_stats))
        lines.append(segment_line(task_stats))
        lines.append(scan_cache_line(task_stats))
        # queued-vs-execution split: same footer shape as the
        # distributed tier's _render_analyze (the single-process runner
        # executes synchronously — queued is always 0)
        lines.append(f"serving: queued 0.000 s, "
                     f"execution {execution_s:.3f} s")
        for d in task.driver_stats:
            lines.append(
                f"driver {d.pipeline}: {d.operators} operators, "
                f"{d.input_rows} -> {d.output_rows} rows, "
                f"{d.wall_ns / 1e6:.1f} ms")
        from presto_tpu.kernelcache import cache_stats

        stats = {n: s for n, s in cache_stats().items()
                 if s["hits"] or s["misses"] or s["size"]}
        if stats:
            lines.append("kernel caches (process-wide): " + "; ".join(
                f"{n}: size={s['size']} hits={s['hits']} "
                f"misses={s['misses']} evictions={s['evictions']}"
                for n, s in stats.items()))
        return "\n".join(lines)

    def _check_scans(self, node) -> None:
        from presto_tpu.sql.plan import TableScanNode

        if isinstance(node, TableScanNode):
            self.access_control.check_can_select(
                self.session.user, node.catalog, node.table)
        for s in node.sources:
            self._check_scans(s)

    def _execute_query(self, q: t.Node) -> QueryResult:
        cfg = self.session.effective_config(self.config)
        logical = Planner(self.metadata).plan(q)
        optimized = optimize(logical, self.metadata, cfg)
        entry = None
        if self._plan_cache_key is not None:
            from presto_tpu.sql import plancache

            key, epochs = self._plan_cache_key
            self._plan_cache_key = None
            cats = plancache.scan_catalogs(optimized)
            cats.add(self.metadata.default_catalog)
            entry = plancache.CachedLocalPlan(optimized, repr(q))
            plancache.put(key, entry, epochs, cats,
                          cfg.plan_cache_capacity)
        return self._execute_optimized(optimized, cfg, repr(q),
                                       cache_entry=entry)

    def _execute_optimized(self, optimized, cfg, label: str,
                           cache_entry=None) -> QueryResult:
        """Run an already-optimized plan (fresh or plan-cache hit);
        access control still runs per execution (the cache key carries
        no identity).  ``cache_entry`` (plancache.CachedLocalPlan)
        shares the physical-planner output across executions: the first
        run fills it, repeats reset-and-reuse the operator factory
        chains instead of re-running the physical planner per
        execution."""
        self._check_scans(optimized)
        if cfg.whole_query_execution:
            result = self._try_whole_query(label, optimized)
            if result is not None:
                return result
        entry = cache_entry
        phys = None
        if entry is not None and entry.physical is not None \
                and not entry.in_use:
            phys = entry.physical
            entry.in_use = True
            phys.reset_for_execution()
        if phys is None:
            phys = PhysicalPlanner(self.registry, cfg).plan(optimized)
            if entry is not None and entry.physical is None \
                    and not entry.in_use:
                entry.physical = phys
                entry.in_use = True
            else:
                entry = None
        try:
            self._last_task = execute_pipelines(
                phys.pipelines, cfg,
                memory_limit=cfg.query_max_memory_bytes or None)
            return QueryResult(phys.column_names, phys.column_types,
                               phys.collector.rows())
        finally:
            if entry is not None:
                entry.in_use = False

    def _try_whole_query(self, label: str,
                         optimized) -> Optional[QueryResult]:
        """Whole-query XLA execution: the mesh-SQL lowering on a
        single-device mesh compiles the ENTIRE query into one cached
        program — repeat executions are one device dispatch instead of
        per-operator dispatches.  Unsupported shapes fall
        back to the operator tier."""
        from presto_tpu.parallel.sqlmesh import (
            MeshQueryRunner, MeshUnsupported,
        )

        if self._whole_query is None:
            self._whole_query = MeshQueryRunner(
                self.registry, self.metadata.default_catalog,
                n_devices=1, config=self.config)
        try:
            # the optimized plan is reused (no second plan+optimize);
            # access control already ran over its scans
            return self._whole_query.execute_plan(optimized, label)
        except (MeshUnsupported, NotImplementedError):
            return None
        except ValueError:
            # query-semantic errors surfaced during mesh EXECUTION (e.g.
            # "scalar subquery returned more than one row") are the user's
            # answer, not a lowering failure — don't re-run the query
            raise
        except Exception as exc:  # noqa: BLE001 - operator tier can still run
            import warnings
            warnings.warn(
                f"whole-query mesh trace failed ({type(exc).__name__}: {exc}); "
                "falling back to the operator tier", RuntimeWarning,
                stacklevel=2)
            return None
