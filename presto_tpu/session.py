"""Sessions, session properties, access control, transactions.

The reference splits these across three subsystems that all hang off the
per-query ``Session``:

- **Session properties** (presto-main/.../SystemSessionProperties.java:51,
  147 properties): per-query overrides of engine behavior, set via
  ``SET SESSION k = v``, typed and validated against a registry.
- **Access control** (presto-main/.../security/, presto-spi security SPI;
  file-based impl in presto-plugin-toolkit): table-level permission
  checks made at analysis time with the session identity.
- **Transactions** (presto-main/.../transaction/TransactionManager
  .java:28): one transaction per query (auto-commit), carrying connector
  transaction handles.

Here ``Session`` carries identity + catalog + property overrides and can
materialize an effective ``EngineConfig``; ``AccessControl`` has allow-all
and rule-based implementations; ``TransactionManager`` issues per-query
transaction contexts with commit/abort callbacks into connectors.
"""

from __future__ import annotations

import dataclasses
import threading
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

from presto_tpu.config import DEFAULT, EngineConfig

# ---------------------------------------------------------------------------
# session properties
# ---------------------------------------------------------------------------

def _enum_parser(name: str, allowed: Tuple[str, ...]):
    def parse(v: str) -> str:
        lv = v.lower()
        if lv not in allowed:
            raise ValueError(
                f"{name} must be one of {', '.join(allowed)}")
        return lv

    return parse


# property name -> (config field, parser); the SystemSessionProperties
# registry: every entry is typed and validated on SET
SESSION_PROPERTIES: Dict[str, Tuple[str, Callable[[str], Any]]] = {
    "spill_enabled": ("spill_enabled",
                      lambda v: v.lower() in ("true", "1", "on")),
    "spill_threshold_bytes": ("spill_threshold_bytes", int),
    "spill_partitions": ("spill_partitions", int),
    "scan_batch_rows": ("scan_batch_rows", int),
    "min_batch_capacity": ("min_batch_capacity", int),
    "task_concurrency": ("task_concurrency", int),
    "join_expansion_factor": ("join_expansion_factor", int),
    "direct_groupby_max_domain": ("direct_groupby_max_domain", int),
    "dynamic_filtering_enabled": ("dynamic_filtering_enabled",
                                  lambda v: v.lower() in ("true", "1",
                                                          "on")),
    "kernel_cache_capacity": ("kernel_cache_capacity", int),
    "whole_query_execution": ("whole_query_execution",
                              lambda v: v.lower() in ("true", "1", "on")),
    "streaming_aggregation_enabled": (
        "streaming_aggregation_enabled",
        lambda v: v.lower() in ("true", "1", "on")),
    "grouped_execution_buckets": ("grouped_execution_buckets", int),
    "join_distribution_type": ("join_distribution_type", _enum_parser(
        "join_distribution_type",
        ("automatic", "broadcast", "partitioned"))),
    "broadcast_join_row_limit": ("broadcast_join_row_limit", int),
    "join_reordering_strategy": ("join_reordering_strategy", _enum_parser(
        "join_reordering_strategy", ("automatic", "none"))),
    "optimizer_use_memo": ("optimizer_use_memo",
                           lambda v: v.lower() in ("true", "1", "on")),
    "memo_max_reorder_relations": ("memo_max_reorder_relations", int),
    "partial_aggregation_enabled": (
        "partial_aggregation_enabled",
        lambda v: v.lower() in ("true", "1", "on")),
    "scaled_writer_rows_per_task": ("scaled_writer_rows_per_task", int),
    "hash_partition_count": ("hash_partition_count", int),
    "query_max_memory_bytes": ("query_max_memory_bytes", int),
    # cluster-wide (summed over every worker) per-query reservation cap,
    # enforced by the coordinator's memory tick
    "query_max_total_memory_bytes": ("query_max_total_memory_bytes",
                                     int),
    "query_max_run_time_s": ("query_max_run_time_s", float),
    "stage_retry_limit": ("stage_retry_limit", int),
    "cancel_fanout_budget_s": ("cancel_fanout_budget_s", float),
    "speculative_execution_enabled": (
        "speculative_execution_enabled",
        lambda v: v.lower() in ("true", "1", "on")),
    "speculation_quantile": ("speculation_quantile", float),
    "speculation_lag_factor": ("speculation_lag_factor", float),
    "speculation_min_runtime_s": ("speculation_min_runtime_s", float),
    "exchange_spooling_enabled": (
        "exchange_spooling_enabled",
        lambda v: v.lower() in ("true", "1", "on")),
    "exchange_max_buffer_bytes": ("exchange_max_buffer_bytes", int),
    "exchange_spool_stall_s": ("exchange_spool_stall_s", float),
    "plan_cache_enabled": ("plan_cache_enabled",
                           lambda v: v.lower() in ("true", "1", "on")),
    "plan_cache_capacity": ("plan_cache_capacity", int),
    "result_cache_enabled": (
        "result_cache_enabled",
        lambda v: v.lower() in ("true", "1", "on")),
    "result_cache_max_entry_bytes": ("result_cache_max_entry_bytes",
                                     int),
    "query_queue_timeout_s": ("query_queue_timeout_s", float),
    "device_join_probe_max_build_rows": (
        "device_join_probe_max_build_rows", int),
    "prereduce_max_group_fraction": (
        "prereduce_max_group_fraction", float),
    "mesh_device_exchange": (
        "mesh_device_exchange",
        lambda v: v.lower() in ("true", "1", "on")),
    "partitioned_join_build": (
        "partitioned_join_build",
        lambda v: v.lower() in ("true", "1", "on")),
    "grouped_mesh_execution": ("grouped_mesh_execution", int),
    "mesh_progress_beacons": (
        "mesh_progress_beacons",
        lambda v: v.lower() in ("true", "1", "on")),
    "mesh_checkpoint_boundaries": (
        "mesh_checkpoint_boundaries",
        lambda v: v.lower() in ("true", "1", "on")),
    "mesh_resume_mode": ("mesh_resume_mode", str),
    "stats_sampling_enabled": (
        "stats_sampling_enabled",
        lambda v: v.lower() in ("true", "1", "on")),
    "stats_sample_interval_s": ("stats_sample_interval_s", float),
    "slow_query_log_threshold_s": ("slow_query_log_threshold_s", float),
}


class SessionError(ValueError):
    pass


@dataclasses.dataclass
class Session:
    """Per-connection context (Session.java role)."""

    user: str = "user"
    catalog: str = "tpch"
    schema: Optional[str] = None
    properties: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # PREPARE name FROM stmt storage (Session.preparedStatements role);
    # values are parsed statement trees
    prepared: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # explicit transaction opened by START TRANSACTION (None = autocommit)
    txn: Optional[Any] = None

    def set_property(self, name: str, value: str) -> None:
        name = name.lower()
        if name not in SESSION_PROPERTIES:
            raise SessionError(f"unknown session property {name!r}")
        _, parse = SESSION_PROPERTIES[name]
        try:
            self.properties[name] = parse(value)
        except (ValueError, TypeError) as e:
            raise SessionError(
                f"bad value for session property {name!r}: {value!r}"
            ) from e

    def reset_property(self, name: str) -> None:
        self.properties.pop(name.lower(), None)

    def effective_config(self, base: EngineConfig = DEFAULT) -> EngineConfig:
        if not self.properties:
            return base
        fields = {SESSION_PROPERTIES[k][0]: v
                  for k, v in self.properties.items()}
        return dataclasses.replace(base, **fields)

    def show_properties(self, base: EngineConfig = DEFAULT
                        ) -> List[Tuple[str, str, str]]:
        """(name, value, default) rows for SHOW SESSION."""
        out = []
        for name, (field, _) in sorted(SESSION_PROPERTIES.items()):
            default = getattr(base, field)
            value = self.properties.get(name, default)
            out.append((name, str(value), str(default)))
        return out


# ---------------------------------------------------------------------------
# access control
# ---------------------------------------------------------------------------

class AccessDeniedError(PermissionError):
    pass


class AccessControl:
    """SystemAccessControl SPI surface used by the engine."""

    def check_can_select(self, user: str, catalog: str, table: str) -> None:
        raise NotImplementedError

    def check_can_delete(self, user: str, catalog: str, table: str) -> None:
        # default: DELETE gated like INSERT (write privilege)
        self.check_can_insert(user, catalog, table)

    def check_can_grant(self, user: str, catalog: str, table: str) -> None:
        # default: granting gated like dropping (ownership-level right)
        self.check_can_drop_table(user, catalog, table)

    def check_can_rename_table(self, user: str, catalog: str,
                               table: str) -> None:
        self.check_can_drop_table(user, catalog, table)

    def notify_table_renamed(self, catalog: str, old: str,
                             new: str) -> None:
        """Hook so implementations can migrate per-table state."""

    def check_can_insert(self, user: str, catalog: str, table: str) -> None:
        raise NotImplementedError

    def check_can_create_table(self, user: str, catalog: str,
                               table: str) -> None:
        raise NotImplementedError

    def check_can_drop_table(self, user: str, catalog: str,
                             table: str) -> None:
        raise NotImplementedError


class AllowAllAccessControl(AccessControl):
    def check_can_select(self, user, catalog, table):
        pass

    def check_can_insert(self, user, catalog, table):
        pass

    def check_can_create_table(self, user, catalog, table):
        pass

    def check_can_drop_table(self, user, catalog, table):
        pass


class RuleBasedAccessControl(AccessControl):
    """The file-based access control model (presto-plugin-toolkit's
    FileBasedSystemAccessControl): ordered rules of
    {user, catalog, table, privileges}; first match wins, no match denies.
    Patterns are '*'-wildcards."""

    def __init__(self, rules: List[Dict[str, Any]]):
        self.rules = rules

    @staticmethod
    def _match(pattern: str, value: str) -> bool:
        import fnmatch

        return fnmatch.fnmatch(value, pattern)

    def _check(self, user: str, catalog: str, table: str,
               privilege: str) -> None:
        for rule in self.rules:
            if not self._match(rule.get("user", "*"), user):
                continue
            if not self._match(rule.get("catalog", "*"), catalog):
                continue
            if not self._match(rule.get("table", "*"), table):
                continue
            if privilege in rule.get("privileges", ()):
                return
            break  # first matching rule decides
        raise AccessDeniedError(
            f"Access denied: {user} cannot {privilege} "
            f"{catalog}.{table}")

    def check_can_select(self, user, catalog, table):
        self._check(user, catalog, table, "select")

    def check_can_insert(self, user, catalog, table):
        self._check(user, catalog, table, "insert")

    def check_can_create_table(self, user, catalog, table):
        self._check(user, catalog, table, "create")

    def check_can_drop_table(self, user, catalog, table):
        self._check(user, catalog, table, "drop")

    def check_can_delete(self, user, catalog, table):
        self._check(user, catalog, table, "delete")

    def check_can_grant(self, user, catalog, table):
        self._check(user, catalog, table, "grant")


class GrantStore:
    """SQL-managed privileges: GRANT/REVOKE state, keyed
    (user, catalog, table) -> set of privileges ('all' covers every
    privilege).  Thread-safe; shared by every session of a runner."""

    def __init__(self):
        self._lock = threading.Lock()
        self._grants: Dict[Tuple[str, str, str], set] = {}

    def grant(self, user: str, catalog: str, table: str,
              privileges) -> None:
        with self._lock:
            self._grants.setdefault((user, catalog, table),
                                    set()).update(privileges)

    def revoke(self, user: str, catalog: str, table: str,
               privileges) -> None:
        with self._lock:
            have = self._grants.get((user, catalog, table))
            if have:
                have.difference_update(privileges)

    def has(self, user: str, catalog: str, table: str,
            privilege: str) -> bool:
        with self._lock:
            have = self._grants.get((user, catalog, table), set())
            return privilege in have or "all" in have

    def rename_table(self, catalog: str, old: str, new: str) -> None:
        """Migrate grants when a table is renamed."""
        with self._lock:
            for key in [k for k in self._grants
                        if k[1] == catalog and k[2] == old]:
                self._grants[(key[0], catalog, new)] = \
                    self._grants.pop(key)


class GrantAwareAccessControl(AccessControl):
    """Access control driven by the GrantStore: the table owner (creator)
    and any ``admin_users`` bypass checks; everyone else needs an explicit
    GRANT.  This is the SQL-standard access-control mode of the reference
    (sql-standard AccessControl in presto-hive, GRANT/REVOKE in
    StatementAnalyzer)."""

    def __init__(self, grants: Optional[GrantStore] = None,
                 admin_users=("admin",)):
        # when None, the runner binds its shared GrantStore at attach time
        self.grants = grants
        self.admins = set(admin_users)
        self._owners: Dict[Tuple[str, str], str] = {}

    def _check(self, user, catalog, table, privilege):
        if user in self.admins:
            return
        if self._owners.get((catalog, table)) == user:
            return
        if self.grants.has(user, catalog, table, privilege):
            return
        raise AccessDeniedError(
            f"Access denied: {user} cannot {privilege} {catalog}.{table}")

    def check_can_select(self, user, catalog, table):
        self._check(user, catalog, table, "select")

    def check_can_insert(self, user, catalog, table):
        self._check(user, catalog, table, "insert")

    def check_can_create_table(self, user, catalog, table):
        # first creator wins: never steal ownership when the table
        # already exists (the create itself will fail later)
        self._owners.setdefault((catalog, table), user)

    def check_can_drop_table(self, user, catalog, table):
        if user in self.admins or self._owners.get(
                (catalog, table)) == user:
            return
        self._check(user, catalog, table, "drop")

    def check_can_delete(self, user, catalog, table):
        self._check(user, catalog, table, "delete")

    def notify_table_renamed(self, catalog, old, new):
        if (catalog, old) in self._owners:
            self._owners[(catalog, new)] = self._owners.pop((catalog, old))
        if self.grants is not None:
            self.grants.rename_table(catalog, old, new)


# ---------------------------------------------------------------------------
# transactions
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TransactionInfo:
    transaction_id: str
    auto_commit: bool = True
    # connector-side commit/abort callbacks registered during execution
    commit_actions: List[Callable[[], None]] = dataclasses.field(
        default_factory=list)
    abort_actions: List[Callable[[], None]] = dataclasses.field(
        default_factory=list)
    state: str = "ACTIVE"          # ACTIVE | COMMITTED | ABORTED


class TransactionManager:
    """Per-query auto-commit transactions (TransactionManager.java:28).
    The engine's writes are single-commit PageSink finishes; the manager
    sequences those commits and exposes abort for failure paths."""

    def __init__(self):
        self._lock = threading.Lock()
        self.transactions: Dict[str, TransactionInfo] = {}

    def begin(self, auto_commit: bool = True) -> TransactionInfo:
        txn = TransactionInfo(uuid.uuid4().hex[:16], auto_commit)
        with self._lock:
            self.transactions[txn.transaction_id] = txn
        return txn

    def commit(self, txn: TransactionInfo) -> None:
        if txn.state != "ACTIVE":
            raise RuntimeError(f"transaction is {txn.state}")
        for action in txn.commit_actions:
            action()
        txn.state = "COMMITTED"
        self._forget(txn)

    def abort(self, txn: TransactionInfo) -> None:
        if txn.state != "ACTIVE":
            return
        for action in txn.abort_actions:
            try:
                action()
            except Exception:  # noqa: BLE001 - abort is best-effort
                pass
        txn.state = "ABORTED"
        self._forget(txn)

    def _forget(self, txn: TransactionInfo) -> None:
        with self._lock:
            self.transactions.pop(txn.transaction_id, None)


# ---------------------------------------------------------------------------
# resource groups
# ---------------------------------------------------------------------------

class QueryQueueFullError(RuntimeError):
    pass


class QueryCancelledError(RuntimeError):
    """A queued admission wait was cancelled (DELETE on a QUEUED query):
    the waiter is dequeued without ever consuming a slot."""


class _Ticket:
    """One queued admission request (ordering handle)."""

    __slots__ = ("seq", "group")

    def __init__(self, seq: int, group: "ResourceGroup"):
        self.seq = seq
        self.group = group


class ResourceGroup:
    """One node of the admission-control tree
    (InternalResourceGroup.java:77,91,95): bounded running + queued
    queries, policy-driven release order, and a soft memory limit that
    stops NEW admissions while the group's tracked usage exceeds it.
    ``hard_concurrency_limit`` / ``max_queued`` / ``soft_memory_limit`` /
    ``scheduling_policy`` / ``scheduling_weight`` follow the reference's
    property names.

    Policies decide which child subtree's waiter runs when a slot frees:
    - 'fair' (default): the child with the fewest running queries, FIFO
      within a child (the reference's fair queue);
    - 'weighted_fair': the child with the lowest running/weight ratio
      (WeightedFairQueue.java role);
    - 'query_priority': strict FIFO over every waiter in the subtree.
    """

    def __init__(self, name: str, hard_concurrency_limit: int = 16,
                 max_queued: int = 64,
                 parent: Optional["ResourceGroup"] = None,
                 scheduling_weight: int = 1,
                 scheduling_policy: str = "fair",
                 soft_memory_limit_bytes: Optional[int] = None,
                 hard_cpu_limit_s: Optional[float] = None,
                 cpu_quota_generation_s_per_s: float = 0.0):
        import time as _time

        self.name = name
        self.hard_concurrency_limit = hard_concurrency_limit
        self.max_queued = max_queued
        self.parent = parent
        self.scheduling_weight = max(int(scheduling_weight), 1)
        self.scheduling_policy = scheduling_policy
        self.soft_memory_limit_bytes = soft_memory_limit_bytes
        self.memory_usage = 0
        # CPU accounting (InternalResourceGroup cpuUsageMillis /
        # hardCpuLimit / cpuQuotaGenerationMillisPerSecond role): queries
        # charge their execution seconds at completion; a group over its
        # hard CPU limit admits nothing until the regeneration rate pays
        # the debt back down.  None = no CPU limit.
        self.cpu_usage_s = 0.0
        self.hard_cpu_limit_s = hard_cpu_limit_s
        self.cpu_quota_generation_s_per_s = cpu_quota_generation_s_per_s
        self._cpu_regen_at = _time.monotonic()
        self.running = 0
        self.queued = 0
        self.children: List["ResourceGroup"] = []
        self._queue: List[_Ticket] = []   # this group's own waiters, FIFO
        # ONE condition per tree: a release in any group must be able to
        # wake a waiter in a sibling (the policy walk decides which)
        self._cond = (parent._cond if parent is not None
                      else threading.Condition())
        if parent is not None:
            parent.children.append(self)
        root = self
        while root.parent is not None:
            root = root.parent
        self._root = root
        if parent is None:
            self._seq = 0

    # -- selection (policy) ---------------------------------------------
    def _regen_cpu_locked(self) -> None:
        """Pay accumulated CPU debt back down at the configured
        generation rate (lazy: applied whenever eligibility is checked
        or usage is charged)."""
        import time as _time

        now = _time.monotonic()
        if self.cpu_quota_generation_s_per_s > 0 and self.cpu_usage_s > 0:
            self.cpu_usage_s = max(
                0.0, self.cpu_usage_s
                - (now - self._cpu_regen_at)
                * self.cpu_quota_generation_s_per_s)
        self._cpu_regen_at = now

    def _slot_free_locked(self) -> bool:
        if self.running >= self.hard_concurrency_limit:
            return False
        if (self.soft_memory_limit_bytes is not None
                and self.memory_usage > self.soft_memory_limit_bytes):
            return False
        if self.hard_cpu_limit_s is not None:
            self._regen_cpu_locked()
            if self.cpu_usage_s >= self.hard_cpu_limit_s:
                return False
        return True

    def _select_locked(self) -> Optional[_Ticket]:
        """The next ticket in this subtree eligible to run, or None."""
        if not self._slot_free_locked():
            return None
        ranked: List[Tuple[float, int, _Ticket]] = []
        if self._queue:
            t = self._queue[0]
            ranked.append((0.0, t.seq, t))
        for c in self.children:
            t = c._select_locked()
            if t is None:
                continue
            if self.scheduling_policy == "weighted_fair":
                # post-admission share: at equal running counts the
                # higher-weight group is the more under-served one
                key = (c.running + 1) / c.scheduling_weight
            elif self.scheduling_policy == "query_priority":
                key = 0.0        # strict FIFO: sequence decides
            else:                # fair
                key = float(c.running)
            ranked.append((key, t.seq, t))
        if not ranked:
            return None
        return min(ranked)[2]

    def acquire(self, timeout_s: Optional[float] = None,
                cancel_event: Optional[threading.Event] = None) -> None:
        """Block until this group's waiter is chosen by the root's policy
        walk AND every ancestor has a free slot; raise when the queue is
        full.  ``cancel_event`` makes the wait cancellable: when set
        (wake the waiter via :meth:`wake`), the ticket is dequeued
        without consuming a slot and ``QueryCancelledError`` raises —
        the queued-query DELETE path."""
        with self._cond:
            if cancel_event is not None and cancel_event.is_set():
                raise QueryCancelledError(
                    f"admission wait for {self.name!r} cancelled")
            root = self._root
            if self._chain_free_locked() and root._select_locked() is None:
                # capacity available and no eligible waiter to barge past
                self._grab_locked()
                return
            if self.queued >= self.max_queued:
                raise QueryQueueFullError(
                    f"Too many queued queries for {self.name!r}")
            root._seq += 1
            ticket = _Ticket(root._seq, self)
            self.queued += 1
            self._queue.append(ticket)
            try:
                ok = self._cond.wait_for(
                    lambda: ((cancel_event is not None
                              and cancel_event.is_set())
                             or (root._select_locked() is ticket
                                 and self._chain_free_locked())),
                    timeout=timeout_s)
                if cancel_event is not None and cancel_event.is_set():
                    raise QueryCancelledError(
                        f"admission wait for {self.name!r} cancelled")
                if not ok:
                    raise QueryQueueFullError(
                        f"queue wait timed out for {self.name!r}")
                self._queue.remove(ticket)
                self._grab_locked()
                # another slot may still be free for the next waiter
                self._cond.notify_all()
            finally:
                self.queued -= 1
                if ticket in self._queue:
                    self._queue.remove(ticket)
                # a removed waiter may unblock the policy walk for a
                # sibling (it can no longer be selected)
                self._cond.notify_all()

    def wake(self) -> None:
        """Wake every waiter on this group's tree (cancellation and
        CPU-quota regeneration are externally-timed eligibility
        changes the condition cannot observe by itself)."""
        with self._cond:
            self._cond.notify_all()

    def _chain_free_locked(self) -> bool:
        node: Optional[ResourceGroup] = self
        while node is not None:
            if not node._slot_free_locked():
                return False
            node = node.parent
        return True

    def _grab_locked(self) -> None:
        node: Optional[ResourceGroup] = self
        while node is not None:
            node.running += 1
            node = node.parent

    def release(self) -> None:
        with self._cond:
            node: Optional[ResourceGroup] = self
            while node is not None:
                node.running -= 1
                node = node.parent
            self._cond.notify_all()

    def set_memory_usage(self, bytes_: int) -> None:
        """Feed tracked memory (ClusterMemoryManager assigns query memory
        to groups); crossing below the soft limit wakes waiters."""
        with self._cond:
            self.memory_usage = bytes_
            self._cond.notify_all()

    def charge_cpu(self, seconds: float) -> None:
        """Charge a completed query's execution seconds to this group
        and every ancestor (the cpuUsageMillis accounting); the next
        eligibility check regenerates at the configured rate."""
        with self._cond:
            node: Optional[ResourceGroup] = self
            while node is not None:
                node._regen_cpu_locked()
                node.cpu_usage_s += max(float(seconds), 0.0)
                node = node.parent

    def stats_locked_snapshot(self) -> Dict[str, Any]:
        """One group's admission counters (the /metrics and
        system.runtime surface)."""
        with self._cond:
            return {"name": self.name, "running": self.running,
                    "queued": self.queued,
                    "hard_concurrency_limit": self.hard_concurrency_limit,
                    "max_queued": self.max_queued,
                    "cpu_usage_s": round(self.cpu_usage_s, 3),
                    "memory_usage_bytes": self.memory_usage}


class ResourceGroupManager:
    """Selects the group for a session (the rule-based selector role:
    per-user groups under a root)."""

    def __init__(self, hard_concurrency_limit: int = 16,
                 max_queued: int = 64, per_user_limit: int = 8,
                 scheduling_policy: str = "fair"):
        self.root = ResourceGroup("global", hard_concurrency_limit,
                                  max_queued,
                                  scheduling_policy=scheduling_policy)
        self.per_user_limit = per_user_limit
        self._groups: Dict[str, ResourceGroup] = {}
        self._lock = threading.Lock()

    def group_for(self, session: Session) -> ResourceGroup:
        with self._lock:
            g = self._groups.get(session.user)
            if g is None:
                g = ResourceGroup(f"global.{session.user}",
                                  self.per_user_limit,
                                  self.root.max_queued, parent=self.root)
                self._groups[session.user] = g
            return g

    def configure_group(self, user: str, **kwargs) -> ResourceGroup:
        """Pre-create / tune a user group (weight, soft memory limit,
        concurrency) — the DB/file-backed resource-group config role."""
        with self._lock:
            g = self._groups.get(user)
            if g is None:
                g = ResourceGroup(f"global.{user}", self.per_user_limit,
                                  self.root.max_queued, parent=self.root)
                self._groups[user] = g
        for k, v in kwargs.items():
            setattr(g, k, v)
        return g

    def update_memory_usage(self, per_user_bytes: Dict[str, int]) -> None:
        with self._lock:
            groups = dict(self._groups)
        for user, g in groups.items():
            g.set_memory_usage(per_user_bytes.get(user, 0))

    def stats(self) -> List[Dict[str, Any]]:
        """Admission counters for the root and every child group — the
        per-group queue-depth / running-count gauges the coordinator's
        /metrics plane renders."""
        with self._lock:
            groups = [self.root] + list(self._groups.values())
        return [g.stats_locked_snapshot() for g in groups]


# ---------------------------------------------------------------------------
# session property managers
# ---------------------------------------------------------------------------

class SessionPropertyManager:
    """Rule-based session property defaults
    (presto-session-property-managers role: the db/file-backed
    SessionPropertyConfigurationManager applies matching rules'
    properties to a session before execution; explicit SET SESSION
    values still win).

    Rules are ordered dicts: {"user": pattern, "source": pattern,
    "properties": {name: value}}; '*' wildcards; all matching rules
    apply, later rules overriding earlier ones."""

    def __init__(self, rules: List[Dict[str, Any]]):
        self.rules = list(rules)

    @staticmethod
    def _match(pattern: str, value: str) -> bool:
        import fnmatch

        return fnmatch.fnmatch(value, pattern)

    def defaults_for(self, user: str, source: str = "") -> Dict[str, str]:
        out: Dict[str, str] = {}
        for rule in self.rules:
            if not self._match(rule.get("user", "*"), user):
                continue
            if not self._match(rule.get("source", "*"), source):
                continue
            out.update(rule.get("properties", {}))
        return out

    def apply(self, session: "Session", source: str = "") -> None:
        """Set matched defaults that the session has not set itself."""
        for name, value in self.defaults_for(session.user,
                                             source).items():
            if name.lower() not in session.properties:
                session.set_property(name, str(value))
