"""Session properties, access control, transactions, resource groups.

Reference analogues: SystemSessionProperties + SET SESSION, the security
SPI with file-based rules, TransactionManager, InternalResourceGroup
(SURVEY §2.12, §5.6)."""

import threading
import time

import pytest

from presto_tpu.localrunner import LocalQueryRunner
from presto_tpu.session import (
    AccessDeniedError, QueryQueueFullError, ResourceGroupManager,
    RuleBasedAccessControl, Session, SessionError, TransactionManager,
)


class TestSessionProperties:
    def test_set_show_reset(self):
        r = LocalQueryRunner.tpch(scale=0.001)
        r.execute("set session spill_enabled = false")
        rows = dict((n, v) for n, v, _ in
                    r.execute("show session").rows)
        assert rows["spill_enabled"] == "False"
        r.execute("reset session spill_enabled")
        rows = dict((n, v) for n, v, _ in
                    r.execute("show session").rows)
        assert rows["spill_enabled"] == "True"

    def test_property_affects_execution(self):
        r = LocalQueryRunner.tpch(scale=0.001)
        r.execute("set session scan_batch_rows = 128")
        assert r.session.effective_config(r.config).scan_batch_rows == 128
        # still executes correctly with tiny batches
        assert r.execute("select count(*) from nation").rows == [(25,)]

    def test_unknown_property_rejected(self):
        s = Session()
        with pytest.raises(SessionError):
            s.set_property("no_such_prop", "1")

    def test_bad_value_rejected(self):
        s = Session()
        with pytest.raises(SessionError):
            s.set_property("spill_partitions", "banana")

    def test_every_session_property_names_a_config_field(self):
        """A property whose field was deleted from EngineConfig fails
        here, not in a user's session."""
        import dataclasses

        from presto_tpu.config import EngineConfig
        from presto_tpu.session import SESSION_PROPERTIES

        fields = {f.name for f in dataclasses.fields(EngineConfig)}
        dangling = {name: field
                    for name, (field, _parse) in SESSION_PROPERTIES.items()
                    if field not in fields}
        assert not dangling


class TestAccessControl:
    def _runner(self, user: str):
        rules = [
            {"user": "admin", "privileges": ["select", "insert", "create",
                                             "drop"]},
            {"user": "reader", "catalog": "tpch",
             "privileges": ["select"]},
        ]
        return LocalQueryRunner.tpch(
            scale=0.001, session=Session(user=user, catalog="tpch"),
            access_control=RuleBasedAccessControl(rules))

    def test_admin_can_do_everything(self):
        r = self._runner("admin")
        r.execute("select count(*) from nation")
        r.execute("create table memory.t (a bigint)")
        r.execute("insert into memory.t values (1)")
        r.execute("drop table memory.t")

    def test_reader_can_only_select_tpch(self):
        r = self._runner("reader")
        assert r.execute("select count(*) from nation").rows == [(25,)]
        with pytest.raises(AccessDeniedError):
            r.execute("create table memory.t (a bigint)")

    def test_stranger_denied(self):
        r = self._runner("stranger")
        with pytest.raises(AccessDeniedError):
            r.execute("select count(*) from nation")


class TestTransactions:
    def test_commit_and_abort_flow(self):
        tm = TransactionManager()
        events = []
        txn = tm.begin()
        txn.commit_actions.append(lambda: events.append("commit"))
        tm.commit(txn)
        assert events == ["commit"]
        assert txn.state == "COMMITTED"

        txn2 = tm.begin()
        txn2.abort_actions.append(lambda: events.append("abort"))
        tm.abort(txn2)
        assert events == ["commit", "abort"]
        assert not tm.transactions

    def test_failed_insert_aborts(self):
        r = LocalQueryRunner.tpch(scale=0.001)
        r.execute("create table memory.t (a bigint)")
        with pytest.raises(Exception):
            r.execute("insert into memory.t "
                      "select no_col from nation")
        # nothing half-written
        assert r.execute("select count(*) from memory.t").rows == [(0,)]


class TestResourceGroups:
    def test_concurrency_limit_queues(self):
        mgr = ResourceGroupManager(hard_concurrency_limit=2,
                                   per_user_limit=2)
        g = mgr.group_for(Session(user="u"))
        g.acquire()
        g.acquire()
        started = threading.Event()
        acquired = threading.Event()

        def waiter():
            started.set()
            g.acquire(timeout_s=10)
            acquired.set()

        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        started.wait(1)
        assert not acquired.wait(0.3)  # blocked at the limit
        g.release()
        assert acquired.wait(5)
        g.release()
        g.release()

    def test_queue_full_rejects(self):
        mgr = ResourceGroupManager(hard_concurrency_limit=1,
                                   per_user_limit=1, max_queued=0)
        g = mgr.group_for(Session(user="u"))
        g.acquire()
        with pytest.raises(QueryQueueFullError):
            g.acquire(timeout_s=0.1)
        g.release()

    def test_per_user_isolation(self):
        mgr = ResourceGroupManager(hard_concurrency_limit=10,
                                   per_user_limit=1)
        ga = mgr.group_for(Session(user="a"))
        gb = mgr.group_for(Session(user="b"))
        ga.acquire()
        gb.acquire()  # b unaffected by a's per-user limit
        ga.release()
        gb.release()

    def test_weighted_fair_prefers_higher_weight(self):
        """When one root slot frees with both users waiting, the
        weighted_fair policy admits the under-served high-weight group
        (WeightedFairQueue.java role)."""
        mgr = ResourceGroupManager(hard_concurrency_limit=1,
                                   per_user_limit=5,
                                   scheduling_policy="weighted_fair")
        heavy = mgr.configure_group("heavy", scheduling_weight=10)
        light = mgr.configure_group("light", scheduling_weight=1)
        blocker = mgr.group_for(Session(user="blocker"))
        blocker.acquire()          # occupies the single root slot
        order = []
        done = {"light": threading.Event(), "heavy": threading.Event()}

        def waiter(name, g):
            g.acquire(timeout_s=10)
            order.append(name)
            done[name].set()

        # light queues FIRST; weighted_fair must still pick heavy
        tl = threading.Thread(target=waiter, args=("light", light),
                              daemon=True)
        tl.start()
        time.sleep(0.1)
        th = threading.Thread(target=waiter, args=("heavy", heavy),
                              daemon=True)
        th.start()
        time.sleep(0.1)
        blocker.release()
        assert done["heavy"].wait(5)
        assert order[0] == "heavy", order
        heavy.release()
        assert done["light"].wait(5)
        light.release()

    def test_fair_policy_fifo_within_group(self):
        mgr = ResourceGroupManager(hard_concurrency_limit=1,
                                   per_user_limit=5)
        g = mgr.group_for(Session(user="u"))
        g.acquire()
        order = []
        evs = [threading.Event() for _ in range(2)]

        def waiter(i):
            g.acquire(timeout_s=10)
            order.append(i)
            evs[i].set()

        for i in range(2):
            threading.Thread(target=waiter, args=(i,), daemon=True).start()
            time.sleep(0.1)
        g.release()
        assert evs[0].wait(5)
        assert order[0] == 0, order   # FIFO: first waiter first
        g.release()
        assert evs[1].wait(5)
        g.release()

    def test_soft_memory_limit_gates_admission(self):
        mgr = ResourceGroupManager(hard_concurrency_limit=10,
                                   per_user_limit=10)
        g = mgr.configure_group("u", soft_memory_limit_bytes=1000)
        g.set_memory_usage(5000)   # over the soft limit
        admitted = threading.Event()

        def waiter():
            g.acquire(timeout_s=10)
            admitted.set()

        threading.Thread(target=waiter, daemon=True).start()
        assert not admitted.wait(0.3)          # blocked by memory
        g.set_memory_usage(0)                  # usage drops
        assert admitted.wait(5)
        g.release()


class TestPlannerSteeringProperties:
    """Round-4 SystemSessionProperties surface: planner/scheduler
    behaviors steerable per query (VERDICT r3 missing #8)."""

    def _runner(self):
        from presto_tpu.localrunner import LocalQueryRunner

        return LocalQueryRunner.tpch(scale=0.01)

    def test_join_distribution_type(self):
        r = self._runner()
        sql = ("select count(*) from tpch.orders o join tpch.customer c "
               "on o.o_custkey = c.c_custkey")
        want = r.execute(sql).rows
        for mode in ("broadcast", "partitioned", "automatic"):
            r.execute(f"SET SESSION join_distribution_type = '{mode}'")
            assert r.execute(sql).rows == want
            plan = r.execute(
                f"EXPLAIN (TYPE DISTRIBUTED) {sql}").rows
            text = "\n".join(row[0] for row in plan)
            if mode == "broadcast":
                assert "broadcast" in text
            if mode == "partitioned":
                assert "broadcast" not in text
        r.execute("RESET SESSION join_distribution_type")

    def test_join_reordering_strategy(self):
        r = self._runner()
        sql = ("select count(*) from tpch.lineitem l, tpch.orders o, "
               "tpch.customer c where l.l_orderkey = o.o_orderkey "
               "and o.o_custkey = c.c_custkey")
        want = r.execute(sql).rows
        r.execute("SET SESSION join_reordering_strategy = 'none'")
        assert r.execute(sql).rows == want
        with pytest.raises(Exception):
            r.execute("SET SESSION join_reordering_strategy = 'bogus'")

    def test_partial_aggregation_toggle(self):
        r = self._runner()
        sql = ("select o_orderpriority, count(*) from tpch.orders "
               "group by o_orderpriority")
        want = sorted(r.execute(sql).rows)
        r.execute("SET SESSION partial_aggregation_enabled = false")
        assert sorted(r.execute(sql).rows) == want
        plan = r.execute(f"EXPLAIN (TYPE DISTRIBUTED) {sql}").rows
        text = "\n".join(row[0] for row in plan)
        assert "partial" not in text.lower()

    def test_query_max_memory(self):
        r = self._runner()
        r.execute("SET SESSION query_max_memory_bytes = 1024")
        r.execute("SET SESSION spill_enabled = false")
        with pytest.raises(Exception, match="[Mm]emory"):
            r.execute("select l_orderkey, count(*) from tpch.lineitem "
                      "group by l_orderkey order by 2 desc limit 5")

    def test_query_max_run_time_enforced(self):
        r = self._runner()
        r.execute("SET SESSION query_max_run_time_s = 0.001")
        with pytest.raises(Exception, match="maximum run time"):
            # nested-loop self cross join: long enough that the deadline
            # fires between scheduling quanta
            r.execute("select count(*) from tpch.lineitem l1, "
                      "tpch.lineitem l2 where l1.l_comment < l2.l_comment")
        r.execute("RESET SESSION query_max_run_time_s")
        rows = r.execute("SHOW SESSION").rows
        assert any(row[0] == "query_max_run_time_s" for row in rows)
