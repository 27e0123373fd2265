"""Sweep the full TPC-DS query set against the engine + sqlite oracle.

Loads the 99 standard query texts (from the benchto-resource naming used
by the reference), normalizes the catalog template, runs each through
LocalQueryRunner at tiny scale, compares with the sqlite oracle, and
prints a per-query verdict + error classification — the worklist for the
conformance tier.
"""

import glob
import os
import re
import sqlite3
import sys
import time
import traceback

# conformance is harvested on the CPU, like the test suite (toy scales,
# hardware-independent answers)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REF = "/root/reference/presto-benchto-benchmarks/src/main/resources/sql/presto/tpcds"
SCALE = 0.003


def normalize(sql: str) -> str:
    sql = sql.replace("${database}.${schema}.", "tpcds.")
    return sql


def main() -> None:
    from presto_tpu.localrunner import LocalQueryRunner
    from test_tpch_conformance import (
        _sqlite_type, _to_sqlite, assert_rows_match, register_sqlite_fns,
        to_sqlite_sql,
    )

    only = None
    slice_lo = slice_hi = None
    if len(sys.argv) > 1:
        if ":" in sys.argv[1]:
            a, _, b = sys.argv[1].partition(":")
            slice_lo, slice_hi = int(a), int(b)
        else:
            only = set(sys.argv[1].split(","))
    runner = LocalQueryRunner.tpch(scale=SCALE)
    oracle = sqlite3.connect(":memory:")
    oracle.execute("PRAGMA case_sensitive_like = ON")
    register_sqlite_fns(oracle)
    tpcds = runner.registry.get("tpcds")
    for table in tpcds.list_tables():
        handle = tpcds.get_table(table)
        schema = tpcds.table_schema(handle)
        names = schema.column_names()
        cols_sql = ", ".join(f"{n} {_sqlite_type(schema.column_type(n))}"
                             for n in names)
        oracle.execute(f"create table {table} ({cols_sql})")
        for split in tpcds.get_splits(handle, 1):
            for batch in tpcds.page_source(split, names, 1 << 20):
                rows = [tuple(_to_sqlite(v) for v in r)
                        for r in batch.to_pylist()]
                ph = ", ".join("?" * len(names))
                oracle.executemany(
                    f"insert into {table} values ({ph})", rows)
        # index the _sk columns: correlated-subquery shapes otherwise run
        # for hours in sqlite
        for n in names:
            if n.endswith("_sk"):
                oracle.execute(
                    f"create index idx_{table}_{n} on {table}({n})")
    oracle.commit()

    import signal

    class _Timeout(Exception):
        pass

    def _alarm(_sig, _frm):
        raise _Timeout()

    signal.signal(signal.SIGALRM, _alarm)
    per_query_s = int(os.environ.get("HARVEST_TIMEOUT_S", "120"))

    ok, results = 0, []
    paths = sorted(glob.glob(os.path.join(REF, "q*.sql")))
    if slice_lo is not None:
        paths = paths[slice_lo:slice_hi]
    for path in paths:
        qn = os.path.basename(path)[1:-4]
        if only and qn not in only:
            continue
        sql = normalize(open(path).read())
        t0 = time.time()
        try:
            signal.alarm(per_query_s)
            got = runner.execute(sql)
        except _Timeout:
            results.append((qn, "ENGINE", "Timeout"))
            print(f"q{qn}: ENGINE Timeout", flush=True)
            continue
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e)[:110]}".replace("\n", " ")
            results.append((qn, "ENGINE", msg))
            print(f"q{qn}: ENGINE {msg}", flush=True)
            continue
        finally:
            signal.alarm(0)
        try:
            signal.alarm(per_query_s)
            osql = to_sqlite_sql(sql.replace("tpcds.", ""))
            cur = oracle.execute(osql)
            want = cur.fetchall()
        except _Timeout:
            results.append((qn, "ORACLE", "Timeout"))
            print(f"q{qn}: ORACLE Timeout", flush=True)
            continue
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e)[:110]}".replace("\n", " ")
            results.append((qn, "ORACLE", msg))
            print(f"q{qn}: ORACLE {msg}", flush=True)
            continue
        finally:
            signal.alarm(0)
        try:
            ordered = "order by" in sql.lower()
            assert_rows_match(got.rows, want, ordered)
        except AssertionError as e:
            msg = str(e)[:160].replace("\n", " ")
            results.append((qn, "MISMATCH", msg))
            print(f"q{qn}: MISMATCH {msg}", flush=True)
            continue
        ok += 1
        results.append((qn, "OK", ""))
        print(f"q{qn}: OK ({time.time()-t0:.0f}s, {len(got.rows)} rows)",
              flush=True)
    print(f"\n{ok}/{len(results)} pass", flush=True)
    from collections import Counter
    cats = Counter()
    for qn, status, msg in results:
        if status != "OK":
            cats[msg.split(":")[0] + ":" + msg[:60]] += 1
    for k, v in cats.most_common(40):
        print(f"{v:3d}  {k}", flush=True)


if __name__ == "__main__":
    main()
