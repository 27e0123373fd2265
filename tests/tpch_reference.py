"""TPC-H Q1 / Q6 / Q3 as the benchmark states them, and their answers from
the benchmark's plain numpy references (``benchmark/references``): an
oracle that shares no code with the engine's operators."""

import datetime
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, manifest, refdata  # noqa: E402

STATEMENTS = ("q1", "q6", "q3")
RTOL = 1e-6
compare = check.compare


def as_served(rows):
    """A LocalQueryRunner's rows as the HTTP protocol would carry them
    (the references' form): a DATE is its ISO string."""
    return [tuple(v.isoformat() if isinstance(v, datetime.date) else v
                  for v in row) for row in rows]


def statement(name):
    with open(manifest.path("statements", name + ".sql")) as f:
        return f.read()


def host_columns(scale, wanted):
    """{column: numpy array} of the generated TPC-H tables
    (``wanted`` is {table: columns})."""
    return refdata.host_columns("tpch", scale, wanted)[0]


def reference_answers(scale):
    """{statement name: rows} at ``scale``, as the CPU engine must answer."""
    refs = {name: manifest.load_module("references", name)
            for name in STATEMENTS}
    wanted = {}
    for ref in refs.values():
        for table, cols in ref.COLUMNS.items():
            wanted.setdefault(table, set()).update(cols)
    columns = host_columns(scale, wanted)
    out = {name: ref.reference(columns) for name, ref in refs.items()}
    # the CPU engine folds Q6's ``0.06 + 0.01`` in IEEE f64, one ulp
    # under the 0.07 the reference (and the chip) compare with, so here
    # Q6 is held to the same numpy computation with the bounds folded so
    sd, disc = columns["l_shipdate"], columns["l_discount"]
    sel = ((sd >= refdata.days("1994-01-01"))
           & (sd < refdata.days("1995-01-01"))
           & (disc >= 0.06 - 0.01) & (disc <= 0.06 + 0.01)
           & (columns["l_quantity"] < 24))
    out["q6"] = [(float((columns["l_extendedprice"][sel]
                         * disc[sel]).sum()),)]
    return out
