"""Everything a cell is made of, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; the traffic names its
statements; a metric names its reader.  Each is a file of its own under
``benchmark/``, so a later PR adds files and manifest entries and edits
nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def path(*parts: str) -> str:
    return os.path.join(HERE, *parts)


def load_json(*parts: str) -> dict:
    with open(path(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots and
    dashes, so this is by file, not by import)."""
    file = path(kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + re.sub(r"\W", "_", name), file)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_cells(metric: dict, bench: dict) -> list:
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell ``name`` with everything it runs resolved: ``config``,
    ``traffic``, ``statements`` {name: sql}, ``references`` {name: module},
    and its ``end_to_end`` / ``per_layer`` metric entries."""
    bench = bench or benchmark_json()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    out = dict(entries[0])
    conf = [c for c in bench["configs"] if c["name"] == out["config"]][0]
    out["config_name"] = out["config"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        out["config"] = json.load(f)
    out["traffic_name"] = out["traffic"]
    out["traffic"] = load_json("traffic", out["traffic_name"] + ".json")
    if out["config"]["chips"] != out["chips"]:
        raise ValueError(f"{name}: the cell asks for {out['chips']} chips, "
                         f"its configuration for {out['config']['chips']}")
    out["statements"], out["references"] = {}, {}
    for s in out["traffic"]["statements"]:
        with open(path("statements", s + ".sql")) as f:
            out["statements"][s] = f.read()
        out["references"][s] = load_module("references", s)
    for group in ("end_to_end", "per_layer"):
        out[group] = [m for m in bench[group]
                      if name in metric_cells(m, bench)]
    return out
