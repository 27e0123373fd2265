"""Everything BENCHMARK.json names resolves to a file of its own, and every
name and unit keeps to the allowed characters: so a later PR adds a cell, a
statement, a configuration or a metric as new files and entries only."""

import json
import os

import pytest

from benchmark import manifest

BENCH = manifest.benchmark_json()
# the manifest as a later PR grows it: a cell on each plane, one entry each
# and no edit to another
NEW_CELLS = {"new.http": ("tpch-sf1-http2w", "repeat-q1", 1),
             "new.device": ("tpch-sf10-mesh4w", "join", 4)}
GROWN = dict(BENCH, workloads=BENCH["workloads"] + [
    {"name": name, "config": config, "traffic": traffic, "chips": chips,
     "why": "a cell added later"}
    for name, (config, traffic, chips) in NEW_CELLS.items()])
BENCHES, BENCH_IDS = [BENCH, GROWN], ["accepted", "grown"]
CELLS = [w["name"] for w in BENCH["workloads"]]
CELL_CASES = [pytest.param(b, w["name"], id=f"{i}-{w['name']}")
              for b, i in zip(BENCHES, BENCH_IDS) for w in b["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_keys_are_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("bench, name", CELL_CASES)
def test_cell_resolves_by_name(bench, name):
    cell = manifest.cell(name, bench)
    assert cell["statements"] and len(cell["why"]) <= 200
    assert cell["chips"] in (1, 4)
    for s, ref in cell["references"].items():
        assert callable(ref.reference) and ref.COLUMNS, s
        assert cell["statements"][s].strip().lower().startswith("select")
    for key in ("source", "scale", "workers", "chips", "engine_config",
                "served_by", "guarantees", "reduced", "assumed"):
        assert key in cell["config"], key
    for key in ("statements", "clients", "loop", "order", "who", "why"):
        assert key in cell["traffic"], key
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:       # what it moves is reported here
        assert m["moves"] in e2e, m["name"]


def test_every_config_has_a_cell_and_its_own_file():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"] and len(c["source"]) <= 200
        assert conf["reduced"] == c["reduced"]
        assert len(c["why"]) <= 200


@pytest.mark.parametrize("bench", BENCHES, ids=BENCH_IDS)
def test_four_chip_cells_are_at_most_half_or_one(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("group, folder", [("end_to_end", "end_to_end"),
                                           ("per_layer", "layer_metrics")])
def test_metrics_have_readers_and_legal_names(group, folder):
    names = [m["name"] for m in BENCH[group]]
    assert len(set(names)) == len(names)
    for m in BENCH[group]:
        assert manifest.NAME.match(m["name"]), m["name"]
        assert manifest.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for cell in m.get("workloads", []):
            assert cell in CELLS
        reader = manifest.load_module(folder, m["name"])
        assert callable(reader.read)
        if group == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
            assert set(m) <= {"name", "unit", "better", "bound", "source",
                              "workloads"}
        else:
            assert (reader.LAYER, reader.UNIT, reader.SOURCE,
                    reader.MOVES) == (m["layer"], m["unit"], m["source"],
                                      m["moves"]), m["name"]
            assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            if m["name"].endswith("_roofline"):
                assert m["unit"] == "%"


def plane_of(metric: dict) -> str | None:
    """The plane a per-layer metric's reader reads on alone (its ``PLANE``),
    or None where it reads on every plane."""
    return getattr(manifest.load_module("layer_metrics", metric["name"]),
                   "PLANE", None)


@pytest.mark.parametrize("bench", BENCHES, ids=BENCH_IDS)
def test_only_one_plane_metrics_list_their_cells(bench):
    for m in bench["per_layer"]:
        plane = plane_of(m)
        assert ("workloads" in m) == (plane is not None), m["name"]
        for name in m.get("workloads", []):
            assert manifest.cell(name, bench)["config"]["served_by"] == \
                plane, m["name"]


@pytest.mark.parametrize("name", NEW_CELLS)
def test_a_new_cell_gets_every_metric_but_the_one_plane_ones(name):
    """A cell that a later PR adds, on either plane, as one manifest entry
    and no edit to another."""
    got = {m["name"] for m in manifest.cell(name, GROWN)["per_layer"]}
    assert got == {m["name"] for m in BENCH["per_layer"]
                   if plane_of(m) is None}


@pytest.mark.parametrize("bench", BENCHES, ids=BENCH_IDS)
def test_names_of_cells_configs_and_files(bench):
    for entry in bench["workloads"] + bench["configs"]:
        assert manifest.NAME.match(entry["name"]), entry["name"]
    for w in bench["workloads"]:
        assert manifest.NAME.match(w["traffic"])
    for folder, _dirs, files in os.walk(manifest.HERE):
        if "__pycache__" in folder:
            continue
        for file in files:
            assert manifest.NAME.match(file), os.path.join(folder, file)


def test_peaks_name_their_source():
    for kind, row in manifest.load_json("peaks.json").items():
        assert row["source"] and row["hbm_bytes_per_s"] > 0, kind


def test_nothing_forces_a_backend():
    """No file of the benchmark outside its tests names the variable that
    would let a run fall back to the CPU."""
    needle = "JAX_" + "PLATFORMS"
    for folder, _dirs, files in os.walk(manifest.HERE):
        if os.path.basename(folder) in ("tests", "__pycache__", "fixtures"):
            continue
        for file in files:
            if file.endswith((".py", ".json", ".sql")):
                with open(os.path.join(folder, file)) as f:
                    assert needle not in f.read(), file
