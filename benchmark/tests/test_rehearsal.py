"""The command's path rehearsed on the CPU at SF0.01: the same functions
``run.py`` calls, with the scale as an argument (the command itself refuses
to run without a TPU)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest, run

FAKE_PEAKS = {"hbm_bytes_per_s": 1e9}   # no device metric is read here
SEED = 2 ** 31 + 12345                  # the driver's seeds are large

MESH_CELL = "mesh4w.repeat-q1"


def rehearse(name, trace, seconds=2.0):
    import jax

    cell = manifest.cell(name)
    return cell, run.run_cell(cell, SEED, seconds, trace,
                              jax.devices()[:cell["chips"]], FAKE_PEAKS,
                              scale=0.01)


def test_command_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"),
         "--workload", "http2w.scan-agg", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=manifest.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_join_cell_end_to_end(capsys):
    cell, result = rehearse("http2w.join", trace=False)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    window = [x for x in lines if x.get("phase") == "window"][0]
    walls = window["wall_s"]["q3"]
    assert walls["n"] == window["samples"]["q3"] == result["attempted"]
    assert (walls["min"] <= walls["q1"] <= walls["median"] <= walls["q3"]
            <= walls["max"])
    assert walls["median"] == pytest.approx(window["median_wall_s"]["q3"])
    # a POST and at least one GET each; no sleep between them
    assert window["responses_per_query"]["q3"]["min"] >= 2
    assert result["correct"] and result["failed"] == 0
    # what was compared, beside its limit, comes last in the line
    assert list(result)[-1] == "compared"
    assert result["compared"]["failed_operations"] == {
        "value": 0, "limit": 0, "why": []}
    assert (0.0 <= result["compared"]["max_rel_err"]["value"]
            <= result["compared"]["max_rel_err"]["limit"] == 1e-6)
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert result["metrics"]["query_geomean_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_join_cell_traced_reads_spans_and_counters(capsys):
    """A window of 1 s, too short for three q3s (0.4 s on the CPU), so the
    slice takes the long branch and holds every q3 of the window."""
    _cell, result = rehearse("http2w.join", trace=True, seconds=1.0)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    traced = [x for x in lines if x.get("phase") == "trace"][0]
    window = [x for x in lines if x.get("phase") == "window"][0]
    assert traced["slice"]["length_s"] == run.UNTIL
    assert traced["whole_samples"] == window["samples"]
    assert traced["stop_s"] >= 0.0 and traced["reduce_s"] >= 0.0
    got = result["metrics"]
    for name in ("coord.plan_s", "worker.leaf_stage_s", "ops.jit_dispatches",
                 "exchange.wire_bytes", "xla.compiles.window",
                 "xla.compile_s.setup", "warmup.first_exec_s"):
        assert name in got, name
    assert got["xla.compiles.window"]["value"] == 0
    assert got["exchange.wire_bytes"]["value"] > 0
    # the CPU has no device plane: the trace readers find nothing to read
    # and are left out, they do not report a CPU number
    assert "device.idle_share" not in got
    assert "kernels.all_roofline" not in got
    assert "breakdown" not in result and "busy_s" not in result["device"]


def test_a_wrong_answer_is_a_failed_operation():
    """On the CPU the engine folds Q6's ``0.06 + 0.01`` in IEEE f64 and
    misses the reference's decimal bounds (ROADMAP C0; the chip does not):
    every q6 of the window must count as failed, and the run as incorrect."""
    _cell, result = rehearse("http2w.scan-agg", trace=False, seconds=1.5)
    assert not result["correct"]
    assert 0 < result["failed"] <= result["attempted"]
    said = result["compared"]["failed_operations"]
    assert said["value"] >= result["failed"] and said["limit"] == 0
    assert "differs from the reference" in said["why"][0]
    # q6 has no good sample, so the geometric mean has nothing to stand on
    assert "query_geomean_s" not in result["metrics"]


def test_an_answer_altered_where_it_is_produced_fails_the_run(monkeypatch):
    """The rest of a run with the timed path broken underneath: every other
    answer of the window's client has one DOUBLE moved by a part in 10,000
    (a hundred times ``double_rtol``, what a lower precision would cost).
    Those operations fail and the run is not correct; the warm-up's client
    is left alone."""
    from benchmark import load

    made = load.bench_client

    def altering(new_client, user):
        client = made(new_client, user)
        if user == "bench-warmup":
            return client
        execute, calls = client.execute, []

        def altered(sql, **kw):
            columns, data = execute(sql, **kw)
            calls.append(1)
            if len(calls) % 2 == 0:
                col = [i for i, v in enumerate(data[0])
                       if isinstance(v, float)][0]
                data[0][col] *= 1.0 + 1e-4
            return columns, data

        client.execute = altered
        return client

    monkeypatch.setattr(load, "bench_client", altering)
    _cell, result = rehearse("http2w.join", trace=False, seconds=4.0)
    assert result["attempted"] >= 2
    assert not result["correct"]
    assert result["failed"] == result["attempted"] // 2
    said = result["compared"]["failed_operations"]
    assert said["value"] == result["failed"] and "rel 1.0" in said["why"][0]


def test_mesh_cell_on_four_virtual_devices():
    import jax
    from test_mesh_metrics import ZERO_IN_A_REPEAT

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell, result = rehearse(MESH_CELL, trace=True)
    assert result["correct"] and result["attempted"] >= 4
    assert result["device"]["count"] == 4
    got = result["metrics"]
    assert got["mesh.execute_s"]["value"] > 0
    # the query thread's brackets, read by the readers every cell has
    assert got["ops.dispatch_s"]["value"] > 0
    assert got["ops.device_wait_s"]["value"] > 0
    assert got["xla.trace_lower_s.setup"]["value"] > 0
    # a repeat generates, stages, serialises and waits on no exchange
    for name in ZERO_IN_A_REPEAT:
        assert got[name]["value"] == 0.0, name
    assert "mesh.dispatch_s" not in got and "mesh.device_wait_s" not in got
    # one kernel-cache lookup a query, in no bracket: not read here
    assert "ops.kernel_lookup_s" not in got
    _cell, e2e = rehearse(MESH_CELL, trace=False)
    assert set(e2e["metrics"]) == {m["name"] for m in cell["end_to_end"]}
