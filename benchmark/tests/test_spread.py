"""The spread of a set of runs, as a bound is measured from it."""

import json

import pytest

from benchmark import spread


@pytest.mark.parametrize("values, want_range, want_iqr", [
    # six runs, one far off: it is left out, the other five decide
    ([1.00, 1.01, 1.02, 1.03, 1.04, 1.50], 0.04 / 1.025, 0.03 / 1.025),
    # the far one does no harm below the median either
    ([0.50, 1.00, 1.01, 1.02, 1.03, 1.04], 0.04 / 1.015, 0.03 / 1.015),
    # two far off: the farther is left out, the other shows
    ([0.50, 1.00, 1.01, 1.02, 1.03, 1.50], 0.50 / 1.015, 0.26 / 1.015),
    # nothing is left out where fewer than three would stay
    ([1.0, 1.1], 0.1 / 1.05, 0.15 / 1.05),
    ([1.0, 1.1, 1.3], 0.3 / 1.1, 0.3 / 1.1),
    ([2.0, 2.0, 2.0, 2.0], 0.0, 0.0),
])
def test_spread_leaves_out_the_farthest_run(values, want_range, want_iqr):
    assert spread.spread(values) == pytest.approx(want_range)
    assert spread.spread(values, spread.iqr) == pytest.approx(want_iqr)


def test_spread_of_nothing_to_compare():
    assert spread.spread([]) is None and spread.spread([1.0]) is None
    assert spread.spread([0.0, 0.0]) is None


def test_a_set_narrower_without_any_run_left_out_keeps_all():
    # leaving a run out never widens what is reported
    values = [1.0, 1.0, 1.0, 1.0, 1.0, 1.2]
    assert spread.spread(values) == 0.0


def write_run(folder, name, metrics, tail=True):
    lines = [json.dumps({"phase": "window"})]
    if tail:
        lines.append(json.dumps({
            "correct": True, "attempted": 1, "failed": 0, "device": {},
            "metrics": {k: {"value": v, "unit": "s"}
                        for k, v in metrics.items()}}))
    (folder / name).write_text("\n".join(lines) + "\n")


def test_summary_over_two_sets_of_result_lines(tmp_path, capsys):
    a, b = tmp_path / "setA", tmp_path / "setB"
    a.mkdir(), b.mkdir()
    for i, v in enumerate([2.0, 2.1, 2.2, 2.3]):
        write_run(a, f"run{i}.out", {"query_geomean_s": v, "setup_s": 40.0})
        write_run(b, f"run{i}.out", {"query_geomean_s": v * 1.1})
    write_run(a, "run9.out", {}, tail=False)      # a run that printed no result
    (a / "run9.err").write_text("not a result\n")
    got = spread.summary([spread.read_set(str(a)), spread.read_set(str(b))])
    geo = got["query_geomean_s"]
    assert [s["n"] for s in geo["sets"]] == [4, 4]
    assert geo["sets"][0]["median"] == pytest.approx(2.15)
    assert geo["sets"][0]["range"] == pytest.approx(0.2 / 2.15)
    assert geo["mean_range"] == pytest.approx(0.2 / 2.15)
    assert geo["second_median_over_first"] == pytest.approx(1.1)
    # a metric that only one set reports has no second median
    assert got["setup_s"]["sets"][1] is None
    assert "second_median_over_first" not in got["setup_s"]
    assert got["setup_s"]["mean_range"] == 0.0
    assert spread.main([str(a), str(b)]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert {x["metric"] for x in lines} == {"query_geomean_s", "setup_s"}
    assert spread.main([]) == 2
